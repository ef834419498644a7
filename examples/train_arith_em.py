"""End-to-end accuracy loop: train -> checkpoint -> reload -> EM-vs-N.

The first full proof that this framework does what the reference did —
answer questions — with every stage running through the repo's own
stack:

1. **Train** ``arith-14m`` (byte-level, ~14M params) on the synthetic
   arithmetic SFT corpus (``eval/arith.py``) with
   ``training/loop.run_training`` — eval triples held out, loss masked
   to completion tokens, orbax checkpoints along the way.
2. **Reload** the final checkpoint from disk (``checkpoint/io``) into a
   fresh :class:`InferenceEngine` (bf16 cast, prefix cache on).
3. **Evaluate** real sampled EM at N in {1, 8, 32} with
   ``evaluate_self_consistency`` — actual decoded text, actual votes.

The reference outsourced all of this to a remote API
(``src/main.rs:82-86``); here the model, the training, the serving, and
the vote are all local TPU programs.

Usage (the recorded run in eval/EM_VS_N.md):
    python examples/train_arith_em.py --steps 6000 \
        --ckpt-dir runs/arith14m --report runs/arith14m/report.json
    python examples/train_arith_em.py --eval-only --ckpt-dir runs/arith14m
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

import jax
import jax.numpy as jnp

from llm_consensus_tpu.engine.engine import EngineConfig, InferenceEngine
from llm_consensus_tpu.engine.tokenizer import ByteTokenizer
from llm_consensus_tpu.eval.arith import build_sft_examples, eval_split
from llm_consensus_tpu.eval.gsm8k import evaluate_self_consistency
from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.training.data import SftBatchLoader
from llm_consensus_tpu.training.loop import LoopConfig, run_training
from llm_consensus_tpu.training.train import TrainConfig


def _splits(args):
    """(eval_problems, holdout_signatures) for the selected task."""
    if args.task == "arith2":
        from llm_consensus_tpu.eval.arith2 import eval_problems

        return eval_problems(args.n_problems, seed=args.eval_seed)
    return eval_split(args.n_problems, seed=args.eval_seed)


def train(args, cfg, tok) -> None:
    _, holdout = _splits(args)
    if args.task == "arith2":
        from llm_consensus_tpu.eval.arith2 import (
            build_sft_examples as build2,
        )

        n_train = args.n_train
        if args.limit:
            n_train = min(n_train, args.limit)
        examples = build2(tok, n_train, exclude=holdout)
    else:
        examples = build_sft_examples(tok, exclude=holdout, limit=args.limit)
    loader = SftBatchLoader(
        examples, args.batch, args.seq, seed=1, pad_id=tok.pad_id
    )
    print(
        f"[train] {loader.n_examples} SFT examples "
        f"({len(holdout)} eval triples held out), "
        f"batch {args.batch} x seq {args.seq}",
        file=sys.stderr,
    )
    tcfg = TrainConfig(
        learning_rate=args.lr,
        warmup_steps=min(200, args.steps // 10),
        total_steps=args.steps,
        compute_dtype="bfloat16"
        if jax.devices()[0].platform == "tpu"
        else None,
    )
    loop = LoopConfig(
        total_steps=args.steps,
        log_every=max(1, args.steps // 30),
        ckpt_every=args.ckpt_every or max(1, args.steps // 4),
        ckpt_dir=args.ckpt_dir,
        seed=0,
    )
    t0 = time.perf_counter()
    _, report = run_training(cfg, tcfg, loader, loop)
    wall = time.perf_counter() - t0
    last = report.losses[-1] if report.losses else None
    print(
        f"[train] {report.final_step} steps in {wall:.0f}s"
        + (f", final loss {last.loss:.4f}" if last else ""),
        file=sys.stderr,
    )


def load_engine(args, cfg, tok) -> InferenceEngine:
    """Reload the latest checkpoint from disk into a fresh engine."""
    from llm_consensus_tpu.checkpoint.io import restore_params_for_inference

    try:
        params, step = restore_params_for_inference(
            cfg, args.ckpt_dir, jnp.bfloat16
        )
    except FileNotFoundError as e:
        raise SystemExit(f"{e}; train first") from e
    print(
        f"[eval] restored from {args.ckpt_dir} (step {step})",
        file=sys.stderr,
    )
    return InferenceEngine(
        cfg,
        params,
        tokenizer=tok,
        engine_config=EngineConfig(max_new_tokens=args.max_new_tokens),
    )


def evaluate(args, engine) -> dict:
    problems, _ = _splits(args)
    rows = []
    for n in args.ns:
        rep = evaluate_self_consistency(
            engine,
            problems,
            n=n,
            temperature=args.temperature,
            seed=1234,
            max_new_tokens=args.max_new_tokens,
        )
        rows.append(rep.to_dict())
        print(
            f"[eval] N={n:<3d} EM={rep.em:.3f} "
            f"({rep.total_candidate_tokens} candidate tokens, "
            f"{rep.candidate_tokens_per_sec:.0f} tok/s)",
            file=sys.stderr,
        )
    return {
        "model": engine.cfg.name,
        "task": args.task,
        "n_problems": args.n_problems,
        "temperature": args.temperature,
        "device": jax.devices()[0].platform,
        "rows": rows,
    }


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument(
        "--task",
        default="arith",
        choices=("arith", "arith2"),
        help="arith: single-template (a+b)*c (the round-4 loop); "
        "arith2: multi-template 2-4-step chains with distractors "
        "(eval/arith2.py) — pair with --model arith-25m, --seq 704",
    )
    p.add_argument(
        "--model",
        default="",
        help="'' = per-task default (arith-14m for arith, arith-25m "
        "for arith2 — the 512-context arith-14m truncates arith2's "
        "~650-byte examples)",
    )
    p.add_argument(
        "--n-train",
        type=int,
        default=60000,
        help="arith2 only: SFT examples to sample (the chain space is "
        "effectively unbounded, unlike arith's 27,848 triples)",
    )
    p.add_argument("--steps", type=int, default=6000)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument(
        "--seq",
        type=int,
        default=0,
        help="0 = per-task default (384 for arith, 704 for arith2; a "
        "too-short seq would silently cut the CoT + '####' answer "
        "off the training pairs)",
    )
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--limit", type=int, default=0, help="cap SFT examples")
    p.add_argument("--ckpt-dir", default="runs/arith14m")
    p.add_argument("--ckpt-every", type=int, default=0)
    p.add_argument("--n-problems", type=int, default=50)
    p.add_argument("--eval-seed", type=int, default=0)
    p.add_argument("--ns", type=int, nargs="+", default=[1, 8, 32])
    p.add_argument("--temperature", type=float, default=0.7)
    p.add_argument(
        "--max-new-tokens",
        type=int,
        default=0,
        help="0 = per-task default (64 for arith's 2-step CoT, 112 for "
        "arith2's up-to-4-step CoT)",
    )
    p.add_argument("--eval-only", action="store_true")
    p.add_argument("--train-only", action="store_true")
    p.add_argument("--report", default="")
    p.add_argument(
        "--cpu",
        action="store_true",
        help="run on the CPU on purpose",
    )
    args = p.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    if not args.max_new_tokens:
        args.max_new_tokens = 112 if args.task == "arith2" else 64
    if not args.model:
        args.model = "arith-25m" if args.task == "arith2" else "arith-14m"
    if not args.seq:
        args.seq = 704 if args.task == "arith2" else 384

    cfg = get_config(args.model)
    if args.task == "arith2" and cfg.max_seq_len < 640:
        raise SystemExit(
            f"--task arith2 needs max_seq_len >= 640 (prompts+CoT reach "
            f"~650 bytes); {cfg.name} has {cfg.max_seq_len}. Use "
            f"--model arith-25m."
        )
    tok = ByteTokenizer()
    if not args.eval_only:
        train(args, cfg, tok)
    if args.train_only:
        return 0
    engine = load_engine(args, cfg, tok)
    result = evaluate(args, engine)
    print(json.dumps(result))
    if args.report:
        Path(args.report).parent.mkdir(parents=True, exist_ok=True)
        Path(args.report).write_text(json.dumps(result, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
