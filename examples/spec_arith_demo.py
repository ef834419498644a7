"""REAL speculative-decoding acceptance: trained target + trained draft.

Random weights only bracket speculation: a self-draft gives the
acceptance~1 overhead ceiling, a random draft the ~0 floor. This
script measures the honest middle — a 14M target and a ~2.5M draft
BOTH trained on the arithmetic SFT corpus (``examples/train_arith_em.py``
recipe), decoding real eval prompts greedily:

1. train (or reuse) ``arith-14m`` and ``arith-3m`` checkpoints;
2. reload both through orbax;
3. run :func:`speculative_generate` on the eval problems' prompts and
   report acceptance rate + tokens/sec vs the plain greedy path.

Usage:
    python examples/spec_arith_demo.py \
        --target-ckpt runs/arith14m --draft-ckpt runs/arith3m \
        [--train-draft]  # trains the draft first if needed

    # Early-snapshot-as-draft: the SAME preset at an earlier training
    # step drafts for the converged target (no separate draft model):
    python examples/spec_arith_demo.py --draft-model arith-14m \
        --target-ckpt runs/arith14m --draft-ckpt runs/arith14m_mid2
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
import numpy as np

from llm_consensus_tpu.engine.speculative import speculative_generate
from llm_consensus_tpu.engine.generate import generate
from llm_consensus_tpu.engine.tokenizer import ByteTokenizer
from llm_consensus_tpu.eval.arith import eval_split
from llm_consensus_tpu.eval.gsm8k import _PROMPT
from llm_consensus_tpu.models.configs import get_config


def _load_params(model: str, ckpt_dir: str):
    from llm_consensus_tpu.checkpoint.io import restore_params_for_inference

    cfg = get_config(model)
    params, _ = restore_params_for_inference(cfg, ckpt_dir, jnp.bfloat16)
    return cfg, params


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--target-ckpt", default="runs/arith14m")
    p.add_argument("--draft-ckpt", default="runs/arith3m")
    p.add_argument("--target-model", default="arith-14m")
    p.add_argument(
        "--draft-model",
        default="arith-3m",
        help="draft preset; pass the TARGET's preset with an earlier "
        "training snapshot as --draft-ckpt to measure the "
        "early-checkpoint-as-draft configuration",
    )
    p.add_argument("--train-draft", action="store_true")
    p.add_argument("--draft-steps", type=int, default=6000)
    p.add_argument("--n-prompts", type=int, default=16)
    p.add_argument(
        "--holdout-n",
        type=int,
        default=50,
        help="size of the eval holdout the checkpoints were trained "
        "with (train_arith_em --n-problems; eval seed must match too) — "
        "prompts past this index were TRAINED ON and would inflate "
        "acceptance",
    )
    p.add_argument("--max-new-tokens", type=int, default=48)
    p.add_argument("--k-spec", type=int, default=4)
    p.add_argument("--iters", type=int, default=3)
    p.add_argument(
        "--cpu", action="store_true",
        help="run on the CPU on purpose",
    )
    args = p.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    if args.train_draft:
        # Reuse the training script via its CLI surface for an identical
        # recipe (same corpus, same holdout). A chip has one owner at a
        # time: the child runs to its end HERE, before this process
        # first touches a device (nothing above initialises a backend).
        import subprocess

        cmd = [
            sys.executable,
            str(Path(__file__).parent / "train_arith_em.py"),
            "--model", args.draft_model,
            "--steps", str(args.draft_steps),
            "--ckpt-dir", args.draft_ckpt,
            "--train-only",
        ] + (["--cpu"] if args.cpu else [])
        print("[spec-demo] training draft:", " ".join(cmd), file=sys.stderr)
        subprocess.run(cmd, check=True)

    t_cfg, t_params = _load_params(args.target_model, args.target_ckpt)
    d_cfg, d_params = _load_params(args.draft_model, args.draft_ckpt)
    tok = ByteTokenizer()

    if args.n_prompts > args.holdout_n:
        # Training held out exactly the first --holdout-n eval problems'
        # triples; prompts past that index were TRAINED ON by both
        # models and would inflate the acceptance number.
        raise SystemExit(
            f"--n-prompts {args.n_prompts} exceeds the training holdout "
            f"({args.holdout_n}; see --holdout-n) — extra prompts come "
            "from the training corpus"
        )
    problems, _ = eval_split(args.n_prompts, seed=0)
    prompts = [_PROMPT.format(q=pr.question) for pr in problems]
    ids = [tok.encode(t) for t in prompts]
    s = max(len(x) for x in ids)
    b = len(ids)
    tokens = np.full((b, s), tok.pad_id, np.int32)
    for i, x in enumerate(ids):
        tokens[i, : len(x)] = x
    lengths = np.asarray([len(x) for x in ids], np.int32)
    tokens_j, lengths_j = jnp.asarray(tokens), jnp.asarray(lengths)

    def run_spec(i):
        return speculative_generate(
            t_cfg, t_params, d_cfg, d_params, tokens_j, lengths_j,
            max_new_tokens=args.max_new_tokens, k_spec=args.k_spec,
            eos_id=tok.eos_id, pad_id=tok.pad_id,
        )

    def run_plain(i):
        return generate(
            t_cfg, t_params, tokens_j, lengths_j,
            jax.random.fold_in(jax.random.PRNGKey(0), i),
            jnp.zeros((b,), jnp.float32),
            max_new_tokens=args.max_new_tokens, eos_id=tok.eos_id,
        )

    out = run_spec(0)
    plain = run_plain(0)
    # Warmup work must not bleed into iteration 1: fetch both results.
    np.asarray(out.tokens), np.asarray(plain.tokens)
    # Greedy speculative output must equal greedy plain output.
    match = bool(
        jnp.all(
            jnp.where(
                jnp.arange(args.max_new_tokens)[None, :]
                < plain.num_tokens[:, None],
                out.tokens == plain.tokens,
                True,
            )
        )
    )
    # Dispatch is asynchronous: each iteration fetches its token buffer
    # inside the timed region.
    t0 = time.perf_counter()
    for i in range(args.iters):
        out = run_spec(i + 1)
        np.asarray(out.tokens)
    spec_wall = (time.perf_counter() - t0) / args.iters
    t0 = time.perf_counter()
    for i in range(args.iters):
        plain = run_plain(i + 1)
        np.asarray(plain.tokens)
    plain_wall = (time.perf_counter() - t0) / args.iters

    produced = float(jnp.sum(out.num_tokens))
    acc = float(out.accepted) / max(1.0, float(out.drafted))
    result = {
        "target": t_cfg.name,
        "draft": d_cfg.name,
        # Checkpoint dirs disambiguate same-preset configurations (the
        # early-snapshot-as-draft mode has target.name == draft.name).
        "target_ckpt": args.target_ckpt,
        "draft_ckpt": args.draft_ckpt,
        "n_prompts": b,
        "k_spec": args.k_spec,
        "acceptance": round(acc, 4),
        "greedy_output_matches_plain": match,
        "spec_tok_s": round(produced / spec_wall, 1),
        "plain_tok_s": round(
            float(jnp.sum(plain.num_tokens)) / plain_wall, 1
        ),
        "speedup": round(plain_wall / spec_wall, 3),
        "device": jax.devices()[0].platform,
    }
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
