#!/usr/bin/env python3
"""Controls for a reference cell, through the benchmark's own verdict.

A cell whose traffic has a ``reference`` block (``panel_ref_ssm``)
calls a run ``correct`` only if the served logits lie within the
traffic file's ``tolerance`` of the plain float32 reference. This
script shows what that comparison can and cannot tell apart: it makes
the logits a faulty or degraded server WOULD return — greedy, the
cell's number of positions, at the cell's prompt sizes — and hands them
to ``generators/closed_loop_consensus_ref._judge``, the function the
cell's ``reduce`` calls, with the cell's configuration and traffic
files. Nothing is compared here: the verdicts are the generator's.

The controls:

- ``sound``: the program's own bf16 ``forward`` (no cache, no kernel).
  Must come out within the tolerance.
- ``residual_f8``: the reference with its residual stream rounded to
  float8 e4m3 after every layer — below the bf16 the configuration
  states for activations. Must fail.
- ``moe_not_renormalised``: the program's ``forward`` with a fault
  planted in the expert layer — the chosen scores not divided by their
  sum, so the routed update is ~5x too large. Must fail.
- ``moe_scale_omitted``: the factor 2.5 left out, a routed update 0.4x
  of what it should be. Reported: on the chip it reads AT the limits
  (0.30-0.37 / 0.057-0.059 against 0.38 / 0.062, PR 32) — the routed
  experts' random out-projection is drawn 1/16 as wide
  (``transformer._routed_out_scale``), and this is what that costs.
- ``state_bf16`` / ``state_bf16_blocks``: the recurrent state kept in
  bfloat16 where the configuration computes it in float32 — in the
  reference (rounded after every token) and in the program's
  ``forward`` (rounded between its 64-token blocks, as a bf16 state
  pool would be). Reported: both read INSIDE the limits, the second no
  different from ``sound`` (PERF.md, Findings PR 32).

    chiprun -- python scripts/reference_limits.py

The parent process imports no JAX (a chip belongs to one process): a
child makes the logits and exits, then the generator's own child runs
the reference. One line a control and size on stdout and in
``chiprun_out/reference_limits/controls.jsonl``; exit 1 if a control
that must fail passes, or ``sound`` fails.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import random
import subprocess
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
OUT = os.path.join(ROOT, "chiprun_out", "reference_limits")

# control -> what the verdict has to be ("any": reported, not judged)
CONTROLS = {
    "sound": "within",
    "residual_f8": "outside",
    "moe_not_renormalised": "outside",
    "moe_scale_omitted": "any",
    "state_bf16": "any",
    "state_bf16_blocks": "any",
}


def _make(args) -> int:
    """The child: every control's logits, as a server's replies."""
    sys.path[:0] = [ROOT, BENCH]
    import jax
    import jax.numpy as jnp
    import numpy as np
    from stats import filler_text

    from llm_consensus_tpu.cli import random_params
    from llm_consensus_tpu.engine.tokenizer import ByteTokenizer
    from llm_consensus_tpu.models import transformer as T
    from llm_consensus_tpu.models.configs import get_config
    from llm_consensus_tpu.models.reference import nemotron_h as R
    from llm_consensus_tpu.ops import ssm as S

    cfg = get_config(args.model)
    if args.layers:
        cfg = cfg.with_layers(args.layers)
    params = random_params(cfg, jax.random.PRNGKey(0), args.quant)
    jax.block_until_ready(params)
    tok = ByteTokenizer()

    def program(c):
        def at(buf, pos):
            return T.forward(c, params, jnp.asarray(buf)[None])[0, pos]
        return at

    def reference(**kw):
        def at(buf, pos):
            return R.forward(cfg, params, buf, at=np.asarray([pos]), **kw)[0]
        return at

    apply = S.ssd_apply

    def rounded_between_blocks(terms, s0):
        y, s1 = apply(terms, s0)
        return y, jax.lax.reduce_precision(s1, 8, 7)

    forms = {
        "sound": program(cfg),
        "residual_f8": reference(round_to=jnp.float8_e4m3fn),
        "moe_not_renormalised": program(cfg.with_(moe_renormalize=False)),
        "moe_scale_omitted": program(cfg.with_(moe_routed_scale=1.0)),
        "state_bf16": reference(state_dtype=jnp.bfloat16),
        "state_bf16_blocks": program(cfg),
    }
    replies = []
    for size in args.sizes:
        prompt = filler_text(size, random.Random(size), f"[limits.{size}]")
        ids = np.asarray(list(tok.encode(prompt)), np.int32)
        for name in args.controls:
            S.ssd_apply = (
                rounded_between_blocks if name == "state_bf16_blocks" else apply
            )
            # Greedy, as the cell's requests are. Every layer is causal,
            # so the buffer keeps one length (one compilation) and the
            # tokens past the position read change nothing.
            buf = np.concatenate(
                [ids, np.full(args.positions - 1, tok.pad_id, np.int32)]
            )
            rows = []
            for i in range(args.positions):
                row = np.asarray(forms[name](buf, len(ids) - 1 + i), "<f4")
                rows.append(row)
                if i + 1 < args.positions:
                    buf[len(ids) + i] = int(row.argmax())
            S.ssd_apply = apply
            got = np.stack(rows)
            replies.append({
                "reference": f"{name}.{size}", "prompt": prompt,
                "status": 200, "error": None, "positions": len(rows),
                "vocab": got.shape[1],
                "b64": base64.b64encode(got.tobytes()).decode(),
            })
            print(f"made {name}.{size}", file=sys.stderr, flush=True)
    with open(args.make, "w") as f:
        json.dump(replies, f)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="nemotron-3-nano-30b-a3b.panel")
    ap.add_argument("--sizes", default="",
                    help="prompt bytes, comma-separated (default: the "
                    "traffic file's two largest)")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--model", default="", help="another preset (a CPU try)")
    ap.add_argument("--layers", type=int, default=-1)
    ap.add_argument("--quant", default="")
    ap.add_argument("--positions", type=int, default=0)
    ap.add_argument("--make", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = next(w for w in json.load(f)["workloads"]
                    if w["name"] == args.workload)
    with open(os.path.join(BENCH, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    serve, spec = config["serve"], traffic["reference"]
    serve["model"] = args.model or serve["model"]
    serve["layers"] = serve.get("layers", 0) if args.layers < 0 else args.layers
    serve["quant"] = args.quant or serve["quant"]
    args.model, args.layers, args.quant = (
        serve["model"], serve["layers"], serve["quant"])
    args.positions = args.positions or spec["positions"]
    args.sizes = [int(s) for s in args.sizes.split(",") if s] or sorted(
        spec["prompt_bytes"])[-2:]
    args.controls = [c for c in args.controls.split(",") if c]
    if args.make:
        return _make(args)

    os.makedirs(OUT, exist_ok=True)
    made = os.path.join(OUT, "replies.json")
    child = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--make", made,
         *sys.argv[1:]], cwd=ROOT,
    )
    if child.returncode:
        print(f"the child that makes the logits: rc {child.returncode}")
        return 2
    with open(made) as f:
        replies = [{**r, "out_dir": OUT} for r in json.load(f)]
    os.remove(made)  # the logits, tens of MB

    sys.path.insert(0, BENCH)
    from generators import closed_loop_consensus_ref as gen

    run = types.SimpleNamespace(config=config, traffic=traffic)
    bad = gen._judge(run, replies)
    if any(b.startswith(("reference child", "no reference")) for b in bad):
        print("\n".join(bad))
        return 2
    with open(os.path.join(OUT, "reference.json")) as f:
        doc = json.load(f)
    wrong = []
    with open(os.path.join(OUT, "controls.jsonl"), "a") as sink:
        for r in doc.get("requests", []):
            name = r["tag"].rsplit(".", 1)[0]
            verdict = "within" if r["within_tolerance"] else "outside"
            want = CONTROLS.get(name, "any")
            if want not in ("any", verdict):
                wrong.append(f"{r['tag']}: {verdict}, must be {want}")
            line = json.dumps({
                "control": r["tag"], "verdict": verdict, "must_be": want,
                "tolerance": doc["tolerance"],
                **{k: r[k] for k in ("prompt_tokens", "positions", "max_abs",
                                     "rel_rms", "argmax_agree")},
            })
            print(line, flush=True)
            sink.write(line + "\n")
    for line in bad:
        print("generator: " + line)
    for line in wrong:
        print("WRONG: " + line)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
