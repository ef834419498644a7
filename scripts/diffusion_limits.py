#!/usr/bin/env python3
"""Readings and planted faults for a block-diffusion reference cell,
through the benchmark's own verdict.

A cell whose traffic has a ``reference`` block (``panel_ref_diffusion``)
calls a run ``correct`` only if the served logits of its reference
requests lie within the traffic file's ``tolerance`` of the plain float32
reference, teacher-forced on the served denoising steps. This script
gives that comparison its two kinds of evidence, and hands both to
``generators/closed_loop_consensus_ref._judge`` — the function the
cell's ``reduce`` calls — with the cell's configuration and traffic
files. Nothing is compared here: the verdicts are the generator's.

- ``served``: the cell's own server (``benchmark/server.py``, the cell's
  configuration), asked for the traffic's reference requests — greedy,
  ``"logits"`` over the cell's denoising steps, two copies of each
  prompt so that the second maps the first's pages — for ``--seeds``
  seeds at ``--sizes``: the readings the limits rest on. Must lie within.
- faults, made in a child by the program's own bf16 forward (no cache,
  no kernel) driving the denoising steps greedily as a faulty server
  would: ``sound`` (none; must lie within), ``causal_in_block`` (plain
  causal attention, the block's positions blind to the ones after
  them), ``no_commit`` (a finished block's K/V are those of its state
  before the last unmask: no commit forward), ``no_qk_norm`` (the
  per-head norms left out). The three faults must lie outside.
- ``residual_f8``: the reference itself with its residual stream
  rounded to float8 e4m3 after every layer — the nearest precision below
  the bf16 activations the configuration states. Must lie outside.

    python3 scripts/diffusion_limits.py     # on a TPU host

The parent imports no JAX (a chip belongs to one process): the server,
then the child that makes the faults, then the generator's reference
child take the chip one after another. One line a request on stdout and
in ``chiprun_out/diffusion_limits/verdicts.jsonl``; after the server's
drain and at the end, the processes still alive that one of these
started (none is the pass); exit 1 if a verdict is not what it must be.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import random
import subprocess
import sys
import threading
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
OUT = os.path.join(ROOT, "chiprun_out", "diffusion_limits")
FAULTS = {"sound": "within", "causal_in_block": "outside",
          "no_commit": "outside", "no_qk_norm": "outside",
          "residual_f8": "outside"}
LEFTOVER = "launcher.py|llm_consensus_tpu|sdar_moe.py|diffusion_limits.py --make"


def leftover() -> list[str]:
    """Processes a run of the cell starts that are still alive."""
    r = subprocess.run(["pgrep", "-af", LEFTOVER], capture_output=True,
                       text=True)
    me = {str(os.getpid()), str(os.getppid())}
    return [line for line in r.stdout.splitlines()
            if line.split()[0] not in me and "pgrep" not in line]


def _served(args, config_path: str, spec: dict) -> list[dict]:
    """The cell's server, the reference requests of every seed."""
    sys.path.insert(0, BENCH)
    from server import Server, http
    from stats import filler_text

    srv = Server(config_path, os.path.join(OUT, "server"),
                 ["--cpu"] if args.cpu else [])
    replies: list[dict] = []
    try:
        srv.wait_listening()
        for seed in args.seeds:
            rng = random.Random(seed ^ 0x5EED)
            for size in args.sizes:
                prompt = filler_text(size, rng, f"[ref.{seed:x}.{size}]")
                got: dict = {}

                def one(copy, prompt=prompt):
                    status, text = http("POST", srv.base + "/v1/generate", {
                        "prompt": prompt, "temperature": 0,
                        "max_new_tokens": spec["positions"],
                        "logits": spec["positions"],
                    }, timeout=600)
                    got[copy] = (status, text)

                threads = [threading.Thread(target=one, args=(c,))
                           for c in range(spec["copies"])]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                for copy, (status, text) in sorted(got.items()):
                    doc = json.loads(text) if status == 200 else {}
                    replies.append({
                        "reference": f"served.{seed}.{size}.{copy}",
                        "prompt": prompt, "status": status,
                        "error": None if status == 200 else text[:300],
                        **((doc.get("meta") or {}).get("logits") or {}),
                    })
        exit_doc = srv.drain()
        print(json.dumps({"server_exit": exit_doc}), flush=True)
    finally:
        srv.kill()
    return replies


def _make(args) -> int:
    """The child: each fault's logits, as a server's replies."""
    sys.path[:0] = [ROOT, BENCH]
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from stats import filler_text

    from llm_consensus_tpu.cli import random_params
    from llm_consensus_tpu.engine.tokenizer import ByteTokenizer
    from llm_consensus_tpu.models import transformer as T
    from llm_consensus_tpu.models.configs import get_config
    from llm_consensus_tpu.models.reference import sdar_moe as R
    from llm_consensus_tpu.ops.rope import rope_cos_sin

    cfg = get_config(args.model)
    if args.layers:
        cfg = cfg.with_layers(args.layers)
    params = random_params(cfg, jax.random.PRNGKey(0), args.quant)
    jax.block_until_ready(params)
    tok = ByteTokenizer()
    b, steps, mask = cfg.block_length, cfg.denoising_steps, cfg.mask_token_id
    variants = {
        "sound": cfg, "no_commit": cfg,
        "causal_in_block": cfg.with_(generation="autoregressive",
                                     block_length=1),
        "no_qk_norm": cfg.with_(qk_norm=False),
    }

    def logits_at(c, params, buf, at):
        x = params["embed"][buf[None]]
        pos = jnp.arange(buf.shape[0])[None]
        cos, sin = rope_cos_sin(pos, c.rope_dim, c.rope_theta, c.rope_scaling)
        x, _ = T._run_layers(c, params, x, cos, sin, None, "full", None, None)
        return T._unembed(c, params, x[0, at])

    # The weights ride as an argument: closed over, they would be
    # constants of every program.
    programs = {name: jax.jit(partial(logits_at, c))
                for name, c in variants.items()}

    def reference_f8(params, buf, at):
        """The reference's forward, its residual stream rounded to
        float8 e4m3 after every layer."""
        f8 = jnp.finfo(jnp.float8_e4m3fn)
        blocks = params["blocks"]
        with jax.default_matmul_precision("highest"):
            x = jnp.asarray(params["embed"][buf], jnp.float32)
            for i in range(cfg.n_layers):
                p = R._index(blocks, i)
                h = R._jit(R._normed, cfg)(p["attn_norm"], x)
                x = x + R._jit(R.attention, cfg)(p, h)
                h = R._jit(R._normed, cfg)(p["mlp_norm"], x)
                x = x + R.experts(cfg, p, h)
                x = jax.lax.reduce_precision(x, f8.nexp, f8.nmant)
            return R._jit(R.head, cfg)(params["norm_f"], params["lm_head"],
                                        x[at])

    programs["residual_f8"] = reference_f8
    prompts = [filler_text(s, random.Random(s), f"[limits.{s}]")
               for s in args.fault_sizes]
    width = max(len(tok.encode(p)) for p in prompts) + (args.positions + 1) * b
    replies = []
    for prompt in prompts:
        ids = list(tok.encode(prompt))
        whole = len(ids) - len(ids) % b
        for name in args.faults:
            ctx, tail = ids[:whole], ids[whole:]
            block = tail + [mask] * (b - len(tail))
            masked0, before_last, rows = b - len(tail), block, []
            for n in range(args.positions):
                step = n % steps
                if n and not step:
                    ctx = ctx + (before_last if name == "no_commit" else block)
                    block, masked0 = [mask] * b, b
                    before_last = block
                buf = np.full((width,), tok.pad_id, np.int32)
                buf[: len(ctx) + b] = ctx + block
                row = np.asarray(programs[name](
                    params, jnp.asarray(buf),
                    jnp.arange(len(ctx), len(ctx) + b),
                ), "<f4")
                rows.append(row)
                nxt = R.unmask(cfg, block, row, masked0, step)
                if nxt != block:
                    before_last = block
                block = nxt
            got = np.concatenate(rows)
            replies.append({
                "reference": f"{name}.{len(prompt)}", "prompt": prompt,
                "status": 200, "error": None, "positions": got.shape[0],
                "vocab": got.shape[1],
                "b64": base64.b64encode(got.tobytes()).decode(),
            })
            print(f"made {name}.{len(prompt)}", file=sys.stderr, flush=True)
    with open(args.make, "w") as f:
        json.dump(replies, f)
    return 0


def _ints(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="sdar-30b-a3b-chat.panel")
    ap.add_argument("--seeds", type=int, default=20,
                    help="served readings: this many seeds (0: none)")
    ap.add_argument("--first-seed", type=int, default=1_000_003)
    ap.add_argument("--sizes", default="",
                    help="prompt bytes of the served readings (default: the "
                    "traffic file's smallest)")
    ap.add_argument("--fault-sizes", default="",
                    help="prompt bytes of the faults (default: the two "
                    "smallest; empty with --faults '' for none)")
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--model", default="", help="another preset (a CPU try)")
    ap.add_argument("--layers", type=int, default=-1)
    ap.add_argument("--quant", default="")
    ap.add_argument("--cpu", action="store_true",
                    help="a CPU try of the plumbing: its verdicts mean nothing")
    ap.add_argument("--make", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = next(w for w in json.load(f)["workloads"]
                    if w["name"] == args.workload)
    config_path = os.path.join(BENCH, "configs", cell["config"] + ".json")
    with open(config_path) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    serve, spec = config["serve"], traffic["reference"]
    serve["model"] = args.model or serve["model"]
    serve["layers"] = serve.get("layers", 0) if args.layers < 0 else args.layers
    serve["quant"] = args.quant or serve["quant"]
    args.model, args.layers, args.quant = (
        serve["model"], serve["layers"], serve["quant"])
    args.positions = spec["positions"]  # denoising steps
    sizes = sorted(spec["prompt_bytes"])
    args.sizes = _ints(args.sizes) or sizes[:1]
    args.fault_sizes = _ints(args.fault_sizes) or sizes[:2]
    args.faults = [c for c in args.faults.split(",") if c]
    args.seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    if args.make:
        return _make(args)

    os.makedirs(OUT, exist_ok=True)
    replies = []
    if args.seeds:
        # The cell's configuration, with the preset asked for.
        config_path = os.path.join(OUT, "config.json")
        with open(config_path, "w") as f:
            json.dump(config, f)
        replies += _served(args, config_path, spec)
        print(json.dumps({"left_after_drain": leftover()}), flush=True)
    if args.faults:
        made = os.path.join(OUT, "faults.json")
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--make", made,
             *sys.argv[1:]], cwd=ROOT,
        )
        if child.returncode:
            print(f"the child that makes the faults: rc {child.returncode}")
            return 2
        with open(made) as f:
            replies += json.load(f)
        os.remove(made)  # the logits, tens of MB
    replies = [{**r, "out_dir": OUT} for r in replies]

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
    with open(os.path.join(OUT, "verdicts.jsonl"), "a") as sink:
        for r in doc.get("requests", []):
            name = r["tag"].split(".", 1)[0]
            verdict = "within" if r["within_tolerance"] else "outside"
            want = "within" if name == "served" else FAULTS.get(name, "any")
            if want not in ("any", verdict):
                wrong.append(f"{r['tag']}: {verdict}, must be {want}")
            line = json.dumps({
                "request": r["tag"], "verdict": verdict, "must_be": want,
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
    print(json.dumps({"left_at_end": leftover()}), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
