#!/usr/bin/env python3
"""Time the ragged attention kernel ALONE on the chip, a layer-call.

ISSUE 29's Step 0 and its after-reading: one jitted program scans the
kernel over the layers of a stacked pool at a cell's real widths (the
layer a traced index, as the step programs call it), and the host clock
around ``block_until_ready`` over ``--reps`` calls gives the time a
layer-call. The rows are given, so what a call's time follows (the
table's width, the rows, the live pages) can be read off one at a time:

    chiprun -- python scripts/time_ragged_attention.py

One JSON line a case on stdout, all of them in
``chiprun_out/time_ragged_attention.jsonl``. Never a CPU number: it
refuses to run without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

PG = 64

# name -> (layers, pages, hkv, g, d, latent_dv)
WIDTHS = {
    "mistral-7b": (32, 512, 8, 4, 128, 0),
    "qwen2-7b": (28, 1024, 4, 7, 128, 0),
    "deepseek-v2-lite": (19, 1024, 1, 16, 640, 512),
}


def _case(model, *, rows, cols, live, chunk=0, groups=0, shared=0):
    """``live``: pages each of the first len(live) decode rows holds (the
    others hold none). ``chunk``: live pages of a 64-query chunk lane (0 =
    no lane). ``groups``: group programs (``max_groups``), the first of
    which is real: rows 0.. of ``live`` share their first ``shared``
    pages; the others are padding, as in a step program."""
    return dict(
        model=model, rows=rows, cols=cols, live=list(live), chunk=chunk,
        groups=groups, shared=shared,
    )


def cases():
    out = [  # Step 0: one decode row of 5 live pages, the table cut
        _case("mistral-7b", rows=8, cols=c, live=[5]) for c in (6, 12, 24, 48)
    ]
    out += [
        # a row count twice as large, the same live work
        _case("mistral-7b", rows=16, cols=48, live=[5]),
        # chat: three rows in flight
        _case("mistral-7b", rows=8, cols=48, live=[3, 7, 11]),
        # nothing live at all: the floor of a call
        _case("mistral-7b", rows=8, cols=48, live=[]),
    ]
    for model, rows, gm in (
        ("mistral-7b", 8, 4), ("qwen2-7b", 16, 8), ("deepseek-v2-lite", 16, 8),
    ):
        # a panel's fused step: a chunk lane of 19 pages, three evaluate
        # rows of 36 pages sharing their first 7, ungrouped and grouped
        out.append(_case(model, rows=rows, cols=48, live=[36] * 3, chunk=19))
        out.append(
            _case(model, rows=rows, cols=48, live=[36] * 3, chunk=19,
                  groups=gm, shared=7)
        )
    return out


def make_pools(model, rng):
    layers, pages, hkv, _, d, dv = WIDTHS[model]
    shape = (PG, d) if dv else (PG, hkv, d)
    base = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    fill = jax.jit(
        lambda x: jnp.broadcast_to(x, (layers, pages, *shape)) + jnp.bfloat16(0)
    )
    return fill(base), None if dv else fill(base * jnp.bfloat16(0.5))


def build(case, pools, rng):
    from llm_consensus_tpu.ops.pallas.attention import ragged_paged_attention

    layers, _, hkv, g, d, dv = WIDTHS[case["model"]]
    b, p_per, h = case["rows"], case["cols"], hkv * g
    k_pools, v_pools = pools
    tbl = np.zeros((b, p_per), np.int32)
    valid = np.zeros((b,), np.int32)
    nxt = 1
    for r, n in enumerate(case["live"]):
        tbl[r, :n] = np.arange(nxt, nxt + n)
        nxt += n
        valid[r] = n * PG - 10
    kw = {}
    if case["groups"]:
        sh = case["shared"]
        members = len(case["live"])
        for r in range(1, members):
            tbl[r, :sh] = tbl[0, :sh]
        gid = np.where(np.arange(b) < members, 0, -1)
        gend = np.zeros((case["groups"],), np.int32)
        gend[0] = sh * PG
        kw["groups"] = (
            jnp.asarray(gid, jnp.int32),
            jnp.zeros((case["groups"],), jnp.int32),
            jnp.asarray(gend),
            jnp.asarray(np.where(gid == 0, sh * PG, 0), jnp.int32),
        )
    if case["chunk"]:
        n = case["chunk"]
        ct = np.zeros((p_per,), np.int32)
        ct[:n] = np.arange(nxt, nxt + n)
        kw.update(
            q_chunk=jnp.asarray(rng.standard_normal((64, h, d)), jnp.bfloat16),
            chunk_table=jnp.asarray(ct),
            chunk_start=jnp.int32(n * PG - 64),
        )
    if dv:
        kw.update(latent_dv=dv, scale=d**-0.5)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.bfloat16)

    def step(q, k_pools, v_pools, tbl, valid):
        def body(acc, layer):
            out = ragged_paged_attention(
                q, k_pools, v_pools, tbl, valid, layer=layer, **kw
            )
            # every output feeds the carry: no call is dead code
            return acc + sum(
                jnp.sum(o.astype(jnp.float32)) for o in jax.tree.leaves(out)
            ), None

        return jax.lax.scan(body, jnp.float32(0), jnp.arange(layers))[0]

    args = (q, k_pools, v_pools, jnp.asarray(tbl), jnp.asarray(valid))
    return jax.jit(step), args, layers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--out", default="chiprun_out/time_ragged_attention.jsonl")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: the default device is {dev.platform!r}", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    rng = np.random.default_rng(0)
    model = pools = fargs = None
    with open(args.out, "a") as fh:
        for case in cases():
            if case["model"] != model:
                pools = fargs = None  # free the last model's before the next
                model, pools = case["model"], make_pools(case["model"], rng)
            fn, fargs, layers = build(case, pools, rng)
            fn(*fargs).block_until_ready()  # compile
            times = []
            for _ in range(5):
                # back to back, one wait: the host's dispatch hides
                # behind the device
                t0 = time.perf_counter()
                outs = [fn(*fargs) for _ in range(args.reps)]
                jax.block_until_ready(outs)
                times.append((time.perf_counter() - t0) / args.reps)
            line = dict(
                case,
                device=dev.device_kind,
                layers=layers,
                us_per_layer_call=statistics.median(times) / layers * 1e6,
                us_min=min(times) / layers * 1e6,
            )
            text = json.dumps(line)
            print(text, flush=True)
            fh.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
