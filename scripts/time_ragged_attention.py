#!/usr/bin/env python3
"""Time the ragged attention kernel ALONE on the chip, a layer-call, and
read its float32 outputs against a float64 oracle.

ISSUE 29's Step 0 and ISSUE 33's: one jitted program scans the kernel
over the layers of a stacked pool at a cell's real widths (the layer a
traced index, as the step programs call it), and the host clock around
``block_until_ready`` over ``--reps`` calls gives the time a layer-call.
The rows are given, so what a call's time follows (the table's width,
the rows, the live pages, the chunk lanes) can be read off one at a
time. Beside each timing, ``err_*``: one layer's call with
``out_dtype=float32`` against ``parity.ragged_oracle`` on the same
bfloat16 inputs (max |kernel - oracle| over max |oracle|).

    chiprun -- python scripts/time_ragged_attention.py

``--module name=path`` (repeatable) times a COPY of
``ops/pallas/attention.py`` beside the package's own: the variants of a
step 0 live in scratch files, never as a switch in the package.

One JSON line a case and variant on stdout, all of them in
``chiprun_out/time_ragged_attention.jsonl``. Never a CPU number: it
refuses to run without a TPU.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

PG = 64
DISTINCT = 64  # random pages a pool is tiled from

# name -> (layers, pages, hkv, g, d, latent_dv)
WIDTHS = {
    "mistral-7b": (32, 512, 8, 4, 128, 0),
    "qwen2-7b": (28, 1024, 4, 7, 128, 0),
    "deepseek-v2-lite": (19, 1024, 1, 16, 640, 512),
}


def _case(model, *, rows, cols, live, chunk=0, lanes=1, groups=0, shared=0):
    """``live``: pages each of the first len(live) decode rows holds (the
    others hold none). ``chunk``: live pages of a 64-query chunk lane (0 =
    no lane), ``lanes`` of them, each over pages of its own. ``groups``:
    group programs (``max_groups``), the first of which is real: rows 0..
    of ``live`` share their first ``shared`` pages; the others are
    padding, as in a step program."""
    return dict(
        model=model, rows=rows, cols=cols, live=list(live), chunk=chunk,
        lanes=lanes if chunk else 0, groups=groups, shared=shared,
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
        # a panel's fused step: chunk lanes of 19 pages (one, and the
        # three a program carries since PR 31), three evaluate rows of 36
        # pages sharing their first 7, ungrouped and grouped; and the
        # evaluate rows alone
        out.append(_case(model, rows=rows, cols=48, live=[36] * 3))
        for lanes in (1, 3):
            out.append(_case(model, rows=rows, cols=48, live=[36] * 3,
                             chunk=19, lanes=lanes))
            out.append(_case(model, rows=rows, cols=48, live=[36] * 3,
                             chunk=19, lanes=lanes, groups=gm, shared=7))
    return out


def make_pools(model, rng):
    """(the stacked pools on the device, the DISTINCT random pages they
    repeat, on the host): page p of every layer is ``base[p % DISTINCT]``."""
    layers, pages, hkv, _, d, dv = WIDTHS[model]
    shape = (PG, d) if dv else (PG, hkv, d)

    def plane():
        base = jnp.asarray(
            rng.standard_normal((DISTINCT, *shape)), jnp.bfloat16
        )
        fill = jax.jit(
            lambda x: jnp.broadcast_to(
                x, (layers, pages // DISTINCT, DISTINCT, *shape)
            ).reshape(layers, pages, *shape) + jnp.bfloat16(0)
        )
        return fill(base), np.asarray(base, np.float64)

    k, k_base = plane()
    v, v_base = (None, None) if dv else plane()
    return (k, v), (k_base, v_base)


def build(case, pools, bases, rng, ragged_paged_attention):
    """(the timed program, its arguments, layers, a function that returns
    the case's ``err_*`` readings)."""
    from llm_consensus_tpu.ops.pallas import parity

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
    ct = cs = None
    if case["chunk"]:
        n, lanes = case["chunk"], case["lanes"]
        ct = np.zeros((lanes, p_per), np.int32)
        for lane in range(lanes):
            ct[lane, :n] = np.arange(nxt, nxt + n)
            nxt += n
        cs = np.full((lanes,), n * PG - 64, np.int32)
        kw.update(
            q_chunk=jnp.asarray(
                rng.standard_normal((lanes, 64, h, d)), jnp.bfloat16
            ),
            chunk_table=jnp.asarray(ct),
            chunk_start=jnp.asarray(cs),
        )
    scale = None
    if dv:
        scale = d**-0.5
        kw.update(latent_dv=dv, scale=scale)
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

    def errors():
        got = jax.jit(
            lambda *a: ragged_paged_attention(
                *a, layer=jnp.int32(0), out_dtype=jnp.float32, **kw
            )
        )(*args)
        k_base, v_base = bases
        oracle = dict(scale=scale, latent_dv=dv)
        errs = {}
        live = valid > 0
        if live.any():
            want = parity.ragged_oracle(
                np.asarray(q)[:, None], k_base, v_base, tbl % DISTINCT, valid,
                **oracle,
            )[:, 0]
            got_dec = np.asarray(got[0] if case["chunk"] else got)
            errs["err_decode"] = parity.rel_err(got_dec[live], want[live])
        if case["chunk"]:
            want = parity.ragged_oracle(
                kw["q_chunk"], k_base, v_base, ct % DISTINCT, cs + 64,
                **oracle,
            )
            errs["err_chunk"] = parity.rel_err(got[1], want)
        return errs

    return jax.jit(step), args, layers, errors


def load_variants(specs, package=True):
    """[(name, ragged_paged_attention)]: the package's own (unless
    ``package`` is False), then a copy of ``ops/pallas/attention.py``
    for every ``name=path``."""
    from llm_consensus_tpu.ops.pallas.attention import ragged_paged_attention

    out = [("package", ragged_paged_attention)] if package else []
    for spec in specs:
        name, _, path = spec.partition("=")
        mod_spec = importlib.util.spec_from_file_location(
            f"ragged_variant_{re.sub(r'\W', '_', name)}", path
        )
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        out.append((name, mod.ragged_paged_attention))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--out", default="chiprun_out/time_ragged_attention.jsonl")
    ap.add_argument("--module", action="append", default=[],
                    metavar="NAME=PATH",
                    help="also time this copy of ops/pallas/attention.py")
    ap.add_argument("--only", default="",
                    help="a regex over a case's JSON: run the matches alone")
    ap.add_argument("--no-package", action="store_true",
                    help="time the --module copies alone")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: the default device is {dev.platform!r}", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    variants = load_variants(args.module, package=not args.no_package)
    rng = np.random.default_rng(0)
    model = pools = bases = fargs = None
    with open(args.out, "a") as fh:
        for case in cases():
            if args.only and not re.search(args.only, json.dumps(case)):
                continue
            if case["model"] != model:
                pools = fargs = None  # free the last model's before the next
                model = case["model"]
                pools, bases = make_pools(model, rng)
            for name, ragged in variants:
                fn, fargs, layers, errors = build(
                    case, pools, bases, np.random.default_rng(1), ragged
                )
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
                    variant=name,
                    device=dev.device_kind,
                    layers=layers,
                    us_per_layer_call=statistics.median(times) / layers * 1e6,
                    us_min=min(times) / layers * 1e6,
                    **errors(),
                )
                text = json.dumps(line)
                print(text, flush=True)
                fh.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
