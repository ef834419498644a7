#!/usr/bin/env python3
"""Time the fused step program ALONE on the chip at L chunk lanes.

ISSUE 31's Step 0: ``fused_step_paged`` jitted under the name the
batcher gives it, at each cell's real shapes (every layer served, int8
weights born on the device, the cell's slots and pool), with half the
slots decoding at a panel's fills and L lanes of 64 prompt tokens each,
mid-prompt. The host clock around ``block_until_ready`` over ``--reps``
calls gives ms a program; prompt tokens a ms is what a lane buys.

    chiprun -- python scripts/time_fused_step.py

One JSON line a case on stdout, all of them in
``chiprun_out/time_fused_step.jsonl``. Never a CPU number: it refuses
to run without a TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

PG = 64
CHUNK = 64
PAGES_PER_SEQ = 48
DECODE_PAGES = 36  # an evaluate row's fill
LANE_PAGES = 19  # a lane mid-prompt: what its chunk reads back

# name -> (layers served, slots, pool pages): benchmark/configs/*.json
CELLS = {
    "mistral-7b": (32, 8, 512),
    "qwen2-7b": (28, 16, 1024),
    "deepseek-v2-lite": (19, 16, 1024),
}


def build(model: str):
    from llm_consensus_tpu.models.configs import PRESETS
    from llm_consensus_tpu.models.paged_cache import PagedKVCache
    from llm_consensus_tpu.models.transformer import init_params_quantized
    from llm_consensus_tpu.ops.kernels import resolve_kernels

    layers, slots, n_pages = CELLS[model]
    cfg = PRESETS[model]
    if layers != cfg.n_layers:
        cfg = cfg.with_layers(layers)
    cfg = resolve_kernels(cfg)
    params = init_params_quantized(cfg, jax.random.PRNGKey(0))
    cache = PagedKVCache.create(cfg, n_pages, PG, slots, PAGES_PER_SEQ)
    return cfg, params, cache, slots


def rows_state(cache, slots: int):
    """Half the slots decode at 36 pages; the others are mid-prefill,
    so the device sees their rows empty. Returns (cache, next page)."""
    table = np.zeros((slots, PAGES_PER_SEQ), np.int32)
    length = np.zeros((slots,), np.int32)
    page = 1
    for row in range(slots // 2):
        table[row, :DECODE_PAGES] = np.arange(page, page + DECODE_PAGES)
        length[row] = DECODE_PAGES * PG - 17
        page += DECODE_PAGES
    return (
        type(cache)(
            k=cache.k, v=cache.v, page_table=jnp.asarray(table),
            length=jnp.asarray(length),
        ),
        page,
    )


def lane_args(lanes: int, live: int, page: int, vocab: int):
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, min(vocab, 30000), (lanes, CHUNK)).astype(np.int32)
    table = np.zeros((lanes, PAGES_PER_SEQ), np.int32)
    start = np.zeros((lanes,), np.int32)
    for lane in range(live):
        table[lane, : LANE_PAGES + 1] = np.arange(page, page + LANE_PAGES + 1)
        start[lane] = LANE_PAGES * PG
        page += LANE_PAGES + 1
    return jnp.asarray(tokens), jnp.asarray(table), jnp.asarray(start)


def time_case(model, cfg, params, cache, slots, lanes, live, reps, tag=""):
    from llm_consensus_tpu.models.transformer import fused_step_paged

    def fused_step(params, cache, tokens, chunk_tokens, chunk_table, chunk_start):
        logits, hidden, cache, *_ = fused_step_paged(
            cfg, params, tokens[:, None], cache, chunk_tokens, chunk_table,
            chunk_start,
        )
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), hidden, cache

    fn = jax.jit(fused_step, donate_argnums=(1,))
    cache, page = rows_state(cache, slots)
    if page + lanes * (LANE_PAGES + 1) > cache.n_pages:
        raise SystemExit("pool too small for the case")
    args = lane_args(lanes, live, page, cfg.vocab_size)
    tokens = jnp.ones((slots,), jnp.int32)
    length0 = np.asarray(cache.length)  # the call donates the cache
    t0 = time.perf_counter()
    tokens, _, cache = fn(params, cache, tokens, *args)
    jax.block_until_ready(tokens)
    build_s = time.perf_counter() - t0
    times = []
    for _ in range(3):
        # Rows advance a token a call: put the fills back, so every
        # repetition times the same work.
        cache = type(cache)(
            k=cache.k, v=cache.v, page_table=cache.page_table,
            length=jnp.asarray(length0),
        )
        jax.block_until_ready(cache.length)
        t0 = time.perf_counter()
        for _ in range(reps):
            tokens, _, cache = fn(params, cache, tokens, *args)
        jax.block_until_ready(tokens)
        times.append((time.perf_counter() - t0) / reps * 1e3)
    ms = min(times)
    out = dict(
        model=model, tag=tag, rows=slots, decoding=slots // 2, lanes=lanes,
        live=live, token_axis=slots + lanes * CHUNK, ms=round(ms, 3),
        ms_all=[round(t, 3) for t in times],
        prefill_tokens_per_ms=round(live * CHUNK / ms, 2),
        build_s=round(build_s, 1),
    )
    return out, cache


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default=",".join(CELLS))
    ap.add_argument("--lanes", default="1,2,3,4")
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: this script times the chip", file=sys.stderr)
        return 2
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/time_fused_step.jsonl", "a")

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    for model in args.models.split(","):
        t0 = time.perf_counter()
        cfg, params, cache, slots = build(model)
        jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])
        emit(dict(model=model, weights_s=round(time.perf_counter() - t0, 1)))
        widths = [int(x) for x in args.lanes.split(",")]
        cases = [(n, n, "") for n in widths]
        # what dead lanes cost the wide program the rule would build
        cases += [(3, n, "dead-lanes") for n in (1, 2) if 3 in widths]
        for lanes, live, tag in cases:
            rec, cache = time_case(
                model, cfg, params, cache, slots, lanes, live, args.reps, tag
            )
            emit(rec)
        del params, cache
        jax.clear_caches()
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
