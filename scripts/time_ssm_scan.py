#!/usr/bin/env python3
"""Time the fused step of a recurrent model on the chip, its state-space
scan as the ``ssm_scan`` kernel against XLA's gather / einsum / scatter.

ISSUE 32's Step 0: ``fused_step_paged`` at the cell's real shapes (the
plan's first 18 layers of nemotron-3-nano-30b-a3b, int8 weights born on
the device, 16 slots, 1,024 pages, 96 state slots), half the slots
decoding at a panel's fills, L lanes of 64 prompt tokens mid-prompt,
each row on its own state slot. The scan's other form is put in place
of ``transformer._ssm_scan_rows`` for the comparison: the program ships
one form, and no switch.

    chiprun -- python scripts/time_ssm_scan.py

One JSON line a case on stdout and in ``chiprun_out/time_ssm_scan.jsonl``.
Never a CPU number: it refuses to run without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import replace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

PG, CHUNK, PAGES_PER_SEQ = 64, 64, 48
DECODE_PAGES, LANE_PAGES = 36, 19
MODEL, LAYERS, SLOTS, N_PAGES, STATE_SLOTS = (
    "nemotron-3-nano-30b-a3b", 18, 16, 1024, 96,
)


def scan_by_xla(cfg, terms, s_pool, layer, slot_in, slot_out):
    from llm_consensus_tpu.ops import ssm

    y, s1 = ssm.ssd_apply(terms, s_pool[layer, slot_in])
    return y, s_pool.at[layer, slot_out].set(s1)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", default="1,3")
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: this script times the chip", file=sys.stderr)
        return 2
    from llm_consensus_tpu.models import transformer as T
    from llm_consensus_tpu.models.configs import PRESETS
    from llm_consensus_tpu.models.paged_cache import PagedKVCache
    from llm_consensus_tpu.ops.kernels import resolve_kernels

    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/time_ssm_scan.jsonl", "a")

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    cfg = resolve_kernels(PRESETS[MODEL].with_layers(LAYERS))
    t0 = time.perf_counter()
    params = T.init_params_quantized(cfg, jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    emit(dict(weights_s=round(time.perf_counter() - t0, 1)))
    cache = PagedKVCache.create(
        cfg, N_PAGES, PG, SLOTS, PAGES_PER_SEQ, state_slots=STATE_SLOTS
    )
    table = np.zeros((SLOTS, PAGES_PER_SEQ), np.int32)
    length = np.zeros((SLOTS,), np.int32)
    slot = np.zeros((SLOTS,), np.int32)
    page = 1
    for row in range(SLOTS // 2):
        table[row, :DECODE_PAGES] = np.arange(page, page + DECODE_PAGES)
        length[row] = DECODE_PAGES * PG - 17
        slot[row] = 1 + row
        page += DECODE_PAGES
    cache = replace(
        cache, page_table=jnp.asarray(table), length=jnp.asarray(length),
        state=replace(cache.state, slot=jnp.asarray(slot)),
    )
    kernel_scan = T._ssm_scan_rows
    for lanes in (int(x) for x in args.lanes.split(",")):
        rng = np.random.default_rng(0)
        tokens_l = rng.integers(1, 259, (lanes, CHUNK)).astype(np.int32)
        tab = np.zeros((lanes, PAGES_PER_SEQ), np.int32)
        start = np.full((lanes,), LANE_PAGES * PG, np.int32)
        state = np.zeros((lanes, 4), np.int32)
        p = page
        for lane in range(lanes):
            tab[lane, : LANE_PAGES + 1] = np.arange(p, p + LANE_PAGES + 1)
            p += LANE_PAGES + 1
            own = 20 + lane
            state[lane] = (own, own, 40 + lane if lane == 0 else 0, CHUNK)
        lane_args = tuple(jnp.asarray(a) for a in (tokens_l, tab, start))
        for form, scan in (("kernel", kernel_scan), ("xla", scan_by_xla)):
            T._ssm_scan_rows = scan

            def fused_step(params, cache, tokens, ct, ctab, cstart, cstate):
                logits, hidden, cache, *_ = T.fused_step_paged(
                    cfg, params, tokens[:, None], cache, ct, ctab, cstart,
                    chunk_state=cstate,
                )
                return jnp.argmax(logits, -1).astype(jnp.int32), hidden, cache

            fn = jax.jit(fused_step, donate_argnums=(1,))
            toks = jnp.ones((SLOTS,), jnp.int32)
            t0 = time.perf_counter()
            toks, _, cache = fn(params, cache, toks, *lane_args, jnp.asarray(state))
            jax.block_until_ready(toks)
            build_s = time.perf_counter() - t0
            times = []
            for _ in range(3):
                cache = replace(cache, length=jnp.asarray(length))
                jax.block_until_ready(cache.length)
                t0 = time.perf_counter()
                for _ in range(args.reps):
                    toks, _, cache = fn(
                        params, cache, toks, *lane_args, jnp.asarray(state)
                    )
                jax.block_until_ready(toks)
                times.append((time.perf_counter() - t0) / args.reps * 1e3)
            emit(dict(
                model=MODEL, scan=form, lanes=lanes, decoding=SLOTS // 2,
                token_axis=SLOTS + lanes * CHUNK, ms=round(min(times), 3),
                ms_all=[round(t, 3) for t in times], build_s=round(build_s, 1),
            ))
    T._ssm_scan_rows = kernel_scan
    stats = jax.local_devices()[0].memory_stats() or {}
    emit(dict(memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
