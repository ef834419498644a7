"""Pipelined decode dispatch (PR 6).

The continuous batcher's host loop is a software pipeline: program n+1
is enqueued before program n's tokens are fetched, fed from the
device-resident token output of the previous dispatch. These tests pin
the acceptance contract — ``pipeline_depth=2`` (the default) serves
byte-identical text to the serialized ``pipeline_depth=1`` baseline
across the hard shapes (multi-token string stops mid-window, staggered
retirement shrinking a decode group, eviction + host-tier restore with
programs in flight, concurrent same-prefix bursts), the PRNG stream is
window- and depth-invariant, the flush/inflight metrics stay in lockstep
with ``stats()``, and a wedged in-flight fetch still goes stale on the
liveness heartbeat.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.transformer import init_params
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)

CFG = get_config("test-tiny")

_HEADER = "Panel shared header for every persona, forty ch: "  # 49 chars
_CCFG = dict(
    max_slots=4,
    page_size=16,
    n_pages=64,
    pages_per_seq=8,
    max_new_tokens=8,
    seq_buckets=(16, 32, 64),
    prefill_chunk=16,
    share_prefix=True,
)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def _serve(batcher, prompts, **kw):
    futs = [batcher.submit(p, **kw) for p in prompts]
    return [f.result(timeout=120) for f in futs]


def _run_depth(params, depth, prompts, cfgkw=None, submit_kw=None, cfg=CFG):
    b = ContinuousBatcher(
        cfg,
        params,
        config=ContinuousConfig(**(cfgkw or _CCFG), pipeline_depth=depth),
    )
    try:
        outs = _serve(b, prompts, **(submit_kw or {}))
        return outs, b.stats()
    finally:
        b.close()


# ---------------------------------------------------------------------------
# Parity: the hard retirement shapes, depth 2 vs the serialized baseline
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rounds", [1, 2, 4])
def test_string_stop_mid_chunk_parity(params, rounds):
    """Multi-token string stop landing mid-window: retirement lags one
    pipeline stage AND up to decode_rounds-1 rounds — the post-stop
    tokens decoded in flight must be discarded with the exact depth-1
    stop-trim semantics (text cut at the stop, honest num_tokens)."""
    cfgkw = dict(_CCFG, decode_rounds=rounds, max_new_tokens=16)
    prompts = [_HEADER + "stop probe"]
    # Derive a stop the tiny random model actually emits: a 2-char
    # substring from the middle of the baseline's output (random
    # weights make a fixed stop string unhittable).
    [free], _ = _run_depth(params, 1, prompts, cfgkw)
    assert len(free.text) >= 4
    mid = len(free.text) // 2
    stop = free.text[mid : mid + 2]
    kw = dict(stop=[stop])
    [want], _ = _run_depth(params, 1, prompts, cfgkw, kw)
    [got], _ = _run_depth(params, 2, prompts, cfgkw, kw)
    assert stop not in want.text  # the baseline really trimmed
    assert len(want.text) < len(free.text)
    assert (got.text, got.num_tokens) == (want.text, want.num_tokens)


def test_staggered_retirement_shrinks_group_parity(params):
    """Same-prefix panel whose members retire at different steps (the
    decode group shrinks while programs are in flight): every text and
    token count identical to the serialized loop."""
    prompts = [_HEADER + f"persona {i} answers" for i in range(4)]
    caps = [2, 9, 5, 13]

    def run(depth):
        b = ContinuousBatcher(
            CFG,
            params,
            config=ContinuousConfig(
                **dict(_CCFG, max_new_tokens=16),
                pipeline_depth=depth,
            ),
        )
        try:
            futs = [
                b.submit(p, max_new_tokens=c) for p, c in zip(prompts, caps)
            ]
            return [(f.result(timeout=120).text,
                     f.result(timeout=120).num_tokens) for f in futs]
        finally:
            b.close()

    assert run(2) == run(1)


def test_concurrent_same_prefix_burst_parity(params):
    """The panel shape submitted all at once: admissions dedup against
    the first request's in-flight prefill WHILE decode programs are in
    flight — text and sharing counters identical to depth 1."""
    prompts = [_HEADER + f"Q{i}: what is {i}+{i}?" for i in range(6)]
    want, st1 = _run_depth(params, 1, prompts)
    got, st2 = _run_depth(params, 2, prompts)
    assert [r.text for r in got] == [r.text for r in want]
    assert st2["prefix_pages_shared"] == st1["prefix_pages_shared"]
    assert st2["prefix_hits"] == st1["prefix_hits"]
    # All pages come home afterwards at either depth.
    assert st2["free_pages"] == st2["total_pages"]


def test_eviction_and_host_restore_during_flight_parity(params):
    """PR 4's hardest shape under the pipeline: a starved pool forces
    eviction (demote to host tier) while decode programs are in
    flight, and the re-vote round restores pages — restore flushes the
    pipeline (metered) and the text stays byte-identical to depth 1."""
    from llm_consensus_tpu.server.metrics import PIPELINE_FLUSHES

    kw = dict(
        max_slots=2,
        page_size=16,
        n_pages=13,  # 12 usable vs a 2x6-page unshared working set
        pages_per_seq=8,
        max_new_tokens=6,
        seq_buckets=(16, 32, 64),
        prefill_chunk=16,
        share_prefix=True,
        host_cache_bytes=8 << 20,
    )
    rounds = [
        [_HEADER + f"p{i} proposes" for i in range(2)],
        [f"{i} unique filler storm with plenty of padding text {i}"
         for i in range(4)],
        [_HEADER + f"r{i} re-votes" for i in range(2)],
    ]

    def run(depth):
        b = ContinuousBatcher(
            CFG, params,
            config=ContinuousConfig(**kw, pipeline_depth=depth),
        )
        try:
            texts = []
            for burst in rounds:
                texts.append([r.text for r in _serve(b, burst)])
            return texts, b.stats()
        finally:
            b.close()

    want, st1 = run(1)
    before = PIPELINE_FLUSHES.value
    got, st2 = run(2)
    assert got == want
    assert st2["offload_restored_pages"] >= 1  # the tier really engaged
    assert st2["offload_restored_pages"] == st1["offload_restored_pages"]
    # Restores are stable-cache operations: each drained the pipeline
    # when programs were in flight, and the Prometheus family moved by
    # exactly the batcher's own count (lockstep).
    assert PIPELINE_FLUSHES.value - before == st2["pipeline_flushes"]


# ---------------------------------------------------------------------------
# PRNG stream: window x depth invariance (greedy AND sampled)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rounds, depths", [(1, (2, 3)), (4, (1, 2))])
def test_prng_stream_chunk_and_depth_invariant(params, rounds, depths):
    """The per-token PRNG stream is (seed, index) — independent of how
    many rounds ride one program (decode_rounds) AND how many programs
    ride in flight (pipeline_depth)."""

    def run(rounds, depth):
        b = ContinuousBatcher(
            CFG,
            params,
            config=ContinuousConfig(
                **dict(_CCFG, decode_rounds=rounds),
                pipeline_depth=depth,
            ),
        )
        try:
            futs = [
                b.submit("hello world"),
                b.submit("the quick", temperature=0.9, seed=7),
                b.submit("abc", temperature=1.3, seed=11, top_k=4),
            ]
            return [f.result(timeout=120).text for f in futs]
        finally:
            b.close()

    want = run(1, 1)
    for depth in depths:
        assert run(rounds, depth) == want, depth


# ---------------------------------------------------------------------------
# Page-overshoot budget: exact-fit tables absorb depth*rounds-1 tokens
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rounds", [1, 4])
def test_overshoot_budget_tight_pages(params, rounds):
    """A config whose pages_per_seq is sized EXACTLY for the deepest
    overshoot (bucket + max_new + depth*rounds - 1): rows that finish
    inside a window keep decoding through the in-flight programs
    without escaping their reservation — completion, parity, and a
    clean pool prove the budget holds."""
    kw = dict(
        max_slots=2,
        page_size=16,
        n_pages=16,
        pages_per_seq=2,  # ceil((16 + 8 + 2*4 - 1) / 16) = 2 at R = 4
        max_new_tokens=8,
        seq_buckets=(16,),
        decode_rounds=rounds,
        prefill_chunk=16,
        share_prefix=False,
    )
    prompts = ["hi", "yo"]

    def run(depth):
        b = ContinuousBatcher(
            CFG, params, config=ContinuousConfig(**kw, pipeline_depth=depth)
        )
        try:
            outs = _serve(b, prompts, max_new_tokens=8)
            st = b.stats()
            return [r.text for r in outs], st
        finally:
            b.close()

    want, _ = run(1)
    got, st = run(2)
    assert got == want
    assert st["free_pages"] == st["total_pages"]


# ---------------------------------------------------------------------------
# Metrics: inflight gauge / flush counter surfaces
# ---------------------------------------------------------------------------


def test_pipeline_metrics_exported_and_lockstep(params):
    """gateway_dispatch_inflight and gateway_pipeline_flushes_total are
    declared on the process registry and mirrored in stats(); a CoW
    boundary copy flushes when its admission lands while programs are
    in flight."""
    from llm_consensus_tpu.server.metrics import (
        DISPATCH_INFLIGHT,
        PIPELINE_FLUSHES,
        REGISTRY,
    )

    before = PIPELINE_FLUSHES.value
    b = ContinuousBatcher(
        CFG,
        params,
        config=ContinuousConfig(
            **dict(_CCFG, max_new_tokens=128, pages_per_seq=13),
            pipeline_depth=2,
        ),
    )
    # Common run = BOS + 40 bytes = 41 ids: 2 full pages + a 9-token
    # run into page 3, which the second admission COPIES.
    common = "Forty common characters of shared text."
    try:
        first = b.submit(common + " runs long", max_new_tokens=128)
        # Wait until the first request is decoding with a program in
        # flight, then admit a second: its boundary copy MUST flush.
        deadline = time.time() + 60
        while b.stats()["decode_steps"] < 2 and time.time() < deadline:
            time.sleep(0.01)
        second = b.submit(common + " arrives late", max_new_tokens=4)
        second.result(timeout=120)
        first.result(timeout=120)
        # Futures resolve DURING fetch bookkeeping; the loop drains the
        # remaining in-flight program(s) on its next ticks.
        deadline = time.time() + 30
        while b.stats()["dispatch_inflight"] and time.time() < deadline:
            time.sleep(0.01)
        st = b.stats()
    finally:
        b.close()
    assert st["prefix_pages_copied"] == 1
    assert st["pipeline_flushes"] >= 1
    assert PIPELINE_FLUSHES.value - before == st["pipeline_flushes"]
    assert st["dispatch_inflight"] == 0  # drained at rest
    text = REGISTRY.render()
    assert "gateway_pipeline_flushes_total" in text
    assert "gateway_dispatch_inflight" in text


def test_sched_overhead_observes_overlapped_dispatches(params):
    """Depth 2 keeps the overhead histogram count-comparable to depth
    1 — one observation per dispatch after the first — but overlapped
    dispatches observe ~0 (the un-overlapped-host-time semantics)."""
    from llm_consensus_tpu.server.metrics import SCHED_OVERHEAD_SECONDS

    h0 = (SCHED_OVERHEAD_SECONDS.count, SCHED_OVERHEAD_SECONDS.sum)
    s0 = None
    b = ContinuousBatcher(
        CFG, params, config=ContinuousConfig(**_CCFG, pipeline_depth=2)
    )
    try:
        s0 = b.stats()
        b.submit("overlap probe", max_new_tokens=8).result(timeout=120)
        st = b.stats()
    finally:
        b.close()
    d_cnt = st["sched_overhead_seconds_count"] - s0["sched_overhead_seconds_count"]
    assert d_cnt >= 1
    # stats() and the process histogram moved together.
    assert SCHED_OVERHEAD_SECONDS.count - h0[0] == d_cnt
    assert SCHED_OVERHEAD_SECONDS.sum - h0[1] == pytest.approx(
        st["sched_overhead_seconds_sum"] - s0["sched_overhead_seconds_sum"]
    )


# ---------------------------------------------------------------------------
# Liveness: a wedged in-flight fetch goes stale on the heartbeat
# ---------------------------------------------------------------------------


def test_wedged_inflight_fetch_flips_heartbeat(params):
    """The acceptance bullet: a wedged in-flight program (the fetch
    never returns) stalls the loop tick, which is exactly what the
    gateway's /readyz stall threshold watches."""
    b = ContinuousBatcher(
        CFG,
        params,
        config=ContinuousConfig(
            **dict(
                _CCFG, share_prefix=False,
                max_new_tokens=256, pages_per_seq=20,
            ),
            pipeline_depth=2,
        ),
    )
    try:
        fut = b.submit("wedge probe", max_new_tokens=256)
        deadline = time.time() + 60
        while b.stats()["decode_steps"] < 1 and time.time() < deadline:
            time.sleep(0.01)
        # Wedge: the instance attribute shadows the bound method, so
        # every fetch of an in-flight program now hangs 1.5 s.
        b._fetch_one = lambda: time.sleep(1.5)
        try:
            stale = False
            for _ in range(40):
                if b.heartbeat()["last_tick_age_s"] > 1.0:
                    stale = True
                    break
                time.sleep(0.1)
            assert stale, "wedged fetch never stalled the heartbeat"
        finally:
            del b._fetch_one
        # Recovery: the real fetch path drains and the request finishes.
        assert fut.result(timeout=120).num_tokens == 256
        assert b.heartbeat()["alive"] is True
    finally:
        b.close()
