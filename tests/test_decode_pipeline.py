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

import functools
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


# ---------------------------------------------------------------------------
# PR 36: first tokens sampled in the step programs, row installs and
# releases as one program a fetch — the loop's thread makes no
# op-by-op jax call
# ---------------------------------------------------------------------------

_PROBE = "the probe's prompt!"  # BOS + 19 bytes: two 16-token chunks


def _quiesce(batcher, timeout=30.0):
    """Stats once the loop has nothing left in flight."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        st = batcher.stats()
        if not (st["active_slots"] or st["prefilling_slots"]
                or st["dispatch_inflight"] or st["waiting"]):
            return st
        time.sleep(0.01)
    return batcher.stats()


def _record_first_tokens(batcher) -> dict:
    """seed -> the first token each request was activated with."""
    firsts, activate = {}, batcher._activate

    def recording(idx, slot, first):
        firsts[slot.request.seed] = first
        return activate(idx, slot, first)

    batcher._activate = recording
    return firsts


@pytest.fixture(scope="module")
def probe(params):
    """A batcher that shares no pages (the same prompt is the same
    computation every time), the float32 logits of ``_PROBE``'s last
    position as a greedy ``logits=1`` request returns them, and the
    first tokens its requests were activated with, by seed."""
    b = ContinuousBatcher(
        CFG, params,
        config=ContinuousConfig(**dict(_CCFG, share_prefix=False)),
    )
    firsts = _record_first_tokens(b)
    try:
        row = b.submit(_PROBE, max_new_tokens=1, logits=1).result(
            timeout=120
        ).logits[0]
        yield b, row, firsts
    finally:
        b.close()


@pytest.mark.parametrize("top_k, top_p", [(0, 1.0), (5, 0.9)])
@pytest.mark.parametrize("temperature", [0.0, 0.7])
@pytest.mark.parametrize("seed", [3, 2147480011])
def test_first_token_is_the_samplers_own_draw(
    probe, seed, temperature, top_k, top_p
):
    """The first token a served request returns is
    ``sample_token_per_request`` on the prompt's last-position logits
    with the ``(seed, 0)`` key — whether its last chunk ran alone or
    rode a decoding companion's step."""
    from llm_consensus_tpu.engine.sampler import sample_token_per_request

    b, row, firsts = probe
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    want, _ = sample_token_per_request(
        jnp.asarray(row)[None], key[None],
        jnp.asarray([temperature], jnp.float32),
        jnp.asarray([top_k], jnp.int32), jnp.asarray([top_p], jnp.float32),
    )
    kw = dict(temperature=temperature, top_k=top_k, top_p=top_p,
              max_new_tokens=2)
    for beside in (False, True):
        firsts.clear()
        futs = []
        if beside:
            futs.append(b.submit("a companion that keeps decoding", seed=1,
                                 max_new_tokens=8))
            deadline = time.monotonic() + 60
            while not b.stats()["active_slots"]:
                assert time.monotonic() < deadline
                time.sleep(0.002)
        futs.append(b.submit(_PROBE, seed=seed, **kw))
        for f in futs:
            f.result(timeout=120)
        assert firsts[seed] == int(want[0]), (beside, firsts)
        _quiesce(b)


def test_three_lanes_that_end_together_activate_three_rows(params):
    """Three prompts whose last chunks ride ONE fused program beside a
    decoding row: each row is activated with the first token it gets
    when served alone, the lane whose request is one token long retires
    at once, and once the burst is served every row of the device's
    tables is released and every page is back."""
    cfgkw = dict(_CCFG, share_prefix=False, max_new_tokens=6)
    lanes = [("lane one, short", 5), ("lane two, short", 6),
             ("lane 3, shorter", 7)]
    kw = {5: dict(temperature=0.0), 6: dict(temperature=0.7),
          7: dict(temperature=0.7, top_k=4, max_new_tokens=1)}
    alone = ContinuousBatcher(CFG, params, config=ContinuousConfig(**cfgkw))
    want = _record_first_tokens(alone)
    try:
        texts = [alone.submit(p, seed=s, **kw[s]).result(timeout=120).text
                 for p, s in lanes]
    finally:
        alone.close()
    b = ContinuousBatcher(CFG, params, config=ContinuousConfig(**cfgkw))
    got = _record_first_tokens(b)
    free0 = b._pools[0].available
    try:
        for _ in range(20):
            got.clear()
            long = b.submit("a companion that keeps decoding", seed=1,
                            max_new_tokens=48)
            deadline = time.monotonic() + 60
            while not b.stats()["active_slots"]:
                assert time.monotonic() < deadline
                time.sleep(0.002)
            # Wedge admission for a moment so the three arrive in one.
            b._admit = lambda: time.sleep(0.05)
            futs = [b.submit(p, seed=s, **kw[s]) for p, s in lanes]
            del b._admit
            outs = [f.result(timeout=120) for f in futs]
            long.result(timeout=120)
            st = _quiesce(b)
            assert [o.text for o in outs] == texts
            assert {s: got[s] for _, s in lanes} == want
            assert outs[2].num_tokens == 1
            if st.get("chunk_lanes_fused_3", 0):
                break
        assert st.get("chunk_lanes_fused_3", 0) >= 1, st
        assert b._pools[0].available == free0
        assert not np.asarray(b.cache.length).any()
        assert not np.asarray(b.cache.page_table).any()  # NULL_PAGE rows
    finally:
        b.close()


def _traced_only(fn):
    """``fn``, refusing a call whose arguments hold no tracer: one made
    op by op, outside a jitted program."""
    @functools.wraps(fn)
    def guarded(*args, **kwargs):
        leaves = jax.tree_util.tree_leaves((args, kwargs))
        if not any(isinstance(x, jax.core.Tracer) for x in leaves):
            raise AssertionError(f"{fn.__name__} called op by op")
        return fn(*args, **kwargs)

    return guarded


_BURST = [_HEADER + f"p{i} answers" for i in range(4)] + [
    "an unshared prompt that arrives with them",
    "and one more, long enough for three chunks",
]
_BURST_CAPS = [3, 12, 6, 12, 9, 5]  # staggered: slots free beside rows


def _serve_burst(params, depth, **cfgkw):
    """A panel-shaped burst: six prompts over four slots, so prompts end
    (and requests retire) beside decoding rows; half sampled."""
    b = ContinuousBatcher(
        CFG, params,
        config=ContinuousConfig(
            **dict(_CCFG, max_new_tokens=12, **cfgkw), pipeline_depth=depth
        ),
    )
    try:
        futs = [
            b.submit(p, seed=i, temperature=0.7 * (i % 2),
                     top_k=3 * (i % 3 == 0), max_new_tokens=cap)
            for i, (p, cap) in enumerate(zip(_BURST, _BURST_CAPS))
        ]
        outs = [(f.result(timeout=120).text, f.result().num_tokens)
                for f in futs]
        return outs, _quiesce(b)
    finally:
        b.close()


def test_the_loop_makes_no_op_by_op_jax_call(params, monkeypatch):
    """With the sampler, ``install_seq`` and ``release_seq`` refusing any
    call from outside a trace, a panel-shaped burst at depth 2 is served
    whole, with the text the serialized loop gives without the guard:
    first tokens come out of the step programs and row changes go
    through the one patch program."""
    from llm_consensus_tpu.serving import continuous

    want, _ = _serve_burst(params, 1)
    for name in ("sample_token_per_request", "install_seq", "release_seq"):
        monkeypatch.setattr(
            continuous, name, _traced_only(getattr(continuous, name))
        )
    got, st = _serve_burst(params, 2)
    assert got == want
    assert st["device_programs_fused"] >= 1  # prompts ended beside rows
    assert not hasattr(ContinuousBatcher, "_sample_first")
    assert not hasattr(ContinuousBatcher, "_jit_unembed")


@pytest.mark.parametrize("depth", [2, 1])
def test_pipeline_drains_counted_by_cause(params, depth):
    """gateway_pipeline_drains_total{after} and its stats() mirror move
    together; over a burst whose prompts end beside decoding rows no
    program at depth 2 waits for a first token — at depth 1 every
    dispatch finds the device empty, and those after a fetch that ended
    a prompt say so."""
    from llm_consensus_tpu.server.metrics import PIPELINE_DRAINS

    labels = ("first_token", "standalone_chunk", "flush", "other")
    before = {a: PIPELINE_DRAINS.labels(after=a).value for a in labels}
    _, st = _serve_burst(params, depth)
    moved = {
        a: PIPELINE_DRAINS.labels(after=a).value - before[a] for a in labels
    }
    assert moved == {a: st[f"pipeline_drains_{a}"] for a in labels}
    assert st["device_programs_fused"] >= 1
    if depth == 2:
        assert moved["first_token"] == 0, moved
    else:
        assert moved["first_token"] >= 1 and moved["other"] >= 1, moved
