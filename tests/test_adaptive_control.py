"""Roofline-adaptive runtime control (PR 15, serving/control.py).

Contract layers:

- CONTROLLER UNITS: EWMA/decision arithmetic in isolation — per-group
  acceptance shrink/regrow over the {1, spec_k} menu, the disengage +
  probe state machine, the two-arm rounds regime (stretch-level
  measured rates, compile-sample discard, probe backoff), chunk/depth
  steering bounds, restore-pacing debt, and ``--hbm-gbps auto``
  resolution.
- BATCHER E2E: with a controller attached, text stays BYTE-IDENTICAL
  to every fixed knob setting (the spec accept rule, multi-round
  early-exit masking, and depth/chunk invariance are pre-existing
  contracts the controller rides); an adversarial draft records a
  spec_k shrink and disengage, a self-draft probe regrows; the
  compiled-program families stay bounded across a steering burst
  (no-recompile guarantee).
- ADMISSION: cost-budget mode bounds queues in MODELED BYTES — the
  same unit the router's load_cost compares — so one 32k-context
  request sheds where N small ones fit, and the overflow hard cap is
  bytes too (the unit-normalization fix).
- SURFACES: gateway_autotune_value/_decisions_total, the stats()
  autotune_* mirrors, and ``autotune`` flight events move in lockstep
  from one decision site.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.transformer import init_params
from llm_consensus_tpu.serving import flight as _flight
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)
from llm_consensus_tpu.serving.control import (
    AdaptiveController,
    ControlConfig,
    resolve_hbm_gbps,
)

CFG = get_config("test-tiny")

_CCFG = dict(
    max_slots=4,
    page_size=16,
    n_pages=96,
    pages_per_seq=12,
    max_new_tokens=10,
    seq_buckets=(16, 32, 64),
    prefill_chunk=16,
    share_prefix=True,
)

_HEADER = "Panel shared header for every persona, forty ch: "


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


@pytest.fixture(scope="module")
def adv_dparams():
    # Random draft weights from another seed: proposes garbage,
    # accepts ~nothing — the adversarial draft spec_k auto-tune
    # exists for.
    return init_params(CFG, jax.random.PRNGKey(1), dtype=jnp.float32)


def _serve(batcher, prompts, **kw):
    futs = [batcher.submit(p, **kw) for p in prompts]
    return [f.result(timeout=180) for f in futs]


def _quiesce(batcher, timeout=30.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        s = batcher.stats()
        if (
            s["active_slots"] == 0
            and s["prefilling_slots"] == 0
            and s["dispatch_inflight"] == 0
            and s["waiting"] == 0
        ):
            return
        time.sleep(0.01)
    raise RuntimeError(f"no quiesce: {batcher.stats()}")


# ---------------------------------------------------------------------------
# Controller units
# ---------------------------------------------------------------------------


def test_spec_k_shrink_and_regrow_units():
    """Per-group acceptance EWMAs drive the {1, k_max} menu: unknown
    groups get the full window (optimistic start), a rejecting group
    shrinks to 1 after min_samples, recovery past accept_low regrows,
    and ONE high-acceptance group keeps the whole dispatch at full k
    (the program-wide k helps whoever has something to gain)."""
    c = AdaptiveController(
        ControlConfig(accept_min_samples=3, ewma_alpha=0.5)
    )
    assert c.spec_k_for([7], 4) == 4  # no samples yet
    for _ in range(4):
        c.note_spec_round([(7, 0, 4)])
    assert c.group_acceptance(7) == pytest.approx(0.0)
    assert c.spec_k_for([7], 4) == 1
    # A second, accepting group keeps the dispatch at full width.
    for _ in range(4):
        c.note_spec_round([(9, 4, 4)])
    assert c.spec_k_for([7, 9], 4) == 4
    # The rejecting group alone recovers past accept_low -> regrow.
    for _ in range(4):
        c.note_spec_round([(7, 4, 4)])
    assert c.spec_k_for([7], 4) == 4


def test_spec_disengage_and_probe_state_machine():
    """Every group rejecting (EWMA < disengage floor) flips the gate
    off with a spec_k=0 decision; plain windows advance the probe
    clock; the armed probe re-engages at the k=1 floor, and a fully
    accepted probe window re-engages for real."""
    c = AdaptiveController(
        ControlConfig(
            accept_min_samples=2, spec_probe_every=5, ewma_alpha=0.5
        )
    )
    for _ in range(3):
        c.note_spec_round([(1, 0, 4), (2, 0, 4)])
    assert c.spec_gate([1, 2]) is False  # disengage decision
    assert c.stats()["autotune_spec_k"] == 0
    assert c.stats()["autotune_spec_engaged"] == 0
    for _ in range(4):
        c.note_plain_window()
        assert c.spec_gate([1, 2]) is False
    c.note_plain_window()  # 5th plain window arms the probe
    assert c.spec_gate([1, 2]) is True
    assert c.spec_k_for([1, 2], 4) == 1  # probes run at the floor
    c.note_spec_round([(1, 1, 1)])  # fully accepted probe window
    assert c.stats()["autotune_spec_engaged"] == 1
    assert c.spec_gate([1, 2]) is True
    # A probe that runs OUT still rejecting restores the knob's
    # disengaged reading (the probe windows recorded spec_k=1; the
    # gauge contract says 0 = disengaged).
    for _ in range(6):
        c.note_spec_round([(1, 0, 4), (2, 0, 4)])
    assert c.spec_gate([1, 2]) is False  # re-disengaged
    for _ in range(5):
        c.note_plain_window()
    assert c.spec_gate([1, 2]) is True  # probe armed again
    assert c.spec_k_for([1, 2], 4) == 1
    for _ in range(4):
        c.note_spec_round([(1, 0, 1)])  # every probe window rejects
    assert c.stats()["autotune_spec_engaged"] == 0
    assert c.stats()["autotune_spec_k"] == 0  # not left at the probe 1


def test_rounds_regime_measured_rates_and_near_stop():
    """The two-arm rounds decision: near-stop always forces 1; the
    first window of an arm (its jit compile) never enters a rate;
    stretch-level measured throughput flips the regime to whichever
    arm actually serves faster; a losing probe backs off."""
    c = AdaptiveController(
        ControlConfig(
            rounds_stretch_windows=3,
            rounds_stretch_min=3,
            rounds_stretch_gap_s=10.0,
            rounds_probe_stretches=2,
            ewma_alpha=0.2,
        )
    )
    clock = [0.0]

    def feed(arm, tokens, step):
        clock[0] += step
        c.note_rounds_window(arm, tokens, now=clock[0])

    assert c.rounds_cap(2, 4) == 1  # near-stop, no data needed
    assert c.rounds_cap(100, 4) == 4  # cold start: configured intent
    # Arm 4: first window discarded (its jit compile), then an
    # anchor + a 3-window stretch at 4 tokens / 0.04 s = 100 tok/s.
    feed(4, 999, 60.0)  # compile window, discarded
    feed(4, 0, 0.04)  # stretch anchor
    for _ in range(3):
        feed(4, 4, 0.04)
    # Stretch folded -> calibration switches the regime to arm 1.
    assert c._arm_rate(4) == pytest.approx(100.0)
    assert c.rounds_cap(100, 4) == 1
    feed(1, 999, 60.0)  # arm 1 compile, discarded + re-anchor
    for _ in range(4):
        feed(1, 4, 0.01)  # anchor + 3 windows at 400 tok/s
    # Both arms measured; arm 1 wins.
    assert c._arm_rate(1) == pytest.approx(400.0)
    assert c.rounds_cap(100, 4) == 1
    # Probe cadence: after rounds_probe_stretches more arm-1
    # stretches the regime probes arm 4 once...
    for _ in range(3):
        feed(1, 4, 0.01)
    assert c._regime_arm == 4 and c._rounds_probing
    assert c.rounds_cap(100, 4) == 4
    # ... which measures slow again -> snaps back + backs off.
    for _ in range(3):
        feed(4, 4, 0.04)
    assert c._regime_arm == 1
    assert c._rounds_probe_backoff == 2  # lost probe -> backoff
    # An idle gap folds the partial stretch (>= rounds_stretch_min)
    # without counting the idle: two windows, then a gap, then one —
    # the 2-window partial is below min and is discarded.
    tok0 = dict(c._rate_tok)
    feed(1, 4, 0.01)
    feed(1, 4, 0.01)
    feed(1, 4, 100.0)  # gap: partial (2 < min 3) discarded
    assert c._rate_tok == tok0
    # A chunk/depth decision mid-stretch poisons it: the fold
    # DISCARDS the stretch (its windows measured the transition —
    # and the steered width's jit — not the arm) and the arms'
    # rates stand. The next clean stretch folds normally.
    feed(1, 4, 0.01)
    c.note_overhead(1.0)
    assert c.depth_for(2) == 2  # first depth decision -> a change
    feed(1, 4, 0.01)
    feed(1, 4, 0.01)
    feed(1, 4, 0.01)  # 3 windows: folds, but dirty -> discarded
    assert c._rate_tok == tok0
    for _ in range(3):
        feed(1, 4, 0.01)  # clean 3-window stretch folds again
    assert c._rate_tok != tok0


def test_chunk_and_depth_steering_units():
    """Chunk: full width while overhead is visible, unknown, or the
    peak is unresolved; half (when it divides the bucket) only once
    the host loop is hidden AND the measured lane MBU reads
    bandwidth-starved — halving is an MBU-driven decision, with
    hysteresis back to full when overhead re-appears. Depth: visible
    overhead pins the configured depth, a hidden one probes lower
    and commits when it stays hidden."""
    c = AdaptiveController(
        ControlConfig(
            overhead_high_s=0.002,
            overhead_low_s=0.0005,
            depth_probe_every=3,
            depth_probe_len=2,
            ewma_alpha=1.0,
        )
    )
    assert c.chunk_for(64, 16) == 16  # no overhead signal yet
    c.note_overhead(0.01)
    assert c.chunk_for(64, 16) == 16  # host-bound: full width
    assert c.depth_for(2) == 2
    c.note_overhead(0.0)
    # Hidden host but NO resolved peak: the configured width stands
    # (halving doubles the per-prompt program count on no evidence
    # that's free — the overhead signal can't price it).
    assert c.chunk_for(64, 16) == 16
    c.bind(hbm_gbps=1.0)
    starved = {
        "hbm_bytes": int(4e8),
        "kv_read_tokens": 0,
        "kv_write_tokens": 0,
    }
    c.note_program("decode", starved, 1.0)  # MBU 0.4: starved lane
    assert c.chunk_for(64, 16) == 8  # hidden + starved: halve
    assert c.chunk_for(64, 15) == 15  # odd width: menu has no half
    assert c.chunk_for(10, 6) == 6  # half wouldn't divide bucket
    assert c.chunk_for(9, 6) == 3  # ... but divides this one
    # An efficient lane (MBU past the 0.6 hysteresis edge) restores
    # the full width even while the host stays hidden.
    c.note_program("decode", {**starved, "hbm_bytes": int(8e8)}, 1.0)
    assert c.chunk_for(64, 16) == 16
    c.note_program("decode", starved, 1.0)
    assert c.chunk_for(64, 16) == 8  # starved again: halve again
    # Depth probes lower after depth_probe_every hidden dispatches,
    # and commits once the probe survives depth_probe_len dispatches.
    seen = [c.depth_for(2) for _ in range(8)]
    assert 1 in seen  # probed
    assert c.depth_for(2) == 1  # committed
    # Overhead re-appearing reverts to the configured depth AND the
    # configured chunk width (the halving hysteresis's other exit).
    c.note_overhead(0.01)
    assert c.depth_for(2) == 2
    assert c.chunk_for(64, 16) == 16


def test_restore_pacing_debt():
    """The preempt hook's consult: demoted-not-restored modeled bytes
    must stay under restore_debt_frac x the host budget; restores
    repay the debt."""
    c = AdaptiveController(ControlConfig(restore_debt_frac=0.5))
    c.bind(host_budget_bytes=1000)
    assert c.restore_pacing_ok(4, 100)  # 400 <= 500
    c.note_preempt_demote(400)
    assert not c.restore_pacing_ok(2, 100)  # 400 + 200 > 500
    c.note_restore(300)
    assert c.restore_pacing_ok(2, 100)  # 100 + 200 <= 500
    # No host budget bound => pacing never blocks (controller-less
    # fleets keep the PR-14 behavior; so do budget-less controllers).
    c2 = AdaptiveController()
    assert c2.restore_pacing_ok(10_000, 10_000)


def test_hbm_gbps_auto_resolution(monkeypatch):
    """Numbers pass through; 'auto' reads the device kind's published
    peak from the table; a kind that is not in it — this box's CPU, for
    one — is an error, never a default or a silent 0."""
    assert resolve_hbm_gbps(3.5) == 3.5
    assert resolve_hbm_gbps("819") == 819.0
    with pytest.raises(ValueError, match="no published peak"):
        resolve_hbm_gbps("auto")  # device kind "cpu"

    class _V5e:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [_V5e()])
    assert resolve_hbm_gbps("auto") == 819.0
    c = AdaptiveController()
    c.bind(hbm_gbps=0.0)
    assert not c.mbu_driven


# ---------------------------------------------------------------------------
# Batcher e2e
# ---------------------------------------------------------------------------


def test_adversarial_shrink_disengage_and_byte_parity(
    params, adv_dparams
):
    """An adversarial draft under the controller: text byte-identical
    to the controller-less plain batcher (the accept rule + masking
    contracts), with a spec_k shrink/disengage decision recorded on
    every surface — flight events, the Prometheus counter, and the
    stats() mirrors — in lockstep."""
    from llm_consensus_tpu.server.metrics import REGISTRY

    prompts = [_HEADER + f"Q{i}" for i in range(4)]
    b0 = ContinuousBatcher(
        CFG, params, config=ContinuousConfig(**_CCFG)
    )
    try:
        want = [r.text for r in _serve(b0, prompts, max_new_tokens=16)]
    finally:
        b0.close()

    ctrl = AdaptiveController(
        ControlConfig(accept_min_samples=2, spec_probe_every=10_000)
    )
    _flight.flight_recorder().clear()

    def autotune_counter():
        return sum(
            v
            for k, v in REGISTRY.snapshot().items()
            if k.startswith("gateway_autotune_decisions_total")
        )

    before = autotune_counter()
    b = ContinuousBatcher(
        CFG,
        params,
        config=ContinuousConfig(**_CCFG, spec_k=4),
        draft=(CFG, adv_dparams),
        controller=ctrl,
    )
    try:
        got = [r.text for r in _serve(b, prompts, max_new_tokens=16)]
        _quiesce(b)
        st = b.stats()
    finally:
        b.close()
    assert got == want, "adaptive spec must not change text"
    # The rejects shrank/disengaged spec_k (decision value < 4).
    evs = [
        e
        for e in _flight.flight_recorder().events()
        if e.kind == "autotune" and e.meta.get("knob") == "spec_k"
    ]
    assert any(e.meta["value"] < 4 for e in evs), evs
    assert st["autotune_spec_engaged"] == 0  # disengaged by the end
    # Lockstep: the Prometheus counter moved by exactly the stats()
    # decision totals, and every decision change is a flight event.
    decisions = sum(
        st[f"autotune_decisions_{k}"]
        for k in ("spec_k", "rounds", "chunk", "depth")
    )
    assert autotune_counter() - before == decisions
    all_evs = [
        e
        for e in _flight.flight_recorder().events()
        if e.kind == "autotune"
    ]
    assert len(all_evs) == decisions


def test_self_draft_probe_regrows(params):
    """A disengaged controller re-probes and REGROWS on a self-draft
    (acceptance 1.0): force the disengaged state with poisoned EWMAs,
    serve, and the probe window's full acceptance re-engages."""
    ctrl = AdaptiveController(
        ControlConfig(accept_min_samples=1, spec_probe_every=2)
    )
    # Poison: pretend every group rejected until disengaged.
    for _ in range(3):
        ctrl.note_spec_round([(-1, 0, 4)])
    assert ctrl.spec_gate([-1]) is False
    b = ContinuousBatcher(
        CFG,
        params,
        config=ContinuousConfig(**_CCFG, spec_k=4),
        draft=(CFG, params),  # self-draft: acceptance 1.0
        controller=ctrl,
    )
    try:
        _serve(
            b,
            [_HEADER + f"regrow {i}" for i in range(3)],
            max_new_tokens=24,
        )
        _quiesce(b)
        st = b.stats()
    finally:
        b.close()
    assert st["autotune_spec_engaged"] == 1, st
    assert st["device_programs_spec"] > 0


def test_adaptive_rounds_byte_parity_vs_fixed_grid(params):
    """Adaptive-R (and chunk/depth steering with it) vs the fixed R
    grid: byte-identical text for R in {1, 4} with and without the
    controller, with at least one rounds decision recorded."""
    prompts = [_HEADER + f"R{i}" for i in range(5)]

    def run(R, ctrl):
        b = ContinuousBatcher(
            CFG,
            params,
            config=ContinuousConfig(**_CCFG, decode_rounds=R),
            controller=ctrl,
        )
        try:
            # 14 % 4 != 0: the tail window must cap.
            return [
                r.text for r in _serve(b, prompts, max_new_tokens=14)
            ]
        finally:
            b.close()

    want = run(1, None)
    assert run(4, None) == want  # the PR-12 contract itself
    _flight.flight_recorder().clear()
    ctrl = AdaptiveController(ControlConfig())
    assert run(4, ctrl) == want
    evs = [
        e
        for e in _flight.flight_recorder().events()
        if e.kind == "autotune" and e.meta.get("knob") == "rounds"
    ]
    assert evs, "no adaptive-R decision recorded"
    assert any(e.meta["value"] == 1 for e in evs), (
        "the tail windows must have capped to 1"
    )


def test_no_recompile_across_steering_burst(params, adv_dparams):
    """The no-recompile guarantee: after a warmup burst has visited
    the controller's menus, further steering bursts leave every
    compiled-program family untouched (jit trace counts and the
    chunk/fused wrapper keys are stable)."""
    ctrl = AdaptiveController(
        ControlConfig(accept_min_samples=2, spec_probe_every=10_000)
    )
    b = ContinuousBatcher(
        CFG,
        params,
        config=ContinuousConfig(**_CCFG, spec_k=4, decode_rounds=4),
        draft=(CFG, adv_dparams),
        controller=ctrl,
    )

    def caches():
        out = {
            "chunk": sorted(b._jit_chunk),
            "fused": sorted(b._jit_fused),
            "chunk_d": sorted(b._jit_chunk_d),
        }
        for name in ("_jit_decode", "_jit_rounds", "_jit_spec"):
            try:
                out[name] = getattr(b, name)._cache_size()
            except Exception:  # noqa: BLE001 - jax without _cache_size
                out[name] = -1
        return out

    try:
        # Warmup: two bursts land the shrink/disengage and the capped
        # tail window, and a half-chunk burst compiles the chunk
        # steering menu's other width — the menus are bounded, so
        # warmup covers them.
        for w in range(2):
            _serve(
                b,
                [_HEADER + f"warm{w} {i}" for i in range(4)],
                max_new_tokens=14,
            )
            _quiesce(b)
        b.controller = None
        b.config.prefill_chunk = _CCFG["prefill_chunk"] // 2
        # Spec off + several prompts: later chunks must RIDE earlier
        # rows' plain decode so the FUSED half-width variant compiles
        # (spec-engaged chunks run standalone and would skip it).
        b.config.spec_decode = False
        _serve(
            b, [_HEADER + f"half {i}" for i in range(3)], max_new_tokens=6
        )
        _quiesce(b)
        b.config.prefill_chunk = _CCFG["prefill_chunk"]
        b.config.spec_decode = True
        b.controller = ctrl
        c0 = caches()
        for w in range(2):
            _serve(
                b,
                [_HEADER + f"steer{w} {i}" for i in range(4)],
                max_new_tokens=14,
            )
            _quiesce(b)
        c1 = caches()
    finally:
        b.close()
    assert c1 == c0, f"steering burst recompiled: {c0} -> {c1}"


# ---------------------------------------------------------------------------
# Modeled-cost admission
# ---------------------------------------------------------------------------


def test_cost_admission_sheds_large_before_small():
    """Cost-budget mode: the queue bound is modeled bytes, so one
    32k-context-sized request sheds while N small ones keep fitting —
    and the overflow hard cap is the SAME byte unit (budget x factor),
    regardless of request count (the unit-normalization fix)."""
    import asyncio

    from llm_consensus_tpu.server import metrics as M
    from llm_consensus_tpu.server.admission import (
        AdmissionConfig,
        AdmissionController,
        QueueFullError,
    )

    async def main():
        reg = M.MetricsRegistry()
        c = AdmissionController(
            AdmissionConfig(
                max_queue=4,
                max_inflight=1,
                cost_budget_bytes=1000.0,
                max_overflow_factor=2,
            ),
            registry=reg,
        )
        gate = asyncio.Event()

        async def wait():
            await gate.wait()

        # An over-budget request on an EMPTY queue still admits: the
        # budget bounds the backlog, never one request's size (a
        # request the backend supports must not be unservable).
        inflight = asyncio.create_task(c.submit(wait, cost=5000))
        await asyncio.sleep(0.02)
        assert not inflight.done()
        small = [
            asyncio.create_task(c.submit(wait, cost=100))
            for _ in range(9)
        ]
        await asyncio.sleep(0.02)
        # 900 bytes queued: the big request (500) does not fit ...
        with pytest.raises(QueueFullError):
            await c.submit(wait, cost=500)
        # ... but a small one still does.
        ok = asyncio.create_task(c.submit(wait, cost=90))
        await asyncio.sleep(0.02)
        assert not ok.done()
        # The queue-cost gauge mirrors the account.
        fam = reg.get("gateway_queue_cost_bytes")
        assert fam.labels(priority="interactive").value == 990.0
        # A granting overflow hook stretches the bound in BYTES: the
        # hard cap lands at budget x factor = 2000 bytes, not at any
        # request count.
        c.overflow_hook = lambda: True
        granted = []
        for _ in range(20):
            granted.append(
                asyncio.create_task(c.submit(wait, cost=300))
            )
            await asyncio.sleep(0.005)
        await asyncio.sleep(0.02)
        queued = c._queue_cost["interactive"]
        assert queued <= 2000.0 + 300.0, queued
        shed = sum(
            1
            for t in granted
            if t.done() and isinstance(t.exception(), QueueFullError)
        )
        assert shed > 0, "the byte hard cap never engaged"
        gate.set()
        await asyncio.gather(
            inflight, ok, *small, *granted, return_exceptions=True
        )
        assert c._queue_cost["interactive"] == 0.0

    asyncio.run(main())


def test_modeled_request_cost_matches_load_cost_units(params):
    """modeled_request_cost prices a waiting request EXACTLY as
    load_cost integrates it — one formula, one byte unit (the
    admission bound and the fleet router can never drift)."""
    b = ContinuousBatcher(CFG, params, config=ContinuousConfig(**_CCFG))
    try:
        base = b.load_cost()
        ids = b.tokenizer.encode(_HEADER + "cost probe")
        want = b.modeled_request_cost(len(ids), 7)
        # Stage a waiting request without letting the worker admit it:
        # hold the admission lock while probing.
        with b._lock:
            from llm_consensus_tpu.serving.continuous import _Request
            from concurrent.futures import Future
            import numpy as np

            b._waiting.append(
                _Request(
                    prompt_ids=np.asarray(ids, np.int32),
                    max_new_tokens=7,
                    temperature=0.0,
                    seed=0,
                    future=Future(),
                )
            )
            # load_cost takes the same lock: compute inline instead.
            kvb = b._kv_token_bytes + b._draft_kv_token_bytes
            got = float(
                b._cost_tokens(len(ids), 7) * kvb
            )
            b._waiting.pop()
        assert got == want
        assert b.load_cost() == base  # nothing leaked
        # A long context costs proportionally more than a short one in
        # the SAME unit (the whole point of cost-budget admission);
        # prompts past the largest bucket clamp like the submit path.
        assert b.modeled_request_cost(64, 8) > 5 * b.modeled_request_cost(
            4, 8
        )
        assert b.modeled_request_cost(4096, 8) == b.modeled_request_cost(
            64, 8
        )
    finally:
        b.close()


def test_fleet_restore_pacing_blocks_preempt(params):
    """A fleet whose victim controller reports restore debt past the
    cap stops granting overflow admissions (classic backpressure
    resumes); repaying the debt re-enables preemption."""
    from llm_consensus_tpu.serving.fleet import FleetConfig, ReplicaSet

    rs = ReplicaSet(
        CFG,
        params,
        config=ContinuousConfig(**_CCFG, host_cache_bytes=1 << 20),
        fleet=FleetConfig(replicas=2),
        control=ControlConfig(),
    )
    try:
        # Give replica 0 a resident chain so the hook has a victim.
        rs.submit_to(0, _HEADER + "resident chain", max_new_tokens=4)
        for b in rs.batchers:
            _quiesce(b)
        assert rs.batchers[0].cached_chain_pages() > 0
        assert rs.preempt_for_admission() is True
        # Saturate the victim's modeled restore debt.
        ctrl = rs.batchers[0].controller
        assert ctrl is not None
        ctrl.note_preempt_demote(10 << 20)
        assert rs.preempt_for_admission() is False
        ctrl.note_restore(10 << 20)
        assert rs.preempt_for_admission() is True
    finally:
        rs.close()
