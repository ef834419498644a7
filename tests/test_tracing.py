"""Request-scoped tracing + step-time telemetry (PR 5).

Covers the trace subsystem end to end: bounded TraceStore/Trace rings
with drop counters mirrored into the metrics registry (lockstep), the
contextvars span protocol, Prometheus label escaping (incl. the newline
case that corrupts an exposition), the gateway acceptance path — one
``POST /v1/consensus`` trace id whose span tree covers admission →
prefill → decode → every consensus round, retrievable at
``GET /debug/traces?id=...`` — liveness/readiness splitting with a
wedged serving loop, the X-Profile device-trace bridge, the
metrics-drift CI gate, and a batcher serving the same text with
tracing off (``serve --no-trace``) and no span recorded.
"""

import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from llm_consensus_tpu.backends.fake import FakeBackend
from llm_consensus_tpu.server.client import GatewayClient, GatewayHTTPError
from llm_consensus_tpu.server.gateway import (
    Gateway,
    GatewayConfig,
    GatewayThread,
)
from llm_consensus_tpu.server.metrics import (
    REGISTRY,
    TRACE_DROPPED,
    MetricsRegistry,
    _label_str,
)
from llm_consensus_tpu.utils import tracing

ROOT = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# Prometheus label escaping (satellite: the newline corruption fix)
# ---------------------------------------------------------------------------


def test_label_escaping_newline_backslash_quote_roundtrip():
    raw = 'line1\nline2 "quoted" back\\slash'
    rendered = _label_str((("reason", raw),))
    # Single physical line — an unescaped \n would split the sample
    # line and corrupt the whole exposition.
    assert "\n" not in rendered
    assert rendered == (
        '{reason="line1\\nline2 \\"quoted\\" back\\\\slash"}'
    )
    # Round-trip through the escaping rules recovers the original.
    inner = rendered[len('{reason="') : -len('"}')]
    unescaped = (
        inner.replace("\\n", "\n").replace('\\"', '"').replace("\\\\", "\\")
    )
    assert unescaped == raw


def test_registry_render_survives_newline_label():
    reg = MetricsRegistry()
    c = reg.counter("weird_total")
    c.labels(reason="a\nb").inc()
    c.labels(reason="plain").inc(2)
    lines = reg.render().splitlines()
    samples = [l for l in lines if l.startswith("weird_total{")]
    assert len(samples) == 2  # one line per child, nothing split
    assert 'weird_total{reason="a\\nb"} 1' in samples


# ---------------------------------------------------------------------------
# Bounded rings + drop-counter lockstep (satellite)
# ---------------------------------------------------------------------------


def _dropped(kind: str) -> float:
    return TRACE_DROPPED.labels(kind=kind).value


def test_request_span_budget_and_drop_counter():
    """Spans opened through ``request_span`` past a trace's budget are
    refused, counted on the trace and mirrored into the registry (was:
    the flat Tracer's evict-oldest ring)."""
    before = _dropped("span")
    trace = tracing.TraceStore(max_spans=4).start("t")
    with tracing.use_trace(trace):
        for i in range(10):
            with tracing.request_span("s", i=i):
                pass
    assert trace.n_spans == 4
    assert trace.dropped_spans == 6
    # Refuse-newest: the survivors are the first four.
    assert [s.meta["i"] for s in trace.spans()] == [0, 1, 2, 3]
    assert _dropped("span") - before == 6


def test_trace_store_bounds_traces_and_spans():
    span_before = _dropped("span")
    trace_before = _dropped("trace")
    store = tracing.TraceStore(max_traces=2, max_spans=3)
    t1 = store.start("a")
    t2 = store.start("b")
    for i in range(5):
        t2.add_span(f"s{i}", time.perf_counter(), 0.001)
    assert t2.n_spans == 3
    assert t2.dropped_spans == 2
    assert _dropped("span") - span_before == 2
    t3 = store.start("c")  # evicts t1 (oldest)
    assert store.get(t1.trace_id) is None
    assert store.get(t2.trace_id) is t2
    assert store.get(t3.trace_id) is t3
    assert store.evicted == 1
    assert _dropped("trace") - trace_before == 1
    # Newest-first listing.
    assert [t.name for t in store.traces()] == ["c", "b"]


def test_trace_store_clamps_nonpositive_caps():
    """`serve --trace-max-traces 0` must not turn into a KeyError 500
    on the first request: caps clamp (tracing off = --no-trace)."""
    store = tracing.TraceStore(max_traces=0, max_spans=-5)
    assert store.max_traces == 1 and store.max_spans == 0
    t = store.start("x")
    assert t is not None
    t.add_span("s", time.perf_counter(), 0.001)
    assert t.n_spans == 0 and t.dropped_spans == 1
    store.configure(max_traces=-3)
    assert store.max_traces == 1
    assert store.start("y") is not None  # evicts x, no crash
    assert store.get(t.trace_id) is None


def test_disabled_tracing_is_a_noop():
    store = tracing.TraceStore()
    tracing.set_enabled(False)
    try:
        assert store.start("x") is None
        with tracing.use_trace(None):
            with tracing.request_span("s") as t:
                assert t is None
        assert tracing.current_trace() is None
    finally:
        tracing.set_enabled(True)
    assert len(store) == 0


def test_request_span_nesting_and_cross_thread_attach():
    store = tracing.TraceStore()
    tr = store.start("req")
    with tracing.use_trace(tr):
        assert tracing.current_trace() is tr
        with tracing.request_span("outer"):
            with tracing.request_span("inner"):
                pass
        # A worker thread has no context: it attaches explicitly.
        th = threading.Thread(
            target=lambda: tr.add_span("worker", time.perf_counter(), 0.01)
        )
        th.start()
        th.join()
    assert tracing.current_trace() is None
    tr.finish()
    tree = tr.to_dict()
    top = {n["name"] for n in tree["spans"]}
    assert top == {"outer", "worker"}
    outer = next(n for n in tree["spans"] if n["name"] == "outer")
    assert [c["name"] for c in outer["children"]] == ["inner"]
    assert tree["finished"] is True and tree["duration_s"] > 0


# ---------------------------------------------------------------------------
# Gateway acceptance: one consensus request -> one retrievable span tree
# ---------------------------------------------------------------------------


def _flatten(nodes):
    for n in nodes:
        yield n
        yield from _flatten(n["children"])


def test_consensus_trace_tree_and_derived_histograms():
    """ISSUE 5 acceptance (FakeBackend half): a POST /v1/consensus
    yields one trace id whose span tree covers admission -> prefill ->
    decode -> every consensus round, retrievable at
    GET /debug/traces?id=..., with the derived histograms at
    GET /metrics."""
    # Default (process-wide) registry: the coordinator's histograms and
    # the canonical trace-derived families must ride the SAME /metrics.
    gw = Gateway(FakeBackend(), config=GatewayConfig(port=0))
    handle = GatewayThread(gw).start()
    client = GatewayClient("127.0.0.1", handle.port)
    try:
        r = client.consensus("What is the tallest mountain?", seed=0)
        assert r["rounds"] == 1 and r["endorsed"] is True
        tid = r["trace_id"]
        assert tid
        tree = client.traces(tid)
        assert tree["trace_id"] == tid
        assert tree["finished"] is True
        names = [n["name"] for n in _flatten(tree["spans"])]
        assert "queued" in names and "execute" in names
        rounds = [
            n
            for n in _flatten(tree["spans"])
            if n["name"] == "consensus_round"
        ]
        # Every protocol phase of the 1-round happy path is a span.
        assert {n["meta"]["phase"] for n in rounds} == {
            "propose",
            "evaluate",
        }
        assert names.count("prefill_chunk") >= 1
        assert names.count("decode_step") >= 1
        # The listing knows this trace; unknown ids 404.
        listing = client.traces()
        assert tid in {t["trace_id"] for t in listing["traces"]}
        with pytest.raises(GatewayHTTPError) as e:
            client.traces("deadbeef00000000")
        assert e.value.status == 404
        text = client.metrics()
        assert 'consensus_round_seconds_bucket{phase="evaluate"' in text
        assert "# TYPE gateway_decode_step_seconds histogram" in text
        assert "# TYPE gateway_sched_overhead_seconds histogram" in text
        assert "# TYPE gateway_trace_dropped_total counter" in text
    finally:
        handle.drain()


def test_generate_carries_trace_id_and_stream_done_event():
    gw = Gateway(FakeBackend(), config=GatewayConfig(port=0))
    handle = GatewayThread(gw).start()
    client = GatewayClient("127.0.0.1", handle.port)
    try:
        r = client.generate("trace me")
        tree = client.traces(r["trace_id"])
        assert {n["name"] for n in _flatten(tree["spans"])} >= {
            "queued",
            "execute",
            "prefill_chunk",
            "decode_step",
        }
        events = list(client.stream_generate("stream trace"))
        assert events[-1]["done"] is True
        assert events[-1]["trace_id"]
        client.traces(events[-1]["trace_id"])  # retrievable
    finally:
        handle.drain()


# ---------------------------------------------------------------------------
# Liveness vs readiness (satellite): /healthz stays 200, /readyz flips
# ---------------------------------------------------------------------------


class _StubHealthBackend(FakeBackend):
    def __init__(self):
        super().__init__()
        self.tick_age = 0.0
        self.explode = False

    def health(self):
        if self.explode:
            raise RuntimeError("probe torn down")
        return {
            "alive": True,
            "last_tick_age_s": self.tick_age,
            "last_step_age_s": None,
        }


def test_readyz_flips_on_stale_heartbeat_healthz_stays_live():
    backend = _StubHealthBackend()
    gw = Gateway(
        backend,
        config=GatewayConfig(port=0, ready_stall_s=1.0),
        registry=MetricsRegistry(),
    )
    handle = GatewayThread(gw).start()
    client = GatewayClient("127.0.0.1", handle.port)
    try:
        assert client.readyz()["ready"] is True
        backend.tick_age = 5.0  # loop "wedged"
        with pytest.raises(GatewayHTTPError) as e:
            client.readyz()
        assert e.value.status == 503
        assert "stalled" in e.value.body
        # Liveness is unaffected: the process still answers.
        h = client.healthz()
        assert h["status"] == "ok"
        assert h["backend"]["last_tick_age_s"] == 5.0
        backend.tick_age = 0.0
        assert client.readyz()["ready"] is True
        # A RAISING health probe fails CLOSED (state unknown => 503),
        # while liveness keeps answering with the error recorded.
        backend.explode = True
        with pytest.raises(GatewayHTTPError) as e:
            client.readyz()
        assert e.value.status == 503
        assert "health probe failed" in e.value.body
        assert "error" in client.healthz()["backend"]
    finally:
        handle.drain()


def test_shed_and_drained_requests_do_not_retain_traces():
    """A request rejected at the admission door did no traceable work:
    retaining its trace would let a 429/503 storm churn the bounded
    ring and evict exactly the slow traces being debugged."""
    gw = Gateway(FakeBackend(), config=GatewayConfig(port=0))
    handle = GatewayThread(gw).start()
    client = GatewayClient("127.0.0.1", handle.port)
    store = tracing.trace_store()
    try:
        ok = client.generate("keep this trace")
        gw.admission.begin_drain()
        before = {t.trace_id for t in store.traces(limit=store.max_traces)}
        with pytest.raises(GatewayHTTPError) as e:
            client.generate("shed me")
        assert e.value.status == 503
        after = {t.trace_id for t in store.traces(limit=store.max_traces)}
        assert after == before  # the drained request left no trace
        assert ok["trace_id"] in after
    finally:
        handle.drain()


@pytest.fixture(scope="module")
def tiny_batcher():
    import jax
    import jax.numpy as jnp

    from llm_consensus_tpu.models.configs import get_config
    from llm_consensus_tpu.models.transformer import init_params
    from llm_consensus_tpu.serving.continuous import (
        ContinuousBatcher,
        ContinuousConfig,
    )

    cfg = get_config("test-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    batcher = ContinuousBatcher(
        cfg,
        params,
        config=ContinuousConfig(
            max_slots=2,
            page_size=4,
            n_pages=64,
            pages_per_seq=16,
            max_new_tokens=8,
            seq_buckets=(16, 32),
            prefill_chunk=8,
        ),
    )
    yield batcher
    batcher.close()


def test_readyz_flips_when_real_batcher_loop_wedges(tiny_batcher):
    """The satellite's stall test: wedge the REAL continuous-batcher
    host loop and watch /readyz flip 503, then recover."""
    from llm_consensus_tpu.serving.continuous import ContinuousBackend

    gw = Gateway(
        ContinuousBackend(tiny_batcher),
        config=GatewayConfig(port=0, ready_stall_s=1.0),
        registry=MetricsRegistry(),
    )
    handle = GatewayThread(gw).start()
    client = GatewayClient("127.0.0.1", handle.port)

    def poll(want_ready, deadline_s=15.0):
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            try:
                if client.readyz()["ready"] is want_ready:
                    return True
            except GatewayHTTPError as e:
                assert e.status == 503
                if not want_ready:
                    return True
            time.sleep(0.1)
        return False

    try:
        assert poll(True), "batcher never reported ready"
        # Wedge: every loop iteration now stalls 3 s in admission (the
        # instance attribute shadows the bound method).
        tiny_batcher._admit = lambda: time.sleep(3.0)
        try:
            assert poll(False), "readyz never flipped to 503"
        finally:
            del tiny_batcher._admit
        assert poll(True), "readyz never recovered after unwedging"
    finally:
        handle.drain()


# ---------------------------------------------------------------------------
# Continuous batcher: spans + stats()/Prometheus lockstep (acceptance)
# ---------------------------------------------------------------------------


def test_batcher_spans_and_stats_metrics_lockstep(tiny_batcher):
    """The real serving half of the acceptance: a traced request's tree
    records prefill chunks and decode steps, and the span-derived
    histograms move in lockstep with the batcher's stats() mirror."""
    step_h = REGISTRY.get("gateway_decode_step_seconds")
    over_h = REGISTRY.get("gateway_sched_overhead_seconds")
    st0 = tiny_batcher.stats()
    h0 = (step_h.count, step_h.sum, over_h.count, over_h.sum)
    trace = tracing.trace_store().start("batcher-req")
    with tracing.use_trace(trace):
        fut = tiny_batcher.submit(
            "a prompt long enough to take several chunks", max_new_tokens=8
        )
    out = fut.result(timeout=300)
    assert out.num_tokens == 8
    names = [s.name for s in trace.spans()]
    assert names.count("prefill_chunk") >= 2  # chunked over the prompt
    assert names.count("decode_step") >= 7  # one per step it decoded in
    st1 = tiny_batcher.stats()
    # stats() moved by exactly what the process-wide histograms moved
    # (this batcher is the only serving activity in this window).
    assert (
        st1["decode_step_seconds_count"] - st0["decode_step_seconds_count"]
        == step_h.count - h0[0]
        >= 7
    )
    assert st1["decode_step_seconds_sum"] - st0[
        "decode_step_seconds_sum"
    ] == pytest.approx(step_h.sum - h0[1])
    assert (
        st1["sched_overhead_seconds_count"]
        - st0["sched_overhead_seconds_count"]
        == over_h.count - h0[2]
        >= 1
    )
    assert st1["sched_overhead_seconds_sum"] - st0[
        "sched_overhead_seconds_sum"
    ] == pytest.approx(over_h.sum - h0[3])
    hb = tiny_batcher.heartbeat()
    assert hb["alive"] is True and hb["last_step_age_s"] is not None


def test_batcher_serves_the_same_text_with_tracing_off(tiny_batcher):
    """``serve --no-trace``: the store hands out no trace, the batcher
    attaches no span for the request, and the request's text is what
    the traced one's was."""
    prompt = "a prompt long enough to take several chunks, again"

    def serve():
        trace = tracing.trace_store().start("switch-req")
        with tracing.use_trace(trace):
            fut = tiny_batcher.submit(prompt, max_new_tokens=8)
        out = fut.result(timeout=300)
        return trace, (out.text, out.num_tokens)

    traced, want = serve()
    assert len(traced.spans()) > 0
    tracing.set_enabled(False)
    try:
        untraced, got = serve()
    finally:
        tracing.set_enabled(True)
    assert untraced is None
    assert got == want


# ---------------------------------------------------------------------------
# X-Profile bridge: a flagged request drops a TensorBoard device trace
# ---------------------------------------------------------------------------


def test_x_profile_writes_device_trace(tmp_path):
    profile_dir = tmp_path / "profiles"
    gw = Gateway(
        FakeBackend(),
        config=GatewayConfig(port=0, profile_dir=str(profile_dir)),
        registry=MetricsRegistry(),
    )
    handle = GatewayThread(gw).start()
    client = GatewayClient("127.0.0.1", handle.port)
    try:
        # Unflagged requests never touch the profiler.
        client.generate("plain")
        assert not profile_dir.exists() or not any(profile_dir.rglob("*"))
        r = client.generate("profile me", headers={"X-Profile": "1"})
        dumped = [p for p in profile_dir.rglob("*") if p.is_file()]
        assert dumped, "X-Profile: 1 produced no device-trace files"
        # The profiled window is marked on the request's host trace.
        tree = client.traces(r["trace_id"])
        names = {n["name"] for n in _flatten(tree["spans"])}
        assert "jax_profile" in names
    finally:
        handle.drain()


# ---------------------------------------------------------------------------
# CI gate: metrics drift
# ---------------------------------------------------------------------------


def test_check_metrics_drift_gate_passes():
    r = subprocess.run(
        [sys.executable, "scripts/check_metrics.py"],
        cwd=ROOT,
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert r.returncode == 0, r.stderr


def test_check_metrics_detects_undeclared_family(tmp_path):
    """The gate actually bites: an instrumentation site registering a
    family that metrics.py does not declare fails the check."""
    import shutil

    clone = tmp_path / "repo"
    clone.mkdir()
    (clone / "scripts").mkdir()
    shutil.copy(ROOT / "scripts" / "check_metrics.py", clone / "scripts")
    shutil.copy(ROOT / "README.md", clone / "README.md")
    pkg = clone / "llm_consensus_tpu"
    for rel in (
        "llm_consensus_tpu/__init__.py",
        "llm_consensus_tpu/version.py",
        "llm_consensus_tpu/server/__init__.py",
        "llm_consensus_tpu/server/metrics.py",
        "llm_consensus_tpu/utils/__init__.py",
        "llm_consensus_tpu/utils/tracing.py",
        "llm_consensus_tpu/utils/logging.py",
        "llm_consensus_tpu/serving/continuous.py",
        "llm_consensus_tpu/serving/scheduler.py",
        "llm_consensus_tpu/serving/offload.py",
        "llm_consensus_tpu/serving/flight.py",
        "llm_consensus_tpu/serving/fleet.py",
        "llm_consensus_tpu/serving/fleet_control.py",
        "llm_consensus_tpu/serving/control.py",
        "llm_consensus_tpu/serving/disagg.py",
        "llm_consensus_tpu/serving/remote_store.py",
        "llm_consensus_tpu/serving/modelset.py",
        "llm_consensus_tpu/serving/vocab_align.py",
        "llm_consensus_tpu/server/gateway.py",
        "llm_consensus_tpu/server/admission.py",
        "llm_consensus_tpu/consensus/coordinator.py",
    ):
        dst = clone / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(ROOT / rel, dst)
    gw = clone / "llm_consensus_tpu/server/gateway.py"
    gw.write_text(
        gw.read_text()
        + '\n_ROGUE = None  # reg.counter("gateway_rogue_total", "oops")\n'
    )
    r = subprocess.run(
        [sys.executable, "scripts/check_metrics.py"],
        cwd=clone,
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert r.returncode == 1
    assert "gateway_rogue_total" in r.stderr


# ---------------------------------------------------------------------------
# Start-up and compile spans (PR 37)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def compile_listener():
    from llm_consensus_tpu.server import metrics as M

    tracing.install_compile_listener(
        M.COMPILE_SECONDS, M.COMPILES, M.EXECUTABLES
    )
    return M


def _samples(family) -> dict:
    return {k: c.value for k, c in list(family._children.items())}


def _grew(before: dict, after: dict) -> dict:
    return {
        dict(k).get("program", "") + "/" + (
            dict(k).get("stage") or dict(k).get("cache")
        ): v - before.get(k, 0.0)
        for k, v in after.items() if v != before.get(k, 0.0)
    }


def test_a_jitted_program_counts_one_trace_of_its_own(compile_listener):
    """The ``jnp`` functions it calls are traced inside its trace: they
    are its children and add no seconds, so the stages of one program
    sum to no more than the wall time its first call took."""
    import jax
    import jax.numpy as jnp

    M = compile_listener

    @jax.jit
    def pr37_probe_step(x, w):
        return jnp.sin(x) @ w + jnp.cos(w)

    x = jnp.ones((8, 8))
    s0, c0 = _samples(M.COMPILE_SECONDS), _samples(M.COMPILES)
    t0 = time.perf_counter()
    pr37_probe_step(x, x).block_until_ready()
    wall = time.perf_counter() - t0
    grew = _grew(s0, _samples(M.COMPILE_SECONDS))
    assert set(grew) == {
        "pr37_probe_step/trace", "pr37_probe_step/lower",
        "pr37_probe_step/backend",
    }, grew
    assert 0 < sum(grew.values()) <= wall
    assert _grew(c0, _samples(M.COMPILES)) == {"pr37_probe_step/miss": 1}


def test_a_cached_program_adds_nothing(compile_listener):
    import jax
    import jax.numpy as jnp

    M = compile_listener
    f = jax.jit(lambda a: a * 3.0)
    x = jnp.ones((4,))
    f(x).block_until_ready()
    s0, c0 = _samples(M.COMPILE_SECONDS), _samples(M.COMPILES)
    f(x).block_until_ready()
    assert _samples(M.COMPILE_SECONDS) == s0
    assert _samples(M.COMPILES) == c0


def test_lower_then_compile_is_counted(compile_listener):
    """How the store of executables builds a program it does not hold:
    ``.lower(shapes).compile()``."""
    import jax
    import jax.numpy as jnp

    M = compile_listener

    def pr37_ahead(a, b):
        return jnp.tanh(a) * b

    shape = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    s0, c0 = _samples(M.COMPILE_SECONDS), _samples(M.COMPILES)
    jax.jit(pr37_ahead).lower(shape, shape).compile()
    grew = _grew(s0, _samples(M.COMPILE_SECONDS))
    assert set(grew) == {
        "pr37_ahead/trace", "pr37_ahead/lower", "pr37_ahead/backend",
    }, grew
    assert _grew(c0, _samples(M.COMPILES)) == {"pr37_ahead/miss": 1}


def test_a_load_from_the_persistent_cache_counts_a_hit(
    compile_listener, tmp_path
):
    """A CPU compile is under jax's 1 s threshold for writing an entry,
    so the test lowers the threshold to 0 (as the benchmark's server
    does) around a cache of its own."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    M = compile_listener
    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
    )
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        cc.reset_cache()
        jax.config.update(names[0], str(tmp_path))
        jax.config.update(names[1], 0)

        def pr37_cached(a):
            return jnp.exp(a) - 1.0

        c0 = _samples(M.COMPILES)
        x = jnp.ones((32,))
        jax.jit(pr37_cached)(x).block_until_ready()
        jax.clear_caches()
        jax.jit(pr37_cached)(x).block_until_ready()
        grew = _grew(c0, _samples(M.COMPILES))
        assert {k: v for k, v in grew.items() if "pr37" in k} == {
            "pr37_cached/miss": 1, "pr37_cached/hit": 1,
        }
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        cc.reset_cache()


def test_phases_sum_to_the_stretch_they_cover():
    """Nested phases stop the outer one's clock and the gaps between
    phases go to the next one: the seconds sum to the wall time from the
    clock's start to the last phase's end, and each is flushed once."""
    fam = MetricsRegistry().counter("setup_seconds")
    t0 = time.perf_counter()
    clock = tracing.PhaseClock("setup.", fam, ("a", "b", "c"), t0=t0)
    time.sleep(0.02)
    with clock.phase("a"):
        time.sleep(0.02)
        with clock.phase("b"):
            time.sleep(0.03)
    time.sleep(0.01)
    with clock.phase("c", note=1):
        time.sleep(0.02)
    wall = time.perf_counter() - t0
    seconds = dict(clock.seconds)
    assert seconds["b"] >= 0.03 and seconds["c"] >= 0.03
    assert seconds["a"] >= 0.04 and seconds["a"] < 0.04 + 0.03
    assert sum(seconds.values()) == pytest.approx(wall, rel=0.01)
    clock.flush()
    assert clock.seconds == {"a": 0.0, "b": 0.0, "c": 0.0}
    assert {p: fam.labels(phase=p).value for p in "abc"} == seconds


def _old_batcher_phase(state: dict, name: str):
    """``ContinuousBatcher._phase`` as it was before PR 37 (its
    annotation aside): the reference the helper must reproduce."""
    open_ = state["open"]
    now = time.perf_counter()
    state["s"][open_[-1] if open_ else name] += now - state["t"]
    state["t"] = now
    open_.append(name)
    yield
    now = time.perf_counter()
    open_.pop()
    state["s"][name] += now - state["t"]
    state["t"] = now


def test_the_batcher_phase_clock_sums_as_before(monkeypatch):
    """The same loop of phases, nested as the batcher nests them, on a
    clock that ticks 1, 2, 3, ...: the helper gathers exactly the seconds
    the old ``_phase`` did, phase by phase."""
    import contextlib
    import itertools

    from llm_consensus_tpu.serving.continuous import _PHASES

    def run(open_phase):
        for _ in range(3):
            with open_phase("admit"):
                pass
            with open_phase("dispatch"):
                with open_phase("device_wait"):
                    pass
            with open_phase("retire"):
                with open_phase("device_wait"):
                    pass
            with open_phase("idle"):
                pass

    ticks = itertools.count(1)
    monkeypatch.setattr(time, "perf_counter", lambda: float(next(ticks)))
    fam = MetricsRegistry().counter("phases")
    clock = tracing.PhaseClock("batcher.", fam, _PHASES)
    run(clock.phase)
    new = dict(clock.seconds)

    ticks = itertools.count(1)
    state = {"s": dict.fromkeys(_PHASES, 0.0), "open": [],
             "t": time.perf_counter()}
    run(lambda name: contextlib.contextmanager(_old_batcher_phase)(
        state, name))
    assert new == state["s"]
    assert new["device_wait"] > 0 and new["restore"] == 0.0


def test_build_ahead_is_a_setup_span(tiny_batcher, compile_listener, caplog):
    """The build-ahead's seconds go to
    ``gateway_setup_seconds_total{phase="build_ahead"}`` and its "built
    in" line prints them; the compiles it causes fit inside it."""
    import logging

    M = compile_listener
    tiny_batcher.submit("warm the plain step", max_new_tokens=2).result(
        timeout=300
    )
    ahead = M.SETUP_SECONDS.labels(phase="build_ahead")
    a0, s0 = ahead.value, sum(_samples(M.COMPILE_SECONDS).values())
    with caplog.at_level(logging.INFO, "llm_consensus_tpu.serving"):
        # A chunk width this batcher has not built for.
        tiny_batcher._build_fused_ahead(16, 32)
    spent = ahead.value - a0
    compiled = sum(_samples(M.COMPILE_SECONDS).values()) - s0
    assert 0 < compiled <= spent
    line = [r.getMessage() for r in caplog.records if "built in" in r.message]
    assert line == [
        f"fused step programs for chunks of 16, lanes "
        f"{sorted({1, tiny_batcher._lanes_for(16)})}, grouped and not: "
        f"built in {spent:.1f} s"
    ]


def test_perf_counter_is_the_monotonic_clock():
    """Every span is on ``perf_counter``; the benchmark stamps its
    window with ``monotonic``. On Linux both read one clock."""
    if not sys.platform.startswith("linux"):
        pytest.skip("the one-clock guarantee is CPython's on Linux")
    pc, mono = time.get_clock_info("perf_counter"), time.get_clock_info(
        "monotonic")
    assert pc.implementation == mono.implementation
    assert "CLOCK_MONOTONIC" in pc.implementation


_TEXT_WITH = """\
# HELP gateway_setup_seconds_total Seconds of start-up by phase
# TYPE gateway_setup_seconds_total counter
gateway_setup_seconds_total{phase="batcher"} 2.5
gateway_setup_seconds_total{phase="weights"} 14.0
# TYPE gateway_compile_seconds_total counter
gateway_compile_seconds_total{program="fused_step",stage="trace"} 3.0
gateway_compile_seconds_total{program="fused_step",stage="lower"} 1.5
"""
_TEXT_LATER = _TEXT_WITH.replace('stage="lower"} 1.5', 'stage="lower"} 2.0')
_TEXT_WITHOUT = "# TYPE gateway_requests_total counter\n"


@pytest.fixture
def counter_value(monkeypatch):
    """The benchmark's reader, imported as ``benchmark/run.py`` does."""
    import importlib

    modules = ("server", "stats", "layer_metrics",
               "layer_metrics.counter_value")
    monkeypatch.syspath_prepend(str(ROOT / "benchmark"))
    for name in modules:
        monkeypatch.delitem(sys.modules, name, raising=False)
    yield importlib.import_module("layer_metrics.counter_value")
    for name in modules:
        sys.modules.pop(name, None)


@pytest.mark.parametrize(
    "before, after, counters, at, want",
    [
        # A program without the family: nothing, never 0.
        (_TEXT_WITHOUT, _TEXT_WITHOUT,
         [["gateway_setup_seconds_total", 'phase="weights"']], "open", None),
        (_TEXT_WITH, _TEXT_WITHOUT,
         [["gateway_compile_seconds_total", ""]], "window", None),
        # Present and unmoved: 0.
        (_TEXT_WITH, _TEXT_WITH,
         [["gateway_compile_seconds_total", ""]], "window", 0.0),
        (_TEXT_WITH, _TEXT_LATER,
         [["gateway_setup_seconds_total", 'phase="weights"']], "open", 14.0),
        (_TEXT_WITH, _TEXT_LATER,
         [["gateway_compile_seconds_total", ""]], "open", 4.5),
        (_TEXT_WITH, _TEXT_LATER,
         [["gateway_compile_seconds_total", ""]], "window", 0.5),
    ],
    ids=["absent-open", "absent-after", "unmoved", "open-one-label",
         "open-family", "window"],
)
def test_counter_value_reader(counter_value, before, after, counters, at,
                              want):
    run = type("Run", (), {"metrics_before": before,
                           "metrics_after": after})()
    got = counter_value.read(run, counters=counters, at=at)
    assert got == (None if want is None else pytest.approx(want))
