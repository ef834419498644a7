"""Serving flight recorder + roofline attribution (PR 10).

Contract layers:

- RING: the bounded evict-oldest FlightRecorder counts every eviction
  and mirrors it into ``gateway_flight_dropped_total`` (lockstep), and
  records nothing when disabled.
- CHROME EXPORT: ``to_chrome`` emits valid Chrome trace-event JSON
  (every event carries ts/ph/pid/tid, the whole doc JSON round-trips),
  and the device track reconstructs EXACTLY the programs
  ``gateway_device_programs_total`` counted over the same window — the
  PR's acceptance criterion.
- TOKEN TIMELINE: ``gateway_tbt_seconds`` moves in lockstep with the
  batcher's ``stats()`` mirror and with the per-request summaries'
  gap counts; TTFT moves once per request on both its surfaces.
- COST MODEL: modeled KV tokens are invariant to HOW the work was
  packaged into programs — fused-vs-split totals are identical over
  the same burst, and (at a round-aligned token budget) spec-on/off
  target KV writes are identical — while the weight term counts
  programs (the thing fusion/speculation amortize).
- GATEWAY: ``/debug/flight`` (+ ``?format=chrome``), ``/debug/requests``
  (+ ``?id=`` by request OR trace id), response ``meta``, and the shed
  event on a 429.
- THE SWITCH: with the recorder off (``serve --no-flight``) a burst
  serves the same text and records nothing.
"""

import json
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.transformer import (
    init_params,
    model_param_bytes,
    program_hbm_cost,
)
from llm_consensus_tpu.serving import flight
from llm_consensus_tpu.serving.continuous import (
    ContinuousBackend,
    ContinuousBatcher,
    ContinuousConfig,
)
from llm_consensus_tpu.server.metrics import (
    FLIGHT_DROPPED,
    REGISTRY,
    TBT_SECONDS,
    MetricsRegistry,
)

ROOT = Path(__file__).resolve().parent.parent

CFG = get_config("test-tiny")

_HEADER = "Panel shared header for every persona, forty ch: "
_CCFG = dict(
    max_slots=4,
    page_size=16,
    n_pages=96,
    pages_per_seq=10,
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
    return [f.result(timeout=180) for f in futs]


def _quiesce(batcher, timeout=20.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        s = batcher.stats()
        if (
            s["active_slots"] == 0
            and s["prefilling_slots"] == 0
            and s["dispatch_inflight"] == 0
            and s["waiting"] == 0
        ):
            return s
        time.sleep(0.01)
    raise AssertionError(f"batcher did not quiesce: {batcher.stats()}")


def _programs_total() -> float:
    return sum(
        v
        for k, v in REGISTRY.snapshot().items()
        if k.startswith("gateway_device_programs_total")
    )


# ---------------------------------------------------------------------------
# Ring: overflow + drop-counter lockstep, disable, request log
# ---------------------------------------------------------------------------


def test_flight_ring_overflow_drop_counter_lockstep():
    rec = flight.FlightRecorder(capacity=8)
    before = FLIGHT_DROPPED.value
    for i in range(20):
        rec.record("unit", float(i), i=i)
    assert len(rec) == 8
    assert rec.dropped == 12
    # The Prometheus mirror moved by exactly the ring's own count.
    assert FLIGHT_DROPPED.value - before == 12
    # Evict-oldest: the survivors are the newest 8.
    assert [e.meta["i"] for e in rec.events()] == list(range(12, 20))
    # Shrinking the cap sheds immediately, still counted.
    rec.configure(capacity=3)
    assert len(rec) == 3 and rec.dropped == 17
    assert FLIGHT_DROPPED.value - before == 17


def test_flight_disabled_records_nothing():
    rec = flight.FlightRecorder(capacity=8)
    flight.set_enabled(False)
    try:
        assert rec.record("unit", 0.0) is None
        assert len(rec) == 0 and rec.dropped == 0
    finally:
        flight.set_enabled(True)
    assert rec.record("unit", 0.0) is not None


def test_request_log_bounds_and_trace_lookup():
    log = flight.RequestLog(max_requests=2)
    log.add({"id": "req-a", "trace_id": "t-a"})
    log.add({"id": "req-b", "trace_id": "t-b"})
    log.add({"id": "req-c", "trace_id": None})
    assert len(log) == 2
    assert log.get("req-a") is None  # evicted (oldest)
    assert log.get("t-a") is None
    assert log.get("req-b")["id"] == "req-b"
    assert log.get("t-b")["id"] == "req-b"  # trace-id lookup
    assert [d["id"] for d in log.recent(10)] == ["req-c", "req-b"]


def test_request_log_shared_trace_returns_every_member():
    """One trace can cover several generations (a consensus panel
    fan-out submits every member under the request's trace): the trace
    key reaches ALL of them, newest first, surviving partial
    eviction."""
    log = flight.RequestLog(max_requests=3)
    log.add({"id": "req-1", "trace_id": "t-panel"})
    log.add({"id": "req-2", "trace_id": "t-panel"})
    log.add({"id": "req-3", "trace_id": "t-panel"})
    assert [d["id"] for d in log.get_all("t-panel")] == [
        "req-3", "req-2", "req-1",
    ]
    assert log.get("t-panel")["id"] == "req-3"  # latest wins
    assert log.get_all("req-2") == [{"id": "req-2", "trace_id": "t-panel"}]
    # Evicting one member prunes only its index entry.
    log.add({"id": "req-4", "trace_id": None})
    assert [d["id"] for d in log.get_all("t-panel")] == ["req-3", "req-2"]


def test_percentile_nearest_rank():
    assert flight.percentile([], 99) == 0.0
    vals = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert flight.percentile(vals, 50) == 3.0
    assert flight.percentile(vals, 99) == 5.0
    # Nearest-rank: every answer is an actually-observed value.
    assert flight.percentile(vals, 1) == 1.0


# ---------------------------------------------------------------------------
# Chrome export: schema validity on synthetic events
# ---------------------------------------------------------------------------


def test_chrome_export_schema_and_tracks():
    evs = [
        flight.FlightEvent(0, "program", 10.0, 0.5, None, {"kind": "decode"}),
        flight.FlightEvent(1, "program", 10.6, 0.0, None, {"kind": "draft"}),
        flight.FlightEvent(2, "host", 9.5, 0.4, None, {}),
        flight.FlightEvent(3, "admit", 9.4, 0.0, "tr1", {"id": "req-9"}),
        flight.FlightEvent(4, "restore", 9.6, 0.1, "tr1", {"page": 7}),
        flight.FlightEvent(
            5, "request", 9.3, 1.9, "tr1", {"id": "req-9", "tokens": 8}
        ),
    ]
    doc = flight.to_chrome(evs)
    # The whole export must survive a JSON round trip (what the
    # gateway serves and Perfetto loads).
    doc = json.loads(json.dumps(doc))
    events = doc["traceEvents"]
    assert all(
        {"ts", "ph", "pid", "tid"} <= set(e) for e in events
    ), "every Chrome event needs ts/ph/pid/tid"
    # ts is relative to the earliest event — never negative.
    assert all(e["ts"] >= 0 for e in events)
    dev = [
        e
        for e in events
        if e.get("cat") == "device" and e["ph"] == "X"
    ]
    assert [e["name"] for e in dev] == ["decode", "draft"]
    assert dev[1]["dur"] == 0  # in-flight/annotation programs allowed
    host = [e for e in events if e.get("cat") == "host"]
    assert len(host) == 1 and host[0]["ph"] == "X"
    # Durationless scheduler events render as instants, timed ones as
    # slices; both carry the trace id in args.
    admit = next(e for e in events if e.get("name") == "admit")
    assert admit["ph"] == "i" and admit["args"]["trace_id"] == "tr1"
    restore = next(e for e in events if e.get("name") == "restore")
    assert restore["ph"] == "X" and restore["dur"] > 0
    # The request span sits on its own named thread row.
    req = next(e for e in events if e.get("cat") == "request")
    names = [
        e
        for e in events
        if e["ph"] == "M"
        and e["name"] == "thread_name"
        and e["pid"] == req["pid"]
        and e["tid"] == req["tid"]
    ]
    assert names and names[0]["args"]["name"] == "req-9"


# ---------------------------------------------------------------------------
# Acceptance: the device track reconstructs gateway_device_programs_total
# ---------------------------------------------------------------------------


def test_device_track_reconstructs_program_counter(params):
    b = ContinuousBatcher(
        CFG, params, config=ContinuousConfig(**_CCFG)
    )
    try:
        # Warm so the measured window is steady-state-ish (irrelevant
        # to counts, keeps the test fast on recompiles).
        _serve(b, [_HEADER + "warm"], max_new_tokens=4)
        _quiesce(b)
        flight.flight_recorder().clear()
        before = _programs_total()
        prompts = [_HEADER + f"tail {i}" for i in range(4)] + [
            "unrelated prompt entirely"
        ]
        outs = _serve(b, prompts, max_new_tokens=8)
        _quiesce(b)
        delta = _programs_total() - before
    finally:
        b.close()
    assert all(o.num_tokens >= 1 for o in outs)
    evs = flight.flight_recorder().events()
    prog_evs = [e for e in evs if e.kind == "program"]
    assert len(prog_evs) == delta > 0
    doc = json.loads(json.dumps(flight.to_chrome(evs)))
    events = doc["traceEvents"]
    assert all({"ts", "ph", "pid", "tid"} <= set(e) for e in events)
    dev = [
        e for e in events if e.get("cat") == "device" and e["ph"] == "X"
    ]
    # THE acceptance assertion: the Chrome device track holds exactly
    # the programs the counter counted over the burst window.
    assert len(dev) == delta
    # Fetched programs carry real windows; the quiesced burst has no
    # pending ones, so every decode/fused program has a duration.
    timed = [e for e in dev if e["name"] in ("decode", "fused")]
    assert timed and all(e["dur"] > 0 for e in timed)
    # The burst's journey shows up as typed scheduler events + one
    # track slice per request.
    kinds = {e.kind for e in evs}
    assert {"admit", "request", "program"} <= kinds
    req_slices = [e for e in events if e.get("cat") == "request"]
    assert len(req_slices) == len(prompts)


# ---------------------------------------------------------------------------
# Token timeline: TTFT/TBT Prometheus <-> stats <-> summaries lockstep
# ---------------------------------------------------------------------------


def test_ttft_tbt_lockstep_and_summaries(params):
    b = ContinuousBatcher(CFG, params, config=ContinuousConfig(**_CCFG))
    try:
        st0 = b.stats()
        h0 = (TBT_SECONDS.count, TBT_SECONDS.sum)
        outs = _serve(
            b, [_HEADER + f"t{i}" for i in range(3)], max_new_tokens=8
        )
        _quiesce(b)
        st1 = b.stats()
    finally:
        b.close()
    # stats() moved by exactly what the process-wide histogram moved
    # (this batcher is the only serving activity in the window).
    d_count = st1["tbt_seconds_count"] - st0["tbt_seconds_count"]
    assert TBT_SECONDS.count - h0[0] == d_count
    assert TBT_SECONDS.sum - h0[1] == pytest.approx(
        st1["tbt_seconds_sum"] - st0["tbt_seconds_sum"]
    )
    # One TBT observation per generated token past each request's
    # first — and the per-request summaries carry the same counts.
    assert d_count == sum(o.num_tokens - 1 for o in outs)
    assert d_count == sum(o.timing["tbt_count"] for o in outs)
    # TTFT: once per request, on both its batcher surfaces.
    assert st1["ttft_seconds_count"] - st0["ttft_seconds_count"] == len(outs)
    for o in outs:
        t = o.timing
        assert t["new_tokens"] == o.num_tokens
        assert t["ttft_s"] > 0 and t["duration_s"] >= t["ttft_s"]
        assert 0 <= t["tbt_p50_s"] <= t["tbt_p99_s"] <= t["tbt_max_s"]
        # The summary is retrievable from the process RequestLog by
        # request id (trace-id lookup is exercised via the gateway).
        assert flight.request_log().get(t["id"]) == t


# ---------------------------------------------------------------------------
# Cost model: packaging-invariant KV, program-counting weights
# ---------------------------------------------------------------------------


def test_program_hbm_cost_units():
    w_bytes, w_params = model_param_bytes(
        {"a": jnp.zeros((4, 8), jnp.float32), "b": jnp.zeros((3,), jnp.int8)}
    )
    assert w_bytes == 4 * 8 * 4 + 3
    assert w_params == 35
    c = program_hbm_cost(
        CFG,
        weight_bytes=1000,
        weight_params=10,
        kv_token_bytes=7,
        kv_read_tokens=20,
        kv_write_tokens=5,
        tokens=3,
    )
    assert c["hbm_bytes"] == 1000 + 25 * 7
    assert c["flops"] == 2 * 10 * 3 + 4 * CFG.n_heads * CFG.head_dim * 20
    assert c["kv_read_tokens"] == 20 and c["kv_write_tokens"] == 5


def _mbu_totals(stats, keys=("kv_read_tokens", "kv_write_tokens")):
    return {
        key: sum(
            stats[f"mbu_{key}_{kind}"]
            for kind in ("fused", "decode", "prefill", "spec")
        )
        for key in keys
    }


def test_cost_model_fused_vs_split_kv_parity(params):
    """The modeled KV traffic is a property of the WORK, not of how the
    scheduler packaged it into programs: the same burst served fused
    (chunks ride the decode dispatch) and split (standalone chunk
    programs) must model identical KV token totals — while the weight
    term moves with the program count, which is exactly what fusion
    saves. Depth pinned to 1 so retirement overshoot can't smear
    row-steps across the legs."""
    b = ContinuousBatcher(
        CFG,
        params,
        config=ContinuousConfig(**_CCFG, pipeline_depth=1),
    )
    # A first prompt is decoding when the others arrive, so their chunks
    # have a dispatch to ride (with every slot mid-prompt at once their
    # chunks share standalone programs, PR 31, and nothing is fused).
    prompts = ["first: it is decoding when the rest arrive"] + [
        _HEADER + f"tail {i}" for i in range(3)
    ]

    def leg(ragged: bool):
        b.config.ragged_attention = ragged
        _quiesce(b)
        s0 = b.stats()
        first = b.submit(prompts[0], max_new_tokens=24)
        deadline = time.monotonic() + 60
        while not b.stats()["active_slots"]:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        texts = [r.text for r in _serve(b, prompts[1:], max_new_tokens=8)]
        texts.append(first.result(timeout=120).text)
        _quiesce(b)
        s1 = b.stats()
        d = {
            k: s1[k] - s0[k]
            for k in s1
            if k.startswith(("mbu_", "device_programs_"))
        }
        return texts, d

    try:
        # Compile, and leave both legs the same registry to map from.
        _serve(b, [prompts[0], _HEADER + "warm fused"], max_new_tokens=4)
        b.config.ragged_attention = False
        _serve(b, [_HEADER + "warm split"], max_new_tokens=4)
        texts_on, d_on = leg(True)
        texts_off, d_off = leg(False)
    finally:
        b.close()
    assert texts_on == texts_off  # the PR-8 parity contract
    t_on, t_off = _mbu_totals(d_on), _mbu_totals(d_off)
    assert t_on["kv_read_tokens"] == t_off["kv_read_tokens"] > 0
    assert t_on["kv_write_tokens"] == t_off["kv_write_tokens"] > 0
    # Fusion ran fewer programs for the same work — fewer weight
    # streams, so the modeled bytes drop by (programs saved) * tree.
    progs_on = d_on["mbu_programs_fused"] + d_on["mbu_programs_decode"] + (
        d_on["mbu_programs_prefill"]
    )
    progs_off = d_off["mbu_programs_decode"] + d_off["mbu_programs_prefill"]
    assert d_on["mbu_programs_fused"] > 0 and d_off["mbu_programs_fused"] == 0
    assert progs_on < progs_off
    bytes_on = sum(
        d_on[f"mbu_hbm_bytes_{k}"] for k in ("fused", "decode", "prefill")
    )
    bytes_off = sum(
        d_off[f"mbu_hbm_bytes_{k}"] for k in ("fused", "decode", "prefill")
    )
    w_bytes, _ = model_param_bytes(params)
    assert bytes_off - bytes_on == (progs_off - progs_on) * w_bytes


def test_cost_model_spec_on_off_write_parity(params):
    """Target-pool KV writes are emitted-text-invariant across the
    speculation flip when the token budget is round-aligned: spec
    writes k+1 positions per round (rewinds are count bookkeeping, the
    traffic happened) and at self-draft acceptance 1.0 each round
    commits k+1 tokens, so a budget of 1 + m*(k+1) tokens makes the
    written totals exactly equal — the cost model must agree. Per
    verify round the target reads its pages ONCE for all k+1 queries
    (the ragged kernel's whole point), so spec READ totals come in
    BELOW the plain leg's k+1 separate programs.

    Prompts are UNIQUE from byte 0 and shorter than one page: no
    shared-prefix groups means no donor draft streams, whose catch-up
    fills can legitimately produce a short round for a staggered
    panel mate (measured: 3,3,1 emissions — correct, but not
    round-aligned), and no full-page registration means the second
    leg's prefill work matches the first's exactly."""
    k = 2
    new_tokens = 1 + 2 * (k + 1)  # first token from prefill + 2 rounds
    b = ContinuousBatcher(
        CFG,
        params,
        config=ContinuousConfig(
            **dict(_CCFG, max_new_tokens=new_tokens),
            pipeline_depth=1,
            spec_k=k,
        ),
        draft=(CFG, params),  # self-draft: the acceptance-1.0 ceiling
    )
    prompts = ["aaa question 1", "bbb question 2", "ccc question 3"]

    def leg(spec_on: bool):
        b.config.spec_decode = spec_on
        _quiesce(b)
        s0 = b.stats()
        outs = _serve(b, prompts, max_new_tokens=new_tokens)
        _quiesce(b)
        s1 = b.stats()
        d = {k_: s1[k_] - s0[k_] for k_ in s1 if k_.startswith("mbu_")}
        return outs, d

    try:
        for on in (True, False):  # compile both program families
            b.config.spec_decode = on
            _serve(b, [_HEADER + f"warm {on}"], max_new_tokens=new_tokens)
        outs_on, d_on = leg(True)
        outs_off, d_off = leg(False)
    finally:
        b.close()
    assert [o.text for o in outs_on] == [o.text for o in outs_off]
    # Round-aligned budget actually filled (no early EOS/stop): the
    # exact-parity precondition.
    assert all(o.num_tokens == new_tokens for o in outs_on)
    assert d_on["mbu_programs_spec"] > 0 and d_off["mbu_programs_spec"] == 0
    t_on, t_off = _mbu_totals(d_on), _mbu_totals(d_off)
    assert t_on["kv_write_tokens"] == t_off["kv_write_tokens"] > 0
    assert t_on["kv_read_tokens"] < t_off["kv_read_tokens"]
    # The per-request summaries carry the speculation tallies.
    for o in outs_on:
        t = o.timing
        assert t["spec_rounds"] == 2
        assert t["spec_accepted_per_round"] == pytest.approx(k)


def test_attention_pages_read_hand_count(params):
    """``attn_pages_read`` of the cost model against a count by hand:
    pages of 16 tokens; rows A (40 tokens), B (33) share their first two
    pages as a group, C (16) and D (70) walk alone; a chunk lane ending
    at token 48."""
    from types import SimpleNamespace

    b = ContinuousBatcher(
        CFG.with_(use_pallas=True), params, config=ContinuousConfig(**_CCFG)
    )
    try:
        assert b._group_decode
        b._groups.add(0, (7, 8))
        b._groups.add(1, (7, 8, 9))
        b._groups.arrays()
        rows = [
            (i, SimpleNamespace(prompt_len=n - 2, generated=[1, 2]))
            for i, n in enumerate((40, 33, 16, 70))
        ]
        decode = b._program_cost("decode", rows, 1)
        fused = b._program_cost("fused", rows, 1, chunk_ext=[(48, 16)])
        rounds = b._program_cost("decode", rows, 2)
        prefill = b._program_cost("prefill", [], 0, chunk_ext=[(48, 16)])
    finally:
        b.close()
    # 3 + 3 + 1 + 5 pages under the fills, the shared two read once.
    assert decode["attn_pages_read"] == 12 - 2
    assert decode["kv_read_tokens"] == 40 + 33 + 16 + 70 - 32
    assert fused["attn_pages_read"] == 10 + 3
    # Two kernel calls: fills 40/41, 33/34, 16/17 (a second page), 70/71.
    assert rounds["attn_pages_read"] == (3 + 3) + (3 + 3) + (1 + 2) + (
        5 + 5
    ) - 2 * 2
    assert prefill["attn_pages_read"] == 3


def test_attention_read_counters_lockstep_on_a_burst(params):
    """``gateway_attention_pages_read_total`` and
    ``gateway_attention_tokens_read_total`` move with their ``stats()``
    mirrors, kind by kind, over a burst with grouped rows (a shared
    header), an ungrouped one and fused chunks; a page holds at most 16
    tokens, so pages x 16 bounds the tokens from above."""
    b = ContinuousBatcher(
        CFG.with_(use_pallas=True), params, config=ContinuousConfig(**_CCFG)
    )
    kinds = ("fused", "decode", "prefill")

    def read():
        snap = REGISTRY.snapshot()
        st = b.stats()
        return {
            (name, kind): (
                snap.get(f'gateway_attention_{name}_total{{kind="{kind}"}}', 0),
                st[f"mbu_{key}_{kind}"],
            )
            for name, key in (
                ("pages_read", "attn_pages_read"),
                ("tokens_read", "kv_read_tokens"),
            )
            for kind in kinds
        }

    try:
        r0 = read()
        _serve(
            b,
            [_HEADER + f"tail {i}" for i in range(3)] + ["alone, no header"],
            max_new_tokens=6,
        )
        _quiesce(b)
        r1 = read()
        peak_group = b.stats()["decode_group_peak"]
    finally:
        b.close()
    d = {k: (r1[k][0] - r0[k][0], r1[k][1] - r0[k][1]) for k in r1}
    assert all(metric == mirror for metric, mirror in d.values()), d
    assert peak_group >= 2  # the header's rows did decode as a group
    for kind in kinds:
        pages, tokens = d["pages_read", kind][0], d["tokens_read", kind][0]
        assert pages * 16 >= tokens, (kind, pages, tokens)
    assert d["pages_read", "decode"][0] > 0
    assert d["pages_read", "fused"][0] + d["pages_read", "prefill"][0] > 0


def test_mbu_gauge_published_with_peak_configured(params):
    b = ContinuousBatcher(
        CFG,
        params,
        config=ContinuousConfig(**_CCFG, hbm_gbps=1.0),
    )
    try:
        st0 = b.stats()
        _serve(b, [_HEADER + "mbu probe"], max_new_tokens=8)
        _quiesce(b)
        st1 = b.stats()
    finally:
        b.close()
    assert st1["mbu_programs_decode"] - st0["mbu_programs_decode"] > 0
    assert st1["mbu_seconds_decode"] > st0["mbu_seconds_decode"]
    snap = REGISTRY.snapshot()
    mbu = {
        key: v for key, v in snap.items() if key.startswith("gateway_program_mbu")
    }
    assert 'gateway_program_mbu{kind="decode"}' in mbu
    assert all(v > 0 for v in mbu.values())


# ---------------------------------------------------------------------------
# Gateway: /debug/flight, /debug/requests, response meta, shed events
# ---------------------------------------------------------------------------


@pytest.fixture()
def gateway(params):
    from llm_consensus_tpu.server.gateway import (
        Gateway,
        GatewayConfig,
        GatewayThread,
    )

    b = ContinuousBatcher(CFG, params, config=ContinuousConfig(**_CCFG))
    gw = Gateway(
        ContinuousBackend(b),
        config=GatewayConfig(port=0),
        registry=MetricsRegistry(),
    )
    handle = GatewayThread(gw).start()
    yield gw, handle, b
    handle.drain()
    b.close()


def test_gateway_flight_and_requests_on_live_burst(gateway):
    from llm_consensus_tpu.server.client import GatewayClient

    gw, handle, b = gateway
    client = GatewayClient("127.0.0.1", handle.port, timeout=180)
    flight.flight_recorder().clear()
    n = 6
    results = [None] * n
    errs = []

    def one(i):
        try:
            results[i] = client.generate(
                _HEADER + f"gw {i}", max_new_tokens=6
            )
        except Exception as e:  # noqa: BLE001 - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not errs and all(r is not None for r in results)
    _quiesce(b)
    # Response meta IS the /debug/requests summary, keyed both ways.
    r0 = results[0]
    assert r0["meta"]["trace_id"] == r0["trace_id"]
    by_rid = client.requests(r0["meta"]["id"])
    by_trace = client.requests(r0["trace_id"])
    assert by_rid == by_trace == r0["meta"]
    listing = client.requests()
    got = {d["id"] for d in listing["requests"]}
    assert {r["meta"]["id"] for r in results} <= got
    # The flight ring served over HTTP, plain and Chrome forms.
    fl = client.flight()
    assert fl["enabled"] is True and fl["n_events"] > 0
    kinds = {e["kind"] for e in fl["events"]}
    assert {"admit", "program", "request"} <= kinds
    doc = client.flight(format="chrome")
    assert all(
        {"ts", "ph", "pid", "tid"} <= set(e) for e in doc["traceEvents"]
    )
    assert sum(
        1 for e in doc["traceEvents"] if e.get("cat") == "request"
    ) >= n
    # Unknown id -> 404.
    from llm_consensus_tpu.server.client import GatewayHTTPError

    with pytest.raises(GatewayHTTPError) as ei:
        client.requests("req-nope")
    assert ei.value.status == 404
    # gateway_ttft_seconds (gateway surface) moved once per request —
    # the request-level lockstep with the batcher-side ttft mirror.
    snap = gw.registry.snapshot()
    assert snap["gateway_ttft_seconds_count"] == n


def test_gateway_shed_records_flight_event(params):
    from llm_consensus_tpu.backends.fake import FakeBackend
    from llm_consensus_tpu.server.admission import AdmissionConfig
    from llm_consensus_tpu.server.client import (
        GatewayClient,
        GatewayHTTPError,
    )
    from llm_consensus_tpu.server.gateway import (
        Gateway,
        GatewayConfig,
        GatewayThread,
    )

    gw = Gateway(
        FakeBackend(latency=0.5),
        config=GatewayConfig(
            port=0,
            admission=AdmissionConfig(max_queue=1, max_inflight=1),
        ),
        registry=MetricsRegistry(),
    )
    handle = GatewayThread(gw).start()
    client = GatewayClient("127.0.0.1", handle.port, timeout=60)
    flight.flight_recorder().clear()
    sheds = []

    def one(i):
        try:
            client.generate(f"burst {i}", max_new_tokens=4)
        except GatewayHTTPError as e:
            if e.status == 429:
                sheds.append(i)

    try:
        threads = [
            threading.Thread(target=one, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        handle.drain()
    assert sheds, "overload burst produced no 429s — resize the test"
    evs = [
        e for e in flight.flight_recorder().events() if e.kind == "shed"
    ]
    assert len(evs) == len(sheds)
    assert all(e.meta["route"] == "/v1/generate" for e in evs)


# ---------------------------------------------------------------------------
# The switch: a burst with the recorder off
# ---------------------------------------------------------------------------


def test_burst_with_the_recorder_off_serves_the_same_text(params):
    """One batcher, the recorder flipped between two identical bursts:
    on, the burst leaves program, admit and token events in the ring;
    off (``serve --no-flight``), every instrumentation site gets None
    back and the burst serves the same text and counts, recording
    nothing."""
    prompts = [_HEADER + f"switch {i}" for i in range(3)]
    rec = flight.flight_recorder()
    b = ContinuousBatcher(CFG, params, config=ContinuousConfig(**_CCFG))
    try:
        rec.clear()
        on = [(r.text, r.num_tokens) for r in _serve(b, prompts)]
        _quiesce(b)
        kinds_on = {e.kind for e in rec.events()}
        rec.clear()
        flight.set_enabled(False)
        try:
            off = [(r.text, r.num_tokens) for r in _serve(b, prompts)]
            _quiesce(b)
            recorded_off = len(rec)
        finally:
            flight.set_enabled(True)
    finally:
        b.close()
    assert {"program", "admit"} <= kinds_on, kinds_on
    assert recorded_off == 0
    assert off == on
