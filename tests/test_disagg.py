"""Disaggregated prefill/decode serving (PR 16).

Covers the role split (:mod:`llm_consensus_tpu.serving.disagg`) and the
remote page-store transport (:mod:`~.serving.remote_store`) end to end:
role resolution and the prefill config specialization, the
length-prefixed wire protocol round-trips pages bit-exactly over a real
TCP socket, every remote-store failure mode (server down at
construction, mid-put disconnect, slow peer hitting the client timeout)
degrades to a local miss with a warning and a
``gateway_remote_store_errors_total`` bump rather than wedging, a roled
fleet over the remote store streams byte-identical text versus a
mixed-role control with >= 1 prefill->decode handoff and ZERO header
pages re-prefilled on the decode side, a roled fleet over a DEAD store
still completes every request with a fresh heartbeat, the controller's
restore-batch knob follows the overhead EWMA, the gateway's
``/debug/chains`` probe and cross-host peer forwarding route by
residency, and the store served by a process of its own
(``python -m llm_consensus_tpu.serving.remote_store``) hands pages
across the process boundary and, killed, turns into misses.
"""

import json
import logging
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.backends.fake import FakeBackend
from llm_consensus_tpu.engine.tokenizer import ByteTokenizer
from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.transformer import init_params
from llm_consensus_tpu.server.admission import AdmissionConfig
from llm_consensus_tpu.server.client import GatewayClient, GatewayHTTPError
from llm_consensus_tpu.server.gateway import (
    Gateway,
    GatewayConfig,
    GatewayThread,
)
from llm_consensus_tpu.server.metrics import REGISTRY, MetricsRegistry
from llm_consensus_tpu.serving import flight
from llm_consensus_tpu.serving.continuous import ContinuousConfig
from llm_consensus_tpu.serving.control import (
    KNOBS,
    AdaptiveController,
    ControlConfig,
)
from llm_consensus_tpu.serving.disagg import (
    ROLES,
    HandoffCoordinator,
    resolve_roles,
    role_config,
)
from llm_consensus_tpu.serving.fleet import (
    FleetConfig,
    ReplicaSet,
)
from llm_consensus_tpu.serving.offload import HostPageStore
from llm_consensus_tpu.serving.remote_store import (
    PageStoreServer,
    RemotePageStore,
    parse_endpoint,
)

CFG = get_config("test-tiny")

# 49 chars -> 3 full 16-token pages + a tail at page_size 16.
_HEADER = "Panel shared header for every persona, forty ch: "

_FCFG = dict(
    max_slots=2,
    page_size=16,
    n_pages=32,
    pages_per_seq=8,
    max_new_tokens=4,
    seq_buckets=(16, 32, 64),
    prefill_chunk=16,
    share_prefix=True,
    host_cache_bytes=64 << 20,
)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def _serve(target, prompts, **kw):
    futs = [target.submit(p, **kw) for p in prompts]
    return [f.result(timeout=300) for f in futs]


def _dead_endpoint():
    """A (host, port) nothing listens on: bind, read the port, close."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return ("127.0.0.1", port)


def _errors_total() -> float:
    return REGISTRY.get("gateway_remote_store_errors_total").value


# ---------------------------------------------------------------------------
# Role resolution and the prefill config specialization (units)
# ---------------------------------------------------------------------------


def test_resolve_roles_broadcast_and_tuple():
    assert resolve_roles("mixed", 3) == ("mixed",) * 3
    assert resolve_roles("decode", 1) == ("decode",)
    assert resolve_roles(("prefill", "decode"), 2) == ("prefill", "decode")
    assert resolve_roles(["prefill", "mixed"], 2) == ("prefill", "mixed")


def test_resolve_roles_rejects_bad_splits():
    with pytest.raises(ValueError, match="2 entries for 3"):
        resolve_roles(("prefill", "decode"), 3)
    with pytest.raises(ValueError, match="unknown replica role"):
        resolve_roles(("prefill", "verifier"), 2)
    # A prefill-only fleet can never stream a token.
    with pytest.raises(ValueError, match="decode-capable"):
        resolve_roles("prefill", 2)
    with pytest.raises(ValueError, match="decode-capable"):
        resolve_roles(("prefill", "prefill"), 2)
    assert "mixed" in ROLES and "prefill" in ROLES and "decode" in ROLES


def test_role_config_prefill_copy_vs_shared_instance():
    base = ContinuousConfig(
        **{**_FCFG, "spec_decode": True, "spec_k": 2, "decode_rounds": 2}
    )
    pre = role_config(base, "prefill")
    assert pre is not base
    assert pre.spec_decode is False and pre.decode_rounds == 1
    # Everything outside the decode-phase machinery is untouched, so
    # the PR-14 store-key scope (which excludes both fields) matches.
    assert pre.page_size == base.page_size
    assert pre.prefill_chunk == base.prefill_chunk
    # Decode/mixed replicas SHARE the live config instance — the
    # fleet-wide knob-flip lever must keep working.
    assert role_config(base, "decode") is base
    assert role_config(base, "mixed") is base


def test_parse_endpoint_forms():
    assert parse_endpoint("tcp://10.0.0.7:9000") == ("tcp", ("10.0.0.7", 9000))
    assert parse_endpoint("10.0.0.7:9000") == ("tcp", ("10.0.0.7", 9000))
    assert parse_endpoint(("127.0.0.1", 123)) == ("tcp", ("127.0.0.1", 123))
    assert parse_endpoint("uds:///tmp/pages.sock") == ("uds", "/tmp/pages.sock")
    assert parse_endpoint("/tmp/pages.sock") == ("uds", "/tmp/pages.sock")


# ---------------------------------------------------------------------------
# Remote store: wire round-trip (real TCP socket)
# ---------------------------------------------------------------------------


def test_remote_store_round_trip_preserves_planes():
    store = HostPageStore(budget_bytes=64 << 20)
    server = PageStoreServer(store)
    server.start()
    rtt0 = REGISTRY.get("gateway_remote_store_rtt_seconds").count
    client = RemotePageStore(server.endpoint, timeout_s=5.0)
    try:
        key = ("chain", 0, 7, 42)
        import ml_dtypes

        planes = (
            np.arange(512, dtype=np.int8).reshape(4, 128),
            np.full((4, 1), 0.5, dtype=np.float32),
            # The KV pool's real dtype: bfloat16 must survive the wire
            # (its ``.str`` form is an opaque void code jax rejects).
            np.arange(64, dtype=np.float32)
            .astype(ml_dtypes.bfloat16)
            .reshape(8, 8),
        )
        resident, demoted, dropped = client.put_counted(key, planes)
        assert resident is True and demoted == 1 and dropped == 0
        assert key in store  # landed in the AUTHORITATIVE store
        assert key in client
        got = client.get(key)
        assert got is not None and len(got) == len(planes)
        for a, b in zip(planes, got):
            assert b.dtype == a.dtype and b.shape == a.shape
            assert np.array_equal(a, b)
        assert client.touch(key) is True
        assert client.touch(("missing",)) is False
        # Piggybacked stats mirror the authoritative store.
        assert len(client) == 1
        assert client.bytes_used == store.bytes_used > 0
        assert client.headroom_bytes == store.headroom_bytes
        assert client.errors == 0
        # Prometheus lockstep: the bytes gauge carries the last
        # exchange's piggybacked view, the RTT histogram observed
        # every successful exchange.
        assert (
            REGISTRY.get("gateway_remote_store_bytes").value
            == store.bytes_used
        )
        assert REGISTRY.get("gateway_remote_store_rtt_seconds").count > rtt0
    finally:
        client.close()
        server.close()


# ---------------------------------------------------------------------------
# Remote store: failure modes degrade to local miss (satellite 3)
# ---------------------------------------------------------------------------


def test_remote_store_server_down_at_construction(caplog):
    e0 = _errors_total()
    with caplog.at_level(
        logging.WARNING, logger="llm_consensus_tpu.serving.remote_store"
    ):
        client = RemotePageStore(
            _dead_endpoint(), timeout_s=0.5, retry_s=0.05
        )
        planes = (np.zeros((2, 4), dtype=np.int8),)
        assert client.put_counted(("k",), planes) == (False, 0, 1)
        assert client.put(("k",), planes) is False
        assert client.get(("k",)) is None
        assert ("k",) not in client
        assert client.touch(("k",)) is False
    # Every failed op counts; reads of the cached stats cost nothing.
    assert client.errors >= 1
    assert _errors_total() - e0 >= client.errors >= 2
    assert client.headroom_bytes == 0  # outage reads as zero headroom
    warned = [
        r
        for r in caplog.records
        if "degrading to local miss" in r.getMessage()
    ]
    # Warn once per outage TRANSITION, not once per failed op.
    assert len(warned) == 1
    client.close()


def test_remote_store_mid_put_disconnect():
    """A peer that accepts then drops the connection mid-exchange:
    the put degrades to (False, 0, 1) instead of raising or hanging."""
    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(8)
    stop = threading.Event()

    def accept_and_slam():
        while not stop.is_set():
            try:
                c, _ = srv.accept()
            except OSError:
                return
            c.close()

    t = threading.Thread(target=accept_and_slam, daemon=True)
    t.start()
    e0 = _errors_total()
    client = RemotePageStore(
        srv.getsockname(), timeout_s=0.5, retry_s=0.0
    )
    try:
        planes = (np.ones((4, 16), dtype=np.float32),)
        assert client.put_counted(("mid",), planes) == (False, 0, 1)
        assert client.errors >= 1
        assert _errors_total() > e0
    finally:
        client.close()
        stop.set()
        srv.close()


def test_remote_store_slow_peer_hits_client_timeout():
    """A peer that accepts and never replies: the configured client
    timeout bounds the stall, then the op degrades to a local miss."""
    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(8)
    held: list[socket.socket] = []
    stop = threading.Event()

    def accept_and_hold():
        while not stop.is_set():
            try:
                c, _ = srv.accept()
            except OSError:
                return
            held.append(c)  # read nothing, reply never

    t = threading.Thread(target=accept_and_hold, daemon=True)
    t.start()
    e0 = _errors_total()
    client = RemotePageStore(
        srv.getsockname(), timeout_s=0.2, retry_s=0.0
    )
    try:
        t0 = time.monotonic()
        assert client.get(("slow",)) is None
        assert time.monotonic() - t0 < 3.0  # bounded by timeout_s
        assert client.errors >= 1
        assert _errors_total() > e0
    finally:
        client.close()
        stop.set()
        srv.close()
        for c in held:
            c.close()


# ---------------------------------------------------------------------------
# Controller: restore-batch sizing from measured overhead (satellite 2)
# ---------------------------------------------------------------------------


def test_restore_batch_follows_overhead_ewma():
    assert "restore_batch" in KNOBS
    ctl = AdaptiveController(ControlConfig())
    cap = ctl.config.restore_batch_max
    assert cap == 8
    # Cold start: overhead unknown, nothing to stall -> full batch.
    assert ctl.restore_batch() == cap
    # Overhead invisible (fully overlapped) -> drain one page per
    # iteration, the classic PR-9 pace.
    ctl.note_overhead(ctl.config.overhead_low_s / 2)
    assert ctl.restore_batch() == 1
    # Overhead visible again -> full batch amortizes the flushes.
    for _ in range(200):
        ctl.note_overhead(ctl.config.overhead_high_s * 10)
    assert ctl.restore_batch() == cap
    st = ctl.stats()
    assert st["autotune_restore_batch"] == cap
    assert st["autotune_decisions_restore_batch"] >= 2  # cap->1->cap


def test_restore_batch_midband_and_disabled():
    mid = AdaptiveController(ControlConfig())
    mid.note_overhead(
        (mid.config.overhead_low_s + mid.config.overhead_high_s) / 2
    )
    assert mid.restore_batch() == max(1, mid.config.restore_batch_max // 2)
    off = AdaptiveController(ControlConfig(tune_restore_batch=False))
    off.note_overhead(off.config.overhead_low_s / 2)
    # Tuning off: the static cap, with no decision recorded.
    assert off.restore_batch() == off.config.restore_batch_max
    assert off.stats()["autotune_decisions_restore_batch"] == 0


# ---------------------------------------------------------------------------
# Roled fleet over the remote store: byte parity + zero re-prefill
# ---------------------------------------------------------------------------


def test_roled_fleet_over_remote_store_byte_parity(params):
    """The acceptance scenario: a ("prefill", "decode") fleet whose
    shared page store is a REMOTE server process boundary away streams
    byte-identical text versus a mixed-role control, with >= 1 chain
    handoff and zero header pages re-prefilled on the decode side."""
    page = _FCFG["page_size"]
    tok = ByteTokenizer()
    header_pages = len(tok.encode(_HEADER)) // page
    assert header_pages >= 3
    # BOS + 49 header chars + short tail stays inside the 64 bucket —
    # a longer tail would left-truncate away the shared header.
    prompts = [f"{_HEADER}p{i}?" for i in range(4)]

    def run(role, host_store=None):
        fleet = ReplicaSet(
            CFG,
            params,
            config=ContinuousConfig(**_FCFG),
            fleet=FleetConfig(replicas=2, role=role),
            host_store=host_store,
        )
        try:
            results = _serve(
                fleet, prompts, max_new_tokens=4, temperature=0.0
            )
            return [r.text for r in results], results, fleet.stats()
        finally:
            fleet.close()

    texts_mix, _, _ = run("mixed")

    store = HostPageStore(budget_bytes=64 << 20)
    server = PageStoreServer(store)
    server.start()
    client = RemotePageStore(server.endpoint, timeout_s=10.0)
    h0 = REGISTRY.get("gateway_role_handoffs_total").value
    f0 = sum(1 for e in flight.flight_recorder().events() if e.kind == "handoff")
    try:
        texts_dis, results, stats = run(
            ("prefill", "decode"), host_store=client
        )
    finally:
        client.close()
        server.close()

    # Byte parity with the mixed-role control (PR-4 restore contract).
    assert texts_dis == texts_mix
    # The handoff happened and is mirrored in fleet stats, the
    # process-global Prometheus family, and the flight recorder.
    assert stats["role_handoffs"] >= 1
    assert (
        REGISTRY.get("gateway_role_handoffs_total").value - h0
        == stats["role_handoffs"]
    )
    f1 = sum(1 for e in flight.flight_recorder().events() if e.kind == "handoff")
    assert f1 - f0 == stats["role_handoffs"]
    # Role surface: per-replica roles reported, prefill never routed.
    assert stats["roles"] == ["prefill", "decode"]
    assert stats["per_replica"][0]["role"] == "prefill"
    # Zero header pages re-prefilled on the decode side: every mate's
    # full header arrived shared (registry-resident) or restored from
    # the remote store.
    restored = 0
    for r in results:
        tm = r.timing
        covered = tm["header_pages_shared"] + tm["header_pages_restored"]
        assert covered >= header_pages, tm
        restored += tm["header_pages_restored"]
    assert restored >= 1  # at least one mate pulled pages over the wire
    # The export landed in the AUTHORITATIVE (server-side) store.
    assert store.demoted_pages >= 1


def test_roled_fleet_dead_store_never_wedges(params):
    """A roled fleet whose remote store endpoint is dead: every
    request still completes (degrade = recompute), the remote-store
    error counter moves, and the serving loops' heartbeats stay
    fresh — the worker loop never blocks on the dead socket."""
    client = RemotePageStore(_dead_endpoint(), timeout_s=0.3, retry_s=0.05)
    e0 = _errors_total()
    fleet = ReplicaSet(
        CFG,
        params,
        config=ContinuousConfig(**_FCFG),
        fleet=FleetConfig(replicas=2, role=("prefill", "decode")),
        host_store=client,
    )
    try:
        prompts = [f"{_HEADER}m{i}?" for i in range(4)]
        results = _serve(fleet, prompts, max_new_tokens=4, temperature=0.0)
        assert len(results) == 4
        # num_tokens, not text: a random model's argmax lands in the
        # padded vocab tail the byte tokenizer decodes to nothing.
        assert all(r.num_tokens > 0 for r in results)
        assert _errors_total() > e0
        hb = fleet.heartbeat()
        assert hb["alive"] is True
        assert hb["last_tick_age_s"] < 5.0
    finally:
        fleet.close()
        client.close()


def test_handoff_dedup_claims_once_per_chain():
    """The dedup table admits ONE warm-up per chain per TTL window —
    a 4-mate panel burst must not warm the same header four times."""

    class _Fleet:
        pass

    co = HandoffCoordinator(_Fleet())
    chain = (("page", 0),)
    assert co._dedup_claim(chain) is True
    assert co._dedup_claim(chain) is False  # live claim
    other = (("page", 1),)
    assert co._dedup_claim(other) is True


# ---------------------------------------------------------------------------
# Gateway: /debug/chains probe + cross-host peer forwarding
# ---------------------------------------------------------------------------


def _boot(backend, admission=None, **gw_kw):
    reg = MetricsRegistry()
    gw = Gateway(
        backend,
        config=GatewayConfig(
            port=0, admission=admission or AdmissionConfig(), **gw_kw
        ),
        registry=reg,
    )
    handle = GatewayThread(gw).start()
    return handle, GatewayClient("127.0.0.1", handle.port), reg


def _probed_backend(registry_tokens=0, host_tokens=0):
    fb = FakeBackend()
    fb.prefix_probe = lambda ids: {
        "registry_tokens": registry_tokens,
        "host_tokens": host_tokens,
    }
    fb.tokenizer = ByteTokenizer()
    return fb


def test_debug_chains_endpoint():
    handle, client, _ = _boot(_probed_backend(registry_tokens=48))
    try:
        doc = client._json("GET", "/debug/chains?ids=1,2,3")
        # PR 20: the probe reply carries a clock-probe stamp too.
        assert doc.pop("now_pc") > 0
        assert doc == {"n_ids": 3, "registry_tokens": 48, "host_tokens": 0}
        n = len(ByteTokenizer().encode("hi"))
        doc = client._json("GET", "/debug/chains?prompt=hi")
        assert doc["n_ids"] == n and doc["registry_tokens"] == 48
        with pytest.raises(GatewayHTTPError) as e:
            client._json("GET", "/debug/chains")
        assert e.value.status == 400
        with pytest.raises(GatewayHTTPError) as e:
            client._json("GET", "/debug/chains?ids=1,nope")
        assert e.value.status == 400
    finally:
        handle.drain()
    # A backend without a probe (plain FakeBackend) 404s.
    handle, client, _ = _boot(FakeBackend())
    try:
        with pytest.raises(GatewayHTTPError) as e:
            client._json("GET", "/debug/chains?ids=1")
        assert e.value.status == 404
    finally:
        handle.drain()


def test_peer_forwarding_routes_by_residency():
    """Front gateway with two peers: the request lands on the peer
    whose /debug/chains reports the longest resident chain, and the
    response relays verbatim with an X-Peer header naming it."""
    warm_h, _, warm_reg = _boot(_probed_backend(registry_tokens=64))
    cold_h, _, cold_reg = _boot(_probed_backend(registry_tokens=0))
    warm_url = f"http://127.0.0.1:{warm_h.port}"
    cold_url = f"http://127.0.0.1:{cold_h.port}"
    front_h, front_client, _ = _boot(
        FakeBackend(), peers=(cold_url, warm_url)
    )
    try:
        resp, data = front_client._request(
            "POST", "/v1/generate", {"prompt": "route me"}
        )
        assert resp.getheader("X-Peer") == warm_url
        doc = json.loads(data)
        assert doc["text"] == "Echo: route me"
        # The warm peer served it; the cold peer saw only the probe.
        # (The peer's route counter lands AFTER its response bytes are
        # relayed — poll briefly instead of racing the handler tail.)
        deadline = time.monotonic() + 5.0
        while (
            time.monotonic() < deadline
            and 'route="/v1/generate"' not in warm_reg.render()
        ):
            time.sleep(0.02)
        assert 'route="/v1/generate"' in warm_reg.render()
        assert 'route="/v1/generate"' not in cold_reg.render()
    finally:
        front_h.drain()
        warm_h.drain()
        cold_h.drain()


def test_peer_forwarding_unreachable_peer_502s():
    dead = _dead_endpoint()
    front_h, front_client, _ = _boot(
        FakeBackend(), peers=(f"http://{dead[0]}:{dead[1]}",)
    )
    try:
        with pytest.raises(GatewayHTTPError) as e:
            front_client.generate("hello")
        assert e.value.status == 502
        assert "unreachable" in e.value.body
    finally:
        front_h.drain()


# ---------------------------------------------------------------------------
# The store as a process of its own
# ---------------------------------------------------------------------------


def test_store_process_serves_pages_and_killed_degrades_to_misses():
    """``python -m llm_consensus_tpu.serving.remote_store``: its first
    line names the endpoint, a page put by one client is read back bit
    for bit by another (the handoff's path between replicas in
    different processes), and once the process is KILLED a read is a
    miss with the error counted — never a hang, never an exception."""
    import ml_dtypes

    proc = subprocess.Popen(
        [
            sys.executable, "-m", "llm_consensus_tpu.serving.remote_store",
            "--budget-mb", "8", "--port", "0",
        ],
        cwd=Path(__file__).resolve().parent.parent,
        stdout=subprocess.PIPE,
        text=True,
    )
    writer = reader = None
    try:
        endpoint = json.loads(proc.stdout.readline())["endpoint"]
        writer = RemotePageStore(endpoint, timeout_s=10.0)
        reader = RemotePageStore(endpoint, timeout_s=2.0, retry_s=0.05)
        key = ("chain", 0, 7, 42)
        planes = (
            np.arange(64, dtype=np.float32)
            .astype(ml_dtypes.bfloat16)
            .reshape(8, 8),
            np.arange(512, dtype=np.int8).reshape(4, 128),
        )
        resident, demoted, dropped = writer.put_counted(key, planes)
        assert (resident, demoted, dropped) == (True, 1, 0)
        got = reader.get(key)
        assert got is not None and len(got) == len(planes)
        for a, b in zip(planes, got):
            assert b.dtype == a.dtype and a.tobytes() == b.tobytes()
        assert reader.errors == 0

        proc.kill()
        proc.wait(timeout=30)
        e0 = _errors_total()
        assert reader.get(key) is None
        assert reader.errors >= 1
        assert _errors_total() > e0
    finally:
        for client in (writer, reader):
            if client is not None:
                client.close()
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=30)
        proc.stdout.close()
