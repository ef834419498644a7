"""Zero-copy pipelined KV movement plane (PR 17).

Covers the v2 scatter-gather wire format end to end: multi-dtype plane
round trips land bit-exact through ``sendmsg``/``recv_into`` with NO
pickled plane bytes, v1 and v2 clients interoperate against one server,
the batched ops (``put_many``/``get_run``/``touch_many``/``run_len``)
keep per-key semantics in one round trip, pipelined concurrent ops over
a single connection dispatch by sequence tag under thread pressure,
the ``gateway_transfer_bytes_total`` family moves in lockstep with the
client's tx/rx mirrors, fuzzed/truncated frames drop one connection
without wedging the server, ``_send_vec`` survives partial sends and
iovec chunking against a slow consumer, TCP_NODELAY is set on both
ends, and a server killed mid-pipeline fails every in-flight op to a
miss (the circuit-breaker degrade contract). The serving-layer half:
streamed exports spill incrementally and respect their deadline,
route-driven prefetch stages store pages ahead of admission (consumed
as restore-plan hits) while a wrong/cold guess falls through to
recompute with byte-identical text, and the handoff-latency histogram
moves in lockstep with fleet stats on a roled fleet.
"""

import hashlib
import socket
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.transformer import init_params
from llm_consensus_tpu.server.metrics import (
    HANDOFF_SECONDS,
    KV_PREFETCH,
    TRANSFER_BYTES,
)
from llm_consensus_tpu.serving import flight
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)
from llm_consensus_tpu.serving.fleet import FleetConfig, ReplicaSet
from llm_consensus_tpu.serving.offload import HostPageStore
from llm_consensus_tpu.serving.remote_store import (
    _IOV_MAX,
    _LEN,
    _MAGIC,
    _PRELUDE,
    PageStoreServer,
    RemotePageStore,
    _send_vec,
    parse_endpoint,
)

CFG = get_config("test-tiny")

# 49 chars -> 3 full 16-token pages + a tail at page_size 16.
_HEADER = "Panel shared header for every persona, forty ch: "

_SCFG = dict(
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
    return [f.result(timeout=300).text for f in futs]


def _planes(seed=0, kib=1):
    """A 2-plane bf16-ish page entry: bf16 K plane (the pool's real
    dtype, an ml_dtypes extension type numpy can't name natively) and
    an f32 V plane — the dtype-by-NAME wire contract's hard case."""
    import ml_dtypes

    rng = np.random.default_rng(seed)
    k = (rng.standard_normal(kib * 512) * 4).astype(ml_dtypes.bfloat16)
    v = (rng.standard_normal(kib * 256) * 4).astype(np.float32)
    return (k, v)


def _bits(planes):
    return tuple((p.dtype.name, p.shape, p.tobytes()) for p in planes)


def _live_pair(**kw):
    store = HostPageStore(budget_bytes=64 << 20)
    server = PageStoreServer(store).start()
    client = RemotePageStore(server.endpoint, timeout_s=10.0, **kw)
    return store, server, client


# ---------------------------------------------------------------------------
# Wire v2: zero-copy round trips, interop, batched ops
# ---------------------------------------------------------------------------


def test_wire_v2_round_trip_multi_dtype():
    """bf16 + f32 + int8-with-scales entries cross the scatter-gather
    wire bit-exact, dtypes resolved by name through ml_dtypes."""
    store, server, client = _live_pair()
    try:
        pages = {
            ("chain", 0): _planes(seed=0),
            ("chain", 1): _planes(seed=1),
            ("int8", 0): (
                np.arange(-64, 64, dtype=np.int8).reshape(8, 16),
                np.linspace(0.1, 2.0, 8, dtype=np.float32),
            ),
        }
        for key, planes in pages.items():
            resident, demoted, dropped = client.put_counted(key, planes)
            assert resident and demoted == 1 and dropped == 0
        for key, planes in pages.items():
            assert key in client
            got = client.get(key)
            assert _bits(got) == _bits(planes)
        assert client.get(("missing",)) is None
        # The authoritative copy landed server-side, verbatim.
        assert _bits(store.get(("chain", 0))) == _bits(pages[("chain", 0)])
        assert len(client) == 3  # piggybacked stats cache
    finally:
        client.close()
        server.close()


def test_wire_v1_v2_interop_one_server():
    """The server speaks both formats per frame: pages put by either
    client read back bit-exact through the other."""
    store, server, c2 = _live_pair()
    c1 = RemotePageStore(server.endpoint, timeout_s=10.0, wire="v1")
    try:
        old, new = _planes(seed=7), _planes(seed=8)
        assert c1.put(("via-v1",), old)
        assert c2.put(("via-v2",), new)
        assert _bits(c2.get(("via-v1",))) == _bits(old)
        assert _bits(c1.get(("via-v2",))) == _bits(new)
        # v1's loop-based batched fallbacks match v2's single frame.
        keys = [("via-v1",), ("via-v2",)]
        assert c1.run_len(keys) == c2.run_len(keys) == 2
        assert [_bits(p) for p in c1.get_run(keys)] == [
            _bits(p) for p in c2.get_run(keys)
        ]
    finally:
        c1.close()
        c2.close()
        server.close()


def test_batched_ops_semantics():
    """put_many/get_run/touch_many/run_len in ONE round trip keep the
    per-key contracts: runs stop at the first miss (chain keys are
    prefix-nested), touches report per-key residency."""
    store, server, client = _live_pair()
    try:
        items = [(("c", i), _planes(seed=i)) for i in range(4)]
        out = client.put_many(items)
        assert out == [(True, 1, 0)] * 4
        # A hole after key 1: the run and its probe stop there.
        probe = [("c", 0), ("c", 1), ("hole",), ("c", 3)]
        assert client.run_len(probe) == 2
        run = client.get_run(probe)
        assert len(run) == 2
        assert _bits(run[1]) == _bits(items[1][1])
        assert client.touch_many(probe) == [True, True, False, True]
        assert client.get_run([]) == [] and client.run_len([]) == 0
    finally:
        client.close()
        server.close()


def test_pipelined_concurrent_ops_bit_exact():
    """Many threads share ONE v2 connection: replies dispatch to their
    waiters by sequence tag, every round trip bit-exact, zero errors."""
    store, server, client = _live_pair()
    failures = []

    def worker(t):
        try:
            for i in range(8):
                key = ("t", t, i)
                planes = _planes(seed=t * 100 + i)
                assert client.put(key, planes)
                got = client.get(key)
                assert got is not None and _bits(got) == _bits(planes)
        except Exception as e:  # noqa: BLE001 - collected for the assert
            failures.append(repr(e))

    try:
        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(4)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not failures
        assert client.errors == 0
        assert len(store) == 32
    finally:
        client.close()
        server.close()


def test_transfer_bytes_family_lockstep():
    """Client tx/rx mirrors count exactly the plane payload bytes that
    crossed the wire, and the process-global
    ``gateway_transfer_bytes_total`` family moves by the same deltas."""
    store, server, client = _live_pair()
    try:
        tx0 = TRANSFER_BYTES.labels(dir="tx").value
        rx0 = TRANSFER_BYTES.labels(dir="rx").value
        ctx0, crx0 = client.tx_bytes, client.rx_bytes
        planes = _planes(seed=3)
        nbytes = sum(int(p.nbytes) for p in planes)
        assert client.put(("xfer",), planes)
        assert client.tx_bytes - ctx0 == nbytes
        assert client.rx_bytes == crx0  # put replies carry no planes
        assert client.get(("xfer",)) is not None
        assert client.rx_bytes - crx0 == nbytes
        assert TRANSFER_BYTES.labels(dir="tx").value - tx0 == nbytes
        assert TRANSFER_BYTES.labels(dir="rx").value - rx0 == nbytes
        # Planeless ops move nothing.
        client.refresh_stats()
        assert ("xfer",) in client
        assert client.tx_bytes - ctx0 == nbytes
        assert client.rx_bytes - crx0 == nbytes
    finally:
        client.close()
        server.close()


# ---------------------------------------------------------------------------
# Transport robustness: fuzzing, backpressure, NODELAY, mid-stream kill
# ---------------------------------------------------------------------------


def test_fuzzed_frames_drop_one_connection_only():
    """Bogus magic, oversized preludes, truncated bodies, and garbage
    v1 pickles each cost ONE connection; the listener and a well-formed
    client keep working after every one of them."""
    store, server, client = _live_pair()
    _, addr = parse_endpoint(server.endpoint)
    good = _planes(seed=9)
    assert client.put(("good",), good)

    def poke(raw: bytes):
        s = socket.create_connection(addr, timeout=5)
        try:
            s.sendall(raw)
            s.settimeout(2)
            try:
                while s.recv(4096):
                    pass  # drain until the server hangs up
            except (socket.timeout, OSError):
                pass  # a reset IS the hang-up
        finally:
            s.close()

    try:
        # Not v2 magic -> sniffed as a v1 length prefix of ~1.4 GiB:
        # past _MAX_FRAME, refused without allocation.
        poke(b"ZZZZ" + b"\x00" * 16)
        # v2 prelude claiming a header past the frame cap.
        poke(_PRELUDE.pack(_MAGIC, 2, 1, 1 << 30, 0))
        # v2 prelude with a plausible size but a truncated body.
        poke(_PRELUDE.pack(_MAGIC, 2, 2, 64, 4096) + b"\x01" * 10)
        # Valid v1 length prefix framing unpicklable bytes.
        poke(_LEN.pack(20) + b"\xde\xad\xbe\xef" * 5)
        # The server survived all four: same client, same connection
        # pool, bit-exact reads and fresh writes still work.
        assert _bits(client.get(("good",))) == _bits(good)
        assert client.put(("after",), _planes(seed=10))
        assert client.errors == 0
    finally:
        client.close()
        server.close()


def test_send_vec_backpressure_and_chunking():
    """_send_vec against a slow consumer with a tiny send buffer: the
    partial-send resume logic and >_IOV_MAX chunking both hit, and the
    byte stream arrives intact and ordered."""
    a, b = socket.socketpair()
    a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
    # 600 small views (> _IOV_MAX forces at least two sendmsg chunks)
    # plus two bulk planes to force partial sends at the buffer size.
    rng = np.random.default_rng(11)
    views = [rng.integers(0, 256, 37, dtype=np.uint8) for _ in range(600)]
    views += [rng.integers(0, 256, 256 << 10, dtype=np.uint8) for _ in range(2)]
    assert len(views) > _IOV_MAX
    want = hashlib.sha256()
    total = 0
    for v in views:
        want.update(v.tobytes())
        total += v.nbytes

    got = hashlib.sha256()
    received = 0

    def consume():
        nonlocal received
        while received < total:
            chunk = b.recv(8192)
            if not chunk:
                break
            got.update(chunk)
            received += len(chunk)
            time.sleep(0.001)  # slow consumer: keep the sender blocked

    t = threading.Thread(target=consume)
    t.start()
    try:
        _send_vec(a, [memoryview(v) for v in views])
    finally:
        a.close()
        t.join(timeout=60)
        b.close()
    assert received == total
    assert got.digest() == want.digest()


def test_tcp_nodelay_set_on_both_ends():
    store, server, client = _live_pair()
    try:
        assert client.put(("nd",), _planes(seed=12))
        opt = client._sock.getsockopt(
            socket.IPPROTO_TCP, socket.TCP_NODELAY
        )
        assert opt != 0
        with server._conns_lock:
            conns = list(server._conns)
        assert conns
        for c in conns:
            assert c.getsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY) != 0
    finally:
        client.close()
        server.close()


def test_killed_server_fails_pipeline_to_misses():
    """A hard server kill mid-pipeline: every in-flight and subsequent
    op degrades to a miss within the op timeout, errors are counted,
    the circuit opens, and nothing wedges."""
    store, server, client = _live_pair()
    client.timeout_s = 1.0
    client.retry_s = 30.0  # keep the circuit open for the test's tail
    assert client.put(("pre",), _planes(seed=13))
    results = []

    def hammer():
        for _ in range(10):
            results.append(client.get(("pre",)))

    threads = [threading.Thread(target=hammer) for _ in range(3)]
    for t in threads:
        t.start()
    server.close()  # hard kill: live conns shut down mid-stream
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    # Post-kill ops are fast misses through the open circuit.
    t0 = time.monotonic()
    assert client.get(("pre",)) is None
    assert not client.put(("post",), _planes(seed=14))
    assert time.monotonic() - t0 < 1.0
    assert client.errors >= 1
    assert None in results  # at least the tail of the hammer missed
    client.close()


# ---------------------------------------------------------------------------
# Serving layer: streamed export, prefetch, handoff-latency lockstep
# ---------------------------------------------------------------------------


def test_streamed_export_spills_and_respects_deadline(params):
    """A streaming export issued WHILE the chain prefills spills every
    usable page and sets its event; one for a chain that never lands
    sets its event at the deadline instead of hanging."""
    store = HostPageStore(budget_bytes=64 << 20)
    b = ContinuousBatcher(
        CFG, params, config=ContinuousConfig(**_SCFG), host_store=store
    )
    try:
        prompt = _HEADER + "s0"
        ids = b.tokenizer.encode(prompt)
        expected = (len(ids) - 1) // _SCFG["page_size"]
        assert expected >= 3
        fut = b.submit(prompt, max_new_tokens=4, temperature=0.0)
        ev = b.request_export(ids, stream_until=time.monotonic() + 20.0)
        assert fut.result(timeout=120).text
        assert ev.wait(20.0)
        assert len(store) >= expected
        assert b.stats()["exported_pages"] >= expected
        # Unknown chain: nothing ever flips ready; the re-arming export
        # gives up at its deadline and STILL sets the event.
        ghost = b.tokenizer.encode(_HEADER + "never-submitted")
        ev2 = b.request_export(ghost, stream_until=time.monotonic() + 0.4)
        assert ev2.wait(5.0)
        assert len(store) >= expected  # the ghost spilled nothing new
    finally:
        b.close()


def test_prefetch_staged_hit_and_cold_fallthrough(params):
    """Route-driven prefetch end to end: a warm store's chain stages
    ahead of admission and is consumed as restore-plan hits (metrics
    in lockstep with the stats mirrors); the same prefetch against a
    COLD store stages nothing and admission recomputes — text
    byte-identical in both worlds."""
    prompt = _HEADER + "pf"
    store = HostPageStore(budget_bytes=64 << 20)

    # Seed the store (and the reference text) from a donor batcher.
    b0 = ContinuousBatcher(
        CFG, params, config=ContinuousConfig(**_SCFG), host_store=store
    )
    try:
        want = _serve(b0, [prompt], max_new_tokens=4, temperature=0.0)[0]
        ids = b0.tokenizer.encode(prompt)
        ev = b0.request_export(ids)
        assert ev.wait(20.0)
        assert len(store) >= 3
    finally:
        b0.close()

    # Warm world: prefetch stages the chain, admission consumes it.
    f0 = KV_PREFETCH.labels(event="fetched").value
    h0 = KV_PREFETCH.labels(event="hit").value
    b1 = ContinuousBatcher(
        CFG, params, config=ContinuousConfig(**_SCFG), host_store=store
    )
    try:
        assert b1.prefetch_chain(ids) is True
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if b1.stats()["prefetch_fetched_pages"] >= 3:
                break
            time.sleep(0.02)
        s = b1.stats()
        assert s["prefetch_fetched_pages"] >= 3
        assert s["prefetch_staged_pages"] >= 3
        got_warm = _serve(b1, [prompt], max_new_tokens=4, temperature=0.0)[0]
        s = b1.stats()
        assert s["prefetch_hit_pages"] >= 1
        # Prometheus family deltas match the batcher's stats mirrors.
        assert (
            KV_PREFETCH.labels(event="fetched").value - f0
            == s["prefetch_fetched_pages"]
        )
        assert (
            KV_PREFETCH.labels(event="hit").value - h0
            == s["prefetch_hit_pages"]
        )
    finally:
        b1.close()
    assert got_warm == want

    # Cold world: the prefetch guess finds nothing; admission falls
    # through to recompute. Never corrupts, never blocks — and the
    # text is still byte-identical.
    b2 = ContinuousBatcher(
        CFG,
        params,
        config=ContinuousConfig(**_SCFG),
        host_store=HostPageStore(budget_bytes=64 << 20),
    )
    try:
        assert b2.prefetch_chain(ids) is True
        time.sleep(0.3)  # let the guess run against the empty store
        got_cold = _serve(b2, [prompt], max_new_tokens=4, temperature=0.0)[0]
        s = b2.stats()
        assert s["prefetch_fetched_pages"] == 0
        assert s["prefetch_hit_pages"] == 0
        assert s["prefetch_staged_pages"] == 0
    finally:
        b2.close()
    assert got_cold == want


def test_handoff_seconds_lockstep_on_roled_fleet(params):
    """gateway_handoff_seconds moves in lockstep with the fleet's
    handoff_seconds_sum/count mirrors, and the streamed handoff's
    flight events say so."""
    hc0, hs0 = HANDOFF_SECONDS.count, HANDOFF_SECONDS.sum
    fleet = ReplicaSet(
        CFG,
        params,
        config=ContinuousConfig(**_SCFG),
        fleet=FleetConfig(replicas=2, role=("prefill", "decode")),
        host_store=HostPageStore(budget_bytes=64 << 20),
    )
    try:
        futs = [
            fleet.submit(f"{_HEADER}h{i}?", max_new_tokens=4, temperature=0.0)
            for i in range(3)
        ]
        # num_tokens, not text: a random model's argmax lands in the
        # padded vocab tail the byte tokenizer decodes to nothing.
        assert all(f.result(timeout=300).num_tokens > 0 for f in futs)
        stats = fleet.stats()
    finally:
        fleet.close()
    assert stats["role_handoffs"] >= 1
    assert stats["handoff_seconds_count"] == stats["role_handoffs"]
    assert HANDOFF_SECONDS.count - hc0 == stats["handoff_seconds_count"]
    assert HANDOFF_SECONDS.sum - hs0 == pytest.approx(
        stats["handoff_seconds_sum"]
    )
    assert stats["handoff_seconds_sum"] > 0.0
    streamed = [
        e.meta.get("streamed")
        for e in flight.flight_recorder().events()
        if e.kind == "handoff"
    ]
    assert streamed and streamed[-1] is True  # handoff_stream default on
