"""Remote host page store: the fleet KV tier across processes (PR 16).

PR 14 made :class:`~llm_consensus_tpu.serving.offload.HostPageStore`
the fleet's page transport — thread-safe, chain-keyed, scoped by each
batcher's config dims + weights fingerprint so heterogeneous replicas
can never cross-restore. But it is in-memory, which confines the fleet
to one process. This module lifts the SAME interface onto a socket:

- :class:`PageStoreServer` wraps ONE authoritative ``HostPageStore``
  behind a TCP or Unix-domain transport. There is no negotiation in
  the protocol because none is needed: the PR-14 ``(scope, chain)``
  keys already carry config dims and the weights fingerprint, so a
  process whose scope differs simply never hits.
- :class:`RemotePageStore` is a client implementing the full
  ``HostPageStore`` surface (``put_counted`` / ``touch`` / ``get`` /
  ``__contains__`` / ``headroom_bytes`` / the counters, plus the PR-17
  batched ``put_many`` / ``get_run`` / ``touch_many`` / ``run_len``),
  so ``ReplicaSet`` / ``ContinuousBatcher(host_store=)`` take a local
  store or a remote one transparently — 4-plane target+draft entries
  included (the store layer is plane-count agnostic).

**Failure contract — degrade, never wedge.** Every client failure
(connect refused, peer disconnect mid-``put``, a slow peer hitting the
client timeout) degrades to a local MISS: ``get`` returns None,
``touch``/``__contains__`` return False, ``put_counted`` reports the
page dropped — so the worker loop recomputes via chunked prefill
(always correct) instead of stalling. Each failure increments
``gateway_remote_store_errors_total``, logs ONE warning per outage
(not per op), records a ``remote_store`` flight event on the
transition, and opens the circuit for ``retry_s`` seconds — ops during
the open window miss immediately with no socket attempt, so a dead
peer costs the worker loop nothing per iteration (heartbeat stays
fresh; tested).

**Cheap reads by piggyback.** Every server response frame carries the
authoritative store's :meth:`stats_snapshot`, which the client caches;
``headroom_bytes`` / ``bytes_used`` / ``len`` / the counters read the
cache and NEVER touch the network — the admission overflow hook reads
headroom on the asyncio event loop, where a blocking RTT would freeze
the gateway under exactly the overload the hook exists to absorb.
``gateway_remote_store_bytes`` mirrors the cached occupancy;
``gateway_remote_store_rtt_seconds`` observes each successful
exchange; ``gateway_transfer_bytes_total{dir}`` counts plane payload
bytes crossing the wire either way.

**Wire format v2 (PR 17) — zero-copy scatter-gather.** A frame is::

    prelude(20B) || pickled header || raw plane bytes

with prelude ``>2sBxIIQ`` = magic ``b"KV"``, version, pad, a u32
sequence tag, header length, body length. Plane arrays are NOT
pickled: the header carries ``(dtype_name, shape, nbytes)`` descriptor
groups and the body is the concatenated raw bytes, written with ONE
``sendmsg`` scatter-gather pass over memoryviews (no ``tobytes()``
staging copy) and read with ``recv_into`` straight into preallocated
numpy buffers (no pickle reassembly copy). The sequence tag makes the
connection PIPELINED: many ops fly in-flight concurrently over one
socket (a dedicated receiver thread dispatches replies by tag), so K
replicas stop serializing through one lock-held round trip. Batched
ops (``put_many``, ``get_run``) make a whole export batch or restore
plan a single round trip. Dtypes travel by NAME so ml_dtypes
extension dtypes (bfloat16 et al.) survive the trip.

**Wire format v1 (PR 16)** — ``4-byte big-endian length || pickle
payload`` with planes as ``(dtype, shape, bytes)`` triples — is still
spoken by the server (it sniffs the first two bytes per frame: v2
frames open with ``b"KV"``, which as a v1 length prefix would mean a
>1 GiB frame, far past ``_MAX_FRAME``) and by
``RemotePageStore(wire="v1")``, which keeps the one-lock synchronous
client as the baseline tests/test_kv_transfer.py compares with.

Pickle headers are a FLEET-INTERNAL trust boundary (bind
localhost/UDS, same deployment): the transport authenticates nothing,
exactly like the in-process store it replaces.
"""

from __future__ import annotations

import logging
import pickle
import socket
import struct
import threading
import time
from typing import Sequence

import numpy as np

from llm_consensus_tpu.server.metrics import (
    REMOTE_STORE_BYTES as _M_BYTES,
)
from llm_consensus_tpu.server.metrics import (
    REMOTE_STORE_ERRORS as _M_ERRORS,
)
from llm_consensus_tpu.server.metrics import (
    REMOTE_STORE_RTT as _M_RTT,
)
from llm_consensus_tpu.server.metrics import (
    TRANSFER_BYTES as _M_XFER,
)
from llm_consensus_tpu.serving.offload import HostPageStore
from llm_consensus_tpu.utils import tracing as _tracing

log = logging.getLogger(__name__)

#: v2 ops that move plane bytes (PR 20): the only ones worth a
#: ``store_op`` span/flight event — control ops (touch/contains/stats)
#: would flood the bounded ring from the worker loop for no
#: attribution value.
_DATA_OPS = frozenset({"put_counted", "put_many", "get", "get_run"})

__all__ = ["PageStoreServer", "RemotePageStore", "parse_endpoint"]

_LEN = struct.Struct(">I")
#: Refuse frames past this (a corrupt length prefix must not allocate
#: gigabytes): generous for any real page payload (a 1B-class bf16
#: page is ~1.5 MiB; 4-plane int8+scales entries are smaller).
_MAX_FRAME = 256 << 20

#: v2 frame prelude: magic, version, pad, sequence tag, header length,
#: body (raw plane bytes) length.
_PRELUDE = struct.Struct(">2sBxIIQ")
_MAGIC = b"KV"
#: Scatter-gather buffers per ``sendmsg`` call — conservatively under
#: Linux's UIO_MAXIOV (1024); longer vectors chunk across calls.
_IOV_MAX = 512


def _send_frame(sock: socket.socket, payload: bytes) -> None:
    sock.sendall(_LEN.pack(len(payload)) + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed mid-frame")
        buf.extend(chunk)
    return bytes(buf)


def _recv_frame(sock: socket.socket) -> bytes:
    (n,) = _LEN.unpack(_recv_exact(sock, _LEN.size))
    if n > _MAX_FRAME:
        raise ConnectionError(f"frame length {n} exceeds cap {_MAX_FRAME}")
    return _recv_exact(sock, n)


def _recv_exact_into(sock: socket.socket, mv: memoryview) -> None:
    """Fill ``mv`` completely from the socket — the zero-copy receive
    half (bytes land straight in the caller's preallocated buffer)."""
    got = 0
    while got < len(mv):
        n = sock.recv_into(mv[got:])
        if n == 0:
            raise ConnectionError("peer closed mid-frame")
        got += n


def _send_vec(sock: socket.socket, views: list) -> None:
    """Scatter-gather send: one ``sendmsg`` pass over the frame's
    memoryviews (prelude+header, then each plane's buffer) instead of
    concatenating into a staging bytes object. Handles partial sends
    and chunks vectors longer than the iovec limit."""
    views = [memoryview(v) for v in views]
    views = [v for v in views if len(v)]
    if not hasattr(sock, "sendmsg"):  # pragma: no cover - non-POSIX
        sock.sendall(b"".join(views))
        return
    i = 0
    while i < len(views):
        sent = sock.sendmsg(views[i : i + _IOV_MAX])
        while sent > 0:
            v = views[i]
            if sent >= len(v):
                sent -= len(v)
                i += 1
            else:
                views[i] = v[sent:]
                sent = 0


def _plane_view(a: np.ndarray) -> memoryview:
    # uint8 view rather than memoryview(a) directly: ml_dtypes
    # extension dtypes don't export a buffer format numpy will cast.
    return memoryview(a.view(np.uint8).reshape(-1))


def _pack_frame(seq: int, payload, groups: Sequence) -> tuple[list, int]:
    """Build a v2 frame as a list of buffers for :func:`_send_vec`.

    ``groups`` is a sequence of plane tuples; each plane contributes a
    ``(dtype_name, shape, nbytes)`` descriptor to the pickled header
    and its raw buffer to the frame tail — the arrays themselves are
    never copied or pickled. Returns ``(buffers, body_bytes)``."""
    descs = []
    views: list = []
    body = 0
    for planes in groups:
        gd = []
        for p in planes:
            a = np.ascontiguousarray(p)
            n = int(a.nbytes)
            gd.append((a.dtype.name, a.shape, n))
            if n:
                views.append(_plane_view(a))
            body += n
        descs.append(gd)
    hdr = pickle.dumps((payload, descs), protocol=4)
    prelude = _PRELUDE.pack(_MAGIC, 2, seq & 0xFFFFFFFF, len(hdr), body)
    return [prelude + hdr] + views, body


def _finish_v2(sock: socket.socket, prelude: bytes) -> tuple:
    """Read the rest of a v2 frame whose prelude bytes are in hand.

    Returns ``(seq, payload, groups)`` with every plane received by
    ``recv_into`` directly into its final numpy buffer. Descriptor
    sizes are validated against the body length BEFORE any allocation,
    so a fuzzed frame can't make the receiver allocate past
    ``_MAX_FRAME``."""
    magic, ver, seq, hdr_len, body_len = _PRELUDE.unpack(prelude)
    if magic != _MAGIC or ver != 2:
        raise ConnectionError(f"bad v2 prelude (magic={magic!r} ver={ver})")
    if hdr_len > _MAX_FRAME or body_len > _MAX_FRAME:
        raise ConnectionError(
            f"v2 frame exceeds cap (hdr={hdr_len} body={body_len})"
        )
    payload, descs = pickle.loads(_recv_exact(sock, hdr_len))
    groups = []
    got = 0
    for gd in descs:
        planes = []
        for dt_name, shape, nbytes in gd:
            dt = _np_dtype(dt_name)
            want = int(nbytes)
            count = 1
            for d in shape:
                count *= int(d)
            if want < 0 or count * dt.itemsize != want or got + want > body_len:
                raise ConnectionError("v2 plane descriptor/body mismatch")
            a = np.empty(shape, dtype=dt)
            if want:
                _recv_exact_into(sock, _plane_view(a))
            got += want
            planes.append(a)
        groups.append(tuple(planes))
    if got != body_len:
        raise ConnectionError("v2 body length mismatch")
    return seq, payload, groups


def _enc_planes(planes: Sequence[np.ndarray]) -> list:
    """Planes -> ``(dtype, shape, bytes)`` triples (the raw-bytes half
    of the v1 wire format; plane COUNT rides along, so 2-plane bf16 and
    4-plane target+draft / int8+scale entries all pass through).

    Dtypes travel by NAME, not ``.str``: the extension dtypes the KV
    pool actually uses (ml_dtypes bfloat16 et al.) stringify as opaque
    void codes (``|V2``) under ``.str``, which would decode to planes
    jax rejects at restore time."""
    out = []
    for p in planes:
        a = np.ascontiguousarray(p)
        out.append((a.dtype.name, a.shape, a.tobytes()))
    return out


def _np_dtype(name: str) -> np.dtype:
    """Dtype from its wire name, resolving extension dtypes (bfloat16,
    float8 variants) through ml_dtypes when numpy alone can't."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _dec_planes(enc: list) -> tuple:
    return tuple(
        np.frombuffer(raw, dtype=_np_dtype(dt)).reshape(shape)
        for dt, shape, raw in enc
    )


def _nodelay(sock: socket.socket) -> None:
    """Disable Nagle on TCP sockets: page-store RPCs interleave small
    header frames with bulk plane bytes, and a delayed-ACK/Nagle stall
    on the header half adds ~40ms per op on cross-host links. No-op
    for UDS."""
    if sock.family == socket.AF_INET:
        try:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:  # pragma: no cover - platform quirk
            pass


def parse_endpoint(spec) -> tuple[str, object]:
    """``"tcp://host:port"`` / ``"uds:///path"`` / ``(host, port)`` /
    a bare filesystem path -> ``("tcp", (host, port))`` or
    ``("uds", path)``."""
    if isinstance(spec, tuple):
        return "tcp", (spec[0], int(spec[1]))
    s = str(spec)
    if s.startswith("tcp://"):
        host, _, port = s[len("tcp://"):].rpartition(":")
        return "tcp", (host or "127.0.0.1", int(port))
    if s.startswith("uds://"):
        return "uds", s[len("uds://"):]
    if "/" in s or not s:
        return "uds", s
    host, _, port = s.rpartition(":")
    return "tcp", (host or "127.0.0.1", int(port))


class PageStoreServer:
    """Page-transport server over ONE authoritative
    :class:`HostPageStore`, speaking both wire formats per frame.

    One accept thread + one daemon thread per connection (a fleet has
    a handful of clients, each holding one long-lived socket). A
    connection's requests are handled in arrival order and replies
    carry the request's sequence tag, which is all the pipelined
    client needs — server-side concurrency stays per-connection. All
    mutation funnels through the wrapped store's own lock, so a local
    in-process user and remote clients can share it. A malformed or
    truncated frame drops THAT connection only (the client reconnects
    or degrades); the listener and other connections are unaffected.
    """

    def __init__(
        self,
        store: HostPageStore,
        *,
        host: str = "127.0.0.1",
        port: int = 0,
        path: str | None = None,
    ):
        self.store = store
        self._path = path
        if path is not None:
            self._sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            self._sock.bind(path)
            self.endpoint = f"uds://{path}"
        else:
            self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            self._sock.bind((host, port))
            self.endpoint = "tcp://{}:{}".format(*self._sock.getsockname())
        self._sock.listen(16)
        self._closed = threading.Event()
        self._accept_thread: threading.Thread | None = None
        self._conns_lock = threading.Lock()
        self._conns: set[socket.socket] = set()

    def start(self) -> "PageStoreServer":
        t = threading.Thread(
            target=self._accept_loop, name="page-store-accept", daemon=True
        )
        self._accept_thread = t
        t.start()
        return self

    def _accept_loop(self) -> None:
        while not self._closed.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return  # listener closed
            _nodelay(conn)
            threading.Thread(
                target=self._serve_conn,
                args=(conn,),
                name="page-store-conn",
                daemon=True,
            ).start()

    def _read_request(self, conn: socket.socket) -> tuple:
        """One request frame, either wire: ``(ver, seq, payload,
        groups)``. Sniffs the first two bytes — ``b"KV"`` opens a v2
        prelude; as a v1 length prefix those bytes would mean a >1 GiB
        frame, far past ``_MAX_FRAME``, so the formats can't collide."""
        head = _recv_exact(conn, 2)
        if head == _MAGIC:
            rest = _recv_exact(conn, _PRELUDE.size - 2)
            return (2,) + _finish_v2(conn, head + rest)
        rest = _recv_exact(conn, 2)
        (n,) = _LEN.unpack(head + rest)
        if n > _MAX_FRAME:
            raise ConnectionError(f"frame length {n} exceeds cap {_MAX_FRAME}")
        return 1, 0, pickle.loads(_recv_exact(conn, n)), []

    def _serve_conn(self, conn: socket.socket) -> None:
        with self._conns_lock:
            if self._closed.is_set():
                try:
                    conn.close()
                except OSError:
                    pass
                return
            self._conns.add(conn)
        try:
            while not self._closed.is_set():
                try:
                    ver, seq, payload, groups = self._read_request(conn)
                except (
                    ConnectionError,
                    OSError,
                    EOFError,
                    struct.error,
                    pickle.PickleError,
                    ValueError,
                    TypeError,
                    MemoryError,
                ):
                    return  # garbage or gone: drop this connection only
                if ver == 1:
                    try:
                        reply = self._handle_v1(payload)
                    except Exception as e:  # noqa: BLE001 - malformed op
                        reply = ("err", repr(e), self._stats_stamped())
                    try:
                        _send_frame(conn, pickle.dumps(reply, protocol=4))
                    except OSError:
                        return
                else:
                    # Optional third header element (PR 20): the owning
                    # request's trace id. Length-tolerant both ways —
                    # an old client sends 2 elements, an old server
                    # ignores the third.
                    tid = payload[2] if len(payload) > 2 else None
                    t_op = time.perf_counter()
                    try:
                        result, out_groups = self._handle_v2(
                            payload[0], payload[1], groups
                        )
                        status = "ok"
                    except Exception as e:  # noqa: BLE001 - malformed op
                        status, result, out_groups = "err", repr(e), []
                    self._flight_op(
                        payload[0], tid, groups, out_groups, t_op
                    )
                    views, _ = _pack_frame(
                        seq,
                        (status, result, self._stats_stamped()),
                        out_groups,
                    )
                    try:
                        _send_vec(conn, views)
                    except OSError:
                        return
        finally:
            with self._conns_lock:
                self._conns.discard(conn)
            try:
                conn.close()
            except OSError:
                pass

    def _stats_stamped(self) -> dict:
        """Store stats + clock-probe stamp (PR 20): the client halves
        the op's RTT around ``now_pc`` to place this store process's
        perf_counter timebase on its own — the store-connection leg of
        the fleet clock-offset estimator."""
        return {
            **self.store.stats_snapshot(),
            "now_pc": time.perf_counter(),
        }

    def _flight_op(
        self, op, tid, groups: list, out_groups: list, t_op: float
    ) -> None:
        """Record one data-plane op in THIS process's flight ring
        (PR 20), tagged with the owning trace id — the store-side lane
        of the merged fleet timeline. Control ops are skipped (the
        worker loop's touch/contains churn would flood the ring)."""
        if op not in _DATA_OPS:
            return
        try:
            from llm_consensus_tpu.serving import flight as _flight

            _flight.flight_recorder().record(
                "store_op",
                t_op,
                time.perf_counter() - t_op,
                trace_id=tid if isinstance(tid, str) else None,
                op=op,
                rx_bytes=sum(
                    int(p.nbytes) for g in groups for p in g
                ),
                tx_bytes=sum(
                    int(p.nbytes) for g in out_groups for p in g
                ),
            )
        except Exception:  # noqa: BLE001 - telemetry must not fail ops
            pass

    def _handle_v1(self, req: tuple) -> tuple:
        """PR-16 ops with pickled plane triples — kept verbatim so a
        ``wire="v1"`` client exercises the exact
        old path."""
        op, args = req[0], req[1:]
        store = self.store
        if op == "put_counted":
            key, enc = args
            result = store.put_counted(key, _dec_planes(enc))
        elif op == "touch":
            result = store.touch(args[0])
        elif op == "get":
            planes = store.get(args[0])
            result = None if planes is None else _enc_planes(planes)
        elif op == "contains":
            result = args[0] in store
        elif op == "stats":
            result = None
        else:
            raise ValueError(f"unknown op {op!r}")
        return "ok", result, self._stats_stamped()

    def _handle_v2(self, op: str, args: tuple, groups: list) -> tuple:
        """v2 ops: planes arrive/depart as raw frame groups, never
        through pickle. Returns ``(result, out_groups)``."""
        store = self.store
        if op == "put_counted":
            return store.put_counted(args[0], groups[0]), []
        if op == "put_many":
            keys = args[0]
            if len(keys) != len(groups):
                raise ValueError("put_many keys/groups mismatch")
            return store.put_many(list(zip(keys, groups))), []
        if op == "touch":
            return store.touch(args[0]), []
        if op == "touch_many":
            return store.touch_many(args[0]), []
        if op == "get":
            planes = store.get(args[0])
            return (False, []) if planes is None else (True, [planes])
        if op == "get_run":
            runs = store.get_run(args[0])
            return len(runs), runs
        if op == "run_len":
            return store.run_len(args[0]), []
        if op == "contains":
            return args[0] in store, []
        if op == "stats":
            return None, []
        raise ValueError(f"unknown op {op!r}")

    def close(self) -> None:
        """Stop the listener AND hang up every live connection (a
        shutdown unblocks the per-connection threads parked in recv,
        so a close is a hard mid-stream kill from the clients' view —
        their in-flight ops fail to misses, exactly the degrade path
        the circuit breaker covers)."""
        self._closed.set()
        try:
            self._sock.close()
        except OSError:
            pass
        with self._conns_lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        if self._path is not None:
            import os

            try:
                os.unlink(self._path)
            except OSError:
                pass


class _Pending:
    """One in-flight v2 op: the waiter blocks on ``ev``; the receiver
    thread fills ``reply``/``groups`` (or marks ``failed``) and sets
    it."""

    __slots__ = ("ev", "reply", "groups", "failed", "t0")

    def __init__(self):
        self.ev = threading.Event()
        self.reply = None
        self.groups: list = []
        self.failed = False
        self.t0 = time.perf_counter()


class RemotePageStore:
    """Client half: the ``HostPageStore`` interface over a socket.

    Drop-in for the places a fleet passes a store —
    ``ReplicaSet(host_store=)`` / ``ContinuousBatcher(host_store=)`` —
    with the degrade-to-miss failure contract described in the module
    docstring. Construction NEVER raises on a dead server: the first
    exchange fails, the circuit opens, and the batcher recomputes
    until the peer answers.

    ``wire="v2"`` (default) speaks the zero-copy scatter-gather
    format with PIPELINED sequence-tagged ops: the socket write is the
    only serialized section, a dedicated receiver thread dispatches
    replies by tag, and any number of worker/prefetch/export threads
    keep ops in flight concurrently. An op that times out poisons the
    connection (frames can't be resynced mid-stream), failing all
    in-flight ops to misses and opening the circuit — the same degrade
    contract as v1, just batched. ``wire="v1"`` keeps the PR-16
    one-lock synchronous client, byte-for-byte the old frames: the
    measured baseline for the transport A/B leg.
    """

    def __init__(
        self,
        endpoint,
        *,
        timeout_s: float = 2.0,
        retry_s: float = 1.0,
        wire: str = "v2",
    ):
        if wire not in ("v1", "v2"):
            raise ValueError(f"wire must be 'v1' or 'v2', got {wire!r}")
        self.wire = wire
        self.kind, self.address = parse_endpoint(endpoint)
        self.endpoint = (
            f"{self.kind}://{self.address}"
            if self.kind == "uds"
            else "tcp://{}:{}".format(*self.address)
        )
        self.timeout_s = float(timeout_s)
        self.retry_s = float(retry_s)
        self._lock = threading.Lock()
        self._send_lock = threading.Lock()
        self._sock: socket.socket | None = None
        self._seq = 0
        self._pending: dict[int, _Pending] = {}
        self._down_until = 0.0
        self._warned_down = False
        #: Local failure count (mirrors gateway_remote_store_errors_total
        #: for this client; the Prometheus family is process-global).
        self.errors = 0
        #: Plane payload bytes this client moved, by direction — the
        #: stats mirrors of ``gateway_transfer_bytes_total{dir=...}``.
        self.tx_bytes = 0
        self.rx_bytes = 0
        #: Clock-offset estimate for the store host (PR 20):
        #: ``t_local ≈ t_store + clock_offset``, from halving each v2
        #: op's RTT around the ``now_pc`` stamp the server piggybacks
        #: on every reply; the min-RTT observation wins (the tightest
        #: round trip bounds the midpoint error). None until a reply
        #: carrying the stamp lands.
        self.clock_offset: float | None = None
        self.clock_rtt: float | None = None
        # Last piggybacked authoritative-store snapshot: the cache
        # behind every read property (no network on the read path).
        self._stats: dict = {}
        # Best-effort warm-up: populates the stats cache when the
        # server is up; opens the circuit (no raise) when it is not.
        self._call_simple("stats")

    # -- transport ------------------------------------------------------

    def _connect(self) -> socket.socket:
        if self.kind == "uds":
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        else:
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.settimeout(self.timeout_s)
        s.connect(self.address)
        _nodelay(s)
        return s

    def _drop_socket(self) -> None:
        """shutdown+close under the send lock: shutdown is what
        reliably unblocks a receiver thread parked in ``recv`` (a bare
        close can leave it blocked on Linux)."""
        with self._send_lock:
            s = self._sock
            self._sock = None
        if s is not None:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass

    def _fail(self, exc: Exception) -> None:
        """One failure: count, open the circuit, warn on the DOWN
        transition only (a dead peer must not log per worker-loop op),
        and drop the socket so the next attempt reconnects."""
        self.errors += 1
        _M_ERRORS.inc()
        self._down_until = time.monotonic() + self.retry_s
        self._drop_socket()
        if not self._warned_down:
            self._warned_down = True
            log.warning(
                "remote page store %s unavailable (%r): degrading to "
                "local miss/recompute until it answers",
                self.endpoint,
                exc,
            )
            self._flight("down", error=repr(exc))

    def _flight(self, state: str, **extra) -> None:
        # Lazy import mirrors control.py: consumers of this module may
        # not want the flight module (and its deps) at import time.
        try:
            from llm_consensus_tpu.serving import flight as _flight

            _flight.flight_recorder().record(
                "remote_store",
                time.perf_counter(),
                endpoint=self.endpoint,
                state=state,
                **extra,
            )
        except Exception:  # noqa: BLE001 - telemetry must not fail ops
            pass

    def _flight_op(
        self, op: str, tid, tx: int, rx: int, dur: float
    ) -> None:
        """One data-plane op in this process's flight ring (PR 20),
        tagged with the owning trace id and the bytes it moved — the
        per-request attribution of the
        ``gateway_transfer_bytes_total`` increments the same op made
        (the counter itself stays label-bounded; the flight event
        carries the join key)."""
        try:
            from llm_consensus_tpu.serving import flight as _flight

            _flight.flight_recorder().record(
                "store_op",
                time.perf_counter() - dur,
                dur,
                trace_id=tid,
                op=op,
                endpoint=self.endpoint,
                tx_bytes=tx,
                rx_bytes=rx,
            )
        except Exception:  # noqa: BLE001 - telemetry must not fail ops
            pass

    def _count_xfer(self, direction: str, n: int) -> None:
        if not n:
            return
        if direction == "tx":
            self.tx_bytes += n
        else:
            self.rx_bytes += n
        _M_XFER.labels(dir=direction).inc(n)

    def _recovered(self) -> None:
        if self._warned_down:
            self._warned_down = False
            log.info("remote page store %s recovered", self.endpoint)
            self._flight("up")

    # -- v1 synchronous exchange ----------------------------------------

    def _call_v1(self, op: str, *args):
        """One lock-held request/response exchange (the PR-16 client,
        byte-for-byte). Returns ``(True, result)``, or None after ANY
        failure (the degrade-to-miss contract). Never raises."""
        with self._lock:
            if time.monotonic() < self._down_until:
                self.errors += 1
                _M_ERRORS.inc()
                return None
            t0 = time.perf_counter()
            try:
                if self._sock is None:
                    self._sock = self._connect()
                payload = pickle.dumps((op, *args), protocol=4)
                _send_frame(self._sock, payload)
                status, result, stats = pickle.loads(_recv_frame(self._sock))
            except (OSError, ConnectionError, EOFError, pickle.PickleError) as e:
                self._fail(e)
                return None
            if status != "ok":
                # The server rejected the op (malformed key): a miss,
                # but the connection is healthy — no circuit.
                self.errors += 1
                _M_ERRORS.inc()
                log.warning(
                    "remote page store %s rejected %s: %s",
                    self.endpoint,
                    op,
                    result,
                )
                return None
            self._stats = stats
            _M_RTT.observe(time.perf_counter() - t0)
            _M_BYTES.set(stats.get("bytes_used", 0))
            self._recovered()
            return (True, result)  # wrap: distinguish None-result hits

    # -- v2 pipelined exchange ------------------------------------------

    def _start_rx(self, sock: socket.socket) -> None:
        threading.Thread(
            target=self._rx_loop, args=(sock,), name="page-store-rx", daemon=True
        ).start()

    def _rx_loop(self, sock: socket.socket) -> None:
        """Receiver half of the pipelined connection: reads reply
        frames forever, dispatching each to its waiter by sequence
        tag. An idle-timeout on the FIRST byte of a frame is benign
        (op deadlines are enforced by the waiters, who poison the
        socket on expiry); a timeout or error mid-frame is fatal —
        the stream can't be resynced — and fails every in-flight op
        to a miss."""
        one = bytearray(1)
        try:
            while True:
                try:
                    n = sock.recv_into(one)
                except socket.timeout:
                    continue
                if n == 0:
                    raise ConnectionError("server closed connection")
                rest = _recv_exact(sock, _PRELUDE.size - 1)
                seq, payload, groups = _finish_v2(sock, bytes(one) + rest)
                self._count_xfer(
                    "rx", sum(int(p.nbytes) for g in groups for p in g)
                )
                with self._lock:
                    pend = self._pending.pop(seq, None)
                if pend is not None:
                    pend.reply = payload
                    pend.groups = groups
                    pend.ev.set()
        except (
            OSError,
            ConnectionError,
            EOFError,
            struct.error,
            pickle.PickleError,
            ValueError,
            TypeError,
            MemoryError,
        ) as e:
            with self._lock:
                current = self._sock is sock
            if current:
                # This thread detected the failure first: open the
                # circuit once. (If a waiter's timeout got here first,
                # the socket is already swapped out and counted.)
                self._fail(e)
            self._abort_pending()

    def _abort_pending(self) -> None:
        with self._lock:
            pend = list(self._pending.values())
            self._pending.clear()
        for p in pend:
            p.failed = True
            p.ev.set()

    def _call_v2(self, op: str, args: tuple = (), groups: Sequence = ()):
        """One pipelined op. Returns ``(True, result, plane_groups)``
        or None after ANY failure. The send is the only serialized
        section; the reply is awaited without holding any lock, so
        concurrent callers keep the wire full. Never raises.

        Trace join (PR 20): the owning request's trace id (the
        contextvar the handoff worker propagated) rides the v2 header
        as an optional third element — the server tags its own flight
        ring with it, and this side lands a ``store_op`` span on the
        trace plus a flight event carrying the moved bytes, so wire
        transfers attribute to the request that caused them."""
        trace = _tracing.current_trace()
        tid = trace.trace_id if trace is not None else None
        with self._lock:
            if time.monotonic() < self._down_until:
                self.errors += 1
                _M_ERRORS.inc()
                return None
        pend = _Pending()
        seq = None
        try:
            with self._send_lock:
                sock = self._sock
                if sock is None:
                    sock = self._connect()
                    self._sock = sock
                    self._start_rx(sock)
                with self._lock:
                    self._seq = seq = (self._seq + 1) & 0xFFFFFFFF
                    self._pending[seq] = pend
                views, tx = _pack_frame(seq, (op, args, tid), groups)
                _send_vec(sock, views)
            self._count_xfer("tx", tx)
        except (
            OSError,
            ConnectionError,
            EOFError,
            pickle.PickleError,
            struct.error,
        ) as e:
            with self._lock:
                self._pending.pop(seq, None)
            self._fail(e)
            return None
        if not pend.ev.wait(self.timeout_s):
            with self._lock:
                self._pending.pop(seq, None)
            self._fail(
                socket.timeout(f"no reply to {op} within {self.timeout_s}s")
            )
            return None
        if pend.failed:
            # The connection died while we waited; whoever detected it
            # already opened the circuit — count THIS op's miss only.
            self.errors += 1
            _M_ERRORS.inc()
            return None
        status, result, stats = pend.reply
        with self._lock:
            self._stats = stats
        t1 = time.perf_counter()
        dur = t1 - pend.t0
        _M_RTT.observe(dur)
        _M_BYTES.set(stats.get("bytes_used", 0))
        # Clock-offset piggyback (PR 20): every reply carrying the
        # server's ``now_pc`` stamp refines the estimate; min-RTT wins.
        now = stats.get("now_pc")
        if isinstance(now, (int, float)) and (
            self.clock_rtt is None or dur <= self.clock_rtt
        ):
            self.clock_offset = (pend.t0 + t1) / 2.0 - float(now)
            self.clock_rtt = dur
        if op in _DATA_OPS:
            rx = sum(int(p.nbytes) for g in pend.groups for p in g)
            if trace is not None:
                trace.add_span(
                    "store_op",
                    pend.t0,
                    dur,
                    op=op,
                    tx_bytes=tx,
                    rx_bytes=rx,
                )
            self._flight_op(op, tid, tx, rx, dur)
        if status != "ok":
            self.errors += 1
            _M_ERRORS.inc()
            log.warning(
                "remote page store %s rejected %s: %s",
                self.endpoint,
                op,
                result,
            )
            return None
        self._recovered()
        return (True, result, pend.groups)

    def _call_simple(self, op: str, *args):
        """Planeless op over whichever wire is active; ``(True,
        result)`` or None."""
        if self.wire == "v1":
            return self._call_v1(op, *args)
        hit = self._call_v2(op, args)
        return None if hit is None else (True, hit[1])

    # -- HostPageStore surface ------------------------------------------

    @staticmethod
    def _as_planes(planes: Sequence[np.ndarray]) -> tuple:
        return tuple(np.ascontiguousarray(p) for p in planes)

    def put(self, key: tuple, planes: Sequence[np.ndarray]) -> bool:
        resident, _, _ = self.put_counted(key, planes)
        return resident

    def put_counted(
        self, key: tuple, planes: Sequence[np.ndarray]
    ) -> tuple[bool, int, int]:
        planes = self._as_planes(planes)
        if self.wire == "v1":
            hit = self._call_v1("put_counted", key, _enc_planes(planes))
            if hit is not None:
                self._count_xfer(
                    "tx", sum(int(p.nbytes) for p in planes)
                )
        else:
            hit = self._call_v2("put_counted", (key,), (planes,))
        if hit is None:
            # The page never left the process: not resident, not
            # demoted anywhere — report it dropped so the caller's
            # accounting reflects a real loss, not a silent no-op.
            return False, 0, 1
        return tuple(hit[1])

    def put_many(
        self, items: Sequence[tuple[tuple, Sequence[np.ndarray]]]
    ) -> list[tuple[bool, int, int]]:
        """Batched :meth:`put_counted`: ONE round trip on v2 (keys in
        the header, every page's planes scatter-gathered into one
        frame); a per-key loop on v1. Degrades to all-dropped."""
        items = [(k, self._as_planes(p)) for k, p in items]
        if not items:
            return []
        if self.wire == "v1":
            return [self.put_counted(k, p) for k, p in items]
        hit = self._call_v2(
            "put_many",
            (tuple(k for k, _ in items),),
            tuple(p for _, p in items),
        )
        if hit is None:
            return [(False, 0, 1)] * len(items)
        return [tuple(t) for t in hit[1]]

    def touch(self, key: tuple) -> bool:
        hit = self._call_simple("touch", key)
        return bool(hit[1]) if hit is not None else False

    def touch_many(self, keys: Sequence[tuple]) -> list[bool]:
        """Batched :meth:`touch`: one round trip on v2, a loop on v1
        (the v1 server predates the op). Degrades to all-False, which
        the demote hook maps to fresh puts — correct, just heavier."""
        keys = list(keys)
        if not keys:
            return []
        if self.wire == "v1":
            return [self.touch(k) for k in keys]
        hit = self._call_v2("touch_many", (keys,))
        if hit is None:
            return [False] * len(keys)
        return [bool(b) for b in hit[1]]

    def get(self, key: tuple):
        if self.wire == "v1":
            hit = self._call_v1("get", key)
            if hit is None or hit[1] is None:
                return None
            planes = _dec_planes(hit[1])
            self._count_xfer("rx", sum(int(p.nbytes) for p in planes))
            return planes
        hit = self._call_v2("get", (key,))
        if hit is None or not hit[1]:
            return None
        return hit[2][0]

    def get_run(self, keys: Sequence[tuple]) -> list:
        """Planes for the longest resident prefix of ``keys``: ONE
        round trip on v2 (a whole restore plan in one frame), a
        get-until-miss loop on v1. Degrades to an empty run —
        admission recomputes the tail."""
        keys = list(keys)
        if not keys:
            return []
        if self.wire == "v1":
            out = []
            for k in keys:
                planes = self.get(k)
                if planes is None:
                    break
                out.append(planes)
            return out
        hit = self._call_v2("get_run", (keys,))
        if hit is None:
            return []
        return list(hit[2])

    def run_len(self, keys: Sequence[tuple]) -> int:
        """Resident-prefix length without plane movement (the probe
        behind prefix_probe's host extension): one round trip on v2,
        a contains loop on v1. Degrades to 0."""
        keys = list(keys)
        if not keys:
            return 0
        if self.wire == "v1":
            n = 0
            for k in keys:
                if k not in self:
                    break
                n += 1
            return n
        hit = self._call_v2("run_len", (keys,))
        return int(hit[1]) if hit is not None else 0

    def __contains__(self, key: tuple) -> bool:
        hit = self._call_simple("contains", key)
        return bool(hit[1]) if hit is not None else False

    def refresh_stats(self) -> dict:
        """One explicit stats exchange (tests + periodic refresh);
        returns the cached snapshot either way."""
        self._call_simple("stats")
        return dict(self._stats)

    # Read properties serve the piggybacked cache — NEVER the network
    # (the admission overflow hook reads headroom on the event loop).

    def __len__(self) -> int:
        return int(self._stats.get("pages", 0))

    @property
    def bytes_used(self) -> int:
        return int(self._stats.get("bytes_used", 0))

    @property
    def budget_bytes(self) -> int:
        return int(self._stats.get("budget_bytes", 0))

    @property
    def headroom_bytes(self) -> int:
        return int(self._stats.get("headroom_bytes", 0))

    @property
    def demoted_pages(self) -> int:
        return int(self._stats.get("demoted_pages", 0))

    @property
    def dropped_pages(self) -> int:
        return int(self._stats.get("dropped_pages", 0))

    @property
    def lookups(self) -> int:
        return int(self._stats.get("lookups", 0))

    @property
    def hits(self) -> int:
        return int(self._stats.get("hits", 0))

    def stats_snapshot(self) -> dict:
        return dict(self._stats)

    def close(self) -> None:
        self._drop_socket()
        self._abort_pending()


def main(argv: list[str] | None = None) -> int:
    """Standalone authoritative store process:
    ``python -m llm_consensus_tpu.serving.remote_store --budget-mb 256``
    prints one JSON line ``{"endpoint": ...}`` then serves until
    SIGTERM/SIGINT — the cross-process half of a multi-host
    deployment."""
    import argparse
    import json
    import signal
    import sys

    p = argparse.ArgumentParser(prog="remote_store")
    p.add_argument("--budget-mb", type=int, default=256)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--uds", default=None, help="serve a unix socket path")
    args = p.parse_args(argv)
    server = PageStoreServer(
        HostPageStore(args.budget_mb << 20),
        host=args.host,
        port=args.port,
        path=args.uds,
    ).start()
    print(json.dumps({"endpoint": server.endpoint}), flush=True)
    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    try:
        while not stop.wait(0.5):
            pass
    finally:
        server.close()
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
