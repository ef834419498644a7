"""Asyncio HTTP/1.1 serving gateway (hand-rolled, stdlib only).

The network-facing layer over the scheduler / continuous-batcher /
coordinator stack. The reference binds actix HTTP handlers straight to
the coordinator with unbounded per-request futures
(``src/main.rs:101,156,182``); this gateway instead routes every request
through :class:`~llm_consensus_tpu.server.admission.AdmissionController`
(bounded queues, shed, deadlines, drain) and exports the metrics
registry at a standard scrape endpoint.

Routes:

- ``POST /v1/generate`` — one completion from the backend. Body:
  ``{"prompt": ..., "max_new_tokens"?, "temperature"?, "top_k"?,
  "top_p"?, "seed"?, "stop"?, "stream"?, "priority"?, "deadline_s"?,
  "logits"?}``. ``"logits": n`` (greedy requests, the continuous
  backend): ``meta.logits`` carries the float32 logits of the first n
  generated positions as the serving programs computed them.
  With ``"stream": true`` the response is Server-Sent Events: one
  ``data: {"text": piece}`` event per token chunk, a final
  ``data: {"done": true, ...}`` summary, then ``data: [DONE]``.
- ``POST /v1/consensus`` — drives the FULL panel protocol
  (:class:`~llm_consensus_tpu.consensus.coordinator.Coordinator`) for
  ``{"question": ..., "max_rounds"?, "seed"?, "priority"?,
  "deadline_s"?}`` and returns answer/rounds/endorsed/author/feedback.
- ``GET /metrics`` — Prometheus text exposition of the registry.
  ``?fleet=1`` on a front gateway (PR 20) scrapes every peer's
  ``/metrics`` and merges the families under a ``host=`` label
  (``host="self"`` is this process) — federation sums equal the sums
  of the per-peer scrapes.
- ``GET /healthz`` — LIVENESS: process up, drain state, backend
  heartbeat ages (always 200 while the process can answer).
- ``GET /readyz`` — READINESS: 503 while draining or while the
  backend's serving-loop heartbeat is staler than
  ``GatewayConfig.ready_stall_s`` (wedged loop => pull this replica
  from rotation without killing it).
- ``GET /debug/traces`` — request-trace summaries (newest first);
  ``?id=<trace_id>`` returns one trace's full span tree. Every
  ``/v1/*`` response carries its ``trace_id`` (body + ``X-Trace-Id``).
- ``GET /debug/flight`` — the serving flight recorder (PR 10): the
  bounded ring of typed scheduler events as JSON, or with
  ``?format=chrome`` as Chrome trace-event JSON loadable in Perfetto
  (device track reconstructed from dispatch→fetch windows, host track
  for un-overlapped scheduler work, one track per request).
  ``?fleet=1`` (PR 20) merges every peer's ring onto this process's
  clock (RTT-halving offset estimate from the ``now_pc`` stamp each
  reply carries); with ``format=chrome`` each host gets its own
  ``pid`` pair so one forwarded request reads as one aligned lane
  across processes.
- ``GET /debug/requests`` — per-request serving summaries (TTFT,
  inter-token-gap percentiles, spec tokens accepted per round,
  restored-vs-prefilled header pages); ``?id=<request or trace id>``
  returns one — or every member, for a trace several generations ran
  under (a consensus panel fan-out). The same summary rides each
  ``/v1/generate`` response as ``meta`` when the backend records one.
- ``GET /debug/chains`` — chain-residency probe (PR 16):
  ``?prompt=<text>`` (tokenized by the backend) or ``?ids=1,2,3``
  returns the backend's ``prefix_probe`` — how many leading tokens
  are registry-resident (``registry_tokens``) vs restorable from the
  host tier (``host_tokens``). This is the wire form of the
  PrefixRouter's affinity question, and what a PEER front gateway
  asks before routing.

Cross-host peer tier (PR 16): ``GatewayConfig(peers=(...))`` turns
this gateway into a ROUTING FRONT — ``/v1/*`` requests are not served
locally but forwarded to the peer gateway whose ``/debug/chains``
probe shows the longest resident chain for the prompt (ties and cold
chains go to the first reachable peer: "move the query, not the
cache" across hosts). The probe + forward run in the default executor
(urllib blocks); the peer's response body/status relay with this
front's ``X-Trace-Id`` attached. PR 20 makes that id a PROPAGATED
context: it rides the forwarded *request* too, the peer *adopts* it
(its spans join the front's trace), and the front folds its routing
time into the relayed ``meta["hops"]`` — so one trace id genuinely
follows the request across hosts and the per-hop breakdown covers the
whole path. An unreachable peer is skipped; all peers unreachable
=> 502.

Status mapping: 429 + ``Retry-After`` on shed, 503 + ``Retry-After``
while draining, 504 on deadline expiry, 502 on backend failure, 400 on
malformed requests. Every response closes the connection
(``Connection: close``) — serving concurrency comes from concurrent
connections, which asyncio multiplexes on one loop.

The HTTP layer is deliberately minimal (HTTP/1.1, Content-Length
bodies, no TLS, no keep-alive, no chunked *request* bodies): it is the
in-process front door for tests and single-host serving, and the
protocol surface later scale-out PRs (multi-replica routing,
disaggregated prefill) stand behind.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import logging
import math
import re
import threading
import time

from llm_consensus_tpu.backends.base import (
    Backend,
    BackendError,
    GenerationRequest,
    GenerationResult,
    SamplingParams,
)
from llm_consensus_tpu.server import metrics as _metrics
from llm_consensus_tpu.server.admission import (
    AdmissionConfig,
    AdmissionController,
    DeadlineExpiredError,
    DrainingError,
    QueueFullError,
)
from llm_consensus_tpu.utils import tracing as _tracing

log = logging.getLogger(__name__)

__all__ = ["Gateway", "GatewayConfig", "GatewayThread"]

_MAX_HEADER_LINES = 100
_TOKENISH = re.compile(r"\S+\s*|\s+")


class _HTTPError(Exception):
    def __init__(self, status: int, message: str, headers=None):
        super().__init__(message)
        self.status = status
        self.message = message
        self.headers = dict(headers or {})


_REASONS = {
    200: "OK",
    400: "Bad Request",
    404: "Not Found",
    405: "Method Not Allowed",
    408: "Request Timeout",
    413: "Payload Too Large",
    429: "Too Many Requests",
    500: "Internal Server Error",
    502: "Bad Gateway",
    503: "Service Unavailable",
    504: "Gateway Timeout",
}

#: One Prometheus exposition sample line: name, optional {labels}, value.
_SAMPLE_RE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})?\s+(\S+.*)$")


def _metrics_family(name: str, known: dict) -> str:
    """Family a sample line belongs to: histogram series (`_bucket`/
    `_sum`/`_count`) group under their base family when its HELP/TYPE
    header was seen; everything else is its own family."""
    for suf in ("_bucket", "_sum", "_count"):
        if name.endswith(suf) and name[: -len(suf)] in known:
            return name[: -len(suf)]
    return name


def _merge_metrics_text(texts: dict) -> str:
    """Merge per-host Prometheus expositions under a ``host=`` label
    (PR 20 federation view). Values relay verbatim — a summed family in
    the merged view is exactly the sum of the per-host scrapes (the
    lockstep the federation tests assert). HELP/TYPE headers dedupe to
    one copy per family; samples group under their family so strict
    parsers stay happy.
    """
    meta_lines: dict[str, list[str]] = {}
    fam_order: list[str] = []
    fam_samples: dict[str, list[str]] = {}
    for host, text in texts.items():
        for line in text.splitlines():
            line = line.rstrip()
            if not line:
                continue
            if line.startswith("#"):
                parts = line.split(None, 3)
                fam = parts[2] if len(parts) >= 3 else line
                if fam not in meta_lines:
                    meta_lines[fam] = []
                    fam_samples.setdefault(fam, [])
                    fam_order.append(fam)
                if line not in meta_lines[fam]:
                    meta_lines[fam].append(line)
                continue
            m = _SAMPLE_RE.match(line)
            if not m:
                continue
            name, labels, value = m.groups()
            fam = _metrics_family(name, meta_lines)
            if fam not in fam_samples:
                fam_samples[fam] = []
                meta_lines.setdefault(fam, [])
                fam_order.append(fam)
            inner = labels[1:-1] if labels else ""
            merged = f'host="{host}"' + ("," + inner if inner else "")
            fam_samples[fam].append(f"{name}{{{merged}}} {value}")
    out: list[str] = []
    for fam in fam_order:
        out.extend(meta_lines.get(fam, ()))
        out.extend(fam_samples.get(fam, ()))
    return "\n".join(out) + "\n"


class GatewayConfig:
    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 8080,
        admission: AdmissionConfig | None = None,
        max_body_bytes: int = 1 << 20,
        # Cap on reading one request's head+body. An idle open socket
        # otherwise pins the handler (and with it drain: Server.
        # wait_closed waits on every active connection) forever.
        read_timeout_s: float = 30.0,
        # Default sampling for /v1/generate when the body omits a field.
        sampling: SamplingParams | None = None,
        # Coordinator defaults for /v1/consensus.
        max_rounds: int = 5,
        consensus_seed: int | None = None,
        # Readiness (GET /readyz): 503 when the backend's serving loop
        # heartbeat is older than this (wedged device call, deadlock).
        # Size it above the longest legitimate device program.
        ready_stall_s: float = 10.0,
        # Opt-in JAX device profiling: a request carrying
        # ``X-Profile: 1`` wraps its backend work in
        # ``jax.profiler.trace(profile_dir)`` (one at a time; TensorBoard
        # format; its ``profile.anchor`` event places the request's
        # host spans on its clock). None = off.
        profile_dir: str | None = None,
        # Cross-host peer tier (PR 16): base URLs of downstream peer
        # gateways ("http://host:port"). Non-empty => this gateway is a
        # routing FRONT: /v1/* is forwarded to the peer whose
        # /debug/chains probe shows the longest resident chain.
        peers: tuple = (),
        # Budget for one forwarded /v1/* request (generation time
        # included — size like a client timeout, not an RPC timeout).
        peer_timeout_s: float = 120.0,
        # Budget for one /debug/chains residency probe; a peer that
        # cannot answer this quickly is skipped for this request.
        peer_probe_timeout_s: float = 2.0,
        # Fleet observability (PR 20): adopt an incoming X-Trace-Id
        # as this process's trace id (child spans join the front's
        # trace instead of rooting a fresh one), attach the per-hop
        # breakdown to response ``meta["hops"]``, and serve the
        # ``/metrics?fleet=1`` / ``/debug/flight?fleet=1`` federation
        # views (``serve --no-fleet-obs`` turns it off).
        fleet_obs: bool = True,
    ):
        self.host = host
        self.port = port
        self.admission = admission or AdmissionConfig()
        self.max_body_bytes = max_body_bytes
        self.read_timeout_s = read_timeout_s
        self.sampling = sampling or SamplingParams()
        self.max_rounds = max_rounds
        self.consensus_seed = consensus_seed
        self.ready_stall_s = ready_stall_s
        self.profile_dir = profile_dir
        self.peers = tuple(p.rstrip("/") for p in peers)
        self.peer_timeout_s = peer_timeout_s
        self.peer_probe_timeout_s = peer_probe_timeout_s
        self.fleet_obs = bool(fleet_obs)


class Gateway:
    """One backend + one panel behind an admission-controlled HTTP front.

    ``panel`` feeds ``POST /v1/consensus``; each request gets a fresh
    :class:`Coordinator` (the coordinator holds per-question state, so
    instances are per-request while panel/backend/config are shared).
    """

    def __init__(
        self,
        backend: Backend,
        panel=None,
        config: GatewayConfig | None = None,
        registry: _metrics.MetricsRegistry | None = None,
    ):
        self.backend = backend
        self.config = config or GatewayConfig()
        self.registry = registry or _metrics.REGISTRY
        if panel is None:
            from llm_consensus_tpu.consensus.personas import default_panel

            panel = default_panel()
        self.panel = panel
        self.admission = AdmissionController(
            self.config.admission, registry=self.registry
        )
        # Preempt-instead-of-shed (PR 14): a backend that can free
        # capacity under overload (the replica fleet demotes resident
        # KV chains to its shared host tier) exposes
        # ``preempt_for_admission``; the admission controller consults
        # it at queue-full moments and admits past the bound while it
        # returns True — 429s resume only when preemption is exhausted.
        hook = getattr(backend, "preempt_for_admission", None)
        if callable(hook):
            self.admission.overflow_hook = hook
        self._server: asyncio.Server | None = None
        self._conn_tasks: set[asyncio.Task] = set()
        self.port: int | None = None  # actual bound port (ephemeral-safe)
        self._started = time.monotonic()
        # One device profile at a time: jax.profiler.start_trace is
        # process-global and errors on nesting.
        self._profile_lock = threading.Lock()
        reg = self.registry
        self._m_requests = reg.counter(
            "gateway_requests_total", "HTTP requests by route and status"
        )
        self._m_ttft = reg.histogram(
            "gateway_ttft_seconds",
            "Time from request arrival to first token byte",
        )
        self._m_latency = reg.histogram(
            "gateway_request_seconds", "Full request latency"
        )
        self._m_tps = reg.histogram(
            "gateway_tokens_per_second",
            "Generated tokens per second of request wall-clock",
            buckets=_metrics.THROUGHPUT_BUCKETS,
        )
        self._m_hops = reg.histogram(
            "gateway_hop_seconds",
            "Per-hop request time attribution (PR 20): front_route, "
            "admission_wait, prefill, handoff, wire_transfer, decode",
        )
        # Best clock-offset estimate per peer host (PR 20):
        # host -> (offset_s, rtt_s); min-RTT wins (NTP-style — the
        # tightest round trip bounds the midpoint error). Fed
        # opportunistically by every /debug/chains routing probe and
        # fleet scrape that sees a peer ``now_pc`` stamp.
        self._peer_offsets: dict[str, tuple[float, float]] = {}

    # -- lifecycle ------------------------------------------------------

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._handle_conn, self.config.host, self.config.port
        )
        self.port = self._server.sockets[0].getsockname()[1]
        log.info(
            "gateway listening on %s:%d (%d panelists)",
            self.config.host,
            self.port,
            len(self.panel),
        )

    async def serve_forever(self) -> None:
        assert self._server is not None, "call start() first"
        async with self._server:
            await self._server.serve_forever()

    async def drain(self) -> None:
        """Graceful shutdown: stop admitting, finish every admitted
        request, then stop accepting connections."""
        log.info("gateway draining (%d pending)", self.admission.pending())
        await self.admission.drain()
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
        # Server.wait_closed() does not wait for in-flight connection
        # HANDLERS before 3.12 (gh-79033) — wait for them explicitly so
        # an admitted request's response finishes writing before exit.
        # Admitted work is already done and reads time out
        # (read_timeout_s), so this is normally write-flush time only —
        # but a client that stops READING its response can pin a write
        # forever, so the wait carries the same bound.
        if self._conn_tasks:
            await asyncio.wait(
                list(self._conn_tasks), timeout=self.config.read_timeout_s
            )
        log.info("gateway drained")

    # -- connection handling --------------------------------------------

    async def _handle_conn(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        # Tracked so drain() can wait for handlers (see drain()).
        task = asyncio.current_task()
        if task is not None:
            self._conn_tasks.add(task)
        try:
            try:
                method, path, headers, body = await asyncio.wait_for(
                    self._read_request(reader), self.config.read_timeout_s
                )
            except _HTTPError as e:
                await self._respond_json(
                    writer, e.status, {"error": e.message}, e.headers
                )
                return
            except (asyncio.TimeoutError, TimeoutError):
                with contextlib.suppress(Exception):
                    await self._respond_json(
                        writer, 408, {"error": "request read timed out"}
                    )
                return
            except (ValueError, asyncio.LimitOverrunError):
                # StreamReader raises ValueError for a request/header
                # line past its 64 KiB limit: a client error, not a
                # handler crash.
                with contextlib.suppress(Exception):
                    await self._respond_json(
                        writer, 400, {"error": "malformed request"}
                    )
                return
            except (asyncio.IncompleteReadError, ConnectionError):
                return
            await self._route(method, path, headers, body, writer)
        except (ConnectionError, asyncio.CancelledError):
            pass
        except Exception:  # noqa: BLE001 - last-resort 500
            log.exception("gateway handler crashed")
            with contextlib.suppress(Exception):
                await self._respond_json(
                    writer, 500, {"error": "internal error"}
                )
        finally:
            if task is not None:
                self._conn_tasks.discard(task)
            with contextlib.suppress(Exception):
                writer.close()
                await writer.wait_closed()

    async def _read_request(self, reader: asyncio.StreamReader):
        line = await reader.readline()
        if not line:
            raise asyncio.IncompleteReadError(b"", None)
        try:
            method, path, _version = line.decode("latin-1").split()
        except ValueError:
            raise _HTTPError(400, "malformed request line") from None
        headers: dict[str, str] = {}
        for _ in range(_MAX_HEADER_LINES):
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            k, sep, v = h.decode("latin-1").partition(":")
            if not sep:
                raise _HTTPError(400, f"malformed header {h!r}")
            headers[k.strip().lower()] = v.strip()
        else:
            raise _HTTPError(400, "too many headers")
        body = b""
        try:
            n = int(headers.get("content-length", "0") or "0")
        except ValueError:
            raise _HTTPError(400, "malformed Content-Length") from None
        if n < 0:
            raise _HTTPError(400, "malformed Content-Length")
        if n > self.config.max_body_bytes:
            raise _HTTPError(413, f"body of {n} bytes exceeds limit")
        if n:
            body = await reader.readexactly(n)
        return method, path, headers, body

    def _health_doc(self) -> dict:
        """Liveness payload: process-level state + the backend's serving
        loop heartbeat (when it exposes one)."""
        doc = {
            "status": "draining" if self.admission.draining else "ok",
            "pending": self.admission.pending(),
            "uptime_s": round(time.monotonic() - self._started, 3),
        }
        health = getattr(self.backend, "health", None)
        if callable(health):
            try:
                doc["backend"] = health()
            except Exception as e:  # noqa: BLE001 - health must not 500
                doc["backend"] = {"error": repr(e)}
        return doc

    def _readiness(self) -> tuple[bool, dict]:
        """Readiness: NOT ready while draining or while the backend's
        serving loop heartbeat is stale (wedged loop => stop routing
        traffic here; liveness stays 200 so the process isn't killed)."""
        doc = self._health_doc()
        if self.admission.draining:
            return False, {**doc, "reason": "draining"}
        hb = doc.get("backend") or {}
        if "error" in hb:
            # Fail CLOSED: a health probe that RAISES means the serving
            # loop's state is unknown — stop routing traffic here.
            return False, {**doc, "reason": f"health probe failed: {hb['error']}"}
        age = hb.get("last_tick_age_s")
        # Replica fleet (PR 14): the backend heartbeat aggregates one
        # entry per batcher replica. The aggregate alive/max-age checks
        # below already flip readiness when ANY replica wedges (alive
        # is ANDed, the age is the stalest loop's); here we NAME the
        # wedged indices so the operator knows which replica to
        # restart — the router has already stopped sending it traffic.
        # Elastic lifecycle (PR 19): a DRAINING replica is deliberately
        # finishing its in-flight work while the router skips it, and a
        # RETIRED replica's loop is deliberately stopped — neither is
        # wedged, and neither may flip readiness. They are surfaced
        # under their own keys so the operator sees the drain progress.
        replicas = hb.get("replicas") or []
        draining = [
            i
            for i, r in enumerate(replicas)
            if r.get("state") == "draining"
        ]
        retired = [
            i
            for i, r in enumerate(replicas)
            if r.get("state") == "retired"
        ]
        wedged = [
            i
            for i, r in enumerate(replicas)
            if r.get("state", "serving") == "serving"
            and (
                not r.get("alive")
                or (
                    r.get("last_tick_age_s") is not None
                    and r["last_tick_age_s"] > self.config.ready_stall_s
                )
            )
        ]
        if draining:
            doc = {**doc, "draining_replicas": draining}
        if retired:
            doc = {**doc, "retired_replicas": retired}
        if wedged:
            doc = {**doc, "wedged_replicas": wedged}
        if hb.get("alive") is False:
            reason = "serving loop dead"
            if wedged:
                reason = f"serving loop dead (replicas {wedged})"
            # A worker that died of an exception says which one — on
            # its own heartbeat, or on the replica entries'.
            failed = [
                h["failed"] for h in (hb, *replicas) if h.get("failed")
            ]
            if failed:
                reason += ": " + "; ".join(failed)
            return False, {**doc, "reason": reason}
        if age is not None and age > self.config.ready_stall_s:
            reason = (
                f"serving loop stalled {age:.1f}s "
                f"(> {self.config.ready_stall_s}s)"
            )
            if wedged:
                reason += f" (replicas {wedged})"
            return False, {**doc, "reason": reason}
        return True, doc

    async def _route(self, method, path, headers, body, writer) -> None:
        path, _, rawq = path.partition("?")
        if path == "/healthz" and method == "GET":
            await self._respond_json(writer, 200, self._health_doc())
            self._count(path, 200)
            return
        if path == "/readyz" and method == "GET":
            ready, doc = self._readiness()
            status = 200 if ready else 503
            await self._respond_json(
                writer,
                status,
                {**doc, "ready": ready},
                None if ready else {"Retry-After": "5"},
            )
            self._count(path, status)
            return
        if path == "/debug/traces" and method == "GET":
            await self._handle_traces(rawq, writer)
            return
        if path == "/debug/flight" and method == "GET":
            await self._handle_flight(rawq, writer)
            return
        if path == "/debug/requests" and method == "GET":
            await self._handle_requests(rawq, writer)
            return
        if path == "/debug/chains" and method == "GET":
            await self._handle_chains(rawq, writer)
            return
        if path == "/metrics" and method == "GET":
            await self._handle_metrics(rawq, writer)
            return
        if path in ("/v1/generate", "/v1/consensus"):
            if method != "POST":
                await self._respond_json(
                    writer, 405, {"error": "POST only"}, {"Allow": "POST"}
                )
                self._count(path, 405)
                return
            try:
                payload = json.loads(body or b"{}")
                if not isinstance(payload, dict):
                    raise ValueError("body must be a JSON object")
            except ValueError as e:
                await self._respond_json(writer, 400, {"error": f"bad JSON: {e}"})
                self._count(path, 400)
                return
            if self.config.peers:
                await self._handle_peer_forward(
                    path, payload, body, writer, headers
                )
                return
            if path == "/v1/generate":
                await self._handle_generate(payload, headers, writer)
            else:
                await self._handle_consensus(payload, headers, writer)
            return
        await self._respond_json(writer, 404, {"error": f"no route {path}"})
        # Arbitrary client paths must not become metric labels (a port
        # scan would grow the family without bound): one shared label.
        self._count("<unmatched>", 404)

    async def _handle_traces(self, rawq: str, writer) -> None:
        """``GET /debug/traces``: newest-first summaries; ``?id=<trace>``
        returns that trace's full span tree; ``?limit=N`` bounds the
        listing."""
        from urllib.parse import parse_qs

        q = parse_qs(rawq)
        store = _tracing.trace_store()
        tid = (q.get("id") or [None])[0]
        if tid:
            trace = store.get(tid)
            if trace is None:
                await self._respond_json(
                    writer, 404, {"error": f"no trace {tid!r}"}
                )
                self._count("/debug/traces", 404)
                return
            await self._respond_json(writer, 200, trace.to_dict())
            self._count("/debug/traces", 200)
            return
        try:
            limit = int((q.get("limit") or ["50"])[0])
        except ValueError:
            limit = 50
        await self._respond_json(
            writer,
            200,
            {
                "enabled": _tracing.enabled(),
                "max_traces": store.max_traces,
                "max_spans_per_trace": store.max_spans,
                "evicted_traces": store.evicted,
                "traces": [t.summary() for t in store.traces(limit)],
            },
        )
        self._count("/debug/traces", 200)

    async def _handle_metrics(self, rawq: str, writer) -> None:
        """``GET /metrics``: Prometheus text exposition. With
        ``?fleet=1`` on a front gateway (PR 20): scrape every peer's
        ``/metrics`` concurrently and merge the families under a
        ``host=`` label (``host="self"`` for this process) — sums over
        the merged view equal the sums of the per-peer scrapes."""
        from urllib.parse import parse_qs

        q = parse_qs(rawq)
        if (
            self.config.fleet_obs
            and (q.get("fleet") or [""])[0] in ("1", "true")
        ):
            texts = {"self": self.registry.render()}
            loop = asyncio.get_running_loop()
            if self.config.peers:
                fetched = await asyncio.gather(
                    *(
                        loop.run_in_executor(
                            None,
                            self._fetch_peer_text,
                            f"{p}/metrics",
                            self.config.peer_probe_timeout_s,
                        )
                        for p in self.config.peers
                    ),
                    return_exceptions=True,
                )
                for peer, got in zip(self.config.peers, fetched):
                    if isinstance(got, str):
                        texts[peer] = got
            await self._respond_raw(
                writer,
                200,
                _merge_metrics_text(texts).encode(),
                "text/plain; version=0.0.4; charset=utf-8",
            )
            self._count("/metrics", 200)
            return
        text = self.registry.render().encode()
        await self._respond_raw(
            writer, 200, text, "text/plain; version=0.0.4; charset=utf-8"
        )
        self._count("/metrics", 200)

    def _fetch_peer_text(self, url: str, timeout: float) -> str:
        """Blocking GET returning a peer's raw text body (executor
        only)."""
        import urllib.request

        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.read().decode("utf-8", "replace")

    def _fetch_peer_json(self, url: str, timeout: float):
        """Blocking GET returning ``(doc, t_send_pc, t_recv_pc)`` —
        the perf_counter stamps bracketing the exchange feed the
        RTT-halving clock-offset estimate (executor only)."""
        import urllib.request

        t0 = time.perf_counter()
        with urllib.request.urlopen(url, timeout=timeout) as r:
            doc = json.loads(r.read())
        return doc, t0, time.perf_counter()

    @staticmethod
    def _clock_offset(doc: dict, t_send: float, t_recv: float):
        """Midpoint clock-offset estimate from a reply carrying the
        peer's ``now_pc`` perf_counter stamp: assuming the reply was
        stamped mid-flight, ``t_local ≈ t_peer + offset`` with
        ``offset = (t_send + t_recv)/2 − now_pc``. Returns
        ``(offset_s, rtt_s)`` or ``(None, None)`` when the peer
        predates the stamp."""
        now = doc.get("now_pc")
        if not isinstance(now, (int, float)):
            return None, None
        return (t_send + t_recv) / 2.0 - float(now), t_recv - t_send

    def _note_offset(self, host: str, offset, rtt) -> None:
        if offset is None:
            return
        cur = self._peer_offsets.get(host)
        if cur is None or rtt <= cur[1]:
            self._peer_offsets[host] = (float(offset), float(rtt))

    async def _handle_flight(self, rawq: str, writer) -> None:
        """``GET /debug/flight``: the flight recorder's event ring
        (PR 10). ``?format=chrome`` renders Chrome trace-event JSON
        (open in Perfetto / chrome://tracing); the plain JSON form
        takes ``?limit=N`` (newest N events). Programs still in flight
        appear with their dispatch stamp and zero duration — quiesce
        before comparing the device track against counters."""
        from urllib.parse import parse_qs

        # Deferred: serving.flight rides the serving package (jax);
        # a FakeBackend gateway only pays that import if someone asks.
        from llm_consensus_tpu.serving import flight as _flight

        q = parse_qs(rawq)
        if (
            self.config.fleet_obs
            and (q.get("fleet") or [""])[0] in ("1", "true")
        ):
            await self._handle_flight_fleet(q, writer)
            return
        rec = _flight.flight_recorder()
        events = rec.events()
        raw_limit = (q.get("limit") or [None])[0]
        limit = None
        if raw_limit is not None:
            try:
                limit = int(raw_limit)
            except ValueError:
                limit = None
        if (q.get("format") or [""])[0] == "chrome":
            # ?limit= applies here too (newest N events); the default
            # is the whole ring — a Perfetto export wants everything.
            if limit is not None:
                # limit <= 0 really means "no events" — a bare -0:
                # slice would return the whole ring.
                events = events[-limit:] if limit > 0 else []
            await self._respond_json(writer, 200, _flight.to_chrome(events))
            self._count("/debug/flight", 200)
            return
        if limit is None:
            limit = 512
        await self._respond_json(
            writer,
            200,
            {
                "enabled": _flight.enabled(),
                "capacity": rec.capacity,
                "dropped": rec.dropped,
                "n_events": len(events),
                # Clock-probe stamp (PR 20): a scraping front halves
                # the exchange's RTT around this to place our
                # perf_counter timebase on its own.
                "now_pc": time.perf_counter(),
                "events": [
                    e.to_dict()
                    for e in (events[-limit:] if limit > 0 else [])
                ],
            },
        )
        self._count("/debug/flight", 200)

    async def _handle_flight_fleet(self, q: dict, writer) -> None:
        """``GET /debug/flight?fleet=1`` (PR 20): merged cross-process
        flight timeline. Scrapes every peer's ``/debug/flight``
        concurrently, estimates each peer's clock offset from the
        ``now_pc`` stamp riding the reply (RTT-halving midpoint;
        min-RTT estimate wins across probes), and merges the rings
        onto this process's perf_counter timebase. ``?format=chrome``
        renders one ``pid`` pair per host so a forwarded request reads
        as one aligned lane across processes."""
        from llm_consensus_tpu.serving import flight as _flight

        loop = asyncio.get_running_loop()
        own = _flight.flight_recorder().events()
        by_host: dict = {"self": (own, 0.0)}
        hosts_doc: dict = {"self": {"offset_s": 0.0, "rtt_s": 0.0}}
        unreachable: list[str] = []
        if self.config.peers:
            fetched = await asyncio.gather(
                *(
                    loop.run_in_executor(
                        None,
                        self._fetch_peer_json,
                        f"{p}/debug/flight?limit=100000",
                        self.config.peer_probe_timeout_s,
                    )
                    for p in self.config.peers
                ),
                return_exceptions=True,
            )
            for peer, got in zip(self.config.peers, fetched):
                if isinstance(got, BaseException):
                    unreachable.append(peer)
                    continue
                doc, t0, t1 = got
                off, rtt = self._clock_offset(doc, t0, t1)
                self._note_offset(peer, off, rtt)
                best = self._peer_offsets.get(peer)
                offset = best[0] if best else 0.0
                evs = [
                    _flight.FlightEvent(
                        seq=int(e.get("seq", 0)),
                        kind=str(e.get("kind", "?")),
                        t0=float(e.get("t0", 0.0)),
                        dur=float(e.get("dur_s", 0.0)),
                        trace_id=e.get("trace_id"),
                        meta=e.get("meta") or {},
                    )
                    for e in doc.get("events", ())
                    if isinstance(e, dict)
                ]
                by_host[peer] = (evs, offset)
                hosts_doc[peer] = {
                    "offset_s": round(offset, 6),
                    "rtt_s": round(best[1], 6) if best else None,
                }
        if (q.get("format") or [""])[0] == "chrome":
            await self._respond_json(
                writer, 200, _flight.to_chrome_fleet(by_host)
            )
            self._count("/debug/flight", 200)
            return
        merged = _flight.merge_fleet(by_host)
        try:
            limit = int((q.get("limit") or ["512"])[0])
        except ValueError:
            limit = 512
        await self._respond_json(
            writer,
            200,
            {
                "hosts": hosts_doc,
                "unreachable": unreachable,
                "n_events": len(merged),
                "events": [
                    {**e.to_dict(), "host": e.meta.get("host")}
                    for e in (merged[-limit:] if limit > 0 else [])
                ],
            },
        )
        self._count("/debug/flight", 200)

    async def _handle_requests(self, rawq: str, writer) -> None:
        """``GET /debug/requests``: per-request serving summaries from
        the RequestLog (newest first); ``?id=`` accepts a request id
        OR a trace id."""
        from urllib.parse import parse_qs

        from llm_consensus_tpu.serving import flight as _flight

        q = parse_qs(rawq)
        log_ = _flight.request_log()
        rid = (q.get("id") or [None])[0]
        if rid:
            docs = log_.get_all(rid)
            if not docs:
                await self._respond_json(
                    writer, 404, {"error": f"no request {rid!r}"}
                )
                self._count("/debug/requests", 404)
                return
            # One trace can cover several generations (a consensus
            # panel fan-out): a unique match returns the summary doc
            # itself, a shared trace returns every member.
            await self._respond_json(
                writer,
                200,
                docs[0]
                if len(docs) == 1
                else {"id": rid, "requests": docs},
            )
            self._count("/debug/requests", 200)
            return
        try:
            limit = int((q.get("limit") or ["50"])[0])
        except ValueError:
            limit = 50
        await self._respond_json(
            writer,
            200,
            {
                "retained": len(log_),
                "requests": log_.recent(limit),
            },
        )
        self._count("/debug/requests", 200)

    async def _handle_chains(self, rawq: str, writer) -> None:
        """``GET /debug/chains``: chain-residency probe (PR 16).
        ``?prompt=<text>`` (backend-tokenized) or ``?ids=1,2,3``
        answers the backend's ``prefix_probe`` — registry-resident vs
        host-restorable leading tokens. The probe itself takes the
        batcher lock, so it runs in the executor, never on the loop."""
        from urllib.parse import parse_qs

        probe = getattr(self.backend, "prefix_probe", None)
        if not callable(probe):
            await self._respond_json(
                writer, 404, {"error": "backend has no prefix probe"}
            )
            self._count("/debug/chains", 404)
            return
        q = parse_qs(rawq)
        raw_ids = (q.get("ids") or [None])[0]
        prompt = (q.get("prompt") or [None])[0]
        loop = asyncio.get_running_loop()
        try:
            if raw_ids:
                ids = [int(x) for x in raw_ids.split(",") if x.strip()]
            elif prompt:
                tok = getattr(self.backend, "tokenizer", None)
                if tok is None:
                    await self._respond_json(
                        writer,
                        404,
                        {"error": "backend has no tokenizer; use ?ids="},
                    )
                    self._count("/debug/chains", 404)
                    return
                # HF tokenizers can be slow on long prompts: executor.
                ids = await loop.run_in_executor(None, tok.encode, prompt)
            else:
                raise ValueError("need ?prompt=<text> or ?ids=1,2,3")
        except ValueError as e:
            await self._respond_json(writer, 400, {"error": str(e)})
            self._count("/debug/chains", 400)
            return
        doc = await loop.run_in_executor(None, probe, ids)
        # ``now_pc`` (PR 20): clock-probe stamp piggybacked on the
        # residency probe — the front halves the probe's RTT around it
        # to estimate this host's perf_counter offset for free.
        await self._respond_json(
            writer,
            200,
            {"n_ids": len(ids), "now_pc": time.perf_counter(), **doc},
        )
        self._count("/debug/chains", 200)

    # -- cross-host peer tier (PR 16) -----------------------------------

    def _probe_peer(self, peer: str, prompt: str) -> int:
        """Blocking residency probe of one peer (executor only).
        Returns the longest resident/restorable prefix in tokens, 0
        for a cold (or probe-less) peer, -1 for an unreachable one."""
        import urllib.parse
        import urllib.request

        url = (
            f"{peer}/debug/chains?prompt="
            f"{urllib.parse.quote(prompt, safe='')}"
        )
        try:
            t_send = time.perf_counter()
            with urllib.request.urlopen(
                url, timeout=self.config.peer_probe_timeout_s
            ) as r:
                doc = json.loads(r.read())
            t_recv = time.perf_counter()
            # Clock-offset piggyback (PR 20): every routing probe that
            # reaches a peer refines its offset estimate for free.
            off, rtt = self._clock_offset(doc, t_send, t_recv)
            self._note_offset(peer, off, rtt)
            return max(
                int(doc.get("registry_tokens", 0)),
                int(doc.get("host_tokens", 0)),
            )
        except Exception:  # noqa: BLE001 - any failure => skip peer
            return -1

    def _forward_peer(self, peer: str, path: str, body: bytes, tid):
        """Blocking forward of one /v1/* body to ``peer`` (executor
        only). Returns (status, body, content_type); raises only on
        transport failure (no HTTP response at all)."""
        import urllib.error
        import urllib.request

        headers = {"Content-Type": "application/json"}
        if tid:
            headers["X-Trace-Id"] = tid
        req = urllib.request.Request(
            f"{peer}{path}", data=body, headers=headers, method="POST"
        )
        try:
            with urllib.request.urlopen(
                req, timeout=self.config.peer_timeout_s
            ) as r:
                return (
                    r.status,
                    r.read(),
                    r.headers.get("Content-Type", "application/json"),
                )
        except urllib.error.HTTPError as e:
            # A peer's 4xx/5xx is a RESPONSE to relay, not a transport
            # failure: the peer's shed/drain statuses must reach the
            # client (its Retry-After semantics are the contract).
            return (
                e.code,
                e.read(),
                e.headers.get("Content-Type", "application/json"),
            )

    async def _handle_peer_forward(
        self, path: str, payload: dict, body: bytes, writer, headers=None
    ) -> None:
        """Front-gateway routing (PR 16): probe every peer's
        ``/debug/chains`` for this prompt concurrently, forward the
        request to the one with the longest resident chain (first
        reachable on ties/cold), relay its response. All blocking I/O
        runs in the executor; the loop never waits on a socket.

        Trace propagation (PR 20): ``X-Trace-Id`` rides the forwarded
        REQUEST (not just the relayed response) and the peer adopts it
        — one id genuinely follows the request across hosts, so the
        front's route spans and the peer's serving spans join under
        the same trace in the merged fleet export. A chained front
        adopts an incoming id the same way. The front also injects its
        own ``front_route`` hop (probe + routing decision time) into
        the relayed response's ``meta["hops"]``."""
        prompt = payload.get("prompt") or payload.get("question") or ""
        trace = _tracing.trace_store().start(
            path, route=path, trace_id=self._incoming_tid(headers)
        )
        tid = trace.trace_id if trace is not None else None
        t_start = time.monotonic()
        loop = asyncio.get_running_loop()
        try:
            if isinstance(prompt, str) and prompt:
                scores = await asyncio.gather(
                    *(
                        loop.run_in_executor(None, self._probe_peer, p, prompt)
                        for p in self.config.peers
                    )
                )
            else:
                # No prompt to probe with (bad body: let the peer 400
                # it) — treat every peer as cold-but-reachable.
                scores = [0] * len(self.config.peers)
            ranked = [
                (p, s)
                for p, s in zip(self.config.peers, scores)
                if s >= 0
            ]
            if not ranked and any(s < 0 for s in scores):
                # Every probe failed — the probes may be down while
                # serving still works (older peers): fall back to
                # trying peers in order rather than 502ing outright.
                ranked = [(p, 0) for p in self.config.peers]
            peer = max(ranked, key=lambda ps: ps[1])[0] if ranked else None
            if peer is None:
                await self._respond_json(
                    writer, 502, {"error": "no peers configured"}
                )
                self._count(path, 502)
                return
            t_fwd = time.monotonic()
            if trace is not None:
                # The routing decision's span: probe fan-out + ranking.
                # (Span stamps live in perf_counter space — backdate
                # the start by the measured monotonic duration.)
                route_s = t_fwd - t_start
                trace.add_span(
                    "front_route",
                    time.perf_counter() - route_s,
                    route_s,
                    peer=peer,
                )
            try:
                status, out, ctype = await loop.run_in_executor(
                    None, self._forward_peer, peer, path, body, tid
                )
            except Exception as e:  # noqa: BLE001 - transport failure
                log.warning("peer %s unreachable: %s", peer, e)
                await self._respond_json(
                    writer,
                    502,
                    {"error": f"peer {peer} unreachable", "trace_id": tid},
                )
                self._count(path, 502)
                return
            out = self._inject_front_hop(
                status, out, ctype, t_fwd - t_start
            )
            hdrs = {"X-Peer": peer}
            if tid:
                hdrs["X-Trace-Id"] = tid
            await self._respond_raw(writer, status, out, ctype, hdrs)
            self._count(path, status)
        finally:
            if trace is not None:
                trace.finish()

    def _inject_front_hop(
        self, status: int, out: bytes, ctype: str, front_s: float
    ) -> bytes:
        """Fold this front's routing time into the relayed response's
        ``meta["hops"]`` (PR 20) so the client-visible hop breakdown
        covers the WHOLE path, front included. Only a parseable 200
        JSON body is touched — anything else relays verbatim."""
        if not (
            self.config.fleet_obs
            and status == 200
            and "json" in (ctype or "")
        ):
            return out
        try:
            doc = json.loads(out)
            if not isinstance(doc, dict):
                return out
            meta = doc.get("meta") or {}
            hops = {
                "front_route": round(front_s, 6),
                **(meta.get("hops") or {}),
            }
            doc["meta"] = {**meta, "hops": hops}
            self._m_hops.labels(hop="front_route").observe(front_s)
            return json.dumps(doc).encode()
        except Exception:  # noqa: BLE001 - relay verbatim on any doubt
            return out

    @staticmethod
    def _shed_reason(e: Exception) -> str:
        """Flight-event reason for a shed (PR 19): ``slo`` = deadline-
        aware shed of a would-miss request, ``tenant`` = fair-share cap,
        ``draining`` = SIGTERM drain, else the classic ``queue_full``."""
        if isinstance(e, DrainingError):
            return "draining"
        if getattr(e, "slo_miss", False):
            return "slo"
        if getattr(e, "tenant_over", False):
            return "tenant"
        return "queue_full"

    def _record_shed(self, route: str, trace, reason: str = "queue_full") -> None:
        """Mirror an admission shed into the flight recorder (PR 10):
        the timeline's counterpart of the 429/503 the client saw.

        Records ONLY when the flight module is already loaded: an
        import here would execute the serving package's __init__ (and
        with it jax) synchronously inside the event loop — seconds of
        stall for every in-flight request, at exactly peak overload.
        A gateway whose backend never loaded the serving stack has no
        batcher feeding the ring, so there is no timeline to join.
        """
        import sys as _sys

        mod = _sys.modules.get("llm_consensus_tpu.serving.flight")
        if mod is None:
            return
        try:
            mod.flight_recorder().record(
                "shed",
                time.perf_counter(),
                trace_id=_tracing.trace_id_of(trace),
                route=route,
                reason=reason,
            )
        except Exception:  # noqa: BLE001 - recording must never 500
            log.exception("flight shed record failed")

    # -- routes ---------------------------------------------------------

    @contextlib.contextmanager
    def _maybe_profile(self, headers: dict):
        """``X-Profile: 1`` (with ``GatewayConfig.profile_dir`` set)
        captures a JAX device profile around this request's backend
        work — a TensorBoard trace in ``profile_dir``. A ``jax_profile``
        span marks the window on the request's trace, and the profile
        opens with a ``profile.anchor`` event that carries
        ``perf_counter_ns`` (:func:`tracing.trace_jax_profile`): the
        stamp that places the request's host spans on the profile's
        clock, beside the batcher's ``batcher.<phase>`` events. One
        capture at a time: concurrent flagged
        requests run unprofiled rather than queueing on the profiler's
        process-global state. SSE streaming requests are not profiled
        (their backend work outlives the handler's await points)."""
        if not (
            self.config.profile_dir
            and headers.get("x-profile", "").strip() == "1"
        ):
            yield False
            return
        if not self._profile_lock.acquire(blocking=False):
            log.warning("X-Profile ignored: a device profile is in flight")
            yield False
            return
        try:
            with _tracing.request_span(
                "jax_profile", logdir=self.config.profile_dir
            ), _tracing.trace_jax_profile(self.config.profile_dir):
                yield True
        finally:
            self._profile_lock.release()

    @staticmethod
    def _trace_id() -> str | None:
        trace = _tracing.current_trace()
        return trace.trace_id if trace is not None else None

    def _incoming_tid(self, headers) -> str | None:
        """The ``X-Trace-Id`` a forwarding front attached (PR 20) —
        adopting it roots this process's spans under the front's trace
        id instead of minting a fresh root. None when fleet
        observability is off or no id arrived; the trace store
        validates the id's shape before adopting."""
        if not self.config.fleet_obs or not headers:
            return None
        return headers.get("x-trace-id")

    def _hop_breakdown(self, trace, meta, dt: float) -> dict | None:
        """Per-hop time attribution for one request (PR 20), sourced
        from the joined trace spans plus the batcher's summary meta:

        - ``admission_wait`` — the admission queue's "queued" span(s);
        - ``prefill`` / ``decode`` — split from the serving summary's
          ``ttft_s`` / ``duration_s`` when the backend records one,
          else the admission "execute" span stands in for ``decode``;
        - ``handoff`` — disagg claim→export→restore spans;
        - ``wire_transfer`` — remote-store ``store_op`` spans.

        A forwarding front prepends ``front_route`` at relay time
        (:meth:`_inject_front_hop`). For a single-generation request
        the hop sum tracks the client-observed latency
        (tests/test_fleet_observability.py); a consensus fan-out's
        spans overlap, so there the breakdown is attribution, not a
        wall-clock identity. Each hop lands in the
        ``gateway_hop_seconds{hop=}`` histogram."""
        if not self.config.fleet_obs or trace is None:
            return None
        sums: dict[str, float] = {}
        for s in trace.spans():
            if s.name == "queued":
                sums["admission_wait"] = (
                    sums.get("admission_wait", 0.0) + s.duration
                )
            elif s.name == "handoff":
                sums["handoff"] = sums.get("handoff", 0.0) + s.duration
            elif s.name == "store_op":
                sums["wire_transfer"] = (
                    sums.get("wire_transfer", 0.0) + s.duration
                )
            elif s.name == "execute":
                sums["execute"] = sums.get("execute", 0.0) + s.duration
        hops: dict[str, float] = {}
        timing = meta if isinstance(meta, dict) else {}
        ttft = timing.get("ttft_s")
        dur = timing.get("duration_s")
        if isinstance(ttft, (int, float)):
            hops["prefill"] = float(ttft)
            if isinstance(dur, (int, float)) and dur >= ttft:
                hops["decode"] = float(dur) - float(ttft)
        elif "execute" in sums:
            # No serving summary (e.g. a FakeBackend): the execute
            # span IS the backend time; call it decode rather than
            # invent a prefill split the backend never measured.
            hops["decode"] = sums["execute"]
        if "handoff" in sums and "wire_transfer" in sums:
            # Store-op spans nest INSIDE the handoff window (the
            # coordinator's claim→export→restore wraps the page
            # put/get): report handoff net of its wire time so the
            # hop sum stays a partition, not a double count.
            sums["handoff"] = max(
                0.0, sums["handoff"] - sums["wire_transfer"]
            )
        for key in ("admission_wait", "handoff", "wire_transfer"):
            if key in sums:
                hops[key] = sums[key]
        if not hops:
            return None
        hops = {k: round(v, 6) for k, v in hops.items()}
        for k, v in hops.items():
            self._m_hops.labels(hop=k).observe(v)
        return hops

    def _sampling_from(self, payload: dict) -> SamplingParams:
        d = self.config.sampling
        stop = payload.get("stop") or ()
        if isinstance(stop, str):
            stop = (stop,)
        temperature = float(payload.get("temperature", d.temperature))
        logits = int(payload.get("logits", 0))
        if logits < 0 or (logits and temperature > 0):
            raise ValueError("'logits' takes n >= 0, on greedy requests only")
        return SamplingParams(
            max_new_tokens=int(
                payload.get("max_new_tokens", d.max_new_tokens)
            ),
            temperature=temperature,
            top_k=int(payload.get("top_k", d.top_k)),
            top_p=float(payload.get("top_p", d.top_p)),
            seed=int(payload.get("seed", d.seed)),
            stop=tuple(stop),
            logits=logits,
        )

    def _cost_kw(
        self, adm_kw: dict, prompt: str, max_new_tokens: int, members: int = 1
    ) -> dict:
        """Attach the request's modeled cost (PR 15) when the
        admission controller runs in cost-budget mode and the backend
        can price it (``request_cost`` — the continuous batcher's
        modeled bytes, the same unit the fleet router's load_cost
        compares). ``members``: a consensus panel fans one question
        into N generations, so it costs N times the single prompt.
        Pricing failures fall back to the controller's nominal-slot
        default rather than 500ing the request."""
        if self.admission.config.cost_budget_bytes <= 0:
            return adm_kw
        rc = getattr(self.backend, "request_cost", None)
        if callable(rc):
            try:
                adm_kw["cost"] = float(rc(prompt, max_new_tokens)) * members
            except Exception:  # noqa: BLE001 - pricing must not 500
                log.exception("request_cost failed; using nominal cost")
        return adm_kw

    def _lane_for(self, model: str | None, fallback: str) -> str:
        """Per-model admission lane (PR 18): when the controller was
        configured with a ``model:<name>`` priority lane for this
        request's model tag, default the request there — one member's
        burst queues behind its own bound instead of starving the
        panel's other models. An explicit payload ``priority`` always
        wins (``_admission_kw`` reads it first); unknown models keep
        the route's base lane and fail later with the backend's
        unknown-model error, not a KeyError here."""
        if model:
            lane = f"model:{model}"
            if lane in self.admission.config.priorities:
                return lane
        return fallback

    def _admission_kw(self, payload: dict, default_priority: str) -> dict:
        kw = {"priority": payload.get("priority", default_priority)}
        if payload.get("deadline_s") is not None:
            d = float(payload["deadline_s"])
            # json.loads accepts NaN/Infinity: a non-finite deadline
            # reaches loop.call_later(nan) and corrupts the shared timer
            # heap (NaN compares False both ways) for the whole process.
            if not math.isfinite(d):
                raise ValueError(f"deadline_s must be finite, got {d}")
            kw["deadline_s"] = d
        # SLO class + tenant (PR 19): validated HERE, at the 400
        # boundary, so a typo'd class never reaches admission as a 500.
        if payload.get("slo") is not None:
            s = payload["slo"]
            classes = self.admission.config.slo_classes or {}
            if not isinstance(s, str) or s not in classes:
                raise ValueError(
                    f"unknown slo class {s!r}; have {sorted(classes)}"
                )
            kw["slo"] = s
        if payload.get("tenant") is not None:
            t = payload["tenant"]
            if not isinstance(t, str) or not t:
                raise ValueError("tenant must be a non-empty string")
            kw["tenant"] = t
        return kw

    async def _handle_generate(self, payload: dict, headers, writer) -> None:
        prompt = payload.get("prompt")
        if not isinstance(prompt, str) or not prompt:
            await self._respond_json(
                writer, 400, {"error": "need a non-empty string 'prompt'"}
            )
            self._count("/v1/generate", 400)
            return
        # Field coercion up front: a mistyped body ("max_new_tokens":
        # "abc") is the client's 400, not a handler crash.
        try:
            req = GenerationRequest(
                prompt=prompt,
                params=self._sampling_from(payload),
                model=payload.get("model"),
            )
            adm_kw = self._cost_kw(
                self._admission_kw(
                    payload,
                    self._lane_for(payload.get("model"), "interactive"),
                ),
                prompt,
                req.params.max_new_tokens,
            )
        except (TypeError, ValueError, OverflowError) as e:
            await self._respond_json(
                writer, 400, {"error": f"bad request field: {e}"}
            )
            self._count("/v1/generate", 400)
            return
        # The trace is minted AFTER validation (a 400 never mints one)
        # and discarded again if admission sheds the request — a 429
        # storm must not churn the bounded ring and evict the slow
        # traces being debugged. Everything downstream — admission
        # queue, coordinator rounds, batcher chunks/steps — attaches
        # spans through the contextvars protocol or explicit trace
        # handles (None when tracing is disabled: every site no-ops).
        trace = _tracing.trace_store().start(
            "/v1/generate",
            route="/v1/generate",
            # Adopt a forwarding front's id (PR 20): this process's
            # spans join the front's trace instead of rooting anew.
            trace_id=self._incoming_tid(headers),
        )
        # Route-driven restore prefetch (PR 17): the destination is
        # decided (single-replica backends) or about to be (the fleet
        # prefetches again at route time), and the request is about to
        # sit in the admission queue — free overlap for staging the
        # chain's host-store pages. Non-blocking, advisory, and never
        # allowed to fail the request.
        pf = getattr(self.backend, "prefetch", None)
        if callable(pf):
            try:
                pf(prompt)
            except Exception:  # noqa: BLE001 - advisory path
                log.exception("prefetch hook failed (ignored)")
        t0 = time.monotonic()
        if payload.get("stream"):
            try:
                with _tracing.use_trace(trace):
                    await self._handle_generate_stream(
                        req, adm_kw, writer, t0
                    )
            finally:
                if trace is not None:
                    trace.finish()
            return

        async def thunk():
            # Profiling wraps ONLY the backend call, inside the
            # dispatched thunk: the capture window (and the one-at-a-
            # time profiler slot) must not include the admission-queue
            # wait, where it would mostly record OTHER requests' work.
            with self._maybe_profile(headers):
                return await self.backend.generate(req)

        try:
            with _tracing.use_trace(trace):
                result: GenerationResult = await self.admission.submit(
                    thunk, **adm_kw
                )
        except Exception as e:  # noqa: BLE001 - mapped to HTTP statuses
            status, doc, hdrs = self._error_response(e)
            if isinstance(e, (QueueFullError, DrainingError)):
                self._record_shed(
                    "/v1/generate", trace, self._shed_reason(e)
                )
                if trace is not None:
                    _tracing.trace_store().discard(trace.trace_id)
            await self._respond_json(writer, status, doc, hdrs)
            self._count("/v1/generate", status)
            return
        finally:
            if trace is not None:
                trace.finish()
        dt = time.monotonic() - t0
        self._observe_generation(dt, dt, result.num_tokens)
        tid = trace.trace_id if trace is not None else None
        meta = getattr(result, "meta", None)
        hops = self._hop_breakdown(trace, meta, dt)
        if hops:
            # Fold IN PLACE when the backend handed us its RequestLog
            # summary (same dict object) — /debug/requests must serve
            # the identical doc the response meta carries.
            if isinstance(meta, dict):
                meta["hops"] = hops
            else:
                meta = {"hops": hops}
        await self._respond_json(
            writer,
            200,
            {
                "text": result.text,
                "num_tokens": result.num_tokens,
                "logprob": result.logprob,
                "trace_id": tid,
                # Per-request serving timeline (PR 10) when the backend
                # records one (the continuous batcher's summary — the
                # same doc /debug/requests?id= serves).
                **({"meta": meta} if meta else {}),
            },
            {"X-Trace-Id": tid} if tid else None,
        )
        self._count("/v1/generate", 200)

    async def _handle_generate_stream(
        self, req: GenerationRequest, adm_kw: dict, writer, t0: float
    ) -> None:
        """SSE streaming: events flow as the backend produces pieces.

        Backends that expose token streaming (an async-generator
        ``generate_stream(request)``) stream truly incrementally; any
        other backend falls back to one admission-controlled generate
        whose text is then chunked into token-ish SSE events — the
        stream CONTENT is identical either way (tested).
        """
        q: asyncio.Queue[str] = asyncio.Queue()
        loop = asyncio.get_running_loop()

        def push(piece: str) -> None:
            loop.call_soon_threadsafe(q.put_nowait, piece)

        task = asyncio.create_task(
            self.admission.submit(
                lambda: self._streaming_thunk(req, push), **adm_kw
            )
        )
        first_at: float | None = None
        headers_sent = False

        async def emit(piece: str) -> None:
            nonlocal first_at, headers_sent
            if not headers_sent:
                await self._start_sse(writer)
                headers_sent = True
            if first_at is None:
                first_at = time.monotonic()
                self._m_ttft.observe(first_at - t0)
            await self._sse_event(writer, {"text": piece})

        try:
            while True:
                getter = asyncio.create_task(q.get())
                done, _pending = await asyncio.wait(
                    {getter, task}, return_when=asyncio.FIRST_COMPLETED
                )
                if getter in done:
                    await emit(getter.result())
                    continue
                getter.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await getter
                break
            # Terminal: flush any pieces the producer pushed after the
            # last wait round, then the summary.
            while not q.empty():
                await emit(q.get_nowait())
            result: GenerationResult = task.result()
        except ConnectionError:
            # The client went away mid-stream (curl ^C, reset): routine,
            # not a server error — stop awaiting the admission outcome
            # (its bookkeeping retires the dispatched work either way)
            # and count a client abort instead of a 500.
            task.cancel()
            with contextlib.suppress(BaseException):
                await task
            self._count("/v1/generate", 499)  # nginx-style client abort
            return
        except Exception as e:  # noqa: BLE001 - mapped to HTTP statuses
            status, doc, headers = self._error_response(e)
            if isinstance(e, (QueueFullError, DrainingError)):
                # Same discard the buffered paths apply: a shed stream
                # did no work, and a 429 storm must not churn the ring.
                trace = _tracing.current_trace()
                self._record_shed(
                    "/v1/generate", trace, self._shed_reason(e)
                )
                if trace is not None:
                    _tracing.trace_store().discard(trace.trace_id)
            if headers_sent:
                # Mid-stream failure: the status line is gone; surface a
                # terminal error event instead.
                with contextlib.suppress(Exception):
                    await self._sse_event(writer, {"error": doc["error"]})
                    await self._sse_done(writer)
            else:
                await self._respond_json(writer, status, doc, headers)
            self._count("/v1/generate", status)
            return
        dt = time.monotonic() - t0
        if not headers_sent:  # empty completion: still a valid stream
            await self._start_sse(writer)
            headers_sent = True
        if first_at is None:
            self._m_ttft.observe(dt)
        self._observe_generation(None, dt, result.num_tokens)
        meta = getattr(result, "meta", None)
        hops = self._hop_breakdown(_tracing.current_trace(), meta, dt)
        if hops:
            # In place for the same /debug/requests identity as the
            # buffered path.
            if isinstance(meta, dict):
                meta["hops"] = hops
            else:
                meta = {"hops": hops}
        await self._sse_event(
            writer,
            {
                "done": True,
                "num_tokens": result.num_tokens,
                "trace_id": self._trace_id(),
                **({"meta": meta} if meta else {}),
            },
        )
        await self._sse_done(writer)
        self._count("/v1/generate", 200)

    async def _streaming_thunk(self, req: GenerationRequest, push):
        """Produce pieces via ``push`` and return the final result."""
        gs = getattr(self.backend, "generate_stream", None)
        if gs is not None:
            parts: list[str] = []
            n = 0
            async for piece in gs(req):
                parts.append(piece)
                n += 1
                push(piece)
            return GenerationResult(text="".join(parts), num_tokens=n)
        result = await self.backend.generate(req)
        for piece in _TOKENISH.findall(result.text):
            push(piece)
        return result

    async def _handle_consensus(self, payload: dict, headers, writer) -> None:
        from llm_consensus_tpu.consensus.coordinator import (
            Coordinator,
            CoordinatorConfig,
        )

        question = payload.get("question")
        if not isinstance(question, str) or not question:
            await self._respond_json(
                writer, 400, {"error": "need a non-empty string 'question'"}
            )
            self._count("/v1/consensus", 400)
            return
        try:
            # Consensus phase -> model routing (PR 18): an explicit
            # "phase_models" map in the payload wins; otherwise a
            # multi-model backend's canonical routing (propose on the
            # draft donor, judge/refine on the default) applies.
            phase_models = payload.get("phase_models")
            if phase_models is None:
                pm_hook = getattr(self.backend, "modelset", None)
                if pm_hook is not None:
                    phase_models = pm_hook.phase_models()
            elif not (
                isinstance(phase_models, dict)
                and all(
                    isinstance(k, str) and isinstance(v, str)
                    for k, v in phase_models.items()
                )
            ):
                raise ValueError(
                    "phase_models must map phase names to model names"
                )
            cfg = CoordinatorConfig(
                max_rounds=int(
                    payload.get("max_rounds", self.config.max_rounds)
                ),
                seed=payload.get("seed", self.config.consensus_seed),
                sampling=self._sampling_from(payload),
                phase_models=phase_models,
            )
            adm_kw = self._cost_kw(
                self._admission_kw(payload, "batch"),
                question,
                cfg.sampling.max_new_tokens,
                members=max(1, len(self.panel)),
            )
        except (TypeError, ValueError, OverflowError) as e:
            await self._respond_json(
                writer, 400, {"error": f"bad request field: {e}"}
            )
            self._count("/v1/consensus", 400)
            return
        trace = _tracing.trace_store().start(
            "/v1/consensus",
            route="/v1/consensus",
            trace_id=self._incoming_tid(headers),
        )
        t0 = time.monotonic()

        async def thunk():
            # A fresh coordinator per request: the protocol state machine
            # is per-question; panel/backend/config are the shared parts.
            # Profiling wraps only this execution, never the queue wait.
            coord = Coordinator(list(self.panel), self.backend, cfg)
            with self._maybe_profile(headers):
                return await coord.run(question)

        try:
            with _tracing.use_trace(trace):
                result = await self.admission.submit(thunk, **adm_kw)
        except Exception as e:  # noqa: BLE001 - mapped to HTTP statuses
            status, doc, hdrs = self._error_response(e)
            if isinstance(e, (QueueFullError, DrainingError)):
                self._record_shed(
                    "/v1/consensus", trace, self._shed_reason(e)
                )
                if trace is not None:
                    _tracing.trace_store().discard(trace.trace_id)
            await self._respond_json(writer, status, doc, hdrs)
            self._count("/v1/consensus", status)
            return
        finally:
            if trace is not None:
                trace.finish()
        dt = time.monotonic() - t0
        self._m_ttft.observe(dt)
        self._m_latency.observe(dt)
        tid = trace.trace_id if trace is not None else None
        # A panel fan-out's spans overlap, so the hop breakdown here
        # is attribution (where the panel's time went), not a
        # wall-clock partition like the single-generation paths.
        hops = self._hop_breakdown(trace, None, dt)
        await self._respond_json(
            writer,
            200,
            {
                "answer": result.answer,
                "rounds": result.rounds,
                "endorsed": result.endorsed,
                "author": result.author,
                "feedback": {k: v.value for k, v in result.feedback.items()},
                "trace_id": tid,
                **({"meta": {"hops": hops}} if hops else {}),
            },
            {"X-Trace-Id": tid} if tid else None,
        )
        self._count("/v1/consensus", 200)

    # -- plumbing -------------------------------------------------------

    def _observe_generation(
        self, ttft: float | None, dt: float, num_tokens: int
    ) -> None:
        if ttft is not None:
            self._m_ttft.observe(ttft)
        self._m_latency.observe(dt)
        if dt > 0 and num_tokens:
            self._m_tps.observe(num_tokens / dt)

    def _error_response(self, e: Exception):
        if isinstance(e, QueueFullError):
            return (
                429,
                {"error": str(e), "retry_after": e.retry_after},
                {"Retry-After": str(max(1, round(e.retry_after)))},
            )
        if isinstance(e, DrainingError):
            return 503, {"error": str(e)}, {"Retry-After": "5"}
        if isinstance(e, DeadlineExpiredError):
            return 504, {"error": str(e)}, {}
        if isinstance(e, BackendError):
            return 502, {"error": str(e)}, {}
        if isinstance(e, ValueError):
            return 400, {"error": str(e)}, {}
        log.exception("unexpected gateway error", exc_info=e)
        return 500, {"error": f"internal error: {e}"}, {}

    def _count(self, route: str, status: int) -> None:
        self._m_requests.labels(route=route, status=str(status)).inc()

    async def _respond_json(
        self, writer, status: int, doc: dict, headers=None
    ) -> None:
        await self._respond_raw(
            writer,
            status,
            json.dumps(doc).encode(),
            "application/json",
            headers,
        )

    async def _respond_raw(
        self, writer, status: int, body: bytes, ctype: str, headers=None
    ) -> None:
        reason = _REASONS.get(status, "Unknown")
        lines = [
            f"HTTP/1.1 {status} {reason}",
            f"Content-Type: {ctype}",
            f"Content-Length: {len(body)}",
            "Connection: close",
        ]
        for k, v in (headers or {}).items():
            lines.append(f"{k}: {v}")
        writer.write(("\r\n".join(lines) + "\r\n\r\n").encode() + body)
        await writer.drain()

    async def _start_sse(self, writer) -> None:
        writer.write(
            b"HTTP/1.1 200 OK\r\n"
            b"Content-Type: text/event-stream\r\n"
            b"Cache-Control: no-cache\r\n"
            b"Connection: close\r\n\r\n"
        )
        await writer.drain()

    async def _sse_event(self, writer, doc: dict) -> None:
        writer.write(f"data: {json.dumps(doc)}\n\n".encode())
        await writer.drain()

    async def _sse_done(self, writer) -> None:
        writer.write(b"data: [DONE]\n\n")
        await writer.drain()


class GatewayThread:
    """Run a :class:`Gateway` on a dedicated event loop in a daemon
    thread — the embedding/test harness (the pytest suite drives the
    gateway from synchronous code; a REPL process can serve on the side).

    ``start()`` blocks until the port is bound; ``drain()`` triggers the
    graceful SIGTERM path from any thread and joins."""

    def __init__(self, gateway: Gateway):
        self.gateway = gateway
        self._stop: asyncio.Event | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._started = threading.Event()
        self._finished = threading.Event()
        self._error: BaseException | None = None
        self._thread = threading.Thread(
            target=self._run, name="gateway", daemon=True
        )

    @property
    def port(self) -> int:
        assert self.gateway.port is not None
        return self.gateway.port

    def _run(self) -> None:
        async def main():
            self._loop = asyncio.get_running_loop()
            self._stop = asyncio.Event()
            try:
                await self.gateway.start()
            finally:
                self._started.set()
            await self._stop.wait()
            await self.gateway.drain()

        try:
            asyncio.run(main())
        except BaseException as e:  # noqa: BLE001 - surfaced on start/drain
            self._error = e
        finally:
            self._started.set()
            self._finished.set()

    def start(self) -> "GatewayThread":
        self._thread.start()
        self._started.wait(timeout=30)
        if self.gateway.port is None:
            raise RuntimeError(f"gateway failed to start: {self._error!r}")
        return self

    def drain(self, timeout: float = 60) -> None:
        """Graceful shutdown from any thread; joins the loop thread."""
        if self._loop is not None and not self._finished.is_set():
            self._loop.call_soon_threadsafe(
                lambda: self._stop.set() if self._stop else None
            )
        self._finished.wait(timeout=timeout)
        self._thread.join(timeout=timeout)
        if self._error is not None:
            raise RuntimeError("gateway thread failed") from self._error
