"""Serving flight recorder: the attribution plane over the batcher (PR 10).

Every perf PR since 5 was found by telemetry — the host-gap histogram
motivated pipelined dispatch (PR 6), ``gateway_device_programs_total``
motivated the ragged fusion (PR 8) — but histograms aggregate away the
*sequence* of events. With five interacting subsystems (pipelined
dispatch, ragged fusion, speculative decode, the host KV tier, prefix
groups) the question is no longer "how long is a step" but "what did
THIS request's journey through all of them look like". This module is
the answer's substrate:

- :class:`FlightRecorder` — a bounded, evict-oldest ring of typed
  scheduler events (program dispatch/fetch windows, admissions/sheds,
  chunk scheduling, spec flips and catch-up replays, stream-plan donor
  changes, demote/restore, pipeline flushes, CoW copies, PR 15's
  ``autotune`` knob decisions from the adaptive controller (recorded
  on value changes), and — PR 16 — ``handoff`` (a prefill→decode
  chain handoff completed: source replica + chain pages) and
  ``remote_store`` (the remote page store's circuit breaker flipped
  ``state=down``/``up`` — one event per outage TRANSITION, not per
  failed op, so a dead peer cannot flood the ring)), each stamped
  with monotonic time and the PR-5 trace id. Evictions are counted and
  mirrored into ``gateway_flight_dropped_total`` so a truncated export
  is detectable. Recording is a bool check when disabled and one
  lock+append when enabled.
- :class:`RequestLog` — a bounded ring of per-request serving
  summaries (TTFT, inter-token-gap percentiles, spec tokens accepted
  per round, restored-vs-prefilled header pages), fed at retirement,
  served at ``GET /debug/requests`` and in the response meta.
- :func:`to_chrome` — Chrome trace-event JSON (Perfetto-loadable) built
  from the ring: a device track reconstructed from dispatch→fetch
  windows (one slice per device program — exactly the programs
  ``gateway_device_programs_total`` counted, asserted in tests), a host
  track for un-overlapped scheduler work, a scheduler-event track, and
  one track per request.

Process-global singletons (:func:`flight_recorder`, :func:`request_log`)
follow :func:`llm_consensus_tpu.utils.tracing.trace_store`'s pattern:
the batcher writes, the gateway reads, tests isolate by ``clear()``.
"""

from __future__ import annotations

import itertools
import threading
from collections import OrderedDict, deque
from dataclasses import dataclass, field

from llm_consensus_tpu.server.metrics import FLIGHT_DROPPED as _M_DROPPED

__all__ = [
    "FlightEvent",
    "FlightRecorder",
    "RequestLog",
    "flight_recorder",
    "request_log",
    "set_enabled",
    "enabled",
    "percentile",
    "to_chrome",
    "merge_fleet",
    "to_chrome_fleet",
]


@dataclass
class FlightEvent:
    """One typed scheduler event.

    ``t0`` is a ``time.perf_counter`` stamp (the batcher's monotonic
    timebase — the same clock every dispatch/fetch stamp already uses);
    ``dur`` is 0 for instantaneous events and for device programs whose
    fetch has not landed yet (the fetch fills the window in place).
    """

    seq: int
    kind: str
    t0: float
    dur: float = 0.0
    trace_id: str | None = None
    meta: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "seq": self.seq,
            "kind": self.kind,
            "t0": self.t0,
            "dur_s": self.dur,
            **(
                {"trace_id": self.trace_id}
                if self.trace_id is not None
                else {}
            ),
            **({"meta": self.meta} if self.meta else {}),
        }


# Process-wide enable switch (``serve --no-flight``). Disabled =>
# record() returns None before touching the lock; instrumentation
# sites stay branch-free.
_ENABLED = True


def set_enabled(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def enabled() -> bool:
    return _ENABLED


class FlightRecorder:
    """Bounded evict-oldest ring of :class:`FlightEvent`; thread-safe.

    The worker thread records; the gateway thread reads. ``record``
    returns the event object so the one writer may fill a device
    program's (t0, dur) window in place once its fetch lands — count
    parity with ``gateway_device_programs_total`` holds by construction
    because the event is recorded AT the counting site, window known or
    not.
    """

    def __init__(self, capacity: int = 8192):
        self.capacity = max(1, capacity)
        self._events: deque[FlightEvent] = deque()
        self._seq = itertools.count()
        self._dropped = 0
        self._lock = threading.Lock()

    def configure(self, capacity: int | None = None) -> None:
        """Adjust the ring bound (serve CLI knob); an over-full ring
        sheds down to the new cap immediately (counted)."""
        with self._lock:
            if capacity is not None:
                self.capacity = max(1, capacity)
            self._evict_locked()

    def _evict_locked(self) -> None:
        n = 0
        while len(self._events) > self.capacity:
            self._events.popleft()
            n += 1
        if n:
            self._dropped += n
            _M_DROPPED.inc(n)

    def record(
        self,
        kind: str,
        t0: float,
        dur: float = 0.0,
        trace_id: str | None = None,
        meta: dict | None = None,
        **extra,
    ) -> FlightEvent | None:
        """Append one event (evicting the oldest past capacity);
        ``None`` when recording is disabled. Metadata rides as keyword
        arguments (or an explicit ``meta`` dict for keys that collide
        with the positional parameters, e.g. a program's ``kind``)."""
        if not _ENABLED:
            return None
        with self._lock:
            ev = FlightEvent(
                seq=next(self._seq),
                kind=kind,
                t0=t0,
                dur=dur,
                trace_id=trace_id,
                meta={**(meta or {}), **extra},
            )
            self._events.append(ev)
            if len(self._events) > self.capacity:
                self._events.popleft()
                self._dropped += 1
                _M_DROPPED.inc()
        return ev

    def events(self) -> list[FlightEvent]:
        """Oldest-first snapshot."""
        with self._lock:
            return list(self._events)

    @property
    def dropped(self) -> int:
        """Events evicted from the ring (lockstep-mirrored into
        ``gateway_flight_dropped_total``)."""
        return self._dropped

    def __len__(self) -> int:
        return len(self._events)

    def clear(self) -> None:
        """Forget retained events (test isolation; not a drop)."""
        with self._lock:
            self._events.clear()


class RequestLog:
    """Bounded evict-oldest ring of per-request serving summaries.

    Keyed by the batcher's request id; a summary carrying a
    ``trace_id`` is reachable under that key too (the PR-5 id a client
    already holds from ``X-Trace-Id``). Eviction is retention policy,
    not data loss — summaries also ride the response meta — so it is
    not drop-counted.
    """

    def __init__(self, max_requests: int = 512):
        self.max_requests = max(1, max_requests)
        self._by_id: OrderedDict[str, dict] = OrderedDict()
        # trace id -> [request ids]: one trace can cover SEVERAL
        # generations (a /v1/consensus panel fan-out submits every
        # member under the request's one trace).
        self._trace_to_ids: dict[str, list[str]] = {}
        self._lock = threading.Lock()

    def add(self, summary: dict) -> None:
        rid = summary["id"]
        with self._lock:
            self._by_id[rid] = summary
            self._by_id.move_to_end(rid)
            tid = summary.get("trace_id")
            if tid:
                self._trace_to_ids.setdefault(tid, []).append(rid)
            while len(self._by_id) > self.max_requests:
                old_rid, old = self._by_id.popitem(last=False)
                old_tid = old.get("trace_id")
                ids = self._trace_to_ids.get(old_tid)
                if ids:
                    try:
                        ids.remove(old_rid)
                    except ValueError:
                        pass
                    if not ids:
                        del self._trace_to_ids[old_tid]

    def get_all(self, key: str) -> list[dict]:
        """Every retained summary for ``key`` — a request id (at most
        one) or a trace id (every generation that ran under that
        trace, newest first: a consensus panel is N of them)."""
        with self._lock:
            doc = self._by_id.get(key)
            if doc is not None:
                return [doc]
            return [
                self._by_id[rid]
                for rid in reversed(self._trace_to_ids.get(key, []))
                if rid in self._by_id
            ]

    def get(self, key: str) -> dict | None:
        """Lookup by request id OR trace id; for a trace shared by
        several generations, the most recently retired one."""
        docs = self.get_all(key)
        return docs[0] if docs else None

    def recent(self, limit: int = 50) -> list[dict]:
        """Newest-first."""
        with self._lock:
            items = list(self._by_id.values())
        return items[::-1][: max(0, limit)]

    def __len__(self) -> int:
        return len(self._by_id)

    def clear(self) -> None:
        with self._lock:
            self._by_id.clear()
            self._trace_to_ids.clear()


_RECORDER = FlightRecorder()
_REQUESTS = RequestLog()


def flight_recorder() -> FlightRecorder:
    return _RECORDER


def request_log() -> RequestLog:
    return _REQUESTS


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of an unsorted list (0 for empty) — the
    per-request tbt_p50/p99 summary helper; nearest-rank keeps every
    reported number an actually-observed gap."""
    if not values:
        return 0.0
    vs = sorted(values)
    idx = min(len(vs) - 1, max(0, int(-(-q / 100.0 * len(vs) // 1)) - 1))
    return vs[idx]


# ---------------------------------------------------------------------------
# Chrome trace-event export (Perfetto / chrome://tracing loadable)
# ---------------------------------------------------------------------------

#: pid/tid layout of the export. Device programs land on ONE device
#: track (they are serialized on one device stream — overlap in this
#: track means the window correction is wrong, which is itself visible
#: evidence); un-overlapped host gaps on the host track; the remaining
#: typed events on the scheduler track; each request gets its own tid
#: under the requests pid.
_PID_SERVING = 1
_TID_DEVICE = 1
_TID_HOST = 2
_TID_SCHED = 3
_PID_REQUESTS = 2


def _emit_process_meta(
    out: list[dict],
    pid_serving: int,
    pid_requests: int,
    serving_name: str,
    requests_name: str,
) -> None:
    """Process/thread metadata rows for one host's pid pair."""
    out.append(
        {
            "ph": "M",
            "ts": 0,
            "pid": pid_serving,
            "tid": 0,
            "name": "process_name",
            "args": {"name": serving_name},
        }
    )
    out.append(
        {
            "ph": "M",
            "ts": 0,
            "pid": pid_requests,
            "tid": 0,
            "name": "process_name",
            "args": {"name": requests_name},
        }
    )
    for tid, name in (
        (_TID_DEVICE, "device programs"),
        (_TID_HOST, "host (un-overlapped)"),
        (_TID_SCHED, "scheduler events"),
    ):
        out.append(
            {
                "ph": "M",
                "ts": 0,
                "pid": pid_serving,
                "tid": tid,
                "name": "thread_name",
                "args": {"name": name},
            }
        )


def _emit_events(
    out: list[dict],
    events: list[FlightEvent],
    base: float,
    pid_serving: int,
    pid_requests: int,
) -> None:
    """Emit one host's flight events against a shared time base."""

    def us(t: float) -> float:
        return round((t - base) * 1e6, 3)

    req_tids: dict[str, int] = {}
    for e in events:
        args = dict(e.meta)
        if e.trace_id is not None:
            args["trace_id"] = e.trace_id
        if e.kind == "program":
            out.append(
                {
                    "name": args.get("kind", "program"),
                    "cat": "device",
                    "ph": "X",
                    "ts": us(e.t0),
                    "dur": round(e.dur * 1e6, 3),
                    "pid": pid_serving,
                    "tid": _TID_DEVICE,
                    "args": args,
                }
            )
        elif e.kind == "host":
            out.append(
                {
                    "name": "sched_host",
                    "cat": "host",
                    "ph": "X",
                    "ts": us(e.t0),
                    "dur": round(e.dur * 1e6, 3),
                    "pid": pid_serving,
                    "tid": _TID_HOST,
                    "args": args,
                }
            )
        elif e.kind == "request":
            rid = str(args.get("id", e.trace_id or e.seq))
            tid = req_tids.setdefault(rid, len(req_tids) + 1)
            out.append(
                {
                    "ph": "M",
                    "ts": 0,
                    "pid": pid_requests,
                    "tid": tid,
                    "name": "thread_name",
                    "args": {"name": rid},
                }
            )
            out.append(
                {
                    "name": rid,
                    "cat": "request",
                    "ph": "X",
                    "ts": us(e.t0),
                    "dur": round(e.dur * 1e6, 3),
                    "pid": pid_requests,
                    "tid": tid,
                    "args": args,
                }
            )
        elif e.dur > 0:
            out.append(
                {
                    "name": e.kind,
                    "cat": "scheduler",
                    "ph": "X",
                    "ts": us(e.t0),
                    "dur": round(e.dur * 1e6, 3),
                    "pid": pid_serving,
                    "tid": _TID_SCHED,
                    "args": args,
                }
            )
        else:
            out.append(
                {
                    "name": e.kind,
                    "cat": "scheduler",
                    "ph": "i",
                    "s": "t",
                    "ts": us(e.t0),
                    "pid": pid_serving,
                    "tid": _TID_SCHED,
                    "args": args,
                }
            )


def to_chrome(events: list[FlightEvent]) -> dict:
    """Chrome trace-event JSON from a flight-ring snapshot.

    Every emitted event carries ``ts``/``ph``/``pid``/``tid`` (the
    schema Perfetto's JSON importer requires); ``ts`` is microseconds
    relative to the snapshot's earliest event. Device-program slices
    (``kind == "program"``) become complete ("X") events on the device
    track — their count equals the ``gateway_device_programs_total``
    delta over the same window (a dispatched-not-yet-fetched program
    appears with its dispatch stamp and zero duration). That count
    parity is R-invariant under multi-round decode (PR 12): a program
    folding R rounds is still ONE slice, carrying ``rounds`` in its
    args (next to ``rows``/``tokens``) so the timeline shows how much
    decoding each dispatch held. Events with a
    duration become "X" slices, instantaneous ones "i" instants.
    Request-span events (``kind == "request"``, recorded at
    retirement) each get their own thread row named by request id.
    """
    out: list[dict] = []
    _emit_process_meta(
        out, _PID_SERVING, _PID_REQUESTS, "serving", "requests"
    )
    if events:
        base = min(e.t0 for e in events)
        _emit_events(out, events, base, _PID_SERVING, _PID_REQUESTS)
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def merge_fleet(
    events_by_host: dict[str, tuple[list[FlightEvent], float]],
) -> list[FlightEvent]:
    """Merge per-host flight rings onto ONE timebase (PR 20).

    ``events_by_host`` maps a host label to ``(events, offset_s)``
    where ``offset_s`` translates that host's ``perf_counter`` stamps
    into the caller's (the front tier's) clock:
    ``t_front ≈ t_host + offset_s`` — the midpoint estimate from the
    RTT-halving probe piggybacked on peer ``/debug/chains`` and store
    stats replies. Returns new events (inputs untouched) with
    corrected ``t0`` and a ``host`` meta key, sorted by corrected
    ``t0`` so a joined trace reads monotonically across processes.
    """
    merged: list[FlightEvent] = []
    for host, (events, offset) in events_by_host.items():
        for e in events:
            merged.append(
                FlightEvent(
                    seq=e.seq,
                    kind=e.kind,
                    t0=e.t0 + offset,
                    dur=e.dur,
                    trace_id=e.trace_id,
                    meta={**e.meta, "host": host},
                )
            )
    merged.sort(key=lambda e: (e.t0, e.meta.get("host", ""), e.seq))
    return merged


def to_chrome_fleet(
    events_by_host: dict[str, tuple[list[FlightEvent], float]],
) -> dict:
    """Fleet Chrome export: one ``pid`` pair per host (PR 20).

    Same per-event schema as :func:`to_chrome`, but each host's
    events land under its own serving/requests process pair (named
    ``"<host> serving"`` / ``"<host> requests"``) against ONE global
    time base computed over the clock-corrected stamps — so a single
    request forwarded front→prefill→store→decode renders as one
    aligned lane across every process that touched it.
    """
    out: list[dict] = []
    hosts = list(events_by_host)
    corrected = {
        host: [
            FlightEvent(
                seq=e.seq,
                kind=e.kind,
                t0=e.t0 + offset,
                dur=e.dur,
                trace_id=e.trace_id,
                meta=e.meta,
            )
            for e in events
        ]
        for host, (events, offset) in events_by_host.items()
    }
    for i, host in enumerate(hosts):
        _emit_process_meta(
            out,
            10 * i + 1,
            10 * i + 2,
            f"{host} serving",
            f"{host} requests",
        )
    all_events = [e for evs in corrected.values() for e in evs]
    if all_events:
        base = min(e.t0 for e in all_events)
        for i, host in enumerate(hosts):
            _emit_events(
                out, corrected[host], base, 10 * i + 1, 10 * i + 2
            )
    return {"traceEvents": out, "displayTimeUnit": "ms"}
