"""Request-scoped span tracing + the JAX profiler hook.

Tracing is ~absent in the reference (wall-clock only paces the readiness
poll, ``src/main.rs:449-454``; SURVEY.md §5). Two layers here:

- **Request-scoped traces** (PR 5) — :class:`TraceStore` /
  :class:`Trace`: every gateway request gets a trace id at admission;
  the id propagates through the serving stack via a
  :mod:`contextvars` context (:func:`use_trace` /
  :func:`current_trace` / :func:`request_span`), and worker threads
  that cannot see the caller's context (the continuous batcher's host
  loop) attach spans explicitly via :meth:`Trace.add_span`. The store
  is a bounded ring of traces (evict-oldest), each trace a bounded
  span tree; drops are counted and mirrored into the Prometheus
  registry through :func:`set_drop_hook` (wired by
  :mod:`llm_consensus_tpu.server.metrics` on import, so the two
  surfaces move in lockstep). ``GET /debug/traces`` on the gateway
  renders :meth:`Trace.to_dict` span trees. All of it is on
  ``time.perf_counter``.
- :func:`trace_jax_profile` — context manager around
  ``jax.profiler.start_trace`` producing a TensorBoard-loadable device
  trace for the real TPU hot loop; the gateway's ``X-Profile: 1``
  header (with ``serve --profile-dir``) drops one. Its first host event
  is ``profile.anchor``, which carries ``perf_counter_ns`` at that
  instant: the one stamp that places request spans and flight events
  (``perf_counter``) on the profile's clock, beside the device planes
  and the batcher's own ``batcher.<phase>`` annotations.

- :class:`PhaseClock` (PR 26's batcher phases, PR 37's start-up) —
  one thread's stretches under phase names, each a
  ``jax.profiler.TraceAnnotation`` and seconds towards a counter from
  the same two clock reads; and :func:`install_compile_listener` (PR
  37), which turns the compile events jax emits into spans by program
  and stage (``trace``, ``lower``, ``backend``).

**One clock.** Every span here, the batcher's phases and the request
spans alike, is on ``time.perf_counter``. On Linux CPython implements it
with ``clock_gettime(CLOCK_MONOTONIC)``, the clock of ``time.monotonic``
that the benchmark's parent stamps its window with, so a span has a
place on that timeline and, through ``profile.anchor``, on a device
trace, without another anchor.

Process-wide tracing can be disabled entirely (:func:`set_enabled`,
``serve --no-trace``): :meth:`TraceStore.start` then returns ``None``
and every downstream call site degrades to a no-op.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
import uuid
from collections import OrderedDict
from contextvars import ContextVar
from dataclasses import dataclass, field


# ---------------------------------------------------------------------------
# Request-scoped traces (PR 5)
# ---------------------------------------------------------------------------

# Process-wide enable switch. Disabled => TraceStore.start returns None
# and request_span/use_trace degrade to no-ops; instrumentation sites
# stay branch-free ("if trace is not None" is the whole protocol).
_ENABLED = True

# Mirror drops into the metrics registry without importing it here
# (utils must stay below server in the layer order; server.metrics sets
# the hook on import). Signature: (kind: "span" | "trace", n: int).
_DROP_HOOK = None


def set_enabled(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def enabled() -> bool:
    return _ENABLED


def set_drop_hook(hook) -> None:
    global _DROP_HOOK
    _DROP_HOOK = hook


def _notify_drop(kind: str, n: int) -> None:
    hook = _DROP_HOOK
    if hook is not None and n:
        try:
            hook(kind, n)
        except Exception:  # noqa: BLE001 - metrics must never break tracing
            pass


@dataclass
class Span:
    """One completed span in a trace (times relative to trace start)."""

    span_id: int
    name: str
    start: float  # seconds since the trace began
    duration: float
    parent_id: int
    meta: dict = field(default_factory=dict)


class Trace:
    """One request's bounded span tree; thread-safe.

    Spans carry ids and parent ids; the tree is assembled lazily by
    :meth:`to_dict`. The implicit ROOT span (``root_id``) is the trace
    itself — ``name`` at offset 0, closed by :meth:`finish`. Spans past
    ``max_spans`` are dropped (counted, hook-mirrored); a dropped
    parent's surviving children re-attach to the root at render time.
    """

    def __init__(self, trace_id: str, name: str, max_spans: int, meta=None):
        self.trace_id = trace_id
        self.name = name
        self.meta = dict(meta or {})
        self.max_spans = max_spans
        self.started_at = time.time()  # wall clock, for humans
        self._t0 = time.perf_counter()  # monotonic origin of span offsets
        self.root_id = 0
        self._ids = itertools.count(1)
        self._spans: list[Span] = []
        self._dropped = 0
        self._duration: float | None = None
        self._lock = threading.Lock()

    # -- recording ------------------------------------------------------

    def next_id(self) -> int:
        return next(self._ids)

    def record(
        self,
        span_id: int,
        name: str,
        start_pc: float,
        duration: float,
        parent_id: int,
        meta: dict | None = None,
    ) -> None:
        """Record a completed span; ``start_pc`` is a perf_counter stamp."""
        sp = Span(
            span_id=span_id,
            name=name,
            start=max(0.0, start_pc - self._t0),
            duration=duration,
            parent_id=parent_id,
            meta=dict(meta or {}),
        )
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self._dropped += 1
                _notify_drop("span", 1)
                return
            self._spans.append(sp)

    def add_span(
        self,
        name: str,
        start_pc: float,
        duration: float,
        parent_id: int | None = None,
        **meta,
    ) -> None:
        """Externally-timed span (worker threads that cannot use the
        contextvar protocol); attaches to the root unless parented."""
        self.record(
            self.next_id(),
            name,
            start_pc,
            duration,
            self.root_id if parent_id is None else parent_id,
            meta,
        )

    def finish(self, **meta) -> None:
        """Close the root span (idempotent; first close wins)."""
        with self._lock:
            if self._duration is None:
                self._duration = time.perf_counter() - self._t0
            if meta:
                self.meta.update(meta)

    # -- introspection --------------------------------------------------

    @property
    def duration(self) -> float:
        """Root duration: final after :meth:`finish`, else elapsed."""
        d = self._duration
        return d if d is not None else time.perf_counter() - self._t0

    @property
    def finished(self) -> bool:
        return self._duration is not None

    @property
    def n_spans(self) -> int:
        return len(self._spans)

    @property
    def dropped_spans(self) -> int:
        return self._dropped

    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def summary(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "name": self.name,
            "started_at": self.started_at,
            "duration_s": self.duration,
            "finished": self.finished,
            "n_spans": self.n_spans,
            "dropped_spans": self._dropped,
            **({"meta": self.meta} if self.meta else {}),
        }

    def to_dict(self) -> dict:
        """The span TREE: root node (the trace) with nested children."""
        spans = self.spans()
        known = {s.span_id for s in spans}
        children: dict[int, list[Span]] = {}
        for s in sorted(spans, key=lambda s: s.start):
            parent = s.parent_id if s.parent_id in known else self.root_id
            children.setdefault(parent, []).append(s)

        def node(s: Span) -> dict:
            return {
                "name": s.name,
                "start_s": round(s.start, 6),
                "duration_s": round(s.duration, 6),
                **({"meta": s.meta} if s.meta else {}),
                "children": [node(c) for c in children.get(s.span_id, ())],
            }

        return {
            **self.summary(),
            "spans": [node(s) for s in children.get(self.root_id, ())],
        }


class TraceStore:
    """Bounded process-wide ring of request traces (evict-oldest)."""

    def __init__(self, max_traces: int = 256, max_spans: int = 2048):
        # Clamp: a 0/negative trace cap would make the evict-oldest
        # walk popitem() an empty dict on the first start(); "retain
        # ~nothing" is max_traces=1 (use set_enabled(False) / serve
        # --no-trace to turn tracing off entirely).
        self.max_traces = max(1, max_traces)
        self.max_spans = max(0, max_spans)
        self._traces: OrderedDict[str, Trace] = OrderedDict()
        self._evicted = 0
        self._lock = threading.Lock()

    def configure(
        self, max_traces: int | None = None, max_spans: int | None = None
    ) -> None:
        """Adjust the bounds (serve CLI knobs); applies to new traces,
        and an over-full ring sheds down to the new cap immediately."""
        with self._lock:
            if max_traces is not None:
                self.max_traces = max(1, max_traces)
            if max_spans is not None:
                self.max_spans = max(0, max_spans)
            while len(self._traces) > self.max_traces:
                self._traces.popitem(last=False)
                self._evicted += 1
                _notify_drop("trace", 1)

    def start(
        self, name: str, trace_id: str | None = None, **meta
    ) -> Trace | None:
        """Open (and retain) a new trace; ``None`` when tracing is off.

        ``trace_id`` ADOPTS a propagated id instead of minting one
        (PR 20): a gateway receiving a forwarded request under
        ``X-Trace-Id`` opens its local trace under the FRONT's id, so
        the hop's spans join the originating request's trace when the
        fleet view merges them. Adoption is per process — each process
        keeps its own Trace object (its own clock origin and span
        ring); the shared id is the join key, never shared state. An
        invalid propagated id (non-hex, wrong length) is ignored and a
        fresh id minted — a malicious or corrupt header must not poison
        the store's keying."""
        if not _ENABLED:
            return None
        if trace_id is not None and not _adoptable_id(trace_id):
            trace_id = None
        trace = Trace(
            trace_id or uuid.uuid4().hex[:16],
            name,
            max_spans=self.max_spans,
            meta={**meta, **({"adopted": True} if trace_id else {})},
        )
        with self._lock:
            while len(self._traces) >= self.max_traces:
                self._traces.popitem(last=False)
                self._evicted += 1
                _notify_drop("trace", 1)
            self._traces[trace.trace_id] = trace
        return trace

    def get(self, trace_id: str) -> Trace | None:
        with self._lock:
            return self._traces.get(trace_id)

    def discard(self, trace_id: str) -> None:
        """Intentionally forget a trace (e.g. a request shed at the
        admission door did no work worth retaining — under a 429 storm
        these would otherwise churn the ring and evict the slow traces
        being debugged). Not counted as a drop."""
        with self._lock:
            self._traces.pop(trace_id, None)

    def traces(self, limit: int = 50) -> list[Trace]:
        """Newest-first."""
        with self._lock:
            items = list(self._traces.values())
        return items[::-1][: max(0, limit)]

    @property
    def evicted(self) -> int:
        return self._evicted

    def __len__(self) -> int:
        return len(self._traces)

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()


def _adoptable_id(trace_id: str) -> bool:
    """A propagated trace id this store will adopt verbatim: 8-64
    hex-ish chars (the local mint is 16 lowercase hex). Bounded and
    charset-checked so a hostile ``X-Trace-Id`` header cannot stuff
    megabyte keys or control bytes into the store."""
    if not isinstance(trace_id, str) or not (8 <= len(trace_id) <= 64):
        return False
    return all(c in "0123456789abcdefABCDEF-" for c in trace_id)


_STORE = TraceStore()


def trace_store() -> TraceStore:
    return _STORE


# Current (trace, span-id) of this context: tasks inherit it across
# awaits, threads started via asyncio.to_thread inherit a copy, and
# plain worker threads see None (they attach via Trace.add_span).
_CTX: ContextVar[tuple[Trace, int] | None] = ContextVar(
    "llm_consensus_trace", default=None
)


def current_trace() -> Trace | None:
    ctx = _CTX.get()
    return ctx[0] if ctx is not None else None


def trace_id_of(trace: Trace | None) -> str | None:
    """The id of a trace-or-None handle — the stamp every flight
    recorder event and request summary carries (PR 10), so the span
    tree at ``/debug/traces?id=``, the timeline at ``/debug/flight``,
    and the summary at ``/debug/requests?id=`` all join on one key.
    None-safe because every handle in the serving stack is None when
    tracing is disabled."""
    return trace.trace_id if trace is not None else None


@contextlib.contextmanager
def use_trace(trace: Trace | None):
    """Make ``trace`` the context's current trace (no-op for None)."""
    if trace is None:
        yield
        return
    token = _CTX.set((trace, trace.root_id))
    try:
        yield
    finally:
        _CTX.reset(token)


@contextlib.contextmanager
def request_span(name: str, **meta):
    """Span on the context's current trace, nested under the context's
    current span; a silent no-op when no trace is active (library code
    can instrument unconditionally)."""
    ctx = _CTX.get()
    if ctx is None or not _ENABLED:
        yield None
        return
    trace, parent = ctx
    span_id = trace.next_id()
    token = _CTX.set((trace, span_id))
    t0 = time.perf_counter()
    try:
        yield trace
    finally:
        _CTX.reset(token)
        trace.record(
            span_id, name, t0, time.perf_counter() - t0, parent, meta
        )


@contextlib.contextmanager
def trace_jax_profile(logdir: str):
    """Capture a JAX/XLA device profile (TensorBoard format) around a
    block — the real profiling story for the TPU hot loop. The profile
    opens with one ``profile.anchor`` host event whose
    ``perf_counter_ns`` stat is this process's ``time.perf_counter_ns``
    at the event's start: subtract the two and every span, flight event
    and summary stamped on ``perf_counter`` has a place on the
    profile's clock."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        with jax.profiler.TraceAnnotation(
            "profile.anchor", perf_counter_ns=time.perf_counter_ns()
        ):
            pass
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# Phases of one thread, and the programs the process builds (PR 37)
# ---------------------------------------------------------------------------


class PhaseClock:
    """One thread's stretches under phase names, twice over from the
    same two clock reads: a ``<prefix><name>``
    ``jax.profiler.TraceAnnotation`` (the profiler's host plane, on the
    device planes' clock; one flag check when no profile is being
    taken; ``meta`` rides as the event's stats), and seconds towards
    ``family{phase=name}``.

    Annotations nest as they ran; the seconds never overlap. The clock
    runs for the innermost open phase only, so an outer phase leaves out
    what ran inside it, and the time between two phases goes to the one
    that opens next: from the clock's start (``t0``, else construction)
    the phases sum to the stretch they cover. Seconds gather in
    :attr:`seconds` until :meth:`flush` moves them into the family. One
    thread only: the clock keeps no lock."""

    def __init__(self, prefix: str, family, names, t0: float | None = None):
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self._prefix = prefix
        self.seconds = dict.fromkeys(names, 0.0)
        self._children = {name: family.labels(phase=name) for name in names}
        self._open: list[str] = []
        self._t = time.perf_counter() if t0 is None else t0

    @contextlib.contextmanager
    def phase(self, name: str, **meta):
        open_ = self._open
        now = time.perf_counter()
        self.seconds[open_[-1] if open_ else name] += now - self._t
        self._t = now
        open_.append(name)
        try:
            with self._annotation(self._prefix + name, **meta):
                yield
        finally:
            now = time.perf_counter()
            open_.pop()
            self.seconds[name] += now - self._t
            self._t = now

    def flush(self) -> None:
        """Move the gathered seconds into the family."""
        for name, seconds in self.seconds.items():
            if seconds:
                self._children[name].inc(seconds)
                self.seconds[name] = 0.0


#: Events of the store of executables (``utils.compile_cache``): the
#: seconds of a load, under the program's ``fun_name`` as jax's own
#: stages carry it, and one event a program the store hands out, with
#: its ``program`` and ``source`` (``loaded`` or ``built``).
LOAD_EVENT = "/llm_consensus_tpu/executable_load_duration"
EXECUTABLE_EVENT = "/llm_consensus_tpu/executable"

# jax's compile events (jax 0.9: ``dispatch.log_elapsed_time`` emits the
# start as a scalar and the end as a duration, on the thread that
# compiles) and the stage each one is.
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # A compile, or a load from the persistent cache.
    "/jax/core/compile/backend_compile_duration": "backend",
    # A load from the store of executables: no trace, lower or compile.
    LOAD_EVENT: "load",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


@dataclass(slots=True)
class _CompileSpan:
    program: str
    stage: str
    start: float  # perf_counter
    parent: "_CompileSpan | None"  # the enclosing span of this thread
    annotation: object
    hit: bool = False


def _program(fun_name: str) -> str:
    """``fused_step`` for both ``fused_step`` (a trace) and
    ``jit(fused_step)`` (its lowering and backend compile)."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


class _CompileListener:
    """Spans from jax's compile events, kept per thread while open.

    A span that ends while an enclosing one of the same thread is open
    (a jitted ``jnp`` function traced inside the step that calls it) is
    that span's child and adds no seconds: only outermost spans count,
    so a stage's seconds never exceed the thread's wall seconds. Each
    span is also a ``compile.<stage>`` annotation with a ``program``
    stat, nested in whatever phase of the thread was open."""

    def __init__(self, seconds, compiles, executables):
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self._seconds = seconds
        self._compiles = compiles
        self._executables = executables
        self._local = threading.local()

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def start(self, event: str, value, **kw) -> None:
        stage = _COMPILE_STAGES.get(event)
        if stage is None:
            return
        program = _program(str(kw.get("fun_name", "")))
        stack = self._stack()
        annotation = self._annotation("compile." + stage, program=program)
        annotation.__enter__()
        stack.append(_CompileSpan(
            program, stage, time.perf_counter(),
            stack[-1] if stack else None, annotation,
        ))

    def end(self, event: str, duration: float, **kw) -> None:
        stage = _COMPILE_STAGES.get(event)
        if stage is None:
            return
        now = time.perf_counter()
        stack = self._stack()
        program = _program(str(kw.get("fun_name", "")))
        if not stack or (stack[-1].stage, stack[-1].program) != (
            stage, program
        ):
            return  # began before the listener was installed
        span = stack.pop()
        span.annotation.__exit__(None, None, None)
        if span.parent is None:
            self._seconds.labels(stage=stage, program=program).inc(
                now - span.start
            )
        if stage == "backend":
            self._compiles.labels(
                program=program, cache="hit" if span.hit else "miss"
            ).inc()

    def event(self, event: str, **kw) -> None:
        if event == EXECUTABLE_EVENT:
            self._executables.labels(
                program=kw["program"], source=kw["source"]
            ).inc()
        elif event == _CACHE_HIT:
            stack = self._stack()
            if stack and stack[-1].stage == "backend":
                stack[-1].hit = True


_LISTENER: _CompileListener | None = None
_LISTENER_LOCK = threading.Lock()


def install_compile_listener(seconds, compiles, executables) -> None:
    """Count every XLA program this process builds from here on:
    outermost seconds into ``seconds{stage, program}`` (``load``: read
    from the store of executables), one ``compiles{program, cache}`` a
    backend span (``cache="hit"`` when it was a load from the persistent
    cache) and one ``executables{program, source}`` a program the store
    hands out (``utils.compile_cache.StoredJit``). Once a process; a
    second call changes nothing. The listener runs only while something
    is traced, lowered, compiled or loaded, so a warm loop pays
    nothing."""
    global _LISTENER
    from jax import monitoring

    with _LISTENER_LOCK:
        if _LISTENER is not None:
            return
        _LISTENER = _CompileListener(seconds, compiles, executables)
        monitoring.register_scalar_listener(_LISTENER.start)
        monitoring.register_event_duration_secs_listener(_LISTENER.end)
        monitoring.register_event_listener(_LISTENER.event)
