"""Process-wide metrics registry with Prometheus text exposition.

The repo's only observability before this module was in-process spans
pulled by Python API. Serving needs the standard scrape surface
instead: a registry of counters/gauges/histograms that the gateway exports at
``GET /metrics`` in the Prometheus text format (version 0.0.4), so the
same dashboards that watch any other fleet watch this one.

Stdlib only, thread-safe (the scheduler/batcher mutate metrics from
their worker threads while the asyncio gateway renders), and dependency
free so the hot serving modules (:mod:`serving.scheduler`,
:mod:`serving.continuous`, :mod:`consensus.coordinator`) can import it
without pulling in the gateway or jax.

Metric families are get-or-create by name — two schedulers in one
process share one ``scheduler_requests_total`` — and support optional
labels (``family.labels(priority="interactive").inc()``) for the
per-priority admission series.
"""

from __future__ import annotations

import threading
from bisect import bisect_left

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "REGISTRY",
    "LATENCY_BUCKETS",
    "THROUGHPUT_BUCKETS",
    "OCCUPANCY_BUCKETS",
    "INSTANCE_FAMILIES",
    "SERVING_SUBMITTED",
    "SERVING_COMPLETED",
    "SERVING_TOKENS",
    "SERVING_STEPS",
    "SERVING_WAITING",
    "SERVING_ACTIVE",
    "SERVING_OCCUPANCY",
    "SCHED_SUBMITTED",
    "SCHED_DEPTH",
    "SCHED_OCCUPANCY",
    "CONSENSUS_QUESTIONS",
    "CONSENSUS_ROUNDS",
    "CONSENSUS_UNANIMOUS",
    "CONSENSUS_FORCED",
    "CONSENSUS_ROUND_SECONDS",
    "GATEWAY_TTFT",
    "DECODE_STEP_SECONDS",
    "SCHED_OVERHEAD_SECONDS",
    "PIPELINE_FLUSHES",
    "PIPELINE_DRAINS",
    "DISPATCH_INFLIGHT",
    "DEVICE_PROGRAMS",
    "BATCHER_PHASE_SECONDS",
    "GENERATED_TOKENS",
    "PREFILL_TOKENS",
    "CHUNK_LANES",
    "STATE_SLOTS",
    "STATE_SNAPSHOTS",
    "PREFIX_TOKENS_RECOMPUTED",
    "SSM_TOKENS",
    "DEVICE_MEMORY_BYTES",
    "RAGGED_ROWS",
    "SPEC_DRAFT_TOKENS",
    "SPEC_ACCEPTED_TOKENS",
    "SPEC_ACCEPTANCE",
    "SPEC_VERIFIED_TOKENS",
    "SPEC_XMODEL_ACCEPTED_TOKENS",
    "SPEC_XMODEL_COVERAGE",
    "MODEL_REQUESTS",
    "MODEL_TOKENS",
    "ACCEPTANCE_BUCKETS",
    "TRACE_DROPPED",
    "FLIGHT_DROPPED",
    "TBT_SECONDS",
    "PROGRAM_MBU",
    "PREFIX_PAGES_SHARED",
    "PREFIX_PAGES_COPIED",
    "PREFIX_LOOKUPS",
    "PREFIX_HITS",
    "PREFILL_STALL_SECONDS",
    "SHARED_KV_BYTES_SAVED",
    "DECODE_GROUP_SIZE",
    "KV_OFFLOAD_DEMOTED",
    "KV_OFFLOAD_RESTORED",
    "KV_OFFLOAD_DROPPED",
    "KV_RESTORE_SECONDS",
    "KV_HOST_TIER_BYTES",
    "REPLICA_ROUTED",
    "REPLICA_PROGRAMS",
    "REPLICA_PREFIX_HIT_RATE",
    "REPLICA_PREEMPTIONS",
    "REPLICA_SHARED_STORE_BYTES",
    "REMOTE_STORE_BYTES",
    "REMOTE_STORE_ERRORS",
    "REMOTE_STORE_RTT",
    "ROLE_HANDOFFS",
]

# Seconds: spans ~1 ms .. 2 min, the TTFT / request-latency range of a
# CPU FakeBackend test and a real chip alike.
LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
)
# Tokens/sec: spans a struggling CPU run .. a healthy chip fleet.
THROUGHPUT_BUCKETS = (
    1.0, 10.0, 50.0, 100.0, 500.0, 1000.0, 5000.0,
    10_000.0, 50_000.0, 100_000.0, 500_000.0,
)
# Batch-occupancy: requests packed per executed program/step.
OCCUPANCY_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)
# Fractions in [0, 1]: speculative-decoding acceptance per verify round.
ACCEPTANCE_BUCKETS = (0.1, 0.25, 0.5, 0.75, 0.9, 0.95, 1.0)


def _fmt(v: float) -> str:
    """Prometheus sample value: integers render bare, floats as repr."""
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def _escape_label_value(v: str) -> str:
    """Prometheus text-format label escaping: backslash, double quote,
    AND line feed (``\\n``) — an unescaped newline in a label value ends
    the sample line mid-token and corrupts the whole exposition."""
    return (
        str(v)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _label_str(labels: tuple[tuple[str, str], ...]) -> str:
    if not labels:
        return ""
    inner = ",".join(
        '{}="{}"'.format(k, _escape_label_value(v)) for k, v in labels
    )
    return "{" + inner + "}"


class _Child:
    """One labeled sample set; the lock is shared with the family."""

    def __init__(self, lock: threading.Lock):
        self._lock = lock


class Counter(_Child):
    """Monotonically increasing count."""

    def __init__(self, lock: threading.Lock):
        super().__init__(lock)
        self._value = 0.0

    def inc(self, n: float = 1.0) -> None:
        if n < 0:
            raise ValueError("counters only go up")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value


class Gauge(_Child):
    """Arbitrary settable value (queue depths, slot occupancy)."""

    def __init__(self, lock: threading.Lock):
        super().__init__(lock)
        self._value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)

    @property
    def value(self) -> float:
        return self._value


class Histogram(_Child):
    """Cumulative-bucket histogram (Prometheus semantics)."""

    def __init__(self, lock: threading.Lock, buckets: tuple[float, ...]):
        super().__init__(lock)
        if not buckets or list(buckets) != sorted(buckets):
            raise ValueError(f"buckets must be sorted and non-empty: {buckets}")
        self.buckets = tuple(float(b) for b in buckets)
        # One slot per finite bucket + the +Inf overflow slot.
        self._counts = [0] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._count = 0

    def observe(self, v: float) -> None:
        i = bisect_left(self.buckets, v)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def cumulative(self) -> list[tuple[float, int]]:
        """[(le, cumulative_count), ...] ending with (inf, total)."""
        out, total = [], 0
        with self._lock:
            counts = list(self._counts)
        for b, c in zip(self.buckets, counts):
            total += c
            out.append((b, total))
        out.append((float("inf"), total + counts[-1]))
        return out


class _Family:
    """A named metric and its labeled children."""

    def __init__(self, name: str, help_: str, kind: str, **kw):
        self.name = name
        self.help = help_
        self.kind = kind  # "counter" | "gauge" | "histogram"
        self._kw = kw
        self._lock = threading.Lock()
        self._children: dict[tuple[tuple[str, str], ...], _Child] = {}

    def _make(self) -> _Child:
        if self.kind == "counter":
            return Counter(self._lock)
        if self.kind == "gauge":
            return Gauge(self._lock)
        return Histogram(self._lock, self._kw["buckets"])

    def labels(self, **labels: str):
        key = tuple(sorted((k, str(v)) for k, v in labels.items()))
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._children[key] = self._make()
        return child

    # Label-less convenience: the family acts as its own single child.
    def _default(self):
        return self.labels()

    def inc(self, n: float = 1.0) -> None:
        self._default().inc(n)

    def dec(self, n: float = 1.0) -> None:
        self._default().dec(n)

    def set(self, v: float) -> None:
        self._default().set(v)

    def observe(self, v: float) -> None:
        self._default().observe(v)

    @property
    def value(self) -> float:
        return self._default().value

    @property
    def count(self) -> int:
        return self._default().count

    @property
    def sum(self) -> float:
        return self._default().sum

    def cumulative(self):
        return self._default().cumulative()

    def render(self) -> list[str]:
        lines = []
        if self.help:
            lines.append(f"# HELP {self.name} {self.help}")
        lines.append(f"# TYPE {self.name} {self.kind}")
        with self._lock:
            children = list(self._children.items())
        for key, child in sorted(children):
            ls = _label_str(key)
            if isinstance(child, Histogram):
                for le, cum in child.cumulative():
                    le_s = "+Inf" if le == float("inf") else _fmt(le)
                    lines.append(
                        f"{self.name}_bucket"
                        f"{_label_str(key + (('le', le_s),))} {cum}"
                    )
                lines.append(f"{self.name}_sum{ls} {_fmt(child.sum)}")
                lines.append(f"{self.name}_count{ls} {child.count}")
            else:
                lines.append(f"{self.name}{ls} {_fmt(child.value)}")
        return lines


class MetricsRegistry:
    """Get-or-create registry of metric families.

    One process-wide instance (:data:`REGISTRY`) backs the default
    instrumentation; tests that need isolation construct their own and
    pass it to the gateway/admission layers.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._families: dict[str, _Family] = {}
        self._render_hooks: list = []

    def _get(self, name: str, help_: str, kind: str, **kw) -> _Family:
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = self._families[name] = _Family(name, help_, kind, **kw)
            elif fam.kind != kind:
                raise ValueError(
                    f"metric {name!r} already registered as {fam.kind}"
                )
            return fam

    def counter(self, name: str, help_: str = "") -> _Family:
        return self._get(name, help_, "counter")

    def gauge(self, name: str, help_: str = "") -> _Family:
        return self._get(name, help_, "gauge")

    def histogram(
        self,
        name: str,
        help_: str = "",
        buckets: tuple[float, ...] = LATENCY_BUCKETS,
    ) -> _Family:
        return self._get(name, help_, "histogram", buckets=tuple(buckets))

    def get(self, name: str) -> _Family | None:
        return self._families.get(name)

    def add_render_hook(self, hook) -> None:
        """Call ``hook()`` at the start of every :meth:`render`: for a
        gauge whose value is read from somewhere else when it is asked
        for (device memory), never from a serving loop. Adding the
        same function twice keeps one."""
        with self._lock:
            if hook not in self._render_hooks:
                self._render_hooks.append(hook)

    def render(self) -> str:
        """The full exposition — Prometheus text format 0.0.4."""
        with self._lock:
            hooks = list(self._render_hooks)
            fams = sorted(self._families.values(), key=lambda f: f.name)
        for hook in hooks:
            try:
                hook()
            except Exception:  # noqa: BLE001 - a scrape must not fail
                pass
        lines: list[str] = []
        for fam in fams:
            lines.extend(fam.render())
        return "\n".join(lines) + "\n"

    def snapshot(self) -> dict[str, float]:
        """Flat {name[{labels}]: value} map of counters/gauges plus
        histogram ``_count``/``_sum`` — the assertion surface for tests."""
        out: dict[str, float] = {}
        with self._lock:
            fams = list(self._families.values())
        for fam in fams:
            with fam._lock:
                children = list(fam._children.items())
            for key, child in children:
                ls = _label_str(key)
                if isinstance(child, Histogram):
                    out[f"{fam.name}_count{ls}"] = child.count
                    out[f"{fam.name}_sum{ls}"] = child.sum
                else:
                    out[f"{fam.name}{ls}"] = child.value
        return out


#: The process-wide default registry (scrape target of ``GET /metrics``).
REGISTRY = MetricsRegistry()


# ---------------------------------------------------------------------------
# Canonical serving-gateway families (PR 2: shared-prefix paged serving).
# Defined HERE — not at their instrumentation sites — so the canonical
# scrape surface is enumerable in one place; the continuous batcher
# imports and feeds them, and they ride REGISTRY into ``GET /metrics``.
# ---------------------------------------------------------------------------

#: Pages mapped into an admission's table from the prefix registry
#: instead of being re-prefilled (each one is page_size tokens of
#: prompt FLOPs the chip never re-spends).
PREFIX_PAGES_SHARED = REGISTRY.counter(
    "gateway_prefix_pages_shared",
    "KV pages mapped from the shared-prefix registry at admission",
)
#: Boundary pages copied (copy-on-write) instead of recomputed.
PREFIX_PAGES_COPIED = REGISTRY.counter(
    "gateway_prefix_pages_copied",
    "Partially-shared boundary pages copied at admission (CoW)",
)
#: Prefix-registry hit rate = hits / lookups.
PREFIX_LOOKUPS = REGISTRY.counter(
    "gateway_prefix_lookups_total",
    "Prefix-registry lookups (one per continuous-batcher admission)",
)
PREFIX_HITS = REGISTRY.counter(
    "gateway_prefix_hits_total",
    "Prefix-registry lookups that mapped or copied at least one page",
)
#: How long each prefill work unit kept the decode loop waiting. Under
#: chunked prefill this is bounded by one chunk's compute; the legacy
#: blocking path records the WHOLE prompt prefill here — the stall the
#: chunked scheduler exists to remove.
PREFILL_STALL_SECONDS = REGISTRY.histogram(
    "gateway_prefill_stall_seconds",
    "Decode-loop stall per prefill work unit (chunk or blocking prefill)",
    buckets=LATENCY_BUCKETS,
)
#: KV bytes the group-aware decode kernel did NOT re-read from HBM
#: (PR 3: shared-prefix decode attention). Each decode step reads a
#: group's shared-prefix pages once instead of once per member; this
#: counts the skipped (members - 1) * shared_tokens * bytes-per-token
#: reads — the dedup PR 2's page sharing made possible in memory, now
#: realized in bandwidth. Incremented only when the grouped program
#: actually ran (jnp-path and windowed-config fallbacks save nothing).
SHARED_KV_BYTES_SAVED = REGISTRY.counter(
    "gateway_shared_kv_bytes_saved_total",
    "KV-cache HBM bytes deduped by group-aware decode attention",
)
#: Members in the largest active decode group at the most recent step
#: (0 = no group — the ungrouped program ran). The panel's N-fanout
#: shows up here as N.
DECODE_GROUP_SIZE = REGISTRY.gauge(
    "gateway_decode_group_size",
    "Largest shared-prefix decode group at the last decode step",
)
#: Hierarchical KV cache (PR 4): the host-RAM tier under the prefix
#: registry. Eviction DEMOTES registry-only prefix pages to pinned host
#: buffers instead of dropping them; a later same-prefix admission
#: RESTORES them (async device_put between decode steps) instead of
#: re-prefilling; host-budget overflow DROPS the LRU page (the tier
#: below host RAM is recompute).
KV_OFFLOAD_DEMOTED = REGISTRY.counter(
    "gateway_kv_offload_demoted_pages_total",
    "Prefix-registry pages demoted to the host-RAM KV tier on eviction",
)
KV_OFFLOAD_RESTORED = REGISTRY.counter(
    "gateway_kv_offload_restored_pages_total",
    "Host-tier KV pages restored to the device pool at admission",
)
KV_OFFLOAD_DROPPED = REGISTRY.counter(
    "gateway_kv_offload_dropped_pages_total",
    "Host-tier KV pages dropped (LRU under the byte budget, or oversize)",
)
#: Host→device promotion latency per page, install included — the
#: number that must beat re-prefilling page_size tokens for the tier to
#: pay for itself.
KV_RESTORE_SECONDS = REGISTRY.histogram(
    "gateway_kv_restore_seconds",
    "Per-page host-to-device KV restore latency (device_put + install)",
    buckets=LATENCY_BUCKETS,
)
#: Host-tier occupancy (bytes resident right now, vs the configured
#: ContinuousConfig.host_cache_bytes budget).
KV_HOST_TIER_BYTES = REGISTRY.gauge(
    "gateway_kv_host_tier_bytes",
    "Bytes resident in the host-RAM KV offload tier",
)


# ---------------------------------------------------------------------------
# Serving / scheduler / consensus process-wide families (PR 5: moved
# here from their instrumentation modules so the canonical surface is
# enumerable in ONE file — scripts/check_metrics.py enforces that every
# family those modules feed is declared here and documented in the
# README observability table).
# ---------------------------------------------------------------------------

SERVING_SUBMITTED = REGISTRY.counter(
    "serving_requests_total", "Requests submitted to the continuous batcher"
)
SERVING_COMPLETED = REGISTRY.counter(
    "serving_completed_total", "Requests retired by the continuous batcher"
)
SERVING_TOKENS = REGISTRY.counter(
    "serving_generated_tokens_total", "Tokens generated (incl. EOS)"
)
SERVING_STEPS = REGISTRY.counter(
    "serving_decode_steps_total", "Device decode steps executed"
)
SERVING_WAITING = REGISTRY.gauge(
    "serving_waiting", "Requests waiting for a continuous-batcher slot"
)
SERVING_ACTIVE = REGISTRY.gauge(
    "serving_active_slots", "Continuous-batcher slots currently decoding"
)
SERVING_OCCUPANCY = REGISTRY.histogram(
    "serving_slot_occupancy",
    "Active slots per decode step (batch occupancy)",
    buckets=OCCUPANCY_BUCKETS,
)
SCHED_SUBMITTED = REGISTRY.counter(
    "scheduler_requests_total", "Requests submitted to the batch scheduler"
)
SCHED_DEPTH = REGISTRY.gauge(
    "scheduler_queue_depth", "Requests pending in the batch scheduler"
)
SCHED_OCCUPANCY = REGISTRY.histogram(
    "scheduler_batch_occupancy",
    "Requests packed per executed scheduler batch",
    buckets=OCCUPANCY_BUCKETS,
)
CONSENSUS_QUESTIONS = REGISTRY.counter(
    "consensus_questions_total", "Questions driven through the protocol"
)
CONSENSUS_ROUNDS = REGISTRY.histogram(
    "consensus_rounds",
    "Evaluation rounds to termination (unanimity or the round cap)",
    buckets=(1, 2, 3, 4, 5, 6, 8, 10, 15, 20),
)
CONSENSUS_UNANIMOUS = REGISTRY.counter(
    "consensus_unanimous_total", "Questions ending in genuine unanimity"
)
CONSENSUS_FORCED = REGISTRY.counter(
    "consensus_forced_total", "Questions force-terminated at the round cap"
)


# ---------------------------------------------------------------------------
# Request-scoped tracing (PR 5): histograms derived from the same
# instrumentation points that record trace spans, so ``/metrics``,
# ``stats()``, and ``GET /debug/traces`` stay in lockstep.
# ---------------------------------------------------------------------------

#: Canonical declaration of the gateway's TTFT histogram (instances
#: with isolated registries re-create it per registry; see
#: INSTANCE_FAMILIES below).
GATEWAY_TTFT = REGISTRY.histogram(
    "gateway_ttft_seconds",
    "Time from request arrival to first token byte",
)
#: One observation per decode-step device program: dispatch through the
#: host fetch of the sampled tokens (the true device step latency the
#: per-trace "decode_step" spans record).
DECODE_STEP_SECONDS = REGISTRY.histogram(
    "gateway_decode_step_seconds",
    "Continuous-batcher decode-step device latency (dispatch to fetch)",
)
#: UN-OVERLAPPED host time per decode dispatch — retirement, admission,
#: prefill-chunk scheduling, group rebuilds that no in-flight decode
#: program hid. Under pipelined dispatch (PR 6, pipeline_depth > 1) a
#: dispatch issued while a program is still in flight did its host work
#: in that program's shadow and observes 0; at depth 1 this reduces to
#: the classic host-gap-between-steps. The scheduler overhead the
#: decode roofline never shows; idle waits do not count.
SCHED_OVERHEAD_SECONDS = REGISTRY.histogram(
    "gateway_sched_overhead_seconds",
    "Un-overlapped host time per decode dispatch (scheduling overhead)",
)
#: Pipelined decode dispatch (PR 6): decode programs dispatched but not
#: yet token-fetched (0..pipeline_depth), and the drains forced by
#: operations that need a stable cache underneath them (host-tier page
#: restores, CoW boundary copies, legacy dense prefill). A flush-heavy
#: workload is paying pipeline restarts for its admission pattern.
DISPATCH_INFLIGHT = REGISTRY.gauge(
    "gateway_dispatch_inflight",
    "Decode programs dispatched but not yet fetched",
)
PIPELINE_FLUSHES = REGISTRY.counter(
    "gateway_pipeline_flushes_total",
    "Decode-pipeline drains before stable-cache operations",
)
#: Programs enqueued to a device the loop knew to be empty while rows
#: were decoding (PR 36), by what emptied the window: ``first_token``
#: (the fetch that ended a prompt left nothing in flight: 0 at
#: ``pipeline_depth`` >= 2, and where a wait put back on that path
#: shows), ``standalone_chunk``, ``flush``
#: (``gateway_pipeline_flushes_total``'s drains) and ``other`` (depth 1,
#: a depth reduction). Over ``gateway_device_programs_total`` it is the
#: share of programs the device had to wait for.
PIPELINE_DRAINS = REGISTRY.counter(
    "gateway_pipeline_drains_total",
    "Programs enqueued to an empty device while rows decoded, by cause",
)
#: Fused scheduler step (PR 8): device programs the scheduler loop
#: dispatched, labeled ``kind="fused"`` (one program carrying the
#: step's decode rows AND a prefill chunk — the ragged-attention
#: target state), ``kind="decode"`` (decode rows only),
#: ``kind="prefill"`` (a standalone prefill program: a chunk with no
#: decode batch to ride, or the legacy dense path), ``kind="spec"``
#: (PR 9: one speculative draft+verify+accept round), or
#: ``kind="draft"`` (the draft model's mirror of a prefill). Programs
#: per scheduler iteration == 1 is the fusion working; 2 is the
#: pre-ragged "one chunk program + one decode program" serialization.
DEVICE_PROGRAMS = REGISTRY.counter(
    "gateway_device_programs_total",
    "Device programs dispatched by the continuous-batcher scheduler loop",
)
#: Where the batcher thread's time goes (PR 26), labeled
#: ``phase="admit"|"restore"|"dispatch"|"device_wait"|"retire"|"idle"``.
#: The phases never overlap and leave nothing out: where one runs
#: inside another (a pipeline flush fetching from inside a dispatch)
#: the outer one's time stops while the inner one runs, and the few
#: microseconds between two phases go to the one that follows. So over
#: any window the phases sum to the window. ``device_wait`` is every
#: blocking wait on the device (the token fetch's host sync, each
#: ``block_until_ready``, the first-token sample's ``int()``); the
#: other four working phases are host work, whether the device is busy
#: behind them or not. Each stretch is also a ``batcher.<phase>``
#: ``jax.profiler.TraceAnnotation`` on the profiler's host plane, from
#: the same two clock reads.
BATCHER_PHASE_SECONDS = REGISTRY.counter(
    "gateway_batcher_phase_seconds_total",
    "Seconds of the continuous-batcher thread by loop phase",
)
#: Output tokens credited to live rows, counted where they are
#: credited: the first at activation (sampled from prefill logits), the
#: rest at the fetch that appends them. Tokens a program decoded for a
#: row that had already finished are discarded there and not counted.
#: Over any interval this equals the summaries' ``new_tokens``, up to
#: what is in flight at the edges (``serving_generated_tokens_total``
#: moves only at retirement, a whole generation at a time).
GENERATED_TOKENS = REGISTRY.counter(
    "gateway_generated_tokens_total",
    "Output tokens credited to live rows (at activation and at fetch)",
)
#: Real prompt tokens computed by prefill programs: chunk lanes
#: (standalone and fused) and the legacy dense path. Padding past the
#: prompt's end and tokens whose pages came from the prefix registry,
#: a boundary copy or the host tier are not computed and not counted.
#: Over ``gateway_device_programs_total{kind="prefill"|"fused"}`` it is
#: the prompt tokens one chunk program really carries.
PREFILL_TOKENS = REGISTRY.counter(
    "gateway_prefill_tokens_total",
    "Prompt tokens computed by prefill programs (no padding, no cached)",
)
#: Chunk programs by how many of their lanes carried a chunk, labeled
#: ``kind="prefill"|"fused"`` and ``lanes="n"``: a step program carries
#: the ready chunks of up to L prefilling slots (PR 31), and "one slot
#: was ready" reads differently from "three rode". Over a kind the
#: lanes sum to ``gateway_device_programs_total`` of that kind.
CHUNK_LANES = REGISTRY.counter(
    "gateway_chunk_lanes_total",
    "Chunk programs by kind and by the lanes that carried a chunk",
)
#: Recurrent state beside the pages (PR 32; a model with state-space
#: layers): the state pool's slots by holder — ``live`` sequences,
#: registry ``snapshot``s, ``free`` — and what became of snapshots: a
#: prefill ``saved`` one at a page end, an admission ``restored`` its
#: state from one, ``missed`` (its page match ran deeper than any
#: snapshot, so ``gateway_prefix_tokens_recomputed_total`` tokens that
#: pages covered were prefilled again), or one was ``evicted``.
STATE_SLOTS = REGISTRY.gauge(
    "gateway_state_slots",
    "Recurrent-state slots by holder: live, snapshot, free",
)
STATE_SNAPSHOTS = REGISTRY.counter(
    "gateway_state_snapshots_total",
    "Recurrent-state snapshots by event: saved, restored, missed, evicted",
)
PREFIX_TOKENS_RECOMPUTED = REGISTRY.counter(
    "gateway_prefix_tokens_recomputed_total",
    "Prompt tokens whose pages matched and whose state no snapshot held",
)
SSM_TOKENS = REGISTRY.counter(
    "gateway_ssm_tokens_total",
    "Tokens through the state-space layers, by device-program kind",
)
#: The dropless expert layer's routing, labeled ``kind`` like the device
#: programs (``decode`` / ``fused`` / ``prefill``), counted where the
#: batcher retires a program from numbers the program itself returned
#: beside its tokens. Over an interval: experts touched / (layer programs
#: x the layer's experts) is the share of a layer's expert matrices a
#: step reads; assignments / experts touched is the rows one expert
#: matrix read serves.
MOE_ASSIGNMENTS = REGISTRY.counter(
    "gateway_moe_assignments_total",
    "(token, expert) pairs the dropless expert layers computed",
)
MOE_EXPERTS_TOUCHED = REGISTRY.counter(
    "gateway_moe_experts_touched_total",
    "Experts some token reached, summed over expert layers and programs",
)
MOE_LAYER_PROGRAMS = REGISTRY.counter(
    "gateway_moe_layer_programs_total",
    "Expert layers run: device programs x the model's expert layers",
)
#: Cached tokens the attention of a retired program read out of the
#: pool, a shared run counted once a group: the ``kv_read_tokens`` of
#: the program's cost model, for every model.
ATTENTION_TOKENS_READ = REGISTRY.counter(
    "gateway_attention_tokens_read_total",
    "Cached tokens attention read (shared runs once a group), by kind",
)
#: Pool pages the ragged attention kernel of a retired program folded a
#: layer (each row's own pages, a group's shared run once, the chunk
#: lane's): ``attn_pages_read`` of the program's cost model. Over
#: programs x (slots + chunk lane + groups) x ``pages_per_seq`` it is
#: the share of a whole-table walk that was live.
ATTENTION_PAGES_READ = REGISTRY.counter(
    "gateway_attention_pages_read_total",
    "Pool pages attention folded a layer (shared runs once a group), by kind",
)
#: Device memory as the allocator reports it, labeled
#: ``kind="in_use"|"peak"|"limit"``: the largest value over the local
#: devices. Filled when ``/metrics`` is rendered (a render hook the
#: first batcher installs), never from the serving loop; a backend
#: without ``memory_stats`` (the CPU) exports no sample.
DEVICE_MEMORY_BYTES = REGISTRY.gauge(
    "gateway_device_memory_bytes",
    "Device memory by kind (in_use, peak, limit), largest local device",
)
#: Rows sharing one ragged device program: active decode rows plus the
#: fused prefill-chunk lane (fused/decode programs only). The mixed
#: prefill+decode occupancy of the one kernel.
RAGGED_ROWS = REGISTRY.histogram(
    "gateway_ragged_rows_per_program",
    "Rows (decode rows + fused prefill-chunk lanes) per device program",
    buckets=OCCUPANCY_BUCKETS,
)
#: Multi-round on-device decode (PR 12): decode rounds folded into one
#: dispatched program. A plain decode/fused program under
#: ``ContinuousConfig.decode_rounds`` R runs up to R decode rounds —
#: stop scan, sampling, emit-count/length bookkeeping on device, frozen
#: rows masked — before the host fetches; a speculative verify round
#: counts 1 (its emit is already multi-token). Rounds count once per
#: PROGRAM, not per row: ``device_rounds_total`` over the
#: decode-advancing ``gateway_device_programs_total`` is the realized
#: rounds per program (→ R when multi-round engages), and device
#: programs per generated token drops ~R× at R for a fixed batch
#: shape (its absolute value carries the 1/batch-rows factor;
#: tests/test_decode_rounds.py). Histogram: the per-program
#: round count at dispatch (R, or 1 when a row's stop sequences have
#: no bounded device screen and the window collapses to the
#: host-checked cadence).
DECODE_ROUNDS_PER_PROGRAM = REGISTRY.histogram(
    "gateway_decode_rounds_per_program",
    "Decode rounds folded into one dispatched device program",
    buckets=OCCUPANCY_BUCKETS,
)
DEVICE_ROUNDS = REGISTRY.counter(
    "gateway_device_rounds_total",
    "Decode rounds dispatched across all decode-advancing device programs",
)
#: Speculative decoding inside the continuous batcher (PR 9). The
#: draft proposes ``spec_k`` tokens per round — ONE stream per
#: shared-prefix panel group (mates whose committed text still agrees
#: with their donor's reuse its stream), so ``drafted`` counts k per
#: STREAM, not per row; the target verifies all rows' drafts through
#: the ragged k+1-token rows of one device program and the leviathan
#: accept rule emits the accepted prefix + a correction/bonus token.
#: acceptance = accepted / (k * rows) per round; verified_tokens is the
#: last spec program's total emitted tokens (tokens-per-device-program
#: > 1 is speculation beating the one-token-per-program roofline).
SPEC_DRAFT_TOKENS = REGISTRY.counter(
    "gateway_spec_draft_tokens_total",
    "Draft tokens proposed by speculative decoding (k per stream/round)",
)
SPEC_ACCEPTED_TOKENS = REGISTRY.counter(
    "gateway_spec_accepted_tokens_total",
    "Draft tokens the target's verify rounds accepted",
)
SPEC_ACCEPTANCE = REGISTRY.histogram(
    "gateway_spec_acceptance",
    "Per-round draft acceptance fraction (accepted / (spec_k * rows))",
    buckets=ACCEPTANCE_BUCKETS,
)
SPEC_VERIFIED_TOKENS = REGISTRY.gauge(
    "gateway_spec_verified_tokens",
    "Tokens emitted by the most recent speculative verify program",
)
#: Cross-model speculation (PR 18): draft tokens accepted when the
#: draft rode a vocab-alignment remap (serving/vocab_align.py) — a
#: DIFFERENT tokenizer than the target's. Counted at the same fetch
#: site as gateway_spec_accepted_tokens_total (the cross-model counts
#: are a subset); the coverage gauge is the construction-time
#: exact-match fraction the pairing engaged with, labeled by the
#: target ``model`` so a heterogeneous ModelSet's pairings read apart.
SPEC_XMODEL_ACCEPTED_TOKENS = REGISTRY.counter(
    "gateway_spec_cross_model_accepted_tokens_total",
    "Draft tokens accepted through a cross-model vocab remap",
)
SPEC_XMODEL_COVERAGE = REGISTRY.gauge(
    "gateway_spec_cross_model_coverage",
    "Exact-match vocab coverage of the engaged cross-model draft pairing",
)
#: Multi-model serving plane (PR 18, serving/modelset.py): one gateway
#: fronting N independent engines. Labeled ``model=<member name>`` —
#: the shared metrics plane's per-model split (requests dispatched to
#: each member and the tokens it generated), mirrored into
#: ``ModelSet.stats()``.
MODEL_REQUESTS = REGISTRY.counter(
    "gateway_model_requests_total",
    "Requests dispatched to each ModelSet member (label: model)",
)
MODEL_TOKENS = REGISTRY.counter(
    "gateway_model_tokens_total",
    "Tokens generated by each ModelSet member (label: model)",
)
#: Consensus protocol phase latency, labeled
#: ``phase="propose"|"evaluate"|"refine"`` — one observation per phase
#: execution (an evaluation round and its refinement observe
#: separately). Mirrors the per-trace "consensus_round" spans.
CONSENSUS_ROUND_SECONDS = REGISTRY.histogram(
    "consensus_round_seconds",
    "Consensus phase latency by phase (propose/evaluate/refine)",
)
#: Ring-buffer pressure in the tracing layer, labeled
#: ``kind="span"`` (a span refused by a full per-trace span budget) or
#: ``kind="trace"`` (a whole trace evicted from the bounded
#: TraceStore). Fed via the tracing drop hook
#: wired below — the lockstep contract between the two surfaces.
TRACE_DROPPED = REGISTRY.counter(
    "gateway_trace_dropped_total",
    "Spans/traces dropped by the bounded tracing ring buffers",
)


# ---------------------------------------------------------------------------
# Serving flight recorder + roofline attribution (PR 10).
# ---------------------------------------------------------------------------

#: Events evicted from the flight recorder's bounded ring
#: (:mod:`llm_consensus_tpu.serving.flight`) — the recorder keeps the
#: newest ``capacity`` scheduler events and counts what it forgot, so a
#: truncated ``GET /debug/flight`` export is detectable, never silent.
FLIGHT_DROPPED = REGISTRY.counter(
    "gateway_flight_dropped_total",
    "Flight-recorder events evicted from the bounded ring",
)
#: Time between consecutive generated tokens as the HOST observes them
#: (one observation per generated token past a request's first; tokens
#: that land in the same program fetch — a multi-round window,
#: accepted speculative runs — observe 0 for all but the first, which
#: is exactly the bursty arrival a streaming client sees). The
#: per-request p50/p99 summary rides ``/debug/requests`` and the
#: response meta; TTFT for the first token stays in
#: ``gateway_ttft_seconds`` (gateway side) + the batcher's stats()
#: ``ttft_seconds_*`` mirror (submit-to-first-token).
TBT_SECONDS = REGISTRY.histogram(
    "gateway_tbt_seconds",
    "Inter-token gap per generated token (time-between-tokens)",
)
#: Model-bandwidth-utilization per device-program kind, labeled
#: ``kind="fused"|"decode"|"spec"|"prefill"``: the static cost model's
#: HBM bytes for the most recent fetched program of that kind (weight
#: bytes + KV page bytes actually touched, group-shared reads counted
#: once — :func:`llm_consensus_tpu.models.transformer.program_hbm_cost`)
#: divided by its measured wall time and by the configured peak
#: bandwidth (``ContinuousConfig.hbm_gbps``; 0 disables the gauge —
#: stats() still exposes the modeled-bytes / measured-seconds sums per
#: kind so MBU can be derived offline). ~1.0 means the program kind is
#: at the weights+KV roofline; meaningful on the chip only (a CPU
#: "MBU" against an HBM peak is a smoke-test plumbing check).
PROGRAM_MBU = REGISTRY.gauge(
    "gateway_program_mbu",
    "Model-bandwidth-utilization of the last device program, by kind",
)


# ---------------------------------------------------------------------------
# Mesh-native serving (PR 13).
# ---------------------------------------------------------------------------

#: The continuous batcher's serving mesh topology, labeled
#: ``axis="data"`` (slot/page-pool shards — each data shard owns a
#: contiguous slot block and its page range) and ``axis="model"``
#: (tensor-parallel shards — kv heads and the MLP hidden split). 1 on
#: both axes = a single-chip batcher. Purely descriptive: every
#: serving feature (fused ragged dispatch, grouped prefix attention,
#: multi-round decode, speculative decoding, the host KV tier) engages
#: at any value since PR 13 — the README Serving engage matrix is the
#: authoritative table. Mirrored in the batcher's stats() as
#: ``mesh_data_shards`` / ``mesh_model_shards`` (lockstep tested).
MESH_SHARDS = REGISTRY.gauge(
    "gateway_mesh_shards",
    "Serving mesh shard count by axis (1 = unsharded)",
)


# ---------------------------------------------------------------------------
# Prefix-affinity replica fleet (PR 14): N continuous-batcher replicas
# behind one gateway (serving/fleet.py), routed by prefix affinity with
# preempt-to-host-tier instead of 429s. All labeled ``replica="<idx>"``
# except the shared-store gauge (the store is fleet-scoped, one per
# ReplicaSet).
# ---------------------------------------------------------------------------

#: One increment per routed request, labeled ``replica`` and ``reason``
#: (``"prefix"`` — the replica held the longest resident chain;
#: ``"load"`` — no affinity anywhere, least modeled-cost replica won;
#: ``"rebalance"`` — the affinity owner was congested, the chain was
#: exported through the shared store and the request re-homed;
#: ``"random"`` — the round-robin control policy). affinity/total is
#: the routed prefix-affinity rate.
REPLICA_ROUTED = REGISTRY.counter(
    "gateway_replica_routed_total",
    "Requests routed to each fleet replica, by routing reason",
)
#: Device programs each replica's scheduler loop has dispatched (the
#: sum of its gateway_device_programs_total contributions — that
#: family is process-global, so the per-replica split lives here).
#: Refreshed at route/preempt time and on every fleet stats() pull.
REPLICA_PROGRAMS = REGISTRY.gauge(
    "gateway_replica_programs",
    "Device programs dispatched by each fleet replica",
)
#: Each replica's prefix-registry hit rate (hits / lookups over
#: committed admissions). Affinity routing drives this toward the
#: panel's share rate on the chain-owning replica; random routing
#: dilutes it fleet-wide. Refresh cadence as gateway_replica_programs.
REPLICA_PREFIX_HIT_RATE = REGISTRY.gauge(
    "gateway_replica_prefix_hit_rate",
    "Per-replica prefix-registry hit rate (hits / lookups)",
)
#: Router-requested preemptions per replica: overload moments where
#: resident chains were demoted to the shared host tier (freeing
#: device pages) so the storm could be admitted instead of shed.
REPLICA_PREEMPTIONS = REGISTRY.counter(
    "gateway_replica_preemptions_total",
    "Router-requested preempt-to-host-tier events per fleet replica",
)
#: Bytes resident in the FLEET-SCOPED host page store (one per
#: ReplicaSet; any replica can restore any chain). The per-batcher
#: gateway_kv_host_tier_bytes gauge tracks the same store when shared.
REPLICA_SHARED_STORE_BYTES = REGISTRY.gauge(
    "gateway_replica_shared_store_bytes",
    "Bytes resident in the fleet-shared host page store",
)


# ---------------------------------------------------------------------------
# Roofline-adaptive runtime control (PR 15, serving/control.py): the
# PR-10 cost model closed into a feedback loop. Labeled
# ``knob="spec_k"|"rounds"|"chunk"|"depth"``. Process-global like
# gateway_device_programs_total: a replica FLEET's controllers all
# write the same families (last writer wins on the gauge) — the
# per-replica split lives in the fleet stats() ``per_replica`` list,
# whose batcher stats carry each controller's ``autotune_*`` mirrors,
# exactly the PR-14 convention for the per-replica program counts.
# ---------------------------------------------------------------------------

#: One increment per knob decision that CHANGED the knob's value
#: (steady-state re-decisions are silent, like spec_flip flight
#: events): spec_k shrink/regrow/disengage (value 0 = speculation
#: disengaged until a probe re-accepts), an adaptive-R window cap, a
#: chunk-width flip, a pipeline-depth probe/commit/revert. Mirrored in
#: the batcher's stats() as ``autotune_decisions_<knob>`` (lockstep
#: tested); each change is also an ``autotune`` flight event.
AUTOTUNE_DECISIONS = REGISTRY.counter(
    "gateway_autotune_decisions_total",
    "Adaptive-controller knob decisions that changed a knob value",
)
#: The last decided effective value per knob (spec_k's 0 =
#: disengaged). Pinned knobs (ControlConfig.tune_* = False) never set
#: their label. stats() mirror: ``autotune_<knob>`` (-1 = no decision
#: yet).
AUTOTUNE_VALUE = REGISTRY.gauge(
    "gateway_autotune_value",
    "Last effective knob value decided by the adaptive controller",
)


# ---------------------------------------------------------------------------
# Disaggregated prefill/decode serving (PR 16, serving/remote_store.py +
# serving/disagg.py): the fleet-scoped host page store becomes a
# length-prefixed TCP/UDS transport so the router/store seam spans
# processes and hosts, and replicas specialize into prefill/decode
# ROLES that hand finished chains through it. Like the autotune
# families above, these are process-global, last-writer-wins across a
# roled fleet — the per-ROLE split lives in the fleet stats()
# ``per_replica`` list (each entry names its replica's ``role``), the
# same PR-14/15 convention for per-replica program counts and autotune
# mirrors.
# ---------------------------------------------------------------------------

#: Bytes resident in the AUTHORITATIVE store behind a RemotePageStore
#: client, as of the client's last successful exchange (every response
#: frame piggybacks the server store's counters, so reading this never
#: costs a network round trip — the admission overflow hook reads
#: headroom on the event loop).
REMOTE_STORE_BYTES = REGISTRY.gauge(
    "gateway_remote_store_bytes",
    "Bytes resident in the remote host page store (last-exchange view)",
)
#: Remote page-store operations that failed (connect refused, peer
#: disconnect mid-frame, client timeout against a slow peer). Every
#: failure degrades to a local MISS — get None / touch False / put
#: dropped — so the worker loop recomputes instead of wedging; a
#: climbing rate with a flat restored-pages rate is a dead peer.
REMOTE_STORE_ERRORS = REGISTRY.counter(
    "gateway_remote_store_errors_total",
    "Remote page-store operations that failed and degraded to a miss",
)
#: Wall-clock round-trip per successful remote store exchange (request
#: frame out to response frame parsed). Page payloads ride put/get, so
#: compare against gateway_kv_restore_seconds to see what the wire adds
#: to a restore.
REMOTE_STORE_RTT = REGISTRY.histogram(
    "gateway_remote_store_rtt_seconds",
    "Round-trip latency per successful remote page-store exchange",
    buckets=LATENCY_BUCKETS,
)
#: Chains handed from a prefill-role replica to a decode-role replica
#: through the (shared or remote) page store: the prefill replica ran
#: admission + chunked prefill, exported the finished chain via the
#: PR-14 export path, and a decode replica's admission restored it —
#: zero header pages re-prefilled on the decode side.
ROLE_HANDOFFS = REGISTRY.counter(
    "gateway_role_handoffs_total",
    "Prefill-to-decode chain handoffs through the fleet page store",
)


# ---------------------------------------------------------------------------
# Zero-copy pipelined KV movement plane (PR 17, serving/remote_store.py
# wire v2 + streamed handoff + route-driven restore prefetch). Process-
# global across a fleet like the PR-16 families above; per-replica
# prefetch splits live in each batcher's stats() mirrors.
# ---------------------------------------------------------------------------

#: Plane PAYLOAD bytes moved over the page-store wire by this process's
#: RemotePageStore clients, labeled ``dir="tx"|"rx"`` (tx = puts out,
#: rx = get/get_run planes in). Framing/header overhead is excluded so
#: the rate divides cleanly into pages/s; divide by
#: gateway_remote_store_rtt_seconds_sum for effective wire throughput.
TRANSFER_BYTES = REGISTRY.counter(
    "gateway_transfer_bytes_total",
    "KV plane payload bytes moved over the page-store wire by direction",
)
#: Route-driven restore prefetch outcomes, labeled ``event=``:
#: ``fetched`` pages pulled store->host ahead of admission; ``hit``
#: pages admission consumed from the prefetch cache (each one is a
#: store round trip shaved off the restore flush); ``expired`` pages
#: evicted from the bounded cache before any admission claimed them
#: (wasted transfer — a high expired:fetched ratio means the router is
#: prefetching chains that never arrive, or the cache cap is too
#: small). Admission falls through expired entries to the store and
#: then to recompute — never corrupt, only slower.
KV_PREFETCH = REGISTRY.counter(
    "gateway_kv_prefetch_total",
    "Route-driven KV restore prefetch page outcomes",
)
#: Wall-clock from a decode replica claiming a cold chain to the chain
#: fully exported and restorable (the prefill->decode handoff the
#: HandoffCoordinator runs). The streamed path overlaps export with
#: prefill compute, so this should hug the prefill time itself; the
#: PR-16 synchronous path pays prefill + whole-chain export serially.
HANDOFF_SECONDS = REGISTRY.histogram(
    "gateway_handoff_seconds",
    "Prefill-to-decode chain handoff wall-clock (claim to exported)",
    buckets=LATENCY_BUCKETS,
)


# ---------------------------------------------------------------------------
# Fleet control plane (PR 19, serving/fleet_control.py). The controller
# is fleet-scoped — one per ReplicaSet — so its families are process-
# global like the PR-16/17 plane families above. Per-request SLO/tenant
# admission families live on the gateway's per-instance registry and are
# manifested in INSTANCE_FAMILIES below.
# ---------------------------------------------------------------------------

#: Replica lifecycle census, labeled ``state="serving"|"draining"|
#: "retired"``. Refreshed by ReplicaSet on every state transition; a
#: nonzero ``draining`` means an elastic retire is mid-drain (the router
#: skips that replica for new work while its in-flight requests finish).
FLEET_REPLICAS = REGISTRY.gauge(
    "gateway_fleet_replicas",
    "Batcher replicas per lifecycle state",
)
#: Elastic lifecycle transitions, labeled ``action="spawn"|"drain"|
#: "retire"``. A retire is always preceded by a drain (router stops new
#: work, in-flight finishes, chains demote to the shared HostPageStore)
#: so ``retire`` without a matching ``drain`` indicates a bug.
FLEET_SCALE = REGISTRY.counter(
    "gateway_fleet_scale_total",
    "Elastic replica lifecycle transitions by action",
)
#: Router load-steering weight per replica, labeled ``replica=``. The
#: fleet controller multiplies each replica's modeled queue cost by this
#: weight inside PrefixRouter's least-cost comparisons, so weight > 1
#: repels new work and weight < 1 attracts it. 1.0 = neutral (the
#: static PR-14 behavior).
ROUTER_WEIGHT = REGISTRY.gauge(
    "gateway_router_weight",
    "PrefixRouter load-steering weight per replica",
)
#: Fleet-controller decisions that CHANGED a setpoint, labeled
#: ``decision="router_weights"|"group_cap"|"restore_cap"|"spawn"|
#: "retire"``. Mirrors the PR-15 autotune convention: gauges refresh
#: every tick, this counter moves only on change, and each change also
#: lands a ``fleet`` flight-recorder event for replay.
FLEET_DECISIONS = REGISTRY.counter(
    "gateway_fleet_decisions_total",
    "Fleet-controller setpoint changes by decision",
)


# ---------------------------------------------------------------------------
# Canonical manifest of families created on PER-INSTANCE registries
# (gateway/admission accept an isolated MetricsRegistry for test
# isolation, so their families cannot be module-level objects here).
# scripts/check_metrics.py treats these names as declared; add a row
# here AND to the README observability table when instrumenting a new
# one.
# ---------------------------------------------------------------------------

INSTANCE_FAMILIES: dict[str, str] = {
    "gateway_requests_total": "counter",
    "gateway_request_seconds": "histogram",
    "gateway_tokens_per_second": "histogram",
    "gateway_queue_depth": "gauge",
    "gateway_inflight": "gauge",
    "gateway_admitted_total": "counter",
    "gateway_shed_total": "counter",
    "gateway_deadline_expired_total": "counter",
    "gateway_completed_total": "counter",
    "gateway_queue_wait_seconds": "histogram",
    "gateway_queue_cost_bytes": "gauge",
    "gateway_slo_miss_total": "counter",
    "gateway_slo_shed_total": "counter",
    "gateway_slo_headroom_seconds": "histogram",
    "gateway_tenant_cost_bytes": "counter",
    "gateway_tenant_shed_total": "counter",
    # PR 20 fleet observability: per-hop request attribution sourced
    # from the joined trace spans (labeled ``hop="front_route"|
    # "admission_wait"|"prefill"|"handoff"|"wire_transfer"|"decode"``),
    # and the admission controller's decayed per-``class`` SLO miss
    # fraction the FleetController reads through burn_rates().
    "gateway_hop_seconds": "histogram",
    "gateway_slo_burn_rate": "gauge",
}


# Mirror tracing-layer drops into the registry (lockstep: the hook runs
# at the drop site, inside the tracing module's accounting).
from llm_consensus_tpu.utils import tracing as _tracing  # noqa: E402

_tracing.set_drop_hook(lambda kind, n: TRACE_DROPPED.labels(kind=kind).inc(n))
