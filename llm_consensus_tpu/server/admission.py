"""Admission control: bounded per-priority queues, shedding, deadlines, drain.

The reference accepts unbounded concurrent work — every HTTP request
spawns a future immediately (``src/main.rs:101,156,182``), so overload
manifests as memory growth and collapse instead of backpressure. This
module is the opposite contract, the one every production serving stack
makes explicit:

- **Bounded queues, one per priority.** When a priority's queue is full
  the request is SHED at the door (:class:`QueueFullError` -> the
  gateway's ``429`` + ``Retry-After``) instead of admitted into an
  ever-deeper backlog. Dispatch drains strictly by priority order.
- **Deadlines.** A request may carry a deadline; if it expires while
  still queued the work is cancelled before it ever touches the backend
  (:class:`DeadlineExpiredError` -> ``504``), and an admitted request's
  backend call runs under ``asyncio.wait_for`` with the remaining
  budget so in-flight work is cancelled at the deadline too.
- **Graceful drain.** :meth:`AdmissionController.drain` stops admitting
  (:class:`DrainingError` -> ``503``) and waits for every
  already-admitted request — queued and in-flight — to reach its
  terminal outcome. The gateway calls it on SIGTERM.

Single-event-loop asyncio; the controller owns a dispatcher task with a
bounded in-flight window (``max_inflight``) so the backend sees at most
a fixed number of concurrent batch calls regardless of queue depth.

Every transition feeds the metrics registry: queue depth gauges,
admitted/shed/expired/completed counters (all labeled by priority), and
queue-wait histograms — the series the overload integration test
cross-checks against observed HTTP outcomes.
"""

from __future__ import annotations

import asyncio
import logging
import time
from collections import deque
from collections.abc import Awaitable, Callable
from dataclasses import dataclass, field

from llm_consensus_tpu.server import metrics as _metrics
from llm_consensus_tpu.utils import tracing as _tracing

log = logging.getLogger(__name__)

__all__ = [
    "AdmissionConfig",
    "AdmissionController",
    "DeadlineExpiredError",
    "DrainingError",
    "QueueFullError",
]


class QueueFullError(Exception):
    """Load shed: the request's priority queue is at its bound.

    ``slo_miss`` marks a deadline-aware shed (PR 19): the victim was
    chosen because it *will miss its SLO*, not because it was newest.
    ``tenant_over`` marks a fair-share shed: the tenant exceeded its
    weighted share of admitted modeled cost while another tenant was
    waiting. Both ride the exception so the gateway's flight-recorder
    shed event can name the reason.
    """

    def __init__(
        self,
        priority: str,
        retry_after: float,
        *,
        slo_miss: bool = False,
        tenant_over: bool = False,
    ):
        super().__init__(
            f"{priority} queue full; retry after {retry_after:.1f}s"
        )
        self.priority = priority
        self.retry_after = retry_after
        self.slo_miss = slo_miss
        self.tenant_over = tenant_over


class DrainingError(Exception):
    """The controller is draining (SIGTERM): no new admissions."""


class DeadlineExpiredError(Exception):
    """The request's deadline passed before the work completed."""


@dataclass
class AdmissionConfig:
    # Priority order = dispatch order: the first listed priority drains
    # first. Every request names one of these.
    priorities: tuple[str, ...] = ("interactive", "batch")
    # Per-priority queue bound; an int applies to every priority, a dict
    # overrides per name.
    max_queue: int | dict[str, int] = 64
    # Concurrent in-flight executions across all priorities. The backend
    # underneath batches, so a handful of concurrent generate_batch
    # calls keeps the chip full without unbounded task fan-out.
    max_inflight: int = 8
    # Deadline applied when a request does not carry one; None = none.
    default_deadline_s: float | None = None
    # Retry-After hint returned on shed when the queue-wait history is
    # still empty.
    retry_after_s: float = 1.0
    # Hard ceiling on overflow admission (PR 14): a granting
    # overflow_hook stretches a priority's queue bound by at most this
    # factor — preemption absorbs storms, it never REMOVES
    # backpressure (a stale preempt signal + a mega-storm must
    # eventually shed fast 429s instead of queueing requests to
    # deadline death and growing queue memory with offered load).
    # UNIT NORMALIZATION (PR 15): the factor multiplies whatever unit
    # the bound itself uses — requests in classic mode, MODELED BYTES
    # in cost-budget mode — so the hard-cap path can never again mix
    # a bytes-denominated preempt signal with a request-count cap.
    max_overflow_factor: int = 16
    # Cost-budget admission (PR 15): > 0 switches every queue bound
    # from request COUNTS to MODELED BYTES — the same unit the fleet
    # router's load_cost compares and ContinuousBatcher.
    # modeled_request_cost prices (a 32k-context request is not one
    # unit of work). Each submit carries its modeled cost; a request
    # without one is priced at one nominal slot
    # (budget / bound_for(priority)). 0 (default) = classic
    # request-count bounds.
    cost_budget_bytes: float = 0.0
    # SLO classes (PR 19): class name -> queue-wait target in seconds
    # (the admission-controlled component of TTFT — the PR-10 TTFT/TBT
    # histograms become targets instead of telemetry). A request names
    # a class via the ``/v1/generate`` ``"slo"`` payload field; unknown
    # names are a 400 at the door. None = SLO-blind admission.
    slo_classes: dict[str, float] | None = None
    # Class applied to requests that carry no ``"slo"`` field. None =
    # untagged requests stay SLO-blind even when classes are defined.
    default_slo_class: str | None = None
    # Tenant fair-share (PR 19): True enables weighted fair queueing
    # across the ``"tenant"`` payload field — WFQ dispatch order within
    # a priority plus an admitted-cost share cap under contention, so
    # one tenant's storm cannot starve panel traffic. Enforcement uses
    # the same modeled-byte unit as cost-budget admission.
    tenant_fair_share: bool = False
    # Tenant -> WFQ weight. Tenants absent from the map weigh 1.0, so
    # an empty map means equal shares.
    tenant_weights: dict[str, float] | None = None
    # Share-cap slack: a tenant is shed at the door only once its
    # decayed admitted-cost share exceeds fair_weight * slack while
    # another tenant has queued work (1.1 = a ±10% band).
    fair_share_slack: float = 1.1
    # Half-life in seconds of the decayed per-tenant admitted-cost
    # window the share cap is computed over.
    fair_window_s: float = 30.0

    def slo_target(self, name: str | None) -> float | None:
        if name is None or not self.slo_classes:
            return None
        return self.slo_classes.get(name)

    def tenant_weight(self, tenant: str) -> float:
        w = (self.tenant_weights or {}).get(tenant, 1.0)
        return max(float(w), 1e-6)

    def bound_for(self, priority: str) -> int:
        if isinstance(self.max_queue, dict):
            return int(self.max_queue.get(priority, 64))
        return int(self.max_queue)


@dataclass
class _Item:
    thunk: Callable[[], Awaitable]
    priority: str
    deadline: float | None  # monotonic seconds, None = no deadline
    enqueued_at: float
    future: asyncio.Future = field(default_factory=asyncio.Future)
    # Request trace captured at submit: the dispatcher's _run task has
    # its own contextvars context (it is NOT a child of the submitter),
    # so the trace must ride the item and be re-installed around the
    # thunk (tracing.use_trace) for downstream spans to attach.
    trace: object | None = None
    # Modeled cost in bytes (PR 15, cost-budget mode): charged to the
    # priority's queue-cost account while queued, released at dispatch
    # or expiry. 0 in classic request-count mode.
    cost: float = 0.0
    # SLO class + queue-wait target (PR 19); None = SLO-blind request.
    slo_class: str | None = None
    slo_target: float | None = None
    # Tenant the request bills to (PR 19 fair-share); None = untagged.
    tenant: str | None = None
    # WFQ finish tag stamped at admission when fair-share is on; the
    # dispatcher picks the smallest tag within a priority. 0 = untagged
    # (dispatches ahead of tagged work — it is outside fair-share).
    wfq_tag: float = 0.0
    # Work units for rate/fairness accounting: modeled bytes in
    # cost-budget mode, 1.0 per request in classic mode.
    units: float = 1.0


class AdmissionController:
    """Bounded-queue dispatcher between the gateway and a backend."""

    def __init__(
        self,
        config: AdmissionConfig | None = None,
        registry: _metrics.MetricsRegistry | None = None,
    ):
        self.config = config or AdmissionConfig()
        if not self.config.priorities:
            raise ValueError("need at least one priority")
        reg = registry or _metrics.REGISTRY
        self._queues: dict[str, deque[_Item]] = {
            p: deque() for p in self.config.priorities
        }
        # Modeled bytes queued per priority (PR 15 cost-budget mode):
        # charged at append, released at every popleft site — the
        # bound AND the overflow hard cap read this one account, so
        # the two can never drift units.
        self._queue_cost: dict[str, float] = {
            p: 0.0 for p in self.config.priorities
        }
        self._inflight = 0
        self._draining = False
        # Overload overflow hook (PR 14): consulted at a queue-full
        # moment BEFORE shedding. Returning True admits the request
        # past the bound — the fleet's preempt-to-host-tier path
        # (ReplicaSet.preempt_for_admission) frees backend capacity by
        # demoting resident KV chains instead of 429ing, so an
        # overload storm degrades to restore latency, not lost work.
        # The hook must be cheap and non-blocking (it runs on the
        # event loop inside submit) and is expected to become False
        # once nothing is left to preempt — that, not the queue bound,
        # is then the shed condition. None (default) = classic shed.
        # CHEAPNESS CONTRACT with remote stores (PR 16): the fleet's
        # hook reads the page store's headroom to decide whether
        # demotion can still land pages. A RemotePageStore serves that
        # read from its last piggybacked stats snapshot — NEVER a
        # network round-trip — precisely because this call sits on the
        # event loop at peak overload. A store outage therefore reads
        # as zero headroom (hook returns False) and overload degrades
        # to the classic 429 shed, not a wedged submit path.
        self.overflow_hook: Callable[[], bool] | None = None
        self._work = asyncio.Event()
        self._idle = asyncio.Event()
        self._idle.set()
        self._dispatcher: asyncio.Task | None = None
        self._m_depth = reg.gauge(
            "gateway_queue_depth", "Requests waiting for admission"
        )
        self._m_inflight = reg.gauge(
            "gateway_inflight", "Requests currently executing"
        )
        self._m_admitted = reg.counter(
            "gateway_admitted_total", "Requests accepted into a queue"
        )
        self._m_shed = reg.counter(
            "gateway_shed_total", "Requests shed with 429 (queue full)"
        )
        self._m_expired = reg.counter(
            "gateway_deadline_expired_total",
            "Requests that hit their deadline before completing",
        )
        self._m_completed = reg.counter(
            "gateway_completed_total",
            "Admitted requests that reached a terminal outcome",
        )
        self._m_wait = reg.histogram(
            "gateway_queue_wait_seconds",
            "Time from admission to dispatch",
        )
        self._m_cost = reg.gauge(
            "gateway_queue_cost_bytes",
            "Modeled bytes waiting for admission (cost-budget mode)",
        )
        # -- PR 19 SLO / tenant families + their stats() mirrors. The
        # mirrors are incremented in the same statement block as the
        # Prometheus family so the lockstep tests can cross-check.
        self._m_slo_miss = reg.counter(
            "gateway_slo_miss_total",
            "Requests whose queue wait exceeded their SLO class target",
        )
        self._m_slo_shed = reg.counter(
            "gateway_slo_shed_total",
            "Deadline-aware sheds of requests that would miss their SLO",
        )
        self._m_headroom = reg.histogram(
            "gateway_slo_headroom_seconds",
            "Predicted SLO slack at admission (target - estimated wait)",
        )
        self._m_tenant_cost = reg.counter(
            "gateway_tenant_cost_bytes",
            "Admitted modeled cost per tenant (bytes in cost-budget "
            "mode, request units otherwise)",
        )
        self._m_tenant_shed = reg.counter(
            "gateway_tenant_shed_total",
            "Fair-share sheds: tenant over its weighted admitted share",
        )
        # -- PR 20 SLO burn rate: decayed per-class miss fraction
        # (misses / SLO-classed outcomes over a fair_window_s
        # half-life window) — 0.0 = the class is meeting its target,
        # 1.0 = every recent request missed. The fleet controller
        # reads this through burn_rates(); the gauge and the mirror
        # update in the same statement blocks (lockstep tested).
        self._m_burn = reg.gauge(
            "gateway_slo_burn_rate",
            "Decayed SLO miss fraction per class (misses over "
            "SLO-classed outcomes, half-life fair_window_s)",
        )
        # class -> [outcomes, misses], both decayed together.
        self._burn: dict[str, list[float]] = {}
        self._burn_mark = time.monotonic()
        self._slo_missed: dict[str, int] = {}
        self._slo_sheds = 0
        self._headroom_sum = 0.0
        self._headroom_count = 0
        self._tenant_admitted: dict[str, float] = {}
        self._tenant_sheds: dict[str, int] = {}
        # Queued-request count per tenant (all lanes): the contention
        # signal for the share cap — a tenant is capped only while
        # someone ELSE is waiting.
        self._tenant_queued: dict[str, int] = {}
        # Decayed admitted-units window per tenant (half-life
        # fair_window_s) the share cap compares against weights.
        self._tenant_recent: dict[str, float] = {}
        self._recent_mark = time.monotonic()
        # WFQ virtual time: per-tenant last finish tag + global floor.
        self._vt: dict[str, float] = {}
        self._vtime = 0.0
        # Dispatch-rate EWMA in units/s — the queue-drain model behind
        # predicted waits and would-miss selection.
        self._rate: float | None = None
        self._rate_mark: float | None = None

    # -- admission ------------------------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining

    def pending(self) -> int:
        """Admitted-but-unfinished request count (queued + in-flight)."""
        return sum(len(q) for q in self._queues.values()) + self._inflight

    async def submit(
        self,
        thunk: Callable[[], Awaitable],
        *,
        priority: str | None = None,
        deadline_s: float | None = None,
        cost: float | None = None,
        slo: str | None = None,
        tenant: str | None = None,
    ):
        """Admit ``thunk`` and await its terminal outcome.

        Raises :class:`DrainingError` / :class:`QueueFullError` at the
        door, :class:`DeadlineExpiredError` when the deadline passes
        (queued or in-flight), else returns/raises whatever the awaited
        thunk does.

        ``cost`` (PR 15): the request's modeled bytes
        (``ContinuousBatcher.modeled_request_cost`` — the unit
        ``load_cost`` routes on). Read only in cost-budget mode
        (``AdmissionConfig.cost_budget_bytes > 0``), where the queue
        bound, the overflow hard cap, and the shed decision all
        compare in modeled bytes; a costless submit is priced at one
        nominal slot (budget / bound) so legacy callers keep
        approximately the classic depth bound.

        ``slo`` (PR 19): SLO class name from ``AdmissionConfig.
        slo_classes`` (unknown -> ValueError -> the gateway's 400);
        None falls back to ``default_slo_class``. At a full queue the
        shed victim is the request that *will miss its SLO* — predicted
        from modeled cost ahead of it and the live dispatch rate —
        never simply the newest arrival.

        ``tenant`` (PR 19): fair-share billing key. With
        ``tenant_fair_share`` on, dispatch within a priority follows
        weighted-fair-queueing finish tags, and a tenant whose decayed
        admitted-cost share exceeds its fair weight is shed at the door
        while another tenant has queued work.
        """
        prio = priority or self.config.priorities[0]
        q = self._queues.get(prio)
        if q is None:
            raise ValueError(
                f"unknown priority {prio!r}; have {self.config.priorities}"
            )
        if self._draining:
            raise DrainingError("gateway is draining; not admitting")
        if slo is None:
            slo = self.config.default_slo_class
        slo_target = self.config.slo_target(slo)
        if slo is not None and self.config.slo_classes and slo_target is None:
            raise ValueError(
                f"unknown slo class {slo!r}; "
                f"have {sorted(self.config.slo_classes)}"
            )
        if slo_target is None:
            slo = None
        bound = self.config.bound_for(prio)
        budget = self.config.cost_budget_bytes
        factor = self.config.max_overflow_factor
        if budget > 0:
            # Cost-budget mode: bound and hard cap in ONE unit,
            # modeled bytes — a 32k-context request charges what it
            # costs, N small ones fit where one huge one would not.
            # An EMPTY queue always admits (classic mode's invariant):
            # the budget bounds the BACKLOG, never a single request's
            # size — a request whose lone modeled cost exceeds the
            # budget must not be unservable forever on an idle
            # gateway.
            if cost is None or cost <= 0:
                cost = budget / max(1, bound)
            units = cost
            queued = self._queue_cost[prio]
            over = len(q) > 0 and queued + cost > budget
            capped = len(q) > 0 and queued + cost > budget * factor
        else:
            cost = 0.0
            units = 1.0
            over = len(q) >= bound
            capped = len(q) >= bound * factor
        now = time.monotonic()
        fair = self.config.tenant_fair_share and tenant is not None
        if fair:
            self._decay_recent(now)
            if len(q) > 0 and self._tenant_over_share(tenant, units):
                # Fair-share shed: this tenant is past its weighted
                # share of the admitted-cost window while another
                # tenant waits. The overflow hook is NOT consulted —
                # preempting backend capacity cannot fix unfairness.
                self._m_shed.labels(priority=prio).inc()
                self._m_tenant_shed.labels(tenant=tenant).inc()
                self._tenant_sheds[tenant] = (
                    self._tenant_sheds.get(tenant, 0) + 1
                )
                raise QueueFullError(
                    prio, self._retry_after_hint(), tenant_over=True
                )
        if over:
            hook = self.overflow_hook
            preempted = False
            if hook is not None and not capped:
                try:
                    preempted = bool(hook())
                except Exception:  # noqa: BLE001 - hook must not 500
                    log.exception("admission overflow hook failed")
            if not preempted and not self._shed_would_miss(
                prio, q, now, slo, slo_target, units
            ):
                # Classic shed: nobody queued is predicted to miss
                # worse than the newcomer (or SLO admission is off).
                self._m_shed.labels(priority=prio).inc()
                miss = False
                if slo_target is not None:
                    est = self._est_wait(self._units_ahead(prio))
                    miss = est > slo_target
                    if miss:
                        self._count_slo_shed(slo)
                raise QueueFullError(
                    prio, self._retry_after_hint(), slo_miss=miss
                )
        if deadline_s is None:
            deadline_s = self.config.default_deadline_s
        item = _Item(
            thunk=thunk,
            priority=prio,
            deadline=(now + deadline_s) if deadline_s is not None else None,
            enqueued_at=now,
            trace=_tracing.current_trace(),
            cost=cost,
            slo_class=slo,
            slo_target=slo_target,
            tenant=tenant,
            units=units,
        )
        if slo_target is not None:
            # Predicted slack at the door: target minus the modeled
            # wait behind everything already queued at >= priority.
            headroom = slo_target - self._est_wait(self._units_ahead(prio))
            self._m_headroom.observe(headroom)
            self._headroom_sum += headroom
            self._headroom_count += 1
        if fair:
            # WFQ finish tag: service start is the later of the global
            # virtual time and the tenant's own last finish, so an idle
            # tenant re-enters at the current front instead of owing
            # phantom debt (or banking phantom credit).
            start = max(self._vtime, self._vt.get(tenant, 0.0))
            item.wfq_tag = start + units / self.config.tenant_weight(tenant)
            self._vt[tenant] = item.wfq_tag
        if tenant is not None:
            self._tenant_admitted[tenant] = (
                self._tenant_admitted.get(tenant, 0.0) + units
            )
            self._m_tenant_cost.labels(tenant=tenant).inc(units)
            self._tenant_recent[tenant] = (
                self._tenant_recent.get(tenant, 0.0) + units
            )
            self._tenant_queued[tenant] = (
                self._tenant_queued.get(tenant, 0) + 1
            )
        q.append(item)
        self._queue_cost[prio] += item.cost
        self._m_admitted.labels(priority=prio).inc()
        self._m_depth.labels(priority=prio).set(len(q))
        self._m_cost.labels(priority=prio).set(self._queue_cost[prio])
        self._idle.clear()
        self._ensure_dispatcher()
        self._work.set()
        if item.deadline is not None:
            # Wake the dispatcher at the deadline so a queued item is
            # cancelled on time, not on the next unrelated admission.
            asyncio.get_running_loop().call_later(
                deadline_s, self._work.set
            )
        return await item.future

    # -- PR 19 SLO / tenant machinery -----------------------------------

    def _est_wait(self, ahead_units: float) -> float:
        """Predicted queue wait behind ``ahead_units`` of work, from the
        dispatch-rate EWMA; falls back to the historical mean wait while
        the rate model is cold, then to zero on a fresh controller."""
        if self._rate is not None and self._rate > 1e-9:
            return ahead_units / self._rate
        h = self._m_wait
        if h.count:
            return h.sum / h.count
        return 0.0

    def _units_ahead(self, prio: str) -> float:
        """Work units queued at ``prio`` and every higher priority —
        what a new arrival at ``prio``'s tail drains behind."""
        total = 0.0
        for p in self.config.priorities:
            for it in self._queues[p]:
                total += it.units
            if p == prio:
                break
        return total

    def _count_slo_shed(self, cls: str | None) -> None:
        label = cls or "default"
        self._m_slo_shed.labels(**{"class": label}).inc()
        self._m_slo_miss.labels(**{"class": label}).inc()
        self._slo_sheds += 1
        self._slo_missed[label] = self._slo_missed.get(label, 0) + 1
        self._burn_observe(label, missed=True)

    def _burn_observe(self, cls: str, missed: bool) -> None:
        """Fold one SLO-classed outcome into the class's decayed burn
        window and refresh the gauge (PR 20). Every SLO outcome site —
        on-time dispatch, late dispatch, deadline-aware shed — lands
        here, so the gauge is the live miss fraction, not a counter
        ratio a scraper has to difference."""
        now = time.monotonic()
        dt = now - self._burn_mark
        self._burn_mark = now
        w = self.config.fair_window_s
        if dt > 0 and w > 0:
            f = 0.5 ** (dt / w)
            for b in self._burn.values():
                b[0] *= f
                b[1] *= f
        b = self._burn.setdefault(cls, [0.0, 0.0])
        b[0] += 1.0
        if missed:
            b[1] += 1.0
        self._m_burn.labels(**{"class": cls}).set(b[1] / b[0])

    def burn_rates(self) -> dict[str, float]:
        """Decayed per-class SLO miss fraction — the gauge's value,
        readable in-process (the PR-19 FleetController's tick pulls
        this instead of scraping its own gateway)."""
        return {
            cls: (b[1] / b[0] if b[0] > 0 else 0.0)
            for cls, b in self._burn.items()
        }

    def _shed_would_miss(
        self,
        prio: str,
        q: deque[_Item],
        now: float,
        slo: str | None,
        slo_target: float | None,
        units: float,
    ) -> bool:
        """Deadline-aware victim selection at a full queue: walk the
        lane computing each queued request's predicted SLO slack
        (target - waited - modeled wait for its position) and compare
        against the newcomer's. If a QUEUED request is more doomed than
        the newcomer, shed IT and admit the newcomer — returns True and
        the caller skips the classic newest-arrival shed. Requests
        without an SLO class are never victimized."""
        if not self.config.slo_classes:
            return False
        ahead = 0.0
        for p in self.config.priorities:
            if p == prio:
                break
            for it in self._queues[p]:
                ahead += it.units
        worst_idx = -1
        worst_slack = (
            slo_target - self._est_wait(self._units_ahead(prio))
            if slo_target is not None
            else float("inf")
        )
        run = ahead
        for i, it in enumerate(q):
            if it.slo_target is not None and not it.future.done():
                slack = (
                    it.slo_target
                    - (now - it.enqueued_at)
                    - self._est_wait(run)
                )
                if slack < worst_slack:
                    worst_slack = slack
                    worst_idx = i
            run += it.units
        if worst_idx < 0:
            return False
        victim = q[worst_idx]
        del q[worst_idx]
        self._release_cost(victim)
        self._m_depth.labels(priority=prio).set(len(q))
        self._m_shed.labels(priority=prio).inc()
        self._count_slo_shed(victim.slo_class)
        # The victim WAS admitted, so its terminal outcome must land in
        # the completed account like every other queue exit.
        self._m_completed.labels(priority=victim.priority).inc()
        if not victim.future.done():
            victim.future.set_exception(
                QueueFullError(
                    victim.priority,
                    self._retry_after_hint(),
                    slo_miss=True,
                )
            )
        self._maybe_idle()
        return True

    def _decay_recent(self, now: float) -> None:
        """Age the per-tenant admitted-cost window (half-life
        ``fair_window_s``) so the share cap reflects current pressure,
        not all-time history."""
        dt = now - self._recent_mark
        if dt <= 0:
            return
        self._recent_mark = now
        w = self.config.fair_window_s
        if w <= 0:
            return
        f = 0.5 ** (dt / w)
        for t in list(self._tenant_recent):
            v = self._tenant_recent[t] * f
            if v < 1e-9:
                del self._tenant_recent[t]
            else:
                self._tenant_recent[t] = v

    def _tenant_over_share(self, tenant: str, units: float) -> bool:
        """True when admitting ``units`` would push ``tenant`` past its
        weighted share of the decayed admitted-cost window while some
        OTHER tenant has queued work. With no contention the cap is
        inert — fair share is work-conserving, spare capacity flows to
        whoever offers load."""
        others = [
            t
            for t, n in self._tenant_queued.items()
            if n > 0 and t != tenant
        ]
        if not others:
            return False
        active = set(others)
        active.add(tenant)
        wsum = sum(self.config.tenant_weight(t) for t in active)
        fair = self.config.tenant_weight(tenant) / max(wsum, 1e-9)
        mine = self._tenant_recent.get(tenant, 0.0) + units
        total = (
            sum(self._tenant_recent.get(t, 0.0) for t in active) + units
        )
        share = mine / max(total, 1e-9)
        return share > fair * self.config.fair_share_slack

    def stats(self) -> dict:
        """Mirror of the PR-19 SLO/tenant counters for lockstep checks
        against the Prometheus families (same increments, same units)."""
        return {
            "slo_miss": dict(self._slo_missed),
            "slo_burn_rate": self.burn_rates(),
            "slo_sheds": self._slo_sheds,
            "slo_headroom_sum": self._headroom_sum,
            "slo_headroom_count": self._headroom_count,
            "tenant_cost_bytes": dict(self._tenant_admitted),
            "tenant_sheds": dict(self._tenant_sheds),
            "tenant_queued": {
                t: n for t, n in self._tenant_queued.items() if n
            },
        }

    def _retry_after_hint(self) -> float:
        """Shed hint: recent mean queue wait, else the configured floor."""
        h = self._m_wait
        if h.count:
            return max(self.config.retry_after_s, h.sum / h.count)
        return self.config.retry_after_s

    # -- dispatch -------------------------------------------------------

    def _ensure_dispatcher(self) -> None:
        if self._dispatcher is None or self._dispatcher.done():
            self._dispatcher = asyncio.create_task(
                self._dispatch_loop(), name="admission-dispatcher"
            )

    def _next_item(self) -> _Item | None:
        """Pop the next runnable item in strict priority order, resolving
        any already-expired queued items along the way. With tenant
        fair-share on, the pick within a priority is the smallest WFQ
        finish tag instead of FIFO — that interleaving is what bounds a
        quiet tenant's wait under another tenant's storm."""
        now = time.monotonic()
        fair = self.config.tenant_fair_share
        for prio in self.config.priorities:
            q = self._queues[prio]
            while q:
                idx = 0
                if fair and len(q) > 1:
                    for i in range(1, len(q)):
                        if q[i].wfq_tag < q[idx].wfq_tag:
                            idx = i
                item = q[idx]
                del q[idx]
                self._release_cost(item)
                self._m_depth.labels(priority=prio).set(len(q))
                if item.wfq_tag:
                    self._vtime = max(self._vtime, item.wfq_tag)
                if item.future.done():
                    # Caller gave up while queued (e.g. an aborted SSE
                    # client cancelled its submit): terminal already —
                    # don't burn backend time on a dead request.
                    self._m_completed.labels(priority=item.priority).inc()
                    self._maybe_idle()
                    continue
                if item.deadline is not None and item.deadline <= now:
                    self._expire(item)
                    continue
                return item
        return None

    def _release_cost(self, item: _Item) -> None:
        """Release a dequeued item's modeled-cost charge and its
        tenant's queued-count (every dequeue site calls this exactly
        once — the accounts mirror queue membership, nothing else)."""
        if item.cost:
            c = self._queue_cost[item.priority] = max(
                0.0, self._queue_cost[item.priority] - item.cost
            )
            self._m_cost.labels(priority=item.priority).set(c)
        if item.tenant is not None:
            n = self._tenant_queued.get(item.tenant, 0)
            if n > 1:
                self._tenant_queued[item.tenant] = n - 1
            else:
                self._tenant_queued.pop(item.tenant, None)

    def _expire(self, item: _Item) -> None:
        self._m_expired.labels(priority=item.priority).inc()
        self._m_completed.labels(priority=item.priority).inc()
        if not item.future.done():
            item.future.set_exception(
                DeadlineExpiredError(
                    f"deadline expired after "
                    f"{time.monotonic() - item.enqueued_at:.3f}s in queue"
                )
            )
        self._maybe_idle()

    def _expire_due(self) -> None:
        """Resolve every queued item whose deadline has passed. Runs on
        each dispatcher wake-up even when the in-flight window is full —
        a queued 504 must not wait for an unrelated slot to free."""
        now = time.monotonic()
        for prio in self.config.priorities:
            q = self._queues[prio]
            for _ in range(len(q)):
                item = q.popleft()
                if item.deadline is not None and item.deadline <= now:
                    self._release_cost(item)
                    self._expire(item)
                else:
                    q.append(item)
            self._m_depth.labels(priority=prio).set(len(q))

    async def _dispatch_loop(self) -> None:
        while True:
            if self._inflight >= self.config.max_inflight:
                self._expire_due()
                await self._work.wait()
                self._work.clear()
                continue
            item = self._next_item()
            if item is None:
                self._maybe_idle()
                await self._work.wait()
                self._work.clear()
                continue
            now = time.monotonic()
            wait = now - item.enqueued_at
            self._m_wait.observe(wait)
            # Dispatch-rate EWMA (units/s): the live drain model the
            # SLO headroom predictions divide by. Updated only while
            # work was actually waiting — idle gaps would read as a
            # collapsed rate.
            if self._rate_mark is not None and wait > 1e-3:
                dt = max(now - self._rate_mark, 1e-6)
                inst = item.units / dt
                self._rate = (
                    inst
                    if self._rate is None
                    else 0.2 * inst + 0.8 * self._rate
                )
            self._rate_mark = now
            if item.slo_target is not None:
                label = item.slo_class or "default"
                missed = wait > item.slo_target
                if missed:
                    # The PR-10 wait histogram is now a TARGET: a
                    # dispatch past its class budget is a recorded
                    # miss, in both the Prometheus family and the
                    # stats() mirror.
                    self._m_slo_miss.labels(**{"class": label}).inc()
                    self._slo_missed[label] = (
                        self._slo_missed.get(label, 0) + 1
                    )
                self._burn_observe(label, missed=missed)
            if item.trace is not None:
                # The admission wait, recorded at dispatch (start
                # reconstructed in the trace's clock).
                item.trace.add_span(
                    "queued",
                    time.perf_counter() - wait,
                    wait,
                    priority=item.priority,
                )
            self._inflight += 1
            self._m_inflight.set(self._inflight)
            asyncio.create_task(self._run(item))

    async def _run(self, item: _Item) -> None:
        try:
            with _tracing.use_trace(item.trace), _tracing.request_span(
                "execute", priority=item.priority
            ):
                coro = item.thunk()
                if item.deadline is not None:
                    remaining = item.deadline - time.monotonic()
                    result = await asyncio.wait_for(coro, max(remaining, 0.0))
                else:
                    result = await coro
        except (asyncio.TimeoutError, TimeoutError):
            self._m_expired.labels(priority=item.priority).inc()
            if not item.future.done():
                item.future.set_exception(
                    DeadlineExpiredError("deadline expired mid-execution")
                )
        except Exception as e:  # noqa: BLE001 - forwarded to the caller
            if not item.future.done():
                item.future.set_exception(e)
        else:
            if not item.future.done():
                item.future.set_result(result)
        finally:
            self._inflight -= 1
            self._m_inflight.set(self._inflight)
            self._m_completed.labels(priority=item.priority).inc()
            self._maybe_idle()
            self._work.set()

    def _maybe_idle(self) -> None:
        if self.pending() == 0:
            self._idle.set()

    # -- drain ----------------------------------------------------------

    def begin_drain(self) -> None:
        """Stop admitting; already-admitted work keeps running."""
        self._draining = True

    async def drain(self) -> None:
        """Stop admitting and wait until every admitted request (queued
        and in-flight) has reached its terminal outcome."""
        self.begin_drain()
        self._work.set()
        await self._idle.wait()
        if self._dispatcher is not None:
            self._dispatcher.cancel()
            try:
                await self._dispatcher
            except asyncio.CancelledError:
                pass
            self._dispatcher = None
