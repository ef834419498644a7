"""Fleet control plane: telemetry -> fleet-level decisions (PR 19).

Everything the repo learned to measure and tune so far stops at the
replica boundary: the PR-15 :class:`~llm_consensus_tpu.serving.control.
AdaptiveController` closes its loop per replica, the PR-10 TTFT/TBT
histograms are telemetry-only, and the PR-14 :class:`~llm_consensus_tpu.
serving.fleet.PrefixRouter` never sees autotune/MBU/queue-cost signals.
This module is the layer above all of it — ONE controller per
:class:`~llm_consensus_tpu.serving.fleet.ReplicaSet` that turns the
existing per-replica telemetry into four coupled fleet-level decisions:

- **SLO-aware admission** (configured here, enforced in
  :mod:`llm_consensus_tpu.server.admission`): requests carry an
  optional SLO class (``/v1/generate`` ``"slo":`` field); admission
  predicts each request's queue wait from modeled cost ahead of it and
  the live dispatch rate, and at a full queue sheds the request that
  *will miss its SLO* — never simply the newest.
  :meth:`FleetControlConfig.admission_kwargs` is the one bridge: the
  CLI splats it into :class:`~llm_consensus_tpu.server.admission.
  AdmissionConfig` so the gateway and the fleet agree on classes.
- **Tenant fair-share** (same split): weighted fair queueing across
  the ``"tenant"`` payload field plus an admitted-cost share cap under
  contention, in the same modeled-byte unit as PR-15 cost-budget
  admission — one tenant's storm cannot starve panel traffic.
- **Router weight steering**: each tick folds per-replica modeled
  queue cost into :meth:`PrefixRouter.set_weights` load weights (a
  loaded replica's cost is inflated, repelling new work), and sizes
  two previously-static knobs from the same signals — the shared-
  prefix group-formation cap (``GroupTracker.max_groups``, via the
  worker-applied :meth:`ContinuousBatcher.request_group_cap`) and the
  host-tier restore-batch ceiling (:meth:`AdaptiveController.
  steer_restore_cap`).
- **Elastic replicas**: spawn batcher replicas against sustained
  queue-depth demand and retire them when the fleet idles, draining
  the retiring replica through the shared HostPageStore exactly like
  PR-14 rebalancing — zero lost requests, chains re-homed
  (:meth:`ReplicaSet.spawn_replica` / :meth:`ReplicaSet.
  retire_replica` do the mechanics; this controller decides WHEN).

Decision discipline mirrors PR-15 autotune: gauges refresh every tick,
``gateway_fleet_decisions_total{decision=}`` moves only when a
setpoint CHANGES, and every change lands a ``fleet`` flight-recorder
event — so a decision storm is visible as a counter slope and
replayable from the ring. All stats() mirrors are lockstep with the
Prometheus families (tested).

Threading: one daemon tick thread per controller (``interval_s``
cadence). Every signal read is a cheap lock-guarded accessor
(waiting_depth / load_cost / active_requests / restore_debt_bytes);
every actuation is either an enqueued worker request (group cap,
preempt) or a trivially-locked setter (router weights, restore cap) —
the tick thread never touches device state. Elastic retire blocks the
tick thread through the drain (bounded by ``retire_wait_s``); routing
and serving continue on their own threads throughout.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field

from llm_consensus_tpu.server.metrics import (
    FLEET_DECISIONS as _M_DECISIONS,
)
from llm_consensus_tpu.serving import flight as _flight

log = logging.getLogger(__name__)

__all__ = ["FleetControlConfig", "FleetController", "DECISIONS"]

#: Decision kinds (the ``decision`` label of
#: ``gateway_fleet_decisions_total`` and the stats() mirror keys).
DECISIONS = ("router_weights", "group_cap", "restore_cap", "spawn", "retire")


@dataclass
class FleetControlConfig:
    #: Tick cadence of the control thread.
    interval_s: float = 0.5

    # -- SLO classes (enforced by server/admission.py) ------------------
    #: Class name -> queue-wait target seconds. The defaults give
    #: interactive traffic a tight TTFT budget and batch traffic a
    #: loose one; ``serve --slo-target class=seconds`` overrides.
    slo_classes: dict = field(
        default_factory=lambda: {"interactive": 2.0, "batch": 30.0}
    )
    #: Class applied to requests without an ``"slo"`` payload field;
    #: None = untagged requests stay SLO-blind.
    default_slo_class: str | None = "interactive"

    # -- tenant fair-share (enforced by server/admission.py) ------------
    #: Weighted fair queueing across the ``"tenant"`` payload field.
    fair_share: bool = True
    #: Tenant -> weight (absent tenants weigh 1.0 — equal shares).
    tenant_weights: dict = field(default_factory=dict)
    #: Shed a tenant only past fair_weight * slack (the ±10% band).
    fair_share_slack: float = 1.1
    #: Half-life of the decayed admitted-cost window the cap reads.
    fair_window_s: float = 30.0

    # -- router weight steering -----------------------------------------
    steer_router: bool = True
    #: Weight clamp: a replica's weight is its modeled load relative
    #: to the fleet mean, bounded to keep one hot replica from being
    #: starved forever (it must keep receiving SOME work to drain).
    weight_min: float = 0.25
    weight_max: float = 4.0

    # -- group-formation / restore-batch sizing -------------------------
    steer_sizing: bool = True
    #: Fleet queue pressure = total waiting / (serving x max_slots).
    #: Above ``pressure_high`` the group cap widens to max_slots (batch
    #: every shareable group per dispatch) and restore batches narrow
    #: (bound the stall injected into saturated decode lanes); below
    #: ``pressure_low`` both return to their defaults. The gap is
    #: hysteresis — each group-cap change re-traces the grouped decode
    #: program, so flapping would thrash the jit cache.
    pressure_high: float = 1.0
    pressure_low: float = 0.25
    #: Restore-debt fraction (fleet debt / host-tier budget) above
    #: which any narrowed restore cap is cleared — repaying demoted
    #: chains takes priority over stall bounding.
    restore_debt_high: float = 0.25
    restore_debt_low: float = 0.05
    #: The narrowed restore-batch ceiling under queue pressure.
    restore_cap_narrow: int = 2

    # -- elastic replicas -----------------------------------------------
    #: Replica-count band. ``elastic_max = 0`` disables elastic
    #: scaling entirely (the controller still steers weights/sizing).
    elastic_min: int = 1
    elastic_max: int = 0
    #: Spawn once mean waiting depth per serving replica has sat at or
    #: above this for ``spawn_sustain_ticks`` consecutive ticks — a
    #: single burst must not spawn a replica it will not need.
    spawn_depth: float = 2.0
    spawn_sustain_ticks: int = 3
    #: Retire (down to elastic_min) after this many consecutive ticks
    #: with zero waiting AND zero active requests fleet-wide.
    retire_idle_ticks: int = 20
    #: Drain bound handed to ReplicaSet.retire_replica.
    retire_wait_s: float = 60.0
    #: SLO burn-rate spawn pressure (PR 20): when an attached
    #: admission controller reports any class's decayed miss fraction
    #: (``gateway_slo_burn_rate{class=}``) at or above this, the tick
    #: counts as spawn pressure even if queue depth looks calm —
    #: misses can burn while depth oscillates under the spawn_depth
    #: threshold. 1.0 < never (burn is a fraction).
    burn_spawn_threshold: float = 0.5

    def admission_kwargs(self) -> dict:
        """The AdmissionConfig field overrides this fleet config
        implies — the ONE bridge between ``serve --fleet-control`` and
        the gateway's admission controller, so SLO classes and tenant
        weights cannot drift between the two layers."""
        return {
            "slo_classes": dict(self.slo_classes),
            "default_slo_class": self.default_slo_class,
            "tenant_fair_share": self.fair_share,
            "tenant_weights": dict(self.tenant_weights),
            "fair_share_slack": self.fair_share_slack,
            "fair_window_s": self.fair_window_s,
        }


class FleetController:
    """Fleet-scoped decision loop over one :class:`ReplicaSet`."""

    def __init__(self, replicas, config: FleetControlConfig | None = None):
        self.replicas = replicas
        self.config = config or FleetControlConfig()
        if self.config.elastic_max:
            if self.config.elastic_min < 1:
                raise ValueError("elastic_min must be >= 1")
            if self.config.elastic_max < self.config.elastic_min:
                raise ValueError(
                    "elastic_max must be >= elastic_min "
                    f"({self.config.elastic_max} < "
                    f"{self.config.elastic_min})"
                )
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._lock = threading.Lock()
        self._decisions = {d: 0 for d in DECISIONS}
        self._ticks = 0
        self._last_weights: list[float] | None = None
        self._group_cap: int | None = None
        self._restore_cap: int | None = None
        self._spawn_streak = 0
        self._idle_streak = 0
        #: The gateway admission controller this fleet serves behind
        #: (PR 20): attached by the CLI after the gateway is built, it
        #: feeds the per-class SLO burn rates into elastic decisions.
        self.admission = None
        # Discoverability: stats surfaces reach the controller
        # through the fleet they already hold.
        replicas.fleet_controller = self

    def attach_admission(self, admission) -> None:
        """Wire the gateway's admission controller in (PR 20) so each
        tick can read its decayed per-class SLO burn rates
        (:meth:`~llm_consensus_tpu.server.admission.
        AdmissionController.burn_rates`, the
        ``gateway_slo_burn_rate{class=}`` mirror) as spawn pressure."""
        self.admission = admission

    def burn_rates(self) -> dict:
        """Per-class decayed SLO miss fractions from the attached
        admission controller; empty when none is attached (the
        pre-PR-20 shape — every decision then falls back to
        depth-only signals)."""
        adm = self.admission
        if adm is None:
            return {}
        try:
            return dict(adm.burn_rates())
        except Exception:  # noqa: BLE001 - telemetry must not kill ticks
            log.exception("burn-rate read failed")
            return {}

    # -- lifecycle ------------------------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, name="fleet-control", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None

    def _run(self) -> None:
        while not self._stop.wait(self.config.interval_s):
            try:
                self.tick()
            except Exception:  # noqa: BLE001 - the loop must survive
                log.exception("fleet control tick failed")

    # -- decision recording ---------------------------------------------

    def _decide(self, decision: str, **meta) -> None:
        """One setpoint CHANGE = counter + mirror + flight event (the
        PR-15 autotune discipline at fleet altitude). Steady-state
        ticks touch gauges only."""
        _M_DECISIONS.labels(decision=decision).inc()
        with self._lock:
            self._decisions[decision] += 1
        _flight.flight_recorder().record(
            "fleet", time.perf_counter(), decision=decision, **meta
        )

    # -- the loop body (public: tests tick synchronously) ---------------

    def tick(self) -> None:
        cfg = self.config
        rs = self.replicas
        serving = rs.serving_indices()
        if not serving:
            return
        with self._lock:
            self._ticks += 1
        bs = [rs.batchers[i] for i in serving]
        depths = [b.waiting_depth() for b in bs]
        actives = [b.active_requests() for b in bs]
        loads = [b.load_cost() for b in bs]
        max_slots = rs.config.max_slots

        if cfg.steer_router:
            self._steer_weights(rs, serving, loads)
        if cfg.steer_sizing:
            pressure = sum(depths) / max(1, len(serving) * max_slots)
            self._steer_group_cap(bs, max_slots, pressure)
            self._steer_restore_cap(rs, bs, pressure)
        if cfg.elastic_max > 0:
            self._steer_elastic(
                rs, serving, depths, actives, self.burn_rates()
            )

    def _steer_weights(self, rs, serving, loads) -> None:
        cfg = self.config
        mean = sum(loads) / len(loads)
        weights = [1.0] * len(rs.batchers)
        if mean > 0:
            for i, cost in zip(serving, loads):
                w = min(max(cost / mean, cfg.weight_min), cfg.weight_max)
                weights[i] = round(w, 3)
        # Gauges refresh every tick (set_weights exports them); the
        # decision counter moves only when the vector changes.
        rs.router.set_weights(weights)
        if weights != self._last_weights:
            self._last_weights = list(weights)
            self._decide("router_weights", weights=tuple(weights))

    def _steer_group_cap(self, bs, max_slots: int, pressure: float) -> None:
        cfg = self.config
        target = self._group_cap
        if pressure >= cfg.pressure_high:
            # Saturated admission queues: widen grouping so every
            # shareable prefix group batches into one dispatch.
            target = max_slots
        elif pressure <= cfg.pressure_low:
            # The GroupTracker construction default.
            target = max(1, max_slots // 2)
        if target is not None and target != self._group_cap:
            for b in bs:
                b.request_group_cap(target)
            self._group_cap = target
            self._decide(
                "group_cap", cap=target, pressure=round(pressure, 3)
            )

    def _steer_restore_cap(self, rs, bs, pressure: float) -> None:
        cfg = self.config
        budget = rs.config.host_cache_bytes
        if rs.store is None or budget <= 0:
            return
        debt = sum(
            b.controller.restore_debt_bytes
            for b in bs
            if b.controller is not None
        )
        frac = debt / budget
        want = self._restore_cap
        if frac >= cfg.restore_debt_high:
            # Heavy restore debt: clear any narrowing — repaying the
            # demoted chains beats bounding per-iteration stalls.
            want = None
        elif pressure >= cfg.pressure_high and frac <= cfg.restore_debt_low:
            # Busy queues, little debt: narrow restore batches so the
            # host tier's promotions inject bounded stalls into the
            # saturated decode lanes.
            want = cfg.restore_cap_narrow
        elif pressure <= cfg.pressure_low:
            want = None
        if want != self._restore_cap:
            for b in bs:
                if b.controller is not None:
                    b.controller.steer_restore_cap(want)
            self._restore_cap = want
            self._decide(
                "restore_cap",
                cap=want if want is not None else -1,
                debt_frac=round(frac, 3),
            )

    def _steer_elastic(self, rs, serving, depths, actives, burn) -> None:
        cfg = self.config
        mean_depth = sum(depths) / len(serving)
        # Burn-rate pressure (PR 20): a class burning SLO misses is
        # demand the depth signal can miss (depth oscillates under
        # spawn_depth while would-miss sheds keep it artificially
        # low) — count it toward the same sustain streak.
        burning = (
            max(burn.values(), default=0.0) >= cfg.burn_spawn_threshold
        )
        if (
            mean_depth >= cfg.spawn_depth or burning
        ) and len(serving) < cfg.elastic_max:
            self._spawn_streak += 1
            if self._spawn_streak >= cfg.spawn_sustain_ticks:
                self._spawn_streak = 0
                idx = rs.spawn_replica()
                self._decide(
                    "spawn",
                    replica=idx,
                    mean_depth=round(mean_depth, 2),
                    burning=burning,
                )
        else:
            self._spawn_streak = 0
        if (
            sum(depths) + sum(actives) == 0
            and len(serving) > cfg.elastic_min
        ):
            self._idle_streak += 1
            if self._idle_streak >= cfg.retire_idle_ticks:
                self._idle_streak = 0
                victims = [
                    i for i in serving if rs.roles[i] != "prefill"
                ]
                if len(victims) > 0 and len(serving) > cfg.elastic_min:
                    victim = max(victims)
                    try:
                        rs.retire_replica(
                            victim, wait_s=cfg.retire_wait_s
                        )
                    except (TimeoutError, ValueError) as e:
                        log.warning(
                            "elastic retire of replica %d skipped: %s",
                            victim,
                            e,
                        )
                        return
                    self._decide("retire", replica=victim)
        else:
            self._idle_streak = 0

    # -- observability --------------------------------------------------

    def stats(self) -> dict:
        """Mirror of gateway_fleet_decisions_total plus the current
        setpoints (lockstep tested)."""
        with self._lock:
            out = {
                f"fleet_decisions_{d}": self._decisions[d]
                for d in DECISIONS
            }
            out["fleet_ticks"] = self._ticks
        out["fleet_router_weights"] = (
            list(self._last_weights) if self._last_weights else []
        )
        out["fleet_group_cap"] = (
            self._group_cap if self._group_cap is not None else -1
        )
        out["fleet_restore_cap"] = (
            self._restore_cap if self._restore_cap is not None else -1
        )
        out["fleet_burn_rate"] = self.burn_rates()
        return out
