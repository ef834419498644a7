"""Prefix-affinity replica fleet: N batcher replicas behind one gateway.

PR 13 finished scale-UP (every serving feature engages on dp×mp
meshes); this module is the scale-OUT half (ROADMAP item 2): a
:class:`ReplicaSet` owns K :class:`~llm_consensus_tpu.serving.
continuous.ContinuousBatcher` replicas — in-process first, each with
its own page pool, prefix registry, and jit caches (optionally its own
mesh) over ONE shared parameter tree — and a :class:`PrefixRouter`
places every request where its KV already lives:

- **Prefix affinity.** The router fingerprints each request's
  page-aligned prompt-prefix chain (the same
  :func:`~llm_consensus_tpu.models.paged_cache.prefix_chain_key`
  identity the registry and host tier key by) and probes every
  replica's registry/host-tier READ-ONLY
  (:meth:`ContinuousBatcher.prefix_probe`) for the longest resident
  match. Consensus panels re-send the same huge header every
  propose/evaluate/refine round, so "requests sharing a
  radix-registry chain land where the pages already live" is the
  COMMON case — the shared header prefills once FLEET-wide, not once
  per replica. "Move the Query, Not the Cache" (PAPERS.md) is the
  routing thesis: ship the request to the KV, never the KV to the
  request.
- **Least-modeled-cost fallback.** A request with no resident chain
  anywhere goes to the replica with the least OUTSTANDING MODELED
  WORK (:meth:`ContinuousBatcher.load_cost` — the PR-10 cost model's
  KV terms integrated over every admitted request's remaining
  schedule), not the shortest request queue: a 32k-context request is
  not one unit of work.
- **Preempt-to-host-tier instead of 429s.** The ReplicaSet creates ONE
  fleet-scoped :class:`~llm_consensus_tpu.serving.offload.
  HostPageStore` (thread-safe since PR 14; keys carry each replica's
  config/weights scope) shared by every replica. Under overload the
  gateway's admission controller consults
  :meth:`ReplicaSet.preempt_for_admission` before shedding: while any
  replica still holds demotable resident chains AND the shared tier
  has headroom, the victim's lowest-priority chains demote to host
  RAM (the PR-4 eviction path, router-requested) and the request is
  ADMITTED past the queue bound — an overload storm degrades to
  restore latency, not lost work. Shedding resumes when the host tier
  is exhausted too, or when the offered traffic registers no chains
  at all (nothing to ever preempt => keep classic backpressure).
- **Rebalancing.** When the affinity owner is congested (its batcher
  queue deeper than ``FleetConfig.rebalance_waiting``) and another
  healthy replica is less loaded, the owner EXPORTS the chain's ready
  pages through the shared store (:meth:`ContinuousBatcher.
  request_export` — a spill, not an eviction: the chain stays hot at
  the owner) and the request re-homes; the destination's admission
  host-hits and restores the chain remotely.
- **Per-replica readiness.** :meth:`ReplicaSet.heartbeat` aggregates
  every replica's serving-loop heartbeat (one wedged replica flips the
  gateway's ``/readyz`` and is reported by index), and the router
  stops routing to stale/dead replicas while any healthy one remains.

Role-specialized since PR 16 (:mod:`llm_consensus_tpu.serving.disagg`):
``FleetConfig.role`` splits the fleet into prefill-heavy and
decode-heavy replicas — prefill replicas warm cold chains and hand
them through the shared store (the export path), decode replicas
restore and stream; the router routes real requests to decode-capable
replicas only. And the shared store itself may be REMOTE
(:mod:`llm_consensus_tpu.serving.remote_store`): pass
``ReplicaSet(host_store=RemotePageStore(...))`` and the same
preempt/export/restore transport crosses process and host boundaries.

Threading: ``submit``/``route`` run on caller threads (the gateway
event loop, tests); probes take each batcher's admission lock
read-only; preempt/export are enqueued REQUESTS the batcher worker
executes (device transfers must not race dispatch-time buffer
donation). The fleet itself keeps only trivially-locked counters.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass

from llm_consensus_tpu.backends import base as _backend_base
from llm_consensus_tpu.engine.tokenizer import ByteTokenizer, Tokenizer
from llm_consensus_tpu.models.configs import ModelConfig
from llm_consensus_tpu.models.paged_cache import prefix_chain_key
from llm_consensus_tpu.server.metrics import (
    REPLICA_PREEMPTIONS as _M_PREEMPTIONS,
)
from llm_consensus_tpu.server.metrics import (
    REPLICA_PREFIX_HIT_RATE as _M_HIT_RATE,
)
from llm_consensus_tpu.server.metrics import (
    REPLICA_PROGRAMS as _M_PROGRAMS,
)
from llm_consensus_tpu.server.metrics import (
    REPLICA_ROUTED as _M_ROUTED,
)
from llm_consensus_tpu.server.metrics import (
    REPLICA_SHARED_STORE_BYTES as _M_STORE_BYTES,
)
from llm_consensus_tpu.server.metrics import (
    FLEET_REPLICAS as _M_FLEET_REPLICAS,
)
from llm_consensus_tpu.server.metrics import (
    FLEET_SCALE as _M_FLEET_SCALE,
)
from llm_consensus_tpu.server.metrics import (
    ROUTER_WEIGHT as _M_ROUTER_WEIGHT,
)
from llm_consensus_tpu.serving import flight as _flight
from llm_consensus_tpu.utils import tracing as _tracing
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)
from llm_consensus_tpu.serving.offload import HostPageStore

log = logging.getLogger(__name__)

__all__ = ["FleetConfig", "PrefixRouter", "ReplicaSet", "FleetBackend"]

#: Routing reasons (the ``reason`` label of
#: ``gateway_replica_routed_total`` and the stats() mirror keys).
ROUTE_REASONS = ("prefix", "load", "rebalance", "random")


@dataclass
class FleetConfig:
    #: Batcher replicas behind the one gateway (``serve --replicas``).
    replicas: int = 2
    #: ``"prefix"`` — affinity routing (the subsystem's point).
    #: ``"random"`` — round-robin, the control tests compare with: it
    #: deliberately ignores resident chains, which isolates what
    #: affinity buys.
    policy: str = "prefix"
    #: Minimum RESIDENT full pages for an affinity claim: below it the
    #: match is noise (every prompt shares a BOS-ish page with
    #: something) and least-loaded placement wins.
    affinity_min_pages: int = 1
    #: The router stops routing to a replica whose serving-loop
    #: heartbeat is staler than this (wedged device call / dead loop)
    #: while any healthy replica remains — the same threshold shape as
    #: the gateway's ``/readyz`` probe.
    ready_stall_s: float = 10.0
    #: Rebalance trigger: when the affinity owner's batcher queue is
    #: deeper than this many requests and a less-loaded healthy
    #: replica exists, export the chain through the shared store and
    #: re-home the request. None = 4 × the batcher's ``max_slots`` —
    #: deep enough that a plain panel burst never scatters its mates.
    rebalance_waiting: int | None = None
    #: Pages demoted per router-requested preemption (one overflow
    #: moment frees about one admission's worth of pool pages).
    preempt_pages: int = 8
    #: How long an auto-rebalance waits for the owner's chain export
    #: to land in the shared store before re-homing the request. The
    #: export runs on the owner's worker at its next loop iteration
    #: (ms-scale even mid-burst); without the wait the destination's
    #: admission usually probes the store BEFORE the spill and
    #: re-prefills the whole chain. Applied ONLY off the asyncio
    #: event loop (the gateway path never blocks — its first re-homed
    #: mate goes cache-cold and the hinted mates behind it restore
    #: once the spill lands); bounded, and rebalances only fire at
    #: congestion moments. 0 = always fire-and-forget.
    rebalance_export_wait_s: float = 0.5
    #: Replica role split (PR 16, serving/disagg.py): ``"mixed"``
    #: (every replica runs both phases — the pre-PR-16 fleet),
    #: ``"prefill"``/``"decode"`` fleet-wide, or a per-replica tuple
    #: like ``("prefill", "decode")``. Prefill replicas warm cold
    #: chains and export them through the shared store; the router
    #: sends real requests to decode-capable replicas only.
    role: str | tuple = "mixed"
    #: Bound on a handoff's warm-prefill + export wait (covers the
    #: prefill replica's first-compile on a cold fleet). Applied ONLY
    #: off the asyncio event loop — on the gateway loop the handoff
    #: completes on a daemon thread instead (the same rule as
    #: rebalance_export_wait_s). 0 = always hand off asynchronously.
    handoff_wait_s: float = 60.0
    #: Streamed handoffs (PR 17): the coordinator issues the chain
    #: export as a STREAM alongside the warm-up prefill, so ready
    #: pages cross the (possibly remote) store wire while the tail is
    #: still computing. False restores the PR-16 sequential shape
    #: (prefill completes, then one whole-chain export).
    handoff_stream: bool = True
    #: Route-driven restore prefetch (PR 17): after the router picks a
    #: request's destination replica, speculatively stage the chain's
    #: host-store pages store->local on that replica (a side thread)
    #: so admission's restore plan finds them staged instead of paying
    #: a synchronous store round trip. Advisory only — a wrong or
    #: expired guess falls through to the normal get_run/recompute
    #: path (chain-keyed entries can never corrupt).
    prefetch: bool = True


class PrefixRouter:
    """Routing policy over a ReplicaSet's batchers. Stateless apart
    from a round-robin cursor; every decision re-probes live replica
    state, so evictions, restores, and retirements re-route the next
    request correctly with no cache-invalidation protocol."""

    #: Bound on the pending-route hint table (entries are tiny; the
    #: registry itself takes over once admissions land).
    RECENT_MAX = 1024
    #: Seconds a pending-route hint stays authoritative. It only needs
    #: to cover the submit→admission window of a burst; after that the
    #: owner's REGISTRY holds the chain and the live probe wins.
    RECENT_TTL_S = 30.0

    def __init__(
        self,
        batchers: list[ContinuousBatcher],
        config: FleetConfig,
        page_size: int,
        roles: list | tuple | None = None,
        states: list[str] | None = None,
    ):
        self.batchers = batchers
        self.config = config
        self.page_size = page_size
        #: Per-replica roles (PR 16): prefill-role replicas never take
        #: real requests through route() — they serve handoff warm-ups
        #: only (serving/disagg.py). None = every replica serves.
        self.roles = roles
        #: Per-replica lifecycle states (PR 19) — ALIASED with the
        #: owning ReplicaSet's list, mutated in place on elastic
        #: transitions: the router skips "draining"/"retired" replicas
        #: for NEW work while a draining replica's in-flight requests
        #: finish on its still-running loop. None = every replica
        #: permanently "serving" (the PR-14 static fleet).
        self.states = states
        #: Fleet-steered load weights (PR 19): multiplied into every
        #: load_cost comparison, so weight > 1 repels new work and
        #: weight < 1 attracts it. Missing entries weigh 1.0.
        self._weights: list[float] = []
        self._rr = 0
        self._rr_lock = threading.Lock()
        # Pending-route hints: first prefix-page run -> (replica,
        # deadline). A burst's mates route BEFORE the first request is
        # even admitted (registration happens at admission), so the
        # live registry probe alone would scatter the panel across
        # replicas; the hint pins the chain's home for the
        # submit→admission window. First-page granularity — the same
        # bucket key GroupTracker's stream planning uses.
        self._recent: dict[tuple, tuple[int, float]] = {}

    def set_weights(self, weights: list[float]) -> None:
        """Install fleet-controller load weights (PR 19). Replaces the
        whole vector; replicas past its end weigh 1.0. Each weight is
        also exported as ``gateway_router_weight{replica=}``."""
        with self._rr_lock:
            self._weights = [max(float(w), 1e-6) for w in weights]
        for i, w in enumerate(self._weights):
            _M_ROUTER_WEIGHT.labels(replica=str(i)).set(w)

    def weights(self) -> list[float]:
        """The effective weight per current replica (1.0 = neutral)."""
        with self._rr_lock:
            w = list(self._weights)
        return [
            w[i] if i < len(w) else 1.0
            for i in range(len(self.batchers))
        ]

    def _weight(self, i: int) -> float:
        with self._rr_lock:
            return self._weights[i] if i < len(self._weights) else 1.0

    def _in_service(self, i: int) -> bool:
        return self.states is None or self.states[i] == "serving"

    def healthy(self) -> list[int]:
        """In-service replicas whose serving loop is alive and fresh.
        Draining/retired replicas (PR 19) are skipped deliberately —
        the router must not hand NEW work to a replica that is
        finishing its in-flight requests on the way out. Falls back to
        ALL in-service replicas when none qualify — routing somewhere
        beats failing everywhere, and the gateway's /readyz is already
        reporting the outage."""
        out = []
        candidates = [
            i for i in range(len(self.batchers)) if self._in_service(i)
        ]
        for i in candidates:
            hb = self.batchers[i].heartbeat()
            if hb["alive"] and hb["last_tick_age_s"] <= self.config.ready_stall_s:
                out.append(i)
        return out or candidates or list(range(len(self.batchers)))

    def serving(self) -> list[int]:
        """Healthy replicas eligible for REAL requests: with roles
        active, prefill-only replicas drop out (they serve handoff
        warm-ups through the coordinator, never routed traffic). Falls
        back to every healthy replica when the filter empties — same
        route-somewhere principle as :meth:`healthy`."""
        healthy = self.healthy()
        if self.roles is None:
            return healthy
        out = [i for i in healthy if self.roles[i] != "prefill"]
        return out or healthy

    def _next_rr(self, candidates: list[int]) -> int:
        with self._rr_lock:
            idx = candidates[self._rr % len(candidates)]
            self._rr += 1
        return idx

    def _hint_get(self, chain) -> int | None:
        """Pending-route hint for this chain's first page run, if the
        hinted replica is still plausible (fresh entry, in-range)."""
        if not chain:
            return None
        with self._rr_lock:
            hit = self._recent.get(chain[0])
            if hit is None:
                return None
            idx, deadline = hit
            if time.monotonic() > deadline:
                del self._recent[chain[0]]
                return None
        return idx

    def _hint_put(self, chain, idx: int) -> None:
        if not chain:
            return
        with self._rr_lock:
            while len(self._recent) >= self.RECENT_MAX:
                self._recent.pop(next(iter(self._recent)))
            self._recent[chain[0]] = (
                idx,
                time.monotonic() + self.RECENT_TTL_S,
            )

    @staticmethod
    def _off_loop() -> bool:
        """True when NOT running on an asyncio event loop — the only
        place a blocking wait is acceptable."""
        import asyncio

        try:
            asyncio.get_running_loop()
        except RuntimeError:
            return True
        return False

    def route(self, ids, chain=None) -> tuple[int, str]:
        """Pick a replica for a request with prompt token ids ``ids``.
        Returns ``(replica index, reason)`` — reason is one of
        :data:`ROUTE_REASONS`. ``chain``: the ids' precomputed
        :func:`prefix_chain_key` (the submit path fingerprints ONCE
        and threads it through; None recomputes)."""
        c = self.config
        healthy = self.serving()
        if c.policy == "random":
            # The control policy stays deliberately chain-blind (no
            # hints either) — the A/B isolates what affinity buys.
            return self._next_rr(healthy), "random"
        if chain is None:
            chain = prefix_chain_key(ids, self.page_size)
        # Longest resident chain wins (registry pages first — they are
        # restore-free; host-tier tokens break registry ties).
        best_score = (0, 0)
        owner = None
        for i in healthy:
            p = self.batchers[i].prefix_probe(ids)
            score = (p["registry_tokens"], p["host_tokens"])
            if score > best_score:
                best_score, owner = score, i
        floor = c.affinity_min_pages * self.page_size
        if best_score[0] < floor and len(chain) >= c.affinity_min_pages:
            # No device-RESIDENT chain clears the floor (a host-tier
            # hit ties across replicas — the store is fleet-shared),
            # but a burst-mate may have been routed milliseconds ago
            # and not admitted yet — the pending-route hint is the
            # affinity signal for that window, and it also keeps a
            # post-preempt burst together so the chain restores ONCE
            # instead of once per scattered mate.
            hinted = self._hint_get(chain)
            if hinted is not None and hinted in healthy:
                owner = hinted
                best_score = (floor, 0)
        if owner is not None and best_score[0] >= floor:
            limit = c.rebalance_waiting
            if limit is None:
                limit = 4 * self.batchers[owner].config.max_slots
            if self.batchers[owner].waiting_depth() > limit:
                # The chain's owner is congested: move the chain, not
                # the cache-miss — export its ready pages through the
                # shared store (spill, not eviction) and re-home the
                # request to a healthy alternative, whose admission
                # will restore the chain remotely. If a mate already
                # moved this chain (the hint names a non-owner), FOLLOW
                # IT: burst mates must coalesce on one destination —
                # re-running min-load per mate scatters the chain onto
                # several replicas and re-exports it once per mate.
                others = [i for i in healthy if i != owner]
                if others:
                    hinted = self._hint_get(chain)
                    if hinted is not None and hinted in others:
                        return hinted, "rebalance"
                    dst = min(
                        others,
                        key=lambda i: self.batchers[i].load_cost()
                        * self._weight(i),
                    )
                    ev = self.batchers[owner].request_export(ids)
                    if c.rebalance_export_wait_s > 0 and self._off_loop():
                        # Let the spill land before the destination's
                        # admission probes the store — otherwise the
                        # re-homed request re-prefills the chain the
                        # export was about to make restorable.
                        # Bounded, and NEVER on an asyncio event loop
                        # (a synchronous wait there would freeze the
                        # whole gateway under exactly the load spike
                        # rebalancing exists to absorb) — the async
                        # path goes cache-cold for this first mate and
                        # the hinted mates behind it restore once the
                        # spill lands.
                        ev.wait(c.rebalance_export_wait_s)
                    _flight.flight_recorder().record(
                        "rebalance",
                        time.perf_counter(),
                        src=owner,
                        dst=dst,
                        chain_pages=best_score[0] // self.page_size,
                    )
                    # The chain is moving: follow-up mates land at the
                    # destination too (the hint check above).
                    self._hint_put(chain, dst)
                    return dst, "rebalance"
            self._hint_put(chain, owner)
            return owner, "prefix"
        # No affinity anywhere: least outstanding MODELED work (the
        # PR-10 cost model integrated over admitted requests), ties by
        # index for determinism. The hint makes this request's replica
        # the chain's home for burst-mates behind it.
        dst = min(
            healthy,
            key=lambda i: (
                self.batchers[i].load_cost() * self._weight(i),
                i,
            ),
        )
        self._hint_put(chain, dst)
        return dst, "load"


def _one_device_mesh(i: int):
    """A one-device mesh on this host's device ``i mod n`` (None on a
    one-device host, where there is nothing to choose). A batcher given
    it places its weights and pool there and, the mesh being of size 1,
    keeps every single-device kernel."""
    import jax

    from llm_consensus_tpu.parallel.mesh import MeshConfig, make_mesh

    devices = jax.local_devices()
    if len(devices) == 1:
        return None
    return make_mesh(MeshConfig(), devices=[devices[i % len(devices)]])


class ReplicaSet:
    """K continuous-batcher replicas + the router + the shared store.

    Construction mirrors :class:`ContinuousBatcher`: one model config
    and parameter tree (shared by every replica — jax arrays are
    immutable; a per-replica mesh re-shards without copying the
    original), one :class:`ContinuousConfig` INSTANCE all replicas
    read live (a knob flipped on it takes effect fleet-wide), and an
    optional draft model passed through to every replica. With
    ``config.host_cache_bytes > 0`` the fleet creates ONE
    :class:`HostPageStore` with that (fleet-wide) budget and hands it
    to every replica — the preempt/rebalance transport.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        tokenizer: Tokenizer | None = None,
        config: ContinuousConfig | None = None,
        fleet: FleetConfig | None = None,
        mesh=None,
        meshes: list | None = None,
        draft: tuple[ModelConfig, dict] | None = None,
        draft_map=None,
        control=None,
        host_store=None,
    ):
        from llm_consensus_tpu.serving.disagg import (
            HandoffCoordinator,
            resolve_roles,
            role_config,
        )

        self.cfg = cfg
        if isinstance(config, (list, tuple)):
            # The fleet's whole control surface — live knob flips
            # (spec_decode, decode_rounds, ragged_attention: a caller
            # and the adaptive controller flip ONE object between
            # bursts), role_config derivation, the router's shared
            # page-size/bucket view, and FleetBackend.request_cost's
            # replica-0 pricing — assumes every decode/mixed replica
            # reads the SAME ContinuousConfig instance. A per-replica
            # list would serve, silently, until the first live flip
            # reached only replica 0.
            raise ValueError(
                "ReplicaSet takes ONE shared ContinuousConfig, not "
                f"per-replica configs (got {type(config).__name__} of "
                f"{len(config)}): every decode/mixed replica aliases "
                "the same live instance so a knob flip (spec_decode, "
                "decode_rounds, ...) reaches the whole fleet at once. "
                "For heterogeneous engines, build a serving.modelset."
                "ModelSet of single-model members instead."
            )
        self.config = config or ContinuousConfig()
        self.fleet_config = fleet or FleetConfig()
        if self.fleet_config.replicas < 1:
            raise ValueError(
                f"need >= 1 replica, got {self.fleet_config.replicas}"
            )
        if self.fleet_config.policy not in ("prefix", "random"):
            raise ValueError(
                f"unknown routing policy {self.fleet_config.policy!r}"
            )
        self.tokenizer = tokenizer or ByteTokenizer()
        k = self.fleet_config.replicas
        if meshes is not None and len(meshes) != k:
            raise ValueError(
                f"meshes has {len(meshes)} entries for {k} replicas"
            )
        # Placement: an explicit mesh (or per-replica meshes) wins;
        # otherwise replica i takes local device i mod n — K replicas
        # on a four-chip host are K chips' worth of serving, not K
        # pools on device 0.
        self._replica_mesh = (
            (lambda i: mesh) if mesh is not None else _one_device_mesh
        )
        replica_meshes = (
            meshes
            if meshes is not None
            else [self._replica_mesh(i) for i in range(k)]
        )
        c = self.config
        # Roles/states are LISTS (PR 19): elastic spawn appends, and
        # the router aliases both in place — replica indices stay
        # stable for metric labels, routed counters, and hints across
        # the whole lifecycle (a retired slot is never reused).
        self.roles = list(resolve_roles(self.fleet_config.role, k))
        self.states: list[str] = ["serving"] * k
        tier_on = c.host_cache_bytes > 0 and c.share_prefix
        self.store: HostPageStore | None = None
        if host_store is not None:
            # EXTERNAL store (PR 16): typically a RemotePageStore over
            # the authoritative tier in another process — the same
            # interface, so everything below (preempt, export,
            # restore, stats) takes it transparently.
            if not tier_on:
                raise ValueError(
                    "a shared host_store needs the offload tier "
                    "engaged: host_cache_bytes > 0 and share_prefix"
                )
            self.store = host_store
        elif tier_on:
            # ONE store, fleet-wide budget: any replica restores any
            # chain (store keys carry each replica's config/weights
            # scope, so a heterogeneous fleet can never cross-restore).
            self.store = HostPageStore(c.host_cache_bytes)
        self.batchers: list[ContinuousBatcher] = []
        scope: tuple | None = None
        for i in range(k):
            # Adaptive control (PR 15): ``control`` is a ControlConfig
            # — each replica gets ITS OWN AdaptiveController (the
            # acceptance/overhead/MBU signals are per-replica streams;
            # one shared controller would average incomparable
            # workloads). None = every knob static, the pre-PR-15
            # fleet.
            ctrl = None
            if control is not None:
                from llm_consensus_tpu.serving.control import (
                    AdaptiveController,
                )

                ctrl = AdaptiveController(control)
            b = ContinuousBatcher(
                cfg,
                params,
                tokenizer=self.tokenizer,
                # Decode/mixed replicas share the fleet's live config
                # instance; a prefill replica gets role_config's copy
                # with the decode-phase machinery pinned off. None of
                # the replaced fields enter the store-key scope, so
                # roled replicas still restore each other's pages.
                config=role_config(c, self.roles[i]),
                mesh=replica_meshes[i],
                draft=draft,
                draft_map=draft_map,
                host_store=self.store,
                # Replica 0 computes the store-key scope (a walk over
                # every param leaf); its siblings share the identical
                # cfg/params, so they reuse it instead of re-walking.
                host_store_scope=scope,
                controller=ctrl,
            )
            if self.store is not None and scope is None:
                scope = b._store_scope
            self.batchers.append(b)
        # Elastic spawn materials (PR 19): references only — jax
        # arrays are immutable and a spawned replica re-shards the
        # SAME parameter tree exactly like the construction loop above.
        self._params = params
        self._draft = draft
        self._draft_map = draft_map
        self._control_cfg = control
        self._store_scope = scope
        # Shared-config audit (PR 18): role_config must hand every
        # decode/mixed replica the SAME live instance (prefill copies
        # are the one sanctioned divergence — their decode machinery is
        # pinned off and none of the replaced fields enter the store
        # scope). A drift here means a live knob flip would reach only
        # part of the fleet — fail loudly at construction, not at the
        # first flip.
        for i, b in enumerate(self.batchers):
            if self.roles[i] != "prefill" and b.config is not c:
                raise RuntimeError(
                    f"replica {i} (role {self.roles[i]!r}) holds a "
                    "private ContinuousConfig copy — the live-knob-flip "
                    "contract requires every decode/mixed replica to "
                    "alias the fleet's one shared instance"
                )
        self.router = PrefixRouter(
            self.batchers,
            self.fleet_config,
            c.page_size,
            roles=self.roles,
            states=self.states,
        )
        # Prefill→decode handoffs engage only when a prefill-role
        # replica exists AND the page transport is live (a roled fleet
        # without a store could never move the chain).
        self.handoff: HandoffCoordinator | None = None
        if "prefill" in self.roles:
            if self.store is not None:
                self.handoff = HandoffCoordinator(self)
            else:
                log.warning(
                    "prefill-role replicas configured without a page "
                    "transport (host_cache_bytes == 0 or sharing off): "
                    "no chain can ever hand off — the prefill replicas "
                    "will idle while decode replicas prefill everything"
                )
        # stats() mirrors of the routed/preempt Prometheus counters
        # (lockstep tested).
        self._lock = threading.Lock()
        self._routed = [
            {r: 0 for r in ROUTE_REASONS} for _ in range(k)
        ]
        self._preempt_requests = [0] * k
        # Elastic lifecycle mirrors of gateway_fleet_scale_total
        # (lockstep tested) + a guard serializing spawn/retire.
        self._scale = {"spawn": 0, "drain": 0, "retire": 0}
        self._scale_lock = threading.Lock()
        self._refresh_state_gauge()

    # -- serving --------------------------------------------------------

    def _route_ids(self, prompt: str):
        """The prompt's token ids AS THE BATCHER WILL SEE THEM (the
        same largest-bucket left-truncation submit applies) — routing
        on the untruncated prompt could affine on a prefix the
        admission then cuts off."""
        ids = self.tokenizer.encode(prompt)
        return ids[-self.config.seq_buckets[-1] :]

    def submit(self, prompt: str, **kw):
        """Route + submit; returns the replica batcher's Future.
        Keyword args pass through to
        :meth:`ContinuousBatcher.submit`. The prompt is tokenized
        ONCE — the FULL encoding is handed to the batcher (so its own
        over-long-prompt policy still applies: reject under
        ``truncate_prompts=False``, warn+left-truncate otherwise)
        while routing sees the truncated view the admission will
        actually serve."""
        full_ids = self.tokenizer.encode(prompt)
        ids = full_ids[-self.config.seq_buckets[-1] :]
        chain = prefix_chain_key(ids, self.config.page_size)
        if self.handoff is not None:
            # Role split (PR 16): a cold chain warms on a prefill
            # replica and lands in the shared store before (off-loop)
            # or while (on the gateway loop) the real request decodes.
            # The submit path runs under the request's trace (PR 20):
            # hand it through so the claim→export→restore window and
            # the store ops inside it attribute to THIS request.
            self.handoff.ensure_prefilled(
                prompt, ids, chain, trace=_tracing.current_trace()
            )
        idx, reason = self.router.route(ids, chain=chain)
        self._count_route(idx, reason, chain)
        if self.fleet_config.prefetch and self.store is not None:
            # Route-driven restore prefetch (PR 17): the destination
            # is known NOW, admission happens later on the replica's
            # worker — stage the chain's store pages on a side thread
            # in between so the restore plan starts from staged planes
            # (one remote round trip saved per restorable page run).
            # Non-blocking and advisory; registry-resident pages are
            # skipped by the prefetcher's own probe.
            self.batchers[idx].prefetch_chain(ids)
        return self.batchers[idx].submit(
            prompt, prompt_ids=full_ids, **kw
        )

    def submit_to(self, idx: int, prompt: str, **kw):
        """Bypass the router (tests, pinned traffic)."""
        return self.batchers[idx].submit(prompt, **kw)

    def _count_route(self, idx: int, reason: str, chain) -> None:
        _M_ROUTED.labels(replica=str(idx), reason=reason).inc()
        with self._lock:
            self._routed[idx][reason] += 1
        b = self.batchers[idx]
        _M_PROGRAMS.labels(replica=str(idx)).set(b.device_programs_total())
        _M_HIT_RATE.labels(replica=str(idx)).set(b.prefix_hit_rate())
        if self.store is not None:
            _M_STORE_BYTES.set(self.store.bytes_used)
        _flight.flight_recorder().record(
            "route",
            time.perf_counter(),
            trace_id=_tracing.trace_id_of(_tracing.current_trace()),
            replica=idx,
            reason=reason,
            chain_pages=len(chain),
        )

    # -- overload: preempt instead of shed ------------------------------

    def preempt_for_admission(self) -> bool:
        """The gateway admission controller's overflow hook: called at
        a queue-full moment, returns True to ADMIT past the bound
        instead of shedding 429.

        Preemption is possible while (a) the shared tier can absorb
        another page (a full tier would evict other requests'
        preserved work — real loss) AND (b) the fleet shows ANY
        preserved or preservable chain work: registry-resident chains
        (pinned-by-live-slots included — a transient all-pinned
        moment still admits; chains demote as slots retire) OR
        entries already in the shared store. The store clause matters
        right after a preemption: the demoted chains have LEFT the
        registries and the storm's own chains have not registered
        yet, but the preserved work is sitting in the tier — shedding
        in that window would 429 the exact storm preemption exists to
        absorb. Traffic that registers NOTHING shareable ever
        (sub-page prompts, a sharing-off fleet) populates neither
        surface and keeps the classic 429 backpressure — admitting it
        past the bound would grow the queue without bound with
        nothing to preempt. When some replica holds demotable chains
        right now, the one with the most (the victim) is asked to
        demote ``FleetConfig.preempt_pages`` of its lowest-priority
        chains, freeing device pool pages for the storm. Cheap on the
        happy path (node-count reads — no registry tree walks on the
        event loop; the demotion itself runs on the victim's worker
        thread), but it MAY briefly synchronize with an in-flight
        spill's device_get through the victim's lock — that
        synchronization is deliberate, see ORDER MATTERS below."""
        store = self.store
        if store is None:
            return False
        page_bytes = max(b.host_page_bytes for b in self.batchers)
        if store.headroom_bytes < page_bytes:
            return False
        # Victim selection by CACHED node counts (O(1) per replica),
        # not by the reclaimable-pages tree walk — this runs on the
        # gateway event loop once per overflowing submit. A victim
        # whose chains are all pinned right now makes the preempt
        # request a worker-side no-op; the pages demote as slots
        # retire either way.
        victim, pages = None, 0
        for i, b in enumerate(self.batchers):
            r = b.cached_chain_pages()
            if r > pages:
                victim, pages = i, r
        # ORDER MATTERS: the registry probe above synchronizes on each
        # batcher's lock, so while a preempt's evict+demote is
        # mid-flight this call blocks until the victim's store puts
        # have landed, and the store read BELOW sees them. Reading the
        # store first can pair a pre-demote store (empty) with a
        # post-demote registry (empty) and shed spuriously in the one
        # window preemption exists to cover (observed: 1/12 storm
        # requests 429'd under the reversed order).
        if victim is None and len(store) == 0:
            return False
        if victim is not None:
            vb = self.batchers[victim]
            grant = min(pages, self.fleet_config.preempt_pages)
            if vb.controller is not None and not vb.controller.restore_pacing_ok(
                grant, vb.host_page_bytes
            ):
                # Restore pacing (PR 15): the modeled restore debt —
                # bytes preemption demoted that the one-page-per-
                # iteration restore path has not repaid — is past its
                # cap. Demoting more chains now just thrashes the
                # tier (everything demoted is about to be restored),
                # so classic 429 backpressure resumes until the debt
                # drains. Controller-less fleets keep the PR-14
                # behavior unchanged.
                return False
            vb.request_preempt(grant)
            _M_PREEMPTIONS.labels(replica=str(victim)).inc()
            with self._lock:
                self._preempt_requests[victim] += 1
        return True

    # -- rebalance (explicit) -------------------------------------------

    def rebalance_chain(
        self, prompt: str, wait_s: float | None = 30.0
    ) -> int | None:
        """Export ``prompt``'s resident chain from its owning replica
        into the shared store (spill, not eviction), so ANY replica's
        next same-prefix admission restores it remotely. Returns the
        owner's index (None when no replica holds the chain). The
        router does this automatically under owner congestion; this is
        the explicit lever (tests, operational drain)."""
        ids = self._route_ids(prompt)
        owner, best = None, 0
        for i, b in enumerate(self.batchers):
            t = b.prefix_probe(ids)["registry_tokens"]
            if t > best:
                owner, best = i, t
        if owner is None:
            return None
        ev = self.batchers[owner].request_export(ids)
        if wait_s is not None and not ev.wait(wait_s):
            raise TimeoutError(
                f"replica {owner} did not run the chain export "
                f"within {wait_s}s"
            )
        return owner

    # -- elastic replicas (PR 19) ---------------------------------------

    def _refresh_state_gauge(self) -> None:
        for state in ("serving", "draining", "retired"):
            _M_FLEET_REPLICAS.labels(state=state).set(
                sum(1 for s in self.states if s == state)
            )

    def _note_scale(self, action: str, idx: int, **meta) -> None:
        """One transition = counter + mirror + flight event + gauge
        refresh (the PR-15 _decide discipline at fleet altitude)."""
        _M_FLEET_SCALE.labels(action=action).inc()
        with self._lock:
            self._scale[action] += 1
        self._refresh_state_gauge()
        _flight.flight_recorder().record(
            "scale", time.perf_counter(), action=action, replica=idx, **meta
        )

    def serving_indices(self) -> list[int]:
        return [i for i, s in enumerate(self.states) if s == "serving"]

    def spawn_replica(self) -> int:
        """Add one mixed-role batcher replica and put it in service.

        The new replica is built exactly like the construction loop —
        same shared ContinuousConfig instance (the live-knob-flip
        contract extends to it), same shared parameter tree, same
        shared store (reusing the cached store-key scope, no
        param-tree re-walk) — and appended so every existing replica's
        index, metric labels, and routing hints stay valid. The router
        sees it on its next ``healthy()`` probe; cold pools make it
        the least-loaded target, so new work drains toward it
        immediately. Returns the new replica's index."""
        from llm_consensus_tpu.serving.control import AdaptiveController
        from llm_consensus_tpu.serving.disagg import role_config

        with self._scale_lock:
            ctrl = (
                AdaptiveController(self._control_cfg)
                if self._control_cfg is not None
                else None
            )
            b = ContinuousBatcher(
                self.cfg,
                self._params,
                tokenizer=self.tokenizer,
                config=role_config(self.config, "mixed"),
                mesh=self._replica_mesh(len(self.batchers)),
                draft=self._draft,
                draft_map=self._draft_map,
                host_store=self.store,
                host_store_scope=self._store_scope,
                controller=ctrl,
            )
            idx = len(self.batchers)
            with self._lock:
                self._routed.append({r: 0 for r in ROUTE_REASONS})
                self._preempt_requests.append(0)
            # Append order: batcher first, then role/state — a router
            # probe between the two sees a shorter states list and
            # simply skips the newcomer for one decision.
            self.batchers.append(b)
            self.roles.append("mixed")
            self.states.append("serving")
            self._note_scale("spawn", idx)
            return idx

    def retire_replica(
        self, idx: int, wait_s: float = 60.0, poll_s: float = 0.05
    ) -> dict:
        """Drain and retire replica ``idx`` with ZERO lost requests.

        The sequence is the PR-14 rebalance discipline pointed at a
        whole replica: (1) mark ``draining`` — the router immediately
        stops handing it NEW work while its loop keeps running; (2)
        wait for its admitted requests (waiting + slotted) to finish —
        their futures resolve normally; (3) demote its resident
        registry chains to the shared HostPageStore (the preempt/evict
        path — after the drain nothing is pinned, so the chains
        re-home: any surviving replica's next same-prefix admission
        restores them at device_put latency instead of re-prefilling);
        (4) stop the loop and mark ``retired``. The slot stays in
        ``batchers`` so indices never shift.

        Raises TimeoutError if in-flight work outlives ``wait_s`` —
        the replica is left DRAINING (never killed with live work;
        call again to finish the retire)."""
        if not 0 <= idx < len(self.batchers):
            raise ValueError(f"no replica {idx}")
        if self.states[idx] not in ("serving", "draining"):
            raise ValueError(
                f"replica {idx} is {self.states[idx]}, not retirable"
            )
        if self.roles[idx] == "prefill":
            raise ValueError(
                "prefill-role replicas anchor the handoff tier; "
                "elastic retire covers decode-capable replicas only"
            )
        with self._scale_lock:
            survivors = [
                i for i in self.serving_indices() if i != idx
            ]
            if not survivors:
                raise ValueError(
                    "cannot retire the last serving replica"
                )
            b = self.batchers[idx]
            if self.states[idx] == "serving":
                self.states[idx] = "draining"
                self._note_scale(
                    "drain", idx, active=b.active_requests()
                )
            deadline = time.monotonic() + wait_s
            while b.active_requests() > 0:
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"replica {idx} still has "
                        f"{b.active_requests()} in-flight requests "
                        f"after {wait_s}s; left draining"
                    )
                time.sleep(poll_s)
            # Chains re-home through the shared store: demote every
            # reclaimable registry page (nothing is pinned post-drain)
            # so survivors restore instead of re-prefilling.
            demoted = 0
            if self.store is not None:
                pages = b.cached_chain_pages()
                if pages:
                    b.request_preempt(pages)
                    while (
                        b.cached_chain_pages() > 0
                        and time.monotonic() <= deadline
                    ):
                        time.sleep(poll_s)
                    demoted = pages - b.cached_chain_pages()
            b.close()
            self.states[idx] = "retired"
            self._note_scale("retire", idx, demoted_pages=demoted)
            return {
                "replica": idx,
                "demoted_pages": demoted,
                "serving": len(self.serving_indices()),
            }

    # -- observability / lifecycle --------------------------------------

    def prefix_probe(self, ids) -> dict:
        """The fleet's best resident-chain view for these token ids —
        the max over every replica's read-only
        :meth:`ContinuousBatcher.prefix_probe` (registry pages first,
        host-tier extension breaks ties: the router's own comparison).
        The ``/debug/chains`` probe surface a front gateway's
        peer-routing reads (PR 16)."""
        best = (0, 0)
        for b in self.batchers:
            p = b.prefix_probe(ids)
            best = max(best, (p["registry_tokens"], p["host_tokens"]))
        # One scope for the whole answer (PR 18): the fleet is
        # homogeneous by the shared-config contract, so replica 0's
        # model/weights identity names every chain counted above.
        return {
            "registry_tokens": best[0],
            "host_tokens": best[1],
            "scope": self.batchers[0].chain_scope(),
        }

    def heartbeat(self) -> dict:
        """Aggregate serving-loop liveness: ``alive`` only when EVERY
        in-service replica's loop is alive (a degraded fleet must flip
        /readyz — one wedged replica is a capacity loss the balancer
        upstream should see), ``last_tick_age_s`` is the stalest such
        replica's, and ``replicas`` carries each loop's own heartbeat
        so the gateway can name the wedged index. Draining/retired
        replicas (PR 19) report their lifecycle state in their entry
        but are EXCLUDED from the aggregate — a deliberate drain or a
        stopped retired loop is not an outage."""
        hbs = [b.heartbeat() for b in self.batchers]
        for h, s in zip(hbs, self.states):
            if s != "serving":
                h["state"] = s
        act = [
            h for h, s in zip(hbs, self.states) if s == "serving"
        ] or hbs
        return {
            "alive": all(h["alive"] for h in act),
            "last_tick_age_s": max(h["last_tick_age_s"] for h in act),
            "last_step_age_s": max(
                (
                    h["last_step_age_s"]
                    for h in act
                    if h["last_step_age_s"] is not None
                ),
                default=None,
            ),
            "replicas": hbs,
        }

    def stats(self) -> dict:
        """Fleet snapshot: per-replica batcher stats plus aggregates.
        Shared-store counters are taken from the STORE once — each
        replica's own ``offload_demoted/dropped/host_bytes`` keys read
        the same shared store, so summing them would multiply-count.
        Pulling stats also refreshes the per-replica gauges
        (``gateway_replica_programs`` / ``_prefix_hit_rate`` /
        ``_shared_store_bytes``), so a scrape following a stats pull
        is current."""
        per = [b.stats() for b in self.batchers]
        for i, role in enumerate(self.roles):
            # The per-ROLE split of the process-global (last-writer-
            # wins) autotune families: each replica's stats carry its
            # role, the PR-14/15 per-replica convention.
            per[i]["role"] = role
            per[i]["state"] = self.states[i]
        for i, b in enumerate(self.batchers):
            # The same accessors the route-time refresh uses — ONE
            # definition of each gauge's value (a second copy keyed on
            # the program-kind list would drift the moment a kind is
            # added).
            _M_PROGRAMS.labels(replica=str(i)).set(
                b.device_programs_total()
            )
            _M_HIT_RATE.labels(replica=str(i)).set(b.prefix_hit_rate())
        if self.store is not None:
            _M_STORE_BYTES.set(self.store.bytes_used)
        with self._lock:
            routed = [dict(r) for r in self._routed]
            preempts = list(self._preempt_requests)
            scale = dict(self._scale)
        agg_lookups = sum(s["prefix_lookups"] for s in per)
        return {
            "replicas": len(self.batchers),
            "serving_replicas": len(self.serving_indices()),
            "states": list(self.states),
            "router_weights": self.router.weights(),
            "scale_events": scale,
            "policy": self.fleet_config.policy,
            "roles": list(self.roles),
            "role_handoffs": (
                self.handoff.handoffs if self.handoff is not None else 0
            ),
            # Claim-to-exported handoff latency (PR 17) — the stats()
            # mirror of gateway_handoff_seconds (lockstep tested).
            "handoff_seconds_sum": (
                self.handoff.handoff_seconds_sum
                if self.handoff is not None
                else 0.0
            ),
            "handoff_seconds_count": (
                self.handoff.handoff_seconds_count
                if self.handoff is not None
                else 0
            ),
            "per_replica": per,
            "routed": routed,
            "routed_total": sum(sum(r.values()) for r in routed),
            "routed_prefix": sum(r["prefix"] for r in routed),
            "preempt_requests": preempts,
            "completed_requests": sum(
                s["completed_requests"] for s in per
            ),
            "generated_tokens": sum(s["generated_tokens"] for s in per),
            "prefill_chunks": sum(s["prefill_chunks"] for s in per),
            "prefix_lookups": agg_lookups,
            "prefix_hits": sum(s["prefix_hits"] for s in per),
            "prefix_hit_rate": (
                sum(s["prefix_hits"] for s in per) / max(1, agg_lookups)
            ),
            "prefix_pages_shared": sum(
                s["prefix_pages_shared"] for s in per
            ),
            "preempted_pages": sum(s["preempted_pages"] for s in per),
            "exported_pages": sum(s["exported_pages"] for s in per),
            "offload_restored_pages": sum(
                s["offload_restored_pages"] for s in per
            ),
            "offload_demoted_pages": (
                self.store.demoted_pages if self.store else 0
            ),
            "offload_dropped_pages": (
                self.store.dropped_pages if self.store else 0
            ),
            "shared_store_bytes": (
                self.store.bytes_used if self.store else 0
            ),
            "shared_store_pages": len(self.store) if self.store else 0,
        }

    def close(self) -> None:
        for b, s in zip(self.batchers, self.states):
            if s != "retired":  # retired loops already stopped
                b.close()


class FleetBackend(_backend_base.Backend):
    """Backend seam over a :class:`ReplicaSet` — the fleet counterpart
    of :class:`~llm_consensus_tpu.serving.continuous.
    ContinuousBackend`. The Coordinator's panel fan-out submits each
    member through the router, so panel mates affine to the replica
    whose registry holds their shared header; ``health()`` exposes the
    aggregate heartbeat (per-replica entries included) for the
    gateway's /readyz, and ``preempt_for_admission`` is the overflow
    hook the gateway wires into its admission controller."""

    def __init__(self, replicas: ReplicaSet):
        self.replicas = replicas

    async def generate_batch(self, requests):
        import asyncio

        BackendError = _backend_base.BackendError
        GenerationResult = _backend_base.GenerationResult

        futs = []
        try:
            for r in requests:
                futs.append(
                    self.replicas.submit(
                        r.prompt,
                        max_new_tokens=r.params.max_new_tokens,
                        temperature=r.params.temperature,
                        seed=r.params.seed,
                        top_k=r.params.top_k,
                        top_p=r.params.top_p,
                        stop=r.params.stop,
                    )
                )
        except (RuntimeError, ValueError) as e:
            # Mirror ContinuousBackend: a mid-batch submit failure must
            # not orphan earlier members' device work silently.
            for f in futs:
                f.cancel()
            raise BackendError(f"fleet submit failed: {e}") from e
        outs = await asyncio.gather(*(asyncio.wrap_future(f) for f in futs))
        return [
            GenerationResult(
                text=o.text, num_tokens=o.num_tokens, meta=o.timing
            )
            for o in outs
        ]

    def health(self) -> dict:
        return self.replicas.heartbeat()

    @property
    def tokenizer(self):
        """The fleet tokenizer — the gateway's ``/debug/chains``
        handler encodes ``?prompt=`` probes with it."""
        return self.replicas.tokenizer

    def prefix_probe(self, ids) -> dict:
        """``/debug/chains`` probe surface: the fleet-wide best
        resident-chain view (PR 16)."""
        return self.replicas.prefix_probe(ids)

    def request_cost(self, prompt: str, max_new_tokens: int) -> float:
        """Modeled bytes for the gateway's cost-budget admission
        (PR 15) — replica 0's pricing: the fleet is homogeneous in
        config terms (one shared ContinuousConfig), so any replica's
        modeled_request_cost is THE fleet price."""
        b = self.replicas.batchers[0]
        return b.modeled_request_cost(
            len(self.replicas.tokenizer.encode(prompt)), max_new_tokens
        )

    def preempt_for_admission(self) -> bool:
        return self.replicas.preempt_for_admission()

    async def close(self) -> None:
        self.replicas.close()
