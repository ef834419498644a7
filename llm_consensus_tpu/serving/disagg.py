"""Disaggregated prefill/decode serving: role-specialized replicas
(PR 16, with :mod:`llm_consensus_tpu.serving.remote_store`).

TPLA (PAPERS.md) argues prefill and decode sit at different roofline
points and want different shardings; "Move the Query, Not the Cache"
supplies the placement rule. The repo already has every seam this
needs — the fleet's shared page store with scoped chain keys (PR 14),
the export/restore transport, the PrefixRouter, per-replica
controllers (PR 15). This module adds the ROLE split on top:

- ``FleetConfig(role=...)`` — ``"mixed"`` (the pre-PR-16 fleet),
  ``"prefill"``/``"decode"`` fleet-wide, or a per-replica tuple like
  ``("prefill", "decode")``.
- **Prefill replicas** run admission + chunked prefill only:
  :func:`role_config` pins ``spec_decode=False`` and
  ``decode_rounds=1`` (speculation and R-round windows are decode-
  phase machinery — a replica that hands chains off right after the
  header lands never amortizes them), while chunk width and mesh
  shape stay per-replica levers (``--prefill-chunk``,
  ``meshes=`` — an mp-heavy mesh suits the prefill roofline, a
  dp-heavy one suits decode; the PR-15 controller then tunes each
  replica toward ITS role's roofline instead of compromise settings).
- **Decode replicas** keep the fleet's shared live config (spec +
  R-round windows) and stream tokens; the router routes real requests
  to decode-capable replicas ONLY — decode phase by prefix affinity,
  the prefill phase by load (the least-loaded prefill replica takes
  each warm-up).
- :class:`HandoffCoordinator` is the seam between them: the first
  request of a cold chain triggers a WARM request (``max_new_tokens=1``)
  on a prefill replica, then exports the finished chain through the
  fleet page store via the PR-14 export path; the decode replica's
  admission host-hits and restores the header bit-identically, so the
  panel's text is byte-identical to a mixed-role fleet (the PR-4
  restore contract) with ZERO header pages re-prefilled on the decode
  side. Each completed handoff counts ``gateway_role_handoffs_total``
  and records a ``handoff`` flight event.

Blocking discipline (the fleet's standing rule): the coordinator
waits for the warm prefill + export ONLY off the asyncio event loop
(test threads). On the gateway loop the handoff runs on a
daemon thread — the triggering request itself goes cache-cold on its
decode replica (correct, just not accelerated) and the panel mates
behind it restore once the export lands, exactly the
``rebalance_export_wait_s`` trade.

Cross-PROCESS disaggregation is this plus
``ReplicaSet(host_store=RemotePageStore(...))``: the store the
export lands in and the decode admission restores from is then the
remote authoritative tier, and the handoff crosses process (or host)
boundaries without any code here changing.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import replace

from llm_consensus_tpu.server.metrics import (
    HANDOFF_SECONDS as _M_HANDOFF_SECONDS,
)
from llm_consensus_tpu.server.metrics import ROLE_HANDOFFS as _M_HANDOFFS
from llm_consensus_tpu.serving import flight as _flight
from llm_consensus_tpu.serving.continuous import ContinuousConfig
from llm_consensus_tpu.utils import tracing as _tracing

log = logging.getLogger(__name__)

__all__ = ["ROLES", "resolve_roles", "role_config", "HandoffCoordinator"]

#: Valid replica roles (the ``role`` entry in fleet stats()
#: ``per_replica`` — the per-ROLE split of the process-global,
#: last-writer-wins autotune families).
ROLES = ("prefill", "decode", "mixed")


def resolve_roles(role, k: int) -> tuple[str, ...]:
    """``FleetConfig.role`` -> one role per replica. A string applies
    fleet-wide; a tuple/list names each replica's role. At least one
    replica must be decode-capable (``decode`` or ``mixed``) — a
    prefill-only fleet could never stream a token."""
    roles = (role,) * k if isinstance(role, str) else tuple(role)
    if len(roles) != k:
        raise ValueError(
            f"role tuple has {len(roles)} entries for {k} replicas"
        )
    for r in roles:
        if r not in ROLES:
            raise ValueError(f"unknown replica role {r!r} (use {ROLES})")
    if all(r == "prefill" for r in roles):
        raise ValueError(
            "at least one replica must be decode-capable "
            "('decode' or 'mixed'): a prefill-only fleet cannot "
            "stream tokens"
        )
    return roles


def role_config(config: ContinuousConfig, role: str) -> ContinuousConfig:
    """The replica's effective config for ``role``. Decode/mixed
    replicas SHARE the fleet's live config instance (the knob-flip
    lever stays fleet-wide); a prefill replica gets its own copy with
    the decode-phase machinery off. None of the replaced fields enter
    the PR-14 store-key scope (config dims + page size + pool dtype +
    weights fingerprint), so roled replicas restore each other's pages
    by construction."""
    if role != "prefill":
        return config
    return replace(config, spec_decode=False, decode_rounds=1)


class HandoffCoordinator:
    """Prefill→decode chain handoffs for one roled :class:`ReplicaSet`.

    ``ensure_prefilled`` is consulted on the fleet submit path for
    every request whose prompt has at least one full header page: a
    chain that is already resident on a decode-capable replica (or
    already restorable from the fleet store) passes through untouched;
    a COLD chain is warmed on the least-loaded prefill replica and
    exported into the store first. A bounded-TTL dedup table keyed by
    the chain's first page run (the pending-route-hint convention)
    keeps a panel burst from warming the same header once per mate.
    """

    #: Dedup entries expire after this long — past it the chain is
    #: either registry-resident on its decode home (the probe short-
    #: circuits) or evicted everywhere and worth re-warming.
    DEDUP_TTL_S = 60.0
    DEDUP_MAX = 1024

    def __init__(self, fleet):
        self.fleet = fleet  # ReplicaSet (import cycle: duck-typed)
        self._lock = threading.Lock()
        self._seen: dict[tuple, float] = {}
        #: Completed handoffs (stats() mirror of
        #: ``gateway_role_handoffs_total``'s increments from this
        #: fleet; the Prometheus family is process-global), plus the
        #: claim-to-exported latency mirror of
        #: ``gateway_handoff_seconds`` (PR 17, lockstep tested).
        self.handoffs = 0
        self.handoff_seconds_sum = 0.0
        self.handoff_seconds_count = 0

    def _prefill_candidates(self) -> list[int]:
        healthy = set(self.fleet.router.healthy())
        return [
            i
            for i, r in enumerate(self.fleet.roles)
            if r == "prefill" and i in healthy
        ]

    def _decode_candidates(self) -> list[int]:
        return [
            i
            for i, r in enumerate(self.fleet.roles)
            if r != "prefill"
        ]

    def _dedup_claim(self, chain) -> bool:
        """True when THIS caller claims the chain (first mate of the
        burst); False when a fresh claim already exists."""
        now = time.monotonic()
        key = chain[0]
        with self._lock:
            dl = self._seen.get(key)
            if dl is not None and now < dl:
                return False
            while len(self._seen) >= self.DEDUP_MAX:
                self._seen.pop(next(iter(self._seen)))
            self._seen[key] = now + self.DEDUP_TTL_S
            return True

    @staticmethod
    def _off_loop() -> bool:
        import asyncio

        try:
            asyncio.get_running_loop()
        except RuntimeError:
            return True
        return False

    def ensure_prefilled(self, prompt: str, ids, chain, trace=None) -> bool:
        """Warm-and-export a cold chain through a prefill replica.
        Returns True when a handoff was INITIATED (completed inline
        off-loop; running on a daemon thread on the event loop).
        No-ops — cheap probes only — when the chain is too short, has
        a live claim, is already resident on a decode replica, or is
        already restorable from the fleet store.

        ``trace`` (PR 20): the owning request's trace. The handoff
        worker runs UNDER it (``use_trace``), so the claim→export→
        restore window lands as a ``handoff`` span on the request's
        trace, the store client's ops inside it carry the id on the
        wire, and the ``handoff`` flight event joins the merged fleet
        timeline by the same id."""
        fleet = self.fleet
        page = fleet.config.page_size
        if not chain or len(ids) <= page:
            return False
        prefillers = self._prefill_candidates()
        if not prefillers:
            return False
        if not self._dedup_claim(chain):
            return False
        # Resident or restorable already? Probe decode-capable
        # replicas (registry = resident home; host extension = the
        # store can restore it — either way the warm-up buys nothing).
        for i in self._decode_candidates():
            p = fleet.batchers[i].prefix_probe(ids)
            if p["registry_tokens"] >= page or p["host_tokens"] >= page:
                return False
        src = min(
            prefillers, key=lambda i: fleet.batchers[i].load_cost()
        )
        # The prefill phase routes by LOAD (the role split's routing
        # rule): affinity is a decode-phase concern — a warm-up runs
        # once per chain, so there is no prefix to re-use on the
        # prefill side.
        t0 = time.perf_counter()
        try:
            fut = fleet.batchers[src].submit(
                prompt, max_new_tokens=1, temperature=0.0
            )
        except (RuntimeError, ValueError) as e:
            log.warning("handoff warm-up submit failed: %s", e)
            return False
        wait_s = fleet.fleet_config.handoff_wait_s
        streamed = (
            fleet.fleet_config.handoff_stream and wait_s > 0
        )
        # Streamed handoff (PR 17): issue the STREAMING export NOW —
        # while the warm-up prefill is still computing the chain's
        # tail, the export step is already spilling each chunk's pages
        # as they flip ready, so the store (the wire, when it is
        # remote) transfers OVERLAP the prefill instead of serializing
        # after it. The non-streamed path (handoff_stream=False, the
        # PR-16 shape) exports the whole
        # chain in one pass after the warm-up completes.
        ev_stream = None
        if streamed:
            ev_stream = fleet.batchers[src].request_export(
                ids, stream_until=time.monotonic() + wait_s
            )
        deadline = time.monotonic() + wait_s

        def finish() -> None:
            try:
                # The handoff worker runs under the owning request's
                # trace (PR 20): store ops issued from THIS thread
                # attach their spans here and carry the id on the wire.
                with _tracing.use_trace(trace):
                    fut.result(timeout=wait_s)
                    if ev_stream is not None:
                        ev = ev_stream
                    else:
                        ev = fleet.batchers[src].request_export(ids)
                    if not ev.wait(
                        max(0.0, deadline - time.monotonic())
                    ):
                        log.warning(
                            "handoff export from replica %d did not "
                            "land within %.1fs; decode side may "
                            "re-prefill",
                            src,
                            wait_s,
                        )
                        return
            except Exception as e:  # noqa: BLE001 - degrade, never wedge
                log.warning("handoff via replica %d failed: %s", src, e)
                return
            dur = time.perf_counter() - t0
            _M_HANDOFFS.inc()
            # Claim-to-exported latency: the window the decode side
            # would otherwise re-prefill in.
            _M_HANDOFF_SECONDS.observe(dur)
            with self._lock:
                self.handoffs += 1
                self.handoff_seconds_sum += dur
                self.handoff_seconds_count += 1
            if trace is not None:
                trace.add_span(
                    "handoff", t0, dur, src=src, chain_pages=len(chain)
                )
            _flight.flight_recorder().record(
                "handoff",
                t0,
                dur,
                trace_id=_tracing.trace_id_of(trace),
                src=src,
                chain_pages=len(chain),
                streamed=streamed,
            )

        if wait_s > 0 and self._off_loop():
            finish()
        else:
            # Gateway event loop: the warm-up + export completes on a
            # daemon thread — the triggering request goes cache-cold
            # on its decode replica, its panel mates restore.
            threading.Thread(
                target=finish, name="disagg-handoff", daemon=True
            ).start()
        return True
