"""Roofline-adaptive runtime control: close the loop on the cost model.

Since PR 10 the batcher has MODELED every device program's HBM bytes
and FLOPs at dispatch and MEASURED its wall-clock window
(``gateway_program_mbu{kind}``, ``gateway_spec_acceptance``,
``gateway_sched_overhead_seconds``) — but every knob that signal
should drive stayed a static config value. This module turns the
attribution plane into a feedback loop (ROADMAP item 4; ClusterFusion++
and TPLA in PAPERS.md are the framing: the right dispatch shape is a
function of where the workload sits on the roofline, and prefill and
decode sit in different places):

- **spec_k auto-tune.** Measured draft acceptance is tracked per
  shared-prefix group (EWMA over the same per-round fractions
  ``gateway_spec_acceptance`` observes; group identity = the row's
  first prefix page, the GroupTracker bucket key) and the effective k
  of each speculative dispatch moves within ``[1, spec_k]`` — menu
  ``{1, spec_k}``, so the jitted program family stays TWO traces. A
  workload whose groups all reject (adversarial draft) stops paying
  full-width verify rows; high-acceptance self-draft groups keep the
  whole window. When every group's EWMA sits below the disengage
  floor, speculation DISENGAGES entirely (the PR-9 live-flip drain
  rules make this safe mid-burst: the pipeline drains on the mode
  change and ``_spec_catch_up`` replays the draft on re-engage) and a
  bounded probe window re-engages periodically so a draft that starts
  accepting again regrows to the full k.
- **Roofline-adaptive R.** Each plain multi-round dispatch picks its
  window from ``{1, R}`` (the SAME two traces ``decode_rounds``
  already compiles — stop-bound windows collapse to 1 today): R when
  every decoding row has budget for the whole window and the modeled
  decode roofline position says weight-read-bound (weights dominate
  the modeled bytes — the ClusterFusion++ regime where folding rounds
  amortizes dispatch overhead against a weight-dominated program), 1
  when the batch is about to retire (max remaining budget < R: the
  masked tail rounds would decode nothing while stretching retirement
  lag). Riding PR 12's early-exit masking keeps text byte-identical
  to ANY fixed R by construction.
- **Chunk/depth steering.** The effective prefill-chunk width for NEW
  admissions moves within the menu ``{chunk, chunk/2}`` (one extra
  compiled (chunk, bucket) trace per bucket, AT MOST — never a
  recompile storm; decisions only ever flip between menu widths) from
  measured un-overlapped scheduler overhead: a host-bound loop keeps
  full-width chunks (fewer programs amortize the host work), a fully
  overlapped loop with a bandwidth-starved chunk lane halves them
  (bounded decode-lane stall per fused window). Pipeline depth moves
  within ``[1, pipeline_depth]`` by probing: un-overlapped overhead
  OBSERVES 0 once hidden, so the controller periodically probes one
  depth lower and backs off the moment overhead re-appears.
- **Modeled-cost admission + restore pacing.** The admission
  controller's cost-budget mode (server/admission.py) uses
  :meth:`llm_consensus_tpu.serving.continuous.ContinuousBatcher.
  modeled_request_cost` — the SAME modeled-bytes unit ``load_cost``
  routes on — for the queue bound AND the overflow hard cap, so a
  32k-context request is no longer one unit of work; and the PR-14
  preempt-to-host-tier hook consults :meth:`AdaptiveController.
  restore_pacing_ok` before demoting — preemption stops once the
  modeled restore debt (bytes demoted by preemption and not yet
  restored) would thrash the host tier instead of absorbing the storm.

Every decision is recorded as an ``autotune`` flight event (on value
CHANGES, like spec flips), counted in
``gateway_autotune_decisions_total{knob}`` and mirrored as the
``gateway_autotune_value{knob}`` gauge + the batcher's ``stats()``
``autotune_*`` keys (lockstep tested). Pin any knob via
:class:`ControlConfig` (``tune_* = False``) to freeze it at its
configured value; without a peak (``--hbm-gbps 0``, the default) the
MBU-driven decisions disable themselves (acceptance and overhead
steering keep working). ``--hbm-gbps auto`` reads the peak from the
table of published ones and refuses a device that is not in it —
:func:`resolve_hbm_gbps`.

Greedy text is byte-identical to every fixed (spec_k x R) grid point,
and steering compiles nothing after warm-up
(tests/test_adaptive_control.py).
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass

from llm_consensus_tpu.server.metrics import (
    AUTOTUNE_DECISIONS as _M_DECISIONS,
)
from llm_consensus_tpu.server.metrics import (
    AUTOTUNE_VALUE as _M_VALUE,
)

log = logging.getLogger(__name__)

__all__ = [
    "ControlConfig",
    "AdaptiveController",
    "resolve_hbm_gbps",
    "HBM_GBPS_TABLE",
]

#: Knob names — the ``knob`` label of gateway_autotune_* and the
#: stats() mirror keys.
KNOBS = ("spec_k", "rounds", "chunk", "depth", "restore_batch")

#: Published peak HBM bandwidth per chip (GB/s, 1e9 bytes/s) for
#: ``--hbm-gbps auto``, matched as a lowercase substring of
#: ``jax.devices()[0].device_kind``. Sources: Google Cloud TPU
#: documentation, the system-architecture pages "TPU v4", "TPU v5e"
#: and "TPU v5p". A CPU has no entry: CPU callers pass a number.
HBM_GBPS_TABLE: tuple[tuple[str, float], ...] = (
    ("v5p", 2765.0),
    ("v5 lite", 819.0),
    ("v5e", 819.0),
    ("v4", 1228.0),
)


def resolve_hbm_gbps(spec) -> float:
    """Resolve an ``--hbm-gbps`` value: a number passes through,
    ``"auto"`` looks the device kind up in :data:`HBM_GBPS_TABLE`. A
    device that is not in the table is an error, not a default: a
    roofline share against a guessed peak is worse than none."""
    if not isinstance(spec, str):
        return float(spec)
    s = spec.strip().lower()
    if s != "auto":
        return float(s)
    import jax

    kind = jax.devices()[0].device_kind
    for sub, gbps in HBM_GBPS_TABLE:
        if sub in kind.lower():
            return gbps
    raise ValueError(
        f"--hbm-gbps auto: no published peak for device kind {kind!r} in "
        "serving.control.HBM_GBPS_TABLE; pass the number"
    )


@dataclass
class ControlConfig:
    """Knob enables + thresholds for :class:`AdaptiveController`.

    Set ``tune_<knob> = False`` to PIN that knob at its configured
    value (the disable-steering lever the README documents); the
    controller still collects signals so a re-enable starts warm.
    """

    # -- knob enables ---------------------------------------------------
    tune_spec_k: bool = True
    tune_rounds: bool = True
    tune_chunk: bool = True
    tune_depth: bool = True

    # -- shared EWMA smoothing ------------------------------------------
    #: Weight of the newest observation in every EWMA here (acceptance,
    #: overhead, MBU). 0.2 ~ a 5-sample memory: fast enough to catch a
    #: burst's character, slow enough that one jittered round doesn't
    #: flip a knob.
    ewma_alpha: float = 0.2

    # -- spec_k auto-tune -----------------------------------------------
    #: Per-group acceptance EWMA below this => the group's recommended
    #: k is 1 (stop wasting verify width on rejects).
    accept_low: float = 0.3
    #: EWMA at/above this => full spec_k again (regrow hysteresis gap
    #: vs accept_low prevents flapping at the boundary).
    accept_high: float = 0.6
    #: When EVERY decoding group's EWMA sits below this, speculation
    #: disengages entirely (the k=1 floor still pays a draft scan +
    #: 2-wide verify for ~nothing) — the PR-9 live-flip drain rules
    #: make the mode change safe mid-burst.
    accept_disengage: float = 0.15
    #: Acceptance samples a group needs before shrink/disengage apply
    #: (optimistic start: unknown groups get the full window).
    accept_min_samples: int = 3
    #: While disengaged, re-probe with one spec window every this many
    #: plain decode windows (a draft that starts accepting again must
    #: be able to regrow; each probe costs one catch-up replay).
    spec_probe_every: int = 64

    # -- roofline-adaptive R --------------------------------------------
    #: Modeled weight fraction (weight bytes / modeled program bytes,
    #: EWMA over fetched decode-kind programs) at/above which the
    #: workload counts as weight-read-bound => full R windows. Below
    #: it (KV-dominated long contexts) the per-program window matters
    #: less and R follows the budget rule only. Ignored (treated as
    #: weight-bound) when hbm_gbps is unresolved — the budget rule is
    #: the non-MBU half of the decision. This is the COLD-START prior
    #: only: once both window arms have measured rates, the measured
    #: throughput arbitrates (see rounds_probe_every).
    weight_bound_frac: float = 0.5
    #: The rounds decision is a measured two-arm choice over {1, R},
    #: arbitrated at STRETCH granularity: the controller runs one arm
    #: for ``rounds_stretch_windows`` consecutive windows, measures
    #: the stretch's wall-clock tokens/sec (Σ tokens between the
    #: first and last fetch — the realized burst throughput of that
    #: regime, prefill interleave and host gaps included; per-window
    #: ratios are far too noisy to rank arms ~10% apart), folds it
    #: into the arm's decayed rate, and picks the better-measured arm
    #: for the next stretch — after first CALIBRATING the unmeasured
    #: arm, and re-probing the losing arm every
    #: ``rounds_probe_stretches`` stretches so a shifted workload (a
    #: dispatch cost appearing, contexts growing KV-bound) can flip
    #: the choice back. A gap longer than ``rounds_stretch_gap_s``
    #: between fetches (idle batcher between bursts) discards the
    #: open stretch instead of counting the idle as regime time.
    rounds_stretch_windows: int = 12
    #: A stretch cut short by an idle gap (burst boundary) still
    #: folds when it accumulated at least this many windows — bursts
    #: shorter than a full stretch are measurements too, or a bursty
    #: workload would never calibrate the second arm.
    rounds_stretch_min: int = 5
    rounds_stretch_gap_s: float = 0.25
    rounds_probe_stretches: int = 8
    #: Flip hysteresis: the challenger arm must measure at least this
    #: fraction FASTER than the incumbent to take the regime. Stretch
    #: rates on a contended box jitter ±5-10%; without a margin a
    #: single misranked fold flips the regime and costs a whole
    #: stretch at the slower arm before the next fold corrects it.
    #: Real regime gaps (dispatch cost, tail-masking waste) are tens of
    #: percent, far past the band.
    rounds_flip_margin: float = 0.05
    #: Probe backoff: a probe that LOSES (the regime snaps back)
    #: doubles its interval up to this many multiples — steady
    #: workloads stop paying a recurring probe tax, while the first
    #: few probes after a real shift still land quickly. Applies to
    #: the rounds-regime probe and the depth-lowering probe alike.
    probe_backoff_max: int = 16

    # -- chunk/depth steering -------------------------------------------
    #: Un-overlapped scheduler overhead per dispatch (EWMA, seconds)
    #: above this => the host is visible between programs: keep
    #: full-width chunks and the full pipeline depth.
    overhead_high_s: float = 0.002
    #: EWMA below this => the host loop is fully hidden; chunk may
    #: halve (bounded fused-window stall) and depth may probe lower.
    overhead_low_s: float = 0.0005
    #: Dispatches between depth-lowering probes, and the probe's
    #: length in dispatches. Probing is how a hidden (0-observing)
    #: overhead signal is re-measured at lower depth.
    depth_probe_every: int = 64
    depth_probe_len: int = 16

    # -- restore-batch sizing (host-tier promotion) ---------------------
    tune_restore_batch: bool = True
    #: Most pages one worker iteration may promote from the host tier
    #: (each restore flushes the decode pipeline and blocks on the
    #: installs). The controller moves the effective batch within
    #: ``[1, restore_batch_max]`` from the SAME un-overlapped-overhead
    #: EWMA chunk/depth steering reads: a host-bound loop takes the
    #: full batch (the flush it amortizes was already stalling on the
    #: host), a fully hidden loop takes 1 (bound the stall injected
    #: into a saturated decode lane). Controller absent => 1, the
    #: exact pre-PR-16 one-page-per-iteration behavior.
    restore_batch_max: int = 8

    # -- restore pacing (fleet preempt hook) ----------------------------
    #: Cap on the modeled restore debt preemption may accumulate,
    #: as a fraction of the host tier's byte budget: past it the
    #: preempt hook stops granting overflow admissions (demoting more
    #: chains that all must restore soon thrashes the tier instead of
    #: absorbing the storm; classic 429 backpressure resumes).
    restore_debt_frac: float = 0.5


class AdaptiveController:
    """Online knob controller for ONE :class:`ContinuousBatcher`.

    The batcher feeds measurements at its existing instrumentation
    sites (``note_*``) and consults decisions at its existing knob
    reads (``spec_gate`` / ``spec_k_for`` / ``rounds_cap`` /
    ``chunk_for`` / ``depth_for``); everything here is cheap host
    arithmetic under one lock. One controller per batcher — fleet
    replicas each get their own (their signals are per-replica).
    """

    def __init__(self, config: ControlConfig | None = None):
        self.config = config or ControlConfig()
        self._lock = threading.Lock()
        # Per-group draft-acceptance EWMAs: group key -> (ewma, n).
        # Bounded evict-oldest — group keys are page ids (recycled),
        # but a pathological workload must not grow this without
        # bound.
        self._accept: dict[int, tuple[float, int]] = {}
        self._accept_max = 1024
        # Spec engage state machine: engaged | disengaged (+ probe).
        self._spec_engaged = True
        self._plain_windows = 0  # windows since disengage (probe clock)
        self._probe_left = 0
        # Overhead / step-duration EWMAs (seconds).
        self._ovh_ewma: float | None = None
        self._dur_ewma: float | None = None
        # Fleet-steered restore-batch ceiling (PR 19); None = the
        # configured restore_batch_max stands alone.
        self._restore_cap: int | None = None
        # Modeled weight fraction of decode-kind programs (EWMA) and
        # the decode-MBU EWMA when a peak is configured.
        self._wf_ewma: float | None = None
        self._mbu_ewma: float | None = None
        # Two-arm rounds arbitration state (see ControlConfig): the
        # per-arm decayed (tokens, seconds) sums stretches fold into,
        # per-arm window counts (the FIRST window of an arm carries
        # its jit compile — seconds on a window worth milliseconds —
        # and is discarded), the active regime + its open stretch,
        # and the probe clock.
        self._rate_tok: dict[int, float] = {}
        self._rate_sec: dict[int, float] = {}
        self._rate_n: dict[int, int] = {}
        self._regime_arm: int | None = None
        self._r_max_seen: int | None = None
        self._stretch_t0: float | None = None
        self._stretch_tokens = 0.0
        self._stretch_windows = 0
        self._last_note_t: float | None = None
        self._stretches_since_probe = 0
        self._rounds_probe_backoff = 1
        self._rounds_probing = False
        self._stretch_dirty = False
        self._depth_probe_backoff = 1
        # Chunk hysteresis state (see chunk_for).
        self._chunk_half = False
        # Depth probe state.
        self._depth_eff: int | None = None
        self._since_probe = 0
        self._probe_depth: int | None = None
        self._probe_dispatches = 0
        # Restore-pacing debt (modeled bytes demoted by preemption,
        # not yet restored).
        self._restore_debt = 0
        # Peak bandwidth + modeled terms bound from the batcher.
        self._hbm_gbps = 0.0
        self._weight_bytes = 0
        self._kv_token_bytes = 0
        self._host_budget = 0
        # Last decision per knob (change detection for flight events)
        # + decision counters (stats mirrors of the Prometheus
        # families; one site, three surfaces).
        self._last: dict[str, float | int | None] = {k: None for k in KNOBS}
        self._decisions: dict[str, int] = {k: 0 for k in KNOBS}

    # -- binding --------------------------------------------------------

    def bind(
        self,
        *,
        hbm_gbps: float = 0.0,
        weight_bytes: int = 0,
        kv_token_bytes: int = 0,
        host_budget_bytes: int = 0,
    ) -> None:
        """Attach the batcher's static modeled terms (called once at
        batcher construction). ``hbm_gbps == 0`` disables the
        MBU-driven clauses; everything else keeps steering."""
        with self._lock:
            self._hbm_gbps = float(hbm_gbps)
            self._weight_bytes = int(weight_bytes)
            self._kv_token_bytes = int(kv_token_bytes)
            self._host_budget = int(host_budget_bytes)

    @property
    def mbu_driven(self) -> bool:
        """Whether roofline-position clauses are live (a resolved
        non-zero peak bandwidth)."""
        return self._hbm_gbps > 0

    # -- decision plumbing ----------------------------------------------

    def _ewma(self, prev: float | None, x: float) -> float:
        a = self.config.ewma_alpha
        return x if prev is None else (1 - a) * prev + a * x

    def _decide(self, knob: str, value: float | int) -> None:
        """Record one knob decision: gauge + stats mirror always, a
        counter bump + flight event on CHANGES only (steady state is
        silent, like spec_flip events). Caller holds the lock."""
        prev = self._last[knob]
        _M_VALUE.labels(knob=knob).set(float(value))
        if prev == value:
            return
        self._last[knob] = value
        self._decisions[knob] += 1
        if knob in ("chunk", "depth"):
            # A chunk/depth move changes the very thing a rounds
            # stretch measures (and the first use of a fresh width
            # carries its jit): poison the open stretch so the arms'
            # rates never absorb another knob's transition.
            self._stretch_dirty = True
        _M_DECISIONS.labels(knob=knob).inc()
        # Lazy import mirrors continuous.py's _flight usage: control is
        # imported by serving/__init__ consumers that may not want the
        # whole flight module at import time.
        from llm_consensus_tpu.serving import flight as _flight

        _flight.flight_recorder().record(
            "autotune",
            time.perf_counter(),
            knob=knob,
            value=value,
            prev=prev,
        )

    # -- signal feeds (batcher instrumentation sites) -------------------

    def note_overhead(self, seconds: float) -> None:
        """One un-overlapped sched-overhead observation (the same
        number gateway_sched_overhead_seconds observes)."""
        with self._lock:
            self._ovh_ewma = self._ewma(self._ovh_ewma, seconds)

    def note_program(self, kind: str, cost: dict | None, dur: float) -> None:
        """One fetched program's modeled cost + measured window (the
        _mbu_account site). Decode-kind programs feed the roofline
        position: modeled weight fraction and — with a peak bound —
        the decode-MBU EWMA."""
        with self._lock:
            self._dur_ewma = self._ewma(self._dur_ewma, dur)
            if cost is None or kind not in ("decode", "fused"):
                return
            hbm = max(1, cost["hbm_bytes"])
            kv_bytes = (
                cost["kv_read_tokens"] + cost["kv_write_tokens"]
            ) * self._kv_token_bytes
            self._wf_ewma = self._ewma(
                self._wf_ewma, max(0.0, hbm - kv_bytes) / hbm
            )
            if self._hbm_gbps > 0 and dur > 0:
                self._mbu_ewma = self._ewma(
                    self._mbu_ewma, hbm / dur / (self._hbm_gbps * 1e9)
                )

    def note_spec_round(self, samples: list[tuple[int, int, int]]) -> None:
        """Per-row acceptance from one fetched spec program:
        ``(group_key, accepted, k)`` triples (group key = the row's
        first prefix page — the GroupTracker bucket key). Feeds the
        per-group EWMAs and ends a probe window that measured well."""
        cfg = self.config
        with self._lock:
            for key, accepted, k in samples:
                ewma, n = self._accept.get(key, (None, 0))
                self._accept[key] = (
                    self._ewma(ewma, accepted / max(1, k)),
                    n + 1,
                )
                if len(self._accept) > self._accept_max:
                    self._accept.pop(next(iter(self._accept)))
            if self._probe_left > 0:
                self._probe_left -= 1
                if any(
                    accepted >= k
                    or self._accept.get(key, (0.0, 0))[0]
                    >= cfg.accept_high
                    for key, accepted, k in samples
                ):
                    # The probe found acceptance again (a fully-
                    # accepted window, or the EWMA recovered): stay
                    # engaged — spec_k_for regrows toward full k as
                    # the EWMAs climb past accept_low.
                    self._spec_engaged = True
                    self._probe_left = 0
                elif self._probe_left == 0 and not self._spec_engaged:
                    # The probe ran out still rejecting: the knob
                    # value must read disengaged again (spec_k_for
                    # recorded 1 for the probe windows; leaving that
                    # standing would contradict the "0 = disengaged"
                    # gauge contract).
                    self._decide("spec_k", 0)

    def note_plain_window(self) -> None:
        """One dispatched PLAIN decode window while a draft is
        configured — the probe clock of a disengaged controller
        (counted at the dispatch site, so idle loop iterations never
        advance it)."""
        cfg = self.config
        with self._lock:
            if self._spec_engaged or self._probe_left > 0:
                return
            self._plain_windows += 1
            if self._plain_windows >= cfg.spec_probe_every:
                # Arm a bounded probe: the next iterations re-engage
                # speculation at the k=1 floor to re-measure
                # acceptance (note_spec_round counts the windows and
                # re-engages for real if they accept).
                self._plain_windows = 0
                self._probe_left = 4

    # -- decisions ------------------------------------------------------

    def spec_gate(self, group_keys: list[int]) -> bool:
        """Whether speculation should run this iteration (consulted
        next to ``_spec_ok``; the flip composes with the PR-9 drain
        rules). ``group_keys``: the decoding rows' group keys."""
        cfg = self.config
        if not cfg.tune_spec_k:
            return True
        with self._lock:
            if not self._spec_engaged:
                # Disengaged: run only armed probe windows.
                return self._probe_left > 0
            known = [
                self._accept[k] for k in group_keys if k in self._accept
            ]
            if (
                known
                and len(known) == len(group_keys)
                and all(n >= cfg.accept_min_samples for _, n in known)
                and all(e < cfg.accept_disengage for e, _ in known)
            ):
                # Every group rejects: stop paying draft+verify (the
                # k=1 floor still costs a draft scan + 2-wide verify).
                self._spec_engaged = False
                self._probe_left = 0
                self._plain_windows = 0
                self._decide("spec_k", 0)
                return False
            return True

    def spec_k_for(self, group_keys: list[int], k_max: int) -> int:
        """Effective k for ONE speculative dispatch: the max of the
        decoding groups' recommendations over the menu ``{1, k_max}``
        (a single high-acceptance group keeps the full window — the
        program-wide k can't help one group without paying for all,
        and the winner is the one with something to gain)."""
        cfg = self.config
        if not cfg.tune_spec_k or k_max <= 1:
            return k_max
        with self._lock:
            if self._probe_left > 0 and not self._spec_engaged:
                # Probe windows run at the k=1 floor: cheapest way to
                # re-measure acceptance.
                self._decide("spec_k", 1)
                return 1
            rec = 1
            for key in group_keys:
                ewma, n = self._accept.get(key, (None, 0))
                if ewma is None or n < cfg.accept_min_samples:
                    rec = k_max  # optimistic start
                    break
                if ewma >= cfg.accept_low:
                    rec = k_max
                    break
            self._decide("spec_k", rec)
            return rec

    def note_rounds_window(
        self,
        arm: int,
        tokens: int,
        clean: bool = True,
        now: float | None = None,
    ) -> None:
        """One fetched window while rounds are arbitrated: ``arm`` is
        the dispatched window length, ``tokens`` its total emitted
        tokens. Feeds the active regime's open STRETCH — consecutive
        fetches measured on the note-to-note wall clock, which tiles
        the burst and therefore captures everything a regime costs
        (device rounds, host gaps, prefill interleave, its own forced
        tails) — and a complete stretch folds into the regime's
        decayed rate and re-decides. ``clean`` = False marks a window
        whose length was FORCED (near-stop cap, unscreenable-stop
        collapse): its tokens and time still belong to the running
        regime, it just isn't evidence that the OTHER arm ran.
        ``now``: test seam for the wall clock."""
        cfg = self.config
        if now is None:
            now = time.perf_counter()
        with self._lock:
            n = self._rate_n.get(arm, 0)
            self._rate_n[arm] = n + 1
            if n == 0:
                # The arm's first window EVER carries its jit compile
                # (clean or not — a near-stop cap can be the first
                # rounds(1) window): discard it AND restart the
                # stretch so the compile seconds never enter a rate.
                self._stretch_t0 = None
                self._last_note_t = now
                return
            prev_note = self._last_note_t
            if (
                prev_note is not None
                and now - prev_note > cfg.rounds_stretch_gap_s
            ):
                # Idle gap (quiesced batcher between bursts): fold
                # what the cut stretch measured — ending at the LAST
                # pre-gap fetch, so the idle never counts as regime
                # time — then re-anchor.
                self._fold_stretch(prev_note)
                self._stretch_t0 = None
            self._last_note_t = now
            if self._regime_arm is None:
                self._regime_arm = arm if clean else None
            if self._stretch_t0 is None:
                # Anchor at this fetch; tokens accumulate from the
                # NEXT one (rate = tokens after anchor / time since).
                self._stretch_t0 = now
                self._stretch_tokens = 0.0
                self._stretch_windows = 0
                return
            self._stretch_tokens += tokens
            self._stretch_windows += 1
            if self._stretch_windows >= cfg.rounds_stretch_windows:
                self._fold_stretch(now)

    def _fold_stretch(self, end: float) -> None:
        """Fold the open stretch into its regime arm's decayed rate
        and re-decide the regime (caller holds the lock). A stretch
        below ``rounds_stretch_min`` windows is discarded — too
        little signal to rank arms on."""
        cfg = self.config
        cur = self._regime_arm
        if (
            self._stretch_t0 is None
            or cur is None
            or self._stretch_windows < cfg.rounds_stretch_min
        ):
            return
        if self._stretch_dirty:
            # The stretch absorbed a chunk/depth transition (or the
            # jit of a freshly-steered width) — it measures the
            # transition, not the arm. Discard it and measure the
            # next one clean; the regime stands.
            self._stretch_dirty = False
            self._stretch_t0 = end
            self._stretch_tokens = 0.0
            self._stretch_windows = 0
            return
        span = end - self._stretch_t0
        if span > 0:
            decay = 1.0 - cfg.ewma_alpha
            self._rate_tok[cur] = (
                self._rate_tok.get(cur, 0.0) * decay
                + self._stretch_tokens
            )
            self._rate_sec[cur] = (
                self._rate_sec.get(cur, 0.0) * decay + span
            )
        self._stretch_t0 = end
        self._stretch_tokens = 0.0
        self._stretch_windows = 0
        other = 1 if cur != 1 else self._r_max_seen
        if other is None:
            return
        if self._arm_rate(other) is None:
            # Calibration: measure the unmeasured arm next.
            self._regime_arm = other
            return
        r_cur, r_oth = self._arm_rate(cur), self._arm_rate(other)
        if r_cur is None:
            return
        # Incumbency hysteresis: the challenger needs a real margin,
        # not a lucky stretch (see rounds_flip_margin).
        best = (
            other
            if r_oth > r_cur * (1.0 + cfg.rounds_flip_margin)
            else cur
        )
        if self._rounds_probing:
            # A probe stretch just folded: if it lost (the other arm
            # still wins), back off the probe cadence — a steady
            # workload must not pay a recurring probe tax.
            self._rounds_probing = False
            if best != cur:
                self._rounds_probe_backoff = min(
                    cfg.probe_backoff_max,
                    self._rounds_probe_backoff * 2,
                )
            else:
                self._rounds_probe_backoff = 1
        self._stretches_since_probe += 1
        if (
            best == cur
            and self._stretches_since_probe
            >= cfg.rounds_probe_stretches * self._rounds_probe_backoff
        ):
            # Periodic probe of the losing arm: a shifted workload
            # (RTT appearing, KV-bound growth) must be able to flip
            # the choice back.
            self._stretches_since_probe = 0
            self._rounds_probing = True
            self._regime_arm = other
        else:
            self._regime_arm = best

    def _arm_rate(self, arm: int | None) -> float | None:
        """The arm's decayed stretch tokens/sec (None before any full
        stretch)."""
        if arm is None:
            return None
        sec = self._rate_sec.get(arm, 0.0)
        if sec <= 0:
            return None
        return self._rate_tok.get(arm, 0.0) / sec

    def rounds_cap(self, max_remaining: int, r_max: int) -> int:
        """Window cap for ONE plain multi-round dispatch, menu
        ``{1, r_max}`` (exactly the trace family _stop_plan already
        compiles — adaptive R adds ZERO traces).

        Decision order: (1) near-stop — the whole batch is about to
        retire (``max_remaining < r_max``): 1, masked tail rounds
        would decode nothing while stretching retirement lag. (2) the
        active measured REGIME (see note_rounds_window — stretch-
        level realized throughput arbitrates: a host-RTT-dominated
        chip measures the R regime faster, a dispatch-cheap box
        measures it slower; ClusterFusion++'s "the right R is a
        function of where the workload sits on the roofline", decided
        by where it actually sits). (3) cold start: r_max — the
        configured intent — unless the modeled-MBU prior is live and
        says KV-dominated with the batch near its budget."""
        cfg = self.config
        if not cfg.tune_rounds or r_max <= 1:
            return r_max
        with self._lock:
            self._r_max_seen = r_max
            if max_remaining < r_max:
                self._decide("rounds", 1)
                return 1
            choice = self._regime_arm
            if choice is None:
                choice = r_max
                if (
                    self.mbu_driven
                    and self._wf_ewma is not None
                    and self._wf_ewma < cfg.weight_bound_frac
                    and max_remaining < 2 * r_max
                ):
                    # Cold-start MBU prior: KV-dominated near the
                    # budget — the weight-amortization win is gone.
                    choice = 1
            self._decide("rounds", choice)
            return choice

    def chunk_for(self, bucket: int, full: int) -> int:
        """Effective prefill-chunk width for ONE admission, menu
        ``{full, full // 2}`` (full//2 only when it still divides the
        bucket — the unshared-footprint invariant — and is a real
        width). At most one extra compiled (chunk, bucket) trace per
        bucket, ever: the no-recompile-storm bound.

        Halving is an MBU-DRIVEN decision: it engages only when the
        host loop is fully hidden AND the measured decode/fused
        program MBU says the lane is bandwidth-STARVED (< 0.5 of the
        resolved peak) — a half-width chunk then bounds the fused
        window's decode stall at no bandwidth cost. Without a
        resolved peak the configured width stands: halving doubles
        the per-prompt program count, and "host hidden" alone is no
        evidence that's free (the overhead signal cannot see
        per-program fixed cost that is ALREADY overlapped; halving on
        overhead evidence alone measured ~10% tok/s loss on the CPU
        smoke). Hysteresis: once halved, full width returns when
        overhead RE-APPEARS (> overhead_high_s) or the lane stops
        measuring starved (>= 0.6) — never at the engage threshold
        itself, so the choice cannot flap on a boundary-riding EWMA.
        """
        cfg = self.config
        half = full // 2
        if (
            not cfg.tune_chunk
            or half < 1
            or full % 2
            or bucket % half
        ):
            return full
        with self._lock:
            ovh = self._ovh_ewma
            starved = (
                self.mbu_driven
                and self._mbu_ewma is not None
                and self._mbu_ewma < 0.5
            )
            if self._chunk_half:
                if (
                    (ovh is not None and ovh > cfg.overhead_high_s)
                    or not self.mbu_driven
                    or self._mbu_ewma is None
                    or self._mbu_ewma >= 0.6
                ):
                    self._chunk_half = False
            elif (
                ovh is not None
                and ovh <= cfg.overhead_low_s
                and starved
            ):
                self._chunk_half = True
            choice = half if self._chunk_half else full
            self._decide("chunk", choice)
            return choice

    def depth_for(self, cfg_depth: int) -> int:
        """Effective pipeline depth this iteration, within
        ``[1, cfg_depth]``. Overhead visible => the configured depth
        (hide it). Overhead at ~0 => periodically PROBE one lower for
        ``depth_probe_len`` dispatches; commit if it stays hidden,
        revert the moment it re-appears. Probing exists because a
        fully overlapped loop observes 0 by construction — the signal
        must be re-exposed to be re-measured."""
        cfg = self.config
        if not cfg.tune_depth or cfg_depth <= 1:
            return cfg_depth
        with self._lock:
            if self._depth_eff is None:
                self._depth_eff = cfg_depth
            ovh = self._ovh_ewma
            if ovh is not None and ovh > cfg.overhead_high_s:
                # Host visible: use everything the config allows. A
                # probe that ran into this loses — back its cadence
                # off (the workload keeps proving it needs depth).
                if self._probe_depth is not None:
                    self._depth_probe_backoff = min(
                        cfg.probe_backoff_max,
                        self._depth_probe_backoff * 2,
                    )
                self._probe_depth = None
                self._depth_eff = cfg_depth
                self._decide("depth", cfg_depth)
                return cfg_depth
            if self._probe_depth is not None:
                self._probe_dispatches += 1
                if self._probe_dispatches >= cfg.depth_probe_len:
                    # Probe survived (a re-appearing overhead would
                    # have taken the revert branch above): commit,
                    # and reset the backoff — a committed probe won.
                    self._depth_eff = self._probe_depth
                    self._probe_depth = None
                    self._depth_probe_backoff = 1
                    self._decide("depth", self._depth_eff)
                return (
                    self._probe_depth
                    if self._probe_depth is not None
                    else self._depth_eff
                )
            self._since_probe += 1
            if (
                self._depth_eff > 1
                and ovh is not None
                and ovh <= cfg.overhead_low_s
                and self._since_probe
                >= cfg.depth_probe_every * self._depth_probe_backoff
            ):
                self._since_probe = 0
                self._probe_depth = self._depth_eff - 1
                self._probe_dispatches = 0
                self._decide("depth", self._probe_depth)
                return self._probe_depth
            self._decide("depth", self._depth_eff)
            return self._depth_eff

    # -- restore-batch sizing (host-tier promotion) ---------------------

    def steer_restore_cap(self, cap: int | None) -> None:
        """Fleet-steered override of the restore-batch ceiling (PR 19):
        the fleet controller narrows or widens ``restore_batch_max``
        from fleet-level restore-debt pressure without touching the
        per-replica overhead steering below it. None clears the
        override (back to the configured cap)."""
        with self._lock:
            self._restore_cap = (
                None if cap is None else max(1, int(cap))
            )

    def restore_batch(self) -> int:
        """Pages ``_restore_step`` may promote THIS iteration, within
        ``[1, restore_batch_max]`` — steered by the same un-overlapped
        overhead EWMA as chunk/depth (see ControlConfig). Unknown
        overhead (cold start) takes the full batch: before any decode
        dispatch the loop has nothing to stall. A fleet-steered cap
        (``steer_restore_cap``) bounds the ceiling from above."""
        cfg = self.config
        cap = max(1, cfg.restore_batch_max)
        with self._lock:
            if self._restore_cap is not None:
                cap = min(cap, self._restore_cap)
        if not cfg.tune_restore_batch or cap <= 1:
            if cap != max(1, cfg.restore_batch_max):
                with self._lock:
                    self._decide("restore_batch", cap)
            return cap
        with self._lock:
            ovh = self._ovh_ewma
            if ovh is None or ovh > cfg.overhead_high_s:
                value = cap
            elif ovh <= cfg.overhead_low_s:
                value = 1
            else:
                # Between the hysteresis edges: half the cap — the
                # host is partly visible, so some amortization pays
                # without a full-batch stall.
                value = max(1, cap // 2)
            self._decide("restore_batch", value)
            return value

    # -- restore pacing (fleet preempt hook) ----------------------------

    def note_preempt_demote(self, bytes_: int) -> None:
        """Pages demoted by router-requested preemption (modeled
        bytes) — the debt side of restore pacing."""
        with self._lock:
            self._restore_debt += int(bytes_)

    def note_restore(self, bytes_: int) -> None:
        """Pages promoted back from the host tier — debt repaid."""
        with self._lock:
            self._restore_debt = max(0, self._restore_debt - int(bytes_))

    def restore_pacing_ok(self, pages: int, page_bytes: int) -> bool:
        """Whether the preempt hook may demote ``pages`` more pages:
        the modeled restore debt this would add must stay under
        ``restore_debt_frac`` x the host tier's budget. Past it,
        preemption is demoting chains faster than the one-page-per-
        iteration restore path can repay — further grants would
        thrash the tier, so classic backpressure resumes."""
        with self._lock:
            if self._host_budget <= 0:
                return True
            cap = self.config.restore_debt_frac * self._host_budget
            return self._restore_debt + pages * page_bytes <= cap

    @property
    def restore_debt_bytes(self) -> int:
        with self._lock:
            return self._restore_debt

    # -- observability --------------------------------------------------

    def group_acceptance(self, key: int) -> float | None:
        """The group's acceptance EWMA (None = no samples yet)."""
        with self._lock:
            hit = self._accept.get(key)
            return hit[0] if hit else None

    def stats(self) -> dict:
        """The batcher stats() mirror of gateway_autotune_* — last
        decided value per knob (-1 = no decision yet) and the decision
        counters (lockstep tested)."""
        with self._lock:
            out = {
                f"autotune_{k}": (
                    self._last[k] if self._last[k] is not None else -1
                )
                for k in KNOBS
            }
            out.update(
                {f"autotune_decisions_{k}": self._decisions[k] for k in KNOBS}
            )
            out["autotune_spec_engaged"] = int(self._spec_engaged)
            out["autotune_restore_debt_bytes"] = self._restore_debt
            out["autotune_restore_cap"] = (
                self._restore_cap if self._restore_cap is not None else -1
            )
            return out
