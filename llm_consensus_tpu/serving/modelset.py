"""Multi-model serving plane (PR 18): N independent engines, one gateway.

The paper's consensus protocol is a HETEROGENEOUS panel — distinct
personas, ideally distinct models — yet until this PR every panel
member decoded on one engine. :class:`ModelSet` owns N members, each a
complete engine (its own :class:`~llm_consensus_tpu.serving.continuous.
ContinuousBatcher` or :class:`~llm_consensus_tpu.serving.fleet.
ReplicaSet`, config, params, mesh), behind ONE gateway with one shared
metrics/trace plane. Three things make it more than a dict of engines:

- **Cross-model speculation**: a member may name another member as its
  ``draft_from`` donor. The donor's (cfg, params) mount as the PR-9
  draft, with a :mod:`~llm_consensus_tpu.serving.vocab_align` remap
  bridging the tokenizer boundary — the small proposer literally
  accelerates the large judge through the existing Leviathan verify,
  mirrored draft pool, 4-plane host-tier entries, and PR-15 adaptive
  ``spec_k``, all unchanged. Below-threshold vocab coverage disengages
  the pairing with a construction warning (never silently).
- **Per-model admission lanes**: :meth:`ModelSet.admission_lanes`
  yields one ``model:<name>`` priority lane per member for the
  gateway's :class:`~llm_consensus_tpu.server.admission.
  AdmissionConfig`; the gateway defaults a request's priority to its
  model's lane so one member's burst queues behind its own bound, not
  the panel's.
- **Consensus phase routing**: :meth:`phase_models` maps
  propose → the draft-donor members (small, cheap, diverse) and
  evaluate/refine → the default member (large), which the Coordinator
  consumes via ``CoordinatorConfig.phase_models`` — "move the query,
  not the cache".

:class:`ModelSetBackend` is the Backend seam: requests dispatch on
``GenerationRequest.model`` (None = default member), batches split per
member and fan out concurrently, and the fleet surfaces the gateway
relies on (health, prefix_probe with per-model chain scopes,
request_cost, prefetch, preempt hooks) aggregate across members.
"""

from __future__ import annotations

import asyncio
import logging
import threading
from dataclasses import dataclass, field

from llm_consensus_tpu.backends import base as _backend_base
from llm_consensus_tpu.engine.tokenizer import ByteTokenizer, Tokenizer
from llm_consensus_tpu.serving.vocab_align import VocabMap, align_vocabs
from llm_consensus_tpu.server.metrics import (
    MODEL_REQUESTS as _M_MODEL_REQUESTS,
)
from llm_consensus_tpu.server.metrics import (
    MODEL_TOKENS as _M_MODEL_TOKENS,
)
from llm_consensus_tpu.server.metrics import (
    SPEC_XMODEL_COVERAGE as _M_XMODEL_COVERAGE,
)

__all__ = ["ModelSpec", "ModelSet", "ModelSetBackend"]

log = logging.getLogger(__name__)


@dataclass
class ModelSpec:
    """One ModelSet member: a complete engine description.

    ``draft_from`` names ANOTHER member whose (cfg, params) should
    mount as this member's speculative draft — the cross-model pairing.
    ``fleet`` (a FleetConfig with replicas > 1) puts a ReplicaSet
    behind this member instead of a single batcher; ``control`` (a
    ControlConfig) engages PR-15 adaptive control. ``config`` defaults
    to a fresh ContinuousConfig — members NEVER share config instances
    (each member's live knobs are its own; sharing across models is
    exactly the aliasing the ReplicaSet contract reserves for
    same-model replicas).
    """

    name: str
    cfg: object
    params: dict
    tokenizer: Tokenizer | None = None
    config: object = None
    mesh: object = None
    fleet: object = None
    draft_from: str | None = None
    control: object = None
    # Precomputed draft->target alignment for the ``draft_from``
    # pairing, already sized to MODEL vocabs (see VocabMap.sized_to).
    # None = derive from the two tokenizers via align_vocabs. Callers
    # with structural knowledge the tokenizers can't express (e.g. a
    # shared padded-tail convention between related checkpoints) pass
    # their own.
    vocab_map: VocabMap | None = None


@dataclass
class _Member:
    spec: ModelSpec
    engine: object  # ContinuousBatcher | ReplicaSet
    backend: object  # ContinuousBackend | FleetBackend
    draft_pair: str | None = None  # engaged donor name, None = no draft
    vocab_map: VocabMap | None = None
    requests: int = 0
    tokens: int = 0
    lock: object = field(default_factory=threading.Lock)


class ModelSet:
    """N independent engines behind one gateway — see module doc."""

    def __init__(
        self,
        specs: list[ModelSpec],
        *,
        default: str | None = None,
        host_store=None,
        min_draft_coverage: float = 0.5,
    ):
        if not specs:
            raise ValueError("a ModelSet needs at least one member")
        names = [s.name for s in specs]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate member names: {names}")
        by_name = {s.name: s for s in specs}
        self.default = default or names[0]
        if self.default not in by_name:
            raise ValueError(
                f"default model {self.default!r} is not a member "
                f"(have {names})"
            )
        self.members: dict[str, _Member] = {}
        for spec in specs:
            if spec.tokenizer is None:
                spec.tokenizer = ByteTokenizer()
            draft = None
            dmap = None
            pair = None
            if spec.draft_from is not None:
                donor = by_name.get(spec.draft_from)
                if donor is None:
                    raise ValueError(
                        f"member {spec.name!r} names draft_from="
                        f"{spec.draft_from!r}, which is not a member "
                        f"(have {names})"
                    )
                if donor is spec:
                    raise ValueError(
                        f"member {spec.name!r} cannot draft from itself"
                    )
                if spec.vocab_map is not None:
                    # Caller-supplied alignment: trusted as-is (the
                    # engine still shape-checks it against both cfgs).
                    dmap = spec.vocab_map
                else:
                    dmap = align_vocabs(
                        spec.tokenizer,
                        donor.tokenizer or ByteTokenizer(),
                        min_coverage=min_draft_coverage,
                    )
                if dmap is None:
                    # align_vocabs already warned with the coverage
                    # numbers; name the pairing so the operator knows
                    # WHICH member lost its draft.
                    log.warning(
                        "member %r: cross-model draft pairing with %r "
                        "disengaged (vocab coverage below %.0f%%) — "
                        "serving without speculation",
                        spec.name,
                        spec.draft_from,
                        100.0 * min_draft_coverage,
                    )
                else:
                    cconf = spec.config
                    if cconf is not None and cconf.spec_k <= 0:
                        raise ValueError(
                            f"member {spec.name!r} pairs draft_from="
                            f"{spec.draft_from!r} but its config has "
                            f"spec_k={cconf.spec_k}: the pairing needs "
                            "spec_k > 0 to size the verify program"
                        )
                    # Alignment runs in tokenizer space; the batcher
                    # gathers with MODEL ids, so size the tables to the
                    # (possibly padded) model vocabs before handoff.
                    donor_tok = donor.tokenizer or ByteTokenizer()
                    dmap = dmap.sized_to(
                        spec.cfg.vocab_size,
                        donor.cfg.vocab_size,
                        target_pad=spec.tokenizer.pad_id,
                        draft_pad=donor_tok.pad_id,
                    )
                    draft = (donor.cfg, donor.params)
                    pair = donor.name
                    _M_XMODEL_COVERAGE.set(dmap.coverage)
            engine, backend = self._build_engine(
                spec, draft, dmap, host_store
            )
            self.members[spec.name] = _Member(
                spec=spec,
                engine=engine,
                backend=backend,
                draft_pair=pair,
                vocab_map=dmap,
            )
        self._audit_engage()

    @staticmethod
    def _build_engine(spec: ModelSpec, draft, dmap, host_store):
        from llm_consensus_tpu.serving.continuous import (
            ContinuousBackend,
            ContinuousBatcher,
            ContinuousConfig,
        )

        config = spec.config if spec.config is not None else (
            ContinuousConfig()
        )
        spec.config = config
        fleet = spec.fleet
        if fleet is not None and getattr(fleet, "replicas", 1) > 1:
            from llm_consensus_tpu.serving.fleet import (
                FleetBackend,
                ReplicaSet,
            )

            rs = ReplicaSet(
                spec.cfg,
                spec.params,
                tokenizer=spec.tokenizer,
                config=config,
                fleet=fleet,
                mesh=spec.mesh,
                draft=draft,
                draft_map=dmap,
                control=spec.control,
                host_store=host_store,
            )
            return rs, FleetBackend(rs)
        controller = None
        if spec.control is not None:
            from llm_consensus_tpu.serving.control import (
                AdaptiveController,
            )

            controller = AdaptiveController(spec.control)
        b = ContinuousBatcher(
            spec.cfg,
            spec.params,
            tokenizer=spec.tokenizer,
            config=config,
            mesh=spec.mesh,
            draft=draft,
            draft_map=dmap,
            host_store=host_store,
            controller=controller,
        )
        return b, ContinuousBackend(b)

    # -- engage audit ---------------------------------------------------

    def engage_matrix(self) -> dict[str, dict]:
        """Per-member engage state of every serving feature — the
        construction audit's data, and the README's "engage matrix
        row per model". Each value is True (engaged), False (not
        configured), or a string naming WHY a configured feature will
        not engage (the batcher's own warnings fire for the same
        conditions; this is the queryable mirror)."""
        out: dict[str, dict] = {}
        for name, m in self.members.items():
            c = m.spec.config
            spec_state: object = False
            if m.draft_pair is not None:
                if c.spec_k <= 0:
                    spec_state = "spec_k == 0"
                elif not c.spec_decode:
                    spec_state = "spec_decode flipped off"
                else:
                    spec_state = True
            rounds_state = c.decode_rounds > 1
            tier_state: object = False
            if c.host_cache_bytes > 0:
                tier_state = True if c.share_prefix else "needs share_prefix"
            out[name] = {
                "default": name == self.default,
                "cross_model_spec": spec_state,
                "draft_from": m.draft_pair,
                "vocab_coverage": (
                    round(m.vocab_map.coverage, 4)
                    if m.vocab_map is not None
                    else None
                ),
                "decode_rounds": rounds_state,
                "host_tier": tier_state,
                "adaptive_control": m.spec.control is not None,
                "replicas": getattr(m.spec.fleet, "replicas", 1),
            }
        return out

    def _audit_engage(self) -> None:
        """No-silent-disengage (PR 18 acceptance): every configured
        feature either engages or gets named in a warning, per member,
        at construction."""
        for name, row in self.engage_matrix().items():
            for feature in ("cross_model_spec", "decode_rounds",
                            "host_tier"):
                state = row[feature]
                if isinstance(state, str):
                    log.warning(
                        "member %r: %s configured but will not engage "
                        "(%s)", name, feature, state,
                    )
            log.info("modelset member %r engage: %s", name, row)

    # -- consensus routing ----------------------------------------------

    def phase_models(self) -> dict[str, str] | None:
        """Default consensus phase routing: propose on the draft-donor
        members (small, cheap — their caches already hold the panel
        header via the cross-model draft pairing), evaluate/refine on
        the default member (large). None when no member pairs a donor
        — a homogeneous set routes nothing."""
        donors = {
            m.draft_pair
            for m in self.members.values()
            if m.draft_pair is not None
        }
        if not donors:
            return None
        # Deterministic pick: the first donor in member order.
        propose = next(
            n for n in self.members if n in donors
        )
        return {
            "propose": propose,
            "evaluate": self.default,
            "refine": self.default,
        }

    def admission_lanes(self) -> tuple[str, ...]:
        """One ``model:<name>`` admission lane per member (gateway
        priorities beyond the base interactive/batch pair)."""
        return tuple(f"model:{n}" for n in self.members)

    # -- aggregate fleet surface ----------------------------------------

    def stats(self) -> dict:
        """Shared-plane snapshot: per-member engine stats plus the
        dispatch split (the ``gateway_model_*`` families' stats()
        mirror, lockstep by construction — both are fed from
        ModelSetBackend's one dispatch site)."""
        per = {}
        for name, m in self.members.items():
            with m.lock:
                doc = {"requests": m.requests, "tokens": m.tokens}
            doc["engine"] = m.engine.stats()
            doc["draft_from"] = m.draft_pair
            per[name] = doc
        return {
            "members": list(self.members),
            "default": self.default,
            "per_model": per,
            "engage": self.engage_matrix(),
        }

    def close(self) -> None:
        for m in self.members.values():
            m.engine.close()


class ModelSetBackend(_backend_base.Backend):
    """Backend seam over a :class:`ModelSet`: requests dispatch on
    ``GenerationRequest.model`` (None = the set's default member), a
    batch splits per member and fans out concurrently — one panel
    fan-out drives N engines at once."""

    def __init__(self, modelset: ModelSet):
        self.modelset = modelset

    def member_backend(self, model: str | None):
        """Resolve a request's model tag to a member backend. Unknown
        tags raise — a typo'd model must 400 at the gateway, not
        silently serve from the default weights."""
        ms = self.modelset
        if model is None:
            model = ms.default
        m = ms.members.get(model)
        if m is None:
            raise _backend_base.BackendError(
                f"unknown model {model!r} (have {list(ms.members)})"
            )
        return m

    async def generate_batch(self, requests):
        ms = self.modelset
        groups: dict[str, list[int]] = {}
        for i, r in enumerate(requests):
            name = r.model if r.model is not None else ms.default
            if name not in ms.members:
                raise _backend_base.BackendError(
                    f"unknown model {name!r} (have {list(ms.members)})"
                )
            groups.setdefault(name, []).append(i)
        results: list = [None] * len(requests)

        async def run(name: str, idxs: list[int]):
            m = ms.members[name]
            outs = await m.backend.generate_batch(
                [requests[i] for i in idxs]
            )
            toks = sum(o.num_tokens for o in outs)
            _M_MODEL_REQUESTS.labels(model=name).inc(len(idxs))
            _M_MODEL_TOKENS.labels(model=name).inc(toks)
            with m.lock:
                m.requests += len(idxs)
                m.tokens += toks
            for i, o in zip(idxs, outs):
                results[i] = o

        await asyncio.gather(
            *(run(name, idxs) for name, idxs in groups.items())
        )
        return results

    # -- gateway surfaces ------------------------------------------------

    def health(self) -> dict:
        """Aggregate /readyz heartbeat: alive only when EVERY member's
        engine is (one wedged model degrades the whole panel — the
        consensus protocol needs all phases servable); per-member
        entries name the wedged one."""
        docs = {
            name: m.engine.heartbeat()
            for name, m in self.modelset.members.items()
        }
        ages = [d["last_tick_age_s"] for d in docs.values()]
        steps = [
            d["last_step_age_s"]
            for d in docs.values()
            if d.get("last_step_age_s") is not None
        ]
        return {
            "alive": all(d["alive"] for d in docs.values()),
            "last_tick_age_s": max(ages),
            "last_step_age_s": max(steps) if steps else None,
            "models": docs,
        }

    @property
    def tokenizer(self):
        """The DEFAULT member's tokenizer (``/debug/chains``'s
        ``?prompt=`` encoding; per-member probes re-encode below)."""
        ms = self.modelset
        return ms.members[ms.default].spec.tokenizer

    def prefix_probe(self, ids) -> dict:
        """``/debug/chains`` across the whole set: the top-level
        registry/host numbers keep the single-engine shape (the
        DEFAULT member's view — peer routing compares those), and
        ``models`` carries every member's own scoped probe so a
        heterogeneous front tier can tell whose chains it is counting
        (the ids land verbatim on members sharing the default's
        tokenizer; others re-encode through their own)."""
        ms = self.modelset
        default_tok = ms.members[ms.default].spec.tokenizer
        text = None
        per = {}
        for name, m in ms.members.items():
            mids = ids
            tok = m.spec.tokenizer
            if name != ms.default and tok is not default_tok:
                if text is None:
                    text = default_tok.decode(ids)
                mids = tok.encode(text)
            per[name] = m.engine.prefix_probe(mids)
        top = per[ms.default]
        return {
            "registry_tokens": top["registry_tokens"],
            "host_tokens": top["host_tokens"],
            "scope": top.get("scope"),
            "models": per,
        }

    def request_cost(self, prompt: str, max_new_tokens: int) -> float:
        """Cost-budget admission pricing (PR 15): the DEFAULT member's
        modeled bytes — the gateway prices before it knows the model
        split, and the default (large) member is the conservative
        anchor."""
        ms = self.modelset
        m = ms.members[ms.default]
        batcher = getattr(m.engine, "batchers", None)
        b = batcher[0] if batcher else m.engine
        return b.modeled_request_cost(
            len(m.spec.tokenizer.encode(prompt)), max_new_tokens
        )

    def prefetch(self, prompt: str) -> bool:
        """Enqueue-time restore prefetch (PR 17) on the default member
        (the one whose host tier most likely holds the chain)."""
        ms = self.modelset
        m = ms.members[ms.default]
        pf = getattr(m.backend, "prefetch", None)
        if callable(pf):
            return bool(pf(prompt))
        return False

    def preempt_for_admission(self) -> bool:
        """Overflow hook: let ANY member free pool pages — the gateway
        queue is shared, so whichever engine can demote helps."""
        did = False
        for m in self.modelset.members.values():
            hook = getattr(m.engine, "preempt_for_admission", None)
            if callable(hook):
                try:
                    did = bool(hook()) or did
                except Exception:  # noqa: BLE001 - advisory hook
                    log.exception("member preempt hook failed")
        return did

    async def close(self) -> None:
        self.modelset.close()
