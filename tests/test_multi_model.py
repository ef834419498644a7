"""Multi-model consensus serving (PR 18).

Contract layers:

- ALIGNMENT: `serving.vocab_align.align_vocabs` builds exact-match
  remap tables between tokenizers — identity for a shared tokenizer,
  a round-tripping subset map for overlapping vocabs, and a documented
  DISENGAGE (None + warning) below the coverage threshold.
- BATCHER: cross-model speculation through a non-identity map is
  byte-identical to spec-off (the accept rule is exact for any one-hot
  proposal; the remap only moves the acceptance rate), and a real
  cross-model accept is visible in stats, Prometheus, and the flight
  trace. The witness draft is a VOCAB-PERMUTED TWIN — the target's own
  weights with embedding rows / lm_head columns gathered through the
  map — so it proposes the target's argmax chain expressed in a
  different vocab and acceptance is structural, not luck.
- MODELSET: N engines behind one backend — per-model dispatch,
  engage-matrix audit, phase routing for consensus, per-model lanes.
- FLEET: `ReplicaSet` rejects per-replica configs at construction
  (the live-knob-flip contract, satellite fix) and its probe reports
  the model/weights scope (satellite fix).
"""

import asyncio
import logging
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.backends.base import (
    BackendError,
    GenerationRequest,
    SamplingParams,
)
from llm_consensus_tpu.engine.tokenizer import ByteTokenizer, Tokenizer
from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.transformer import init_params
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)
from llm_consensus_tpu.serving.modelset import (
    ModelSet,
    ModelSetBackend,
    ModelSpec,
)
from llm_consensus_tpu.serving.vocab_align import align_vocabs

CFG = get_config("test-tiny")

_CCFG = dict(
    max_slots=4,
    page_size=16,
    n_pages=96,
    pages_per_seq=10,
    max_new_tokens=10,
    seq_buckets=(16, 32, 64),
    prefill_chunk=16,
    share_prefix=True,
)


class ShiftedByteTokenizer(Tokenizer):
    """Byte tokenizer with a DIFFERENT id layout (byte + 4, id 3 is a
    hole): same text space, shifted vocab — the minimal heterogeneous
    tokenizer for the alignment path. Deliberately not a ByteTokenizer
    subclass: that would take align_vocabs' byte fast path instead of
    the round-trip scan under test."""

    def __init__(self) -> None:
        self.pad_id = 0
        self.bos_id = 1
        self.eos_id = 2
        self._offset = 4
        self.vocab_size = 256 + self._offset

    def encode(self, text: str, add_bos: bool = True) -> list[int]:
        ids = [
            b + self._offset
            for b in text.encode("utf-8", errors="surrogateescape")
        ]
        return [self.bos_id] + ids if add_bos else ids

    def decode(self, ids: Sequence[int]) -> str:
        data = bytes(
            i - self._offset
            for i in ids
            if self._offset <= i < self._offset + 256
        )
        return data.decode("utf-8", errors="surrogateescape")


class WordTokenizer(Tokenizer):
    """Closed word vocab whose every id decodes to a multi-byte string:
    nothing round-trips to a single byte id, so alignment coverage
    against the byte layout collapses to ~0 (the disengage case)."""

    def __init__(self, n: int = 64) -> None:
        self.pad_id = 0
        self.bos_id = 1
        self.eos_id = 2
        self.vocab_size = n

    def encode(self, text: str, add_bos: bool = True) -> list[int]:
        return [self.bos_id] if add_bos else []

    def decode(self, ids: Sequence[int]) -> str:
        return " ".join(f"word{i}" for i in ids if i > 2)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def _twin_params(params, d2t):
    """Vocab-permuted twin: the target's own network with embedding
    rows and lm_head columns gathered through the draft->target map, so
    the twin computes the target's function expressed in the DRAFT
    vocab. Its greedy chain, remapped, IS the target's — cross-model
    acceptance is then structural rather than random-weight luck."""
    g = jnp.asarray(np.asarray(d2t), jnp.int32)
    twin = dict(params)
    twin["embed"] = params["embed"][g]
    if "lm_head" in params:
        twin["lm_head"] = params["lm_head"][:, g]
    return twin


def _serve(batcher, prompts, **kw):
    futs = [batcher.submit(p, **kw) for p in prompts]
    return [f.result(timeout=180) for f in futs]


# ---------------------------------------------------------------------------
# Alignment grid
# ---------------------------------------------------------------------------


def test_align_identity_for_shared_tokenizer():
    tok = ByteTokenizer()
    m = align_vocabs(tok, tok)
    assert m is not None and m.identity
    assert m.coverage == 1.0
    assert np.array_equal(np.asarray(m.d2t), np.arange(tok.vocab_size))
    assert np.array_equal(np.asarray(m.t2d), np.arange(tok.vocab_size))
    # Two distinct byte tokenizers are the same closed layout.
    m2 = align_vocabs(ByteTokenizer(), ByteTokenizer())
    assert m2 is not None and m2.identity
    assert m.scope_key() == m2.scope_key()


def test_align_overlapping_subset_round_trip():
    target, draft = ByteTokenizer(), ShiftedByteTokenizer()
    m = align_vocabs(target, draft)
    assert m is not None and not m.identity
    assert m.coverage > 0.99  # 256 of 257 scanned (id 3 is the hole)
    d2t, t2d = np.asarray(m.d2t), np.asarray(m.t2d)
    # Specials pinned structurally.
    assert d2t[draft.pad_id] == target.pad_id
    assert d2t[draft.bos_id] == target.bos_id
    assert d2t[draft.eos_id] == target.eos_id
    # Every mapped byte id round-trips: same byte under both layouts,
    # and the inverse view returns to the original id.
    for b in (0, 7, 65, 128, 200, 255):
        did, tid = b + 4, b + 3
        assert d2t[did] == tid
        assert t2d[tid] == did
        assert target.decode([int(d2t[did])]) == draft.decode([did])
    # The hole id stays unmapped (-> target pad).
    assert d2t[3] == target.pad_id


def test_align_below_threshold_disengages_with_warning(caplog):
    with caplog.at_level(logging.WARNING, "llm_consensus_tpu"):
        m = align_vocabs(ByteTokenizer(), WordTokenizer())
    assert m is None
    assert any("DISENGAGED" in r.message for r in caplog.records)


def test_align_sized_to_model_vocabs():
    m = align_vocabs(ByteTokenizer(), ShiftedByteTokenizer())
    big = m.sized_to(CFG.vocab_size, CFG.vocab_size, target_pad=0,
                     draft_pad=0)
    assert len(big.d2t) == len(big.t2d) == CFG.vocab_size
    assert not big.identity
    # Tokenizer-range entries are preserved; the padded tail is unmapped.
    assert np.array_equal(np.asarray(big.d2t[:260]), np.asarray(m.d2t))
    assert np.all(np.asarray(big.d2t[260:]) == 0)
    assert big.scope_key() != m.scope_key()
    # Model vocab smaller than the tokenizer's tables is a hard error.
    with pytest.raises(ValueError, match="smaller than the tokenizer"):
        m.sized_to(128, CFG.vocab_size)
    # Identity survives padding only at equal vocabs (pass-through).
    ident = align_vocabs(ByteTokenizer(), ByteTokenizer())
    assert ident.sized_to(384, 384).identity
    assert np.array_equal(np.asarray(ident.sized_to(384, 384).d2t),
                          np.arange(384))


# ---------------------------------------------------------------------------
# Batcher: cross-model spec parity + accept witness
# ---------------------------------------------------------------------------


def _xmodel_map():
    m = align_vocabs(ByteTokenizer(), ShiftedByteTokenizer())
    return m.sized_to(CFG.vocab_size, CFG.vocab_size, target_pad=0,
                      draft_pad=0)


def _burst(params, draft, spec_k, draft_map=None, prompts=None):
    b = ContinuousBatcher(
        CFG,
        params,
        config=ContinuousConfig(**_CCFG, spec_k=spec_k),
        draft=draft,
        draft_map=draft_map,
    )
    prompts = prompts or [
        "Panel shared header, forty characters xx: alpha",
        "Panel shared header, forty characters xx: beta",
        "unrelated prompt entirely",
    ]
    try:
        return [r.text for r in _serve(b, prompts)], b.stats()
    finally:
        b.close()


def test_xmodel_spec_byte_parity_and_accept_witness(params):
    """THE PR-18 acceptance contract: greedy text byte-identical with
    cross-model speculation ON vs OFF, with at least one genuinely
    cross-model accept counted in stats, Prometheus, and the flight
    trace."""
    from llm_consensus_tpu.server.metrics import (
        SPEC_XMODEL_ACCEPTED_TOKENS,
    )
    from llm_consensus_tpu.serving import flight as _flight

    vmap = _xmodel_map()
    twin = _twin_params(params, vmap.d2t)
    want, _ = _burst(params, None, 0)
    before = SPEC_XMODEL_ACCEPTED_TOKENS.value
    got, st = _burst(params, (CFG, twin), 3, draft_map=vmap)
    assert got == want
    assert st["device_programs_spec"] >= 1
    # The twin proposes the target's chain through the remap: accepts
    # are structural, and they are cross-model accepts.
    assert st["spec_cross_model_accepted_tokens"] > 0
    assert st["spec_cross_model_accepted_tokens"] <= (
        st["spec_accepted_tokens"]
    )
    assert SPEC_XMODEL_ACCEPTED_TOKENS.value - before == (
        st["spec_cross_model_accepted_tokens"]
    )
    kinds = [e.kind for e in _flight.flight_recorder().events()]
    assert "spec_xmodel_accept" in kinds


def test_xmodel_spec_adversarial_draft_parity(params):
    """The adversarial pair (independently random draft weights) through
    the SAME non-identity map: acceptance ~0, byte parity still exact —
    alignment quality moves speed, never text."""
    vmap = _xmodel_map()
    dparams = init_params(CFG, jax.random.PRNGKey(9), dtype=jnp.float32)
    want, _ = _burst(params, None, 0)
    got, st = _burst(params, (CFG, dparams), 3, draft_map=vmap)
    assert got == want
    assert st["device_programs_spec"] >= 1


def test_xmodel_vocab_mismatch_without_map_raises(params):
    dcfg = CFG.with_(vocab_size=CFG.vocab_size + 64)
    dparams = init_params(dcfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    with pytest.raises(ValueError, match="vocab alignment map"):
        ContinuousBatcher(
            CFG,
            params,
            config=ContinuousConfig(**_CCFG, spec_k=3),
            draft=(dcfg, dparams),
        )


def test_xmodel_map_scopes_host_store_key(params):
    """Two batchers differing only in the vocab map must not share
    host-tier entries: the draft planes a restore installs were written
    through the map."""
    from llm_consensus_tpu.serving.offload import HostPageStore

    # Scopes are only computed for a SHARED store (a private one never
    # cross-restores by construction).
    store = HostPageStore(8 << 20)
    cconf = dict(_CCFG, host_cache_bytes=8 << 20)
    b1 = ContinuousBatcher(
        CFG, params,
        config=ContinuousConfig(**cconf, spec_k=3),
        draft=(CFG, params), host_store=store,
    )
    vmap = _xmodel_map()
    b2 = ContinuousBatcher(
        CFG, params,
        config=ContinuousConfig(**cconf, spec_k=3),
        draft=(CFG, params), draft_map=vmap, host_store=store,
    )
    try:
        assert b1._store_scope and b2._store_scope
        assert b1._store_scope != b2._store_scope
        assert vmap.scope_key() == b2._store_scope[-3:]
    finally:
        b1.close()
        b2.close()


# ---------------------------------------------------------------------------
# Probe scope (satellite: /debug/chains reports model/weights scope)
# ---------------------------------------------------------------------------


def test_prefix_probe_reports_model_scope(params):
    b = ContinuousBatcher(CFG, params, config=ContinuousConfig(**_CCFG))
    try:
        doc = b.prefix_probe([5, 6, 7])
        scope = doc["scope"]
        assert scope["model"] == CFG.name
        assert len(scope["weights"]) == 12
        # Same weights -> same scope fingerprint; different weights ->
        # different (the probe answers "WHOSE chain is resident").
        assert b.chain_scope()["weights"] == scope["weights"]
    finally:
        b.close()
    b2 = ContinuousBatcher(
        CFG,
        init_params(CFG, jax.random.PRNGKey(5), dtype=jnp.float32),
        config=ContinuousConfig(**_CCFG),
    )
    try:
        assert b2.prefix_probe([5, 6, 7])["scope"]["weights"] != (
            scope["weights"]
        )
    finally:
        b2.close()


# ---------------------------------------------------------------------------
# ReplicaSet: shared-config invariant (satellite fix)
# ---------------------------------------------------------------------------


def test_replicaset_rejects_per_replica_configs(params):
    from llm_consensus_tpu.serving.fleet import FleetConfig, ReplicaSet

    cfgs = [ContinuousConfig(**_CCFG) for _ in range(2)]
    with pytest.raises(ValueError, match="ONE shared ContinuousConfig"):
        ReplicaSet(
            CFG,
            params,
            config=cfgs,
            fleet=FleetConfig(replicas=2),
        )


# ---------------------------------------------------------------------------
# ModelSet: dispatch, engage audit, phase routing
# ---------------------------------------------------------------------------


def _mini_set(params, paired=True):
    twin = _twin_params(
        params,
        align_vocabs(ByteTokenizer(), ShiftedByteTokenizer())
        .sized_to(CFG.vocab_size, CFG.vocab_size).d2t,
    )
    specs = [
        ModelSpec(
            name="large", cfg=CFG, params=params,
            tokenizer=ByteTokenizer(),
            config=ContinuousConfig(**_CCFG, spec_k=3),
            draft_from="small" if paired else None,
        ),
        ModelSpec(
            name="small", cfg=CFG, params=twin,
            tokenizer=ShiftedByteTokenizer(),
            config=ContinuousConfig(**_CCFG),
        ),
    ]
    return ModelSet(specs, default="large")


def test_modelset_dispatch_and_routing(params):
    ms = _mini_set(params)
    be = ModelSetBackend(ms)
    try:
        eng = ms.engage_matrix()
        assert eng["large"]["cross_model_spec"] is True
        assert eng["large"]["draft_from"] == "small"
        assert eng["large"]["vocab_coverage"] > 0.99
        assert eng["small"]["cross_model_spec"] is False
        assert ms.phase_models() == {
            "propose": "small", "evaluate": "large", "refine": "large",
        }
        assert ms.admission_lanes() == ("model:large", "model:small")

        sp = SamplingParams(max_new_tokens=6, temperature=0.0)
        reqs = [
            GenerationRequest("route me", sp, model=m)
            for m in ("large", "small", None)
        ]
        outs = asyncio.run(be.generate_batch(reqs))
        assert all(o.text is not None for o in outs)
        # None routed to the default member -> identical greedy text.
        assert outs[2].text == outs[0].text
        st = ms.stats()
        assert st["per_model"]["large"]["requests"] == 2
        assert st["per_model"]["small"]["requests"] == 1
        assert st["per_model"]["large"]["tokens"] > 0

        with pytest.raises(BackendError, match="unknown model"):
            asyncio.run(
                be.generate_batch(
                    [GenerationRequest("x", sp, model="nope")]
                )
            )

        doc = be.prefix_probe(ByteTokenizer().encode("route me"))
        assert doc["scope"]["model"] == CFG.name
        assert set(doc["models"]) == {"large", "small"}
        h = be.health()
        assert h["alive"] and set(h["models"]) == {"large", "small"}
    finally:
        asyncio.run(be.close())


def test_modelset_low_coverage_pairing_warns_not_raises(params, caplog):
    """Below-threshold pairing is the DOCUMENTED disengage: a warning
    naming the member, an engine without a draft, serving continues."""
    specs = [
        ModelSpec(
            name="large", cfg=CFG, params=params,
            tokenizer=ByteTokenizer(),
            config=ContinuousConfig(**_CCFG, spec_k=3),
            draft_from="small",
        ),
        ModelSpec(
            name="small", cfg=CFG, params=params,
            tokenizer=WordTokenizer(),
            config=ContinuousConfig(**_CCFG),
        ),
    ]
    with caplog.at_level(logging.WARNING, "llm_consensus_tpu"):
        ms = ModelSet(specs, default="large")
    try:
        assert any("disengaged" in r.message for r in caplog.records)
        eng = ms.engage_matrix()
        assert eng["large"]["cross_model_spec"] is not True
        assert ms.members["large"].draft_pair is None
        assert ms.phase_models() is None
    finally:
        ms.close()


def test_modelset_pair_decides_alike_spec_on_and_off(params):
    """A judge that drafts from a vocab-permuted twin of itself under
    another tokenizer, paired by the ModelSet: the judge's greedy
    answers are the same bytes with its speculation on and off, and
    with it on the pair's accepted draft tokens crossed the vocabulary
    map (the member's own counter)."""
    vmap = _xmodel_map()
    ms = ModelSet(
        [
            ModelSpec(
                name="large", cfg=CFG, params=params,
                tokenizer=ByteTokenizer(),
                config=ContinuousConfig(**_CCFG, spec_k=3),
                draft_from="small",
                # The twin is DEFINED by this map, padded tail included.
                vocab_map=vmap,
            ),
            ModelSpec(
                name="small", cfg=CFG,
                params=_twin_params(params, vmap.d2t),
                tokenizer=ShiftedByteTokenizer(),
                config=ContinuousConfig(**_CCFG),
            ),
        ],
        default="large",
    )
    be = ModelSetBackend(ms)
    judge = ms.members["large"].engine
    sp = SamplingParams(max_new_tokens=8, temperature=0.0)
    header = "Panel shared header, forty characters xx: "

    def answers(tag):
        outs = asyncio.run(
            be.generate_batch(
                [
                    GenerationRequest(header + f"{tag} {i}", sp, model="large")
                    for i in range(3)
                ]
            )
        )
        return [o.text for o in outs]

    def accepted():
        return ms.stats()["per_model"]["large"]["engine"][
            "spec_cross_model_accepted_tokens"
        ]

    try:
        assert ms.engage_matrix()["large"]["cross_model_spec"] is True
        on = answers("case")
        crossed = accepted()
        judge.config.spec_decode = False
        assert (
            ms.engage_matrix()["large"]["cross_model_spec"]
            == "spec_decode flipped off"
        )
        off = answers("case")
        assert accepted() == crossed  # nothing drafts while it is off
    finally:
        asyncio.run(be.close())
    assert on == off
    assert crossed > 0


def test_engage_matrix_says_what_is_engaged_and_why_not(params):
    """One row a member: a configured feature reads True, one that is
    not configured False, and one that cannot engage names the reason
    (the host tier without prefix sharing)."""
    ms = ModelSet(
        [
            ModelSpec(
                name="windows", cfg=CFG, params=params,
                config=ContinuousConfig(
                    **_CCFG, decode_rounds=4, host_cache_bytes=1 << 20
                ),
            ),
            ModelSpec(
                name="plain", cfg=CFG, params=params,
                config=ContinuousConfig(
                    **{**_CCFG, "share_prefix": False},
                    host_cache_bytes=1 << 20,
                ),
            ),
        ],
        default="windows",
    )
    try:
        eng = ms.engage_matrix()
    finally:
        ms.close()
    assert eng["windows"]["decode_rounds"] is True
    assert eng["windows"]["host_tier"] is True
    assert eng["windows"]["cross_model_spec"] is False
    assert eng["plain"]["decode_rounds"] is False
    assert eng["plain"]["host_tier"] == "needs share_prefix"


def test_modelset_duplicate_and_unknown_member_validation(params):
    spec = ModelSpec(
        name="a", cfg=CFG, params=params,
        config=ContinuousConfig(**_CCFG),
    )
    with pytest.raises(ValueError, match="default model"):
        ModelSet([spec], default="nope")
    with pytest.raises(ValueError, match="draft_from"):
        ModelSet([
            ModelSpec(
                name="a", cfg=CFG, params=params,
                config=ContinuousConfig(**_CCFG), draft_from="ghost",
            ),
        ])
