"""Shared-prefix decode attention (PR 3): read common KV once per group.

Parity anchors for the two-phase kernel family
(`ops/pallas/attention.py`: dense bf16, dense int8 head-major, paged
grouped) against the single-pass references — CPU interpret mode,
seeded, including the boundary-page (partially shared) group, a group
that shrinks mid-decode as members retire, and the degenerate 1-member
group. Plus the GroupTracker metadata builder, the batcher's grouped
end-to-end path (text parity + bytes-saved metrics), the engine
N-fanout A/B, and the memory planners' prefix-shared accounting.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.models.cache import quantize_kv
from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.paged_cache import GroupTracker
from llm_consensus_tpu.models.transformer import init_params
from llm_consensus_tpu.ops.attention import (
    decode_attention,
    decode_attention_quant,
    decode_attention_shared_prefix,
    decode_attention_shared_prefix_quant,
    merge_decode_partials,
)
from llm_consensus_tpu.ops.pallas.attention import (
    flash_decode_attention_shared_prefix,
    flash_decode_attention_shared_prefix_q8,
    paged_decode_attention_grouped,
)
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)

CFG = get_config("test-tiny")


def _params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def _shared_cache(key, b, s, hkv, d, plen):
    """Dense [B, S, Hkv, D] K/V whose slots [0, plen) are identical
    across rows — the shared-prefill invariant."""
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d))
    k = k.at[:, :plen].set(k[0, :plen])
    v = v.at[:, :plen].set(v[0, :plen])
    return k, v


# ---------------------------------------------------------------------------
# The LSE merge and the XLA reference
# ---------------------------------------------------------------------------


def test_merge_decode_partials_recombines_split_softmax():
    """Splitting softmax attention at an arbitrary slot and merging the
    (m, l, o) partials must reproduce the single-pass result — the
    identity the whole kernel family rests on."""
    key = jax.random.PRNGKey(0)
    s, d = 24, 8
    scores = jax.random.normal(key, (1, 1, 1, 1, s), jnp.float32) * 4.0
    v = jax.random.normal(jax.random.fold_in(key, 1), (1, s, 1, d))

    def partial_over(mask):
        sc = jnp.where(mask, scores, -1e30)
        m = sc.max(-1, keepdims=True)
        p = jnp.exp(sc - jnp.where(m <= -5e29, 0.0, m))
        l = p.sum(-1, keepdims=True)
        o = jnp.einsum("bkgqs,bskd->bkgqd", p, v) / jnp.maximum(l, 1e-30)
        return m, l, o

    full = partial_over(jnp.ones((s,), bool))[2]
    slot = jnp.arange(s)
    for split in (0, 7, 12, s):
        left = partial_over(slot < split)
        right = partial_over(slot >= split)
        merged = merge_decode_partials(*left, *right)
        np.testing.assert_allclose(
            np.asarray(merged), np.asarray(full), rtol=1e-6, atol=1e-6,
            err_msg=f"split={split}",
        )


def test_xla_reference_matches_plain_decode_attention():
    key = jax.random.PRNGKey(1)
    b, h, hkv, d, s = 4, 4, 2, 32, 40
    q = jax.random.normal(key, (b, 1, h, d), jnp.float32)
    valid = jnp.asarray([20, 40, 17, 33], jnp.int32)
    for plen in (0, 8, 16):
        k, v = _shared_cache(jax.random.fold_in(key, plen), b, s, hkv, d, plen)
        want = decode_attention(q, k, v, valid)
        got = decode_attention_shared_prefix(q, k, v, valid, jnp.int32(plen))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
            err_msg=f"plen={plen}",
        )


# ---------------------------------------------------------------------------
# Dense kernels (CPU interpret)
# ---------------------------------------------------------------------------


def test_flash_shared_prefix_bf16_matches_ungrouped():
    """Grouped output == ungrouped row-kernel reference output, ragged
    valid lengths, including a prefix that ends mid-block and an S the
    block width must divide unevenly (the _sp_block path)."""
    key = jax.random.PRNGKey(2)
    b, h, hkv, d, s = 3, 4, 2, 128, 48  # blk = 48, single S-block
    q = jax.random.normal(key, (b, 1, h, d), jnp.float32)
    valid = jnp.asarray([22, 48, 19], jnp.int32)
    for plen in (0, 16, 18):
        k, v = _shared_cache(jax.random.fold_in(key, plen), b, s, hkv, d, plen)
        want = decode_attention(q, k, v, valid)
        got = flash_decode_attention_shared_prefix(
            q, k, v, valid, jnp.int32(plen), interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
            err_msg=f"plen={plen}",
        )


def test_flash_shared_prefix_bf16_multi_block():
    """Multi-S-block shapes (nblk > 1): the suffix pass SKIPS the block
    the prefix covers (the bandwidth point of the split) and the online
    softmax folds across blocks — parity at a block-aligned prefix, a
    mid-block prefix, and a row whose fill ends mid-block."""
    key = jax.random.PRNGKey(12)
    b, h, hkv, d, s = 2, 4, 2, 128, 256  # blk = 128, nblk = 2
    q = jax.random.normal(key, (b, 1, h, d), jnp.float32)
    valid = jnp.asarray([200, 131], jnp.int32)
    for plen in (128, 100):
        k, v = _shared_cache(jax.random.fold_in(key, plen), b, s, hkv, d, plen)
        want = decode_attention(q, k, v, valid)
        got = flash_decode_attention_shared_prefix(
            q, k, v, valid, jnp.int32(plen), interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
            err_msg=f"plen={plen}",
        )


def test_flash_shared_prefix_q8_matches_quant_reference():
    """int8-KV variant == the dequantizing jnp reference (and the XLA
    shared-prefix quant reference) — MQA edge included (hkv=1)."""
    key = jax.random.PRNGKey(3)
    for hkv in (2, 1):
        b, h, d, s = 3, 4, 64, 32
        plen = 16
        q = jax.random.normal(jax.random.fold_in(key, hkv), (b, 1, h, d))
        k, v = _shared_cache(jax.random.fold_in(key, 7), b, s, hkv, d, plen)
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        # Sequence-major -> head-major QuantKVCache layout. The shared
        # prefix stays identical across rows after quantization (the
        # per-(token, head) scales are row-independent).
        kq, ks = kq.transpose(0, 2, 1, 3), ks.transpose(0, 2, 1)
        vq, vs = vq.transpose(0, 2, 1, 3), vs.transpose(0, 2, 1)
        valid = jnp.asarray([20, 32, 17], jnp.int32)
        want = decode_attention_quant(q, kq, ks, vq, vs, valid)
        ref = decode_attention_shared_prefix_quant(
            q, kq, ks, vq, vs, valid, jnp.int32(plen)
        )
        got = flash_decode_attention_shared_prefix_q8(
            q, kq, ks, vq, vs, valid, jnp.int32(plen), interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(want), rtol=2e-5, atol=2e-5
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
            err_msg=f"hkv={hkv}",
        )


# ---------------------------------------------------------------------------
# Paged grouped kernel (CPU interpret)
# ---------------------------------------------------------------------------


def test_paged_grouped_kernel_mixed_membership():
    """Two groups + an ungrouped row + a degenerate 1-member group in
    ONE program, all matching the gather reference. Rows 0/1 share
    pages [7, 2] (two full shared pages, private boundary/suffix pages
    after — the partially-shared admission shape); row 2 is ungrouped;
    row 3 is a 1-member group (must be exact, not just tolerated)."""
    key = jax.random.PRNGKey(4)
    b, h, hkv, d = 4, 4, 2, 128
    n_pages, pg, p_per = 12, 8, 4
    k_pool = jax.random.normal(jax.random.fold_in(key, 1), (n_pages, pg, hkv, d))
    v_pool = jax.random.normal(jax.random.fold_in(key, 2), (n_pages, pg, hkv, d))
    tables = jnp.asarray(
        [[7, 2, 9, 0], [7, 2, 3, 10], [5, 4, 0, 0], [6, 1, 0, 0]]
    )
    valid = jnp.asarray([19, 27, 10, 14], jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 3), (b, h, d), jnp.float32)

    gid = jnp.asarray([0, 0, -1, 1], jnp.int32)
    rep = jnp.asarray([0, 3, 0, 0], jnp.int32)
    gpages = jnp.asarray([2, 1, 0, 0], jnp.int32)
    sstart = jnp.asarray([16, 16, 0, 8], jnp.int32)
    got = paged_decode_attention_grouped(
        q, k_pool, v_pool, tables, valid, gid, rep, gpages, sstart,
        interpret=True,
    )
    k_seq = k_pool[tables].reshape(b, p_per * pg, hkv, d)
    v_seq = v_pool[tables].reshape(b, p_per * pg, hkv, d)
    want = decode_attention(q[:, None], k_seq, v_seq, valid)[:, 0]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_paged_grouped_kernel_no_groups_degrades_to_plain():
    """All rows ungrouped (gid -1 everywhere, zero-page groups): the
    grouped program must still equal the plain path — phase 1
    contributes nothing anywhere."""
    key = jax.random.PRNGKey(5)
    b, h, hkv, d = 2, 4, 2, 128
    n_pages, pg, p_per = 8, 8, 3
    k_pool = jax.random.normal(jax.random.fold_in(key, 1), (n_pages, pg, hkv, d))
    v_pool = jax.random.normal(jax.random.fold_in(key, 2), (n_pages, pg, hkv, d))
    tables = jnp.asarray([[4, 1, 0], [2, 6, 0]])
    valid = jnp.asarray([13, 20], jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 3), (b, h, d), jnp.float32)
    got = paged_decode_attention_grouped(
        q, k_pool, v_pool, tables, valid,
        jnp.asarray([-1, -1], jnp.int32),
        jnp.zeros((1,), jnp.int32),
        jnp.zeros((1,), jnp.int32),
        jnp.zeros((2,), jnp.int32),
        interpret=True,
    )
    k_seq = k_pool[tables].reshape(b, p_per * pg, hkv, d)
    v_seq = v_pool[tables].reshape(b, p_per * pg, hkv, d)
    want = decode_attention(q[:, None], k_seq, v_seq, valid)[:, 0]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


# ---------------------------------------------------------------------------
# GroupTracker: metadata builder over shared page runs
# ---------------------------------------------------------------------------


def test_group_tracker_lcp_groups_donor_and_mappers():
    """The donor's run extends past the mapped header (its own tail
    pages) — LCP grouping still puts donor + mappers in ONE group with
    the common run as the shared region."""
    t = GroupTracker(max_seqs=8, page_size=16)
    t.add(0, (3, 5, 9, 11))  # donor: header pages 3,5 + private tail
    t.add(1, (3, 5))  # mapper
    t.add(2, (3, 5, 20))  # mapper with its own extra full page
    t.add(3, (7, 8))  # unrelated private run
    arrs = t.arrays()
    assert arrs is not None
    gid = np.asarray(arrs.group_id)
    assert gid[0] == gid[1] == gid[2] != -1
    assert gid[3] == -1
    g = int(gid[0])
    assert int(np.asarray(arrs.group_pages)[g]) == 2  # LCP = pages 3,5
    assert int(np.asarray(arrs.shared_start)[0]) == 32
    assert int(np.asarray(arrs.group_rep)[g]) in (0, 1, 2)
    assert t.largest_group == 3
    assert t.saved_tokens_per_step == 2 * 2 * 16  # (3-1) members * 2pg * 16


def test_group_tracker_shrinks_and_drops_singletons():
    t = GroupTracker(max_seqs=4, page_size=16)
    t.add(0, (1, 2))
    t.add(1, (1, 2))
    assert t.arrays() is not None
    assert t.peak_group == 2
    t.remove(1)  # group shrinks to one member -> no group
    assert t.arrays() is None
    assert t.largest_group == 0
    assert t.peak_group == 2  # high-water mark survives
    t.add(2, ())  # empty run: stays ungrouped, never groups
    assert t.arrays() is None
    t.add(3, (1, 2, 7))
    assert t.arrays() is not None  # seqs 0 and 3 share (1, 2)
    assert int(np.asarray(t.arrays().group_pages)[0]) == 2


def test_group_tracker_caps_group_count():
    t = GroupTracker(max_seqs=8, page_size=4, max_groups=1)
    t.add(0, (1,))
    t.add(1, (1,))
    t.add(2, (2, 3))
    t.add(3, (2, 3))
    t.add(4, (2, 3))
    arrs = t.arrays()
    gid = np.asarray(arrs.group_id)
    # Only the larger group (by members * pages) fits the cap; the
    # other rows stay ungrouped (correct, just undeduped).
    assert (gid != -1).sum() == 3
    assert gid[2] == gid[3] == gid[4] == 0


# ---------------------------------------------------------------------------
# End-to-end: the continuous batcher's grouped decode program
# ---------------------------------------------------------------------------

_HEADER = "Panel shared header for every persona, forty ch: "  # 49 chars
_CCFG = dict(
    max_slots=4,
    page_size=16,
    n_pages=64,
    pages_per_seq=8,
    max_new_tokens=6,
    seq_buckets=(16, 32, 64),
    prefill_chunk=16,
    share_prefix=True,
)


def _serve(batcher, prompts, **kw):
    futs = [batcher.submit(p, **kw) for p in prompts]
    return [f.result(timeout=120) for f in futs]


def test_batcher_grouped_attention_parity_and_metrics():
    """The acceptance criterion end to end: a same-header burst served
    with group-aware decode attention produces IDENTICAL text to the
    ungrouped path, reports shared-KV bytes saved > 0, and exposes the
    group size (the panel's N) — grouped and ungrouped rows coexisting
    in one decode program throughout (slots admit/retire mid-flight)."""
    from llm_consensus_tpu.server.metrics import SHARED_KV_BYTES_SAVED

    params = _params()
    prompts = [_HEADER + f"Q{i}: what is {i}+{i}?" for i in range(4)]

    base = ContinuousBatcher(
        CFG, params,
        config=ContinuousConfig(**{**_CCFG, "share_prefix": False}),
    )
    try:
        want = [r.text for r in _serve(base, prompts)]
        base_stats = base.stats()
    finally:
        base.close()
    # The ungrouped baseline must not count savings.
    assert base_stats["shared_kv_bytes_saved"] == 0

    before = SHARED_KV_BYTES_SAVED.value
    grouped = ContinuousBatcher(
        CFG.with_(use_pallas=True), params,
        config=ContinuousConfig(**_CCFG),
    )
    try:
        got = [r.text for r in _serve(grouped, prompts)]
        stats = grouped.stats()
    finally:
        grouped.close()

    assert got == want
    assert stats["shared_kv_bytes_saved"] > 0
    assert stats["decode_group_peak"] >= 2
    # The Prometheus counter moved by exactly the batcher's own count.
    assert SHARED_KV_BYTES_SAVED.value - before == stats["shared_kv_bytes_saved"]


def test_batcher_grouped_boundary_page_and_shrinking_group():
    """The boundary-page shape (prefix ends mid-page: full pages map,
    the partial page is CoW-copied and stays SUFFIX) with members
    retiring at different steps (different max_new_tokens), so the
    group shrinks mid-decode — every text byte-identical to the
    ungrouped path."""
    params = _params()
    # BOS + 40 chars = 41 ids: 2 full pages of 16 + a 9-token boundary.
    common = "Forty common characters of shared text."
    prompts = [common + " tail one", common + " tail two",
               common + " tail three"]
    caps = [6, 2, 4]  # retire at different decode steps

    def run(cfg, share_prefix):
        # share_prefix=False: no page is mapped, so no group can form.
        b = ContinuousBatcher(
            cfg, params,
            config=ContinuousConfig(
                **{**_CCFG, "share_prefix": share_prefix}
            ),
        )
        try:
            # Serialize the first admission so the boundary content is
            # READY and the CoW copy actually happens for successors.
            out = [_serve(b, [prompts[0]], max_new_tokens=caps[0])[0].text]
            rest = [
                b.submit(p, max_new_tokens=c)
                for p, c in zip(prompts[1:], caps[1:])
            ]
            out += [f.result(timeout=120).text for f in rest]
            return out, b.stats()
        finally:
            b.close()

    want, base_stats = run(CFG, False)
    got, stats = run(CFG.with_(use_pallas=True), True)
    assert got == want
    assert stats["prefix_pages_copied"] >= 1  # the boundary page rode CoW
    assert stats["shared_kv_bytes_saved"] > 0
    assert stats["decode_group_peak"] >= 2


def test_grouped_attention_survives_host_round_trip():
    """GroupTracker × offload (PR 4): a decode group's shared prefix
    cannot be demoted out from under its ACTIVE members (eviction skips
    pages live tables hold — a filler storm during grouped decode must
    leave the group's output untouched), and once the members retire
    and the header DOES demote to host, the next same-header burst
    restores it under fresh page ids and the group RE-FORMS over them
    — text byte-identical to the ungrouped, offload-off path
    throughout."""
    params = _params()
    # Tails stay short: header (49 chars) + tail must fit the largest
    # bucket (64) or truncation cuts the header off the front.
    group_prompts = [_HEADER + f"p{i} talks" for i in range(3)]
    fillers = [
        f"{i} unique filler storm prompt with enough padding text."
        for i in range(6)
    ]
    # Second wave AFTER the group retires: only then is the header
    # registry-only (refcount 1) and actually evictable — four of
    # these concurrently demand the whole 20-page pool, forcing the
    # header's chain to demote before the re-vote.
    fillers2 = [
        f"{i} second wave filler with just as much padding text."
        for i in range(6, 12)
    ]
    revote = [_HEADER + f"r{i} votes" for i in range(3)]
    # Starved pool: 20 usable pages vs a peak concurrent demand of ~17
    # (3 grouped members + 1 filler slot), so the filler storm must
    # recycle registry pages while the group decodes.
    kw = dict(
        max_slots=4,
        page_size=16,
        n_pages=21,
        pages_per_seq=8,
        max_new_tokens=4,
        seq_buckets=(16, 32, 64),
        prefill_chunk=16,
    )

    def run(cfg, share_prefix, host_cache_bytes):
        b = ContinuousBatcher(
            cfg, params,
            config=ContinuousConfig(
                **kw,
                share_prefix=share_prefix,
                host_cache_bytes=host_cache_bytes,
            ),
        )
        try:
            # Group decodes (mnt 20) WHILE the filler storm churns the
            # pool through the one remaining slot.
            gf = [b.submit(p, max_new_tokens=20) for p in group_prompts]
            ff = [b.submit(p, max_new_tokens=4) for p in fillers]
            texts = [f.result(timeout=120).text for f in gf + ff]
            texts += [
                r.text for r in _serve(b, fillers2, max_new_tokens=4)
            ]
            mid = b.stats()
            if share_prefix:
                # Scope the lifetime peak to the re-vote round: the
                # worker is idle here (all futures resolved, queue
                # empty), and a fresh peak proves the group RE-FORMED
                # over the restored pages rather than riding round 1's.
                b._groups.peak_group = 0
            texts += [r.text for r in _serve(b, revote)]
            return texts, mid, b.stats()
        finally:
            b.close()

    want, _, _ = run(CFG, False, 0)
    got, mid, stats = run(CFG.with_(use_pallas=True), True, 64 << 20)
    assert got == want
    # The storm really pressured the pool while the group was live —
    # and could not touch the group's own pages (parity above is the
    # proof; rc > 1 pages are not evictable by construction).
    assert mid["prefix_evictions"] > 0
    assert stats["offload_demoted_pages"] > 0
    # The re-vote header came back from the host tier (3 full pages of
    # the 50-id header), and the group re-formed on the restored ids.
    assert stats["offload_restored_pages"] >= 3
    assert stats["decode_group_peak"] >= 2
    assert stats["free_pages"] == stats["total_pages"]


# ---------------------------------------------------------------------------
# Engine N-fanout path
# ---------------------------------------------------------------------------


def test_engine_fanout_shared_prefix_attention_parity():
    """generate(shared_prefill=True) with the two-phase kernel on vs
    off: identical greedy tokens (the bf16 dense variant; the q8
    variant is kernel-gated to single-device and covered above)."""
    from llm_consensus_tpu.engine.generate import generate

    cfg = CFG.with_(use_pallas=True)
    params = _params()
    b, s = 4, 16
    tokens = jnp.tile(jnp.arange(5, 5 + s, dtype=jnp.int32)[None], (b, 1))
    lengths = jnp.full((b,), s, jnp.int32)
    temps = jnp.full((b,), 0.9, jnp.float32)
    key = jax.random.PRNGKey(11)
    on = generate(
        cfg, params, tokens, lengths, key, temps, max_new_tokens=6,
        eos_id=-1, shared_prefill=True, shared_prefix_attention=True,
    )
    off = generate(
        cfg, params, tokens, lengths, key, temps, max_new_tokens=6,
        eos_id=-1, shared_prefill=True, shared_prefix_attention=False,
    )
    np.testing.assert_array_equal(np.asarray(on.tokens), np.asarray(off.tokens))
    np.testing.assert_allclose(
        np.asarray(on.logprob_sum), np.asarray(off.logprob_sum),
        rtol=1e-4, atol=1e-4,
    )


# ---------------------------------------------------------------------------
# Memory planners: prefix-shared KV accounting
# ---------------------------------------------------------------------------


def test_memory_estimate_accounts_for_shared_prefix():
    from llm_consensus_tpu.engine.engine import (
        EngineConfig, InferenceEngine, plan_memory,
    )

    eng = InferenceEngine(
        CFG, _params(),
        engine_config=EngineConfig(max_new_tokens=8, seq_buckets=(16, 32)),
    )
    full = eng.memory_estimate(n_candidates=4, prompt_len=16)
    deduped = eng.memory_estimate(
        n_candidates=4, prompt_len=16, shared_prefix_len=16
    )
    # prefix stored once instead of once per row: (b-1) * s token-slots
    # of KV come off the estimate, everything else unchanged.
    per_token = CFG.n_layers * CFG.n_kv_heads * 2 * CFG.head_dim * 2
    assert full["kv_cache_bytes"] - deduped["kv_cache_bytes"] == (
        (full["batch"] - 1) * 16 * per_token
    )
    assert deduped["params_bytes"] == full["params_bytes"]
    # Over-asking caps at the prompt bucket (suffixes never share).
    capped = eng.memory_estimate(
        n_candidates=4, prompt_len=16, shared_prefix_len=10_000
    )
    assert capped["kv_cache_bytes"] == deduped["kv_cache_bytes"]

    # plan_memory (config-only) agrees with the instantiated estimate.
    plan = plan_memory(
        CFG, n_candidates=4, prompt_len=16, new_tokens=8,
        seq_buckets=(16, 32), shared_prefix_len=16,
    )
    assert plan["kv_cache_bytes"] == deduped["kv_cache_bytes"]
