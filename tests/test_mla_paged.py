"""The latent (MLA) pool through the paged step programs, against the
plain reference (``models/reference/deepseek_v2.py``): chunked prefill,
then decode, in the standalone, fused and grouped (shared-prefix)
programs, on the XLA ops and on the Pallas kernels (interpret mode).

The system here runs float32 weights and a float32 pool under the test
process's ``highest`` matmul precision, so what is compared is the
mathematics (absorbed attention through pages, the dropless expert
layer, YaRN), not rounding: the tolerance is 2e-4 on logits of
magnitude ~1 — float32 sums in another order (absorbed against
expanded products, online softmax by page against one softmax) reach
~2e-5 here; the same reference rounded to bf16 after every layer
misses by ~2e-2 and fails (``test_tolerance_rejects_bf16``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.paged_cache import (
    NULL_PAGE,
    DecodeGroupArrays,
    PagedKVCache,
    install_seq,
)
from llm_consensus_tpu.models.reference import deepseek_v2 as ref
from llm_consensus_tpu.models.transformer import (
    decode_step_paged,
    fused_step_paged,
    init_params,
    prefill_chunk_paged,
    unembed_one,
)

TOL = 2e-4
PAGE, CHUNK, N_PAGES, SLOTS, PER_SEQ = 8, 16, 40, 4, 8


@functools.lru_cache(maxsize=None)
def _model(use_pallas: bool):
    cfg = get_config("test-tiny-mla").with_(use_pallas=use_pallas)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    # Weights three times the init's: attention and routing that depend
    # on the input, logits of magnitude ~1.
    params = jax.tree.map(lambda a: a * 3 if a.ndim > 1 else a, params)
    return cfg, params


def _tokens(n: int, salt: int) -> np.ndarray:
    return (np.arange(n) * 7 + salt * 13) % 259


def _fresh_cache(cfg) -> PagedKVCache:
    return PagedKVCache.create(
        cfg, N_PAGES, PAGE, SLOTS, PER_SEQ, dtype=jnp.float32
    )


def _table(pages) -> jnp.ndarray:
    t = np.full((PER_SEQ,), NULL_PAGE, np.int32)
    t[: len(pages)] = pages
    return jnp.asarray(t)


def _prefill(cfg, params, cache, ids, table, start=0):
    """Standalone chunk programs over ``ids[start:]``; returns the last
    prompt position's logits and the cache."""
    n = len(ids)
    padded = np.zeros((-(-n // CHUNK) * CHUNK,), np.int32)
    padded[:n] = ids
    hidden = None
    for c0 in range(start, n, CHUNK):
        hidden, cache, *_ = prefill_chunk_paged(
            cfg, params, jnp.asarray(padded[None, c0 : c0 + CHUNK]), table,
            jnp.int32(c0), cache,
        )
        last_c0 = c0
    logits = unembed_one(cfg, params, hidden[0, n - 1 - last_c0])
    return logits, cache


def _ref_logits(cfg, params, ids, at):
    return np.asarray(ref.forward(cfg, params, np.asarray(ids), at=at))


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_chunked_prefill_then_decode_matches_reference(use_pallas):
    """Standalone programs: 37 prompt tokens in chunks of 16 through the
    latent pool, then 5 decode steps teacher-forced on fixed tokens."""
    cfg, params = _model(use_pallas)
    ids = _tokens(37, 1)
    follow = _tokens(5, 2)
    table = _table([3, 4, 5, 6, 7, 8])
    logits, cache = _prefill(cfg, params, _fresh_cache(cfg), ids, table)
    got = [np.asarray(logits)]
    cache = install_seq(cache, jnp.int32(1), table, jnp.int32(len(ids)))
    for t in follow[:-1]:
        toks = jnp.zeros((SLOTS, 1), jnp.int32).at[1, 0].set(int(t))
        step_logits, cache, stats = decode_step_paged(cfg, params, toks, cache)
        got.append(np.asarray(step_logits[1]))
        # One live row: its 3 experts in each of the 2 expert layers.
        assert stats.tolist() == [6, 6]
    full = np.concatenate([ids, follow[:-1]])
    want = _ref_logits(cfg, params, full, np.arange(len(ids) - 1, len(full)))
    np.testing.assert_allclose(np.stack(got), want, atol=TOL, rtol=0)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_fused_and_grouped_programs_match_reference(use_pallas):
    """Two rows that share their first two pages decode (grouped on the
    kernel path) while a third prompt's chunks ride their steps."""
    cfg, params = _model(use_pallas)
    shared = _tokens(2 * PAGE, 3)
    a = np.concatenate([shared, _tokens(5, 4)])
    b = np.concatenate([shared, _tokens(9, 5)])
    c = _tokens(30, 6)
    ta, tb, tc = _table([1, 2, 3]), _table([1, 2, 4, 5]), _table([9, 10, 11, 12])
    cache = _fresh_cache(cfg)
    la, cache = _prefill(cfg, params, cache, a, ta)
    lb, cache = _prefill(cfg, params, cache, b, tb, start=2 * PAGE)
    cache = install_seq(cache, jnp.int32(0), ta, jnp.int32(len(a)))
    cache = install_seq(cache, jnp.int32(2), tb, jnp.int32(len(b)))
    groups = DecodeGroupArrays(
        group_id=jnp.asarray([0, -1, 0, -1], jnp.int32),
        group_rep=jnp.asarray([0, 0], jnp.int32),
        group_pages=jnp.asarray([2, 0], jnp.int32),
        shared_start=jnp.asarray([2 * PAGE, 0, 2 * PAGE, 0], jnp.int32),
    )
    fa, fb = _tokens(3, 7), _tokens(3, 8)
    padded_c = np.zeros((32,), np.int32)
    padded_c[: len(c)] = c
    got_a, got_b = [np.asarray(la)], [np.asarray(lb)]
    hidden = None
    for step in range(2):
        toks = (
            jnp.zeros((SLOTS, 1), jnp.int32)
            .at[0, 0].set(int(fa[step]))
            .at[2, 0].set(int(fb[step]))
        )
        logits, hidden, cache, stats = fused_step_paged(
            cfg, params, toks, cache,
            jnp.asarray(padded_c[None, step * CHUNK : (step + 1) * CHUNK]),
            tc, jnp.int32(step * CHUNK), groups=groups,
        )
        got_a.append(np.asarray(logits[0]))
        got_b.append(np.asarray(logits[2]))
        # 2 live rows + 16 chunk tokens, 3 experts each, 2 expert layers.
        assert int(stats[1]) == 2 * 3 * (2 + CHUNK)
    got_c = np.asarray(unembed_one(cfg, params, hidden[0, len(c) - 1 - CHUNK]))
    for ids, follow, got in ((a, fa, got_a), (b, fb, got_b)):
        full = np.concatenate([ids, follow[:2]])
        want = _ref_logits(
            cfg, params, full, np.arange(len(ids) - 1, len(full))
        )
        np.testing.assert_allclose(np.stack(got), want, atol=TOL, rtol=0)
    want_c = _ref_logits(cfg, params, c, [len(c) - 1])[0]
    np.testing.assert_allclose(got_c, want_c, atol=TOL, rtol=0)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_fused_step_with_three_lanes_matches_reference(use_pallas):
    """Three prompts of 30, 45 and 16 tokens prefill together, a lane
    each of one fused program a step, beside two grouped decoding rows:
    lanes at different fills over tables of their own, and a lane that
    has run out of prompt riding on dead (an all-NULL table)."""
    cfg, params = _model(use_pallas)
    shared = _tokens(2 * PAGE, 3)
    a = np.concatenate([shared, _tokens(5, 4)])
    b = np.concatenate([shared, _tokens(9, 5)])
    ta, tb = _table([1, 2, 3]), _table([1, 2, 4, 5])
    cache = _fresh_cache(cfg)
    la, cache = _prefill(cfg, params, cache, a, ta)
    lb, cache = _prefill(cfg, params, cache, b, tb, start=2 * PAGE)
    cache = install_seq(cache, jnp.int32(0), ta, jnp.int32(len(a)))
    cache = install_seq(cache, jnp.int32(2), tb, jnp.int32(len(b)))
    groups = DecodeGroupArrays(
        group_id=jnp.asarray([0, -1, 0, -1], jnp.int32),
        group_rep=jnp.asarray([0, 0], jnp.int32),
        group_pages=jnp.asarray([2, 0], jnp.int32),
        shared_start=jnp.asarray([2 * PAGE, 0, 2 * PAGE, 0], jnp.int32),
    )
    prompts = [_tokens(30, 6), _tokens(45, 7), _tokens(16, 8)]
    tables = [
        _table([9, 10, 11, 12]), _table([13, 14, 15, 16, 17, 18]),
        _table([19, 20]),
    ]
    dead = _table([])
    fa, fb = _tokens(4, 9), _tokens(4, 10)
    got_a, got_b, got_c = [np.asarray(la)], [np.asarray(lb)], {}
    for step in range(3):
        c0 = step * CHUNK
        live = [c0 < len(ids) for ids in prompts]
        chunk = np.zeros((3, CHUNK), np.int32)
        for lane, ids in enumerate(prompts):
            part = ids[c0 : c0 + CHUNK]
            chunk[lane, : len(part)] = part
        toks = (
            jnp.zeros((SLOTS, 1), jnp.int32)
            .at[0, 0].set(int(fa[step]))
            .at[2, 0].set(int(fb[step]))
        )
        logits, hidden, cache, stats = fused_step_paged(
            cfg, params, toks, cache, jnp.asarray(chunk),
            jnp.stack([t if on else dead for t, on in zip(tables, live)]),
            jnp.asarray([c0 if on else 0 for on in live], jnp.int32),
            groups=groups,
        )
        got_a.append(np.asarray(logits[0]))
        got_b.append(np.asarray(logits[2]))
        # 2 live rows + the live lanes' tokens, 3 experts each, 2 expert
        # layers: a dead lane's tokens take no expert.
        assert int(stats[1]) == 2 * 3 * (2 + CHUNK * sum(live))
        for lane, ids in enumerate(prompts):
            if c0 < len(ids) <= c0 + CHUNK:
                got_c[lane] = np.asarray(
                    unembed_one(cfg, params, hidden[lane, len(ids) - 1 - c0])
                )
    for ids, follow, got in ((a, fa, got_a), (b, fb, got_b)):
        full = np.concatenate([ids, follow[:3]])
        want = _ref_logits(
            cfg, params, full, np.arange(len(ids) - 1, len(full))
        )
        np.testing.assert_allclose(np.stack(got), want, atol=TOL, rtol=0)
    for lane, ids in enumerate(prompts):
        want = _ref_logits(cfg, params, ids, [len(ids) - 1])[0]
        np.testing.assert_allclose(got_c[lane], want, atol=TOL, rtol=0)


@pytest.mark.parametrize(
    "starts", [[11, 24], [11, -16, 29], [-16, 0, 5]],
    ids=["two", "three-one-dead", "three-two-dead"],
)
def test_latent_kernel_chunk_lanes_match_reference(starts):
    """The kernel on a latent pool with ``nc`` in {2, 3}: lanes at
    different fills and tables, dead ones, a group present, the stacked
    pool indexed in place."""
    from llm_consensus_tpu.ops.pallas import parity

    errs = parity.ragged_attention_error(
        seed=5, pg=8, hkv=1, g=4, d=64, latent_dv=32, p_per=6, n_pages=64,
        valid_len=[13, 9, 40, 23], cq=16, chunk_start=starts,
        group_rows=(0, 2, 3), layer=(1, 2), interpret=True,
    )
    for lane, err in errs.items():
        parity.check(lane, err, parity.ATTENTION_TOL)


def test_tolerance_rejects_bf16():
    """The reference with its residual stream rounded to bfloat16 after
    every layer misses the float32 reference by far more than TOL."""
    cfg, params = _model(False)
    ids = _tokens(37, 1)
    want = _ref_logits(cfg, params, ids, [len(ids) - 1])[0]
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(len(ids))
        x = jnp.asarray(params["embed"], jnp.float32)[jnp.asarray(ids)]
        for p in ref.layers_of(params):
            x = ref.layer(cfg, p, x, pos)
            x = x.astype(jnp.bfloat16).astype(jnp.float32)
        x = ref.rms_norm(x[-1], params["norm_f"], cfg.rms_norm_eps)
        low = np.asarray(x @ params["lm_head"])
    assert np.abs(low - want).max() > 10 * TOL


def test_pool_is_one_latent_plane():
    cfg, _ = _model(False)
    cache = _fresh_cache(cfg)
    lanes = cfg.latent_pool_dim
    assert cfg.latent_dim == 40 and lanes == 128
    assert cache.k.shape == (cfg.n_layers, N_PAGES, PAGE, lanes)
    assert cache.v.shape == (cfg.n_layers, N_PAGES, PAGE, 0)
    big = get_config("deepseek-v2-lite")
    assert (big.latent_dim, big.latent_pool_dim) == (576, 640)
