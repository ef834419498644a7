"""Attention: causal prefill and single-step decode against a KV cache.

Reference counterpart: none (the reference's compute is a remote API call,
``src/main.rs:82-86``); BASELINE.json's north star requires native attention
for the TPU candidate-sampling hot loop. The jnp path here is the
XLA-compiled baseline; :mod:`llm_consensus_tpu.ops.pallas` provides the
flash-style kernels that replace it on the hot path.

Conventions:
- q/k/v are [B, S, H, D] / [B, S, Hkv, D]; GQA groups are expanded by
  broadcasting (no materialized repeat: the einsum indexes kv heads).
- Softmax runs in float32; outputs are cast back to the input dtype.
- Masks are additive-free boolean `where` selects (XLA folds them).
"""

from __future__ import annotations

import jax.numpy as jnp

_NEG_INF = -1e30


def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Scores [B, Hkv, G, Sq, Sk] where H = Hkv * G (GQA without repeat)."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    return jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32)


def _gqa_out(probs: jnp.ndarray, v: jnp.ndarray, dtype) -> jnp.ndarray:
    b, hkv, g, sq, sk = probs.shape
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, sq, hkv * g, -1).astype(dtype)


def causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    positions: jnp.ndarray | None = None,
    window: int = 0,
    scale: float | None = None,
    block: int = 1,
) -> jnp.ndarray:
    """Causal self-attention over a full (prefill) sequence.

    q: [B, S, H, D]; k/v: [B, S, Hkv, D] with H a multiple of Hkv (GQA).
    positions: optional [B, S] integer positions; when given, key j attends
    to query i iff pos_j <= pos_i (supports packed/offset layouts). Default
    is index-causal. ``window`` > 0 adds Mistral-style sliding-window
    masking: query i also ignores keys with pos_i - pos_j >= window.
    ``scale`` (default ``D ** -0.5``) and a value width other than the
    key's are what a latent-attention model passes. ``block`` > 1 is the
    block-causal rule of a block-diffusion model: key j is seen iff
    ``j // block <= i // block`` (no window with it).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = _gqa_scores(q, k) * scale  # [B, Hkv, G, Sq, Sk] fp32
    sq, sk = scores.shape[-2], scores.shape[-1]
    if block > 1:
        qi = jnp.arange(sq)[None] if positions is None else positions
        kj = jnp.arange(sk)[None] if positions is None else positions
        mask = kj[:, None, :] // block <= qi[:, :, None] // block
        mask = mask[:, None, None]  # [B or 1, 1, 1, Sq, Sk]
    elif positions is None:
        qi = jnp.arange(sq)[:, None]
        kj = jnp.arange(sk)[None, :]
        mask = kj <= qi  # [Sq, Sk]
        if window > 0:
            mask &= (qi - kj) < window
        mask = mask[None, None, None]
    else:
        qi = positions[:, :, None]  # [B, Sq, 1]
        kj = positions[:, None, :]  # [B, 1, Sk]
        mask = kj <= qi
        if window > 0:
            mask &= (qi - kj) < window
        mask = mask[:, None, None]  # [B, 1, 1, Sq, Sk]
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    return _gqa_out(probs, v, q.dtype)


def decode_attention_quant(
    q: jnp.ndarray,
    k_q: jnp.ndarray,
    k_scale: jnp.ndarray,
    v_q: jnp.ndarray,
    v_scale: jnp.ndarray,
    valid_len: jnp.ndarray,
    window: int = 0,
) -> jnp.ndarray:
    """Decode attention over an int8 cache (jnp reference path).

    q: [B, 1, H, D]; k_q/v_q: [B, Hkv, S, D] int8 (head-major,
    QuantKVCache layout); k_scale/v_scale: [B, Hkv, S] f32.
    Dequantizes and defers to :func:`decode_attention` — correct
    everywhere, but materializes the bf16 cache; the Pallas kernel
    (ops/pallas.flash_decode_attention_q8) is the TPU hot path.
    """
    k = (k_q.astype(jnp.float32) * k_scale[..., None]).astype(q.dtype)
    v = (v_q.astype(jnp.float32) * v_scale[..., None]).astype(q.dtype)
    # [B, Hkv, S, D] -> [B, S, Hkv, D]
    return decode_attention(
        q,
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        valid_len,
        window=window,
    )


def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    valid_len: jnp.ndarray,
    window: int = 0,
    scale: float | None = None,
) -> jnp.ndarray:
    """One-token decode attention against a fixed-size KV cache.

    q: [B, 1, H, D]; k_cache/v_cache: [B, max_len, Hkv, D];
    valid_len: [B] number of valid cache slots per sequence (the new token's
    k/v must already be written; slots >= valid_len are masked out).
    ``window`` > 0: only the last ``window`` cache slots attend (cache slot
    index == token position; the query sits at position valid_len - 1).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = _gqa_scores(q, k_cache) * scale  # [B, Hkv, G, 1, max_len]
    max_len = k_cache.shape[1]
    slot = jnp.arange(max_len)[None, :]  # [1, max_len]
    mask = slot < valid_len[:, None]
    if window > 0:
        mask &= slot >= (valid_len[:, None] - window)
    mask = mask[:, None, None, None]  # [B,1,1,1,max_len]
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    return _gqa_out(probs, v_cache, q.dtype)


def merge_decode_partials(
    m1: jnp.ndarray,
    l1: jnp.ndarray,
    o1: jnp.ndarray,
    m2: jnp.ndarray,
    l2: jnp.ndarray,
    o2: jnp.ndarray,
) -> jnp.ndarray:
    """Exact two-way merge of partial softmax-attention results.

    Each partial is the flash-decoding (m, l, o) triple over a disjoint
    slice of the key/value slots: ``m`` the running max score, ``l`` the
    softmax denominator at that max, ``o = acc / l`` the normalized
    partial output (m/l broadcast against o's trailing dims). The merge
    is the standard log-sum-exp recombination

        m = max(m1, m2);  a_i = l_i * exp(m_i - m)
        out = (a1 * o1 + a2 * o2) / (a1 + a2)

    which reproduces the single-pass softmax EXACTLY (up to float
    associativity) — the identity that makes the shared-prefix /
    per-sequence-suffix attention split lossless. Empty partials ride
    through as (m = -inf, l = 0): their weight a_i is forced to zero, so
    a row whose phase contributed nothing (an ungrouped sequence's
    shared phase) falls back to the other phase's result alone.
    """
    m = jnp.maximum(m1, m2)
    # exp(-inf - -inf) is NaN; substitute 0 for the max when BOTH
    # phases are empty (the all-masked row — output is garbage anyway,
    # but it must be finite garbage, mirroring the paged kernel).
    m_safe = jnp.where(m <= _NEG_INF / 2, 0.0, m)
    a1 = jnp.where(l1 > 0, l1 * jnp.exp(m1 - m_safe), 0.0)
    a2 = jnp.where(l2 > 0, l2 * jnp.exp(m2 - m_safe), 0.0)
    denom = jnp.maximum(a1 + a2, 1e-30)
    return (a1 * o1 + a2 * o2) / denom


def _partial_softmax(scores: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray):
    """(m, l, o) partial over one masked slot range.

    scores: [B, Hkv, G, 1, S] fp32; v: [B, S, Hkv, D]; mask broadcastable
    to scores. Returns m/l [B, Hkv, G, 1, 1] and o [B, Hkv, G, 1, D]
    (normalized; zeros where the range is empty).
    """
    scores = jnp.where(mask, scores, _NEG_INF)
    m = scores.max(axis=-1, keepdims=True)
    m_safe = jnp.where(m <= _NEG_INF / 2, 0.0, m)
    p = jnp.exp(scores - m_safe)
    l = p.sum(axis=-1, keepdims=True)
    acc = jnp.einsum(
        "bkgqs,bskd->bkgqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    o = acc / jnp.maximum(l, 1e-30)
    return m, l, o


def decode_attention_shared_prefix(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    valid_len: jnp.ndarray,
    prefix_len: jnp.ndarray,
) -> jnp.ndarray:
    """Two-phase decode attention over a batch sharing one prompt prefix.

    The XLA reference for the shared-prefix kernel family
    (:mod:`llm_consensus_tpu.ops.pallas`): every row's cache slots
    [0, prefix_len) hold IDENTICAL K/V (the self-consistency fan-out
    after a shared prefill), so phase 1 attends all rows' queries
    against ROW 0's copy of the prefix — one logical read of the common
    KV — and phase 2 attends each row against its own suffix slots
    [prefix_len, valid_len). The two partial softmaxes merge exactly
    via :func:`merge_decode_partials`. Output equals
    :func:`decode_attention` whenever the shared-prefix precondition
    holds (and ``prefix_len`` may be 0, degrading to the plain path).

    q: [B, 1, H, D]; k_cache/v_cache: [B, max_len, Hkv, D];
    valid_len: [B]; prefix_len: scalar int32 (uniform — the fan-out's
    shared prompt length). No sliding-window support: callers fall back
    to :func:`decode_attention` for windowed configs.
    """
    scale = q.shape[-1] ** -0.5
    b = q.shape[0]
    max_len = k_cache.shape[1]
    slot = jnp.arange(max_len)[None, :]  # [1, max_len]

    # Phase 1: all B rows' queries vs row 0's prefix KV.
    k_shared = k_cache[:1]  # [1, S, Hkv, D] — the one copy phase 1 reads
    v_shared = v_cache[:1]
    scores1 = _gqa_scores(q, jnp.broadcast_to(k_shared, k_cache.shape))
    scores1 = scores1 * scale
    mask1 = (slot < prefix_len)[:, None, None, None]
    m1, l1, o1 = _partial_softmax(
        scores1, jnp.broadcast_to(v_shared, v_cache.shape), mask1
    )

    # Phase 2: each row vs its own suffix slots [prefix_len, valid).
    scores2 = _gqa_scores(q, k_cache) * scale
    mask2 = ((slot >= prefix_len) & (slot < valid_len[:, None]))[
        :, None, None, None
    ]
    m2, l2, o2 = _partial_softmax(scores2, v_cache, mask2)

    out = merge_decode_partials(m1, l1, o1, m2, l2, o2)
    hkv, g = out.shape[1], out.shape[2]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, 1, hkv * g, -1).astype(
        q.dtype
    )


def decode_attention_shared_prefix_quant(
    q: jnp.ndarray,
    k_q: jnp.ndarray,
    k_scale: jnp.ndarray,
    v_q: jnp.ndarray,
    v_scale: jnp.ndarray,
    valid_len: jnp.ndarray,
    prefix_len: jnp.ndarray,
) -> jnp.ndarray:
    """Shared-prefix decode attention over the int8 head-major cache
    (jnp reference path — dequantize, defer). Layouts as
    :func:`decode_attention_quant`."""
    k = (k_q.astype(jnp.float32) * k_scale[..., None]).astype(q.dtype)
    v = (v_q.astype(jnp.float32) * v_scale[..., None]).astype(q.dtype)
    return decode_attention_shared_prefix(
        q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        valid_len, prefix_len,
    )


def ragged_paged_attention_reference(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    valid_len: jnp.ndarray,
    *,
    q_chunk: jnp.ndarray | None = None,
    chunk_table: jnp.ndarray | None = None,
    chunk_start=None,
    window: int = 0,
    scale: float | None = None,
    latent_dv: int = 0,
    block: int = 1,
):
    """XLA reference for the ragged paged attention kernel — the parity
    oracle, the non-Pallas serving path, AND the mesh fallback: when
    the Pallas kernel can't shard over a mesh (``transformer.
    ragged_mesh_shardable`` — e.g. kv heads indivisible by the model
    axis), the serving stack runs THIS function under GSPMD, which
    partitions the gathers/softmax automatically, so every feature
    still engages (PR 13).

    Same ragged semantics as
    :func:`llm_consensus_tpu.ops.pallas.ragged_paged_attention`,
    composed from the gather-then-attend references: decode rows
    materialize their tables out of the pool and apply
    :func:`decode_attention`'s one-token rule; the optional
    prefill-chunk lanes (``q_chunk`` [L, C, H, D], lane l's queries at
    absolute positions ``chunk_start[l] + i`` through ``chunk_table[l]``
    of [L, P]; one lane may come as [C, H, D], [P] and a scalar) apply
    :func:`chunk_decode_attention`'s ragged-causal rule, each over its
    own table; a dead lane (``chunk_start`` = -C) sees nothing and its
    output is garbage nobody reads. Shared-prefix
    groups are a pure bandwidth optimization in the kernel and do not
    exist here — the kernel's grouped output must match this ungrouped
    math (the PR 3 contract, extended to mixed rows).

    q: [B, H, D] — one query per decode row — or [B, NQ, H, D]:
    NQ-token speculative VERIFY rows (PR 9), row b's queries at
    positions ``valid_len[b] - NQ + i`` (``valid_len`` stays "tokens
    readable", the NQ new tokens' K/V already written), masked by
    :func:`chunk_decode_attention`'s ragged-causal rule per row — a
    verify row is exactly a chunk row over the row's own table.
    k_pool/v_pool: [n_pages, page, Hkv, D]; page_table: [B, P];
    valid_len: [B]. Returns out_dec shaped like ``q`` (and out_chunk
    shaped like ``q_chunk`` when it is given).

    ``latent_dv`` > 0: the latent (MLA) pool. ``k_pool`` is
    [n_pages, page, D] — one key a token, shared by all H query heads
    (multi-query attention) — ``v_pool`` is ignored and the value is the
    key's first ``latent_dv`` lanes; outputs are [.., H, latent_dv].
    ``scale`` overrides ``D ** -0.5``. ``block`` > 1: the block-causal
    rule on the verify rows and the chunk lanes (:func:`
    chunk_decode_attention`).
    """
    nq = None
    if q.ndim == 4:
        b, nq, h, d = q.shape
    else:
        b, h, d = q.shape
    if latent_dv:
        k_pool = k_pool[:, :, None, :]
        v_pool = k_pool[..., :latent_dv]
    hkv = k_pool.shape[2]
    dv = v_pool.shape[-1]
    k_seq = k_pool[page_table].reshape(b, -1, hkv, d)
    v_seq = v_pool[page_table].reshape(b, -1, hkv, dv)
    if nq is None:
        out = decode_attention(
            q[:, None], k_seq, v_seq, valid_len, window=window, scale=scale
        )[:, 0]
    else:
        out = chunk_decode_attention(
            q, k_seq, v_seq, valid_len - nq, window=window, scale=scale,
            block=block,
        )
    if q_chunk is None:
        return out
    one_lane = q_chunk.ndim == 3
    if one_lane:
        q_chunk, chunk_table = q_chunk[None], chunk_table[None]
    lanes = q_chunk.shape[0]
    kc = k_pool[chunk_table].reshape(lanes, -1, hkv, d)
    vc = v_pool[chunk_table].reshape(lanes, -1, hkv, dv)
    start = jnp.asarray(chunk_start, jnp.int32).reshape(lanes)
    out_chunk = chunk_decode_attention(
        q_chunk, kc, vc, start, window=window, scale=scale, block=block
    )
    return out, out_chunk[0] if one_lane else out_chunk


def chunk_decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    valid_len: jnp.ndarray,
    window: int = 0,
    scale: float | None = None,
    block: int = 1,
) -> jnp.ndarray:
    """K-token chunk decode against the cache (speculative verification).

    q: [B, K, H, D] — K new tokens per row whose k/v are already written
    at slots [valid_len, valid_len + K); k_cache/v_cache: [B, S, Hkv, D];
    valid_len: [B] pre-chunk fill. Chunk token i attends cache slots
    < valid_len + i + 1 — ragged causal within the chunk, exactly the
    one-token :func:`decode_attention` rule extended to K queries (one
    forward verifies a whole draft, the speculative-decoding hot path).
    ``window`` > 0 (Mistral): token i also ignores slots
    <= valid_len + i - window (cache slot j holds position j).
    ``block`` > 1 (block diffusion): token i sees every slot of its
    block, ``< (qpos // block + 1) * block``, where ``qpos = valid_len +
    i`` (so the block's slots must be written, as they are here).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = _gqa_scores(q, k_cache) * scale  # [B, Hkv, G, K, S]
    kq = q.shape[1]
    s = k_cache.shape[1]
    limit = valid_len[:, None, None] + jnp.arange(kq)[None, :, None] + 1
    if block > 1:
        limit = ((limit - 1) // block + 1) * block
    slots = jnp.arange(s)[None, None, :]
    mask = slots < limit  # [B, K, S]
    if window > 0:
        mask &= slots > limit - 1 - window
    scores = jnp.where(mask[:, None, None], scores, _NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    return _gqa_out(probs, v_cache, q.dtype)
