"""Fused attention kernels (Pallas/Mosaic).

Three kernel families, mirroring the jnp reference paths in
:mod:`llm_consensus_tpu.ops.attention`:

- :func:`flash_causal_attention` — prefill/full attention. Grid over
  (batch x kv-head, query blocks); each program holds its (b, kv) K/V
  slab in VMEM, computes a [G*blk_q, S] score tile in fp32 on the MXU,
  applies the causal mask, does the softmax in VMEM, and writes the
  [G*blk_q, D] output — the score matrix never touches HBM.
- :func:`flash_decode_attention` — single-token decode against the KV
  cache with per-sequence ``valid_len`` masking (the ragged-decode op of
  BASELINE.json's north star). Grid over (batch, kv-head).
- :func:`ragged_paged_attention` — ONE program for the whole serving
  mix: decode rows, prefill-chunk rows, shared-prefix groups, and
  sliding windows over the page pool (and, via thin wrappers, the
  dense bf16 / int8 head-major / stacked int8 caches), with per-row
  metadata riding scalar prefetch. Everything that used to be its own
  kernel (plain paged decode, the grouped two-phase family) is now a
  wrapper over this body.

GQA layout: H = Hkv * G query heads share each kv head; programs are
per-(batch, kv-head) and process all G group heads at once, so K/V are
read exactly once per program (no repeated-KV materialization anywhere).

Tiling: D (head_dim) and S pad to lane width (128); fp32 accumulation via
``preferred_element_type``. On CPU tests, ``interpret=True`` is selected
automatically (same kernels, interpreted).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_consensus_tpu.ops.kernels import interpret_default

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Prefill / full causal attention
# ---------------------------------------------------------------------------


def _causal_kernel(q_ref, k_ref, v_ref, o_ref, *, blk_q: int, scale: float):
    """One (b, kv-head, q-block) program.

    q_ref: [1, blk_q, G, D]; k_ref/v_ref: [1, S, D]; o_ref: [1, blk_q, G, D].
    """
    qi = pl.program_id(1)
    _, _, g, d = q_ref.shape
    s = k_ref.shape[1]

    q = q_ref[0].astype(jnp.float32)  # [blk_q, G, D]
    q2 = q.reshape(blk_q * g, d)
    k = k_ref[0]  # [S, D]
    scores = jax.lax.dot_general(
        q2,
        k.astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [blk_q*G, S]
    scores = scores.reshape(blk_q, g, s)

    q_pos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, 1, 1), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, s), 2)
    scores = jnp.where(k_pos <= q_pos, scores, _NEG_INF)

    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = (p / denom).reshape(blk_q * g, s)

    out = jax.lax.dot_general(
        p,
        v_ref[0].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [blk_q*G, D]
    o_ref[0] = out.reshape(blk_q, g, d).astype(o_ref.dtype)


def flash_causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    blk_q: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Causal attention, index-causal positions (the prefill hot path).

    q: [B, S, H, D]; k/v: [B, S, Hkv, D]. S must divide by ``blk_q``
    (callers pad prompts to buckets, ``engine.EngineConfig.seq_buckets``).
    Returns [B, S, H, D] in q's dtype.
    """
    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    blk_q = min(blk_q, s)
    if s % blk_q:
        raise ValueError(f"seq len {s} not divisible by q block {blk_q}")
    if interpret is None:
        interpret = interpret_default()
    scale = d**-0.5

    # [B, S, Hkv, G, D] -> per-(b, kv) programs see [blk_q, G, D] q tiles.
    q5 = q.reshape(b, s, hkv, g, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    q5 = q5.transpose(0, 2, 1, 3, 4).reshape(b * hkv, s, g, d)

    out = pl.pallas_call(
        functools.partial(_causal_kernel, blk_q=blk_q, scale=scale),
        out_shape=jax.ShapeDtypeStruct((b * hkv, s, g, d), q.dtype),
        grid=(b * hkv, s // blk_q),
        in_specs=[
            pl.BlockSpec(
                (1, blk_q, g, d),
                lambda bh, qi: (bh, qi, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, s, d), lambda bh, qi: (bh, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, s, d), lambda bh, qi: (bh, 0, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, blk_q, g, d),
            lambda bh, qi: (bh, qi, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        interpret=interpret,
    )(q5, kt, vt)
    # [B*Hkv, S, G, D] -> [B, S, H, D]
    return (
        out.reshape(b, hkv, s, g, d).transpose(0, 2, 1, 3, 4).reshape(b, s, h, d)
    )


# ---------------------------------------------------------------------------
# Decode attention against the KV cache
# ---------------------------------------------------------------------------


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, scale: float):
    """One (batch, kv-head) program.

    len_ref: [B*Hkv] whole-array SMEM valid lengths (unblocked — Mosaic
    rejects rank-1 blocked SMEM specs; index by program id instead);
    q_ref: [1, 1, G, D]; k_ref/v_ref: [1, S, D]; o_ref: [1, 1, G, D].
    """
    _, _, g, d = q_ref.shape
    s = k_ref.shape[1]
    valid = len_ref[pl.program_id(0)]

    q = q_ref[0, 0].astype(jnp.float32)  # [G, D]
    scores = jax.lax.dot_general(
        q,
        k_ref[0].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [G, S]
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    scores = jnp.where(slot < valid, scores, _NEG_INF)

    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)

    out = jax.lax.dot_general(
        p,
        v_ref[0].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [G, D]
    o_ref[0, 0] = out.astype(o_ref.dtype)


def _q8_attend(q, kq, ks_row, vq, vs_row, mask, scale: float):
    """Shared q8 decode-attention arithmetic for one (row, kv-head).

    q: [G, D]; kq/vq: [S, D] int8; ks_row/vs_row: [1, S] f32;
    mask: [1, S] bool. Returns [G, D] f32. All three q8 decode kernels
    (per-head grid, batch-row grid, stacked-cache grid) call this — the
    numerics live in exactly one place.

    Dequant is linear: fold the per-slot scales into the [G, S]
    scores/probs instead of scaling the [S, D] K/V slabs (D-times
    fewer VPU ops; int8 slabs feed the MXU after a bare cast).
    """
    scores = jax.lax.dot_general(
        q.astype(jnp.float32),
        kq.astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * (ks_row * scale)  # [G, S] * [1, S]
    scores = jnp.where(mask, scores, _NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jax.lax.dot_general(
        p * vs_row,  # [G, S] * [1, S]
        vq.astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [G, D]


def _decode_q8_kernel(
    len_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref, o_ref, *, scale: float
):
    """One (batch, kv-head) program over an int8 cache.

    len_ref: [B*Hkv] whole-array SMEM (unblocked, indexed by program id);
    q_ref: [1, 1, G, D]; kq_ref/vq_ref: [1, S, D] int8;
    ks_ref/vs_ref: [1, 1, S] f32 (leading singleton keeps the block's
    trailing dims equal to the array's — the Mosaic tiling rule);
    o_ref: [1, 1, G, D]. K/V dequantize in-register — HBM reads stay
    int8 (+ one f32 scale per slot).
    """
    s = kq_ref.shape[1]
    valid = len_ref[pl.program_id(0)]
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    out = _q8_attend(
        q_ref[0, 0], kq_ref[0], ks_ref[0], vq_ref[0], vs_ref[0],
        slot < valid, scale,
    )
    o_ref[0, 0] = out.astype(o_ref.dtype)


def _decode_q8_row_kernel(
    len_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref, o_ref, *, scale: float
):
    """One batch-row program over the int8 cache, ALL kv heads.

    len_ref: [B] whole-array SMEM; q_ref: [1, Hkv, G, D];
    kq_ref/vq_ref: [1, Hkv, S, D] int8; ks_ref/vs_ref: [1, Hkv, S] f32;
    o_ref: [1, Hkv, G, D].

    Per-(batch, head) programs (``_decode_q8_kernel``) move ~64 KB of
    cache each — too little work per grid step, and at bench shapes the
    per-step pipeline overhead dominates (measured 4.7x slower than this
    row-program on v5e at B=64, Hkv=8, S=256). One program per batch row
    streams Hkv slabs (~0.5 MB) and unrolls the per-head attention; the
    arithmetic is identical (f32 dots), so outputs are bit-equal.
    """
    hkv = q_ref.shape[1]
    s = kq_ref.shape[2]
    valid = len_ref[pl.program_id(0)]
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    mask = slot < valid
    for head in range(hkv):  # static unroll over kv heads
        out = _q8_attend(
            q_ref[0, head],
            kq_ref[0, head],
            ks_ref[0, head][None, :],
            vq_ref[0, head],
            vs_ref[0, head][None, :],
            mask,
            scale,
        )
        o_ref[0, head] = out.astype(o_ref.dtype)


# Per-program K+V int8 block budget for the row kernel (double-buffered
# by the grid pipeline); caches larger than this fall back to the
# per-(batch, head) grid, whose blocks are Hkv-times smaller.
_ROW_KERNEL_MAX_KV_BYTES = 4 * 1024 * 1024


def flash_decode_attention_q8(
    q: jnp.ndarray,
    k_q: jnp.ndarray,
    k_scale: jnp.ndarray,
    v_q: jnp.ndarray,
    v_scale: jnp.ndarray,
    valid_len: jnp.ndarray,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Decode attention over the int8 head-major cache.

    q: [B, 1, H, D]; k_q/v_q: [B, Hkv, S, D] int8 (QuantKVCache layout —
    the reshape to per-(b, head) [S, D] slabs is zero-copy, unlike the
    bf16 kernel's transpose); k_scale/v_scale: [B, Hkv, S] f32;
    valid_len: [B]. Returns [B, 1, H, D] in q's dtype.

    Dispatches to the batch-row program (one grid step per row, all kv
    heads — the fast path at decode shapes) when the row's K+V block
    fits the VMEM budget, else to the per-(batch, head) program.
    """
    b, _, h, d = q.shape
    hkv, s = k_q.shape[1], k_q.shape[2]
    g = h // hkv
    if interpret is None:
        interpret = interpret_default()
    scale = d**-0.5

    if 2 * hkv * s * d <= _ROW_KERNEL_MAX_KV_BYTES:
        q4 = q.reshape(b, 1, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
            b, hkv, g, d
        )
        out = pl.pallas_call(
            functools.partial(_decode_q8_row_kernel, scale=scale),
            out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
            grid=(b,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(
                    (1, hkv, g, d),
                    lambda i: (i, 0, 0, 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, hkv, s, d),
                    lambda i: (i, 0, 0, 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, hkv, s), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
                ),
                pl.BlockSpec(
                    (1, hkv, s, d),
                    lambda i: (i, 0, 0, 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, hkv, s), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, hkv, g, d), lambda i: (i, 0, 0, 0), memory_space=pltpu.VMEM
            ),
            interpret=interpret,
        )(valid_len.astype(jnp.int32), q4, k_q, k_scale, v_q, v_scale)
        return (
            out.reshape(b, hkv, 1, g, d)
            .transpose(0, 2, 1, 3, 4)
            .reshape(b, 1, h, d)
        )

    q4 = q.reshape(b, 1, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b * hkv, 1, g, d
    )
    kq2 = k_q.reshape(b * hkv, s, d)
    vq2 = v_q.reshape(b * hkv, s, d)
    ks2 = k_scale.reshape(b * hkv, 1, s)
    vs2 = v_scale.reshape(b * hkv, 1, s)
    lens = jnp.repeat(valid_len.astype(jnp.int32), hkv)

    out = pl.pallas_call(
        functools.partial(_decode_q8_kernel, scale=scale),
        out_shape=jax.ShapeDtypeStruct((b * hkv, 1, g, d), q.dtype),
        grid=(b * hkv,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(
                (1, 1, g, d), lambda bh: (bh, 0, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, s, d), lambda bh: (bh, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, 1, s), lambda bh: (bh, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, s, d), lambda bh: (bh, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, 1, s), lambda bh: (bh, 0, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, d), lambda bh: (bh, 0, 0, 0), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(lens, q4, kq2, ks2, vq2, vs2)
    return (
        out.reshape(b, hkv, 1, g, d).transpose(0, 2, 1, 3, 4).reshape(b, 1, h, d)
    )


def flash_decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    valid_len: jnp.ndarray,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One-token decode attention with ragged valid lengths.

    q: [B, 1, H, D]; k_cache/v_cache: [B, max_len, Hkv, D];
    valid_len: [B] int32. Returns [B, 1, H, D] in q's dtype.
    """
    b, _, h, d = q.shape
    s = k_cache.shape[1]
    hkv = k_cache.shape[2]
    g = h // hkv
    if interpret is None:
        interpret = interpret_default()
    scale = d**-0.5

    q4 = q.reshape(b, 1, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b * hkv, 1, g, d
    )
    kt = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vt = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    lens = jnp.repeat(valid_len.astype(jnp.int32), hkv)  # [B*Hkv]

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        out_shape=jax.ShapeDtypeStruct((b * hkv, 1, g, d), q.dtype),
        grid=(b * hkv,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(
                (1, 1, g, d), lambda bh: (bh, 0, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, s, d), lambda bh: (bh, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, s, d), lambda bh: (bh, 0, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, d), lambda bh: (bh, 0, 0, 0), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(lens, q4, kt, vt)
    return (
        out.reshape(b, hkv, 1, g, d).transpose(0, 2, 1, 3, 4).reshape(b, 1, h, d)
    )


def flash_decode_attention_q8_stacked(
    q: jnp.ndarray,
    k_q: jnp.ndarray,
    k_scale: jnp.ndarray,
    v_q: jnp.ndarray,
    v_scale: jnp.ndarray,
    valid_len: jnp.ndarray,
    layer: jnp.ndarray,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Decode attention reading ONE layer of the stacked int8 cache.

    q: [B, 1, H, D]; k_q/v_q: [L, B, Hkv, S, D] int8 (the WHOLE stacked
    QuantKVCache buffer); k_scale/v_scale: [L, B, Hkv, S] f32;
    valid_len: [B]; layer: traced scalar.

    Inside the layer scan a sliced cache layer must be materialized
    before it can feed ``flash_decode_attention_q8`` (Pallas operands
    are whole buffers) — XLA copies ~2 x B*Hkv*S*D bytes per layer per
    step. Here the stack itself is the operand and the layer index rides
    scalar prefetch into the index_maps, so each row's slab DMAs
    straight from the resident cache. Same arithmetic as the row
    program (:func:`_decode_q8_row_kernel`). Falls back to the sliced
    kernel when the row block exceeds the VMEM budget.
    """
    b, _, h, d = q.shape
    hkv, s = k_q.shape[2], k_q.shape[3]
    g = h // hkv
    if interpret is None:
        interpret = interpret_default()
    if 2 * hkv * s * d > _ROW_KERNEL_MAX_KV_BYTES:
        idx = layer
        return flash_decode_attention_q8(
            q,
            jax.lax.dynamic_index_in_dim(k_q, idx, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(k_scale, idx, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(v_q, idx, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(v_scale, idx, 0, keepdims=False),
            valid_len,
            interpret=interpret,
        )
    scale = d**-0.5

    q4 = q.reshape(b, 1, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, g, d
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # layer index, per-row valid lengths
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), lambda i, l, lens: (i, 0, 0, 0)),
            pl.BlockSpec(
                (1, 1, hkv, s, d), lambda i, l, lens: (l[0], i, 0, 0, 0)
            ),
            pl.BlockSpec((1, 1, hkv, s), lambda i, l, lens: (l[0], i, 0, 0)),
            pl.BlockSpec(
                (1, 1, hkv, s, d), lambda i, l, lens: (l[0], i, 0, 0, 0)
            ),
            pl.BlockSpec((1, 1, hkv, s), lambda i, l, lens: (l[0], i, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, hkv, g, d), lambda i, l, lens: (i, 0, 0, 0)
        ),
    )
    out = pl.pallas_call(
        functools.partial(_decode_q8_stacked_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(
        jnp.atleast_1d(layer).astype(jnp.int32),
        valid_len.astype(jnp.int32),
        q4,
        k_q,
        k_scale,
        v_q,
        v_scale,
    )
    return (
        out.reshape(b, hkv, 1, g, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, 1, h, d)
    )


def _decode_q8_stacked_kernel(
    l_ref, len_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref, o_ref, *,
    scale: float,
):
    """One batch-row program against the stacked cache, all kv heads.

    l_ref: [1] layer (consumed by index_maps); len_ref: [B] valid
    lengths; q_ref: [1, Hkv, G, D]; kq_ref/vq_ref: [1, 1, Hkv, S, D]
    int8; ks_ref/vs_ref: [1, 1, Hkv, S] f32; o_ref: [1, Hkv, G, D].
    Arithmetic is identical to :func:`_decode_q8_row_kernel`.
    """
    hkv = q_ref.shape[1]
    s = kq_ref.shape[3]
    valid = len_ref[pl.program_id(0)]
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    mask = slot < valid
    for head in range(hkv):  # static unroll over kv heads
        out = _q8_attend(
            q_ref[0, head],
            kq_ref[0, 0, head],
            ks_ref[0, 0, head][None, :],
            vq_ref[0, 0, head],
            vs_ref[0, 0, head][None, :],
            mask,
            scale,
        )
        o_ref[0, head] = out.astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Ragged paged attention: ONE program for mixed decode + prefill-chunk rows
# ---------------------------------------------------------------------------
#
# The serving hot loop used to run a zoo of per-shape kernels — plain
# paged decode, grouped shared-prefix decode (two pallas_calls + a host
# merge), dense/int8 shared-prefix pairs — each with its own
# engage/fallback matrix entry (sliding window, stacked cache), and
# chunked prefill as a SEPARATE device program serializing against
# decode. The kernel below replaces the family with one program in the
# style of TPU Ragged Paged Attention (PAPERS.md): every row carries
# per-row (length, suffix-start, group-id) metadata via scalar
# prefetch, and row KIND is a grid-position case of the same body, one
# grid step a row:
#
#   programs [0, B)          decode rows — one query token each, pages
#                            walked through the row's scalar-prefetched
#                            table, sliding window as extra masking;
#   program  B (optional)    ONE prefill-chunk row — C query tokens with
#                            the ragged-causal rule (query i at absolute
#                            position start+i sees slots <= start+i),
#                            walked through the chunk's own host table;
#   programs [B+nc, +Gm)     shared-prefix groups — ALL decode queries
#                            stacked against one read of the group's
#                            shared page run (members masked in),
#                            folding into a separate accumulator.
#
# Every class folds pages with the same :func:`_online_fold`; row
# partials come out per row, the group phase comes out once, and the
# two merge EXACTLY on the host via flash-decoding log-sum-exp
# (:func:`~llm_consensus_tpu.ops.attention.merge_decode_partials`) —
# bit-for-bit the arithmetic of the two-phase kernels this replaces.
# The pools stay in HBM and a step walks its row's LIVE pages only
# (from the suffix start or the sliding window's edge up to the fill; a
# group's shared run), two pool pages a tile of 128 keys, copied through
# a two-slot buffer with the next tile's pages in flight: a call's time
# follows the pages its rows hold, not the table's width (a grid step
# costs 0.16 us on a v5e even when it folds nothing, and a table has 48
# columns a row).
#
# Three static layouts share the body (there is one kernel, not three):
# the serving pool [n_pages, page, Hkv, D]; the dense int8 head-major
# cache [B, Hkv, S, D] (+ scales), viewed as identity-tabled pages; and
# the STACKED int8 cache [L, B, Hkv, S, D] with the layer index riding
# scalar prefetch into the page copies. The dense bf16 cache needs no
# layout of its own — [B, S, Hkv, D] reshapes into pool pages for free.
# The XLA reference (ops.attention.ragged_paged_attention_reference) is
# the parity oracle and the non-Pallas path.


_LATENT_VMEM_BYTES = 64 * 1024 * 1024  # of a v5e core's 128 MiB


def _sp_block(s: int, cap: int = 128) -> int:
    """Largest divisor of ``s`` <= cap — the S-axis page width the
    DENSE-cache wrappers use to view a contiguous cache as pool pages.

    The cap trades DMA size against skip granularity: the suffix pass
    can only skip whole blocks, so a prefix shorter than one block
    saves nothing there while the group phase still pays one extra
    read of the prefix region — a bounded overhead of < blk slots per
    row plus one prefix read, flipping to a win as soon as the prefix
    spans a block (the canonical fan-out prompt buckets are >= 128).
    128 keeps the blocks at lane width; the paged variant's unit is
    the pool page and needs none of this.
    """
    blk = min(cap, s)
    while s % blk:
        blk -= 1
    return blk


#: bfloat16 terms the softmax weights ``p`` are split into before the
#: value product on bf16 pages. ``p`` is the fold's one operand with
#: float32 digits; hi = bf16(p), mid = bf16(p - hi) are exact bf16
#: values whose products with a bf16 ``v`` are exact in the f32
#: accumulator, so n terms carry ~8n of p's 24 bits. Measured on a v5e
#: (PERF.md §6, PR 33, step 0): the f32 product this replaced ran at
#: Mosaic's default precision, which rounds BOTH operands to bf16 — one
#: term reproduces its outputs to the digit (1e-3 of the float64
#: oracle); two read 3e-6 and are no slower than one on any case
#: timed (faster on the dense pools), so two is what ships. One is the
#: floor: fewer digits than the kernel had is a precision decision.
_P_TERMS = 2


def _bf16_terms(p, n: int) -> list:
    """``p`` (f32) as ``n`` bf16 terms, largest first, summing to it."""
    terms = [p.astype(jnp.bfloat16)]
    for _ in range(n - 1):
        p = p - terms[-1].astype(jnp.float32)
        terms.append(p.astype(jnp.bfloat16))
    return terms


def _online_fold(m_ref, l_ref, acc_ref, idx, scores, v, v_row_scale=None):
    """Fold one score block into running (m, l, acc) softmax state.

    ``idx`` selects the scratch slice (slice or int); scores [R, blk]
    fp32 (already masked to -inf outside the live range); v [blk, D].
    A bfloat16 ``v`` is used as it is: ``p`` meets it as
    :data:`_P_TERMS` bf16 terms, each product one pass of the MXU into
    the f32 accumulator. Any other ``v`` is widened and takes the f32
    product. ``v_row_scale`` [1, blk]: per-slot dequant scale folded
    into the VALUE product only (the l denominator stays the true
    softmax sum) — the same linear-dequant trick as :func:`_q8_attend`.
    Every program class of the ragged kernel folds through this one
    function.
    """
    m_prev = m_ref[idx]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(scores - m_safe)
    alpha = jnp.where(m_prev <= _NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
    l_ref[idx] = l_ref[idx] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if v.dtype == jnp.bfloat16:
        pv = functools.reduce(
            jnp.add,
            [
                jax.lax.dot_general(
                    term,
                    v,
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32,
                )
                for term in _bf16_terms(p, _P_TERMS)
            ],
        )
    else:
        pv = jax.lax.dot_general(
            p if v_row_scale is None else p * v_row_scale,
            v.astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    acc_ref[idx] = acc_ref[idx] * alpha + pv
    m_ref[idx] = m_new


def _pool_head(ref, head: int, dtype):
    """One kv head's [pg, D] slab, as ``dtype`` (the pool's own, or
    f32), from a [1, pg, Hkv, D] pool block.

    The pool keeps kv heads on the second-minor axis, so a head's rows
    are a stride-Hkv walk over the block viewed as [pg * Hkv, D] — the
    addressing of jax's own TPU ragged paged attention kernel. Mosaic
    strides 32-bit rows only: a bf16 pool is read as uint32 words (two
    adjacent heads of one token per word) and the wanted half is
    shifted into an f32's high bits, which is exactly bf16 -> f32;
    narrowing those words back to bf16 drops sixteen zero bits. An odd
    head count cannot pair up and takes the plain indexed load.
    """
    _, pg, hkv, d = ref.shape
    packing = 4 // ref.dtype.itemsize
    if hkv == 1:
        return ref[0, :, 0, :].astype(dtype)
    if packing == 1:
        return ref.at[0].reshape(pg * hkv, d)[head::hkv, :]
    if packing != 2 or hkv % 2:
        return ref[0, :, head, :].astype(dtype)
    words = ref.at[0].reshape(pg * hkv, d).bitcast(jnp.uint32)
    w = words[head // 2 :: hkv // 2, :]
    bits = w << 16 if head % 2 == 0 else w & jnp.uint32(0xFFFF0000)
    return pltpu.bitcast(bits, jnp.float32).astype(dtype)


def _ragged_kernel(
    *refs,
    scale: float,
    b: int,
    hkv: int,
    g: int,
    d: int,
    nc: int,
    cq: int,
    nq: int,
    gm: int,
    pg: int,
    p_per: int,
    npp: int,
    window: int,
    quant: bool,
    stacked: bool,
    fold: int,
    dv: int = 0,
):
    """One program-class row of the ragged kernel: a decode row, a
    chunk lane or a group, walking ITS OWN live pages.

    The grid is one step a row. The pools stay in HBM; a step computes
    its row's live page range from the scalar-prefetched lengths (past
    the suffix start, under the fill, inside the sliding window; a
    group's shared run) and loops over it with a dynamic trip count,
    ``fold`` pages an iteration: each copied from ``pool[layer,
    table[row, j]]`` into its part of a two-slot VMEM buffer, side by
    side, so that an iteration folds ONE tile of ``fold * pg`` keys
    (128 on the pool's 64-token pages: the score tile fills its lanes
    and the two products the MXU), the next tile's pages in flight
    meanwhile. A row with no live page costs its one grid step,
    whatever the table's width.

    ``dv`` (static, default 0 = off): the LATENT pool of an MLA model.
    There is one key a token, [pg, d] with no head axis, shared by all
    ``g`` query heads (``hkv`` is 1), and no value plane: the value is
    the key's first ``dv`` lanes, so a page is read once and used as
    both. Accumulators and outputs are ``dv`` wide.

    ``nq`` (static, default 1): queries per DECODE row. > 1 is the
    speculative-verify lane (PR 9): row b carries its previous token
    plus k draft tokens at positions ``kvlen[b] - nq + i``, masked by
    the same ragged-causal rule as the chunk lane — a verify row IS a
    chunk row over the row's own table, which is why the one kernel
    body serves both.

    ``refs`` is parsed positionally by the same static layout the
    wrapper builds: scalar prefetch ([layer?], tbl, kvlen, sstart,
    [rep, gend]), VMEM inputs ([gid_rows, wlo_rows?], q_dec, [q_chunk?],
    [q_all?]), the pools in HBM (K(+scales), V(+scales)), outputs
    (decode partials, [chunk out?], [group partials?]), then scratch:
    row state, [group state], one two-slot page buffer a plane and
    their DMA semaphores (a plane, a slot, a page of the tile). Row
    state is re-initialized at every row; the
    group accumulator persists across all group programs (they run
    last) and is written once at the very last program.

    Shapes are chosen for Mosaic, not for brevity: every per-head
    quantity keeps the kv head on a LEADING axis (scratch
    [Hkv, rows, ·], outputs [·, Hkv, rows, ·]) so a head's slab is a
    tile-aligned view and nothing is reshaped across the (sublane,
    lane) dims in-kernel; masks are built 2-D from iotas; queries
    arrive in the dtype the fold's products take (bf16 on a bf16 pool,
    else f32: ``_fold``).
    """
    i = 0
    if stacked:
        layer_ref = refs[0]
        i += 1
    tbl_ref, kvlen_ref, sstart_ref = refs[i : i + 3]
    i += 3
    if gm:
        rep_ref, gend_ref = refs[i : i + 2]
        i += 2
        gid_ref, wlo_ref = refs[i : i + 2]
        i += 2
    q_dec_ref = refs[i]
    i += 1
    if nc:
        q_chunk_ref = refs[i]
        i += 1
    if gm:
        q_all_ref = refs[i]
        i += 1
    n_planes = 4 if quant else 1 if dv else 2
    pools = refs[i : i + n_planes]
    i += n_planes
    md_ref, ld_ref, od_ref = refs[i : i + 3]
    i += 3
    if nc:
        oc_ref = refs[i]
        i += 1
    if gm:
        mg_ref, lg_ref, og_ref = refs[i : i + 3]
        i += 3
    m_s, l_s, acc_s = refs[i : i + 3]
    i += 3
    if gm:
        m2_s, l2_s, acc2_s = refs[i : i + 3]
        i += 3
    bufs = refs[i : i + n_planes]
    sem = refs[i + n_planes]

    s = pl.program_id(0)
    R = b + nc
    total = R + gm
    tk = fold * pg  # keys a tile

    def _page_src(plane, page):
        """Plane ``plane``'s slab of pool page ``page``, in HBM. The
        int8 caches are identity-tabled virtual pages: page p is slots
        [(p % npp) * pg, + pg) of cache row p // npp, every kv head."""
        at = (layer_ref[0],) if stacked else ()
        if not quant:
            return pools[plane].at[(*at, page)]
        at += (jax.lax.div(page, jnp.int32(npp)), slice(None))
        slots = pl.ds(jax.lax.rem(page, jnp.int32(npp)) * pg, pg)
        if plane % 2:  # a scale plane [.., Hkv, S]
            return pools[plane].at[(*at, slots)]
        return pools[plane].at[(*at, slots, slice(None))]

    def _page_dst(plane, slot, part):
        """Where page ``part`` of a tile lands in buffer slot ``slot``."""
        if quant:
            return bufs[plane].at[slot]
        return bufs[plane].at[slot, pl.ds(part * pg, pg)]

    def _walk(row, j_lo, j_hi, fold_tile):
        """``fold_tile(j, slot)`` for every tile of ``fold`` pages j, j +
        1, .. that covers pages j_lo <= j < j_hi of table row ``row``,
        ascending, each waited for in buffer slot ``slot`` with the next
        one's copies already started. Where the last tile runs past
        j_hi it holds the row's last page again: finite values under
        slots that lie past every fill and run, which each mask drops."""
        n = jax.lax.div(j_hi - j_lo + (fold - 1), jnp.int32(fold))

        def copies(t, slot):
            out = []
            for part in range(fold):
                j = jnp.minimum(j_lo + t * fold + part, j_hi - 1)
                page = tbl_ref[row * p_per + j]
                out += [
                    pltpu.make_async_copy(
                        _page_src(plane, page),
                        _page_dst(plane, slot, part),
                        sem.at[plane, slot, part],
                    )
                    for plane in range(n_planes)
                ]
            return out

        @pl.when(n > 0)
        def _start_first():
            for copy in copies(0, 0):
                copy.start()

        def step(t, carry):
            slot = jax.lax.rem(t, 2)

            @pl.when(t + 1 < n)
            def _start_next():
                for copy in copies(t + 1, 1 - slot):
                    copy.start()

            for copy in copies(t, slot):
                copy.wait()
            fold_tile(j_lo + t * fold, slot)
            return carry

        jax.lax.fori_loop(0, n, step, 0)

    def _pages(lo, hi):
        """The table columns j that hold some of slots [lo, hi), as a
        range: (j + 1) * pg > lo and j * pg < hi."""
        page = jnp.int32(pg)  # lengths are >= 0: lax.div is the floor
        return jax.lax.div(lo, page), jnp.minimum(
            jax.lax.div(hi + (pg - 1), page), p_per
        )

    def _kv_head(plane, slot, head, dtype):
        """The tile in buffer slot ``slot``: one kv head's K (``plane``
        0) or V slab [tk, D], plus its [1, tk] dequant row (None for
        the pool layout, whose slab comes as ``dtype``)."""
        if quant:
            return (
                bufs[2 * plane][slot, head],
                bufs[2 * plane + 1][slot, pl.ds(head, 1), :],
            )
        return _pool_head(bufs[plane].at[pl.ds(slot, 1)], head, dtype), None

    def _fold(idx, q, head, mask, slot, mr, lr, ar):
        # The operands' width is the query's: bf16 (the wrapper sends it
        # so on a bf16 pool alone) meets the page's own bf16 values in
        # one pass of the MXU, each product exact in the f32 it
        # accumulates in; an f32 query widens the page and takes the f32
        # product at the process's default precision, as it always did.
        if dv:
            k = bufs[0][slot].astype(q.dtype)  # [tk, d]: the tile, once
            v = k[:, :dv]
            ks = vs = None
        else:
            k, ks = _kv_head(0, slot, head, q.dtype)
            v, vs = _kv_head(1, slot, head, q.dtype)
        narrow = q.dtype == jnp.bfloat16
        scores = jax.lax.dot_general(
            q,
            k.astype(q.dtype),  # the int8 layouts' alone is a change
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT if narrow else None,
            preferred_element_type=jnp.float32,
        ) * (scale if ks is None else ks * scale)
        scores = jnp.where(mask, scores, _NEG_INF)
        _online_fold(mr, lr, ar, idx, scores, v, v_row_scale=vs)

    def _init_row():
        # Row scratch: shared by decode and chunk programs, one a step.
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def _causal_mask(n, qbase, lo, j):
        """[n * g, tk] ragged-causal mask of the tile that starts at
        page j for n queries at absolute positions qbase + i (rows
        (query, g)-ordered): query i sees slots <= its own position —
        chunk_decode_attention's rule; n == 1 is the classic slot <
        valid decode mask."""
        row = jax.lax.broadcasted_iota(jnp.int32, (n * g, tk), 0)
        qpos = qbase + (row // g if g > 1 else row)
        slot = j * pg + jax.lax.broadcasted_iota(jnp.int32, (n * g, tk), 1)
        mask = (slot <= qpos) & (slot >= lo)
        if window > 0:
            mask &= slot > qpos - window
        return mask

    def _row(row, n, q_ref):
        """A decode/verify row or the chunk lane: ``n`` queries ending
        at the row's fill, over the row's own pages."""
        _init_row()
        valid = kvlen_ref[row]
        qbase = valid - n  # first query's absolute position
        lo = sstart_ref[row]
        lo_all = lo
        if window > 0:
            # Sliding window: query i sits at qbase + i and sees slots
            # (qbase + i - window, qbase + i] — the union of the n
            # windows starts at the FIRST query's edge (n == 1 reduces
            # to ops.attention.decode_attention's rule).
            lo_all = jnp.maximum(lo, qbase + 1 - window)

        def fold_tile(j, slot):
            # The cache so far plus (a chunk, verify row) the row itself.
            mask = _causal_mask(n, qbase, lo, j)
            for head in range(hkv):  # static unroll over kv heads
                _fold(
                    (head, slice(0, n * g)),
                    q_ref[0, head],
                    head,
                    mask,
                    slot,
                    m_s,
                    l_s,
                    acc_s,
                )

        _walk(row, *_pages(lo_all, valid), fold_tile)

    # Slices, never [...]: the row scratch is sized for the WIDER of
    # the chunk lane (cq) and the decode/verify lane (nq) — each lane's
    # rows are the leading n * g of every head.

    @pl.when(s < b)
    def _decode_row():
        _row(s, nq, q_dec_ref)
        l = l_s[:, 0 : nq * g]
        md_ref[0] = m_s[:, 0 : nq * g]
        ld_ref[0] = l
        od_ref[0] = acc_s[:, 0 : nq * g] / jnp.maximum(l, 1e-30)

    if nc:

        @pl.when((s >= b) & (s < R))
        def _chunk_row():
            _row(s, cq, q_chunk_ref)
            oc_ref[0] = acc_s[:, 0 : cq * g] / jnp.maximum(
                l_s[:, 0 : cq * g], 1e-30
            )

    if gm:
        # Group programs run LAST; their accumulator spans all of them.
        @pl.when(s == R)
        def _init_group():
            m2_s[...] = jnp.full((hkv, b * nq * g, 1), _NEG_INF, jnp.float32)
            l2_s[...] = jnp.zeros((hkv, b * nq * g, 1), jnp.float32)
            acc2_s[...] = jnp.zeros(acc2_s.shape, jnp.float32)

        @pl.when(s >= R)
        def _group():
            gi = s - R
            ge = gend_ref[gi]

            def fold_tile(j, slot):
                at = j * pg + jax.lax.broadcasted_iota(
                    jnp.int32, (b * nq * g, tk), 1
                )
                # Every decode query sits past the shared run's end
                # (shared pages cover prompt prefixes only), so the
                # causal limit never binds here — mask is membership +
                # run extent, for all nq queries alike.
                mask = (gid_ref[...] == gi) & (at < ge)
                if window > 0:
                    # Per-member, per-query window edge (the wrapper
                    # precomputes it per stacked row): members of one
                    # group can sit at different fills, and the nq
                    # verify queries of one member at different
                    # positions.
                    mask &= at >= wlo_ref[...]
                for head in range(hkv):  # static unroll over kv heads
                    _fold(
                        head, q_all_ref[head], head, mask, slot,
                        m2_s, l2_s, acc2_s,
                    )

            _walk(rep_ref[gi], *_pages(jnp.int32(0), ge), fold_tile)

        @pl.when(s == total - 1)
        def _write_group():
            l = l2_s[...]
            mg_ref[...] = m2_s[...]
            lg_ref[...] = l
            og_ref[...] = acc2_s[...] / jnp.maximum(l, 1e-30)


def _ragged_attention(
    q_dec,
    k_kv,
    v_kv,
    page_table,
    kv_len,
    suffix_start,
    *,
    pg: int,
    q_chunk=None,
    gid=None,
    rep=None,
    gend=None,
    window: int = 0,
    k_scale=None,
    v_scale=None,
    layer=None,
    scale: float | None = None,
    latent_dv: int = 0,
    out_dtype=None,
    interpret: bool | None = None,
):
    """Assemble and launch ONE ragged program; merge group partials.

    q_dec: [B, H, D] (one query per decode row) or [B, NQ, H, D]
    (NQ-query verify rows, PR 9 — queries at kv_len - NQ + i, the
    chunk lane's ragged-causal rule per row); page_table: [B + nc, P]
    (rows B.. are the chunk lanes' tables when ``q_chunk`` [nc, C, H, D]
    rides along); kv_len/suffix_start: [B + nc]. K/V layout is static: the
    pool [n_pages, pg, Hkv, D] (``k_scale`` None), the int8 head-major
    cache [B, Hkv, S, D] with [B, Hkv, S] scales, or either of them
    stacked over layers — pools [L, n_pages, pg, Hkv, D], int8 cache
    [L, B, Hkv, S, D] — with ``layer`` a traced index that rides scalar
    prefetch into the kernel's page copies, so the pages it folds are
    the unstacked layout's. The dense layouts are addressed as
    identity-tabled virtual pages of width ``pg``. Returns out_dec
    shaped like q_dec (and out_chunk [nc, C, H, D] when ``q_chunk``) in
    ``out_dtype`` (default q's; the kernel's own outputs are float32).

    ``latent_dv`` > 0: ``k_kv`` is the latent pool [n_pages, pg, D]
    (stacked: [L, n_pages, pg, D]) of an MLA model and ``v_kv`` is
    ignored — one key a token for all H heads, value = its first
    ``latent_dv`` lanes; outputs are [.., H, latent_dv]. ``scale``
    overrides ``D ** -0.5``.
    """
    squeeze_nq = q_dec.ndim == 3
    if squeeze_nq:
        b, h, d = q_dec.shape
        nq = 1
    else:
        b, nq, h, d = q_dec.shape
    quant = k_scale is not None
    stacked = layer is not None
    if quant:
        s_len = k_kv.shape[-2]
        hkv = k_kv.shape[-3]
        npp = s_len // pg
        if s_len % pg:
            raise ValueError(f"cache len {s_len} not a multiple of {pg}")
    elif latent_dv:
        hkv = 1
        npp = 0  # unused
    else:
        hkv = k_kv.shape[-2]
        npp = 0  # unused
    dv = latent_dv or d
    g = h // hkv
    nc = 0 if q_chunk is None else q_chunk.shape[0]
    cq = q_chunk.shape[1] if nc else 1
    gm = 0 if gid is None else int(rep.shape[0])
    p_per = page_table.shape[1]
    R = b + nc
    total = R + gm
    if interpret is None:
        interpret = interpret_default()
    if scale is None:
        scale = d**-0.5
    if out_dtype is None:
        out_dtype = q_dec.dtype

    kvlen = kv_len.astype(jnp.int32)
    sstart = suffix_start.astype(jnp.int32)
    pf = []
    if stacked:
        pf.append(jnp.atleast_1d(layer).astype(jnp.int32))
    pf += [page_table.reshape(-1).astype(jnp.int32), kvlen, sstart]
    if gm:
        pf += [rep.astype(jnp.int32), gend.astype(jnp.int32)]
    inputs = []
    in_specs = []
    if gm:
        # Per STACKED group row (b, nq, g)-ordered, like q_all below:
        # its group id and its sliding-window low edge — built here so
        # the kernel compares [rows, 1] columns and reshapes nothing.
        rows = b * nq * g
        qoff = jnp.tile(jnp.repeat(jnp.arange(nq, dtype=jnp.int32), g), b)
        wlo = jnp.repeat(kvlen[:b], nq * g) - nq + qoff + 1 - window
        for col in (jnp.repeat(gid.astype(jnp.int32), nq * g), wlo):
            inputs.append(col.reshape(rows, 1))
            in_specs.append(
                pl.BlockSpec((rows, 1), lambda s, *pf: (0, 0))
            )
    # Per-row q block rows are (nq, g)-ordered — the order the fold's
    # mask and the write-out both assume. The queries' dtype is the
    # fold's operand width: bf16 queries on a bf16 pool ride in as they
    # are and meet the pages' own values on the MXU; anything else (an
    # f32 pool or query, whose digits narrowing would lose; the int8
    # layouts, whose scales ride the products) is folded in f32.
    narrow = not quant and q_dec.dtype == k_kv.dtype == jnp.bfloat16
    q_dtype = jnp.bfloat16 if narrow else jnp.float32
    q4 = q_dec.astype(q_dtype).reshape(b, nq, hkv, g, d)
    inputs.append(q4.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nq * g, d))
    in_specs.append(
        pl.BlockSpec(
            (1, hkv, nq * g, d),
            lambda s, *pf: (jnp.where(s < b, s, 0), 0, 0, 0),
        )
    )
    if nc:
        # One lane's block at a time: the row's own while a chunk row
        # runs, an end lane's (already resident) otherwise.
        inputs.append(
            q_chunk.astype(q_dtype)
            .reshape(nc, cq, hkv, g, d)
            .transpose(0, 2, 1, 3, 4)
            .reshape(nc, hkv, cq * g, d)
        )
        in_specs.append(
            pl.BlockSpec(
                (1, hkv, cq * g, d),
                lambda s, *pf: (jnp.clip(s - b, 0, nc - 1), 0, 0, 0),
            )
        )
    if gm:
        inputs.append(
            q4.transpose(2, 0, 1, 3, 4).reshape(hkv, b * nq * g, d)
        )
        in_specs.append(
            pl.BlockSpec(
                (hkv, b * nq * g, d), lambda s, *pf: (0, 0, 0)
            )
        )
    # The pools stay where they are, in HBM: the kernel copies the pages
    # a row holds, a tile at a time, into its own two-slot buffers (one a
    # plane; a page of a plane has the same shape stacked or not). A
    # tile is two pool pages side by side (128 keys of the serving
    # pool's 64-token pages); the int8 caches' virtual pages are 128
    # slots wide already and head-major, and stay one a tile.
    fold = 1 if quant else 2
    if quant:
        planes = [k_kv, k_scale, v_kv, v_scale]
        tile_shapes = [(hkv, pg, d), (hkv, pg)] * 2
    elif latent_dv:
        planes, tile_shapes = [k_kv], [(fold * pg, d)]
    else:
        planes, tile_shapes = [k_kv, v_kv], [(fold * pg, hkv, d)] * 2
    inputs += planes
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(planes)

    # Outputs. Row partials are blocked per row with one TRASH block
    # (index b / index nc) absorbing the write-backs of programs that
    # own a different class's output — an output block revisited after
    # its owner moved on would otherwise land stale buffer contents.
    def _dec_out_map(s, *pf):
        return (jnp.where(s < b, s, b), 0, 0, 0)

    def _out(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    out_shapes = [
        _out(b + 1, hkv, nq * g, 1),
        _out(b + 1, hkv, nq * g, 1),
        _out(b + 1, hkv, nq * g, dv),
    ]
    out_specs = [
        pl.BlockSpec((1, hkv, nq * g, 1), _dec_out_map),
        pl.BlockSpec((1, hkv, nq * g, 1), _dec_out_map),
        pl.BlockSpec((1, hkv, nq * g, dv), _dec_out_map),
    ]
    if nc:
        # A chunk lane never meets a group partial, so only its
        # normalized output leaves the kernel.
        out_shapes.append(_out(nc + 1, hkv, cq * g, dv))
        out_specs.append(
            pl.BlockSpec(
                (1, hkv, cq * g, dv),
                lambda s, *pf: (
                    jnp.where((s >= b) & (s < R), s - b, nc), 0, 0, 0
                ),
            )
        )
    if gm:
        out_shapes += [
            _out(hkv, b * nq * g, 1),
            _out(hkv, b * nq * g, 1),
            _out(hkv, b * nq * g, dv),
        ]
        out_specs += [
            pl.BlockSpec((hkv, b * nq * g, 1), lambda s, *pf: (0, 0, 0)),
            pl.BlockSpec((hkv, b * nq * g, 1), lambda s, *pf: (0, 0, 0)),
            pl.BlockSpec((hkv, b * nq * g, dv), lambda s, *pf: (0, 0, 0)),
        ]

    qs = max(nq, cq if nc else 1)
    scratch = [
        pltpu.VMEM((hkv, qs * g, 1), jnp.float32),
        pltpu.VMEM((hkv, qs * g, 1), jnp.float32),
        pltpu.VMEM((hkv, qs * g, dv), jnp.float32),
    ]
    if gm:
        scratch += [
            pltpu.VMEM((hkv, b * nq * g, 1), jnp.float32),
            pltpu.VMEM((hkv, b * nq * g, 1), jnp.float32),
            pltpu.VMEM((hkv, b * nq * g, dv), jnp.float32),
        ]
    scratch += [
        pltpu.VMEM((2, *shape), plane.dtype)
        for shape, plane in zip(tile_shapes, planes)
    ]
    scratch.append(pltpu.SemaphoreType.DMA((len(planes), 2, fold)))
    # A latent chunk lane stacks 16 heads on every query: 64 queries are
    # 1,024 rows of 640 lanes in (bf16 on a bf16 pool), 512 f32 lanes
    # out and 512 of accumulator, double-buffered — past Mosaic's
    # default 16 MiB of scoped VMEM.
    params = (
        dict(compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_LATENT_VMEM_BYTES))
        if latent_dv
        else {}
    )

    outs = pl.pallas_call(
        functools.partial(
            _ragged_kernel,
            scale=scale,
            b=b,
            hkv=hkv,
            g=g,
            d=d,
            nc=nc,
            cq=cq,
            nq=nq,
            gm=gm,
            pg=pg,
            p_per=p_per,
            npp=npp,
            window=window,
            quant=quant,
            stacked=stacked,
            fold=fold,
            dv=latent_dv,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(pf),
            grid=(total,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=tuple(out_shapes),
        interpret=interpret,
        name="ragged_attention",
        **params,
    )(*pf, *inputs)

    md, ld, od = outs[0][:b], outs[1][:b], outs[2][:b]
    od5 = od.reshape(b, hkv, nq, g, dv)
    if gm:
        from llm_consensus_tpu.ops.attention import merge_decode_partials

        mg, lg, og = outs[-3], outs[-2], outs[-1]
        m1r = mg.reshape(hkv, b, nq, g, 1).transpose(1, 0, 2, 3, 4)
        l1r = lg.reshape(hkv, b, nq, g, 1).transpose(1, 0, 2, 3, 4)
        o1r = og.reshape(hkv, b, nq, g, dv).transpose(1, 0, 2, 3, 4)
        m2r = md.reshape(b, hkv, nq, g, 1)
        l2r = ld.reshape(b, hkv, nq, g, 1)
        out5 = merge_decode_partials(m1r, l1r, o1r, m2r, l2r, od5)
    else:
        out5 = od5
    out_dec = (
        out5.transpose(0, 2, 1, 3, 4)
        .reshape(b, nq, h, dv)
        .astype(out_dtype)
    )
    if squeeze_nq:
        out_dec = out_dec[:, 0]
    if not nc:
        return out_dec
    oc = outs[3][:nc]  # [nc, Hkv, cq*G, D]
    out_chunk = (
        oc.reshape(nc, hkv, cq, g, dv)
        .transpose(0, 2, 1, 3, 4)
        .reshape(nc, cq, h, dv)
        .astype(out_dtype)
    )
    return out_dec, out_chunk


def ragged_paged_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    valid_len: jnp.ndarray,
    *,
    q_chunk: jnp.ndarray | None = None,
    chunk_table: jnp.ndarray | None = None,
    chunk_start=None,
    groups: tuple | None = None,
    window: int = 0,
    layer=None,
    scale: float | None = None,
    latent_dv: int = 0,
    out_dtype=None,
    interpret: bool | None = None,
):
    """Mixed prefill+decode attention over the page pool — ONE program.

    q: [B, H, D] decode-row queries, or [B, NQ, H, D] NQ-token
    speculative VERIFY rows (PR 9): row b's queries sit at absolute
    positions ``valid_len[b] - NQ + i`` (``valid_len`` stays "tokens
    readable" — the NQ new tokens' K/V already written), masked by the
    chunk lane's ragged-causal rule per row. k_pool/v_pool: [n_pages,
    page, Hkv, D]; page_table: [B, P]; valid_len: [B] tokens readable
    per decode row. With ``layer`` (a traced index) the pools are the
    stacked [L, n_pages, page, Hkv, D] and that layer of them is read
    in place — what the step programs' layer scan passes, since a layer
    sliced out for a Pallas call is a copy of it.

    ``q_chunk`` [L, C, H, D] adds L prefill-chunk rows (lanes), each a
    different sequence's chunk: lane l's C queries sit at absolute
    positions ``chunk_start[l] + i`` and walk ``chunk_table[l]`` ([L, P];
    the chunk's K/V must already be scattered through it), with the
    ragged-causal rule of
    :func:`~llm_consensus_tpu.ops.attention.chunk_decode_attention`.
    A lane with ``chunk_start[l] == -C`` is DEAD: it reads nothing and
    returns zeros. One lane may come without the lane axis (``q_chunk``
    [C, H, D], ``chunk_table`` [P], scalar ``chunk_start``) and
    ``out_chunk`` then has none either.
    ``groups`` = (group_id [B] (-1 ungrouped), group_rep [Gm],
    group_end [Gm] tokens, shared_start [B]) — decode rows sharing a
    prefix page run read it ONCE per group (all member queries
    stacked), each row's own walk starting at ``shared_start``; the
    partials merge exactly via flash-decoding LSE. ``window`` > 0
    applies sliding-window masking to every row kind. Returns
    out_dec [B, H, D] (and out_chunk, shaped like ``q_chunk`` but
    ``latent_dv`` wide on a latent pool, when ``q_chunk``).

    ``latent_dv`` > 0: the latent pool of an MLA model, [n_pages, page,
    D] (stacked [L, n_pages, page, D]); ``v_pool`` is ignored, each page
    is read once as key (D lanes) and value (its first ``latent_dv``),
    and outputs are [.., H, latent_dv]. ``scale`` overrides
    ``D ** -0.5``. ``out_dtype`` (default q's): the outputs' dtype —
    float32 returns the kernel's own, unrounded.
    """
    b = q.shape[0]
    pg = k_pool.shape[-2 if latent_dv else -3]
    kvlen = valid_len.astype(jnp.int32)
    if groups is not None:
        gid, rep, gend, sstart = groups
        sstart = sstart.astype(jnp.int32)
    else:
        gid = rep = gend = None
        sstart = jnp.zeros((b,), jnp.int32)
    tbl = page_table
    one_lane = q_chunk is not None and q_chunk.ndim == 3
    if one_lane:
        q_chunk, chunk_table = q_chunk[None], chunk_table[None]
    if q_chunk is not None:
        nc, cq = q_chunk.shape[:2]
        tbl = jnp.concatenate(
            [page_table.astype(jnp.int32), chunk_table.astype(jnp.int32)]
        )
        kvlen = jnp.concatenate(
            [kvlen, jnp.asarray(chunk_start, jnp.int32).reshape(nc) + cq]
        )
        sstart = jnp.concatenate([sstart, jnp.zeros((nc,), jnp.int32)])
    out = _ragged_attention(
        q,
        k_pool,
        v_pool,
        tbl,
        kvlen,
        sstart,
        pg=pg,
        q_chunk=q_chunk,
        gid=gid,
        rep=rep,
        gend=gend,
        window=window,
        layer=layer,
        scale=scale,
        latent_dv=latent_dv,
        out_dtype=out_dtype,
        interpret=interpret,
    )
    return (out[0], out[1][0]) if one_lane else out


def ragged_paged_attention_sharded(
    mesh,
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    valid_len: jnp.ndarray,
    *,
    q_chunk: jnp.ndarray | None = None,
    chunk_table: jnp.ndarray | None = None,
    chunk_start=None,
    groups: tuple | None = None,
    window: int = 0,
    interpret: bool | None = None,
):
    """:func:`ragged_paged_attention` under ``shard_map`` on a dp×mp
    mesh (PR 13) — the serving kernel's mesh-native lowering.

    Partitioning: kv heads over ``model`` (each shard's kernel runs the
    same body over Hkv/mp heads — GQA keeps K/V read once per local
    head program); decode rows, their page tables, and the page pool
    over ``data``. The batcher's slot→shard page affinity is the
    correctness invariant: every row's table references only pages of
    its own data shard, so per-shard the GLOBAL page ids rebase to
    local pool indices (``id - shard * local_pages``, clamped — NULL
    and foreign ids sit only past a row's fill, in columns the kernel
    does not walk; the chunk lane's on a shard that does not own it
    clamp to a harmless local read). Shared-prefix groups live entirely on one
    shard for the same reason (one prefix registry per shard), so the
    group phase rides along by rebasing ``group_rep``: a shard that
    holds no members of group g folds an all-masked read (l = 0) that
    the LSE merge ignores. The prefill-chunk lane's pages live on its
    admitting slot's shard; every shard folds the lane against its
    local pool and the owner's result is selected with one psum over
    ``data`` (non-owners contribute exact zeros).

    Semantics are identical to the single-device kernel — this wrapper
    only decides which shard reads which bytes.
    """
    from jax.sharding import PartitionSpec as P

    has_chunk = q_chunk is not None
    has_groups = groups is not None
    q_spec = (
        P("data", None, "model", None)
        if q.ndim == 4
        else P("data", "model", None)
    )
    pool_spec = P("data", None, "model", None)
    in_specs = [q_spec, pool_spec, pool_spec, P("data", None), P("data")]
    args = [
        q,
        k_pool,
        v_pool,
        page_table.astype(jnp.int32),
        valid_len.astype(jnp.int32),
    ]
    if has_chunk:
        args += [
            q_chunk,
            chunk_table.astype(jnp.int32),
            jnp.asarray(chunk_start, jnp.int32),
        ]
        in_specs += [P(None, "model", None), P(None), P()]
    if has_groups:
        gid, rep, gend, sstart = groups
        args += [
            gid.astype(jnp.int32),
            rep.astype(jnp.int32),
            gend.astype(jnp.int32),
            sstart.astype(jnp.int32),
        ]
        in_specs += [P("data"), P(None), P(None), P("data")]
    out_specs = (q_spec, P(None, "model", None)) if has_chunk else q_spec

    def fn(*a):
        q_l, kp_l, vp_l, tbl_l, val_l = a[:5]
        i = 5
        local_pages = kp_l.shape[0]
        bl = q_l.shape[0]
        didx = jax.lax.axis_index("data")
        poff = didx * local_pages
        tbl = jnp.clip(tbl_l - poff, 0, local_pages - 1)
        qc = ct = cs = None
        if has_chunk:
            qc, ct, cs = a[i : i + 3]
            i += 3
        g_l = None
        if has_groups:
            gid_l, rep_g, gend_g, sst_l = a[i : i + 4]
            g_l = (
                gid_l,
                jnp.clip(rep_g - didx * bl, 0, bl - 1),
                gend_g,
                sst_l,
            )
        if has_chunk:
            out_dec, out_chunk = ragged_paged_attention(
                q_l,
                kp_l,
                vp_l,
                tbl,
                val_l,
                q_chunk=qc,
                chunk_table=jnp.clip(ct - poff, 0, local_pages - 1),
                chunk_start=cs,
                groups=g_l,
                window=window,
                interpret=interpret,
            )
            # Position 0's page identifies the chunk's owner shard (the
            # admitting slot's pool); the other shards folded local
            # garbage under the same masks and are zeroed exactly.
            owner = (ct[0] >= poff) & (ct[0] < poff + local_pages)
            out_chunk = jax.lax.psum(
                jnp.where(owner, out_chunk, jnp.zeros_like(out_chunk)),
                "data",
            )
            return out_dec, out_chunk
        return ragged_paged_attention(
            q_l,
            kp_l,
            vp_l,
            tbl,
            val_l,
            groups=g_l,
            window=window,
            interpret=interpret,
        )

    # check_vma=False: with the check on, jax 0.9.0 wants ``vma=`` on
    # the pallas_call's out_shapes — and then its Pallas interpreter
    # (the CPU tests' path) fails the same check on itself, slicing
    # scalar-prefetch operands that vary over ``data`` with grid indices
    # that do not. Nothing here relies on replication tracking: every
    # output is declared varying by ``out_specs`` and the one collective
    # is the explicit psum above.
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=out_specs,
        check_vma=False,
    )(*args)


# -- thin wrappers: the pre-ragged kernel family ----------------------------
#
# Everything below is signature-compatible with the kernels it replaced
# (PR 3's two-phase family and the plain paged row kernel) but runs the
# ONE ragged kernel body above — same arithmetic, one implementation.


def paged_decode_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    valid_len: jnp.ndarray,
    window: int = 0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Decode attention THROUGH the page table — no pool gather.

    q: [B, H, D]; k_pool/v_pool: [n_pages, page, Hkv, D]; page_table:
    [B, P]; valid_len: [B]. The all-decode, ungrouped case of
    :func:`ragged_paged_attention`.
    """
    return ragged_paged_attention(
        q, k_pool, v_pool, page_table, valid_len,
        window=window, interpret=interpret,
    )


def paged_decode_attention_grouped(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    valid_len: jnp.ndarray,
    group_id: jnp.ndarray,
    group_rep: jnp.ndarray,
    group_pages: jnp.ndarray,
    shared_start: jnp.ndarray,
    window: int = 0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Group-aware paged decode attention (serving hot path).

    Group metadata as built by
    :class:`~llm_consensus_tpu.models.paged_cache.GroupTracker`:
    group_id [B] (-1 ungrouped), group_rep [Gm] (a member row whose
    table phase 1 walks), group_pages [Gm] (pages in the shared run,
    0 = padding), shared_start [B] (tokens the shared phase covers,
    page-aligned). Output-equal to :func:`paged_decode_attention` —
    the grouped read is a bandwidth optimization, not a semantic one.
    Sliding windows now ride through (``window``); the old fallback is
    gone.
    """
    pg = k_pool.shape[1]
    return ragged_paged_attention(
        q,
        k_pool,
        v_pool,
        page_table,
        valid_len,
        groups=(
            group_id,
            group_rep,
            group_pages.astype(jnp.int32) * pg,
            shared_start,
        ),
        window=window,
        interpret=interpret,
    )


def flash_decode_attention_shared_prefix(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    valid_len: jnp.ndarray,
    prefix_len: jnp.ndarray,
    window: int = 0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Shared-prefix decode attention, dense bf16 cache (engine fan-out).

    q: [B, 1, H, D]; k_cache/v_cache: [B, max_len, Hkv, D]; valid_len:
    [B]; prefix_len: traced scalar — every row's slots [0, prefix_len)
    hold identical K/V. The dense cache reshapes (zero-copy) into pool
    pages and the whole batch forms one group of the ragged kernel:
    the prefix region streams once for all rows, each row walks only
    its own suffix blocks. Matches
    :func:`~llm_consensus_tpu.ops.attention.decode_attention_shared_prefix`
    wherever the precondition holds.
    """
    b, _, h, d = q.shape
    s = k_cache.shape[1]
    hkv = k_cache.shape[2]
    blk = _sp_block(s)
    npp = s // blk
    k_pool = k_cache.reshape(b * npp, blk, hkv, d)
    v_pool = v_cache.reshape(b * npp, blk, hkv, d)
    table = jnp.arange(b * npp, dtype=jnp.int32).reshape(b, npp)
    plen = jnp.asarray(prefix_len, jnp.int32)
    out = ragged_paged_attention(
        q[:, 0],
        k_pool,
        v_pool,
        table,
        valid_len,
        groups=(
            jnp.zeros((b,), jnp.int32),
            jnp.zeros((1,), jnp.int32),
            plen.reshape(1),
            jnp.broadcast_to(plen, (b,)),
        ),
        window=window,
        interpret=interpret,
    )
    return out[:, None]


def flash_decode_attention_shared_prefix_q8(
    q: jnp.ndarray,
    k_q: jnp.ndarray,
    k_scale: jnp.ndarray,
    v_q: jnp.ndarray,
    v_scale: jnp.ndarray,
    valid_len: jnp.ndarray,
    prefix_len: jnp.ndarray,
    window: int = 0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Shared-prefix decode attention over the int8 head-major cache.

    q: [B, 1, H, D]; k_q/v_q: [B, Hkv, S, D] int8 (QuantKVCache
    layout, addressed in place as identity-tabled virtual pages — no
    transpose, no dequantized materialization); k_scale/v_scale:
    [B, Hkv, S] f32; valid_len: [B]; prefix_len: traced scalar. Same
    one-group ragged program as the bf16 wrapper with the dequant
    scales folded into scores/values in-register.
    """
    b, _, h, d = q.shape
    hkv, s = k_q.shape[1], k_q.shape[2]
    blk = _sp_block(s)
    npp = s // blk
    table = jnp.arange(b * npp, dtype=jnp.int32).reshape(b, npp)
    plen = jnp.asarray(prefix_len, jnp.int32)
    out = _ragged_attention(
        q[:, 0],
        k_q,
        v_q,
        table,
        valid_len.astype(jnp.int32),
        jnp.broadcast_to(plen, (b,)),
        pg=blk,
        gid=jnp.zeros((b,), jnp.int32),
        rep=jnp.zeros((1,), jnp.int32),
        gend=plen.reshape(1),
        window=window,
        k_scale=k_scale,
        v_scale=v_scale,
        interpret=interpret,
    )
    return out[:, None]


def flash_decode_attention_shared_prefix_q8_stacked(
    q: jnp.ndarray,
    k_q: jnp.ndarray,
    k_scale: jnp.ndarray,
    v_q: jnp.ndarray,
    v_scale: jnp.ndarray,
    valid_len: jnp.ndarray,
    prefix_len: jnp.ndarray,
    layer: jnp.ndarray,
    window: int = 0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Shared-prefix decode attention reading ONE layer of the stacked
    int8 cache — the case that used to FALL BACK to the ungrouped
    stacked kernel. k_q/v_q: [L, B, Hkv, S, D] int8 (the whole stacked
    buffer); k_scale/v_scale: [L, B, Hkv, S]; ``layer`` a traced index
    riding scalar prefetch, exactly like
    :func:`flash_decode_attention_q8_stacked`.
    """
    b, _, h, d = q.shape
    hkv, s = k_q.shape[2], k_q.shape[3]
    blk = _sp_block(s)
    npp = s // blk
    table = jnp.arange(b * npp, dtype=jnp.int32).reshape(b, npp)
    plen = jnp.asarray(prefix_len, jnp.int32)
    out = _ragged_attention(
        q[:, 0],
        k_q,
        v_q,
        table,
        valid_len.astype(jnp.int32),
        jnp.broadcast_to(plen, (b,)),
        pg=blk,
        gid=jnp.zeros((b,), jnp.int32),
        rep=jnp.zeros((1,), jnp.int32),
        gend=plen.reshape(1),
        window=window,
        k_scale=k_scale,
        v_scale=v_scale,
        layer=layer,
        interpret=interpret,
    )
    return out[:, None]
