"""Fused int8-weight matmul kernel (Pallas/Mosaic).

Why a kernel: XLA:TPU dots read *materialized* operand buffers, so the
weight-only int8 path (``x @ dequantize(w)``) round-trips a bf16 copy of
the weights through HBM — and inside the token-decode ``lax.scan`` XLA
hoists the loop-invariant dequant entirely, making int8 decode no faster
than bf16. This kernel loads int8 tiles straight into VMEM, converts
in-register, and feeds the MXU — per decode step the weights cost half
the HBM traffic of bf16, which is the whole point of
:mod:`llm_consensus_tpu.ops.quant`.

Scope: the M dimension (batch rows) must be small enough that ``x`` fits
VMEM whole — exactly the decode/GEMV regime where weight bandwidth
dominates. Callers fall back to the XLA path for prefill-sized M (there
the dequant is amortized over S columns and XLA's behavior is fine).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_consensus_tpu.ops.kernels import interpret_default


def _pick_block(n: int, target: int = 512, align: int = 128) -> int | None:
    """Largest divisor of n that is a multiple of ``align`` and <= target."""
    best = None
    blk = align
    while blk <= min(n, target):
        if n % blk == 0:
            best = blk
        blk += align
    return best


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref):
    """One N-block program: o = (x @ bf16(w_int8)) * scale.

    x_ref: [M, K] bf16; w_ref: [K, blk_n] int8; s_ref: [1, blk_n] f32;
    o_ref: [M, blk_n].
    """
    w = w_ref[...].astype(jnp.bfloat16)  # in-register dequant (int8 HBM read)
    acc = jax.lax.dot_general(
        x_ref[...],
        w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


def quant_matmul_2d(
    x: jnp.ndarray,
    w_q: jnp.ndarray,
    scale: jnp.ndarray,
    out_dtype=None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """x [M, K] x int8 w_q [K, N] (per-column ``scale`` [1, N]) -> [M, N].

    Raises ValueError when shapes don't tile (callers pre-check with
    :func:`quant_matmul_supported`).
    """
    m, k = x.shape
    k2, n = w_q.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {k} vs {k2}")
    blk_n = _pick_block(n, target=_blk_target(k))
    if blk_n is None:
        raise ValueError(
            f"N={n} (K={k}) has no 128-aligned block within the VMEM budget"
        )
    if interpret is None:
        interpret = interpret_default()
    out_dtype = out_dtype or x.dtype

    return pl.pallas_call(
        functools.partial(_qmm_kernel),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid=(n // blk_n,),
        in_specs=[
            pl.BlockSpec((m, k), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((k, blk_n), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_n), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (m, blk_n), lambda i: (0, i), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
        name="quant_matmul",
    )(x.astype(jnp.bfloat16), w_q, scale.astype(jnp.float32))


# VMEM budget: no kernel here raises Mosaic's default scoped limit
# (16 MiB on a v5e). Live at once: x and the int8 weight tile, each
# double-buffered by the grid pipeline, plus the tile's bf16 copy (twice
# its bytes) — 2 * x + 4 * tile. The caps below bound that at 6 + 8 =
# 14 MiB, leaving the out block and the compiler's scratch their room.
_MAX_M = 256
_MAX_X_BYTES = 3 * 1024 * 1024
_MAX_W_TILE_BYTES = 2 * 1024 * 1024  # int8 K x blk_n


def _blk_target(k: int) -> int:
    """Largest 128-multiple blk_n keeping the K x blk_n int8 tile in
    budget (capped at 512 — wider tiles stop helping)."""
    by_vmem = (_MAX_W_TILE_BYTES // max(k, 1)) // 128 * 128
    return max(128, min(512, by_vmem))


def quant_matmul_supported(m: int, k: int, n: int) -> bool:
    return (
        m <= _MAX_M
        and m * k * 2 <= _MAX_X_BYTES
        and n % 128 == 0
        and k % 128 == 0
        and k * 128 <= _MAX_W_TILE_BYTES  # smallest tile must fit
        and _pick_block(n, target=_blk_target(k)) is not None
    )


# ---------------------------------------------------------------------------
# int4 (packed-nibble) variant
#
# STATUS: numerics verified (interpret mode, tests/test_quant.py); the
# small-shape unpack lowers and runs on real TPU, but full-size compiles
# (K=2048, N=32000) have shown pathological Mosaic compile times on this
# environment's toolchain. The kernel is therefore OPT-IN via
# ops.quant.set_kernel4_enabled(True) — the default int4 path is the jnp
# unpack + XLA dot (capacity win, no decode-bandwidth win).
# ---------------------------------------------------------------------------


def _q4mm_kernel(x_ref, w_ref, s_ref, o_ref):
    """One N-block program: o = (x @ bf16(unpack4(w))) * scale.

    x_ref: [M, K] bf16; w_ref: [K/2, blk_n] int8 (two nibbles/byte,
    low nibbles = rows [0, K/2), high = [K/2, K) — the
    ops.quant.Quantized4Tensor contract); s_ref: [1, blk_n] f32.
    Bit ops run in int32 — int8 shifts don't legalize on Mosaic — and
    the K split becomes TWO dots (x_low @ low + x_high @ high) instead
    of a sublane concat of the unpacked halves.
    """
    k2 = w_ref.shape[0]
    w32 = w_ref[...].astype(jnp.int32)
    low = ((w32 & 0xF) - ((w32 & 0x8) << 1)).astype(jnp.bfloat16)
    nib = (w32 >> 4) & 0xF
    high = (nib - ((nib & 0x8) << 1)).astype(jnp.bfloat16)
    dn = (((1,), (0,)), ((), ()))
    acc = jax.lax.dot_general(
        x_ref[:, :k2], low, dn, preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(
        x_ref[:, k2:], high, dn, preferred_element_type=jnp.float32
    )
    o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


def quant4_matmul_2d(
    x: jnp.ndarray,
    w_q: jnp.ndarray,
    scale: jnp.ndarray,
    out_dtype=None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """x [M, K] x packed-int4 w_q [K/2, N] (per-column ``scale`` [1, N])
    -> [M, N]."""
    m, k = x.shape
    k2, n = w_q.shape
    if k != 2 * k2:
        raise ValueError(f"contraction mismatch {k} vs packed 2*{k2}")
    blk_n = _pick_block(n, target=_blk4_target(k))
    if blk_n is None:
        raise ValueError(
            f"N={n} (K={k}) has no 128-aligned block within the VMEM budget"
        )
    if interpret is None:
        interpret = interpret_default()
    out_dtype = out_dtype or x.dtype

    return pl.pallas_call(
        _q4mm_kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid=(n // blk_n,),
        in_specs=[
            pl.BlockSpec((m, k), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (k2, blk_n), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec((1, blk_n), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (m, blk_n), lambda i: (0, i), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(x.astype(jnp.bfloat16), w_q, scale.astype(jnp.float32))


def _blk4_target(k: int) -> int:
    """blk_n budget for int4: the unpacked bf16 tile (K x blk_n x 2B) is
    4x the packed bytes, so budget against THAT."""
    by_vmem = (_MAX_W_TILE_BYTES // max(2 * k, 1)) // 128 * 128
    return max(128, min(512, by_vmem))


def quant4_matmul_supported(m: int, k: int, n: int) -> bool:
    return (
        m <= _MAX_M
        and m * k * 2 <= _MAX_X_BYTES
        and k % 2 == 0
        and n % 128 == 0
        and (k // 2) % 8 == 0  # packed sublane tiling
        and k % 128 == 0
        and 2 * k * 128 <= _MAX_W_TILE_BYTES  # smallest unpacked tile
        and _pick_block(n, target=_blk4_target(k)) is not None
    )


# ---------------------------------------------------------------------------
# Stacked-weight variant: the layer index rides scalar prefetch
# ---------------------------------------------------------------------------


def _qmm_stacked_kernel(l_ref, x_ref, w_ref, s_ref, o_ref):
    """One N-block program against the [L, K, N] stack.

    l_ref: [1] scalar-prefetch layer index (consumed by the index_maps);
    x_ref: [M, K] bf16; w_ref: [1, K, blk_n] int8 (this layer's tile);
    s_ref: [1, 1, blk_n] f32; o_ref: [M, blk_n].
    """
    w = w_ref[0].astype(jnp.bfloat16)
    acc = jax.lax.dot_general(
        x_ref[...],
        w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = (acc * s_ref[0]).astype(o_ref.dtype)


def quant_matmul_stacked(
    x: jnp.ndarray,
    w_q: jnp.ndarray,
    scale: jnp.ndarray,
    layer: jnp.ndarray,
    out_dtype=None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """x [M, K] x int8 w_q[layer] from the stacked [L, K, N] buffer.

    Inside the token-decode layer scan, a sliced per-layer weight must
    be MATERIALIZED before it can feed ``quant_matmul_2d`` (Pallas
    operands are whole buffers) — XLA copies every layer's int8 weights
    every step. Here the STACK is the operand and the traced ``layer``
    index rides scalar prefetch into the BlockSpec index_maps, so Mosaic
    DMAs each [K, blk_n] tile straight from the resident stacked buffer:
    zero copies, same arithmetic as :func:`quant_matmul_2d`.
    """
    m, k = x.shape
    n_layers, k2, n = w_q.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {k} vs {k2}")
    blk_n = _pick_block(n, target=_blk_target(k))
    if blk_n is None:
        raise ValueError(
            f"N={n} (K={k}) has no 128-aligned block within the VMEM budget"
        )
    if interpret is None:
        interpret = interpret_default()
    out_dtype = out_dtype or x.dtype

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // blk_n,),
        in_specs=[
            pl.BlockSpec((m, k), lambda i, l: (0, 0)),
            pl.BlockSpec((1, k, blk_n), lambda i, l: (l[0], 0, i)),
            pl.BlockSpec((1, 1, blk_n), lambda i, l: (l[0], 0, i)),
        ],
        out_specs=pl.BlockSpec((m, blk_n), lambda i, l: (0, i)),
    )
    return pl.pallas_call(
        _qmm_stacked_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
        name="quant_matmul_stacked",
    )(
        jnp.atleast_1d(layer).astype(jnp.int32),
        x.astype(jnp.bfloat16),
        w_q,
        scale.astype(jnp.float32),
    )
