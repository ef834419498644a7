"""Grouped int8 matmul for a dropless expert layer (Pallas/Mosaic).

A step's tokens reach some of a layer's experts, each with a handful of
rows (80 tokens x 6 experts over 64 experts: ~7 rows an expert). Rows
arrive sorted by expert and padded to whole tiles of ``tm`` rows, one
grid step a tile; the tile's expert rides scalar prefetch into the
weight's index map, so Mosaic DMAs that expert's whole ``[K, N]`` int8
matrix out of the resident ``[L * E, K, N]`` stack — consecutive tiles
of one expert find it already in VMEM — and an expert no token reached
is never read. The int8 tile is widened in registers, as in
:mod:`llm_consensus_tpu.ops.pallas.quant_matmul`: HBM sees int8 once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_consensus_tpu.ops.kernels import interpret_default

# Rows a tile: one packed bf16 sublane tile. Larger tiles pad more rows
# onto experts that hold one or two tokens; the weight read is the cost
# either way.
MOE_TILE = 16

# Contraction rows widened to bf16 at a time: the whole matrix as one
# value would double its VMEM footprint (2.9 MB int8 -> 5.8 MB bf16).
_K_CHUNK = 512

# Two buffers of one [K, N] int8 matrix (2.9 MB at 2048 x 1408), a
# widened chunk and the row tile: past half of Mosaic's default 16 MiB.
_VMEM_BYTES = 32 * 1024 * 1024


def n_tiles_for(n_rows: int, n_groups: int, tm: int = MOE_TILE) -> int:
    """Tiles that hold ``n_rows`` rows split over at most ``n_groups``
    groups, each group padded to whole tiles: every non-empty group
    wastes less than one tile."""
    return n_rows // tm + min(n_groups, n_rows)


def _gmm_kernel(tg_ref, nl_ref, x_ref, w_ref, s_ref, o_ref):
    """One row tile against its group's matrix.

    tg_ref: [n_tiles] group of each tile (read by the index maps);
    nl_ref: [1] live tiles; x_ref: [tm, K] bf16; w_ref: [1, K, N] int8;
    s_ref: [1, 1, N] f32; o_ref: [tm, N]. A dead tile (past the live
    count) names the last live tile's group, so it starts no DMA, and
    computes nothing: its rows are never gathered.
    """
    del tg_ref

    @pl.when(pl.program_id(0) < nl_ref[0])
    def _live():
        k = w_ref.shape[1]
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for k0 in range(0, k, _K_CHUNK):  # static unroll
            k1 = min(k0 + _K_CHUNK, k)
            acc += jax.lax.dot_general(
                x_ref[:, k0:k1],
                w_ref[0, k0:k1, :].astype(jnp.bfloat16),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                # bf16 x bf16 on the MXU, whatever the process's default
                # matmul precision says (Mosaic refuses "highest" here).
                precision=jax.lax.Precision.DEFAULT,
            )
        o_ref[...] = (acc * s_ref[0]).astype(o_ref.dtype)


def moe_grouped_matmul_supported(k: int, n: int) -> bool:
    return k % 128 == 0 and n % 128 == 0


def moe_grouped_matmul(
    x: jnp.ndarray,
    w_q: jnp.ndarray,
    scale: jnp.ndarray,
    tile_group: jnp.ndarray,
    n_live: jnp.ndarray,
    *,
    tm: int = MOE_TILE,
    out_dtype=None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """``x [n_tiles * tm, K]`` (rows sorted by group, each group padded
    to whole tiles) times int8 ``w_q [G, K, N]`` with per-column
    ``scale [G, 1, N]``: tile i is multiplied by matrix
    ``tile_group[i]``. ``n_live`` [1]: tiles that hold rows; the rest
    are skipped and their output rows are undefined. Returns
    ``[n_tiles * tm, N]``.
    """
    m, k = x.shape
    g, k2, n = w_q.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {k} vs {k2}")
    if m % tm:
        raise ValueError(f"{m} rows are not whole tiles of {tm}")
    if interpret is None:
        interpret = interpret_default()
    out_dtype = out_dtype or x.dtype
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec((tm, k), lambda i, tg, nl: (i, 0)),
            pl.BlockSpec((1, k, n), lambda i, tg, nl: (tg[i], 0, 0)),
            pl.BlockSpec((1, 1, n), lambda i, tg, nl: (tg[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, n), lambda i, tg, nl: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
        name="moe_grouped_matmul",
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BYTES),
    )(
        tile_group.astype(jnp.int32),
        jnp.atleast_1d(n_live).astype(jnp.int32),
        x.astype(jnp.bfloat16),
        w_q,
        scale.astype(jnp.float32),
    )
