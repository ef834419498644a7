"""The state-touching half of a Mamba-2 layer as one kernel (Pallas/Mosaic).

A step program's rows — decode rows of one token, chunk lanes of up to
64 — each own a state slot ``[H, P, N]`` float32 in the pool
``[state layer, slot, H, P, N]``. :func:`llm_consensus_tpu.ops.ssm.ssd_terms`
has already turned a row's tokens into a head's five matrices; this
kernel reads the row's start state out of the resident pool (the layer
and the slots ride scalar prefetch into the index maps, as the int8
matmul's layer does), forms

    Y  = M X + Ce S₀ᵀ            [T, P]
    S' = f ∘ S₀ + Xᵀ Bw           [P, N]

a head, and writes ``S'`` back INTO the pool (the output aliases the
input): a state is read once and written once, and nothing pool-shaped
is gathered, scattered or copied around the call. A row may read one
slot and write another (a sequence's first chunk starts from a
registry snapshot's slot, or from slot 0, which holds zeros and is
where rows that carry no request write). Appears in a device trace as
``ssm_scan``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_consensus_tpu.ops.kernels import interpret_default

# Heads a grid step: 8 x [64, 128] f32 states are 256 KB in and out.
_HEADS = 8


def _kernel(layer_ref, sin_ref, sout_ref, x_ref, m_ref, ce_ref, bw_ref,
            f_ref, s_ref, y_ref, so_ref):
    del layer_ref, sin_ref, sout_ref  # read by the index maps
    hi = jax.lax.Precision.HIGHEST
    for h in range(x_ref.shape[0]):  # static unroll over the block's heads
        x, s0 = x_ref[h], s_ref[h]
        y = jnp.dot(m_ref[h], x, preferred_element_type=jnp.float32,
                    precision=hi)
        y += jax.lax.dot_general(
            ce_ref[h], s0, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=hi,
        )
        y_ref[h] = y
        so_ref[h] = f_ref[h] * s0 + jax.lax.dot_general(
            x, bw_ref[h], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=hi,
        )


def ssm_scan(terms: dict, s_pool, layer, slot_in, slot_out, *,
             interpret: bool | None = None):
    """``terms`` (:func:`~llm_consensus_tpu.ops.ssm.ssd_terms`, R rows of
    T tokens, T a multiple of 8) against ``s_pool`` [Ls, slots, H, P, N]
    float32: row r starts from ``s_pool[layer, slot_in[r]]`` and leaves
    its end state in ``s_pool[layer, slot_out[r]]``. Returns (y [R, H,
    T, P] float32, the pool)."""
    x, m, ce, bw, f = (terms[k] for k in ("x", "m", "ce", "bw", "f"))
    r, heads, t, p = x.shape
    n = ce.shape[-1]
    if t % 8:
        raise ValueError(f"{t} tokens a row: pad to a multiple of 8")
    hb = _HEADS if heads % _HEADS == 0 else heads
    if interpret is None:
        interpret = interpret_default()

    def row(i, j, layer, sin, sout):
        return (i, j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(r, heads // hb),
        in_specs=[
            pl.BlockSpec((None, hb, t, p), row),
            pl.BlockSpec((None, hb, t, t), row),
            pl.BlockSpec((None, hb, t, n), row),
            pl.BlockSpec((None, hb, t, n), row),
            pl.BlockSpec((None, hb, 1, n), row),
            pl.BlockSpec(
                (None, None, hb, p, n),
                lambda i, j, layer, sin, sout: (layer[0], sin[i], j, 0, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((None, hb, t, p), row),
            pl.BlockSpec(
                (None, None, hb, p, n),
                lambda i, j, layer, sin, sout: (layer[0], sout[i], j, 0, 0),
            ),
        ],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, heads, t, p), jnp.float32),
            jax.ShapeDtypeStruct(s_pool.shape, s_pool.dtype),
        ],
        # The pool is operand 8 (three scalar-prefetch rows first).
        input_output_aliases={8: 1},
        interpret=interpret,
        name="ssm_scan",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )(
        jnp.atleast_1d(layer).astype(jnp.int32),
        slot_in.astype(jnp.int32),
        slot_out.astype(jnp.int32),
        x, m, ce, bw, f, s_pool,
    )
