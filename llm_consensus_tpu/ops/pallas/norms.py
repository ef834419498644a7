"""Fused RMSNorm kernel.

Twin of the jnp reference :func:`llm_consensus_tpu.ops.norms.rms_norm`:
one VMEM pass computes the fp32 mean-square, rsqrt, and the weighted
scale — no intermediate arrays in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_consensus_tpu.ops.kernels import interpret_default


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)  # [blk, D]
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    w = w_ref[:].astype(jnp.float32)  # [1, D] (2-D: Mosaic rejects rank-1 blocks)
    o_ref[:] = (x * inv * w).astype(o_ref.dtype)


def fused_rms_norm(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    eps: float = 1e-5,
    blk: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """RMSNorm over the last axis. x: [..., D]; weight: [D]."""
    if interpret is None:
        interpret = interpret_default()
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    blk = min(blk, n)
    pad = (-n) % blk
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        grid=(x2.shape[0] // blk,),
        in_specs=[
            pl.BlockSpec((blk, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (blk, d), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
        name="rms_norm",
    )(x2, weight.reshape(1, d))
    if pad:
        out = out[:n]
    return out.reshape(orig_shape)
