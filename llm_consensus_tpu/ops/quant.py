"""Weight-only int8 quantization (per-output-channel, symmetric).

Decode on TPU is HBM-bandwidth-bound: every generated token re-reads the
full weight set, so halving weight bytes nearly halves the per-token
latency floor. This module stores each large matmul weight as an int8
tensor plus a per-output-channel fp32 scale; the dequantize (convert +
multiply) happens on-chip and XLA fuses it into the consumer matmul's
operand — HBM sees only int8 + scales. (The reference has no local
compute at all to quantize — its model calls are remote HTTPS,
``src/main.rs:82-86``; this is part of the TPU build's own perf work
toward BASELINE.json's >=1k candidate-tokens/sec/chip floor.)

Inference-only: quantized params are not differentiable (training keeps
bf16 masters).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp

from llm_consensus_tpu.ops.kernels import on_tpu

# Weight leaves that get quantized, with the axis index of the
# *contraction* (input) dimension in the stacked [L, ...] layout from
# ``init_params`` (llm_consensus_tpu.models.transformer). Scales keep
# that axis as size 1 (keepdims) so ranks — and therefore the sharding
# rules in parallel/partitioning.py — are unchanged.
_QUANT_AXES_DENSE = {
    "wq": 1,
    "wk": 1,
    "wv": 1,
    "wo": 1,
    "w_gate": 1,
    "w_up": 1,
    "w_down": 1,
    # MLA projections and the shared experts (models.transformer).
    "w_kva": 1,
    "w_kvb": 1,
    "ws_gate": 1,
    "ws_up": 1,
    "ws_down": 1,
    # Mamba-2 projections (in_proj split into its z and xBC parts; the
    # 64 dt columns stay a float matmul).
    "w_in_z": 1,
    "w_in_xbc": 1,
    "w_out": 1,
}
_QUANT_AXES_MOE = {"w_gate": 2, "w_up": 2, "w_down": 2}


@jax.tree_util.register_dataclass
@dataclass
class QuantizedTensor:
    """int8 weight + fp32 per-output-channel scale (keepdims layout)."""

    q: jnp.ndarray  # int8, same shape as the original weight
    scale: jnp.ndarray  # float32, original shape with contraction dim = 1
    # Placed on a multi-device mesh (parallel.partitioning.shard_params
    # sets it): the matmul belongs to GSPMD, which cannot partition a
    # pallas_call, so the fused kernel stays off for this weight.
    gspmd: bool = field(default=False, metadata=dict(static=True))

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim


def quantize_tensor(w: jnp.ndarray, axis: int) -> QuantizedTensor:
    """Symmetric per-channel int8: q = round(w / s), s = amax/127."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QuantizedTensor(q=q, scale=scale)


def dequantize(qt: QuantizedTensor, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Materialize the bf16 weight on-chip (fused into the consumer)."""
    return qt.q.astype(dtype) * qt.scale.astype(dtype)


@jax.tree_util.register_dataclass
@dataclass
class Quantized4Tensor:
    """int4 weight (two nibbles per int8 byte) + fp32 per-channel scale.

    Packing contract: the CONTRACTION axis is always the second-to-last
    axis of the logical weight (true for every quantized leaf layout:
    dense [L, K, N], MoE [L, E, K, F], lm_head [K, N]); rows [0, K/2)
    live in the low nibbles and rows [K/2, K) in the high nibbles, so
    ``q``'s contraction dim is K/2 and unpack is a concat — no
    per-element interleave. Halves weight HBM bytes vs int8 (decode's
    bandwidth floor) at int4 precision (symmetric, amax/7).
    """

    q: jnp.ndarray  # int8 carrying 2x int4; contraction dim halved
    scale: jnp.ndarray  # float32, logical shape with contraction dim = 1
    gspmd: bool = field(default=False, metadata=dict(static=True))

    @property
    def shape(self):  # logical (unpacked) shape
        s = list(self.q.shape)
        s[-2] *= 2
        return tuple(s)

    @property
    def ndim(self):
        return self.q.ndim


def quantize_tensor4(w: jnp.ndarray, axis: int) -> Quantized4Tensor:
    """Symmetric per-channel int4: q = round(w/s) in [-8, 7], s = amax/7.

    ``axis`` must be the second-to-last axis (the packing contract) and
    even-sized.
    """
    if axis % w.ndim != w.ndim - 2:
        raise ValueError(
            f"int4 packs along axis -2; got axis {axis} for rank {w.ndim}"
        )
    k = w.shape[axis]
    if k % 2:
        raise ValueError(f"contraction dim {k} must be even for int4")
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(w32 / scale), -8, 7).astype(jnp.int32)
    low, high = jnp.split(q, 2, axis=axis)
    packed = ((low & 0xF) | ((high & 0xF) << 4)).astype(jnp.int8)
    return Quantized4Tensor(q=packed, scale=scale)


def unpack4(packed: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Nibbles -> values in [-8, 7], restoring the logical contraction
    dim (int32 bit ops — int8 shifts don't legalize on Mosaic)."""
    w32 = packed.astype(jnp.int32)
    low = (w32 & 0xF) - ((w32 & 0x8) << 1)
    nib = (w32 >> 4) & 0xF
    high = nib - ((nib & 0x8) << 1)
    return jnp.concatenate([low, high], axis=-2).astype(dtype)


def dequantize4(qt: Quantized4Tensor, dtype=jnp.bfloat16) -> jnp.ndarray:
    return unpack4(qt.q, dtype) * qt.scale.astype(dtype)


def maybe_dequantize(leaf, dtype=jnp.bfloat16):
    """Pass-through for plain arrays; dequantize quantized leaves (a
    ``StackedQuant`` view: that layer's slice of the stack)."""
    if isinstance(leaf, StackedQuant):
        leaf = leaf.sliced()
    if isinstance(leaf, QuantizedTensor):
        return dequantize(leaf, dtype)
    if isinstance(leaf, Quantized4Tensor):
        return dequantize4(leaf, dtype)
    return leaf


# Kernel override: None = observe (kernel on a TPU for weights GSPMD does
# not partition); True/False forces. For tests.
_FORCE_KERNEL: bool | None = None


def set_kernel_enabled(enabled: bool | None) -> None:
    """Force the fused int8 kernel on/off; None restores auto-detect."""
    global _FORCE_KERNEL
    _FORCE_KERNEL = enabled


# The int4 kernel is OPT-IN only (no auto-detect): its nibble-unpack bit
# ops have shown pathological Mosaic compile times on some toolchain
# versions, and a wedged compile service is worse than the jnp fallback
# (which still stores int4 in HBM — capacity win — but lets XLA
# materialize the dequant, losing the bandwidth win inside scan).
_FORCE_KERNEL4: bool = False


def set_kernel4_enabled(enabled: bool) -> None:
    """Enable the fused int4 matmul kernel (verify it compiles on your
    jax/libtpu first — see ops/pallas/quant_matmul.py)."""
    global _FORCE_KERNEL4
    _FORCE_KERNEL4 = enabled


def _use_kernel4(leaf: Quantized4Tensor) -> bool:
    return _FORCE_KERNEL4 and on_tpu() and not leaf.gspmd


def _use_kernel(leaf: QuantizedTensor) -> bool:
    if _FORCE_KERNEL is not None:
        return _FORCE_KERNEL
    # pallas_call is opaque to GSPMD: on a weight sharded over a mesh
    # the kernel would force an all-gather of it — the XLA dequant path
    # shards fine there.
    return on_tpu() and not leaf.gspmd


def _try_kernel_matmul(x, leaf, out_dtype):
    """Shared fused-kernel dispatch for int8/int4 weights.

    Returns the kernel result, or None when the kernel is gated off or
    the shapes don't tile (caller falls back to dequant + XLA dot).
    """
    if leaf.q.ndim != 2:
        return None
    if isinstance(leaf, QuantizedTensor):
        if not _use_kernel(leaf):
            return None
        from llm_consensus_tpu.ops.pallas.quant_matmul import (
            quant_matmul_2d as kernel,
        )
        from llm_consensus_tpu.ops.pallas.quant_matmul import (
            quant_matmul_supported as supported,
        )

        k = leaf.q.shape[0]
    else:
        if not _use_kernel4(leaf):
            return None
        from llm_consensus_tpu.ops.pallas.quant_matmul import (
            quant4_matmul_2d as kernel,
        )
        from llm_consensus_tpu.ops.pallas.quant_matmul import (
            quant4_matmul_supported as supported,
        )

        k = 2 * leaf.q.shape[0]  # logical contraction dim (packed)
    n = leaf.q.shape[1]
    lead = x.shape[:-1]
    m = 1
    for s in lead:
        m *= s
    if not supported(m, k, n):
        return None
    out = kernel(x.reshape(m, k), leaf.q, leaf.scale, out_dtype=out_dtype)
    return out.reshape(*lead, n)


@dataclass
class StackedQuant:
    """Trace-local lazy view of one layer of a stacked quantized weight.

    Built by the layer scan (``models.transformer._run_layers``) instead
    of slicing the [L, K, N] stack per iteration: a sliced operand to a
    Pallas kernel must be materialized (XLA copies the whole layer's
    weights every decode step), but the stacked kernel
    (:func:`llm_consensus_tpu.ops.pallas.quant_matmul.quant_matmul_stacked`)
    reads its tiles straight out of the resident stack via a
    scalar-prefetched layer index. Not a pytree — it never crosses a
    jit boundary; :func:`matmul` consumes it in-trace.
    """

    full: QuantizedTensor  # q [L, K, N], scale [L, 1, N] (or [L, E, ..])
    layer: jnp.ndarray  # traced scalar int32

    def sliced(self) -> QuantizedTensor:
        return replace(
            self.full,
            q=jax.lax.dynamic_index_in_dim(
                self.full.q, self.layer, 0, keepdims=False
            ),
            scale=jax.lax.dynamic_index_in_dim(
                self.full.scale, self.layer, 0, keepdims=False
            ),
        )


def _try_kernel_matmul_stacked(x, leaf: StackedQuant, out_dtype):
    if not _use_kernel(leaf.full):
        return None
    from llm_consensus_tpu.ops.pallas.quant_matmul import (
        quant_matmul_stacked,
        quant_matmul_supported,
    )

    _, k, n = leaf.full.q.shape
    lead = x.shape[:-1]
    m = 1
    for s in lead:
        m *= s
    if not quant_matmul_supported(m, k, n):
        return None
    out = quant_matmul_stacked(
        x.reshape(m, k),
        leaf.full.q,
        leaf.full.scale,
        leaf.layer,
        out_dtype=out_dtype,
    )
    return out.reshape(*lead, n)


def matmul(x: jnp.ndarray, leaf, out_dtype=None) -> jnp.ndarray:
    """``x [..., K] @ leaf [K, N]`` — quantization-aware.

    Plain arrays use the regular XLA dot. QuantizedTensor weights use the
    fused Pallas int8 kernel in the single-chip decode/GEMV regime
    (small M), where XLA's materialize-the-dequant behavior would
    otherwise erase the int8 bandwidth win (see
    ops/pallas/quant_matmul.py); other shapes and sharded runs fall back
    to dequant + XLA dot. ``StackedQuant`` views additionally skip the
    per-layer slice materialization inside the decode layer scan.
    """
    if isinstance(leaf, StackedQuant):
        out = _try_kernel_matmul_stacked(x, leaf, out_dtype)
        if out is not None:
            return out
        leaf = leaf.sliced()  # XLA fuses the slice into the dequant+dot
    if isinstance(leaf, (QuantizedTensor, Quantized4Tensor)):
        out = _try_kernel_matmul(x, leaf, out_dtype)
        if out is not None:
            return out
        w = maybe_dequantize(leaf, x.dtype)
    else:
        w = leaf
    if out_dtype is not None:
        return jnp.einsum(
            "...k,kn->...n", x, w, preferred_element_type=out_dtype
        )
    return x @ w


def quantize_params(
    params: dict, *, quantize_lm_head: bool = True, bits: int = 8
) -> dict:
    """Quantize the large matmul weights of an ``init_params`` tree.

    Norms, biases, the router (tiny), and the embedding gather table stay
    in their original dtype. Works for dense and MoE block layouts (the
    MoE leaves carry an extra leading expert axis). ``bits``: 8 (int8,
    amax/127) or 4 (packed int4, amax/7 — half the HBM bytes again at
    reduced precision).
    """
    qfn = quantizer(bits)
    qtypes = (QuantizedTensor, Quantized4Tensor)
    out = dict(params)
    for stack in (
        "dense_blocks", "blocks",
        "ssm_blocks", "attn_blocks", "moe_blocks", "mlp_blocks",
    ):
        if stack not in params:
            continue
        blocks = dict(params[stack])
        for name, w in blocks.items():
            axis = quant_axis(name, w.ndim)
            if axis is not None and not isinstance(w, qtypes):
                blocks[name] = qfn(w, axis)
        out[stack] = blocks
    if quantize_lm_head and "lm_head" in params and not isinstance(
        params["lm_head"], qtypes
    ):
        out["lm_head"] = qfn(params["lm_head"], quant_axis("lm_head", 2))
    return out


def quantizer(bits: int):
    """``quantize_tensor`` (8) or ``quantize_tensor4`` (4)."""
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")
    return quantize_tensor if bits == 8 else quantize_tensor4


def quant_axis(name: str, ndim: int) -> int | None:
    """Contraction axis of the parameter leaf ``name`` (``init_params``
    layout, rank ``ndim``) when weight-only quantization covers it, else
    None — the one rule :func:`quantize_params` and the quantizing init
    (``models.transformer.init_params_quantized``) share."""
    if name == "lm_head":
        return 0
    if name in _QUANT_AXES_MOE and ndim == 4:
        return _QUANT_AXES_MOE[name]
    return _QUANT_AXES_DENSE.get(name)


def quantized_bytes(params) -> int:
    """Total parameter bytes as stored (int8 + scales count as-is)."""
    return sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(params)
    )
