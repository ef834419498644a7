"""Functional pre-norm transformer (Llama/Mistral/Qwen2/Mixtral family).

The reference delegates all model compute to a remote API
(``src/main.rs:82-86``); this module is its TPU-native replacement per
BASELINE.json's north star. Design choices are XLA-first:

- **Params are a flat pytree with layers stacked on a leading axis**, and
  the layer loop is ``lax.scan`` — one traced block, compiled once,
  regardless of depth (compile time stays flat as n_layers grows).
- **Static shapes everywhere**: the KV cache is a fixed-size buffer,
  per-sequence fill state is data (``KVCache.length``), never shape.
- **bf16 weights/activations, fp32 softmax/norms/logits** — MXU-friendly
  matmuls with numerically safe reductions.
- GQA is computed without materializing repeated KV heads
  (see :mod:`llm_consensus_tpu.ops.attention`).
- Mixtral-style MoE computes all experts densely and combines with the
  top-k router weights (or the capacity dispatch); a ``moe_dropless``
  config (DeepSeek-V2-Lite) sorts tokens by expert and runs a grouped
  int8 matmul that reads only the experts reached (``_moe_dropless``).
- A latent-attention (MLA) model keeps one compressed latent a token in
  the page pool and attends in the absorbed form (``_paged_layers``);
  layers of two shapes are two stacks (``layer_stacks``).
- A PLANNED model (``ModelConfig.layer_plan``: Nemotron-3-Nano) is
  one-mixer layers of several kinds, a stack a kind; its state-space
  layers keep a recurrent state a sequence in the paged cache's state
  pool (``_ssm_mix``), beside the attention layers' pages.

Three entry points:
- :func:`forward` — full causal forward, logits for every position
  (training / scoring).
- :func:`prefill` — fill the KV cache from right-padded prompts, return
  last-valid-token logits only (avoids a [B, S, V] logits buffer).
- :func:`decode_step` — one-token step against the cache.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from llm_consensus_tpu.models.cache import KVCache, QuantKVCache, quantize_kv
from llm_consensus_tpu.models.configs import ModelConfig
from llm_consensus_tpu.ops import ssm as _ssm
from llm_consensus_tpu.ops.activations import swiglu
from llm_consensus_tpu.ops.attention import (
    causal_attention,
    chunk_decode_attention,
    decode_attention,
)
from llm_consensus_tpu.ops.kernels import single_device
from llm_consensus_tpu.ops.norms import rms_norm
from llm_consensus_tpu.ops.quant import matmul as _qmm
from llm_consensus_tpu.ops.quant import maybe_dequantize as _w
from llm_consensus_tpu.ops.rope import apply_rope, apply_rope_tail, rope_cos_sin


def _local_kernels(cfg: ModelConfig, mesh) -> bool:
    """Kernels that carry no ``shard_map`` of their own (norm, the dense
    attention family) run only where the program is not partitioned:
    GSPMD cannot see inside a ``pallas_call`` and would gather its
    operands. Gated on the mesh the caller was GIVEN — a one-chip engine
    on a four-chip host keeps its kernels."""
    return bool(cfg.use_pallas) and single_device(mesh)


def _rms(cfg: ModelConfig, x, w, mesh=None):
    if _local_kernels(cfg, mesh):
        from llm_consensus_tpu.ops.pallas import fused_rms_norm

        return fused_rms_norm(x, w, cfg.rms_norm_eps)
    return rms_norm(x, w, cfg.rms_norm_eps)


def _attn_causal(cfg: ModelConfig, q, k, v, positions, mesh=None):
    # Sequence-parallel long context: the ring (parallel/ring.py) handles
    # index-causal layouts over a seq-sharded mesh; K/V chunks rotate on
    # ICI instead of any device holding the full sequence.
    if (
        cfg.use_ring
        and mesh is not None
        and positions is None
        and cfg.sliding_window == 0
        and mesh.shape.get("seq", 1) > 1
        and q.shape[1] % mesh.shape["seq"] == 0
    ):
        from llm_consensus_tpu.parallel.ring import ring_attention_sharded

        return ring_attention_sharded(q, k, v, mesh)
    # The fused kernel implements index-causal masking; packed/offset
    # layouts (explicit positions) and sliding windows use the jnp path.
    if (
        _local_kernels(cfg, mesh)
        and positions is None
        and cfg.sliding_window == 0
        and q.shape[1] % _pallas_blk(q.shape[1]) == 0
    ):
        from llm_consensus_tpu.ops.pallas import flash_causal_attention

        return flash_causal_attention(q, k, v, blk_q=_pallas_blk(q.shape[1]))
    return causal_attention(q, k, v, positions, window=cfg.sliding_window)


def _pallas_blk(s: int) -> int:
    blk = min(256, s)
    while s % blk:
        blk //= 2
    return max(blk, 1)


def _attn_decode(
    cfg: ModelConfig, q, k_cache, v_cache, valid_len, shared_prefix_len=None
):
    """``shared_prefix_len`` (traced scalar or None): every row's cache
    slots [0, shared_prefix_len) hold identical K/V — the
    shared-prefill fan-out invariant — so the two-phase kernel reads
    that region ONCE for the whole batch instead of once per row.
    Engages only on the Pallas path with no sliding window; everything
    else falls back to the ungrouped read (same outputs)."""
    if cfg.use_pallas and cfg.sliding_window == 0:
        if shared_prefix_len is not None:
            from llm_consensus_tpu.ops.pallas import (
                flash_decode_attention_shared_prefix,
            )

            return flash_decode_attention_shared_prefix(
                q, k_cache, v_cache, valid_len, shared_prefix_len
            )
        from llm_consensus_tpu.ops.pallas import flash_decode_attention

        return flash_decode_attention(q, k_cache, v_cache, valid_len)
    return decode_attention(
        q, k_cache, v_cache, valid_len, window=cfg.sliding_window
    )


_STACKED_DECODE = False


def set_stacked_decode(enabled: bool) -> None:
    """Toggle the stacked-cache decode path (see ``_run_layers``).

    The flag is read at TRACE time, so already-compiled decode programs
    would silently keep their old path — the setter clears the jit
    caches so the next call really recompiles with the new setting.
    """
    global _STACKED_DECODE
    _STACKED_DECODE = enabled
    jax.clear_caches()


def _attn_decode_quant_stacked(
    cfg: ModelConfig, q, k_q, k_s, v_q, v_s, valid_len, layer,
    shared_prefix_len=None,
):
    """Decode attention over ONE layer of the stacked int8 cache.

    k_q/v_q: [L, B, Hkv, S, D]; k_s/v_s: [L, B, Hkv, S]; ``layer`` is a
    traced index. The Pallas path reads the stack in place (scalar
    prefetch); the jnp fallback slices the layer (XLA fuses the slice
    into the dequant + einsum).

    ``shared_prefix_len`` (traced scalar or None): the shared-prefill
    fan-out invariant now engages HERE too — the ragged kernel's
    stacked layout reads the common prefix once for the whole batch
    (the stacked-decode fallback PR 3 documented is gone).
    """
    if cfg.use_pallas and cfg.sliding_window == 0:
        if shared_prefix_len is not None:
            from llm_consensus_tpu.ops.pallas import (
                flash_decode_attention_shared_prefix_q8_stacked,
            )

            return flash_decode_attention_shared_prefix_q8_stacked(
                q, k_q, k_s, v_q, v_s, valid_len, shared_prefix_len, layer
            )
        from llm_consensus_tpu.ops.pallas import (
            flash_decode_attention_q8_stacked,
        )

        return flash_decode_attention_q8_stacked(
            q, k_q, k_s, v_q, v_s, valid_len, layer
        )
    from llm_consensus_tpu.ops.attention import decode_attention_quant

    def sl(a):
        return jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)

    return decode_attention_quant(
        q, sl(k_q), sl(k_s), sl(v_q), sl(v_s), valid_len,
        window=cfg.sliding_window,
    )


def _attn_decode_quant(
    cfg: ModelConfig, q, k_q, k_s, v_q, v_s, valid_len,
    shared_prefix_len=None,
):
    """int8-cache decode attention: the Pallas kernel reads int8 straight
    from HBM (the whole point of the quantized cache) but pallas_call is
    opaque to GSPMD, so an engine on a multi-device mesh resolves
    ``cfg.use_pallas`` off (``ops.kernels.resolve_kernels``) and takes
    the shardable jnp dequant path.

    ``shared_prefix_len``: as :func:`_attn_decode` — the two-phase
    shared-prefix kernel reads the fan-out's common prefix KV once for
    the whole batch (kernel path only; the jnp dequant path has no
    bandwidth to save and stays ungrouped)."""
    if cfg.use_pallas and cfg.sliding_window == 0:
        if shared_prefix_len is not None:
            from llm_consensus_tpu.ops.pallas import (
                flash_decode_attention_shared_prefix_q8,
            )

            return flash_decode_attention_shared_prefix_q8(
                q, k_q, k_s, v_q, v_s, valid_len, shared_prefix_len
            )
        from llm_consensus_tpu.ops.pallas import flash_decode_attention_q8

        return flash_decode_attention_q8(q, k_q, k_s, v_q, v_s, valid_len)
    from llm_consensus_tpu.ops.attention import decode_attention_quant

    return decode_attention_quant(
        q, k_q, k_s, v_q, v_s, valid_len, window=cfg.sliding_window
    )

# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(
    cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16, *, quant_bits: int = 0
) -> dict:
    """Random-init parameters (truncated-normal-free simple scheme:
    normal(0, 0.02), residual projections scaled by 1/sqrt(2*n_layers)).

    ``quant_bits`` (8 or 4; see :func:`init_params_quantized`): the
    weights ``ops.quant.quantize_params`` covers come out already
    quantized, generated a layer at a time."""
    from llm_consensus_tpu.ops.quant import quant_axis

    keys = iter(
        jax.random.split(
            key, 32 if _two_stacks(cfg) or cfg.layer_plan else 16
        )
    )

    def normal(name, shape, scale=0.02, pad=None):
        """``pad`` = (axis, width): the leaf is drawn at ``shape`` and
        stored zero-padded along ``axis`` to ``width``."""
        k = next(keys)
        axis = quant_axis(name, len(shape)) if quant_bits else None
        if axis is not None:
            return _init_quantized_leaf(
                k, shape, scale, axis, quant_bits, dtype, pad
            )
        w = (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)
        return w if pad is None else _zero_pad(w, *pad)

    L, D, H, Hkv, F, V = (
        cfg.n_layers,
        cfg.d_model,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.d_ff,
        cfg.vocab_size,
    )
    Dh = cfg.head_dim
    resid_scale = 0.02 / math.sqrt(2 * L)

    if cfg.layer_plan:
        # One stack a layer kind, the largest leaves drawn first (while
        # the device is emptiest: an expert layer's float slice is GBs).
        params = {}
        for kind in ("moe", "ssm", "attn", "mlp"):
            if cfg.n_of(kind):
                params[PLAN_STACKS[kind]] = _init_plan_stack(
                    cfg, kind, cfg.n_of(kind), normal, next(keys),
                    resid_scale, dtype,
                )
        params["embed"] = normal("embed", (V, D))
        params["norm_f"] = jnp.ones((D,), dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = normal("lm_head", (D, V))
        return params

    if _two_stacks(cfg):
        Ld = cfg.n_dense_layers if cfg.is_moe else L
        params = {}
        if Ld:
            params["dense_blocks"] = _init_stack(
                cfg, Ld, normal, resid_scale, dtype, moe=False
            )
        if L - Ld:
            params["blocks"] = _init_stack(
                cfg, L - Ld, normal, resid_scale, dtype, moe=True
            )
        elif Ld:  # a dense MLA model: its one stack under the usual name
            params["blocks"] = params.pop("dense_blocks")
        params["embed"] = normal("embed", (V, D))
        params["norm_f"] = jnp.ones((D,), dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = normal("lm_head", (D, V))
        return params

    blocks: dict = {
        "attn_norm": jnp.ones((L, D), dtype),
        "mlp_norm": jnp.ones((L, D), dtype),
        "wq": normal("wq", (L, D, H * Dh)),
        "wk": normal("wk", (L, D, Hkv * Dh)),
        "wv": normal("wv", (L, D, Hkv * Dh)),
        "wo": normal("wo", (L, H * Dh, D), resid_scale),
    }
    if cfg.qkv_bias:
        blocks["bq"] = jnp.zeros((L, H * Dh), dtype)
        blocks["bk"] = jnp.zeros((L, Hkv * Dh), dtype)
        blocks["bv"] = jnp.zeros((L, Hkv * Dh), dtype)
    if cfg.is_moe:
        E = cfg.n_experts
        blocks["router"] = normal("router", (L, D, E))
        blocks["w_gate"] = normal("w_gate", (L, E, D, F))
        blocks["w_up"] = normal("w_up", (L, E, D, F))
        blocks["w_down"] = normal("w_down", (L, E, F, D), resid_scale)
    else:
        blocks["w_gate"] = normal("w_gate", (L, D, F))
        blocks["w_up"] = normal("w_up", (L, D, F))
        blocks["w_down"] = normal("w_down", (L, F, D), resid_scale)

    params = {
        "embed": normal("embed", (V, D)),
        "blocks": blocks,
        "norm_f": jnp.ones((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal("lm_head", (D, V))
    return params


def _two_stacks(cfg: ModelConfig) -> bool:
    """Models :func:`_init_stack` builds: latent attention, leading
    dense layers, shared experts. Every older preset keeps the one
    stack, and the key order, it always had."""
    return cfg.is_mla or cfg.n_dense_layers > 0 or cfg.n_shared_experts > 0


def _init_stack(cfg: ModelConfig, n: int, normal, resid_scale, dtype, moe):
    """``n`` stacked layers of one shape: attention (MLA or GQA) and
    either a dense SwiGLU of width ``d_ff`` or routed experts of width
    ``expert_d_ff`` with their router and shared experts."""
    D, H = cfg.d_model, cfg.n_heads
    blocks = {
        "attn_norm": jnp.ones((n, D), dtype),
        "mlp_norm": jnp.ones((n, D), dtype),
    }
    if cfg.is_mla:
        r = cfg.kv_lora_rank
        per = cfg.qk_nope_head_dim + cfg.v_head_dim
        blocks["wq"] = normal("wq", (n, D, H * cfg.head_dim))
        blocks["w_kva"] = normal("w_kva", (n, D, cfg.latent_dim))
        blocks["kv_a_norm"] = jnp.ones((n, r), dtype)
        blocks["w_kvb"] = normal("w_kvb", (n, r, H * per))
        blocks["wo"] = normal("wo", (n, H * cfg.v_head_dim, D), resid_scale)
    else:
        Dh, Hkv = cfg.head_dim, cfg.n_kv_heads
        blocks["wq"] = normal("wq", (n, D, H * Dh))
        blocks["wk"] = normal("wk", (n, D, Hkv * Dh))
        blocks["wv"] = normal("wv", (n, D, Hkv * Dh))
        blocks["wo"] = normal("wo", (n, H * Dh, D), resid_scale)
        if cfg.qkv_bias:
            blocks["bq"] = jnp.zeros((n, H * Dh), dtype)
            blocks["bk"] = jnp.zeros((n, Hkv * Dh), dtype)
            blocks["bv"] = jnp.zeros((n, Hkv * Dh), dtype)
    if not moe:
        F = cfg.d_ff
        blocks["w_gate"] = normal("w_gate", (n, D, F))
        blocks["w_up"] = normal("w_up", (n, D, F))
        blocks["w_down"] = normal("w_down", (n, F, D), resid_scale)
        return blocks
    E, F = cfg.n_experts, cfg.expert_d_ff
    blocks["router"] = normal("router", (n, D, E))
    blocks["w_gate"] = normal("w_gate", (n, E, D, F))
    blocks["w_up"] = normal("w_up", (n, E, D, F))
    blocks["w_down"] = normal(
        "w_down", (n, E, F, D), resid_scale * _routed_out_scale(cfg)
    )
    if cfg.n_shared_experts:
        Fs = cfg.n_shared_experts * F
        blocks["ws_gate"] = normal("ws_gate", (n, D, Fs))
        blocks["ws_up"] = normal("ws_up", (n, D, Fs))
        blocks["ws_down"] = normal("ws_down", (n, Fs, D), resid_scale)
    return blocks


# Layer kinds of a planned model (``ModelConfig.layer_plan``) and the
# parameter stack each lives in.
PLAN_STACKS = {
    "ssm": "ssm_blocks", "attn": "attn_blocks", "moe": "moe_blocks",
    "mlp": "mlp_blocks",
}


def _zero_pad(w, axis: int, width: int):
    pad = [(0, 0)] * w.ndim
    pad[axis] = (0, width - w.shape[axis])
    return jnp.pad(w, pad)


def _routed_out_scale(cfg: ModelConfig) -> float:
    """Width of the routed experts' random out-projection relative to
    the other out-projections.

    Keyed on the ROUTER's form, which is what makes the difference: a
    "sigmoid_topk" router's weights are renormalised and scaled, so
    the k chosen experts enter with weights that sum to
    ``moe_routed_scale`` (2.5), where "softmax_topk" leaves them a
    softmax's shares (top-6 of 64: ~0.15 in all). A random router is
    also indecisive — its k-th and (k+1)-th scores tie within bf16
    rounding for ~2% of tokens a layer — and at equal widths ONE
    swapped expert then moves the residual stream more than every
    rounding in the model, each expert layer multiplying the error it
    is given (a float32 and a bf16 pass of the same weights end 30-50%
    apart, measured on the chip). 1/16 makes a routed update a few per
    cent of the stream, as a trained model's is and as the softmax
    router's weights make it by themselves; bytes, operations and
    routing statistics are what they were."""
    return 1 / 16 if cfg.moe_router == "sigmoid_topk" else 1.0


def _init_plan_stack(cfg, kind, n, normal, key, resid_scale, dtype):
    """``n`` stacked ONE-mixer layers of ``kind``: the pre-norm and the
    mixer's weights. An ungated ("relu2") feed-forward has no gate
    matrix; an expert's width is stored padded to whole tiles
    (``ModelConfig.expert_d_ff_stored``: zero columns of ``w_up``, zero
    rows of ``w_down``)."""
    D = cfg.d_model
    gated = cfg.mlp_form == "swiglu"
    blocks = {"norm": jnp.ones((n, D), dtype)}
    if kind == "attn":
        H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        blocks["wq"] = normal("wq", (n, D, H * Dh))
        blocks["wk"] = normal("wk", (n, D, Hkv * Dh))
        blocks["wv"] = normal("wv", (n, D, Hkv * Dh))
        blocks["wo"] = normal("wo", (n, H * Dh, D), resid_scale)
    elif kind == "mlp":
        F = cfg.d_ff
        if gated:
            blocks["w_gate"] = normal("w_gate", (n, D, F))
        blocks["w_up"] = normal("w_up", (n, D, F))
        blocks["w_down"] = normal("w_down", (n, F, D), resid_scale)
    elif kind == "moe":
        E, F, Fp = cfg.n_experts, cfg.expert_d_ff, cfg.expert_d_ff_stored
        if gated:
            blocks["w_gate"] = normal("w_gate", (n, E, D, F), pad=(3, Fp))
        blocks["w_up"] = normal("w_up", (n, E, D, F), pad=(3, Fp))
        blocks["w_down"] = normal(
            "w_down", (n, E, F, D), resid_scale * _routed_out_scale(cfg),
            pad=(2, Fp),
        )
        blocks["router"] = normal("router", (n, D, E))
        if cfg.moe_router == "sigmoid_topk":
            blocks["router_bias"] = 0.02 * jax.random.normal(
                jax.random.fold_in(key, 0), (n, E), jnp.float32
            )
        if cfg.n_shared_experts:
            Fs = cfg.shared_d_ff
            if gated:
                blocks["ws_gate"] = normal("ws_gate", (n, D, Fs))
            blocks["ws_up"] = normal("ws_up", (n, D, Fs))
            blocks["ws_down"] = normal("ws_down", (n, Fs, D), resid_scale)
    else:  # ssm
        Hs, inner, cx = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_dim
        blocks["w_in_z"] = normal("w_in_z", (n, D, inner))
        blocks["w_in_xbc"] = normal("w_in_xbc", (n, D, cx))
        blocks["w_in_dt"] = normal("w_in_dt", (n, D, Hs))
        blocks["w_out"] = normal("w_out", (n, inner, D), resid_scale)
        blocks["conv_w"] = normal("conv_w", (n, cfg.ssm_conv, cx), 0.3)
        blocks["conv_b"] = normal("conv_b", (n, cx))
        blocks["gate_norm"] = jnp.ones((n, inner), dtype)
        # Float32, as published: the step's bias so that softplus of it
        # is log-uniform in [1e-3, 1e-1] (``time_step_min`` / ``_max``),
        # A = -exp(a_log) in [-16, -1], the skip D = 1.
        dt0 = jnp.exp(
            jax.random.uniform(
                jax.random.fold_in(key, 1), (n, Hs), jnp.float32,
                math.log(1e-3), math.log(1e-1),
            )
        )
        blocks["dt_bias"] = dt0 + jnp.log(-jnp.expm1(-dt0))
        blocks["a_log"] = jnp.log(
            jnp.broadcast_to(1.0 + jnp.arange(Hs, dtype=jnp.float32) % 16, (n, Hs))
        )
        blocks["d_skip"] = jnp.ones((n, Hs), jnp.float32)
    return blocks


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _init_quantized_leaf(key, shape, scale, axis, bits, dtype, pad=None):
    """One quantized weight leaf whose float form never exists whole.

    A stacked leaf ([L, ...], every quantized block weight) is drawn and
    quantized one layer at a time under ``lax.map`` with a key per
    layer, so the live float temporary is one layer's slice — a few
    hundred MB at 7B widths against the 3.8 GB bf16 leaf. The values
    are therefore NOT those of ``quantize_params(init_params(...))``,
    which draws the stack in one call; both are N(0, scale) from the
    same key."""
    from llm_consensus_tpu.ops.quant import quantizer

    qfn = quantizer(bits)

    def draw(k, shp, ax, lead=1):
        w = (jax.random.normal(k, shp, jnp.float32) * scale).astype(dtype)
        if pad is not None:
            w = _zero_pad(w, pad[0] - lead, pad[1])
        return qfn(w, ax)

    if len(shape) == 2:  # lm_head: no layer axis
        return draw(key, shape, axis, 0)
    if len(shape) == 4 and math.prod(shape[1:]) > 2**28:
        # A layer of experts too large to hold in float (128 x 2688 x
        # 1856 is 2.6 GB in float32 beside 11 GB of weights): one
        # expert at a time, over the merged [layer, expert] axis.
        flat = jax.lax.map(
            lambda k: draw(k, shape[2:], axis - 2, 2),
            jax.random.split(key, shape[0] * shape[1]),
        )
        return jax.tree.map(
            lambda a: a.reshape(shape[0], shape[1], *a.shape[1:]), flat
        )
    return jax.lax.map(
        lambda k: draw(k, shape[1:], axis - 1),
        jax.random.split(key, shape[0]),
    )


def param_count(params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))


def model_param_bytes(params) -> tuple[int, int]:
    """``(hbm_bytes, n_params)`` of a parameter tree as it sits in HBM.

    The roofline cost model's weight term (PR 10): every decode-shaped
    device program streams the whole tree once, so its byte size (at
    the ACTUAL leaf dtypes — int8 quantized leaves count 1 byte + their
    scales, not the bf16 they stand for) is the floor of the program's
    HBM traffic. ``n_params`` (scales included — they are read too) is
    the matmul-FLOPs multiplier :func:`program_hbm_cost` uses.
    """
    leaves = [
        p for p in jax.tree_util.tree_leaves(params) if hasattr(p, "dtype")
    ]
    return (
        int(sum(p.size * jnp.dtype(p.dtype).itemsize for p in leaves)),
        int(sum(p.size for p in leaves)),
    )


def kv_plane_token_bytes(cfg: ModelConfig, kv_dtype) -> int:
    """HBM bytes one token position costs per full K+V read/write across
    all layers at the pool's dtype — the cost model's KV unit (and the
    unit of ``gateway_shared_kv_bytes_saved_total``, same formula). An MLA
    pool holds one latent a token a layer, at its padded lane width."""
    if cfg.is_mla:
        return (
            cfg.n_layers * cfg.latent_pool_dim * jnp.dtype(kv_dtype).itemsize
        )
    return (
        cfg.n_attn_layers
        * cfg.n_kv_heads
        * cfg.head_dim
        * 2
        * jnp.dtype(kv_dtype).itemsize
    )


def program_hbm_cost(
    cfg: ModelConfig,
    *,
    weight_bytes: int,
    weight_params: int,
    kv_token_bytes: int,
    kv_read_tokens: int,
    kv_write_tokens: int,
    tokens: int,
) -> dict:
    """Static HBM-bytes + FLOPs model for ONE device program (PR 10).

    The decode roofline in the terms ClusterFusion++ and the
    operation-fusion paper argue it (PAPERS.md): a program moves
    ``weight_bytes`` (the whole tree, once — the term fusion amortizes
    across rows and speculation amortizes across tokens) plus
    ``(kv_read_tokens + kv_write_tokens) * kv_token_bytes`` of KV pages
    it actually touches (group-shared prefix reads counted ONCE per
    group — callers pass post-dedup token counts), and computes
    ``2 * weight_params`` matmul FLOPs per processed token plus the
    attention dot-products (4 * n_heads * head_dim per (query, kv)
    pair). Measured wall time / (hbm_bytes / peak_bw) is the program's
    model-bandwidth-utilization — ``gateway_program_mbu{kind}``.

    A MODEL, not a measurement: activation traffic, index/table reads,
    and padding rows are excluded; on a chip whose decode programs are
    truly bandwidth-bound the modeled bytes are the dominant term and
    MBU lands near 1.0. Multi-round programs (PR 12) are R rounds of
    KV growth under ONE weight read: the caller passes the summed
    per-round reads (``k*L + k*(k-1)/2`` per row at committed length
    L) and ``k`` writes/tokens per row, so amortization shows up as
    hbm_bytes growing sublinearly in k while tokens grow linearly —
    rows frozen by early-exit masking make the passed counts an upper
    bound, exactly like padding rows make the weight term a floor.
    """
    hbm_bytes = int(
        weight_bytes + (kv_read_tokens + kv_write_tokens) * kv_token_bytes
    )
    # A (query, key) pair: head_dim products for the score and as many
    # for the value, a head; absorbed MLA scores over the latent and the
    # rotary key and sums values of the latent's width.
    pair = (
        2 * (cfg.latent_dim + cfg.kv_lora_rank)
        if cfg.is_mla
        else 4 * cfg.head_dim
    )
    flops = int(
        2 * weight_params * tokens + cfg.n_heads * pair * kv_read_tokens
    )
    return {
        "hbm_bytes": hbm_bytes,
        "flops": flops,
        "kv_read_tokens": int(kv_read_tokens),
        "kv_write_tokens": int(kv_write_tokens),
        "tokens": int(tokens),
    }


def init_params_quantized(
    cfg: ModelConfig, key: jax.Array, *, bits: int = 8, dtype=jnp.bfloat16
) -> dict:
    """Random weights that reach int8/int4 without the bf16 tree ever
    existing — on the device or anywhere else.

    ``init_params`` + ``quantize_params`` holds both copies at once
    (~22 GB for a 7B preset at int8) and cannot start on a 16 GB chip.
    Here every quantized leaf is generated layer by layer on the
    default device (:func:`_init_quantized_leaf`), so the peak is the
    quantized tree plus one layer's float slice. The CLI's one
    random-weight start-up path.
    """
    return init_params(cfg, key, dtype, quant_bits=bits)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _project_qkv(cfg: ModelConfig, p: dict, h: jnp.ndarray):
    b, s, _ = h.shape
    q = _qmm(h, p["wq"])
    k = _qmm(h, p["wk"])
    v = _qmm(h, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def moe_router_aux(
    cfg: ModelConfig, router_logits: jnp.ndarray, top_idx: jnp.ndarray
) -> dict:
    """Router auxiliary losses for MoE training (Mixtral config).

    router_logits: [..., E] pre-softmax; top_idx: [..., k] chosen experts.
    Returns {"load_balance", "z_loss"} scalars:

    - load_balance: Switch-Transformer style ``E * sum_e f_e * P_e``
      where f_e is the fraction of (token, choice) assignments routed to
      expert e and P_e the mean router probability mass — equals 1.0
      under perfectly uniform routing, grows as experts collapse.
    - z_loss: ``mean(logsumexp(logits)^2)`` — keeps router logits from
      drifting to magnitudes where the softmax saturates.
    """
    e = cfg.n_experts
    logits2 = router_logits.reshape(-1, e)
    probs = jax.nn.softmax(logits2, axis=-1)
    p_e = probs.mean(axis=0)  # [E]
    assign = jax.nn.one_hot(top_idx.reshape(-1), e, dtype=jnp.float32)
    f_e = assign.mean(axis=0)  # fraction of assignments per expert
    load_balance = e * jnp.sum(f_e * p_e)
    z = jnp.mean(jax.nn.logsumexp(logits2, axis=-1) ** 2)
    return {"load_balance": load_balance, "z_loss": z}


def _zero_aux() -> dict:
    return {
        "load_balance": jnp.zeros((), jnp.float32),
        "z_loss": jnp.zeros((), jnp.float32),
    }


def _mlp(
    cfg: ModelConfig,
    p: dict,
    h: jnp.ndarray,
    collect_aux: bool = False,
    active=None,
):
    """The block's feed-forward. A layer without a router is dense
    SwiGLU, whatever the model (an MoE model's leading dense layers);
    ``cfg.moe_dropless`` takes :func:`_moe_dropless` (``active``: see
    there), every older MoE preset the two paths below."""
    if not cfg.is_moe or "router" not in p:
        y = _ffn(cfg, h, p.get("w_gate"), p["w_up"], p["w_down"])
        return (y, _zero_aux()) if collect_aux else y
    if cfg.moe_dropless:
        y, logits, top_idx, _ = _moe_dropless(cfg, p, h, active=active)
        if collect_aux:
            return y, moe_router_aux(cfg, logits, top_idx)
        return y
    if not cfg.moe_dense_at(h.shape[0] * h.shape[1]):
        return _moe_dispatch(cfg, p, h, collect_aux=collect_aux)
    # Mixtral MoE: top-k routing, dense all-experts compute, weighted combine.
    router_logits = (h @ p["router"]).astype(jnp.float32)  # [B, S, E]
    top_vals, top_idx = jax.lax.top_k(router_logits, cfg.n_experts_per_token)
    top_w = jax.nn.softmax(top_vals, axis=-1)  # [B, S, k]
    # combine weights scattered back over the expert axis: [B, S, E]
    combine = jnp.sum(
        jax.nn.one_hot(top_idx, cfg.n_experts, dtype=jnp.float32)
        * top_w[..., None],
        axis=-2,
    )
    gate = jax.nn.silu(jnp.einsum("bsd,edf->bsef", h, _w(p["w_gate"])))
    up = jnp.einsum("bsd,edf->bsef", h, _w(p["w_up"]))
    expert_out = jnp.einsum("bsef,efd->bsed", gate * up, _w(p["w_down"]))
    y = jnp.einsum(
        "bsed,bse->bsd", expert_out, combine.astype(expert_out.dtype)
    )
    if collect_aux:
        return y, moe_router_aux(cfg, router_logits, top_idx)
    return y


def _ffn(cfg: ModelConfig, h, w_gate, w_up, w_down):
    """A feed-forward in the configuration's form: SwiGLU, or the
    ungated ``W_down · relu(W_up h)²`` (which has no gate matrix)."""
    if cfg.mlp_form == "relu2":
        up = jax.nn.relu(_qmm(h, w_up))
        return _qmm(up * up, w_down)
    return swiglu(h, w_gate, w_up, w_down)


def moe_route(cfg: ModelConfig, router, x: jnp.ndarray, bias=None):
    """Router of the dropless layer: x [T, D] -> (logits [T, E] f32,
    weights [T, k] f32, experts [T, k]).

    ``softmax_topk`` (DeepSeek-V2): softmax over ALL experts in float32
    (the product too: which experts a token takes is a discrete choice,
    and the system's k-th expert should be the reference's wherever
    their inputs agree), the k largest probabilities as they are —
    divided by their sum only under ``moe_renormalize`` — times
    ``moe_routed_scale``. ``topk_softmax`` (Mixtral): the k largest
    logits, softmaxed among themselves. ``sigmoid_topk`` (DeepSeek-V3,
    Nemotron-3): sigmoid scores in float32; the k largest of score +
    ``bias`` [E] are CHOSEN, the weights are the chosen scores
    themselves, renormalised and scaled as above."""
    k = cfg.n_experts_per_token
    if cfg.moe_router == "sigmoid_topk":
        logits = jnp.einsum(
            "td,de->te",
            x.astype(jnp.float32),
            router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        scores = jax.nn.sigmoid(logits)
        _, top_idx = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
        if cfg.moe_renormalize:
            top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
        return logits, top_w * cfg.moe_routed_scale, top_idx
    if cfg.moe_router == "softmax_topk":
        logits = jnp.einsum(
            "td,de->te",
            x.astype(jnp.float32),
            router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        top_w, top_idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        if cfg.moe_renormalize:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        return logits, top_w * cfg.moe_routed_scale, top_idx
    logits = (x @ router).astype(jnp.float32)
    top_vals, top_idx = jax.lax.top_k(logits, k)
    return logits, jax.nn.softmax(top_vals, axis=-1), top_idx


def _grouped_matmul(x, leaf, tile_expert, n_live):
    """``x [n_tiles * tm, K]`` times expert ``tile_expert[i]``'s matrix
    for tile i, out of ``leaf``: one layer's experts [E, K, N] (an
    array or a QuantizedTensor), or a ``StackedQuant`` view of the
    [L, E, K, N] stack, which the Pallas kernel indexes in place at
    ``layer * E + expert`` (the stack's leading axes merge for free).
    Off the kernel: the tiles' matrices gathered and one batched dot —
    the CPU path of the tests, exact but no bandwidth shaping."""
    from llm_consensus_tpu.ops.pallas.moe_matmul import (
        MOE_TILE,
        moe_grouped_matmul,
        moe_grouped_matmul_supported,
    )
    from llm_consensus_tpu.ops.quant import (
        QuantizedTensor,
        StackedQuant,
        _use_kernel,
    )

    full = leaf.full if isinstance(leaf, StackedQuant) else leaf
    if (
        isinstance(full, QuantizedTensor)
        and _use_kernel(full)
        and moe_grouped_matmul_supported(*full.q.shape[-2:])
    ):
        k, n = full.q.shape[-2:]
        group = tile_expert
        if isinstance(leaf, StackedQuant):
            group = tile_expert + leaf.layer * full.q.shape[1]
        return moe_grouped_matmul(
            x,
            full.q.reshape(-1, k, n),
            full.scale.reshape(-1, 1, n),
            group,
            n_live,
        )
    if isinstance(leaf, StackedQuant):
        leaf = leaf.sliced()
    xt = x.reshape(tile_expert.shape[0], MOE_TILE, x.shape[-1])
    if isinstance(leaf, QuantizedTensor):
        out = jnp.einsum(
            "tmk,tkn->tmn", xt, leaf.q[tile_expert].astype(x.dtype),
            preferred_element_type=jnp.float32,
        ) * leaf.scale[tile_expert]
    else:
        out = jnp.einsum(
            "tmk,tkn->tmn", xt, _w(leaf)[tile_expert].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
    return out.reshape(x.shape[0], -1).astype(x.dtype)


def _moe_dropless(cfg: ModelConfig, p: dict, h: jnp.ndarray, active=None):
    """Dropless expert layer: every token's k experts, exactly.

    Assignments (token, expert) are sorted by expert and each expert's
    rows padded to whole tiles (``ops.pallas.moe_matmul``); three
    grouped matmuls (gate, up, down) then read only the experts some
    token reached, each once. No capacity, so nothing is dropped and a
    1-row decode step and an 80-token fused step run the same code.
    The shared experts are one SwiGLU every token takes.

    ``active`` ([T] bool or None): rows that carry no request (idle
    decode slots) take no expert — they would reach up to k experts
    each that nobody needs read; their routed output is zero.

    Returns (y, router logits, chosen experts, stats) with ``stats`` =
    int32 [experts reached, assignments] of this call.
    """
    from llm_consensus_tpu.ops.pallas.moe_matmul import MOE_TILE, n_tiles_for

    b, s, d = h.shape
    t = b * s
    e, k = cfg.n_experts, cfg.n_experts_per_token
    tm = MOE_TILE
    x = h.reshape(t, d)
    logits, top_w, top_idx = moe_route(
        cfg, _w(p["router"]), x, p.get("router_bias")
    )

    a = t * k
    n_tiles = n_tiles_for(a, e, tm)
    e_flat = top_idx.reshape(a).astype(jnp.int32)
    if active is not None:
        # Expert id E sorts last and owns no tile.
        e_flat = jnp.where(jnp.repeat(active.reshape(t), k), e_flat, e)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    counts = jnp.zeros((e + 1,), jnp.int32).at[e_flat].add(1)[:e]
    tiles_e = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles_e)
    n_live = tile_end[-1]
    row0 = (tile_end - tiles_e) * tm  # first padded row of each expert
    first = jnp.cumsum(counts) - counts  # first sorted assignment
    es = jnp.minimum(e_sorted, e - 1)
    dest_sorted = jnp.where(
        e_sorted < e,
        row0[es] + jnp.arange(a, dtype=jnp.int32) - first[es],
        n_tiles * tm,  # out of range: dropped by the scatter below
    )
    tiles = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_expert = jnp.searchsorted(
        tile_end, jnp.minimum(tiles, jnp.maximum(n_live - 1, 0)), side="right"
    ).astype(jnp.int32)
    tile_expert = jnp.minimum(tile_expert, e - 1)
    src_tok = (
        jnp.zeros((n_tiles * tm,), jnp.int32)
        .at[dest_sorted]
        .set(order // k, mode="drop")
    )
    x_sorted = x[src_tok]
    if cfg.mlp_form == "relu2":  # ungated: two matrices an expert
        up = jax.nn.relu(
            _grouped_matmul(x_sorted, p["w_up"], tile_expert, n_live)
        )
        act = up * up
    else:
        gate = _grouped_matmul(x_sorted, p["w_gate"], tile_expert, n_live)
        up = _grouped_matmul(x_sorted, p["w_up"], tile_expert, n_live)
        act = jax.nn.silu(gate) * up
    y_sorted = _grouped_matmul(act, p["w_down"], tile_expert, n_live)
    dest = jnp.zeros((a,), jnp.int32).at[order].set(dest_sorted)
    live = dest < n_tiles * tm
    y_tok = jnp.where(
        live[:, None],
        y_sorted[jnp.minimum(dest, n_tiles * tm - 1)].astype(jnp.float32),
        0.0,
    ).reshape(t, k, d)
    y = jnp.einsum("tkd,tk->td", y_tok, top_w).astype(h.dtype).reshape(b, s, d)
    if cfg.n_shared_experts:
        y = y + _ffn(cfg, h, p.get("ws_gate"), p["ws_up"], p["ws_down"])
    stats = jnp.stack([jnp.sum(counts > 0), jnp.sum(counts)]).astype(jnp.int32)
    return y, logits, top_idx, stats


def _moe_dispatch(
    cfg: ModelConfig, p: dict, h: jnp.ndarray, collect_aux: bool = False
):
    """GShard/Switch-style capacity-bounded expert dispatch.

    The dense path above computes EVERY expert for every token (E/k times
    the needed FLOPs — 4x for Mixtral's 8-choose-2); this packs each
    expert's assigned tokens into a fixed-capacity [E, C, D] buffer via
    einsum dispatch masks, so only routed tokens are computed and the
    expert axis shards cleanly over ``expert`` (the dispatch einsums
    become GSPMD all-to-alls). Static capacity
    C = ceil(T * k / E * capacity_factor); tokens past an expert's
    capacity fall back to that expert contributing nothing (standard
    GShard semantics — first-come within (choice-rank, token) order).
    """
    b, s, d = h.shape
    t = b * s
    e, k = cfg.n_experts, cfg.n_experts_per_token
    cap = -(-t * k * cfg.moe_capacity_factor // e)
    cap = int(min(max(cap, 1), t * k))
    x = h.reshape(t, d)

    router_logits = (x @ p["router"]).astype(jnp.float32)  # [T, E]
    top_vals, top_idx = jax.lax.top_k(router_logits, k)
    top_w = jax.nn.softmax(top_vals, axis=-1)  # [T, k]

    # Queue position of each (choice-rank, token) in its expert's buffer:
    # rank-major order gives first choices priority when capacity binds.
    # Built one rank at a time so peak temporaries stay [T, E, C] (a
    # k-expanded [k*T, E, C] buffer would be ~1.3 GB per copy at
    # Mixtral prefill scale).
    onehot = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)  # [T, k, E]
    counts = jnp.zeros((e,), jnp.float32)
    disp_mask = jnp.zeros((t, e, cap), jnp.float32)
    combine = jnp.zeros((t, e, cap), jnp.float32)
    for r in range(k):
        oh_r = onehot[:, r, :]  # [T, E]
        pos_r = jnp.cumsum(oh_r, axis=0) - oh_r + counts  # [T, E]
        keep_r = (pos_r < cap) * oh_r
        slot_r = (
            jax.nn.one_hot(pos_r.astype(jnp.int32), cap, dtype=jnp.float32)
            * keep_r[..., None]
        )  # [T, E, C]
        disp_mask = disp_mask + slot_r
        combine = combine + slot_r * top_w[:, r][:, None, None]
        counts = counts + oh_r.sum(axis=0)

    xin = jnp.einsum("td,tec->ecd", x.astype(jnp.float32), disp_mask)
    xin = xin.astype(h.dtype)
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, _w(p["w_gate"])))
    up = jnp.einsum("ecd,edf->ecf", xin, _w(p["w_up"]))
    out_e = jnp.einsum("ecf,efd->ecd", gate * up, _w(p["w_down"]))
    y = jnp.einsum("ecd,tec->td", out_e.astype(jnp.float32), combine)
    y = y.astype(h.dtype).reshape(b, s, d)
    if collect_aux:
        return y, moe_router_aux(cfg, router_logits, top_idx)
    return y


def _mla_project(cfg: ModelConfig, p: dict, h: jnp.ndarray, cos, sin):
    """MLA projections of h [b, s, D]: queries [b, s, H, nope + rope]
    with their rotary tail rotated, the normalised latent c [b, s, r]
    and the ONE rotated rotary key a token k_pe [b, s, rope]."""
    b, s, _ = h.shape
    r = cfg.kv_lora_rank
    q = _qmm(h, p["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    q = apply_rope_tail(q, cos, sin)
    kva = _qmm(h, p["w_kva"])
    c = rms_norm(kva[..., :r], p["kv_a_norm"], cfg.rms_norm_eps)
    k_pe = apply_rope(kva[..., None, r:], cos, sin)[..., 0, :]
    return q, c, k_pe


def _kvb_heads(cfg: ModelConfig, leaf, dtype):
    """``w_kvb`` [r, H * (nope + v)] as (w [r, H, nope + v] in
    ``dtype``, scale [H, nope + v] or None): an int8 leaf stays
    unscaled integers beside its per-column scale, so that the absorbed
    products can fold the scale into the side it belongs to."""
    from llm_consensus_tpu.ops.quant import QuantizedTensor, StackedQuant

    if isinstance(leaf, StackedQuant):
        leaf = leaf.sliced()
    h, per = cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim
    if isinstance(leaf, QuantizedTensor):
        return (
            leaf.q.astype(dtype).reshape(-1, h, per),
            leaf.scale.reshape(h, per),
        )
    return _w(leaf, dtype).reshape(-1, h, per), None


def mla_absorb_q(cfg: ModelConfig, w_kvb, q_nope: jnp.ndarray):
    """q_lat = q_nope · W_uk^T: [.., H, nope] -> [.., H, r]. A query in
    latent space scores against the cached latent directly, so no
    per-head key is ever expanded."""
    w, scale = _kvb_heads(cfg, w_kvb, q_nope.dtype)
    dn = cfg.qk_nope_head_dim
    if scale is not None:
        q_nope = (q_nope * scale[:, :dn]).astype(q_nope.dtype)
    return jnp.einsum(
        "...hd,rhd->...hr", q_nope, w[..., :dn],
        preferred_element_type=jnp.float32,
    ).astype(q_nope.dtype)


def mla_expand_o(cfg: ModelConfig, w_kvb, o_lat: jnp.ndarray):
    """o = o_lat · W_uv: [.., H, r] -> [.., H, v]."""
    w, scale = _kvb_heads(cfg, w_kvb, o_lat.dtype)
    dn = cfg.qk_nope_head_dim
    o = jnp.einsum(
        "...hr,rhd->...hd", o_lat, w[..., dn:],
        preferred_element_type=jnp.float32,
    )
    if scale is not None:
        o = o * scale[:, dn:]
    return o.astype(o_lat.dtype)


def _mla_attn_full(cfg: ModelConfig, p: dict, q, c, k_pe, positions):
    """Full causal MLA in the EXPANDED form (per-head keys and values
    from ``w_kvb``): the non-paged :func:`forward`. The paged programs
    run the absorbed form; tests hold the two to each other."""
    b, s, _ = c.shape
    dn = cfg.qk_nope_head_dim
    kv = _qmm(c, p["w_kvb"]).reshape(b, s, cfg.n_heads, dn + cfg.v_head_dim)
    k = jnp.concatenate(
        [
            kv[..., :dn],
            jnp.broadcast_to(
                k_pe[:, :, None, :], (b, s, cfg.n_heads, cfg.qk_rope_head_dim)
            ),
        ],
        axis=-1,
    )
    return causal_attention(
        q, k, kv[..., dn:], positions, scale=cfg.attn_scale
    )


def _pad_lanes(x: jnp.ndarray, width: int) -> jnp.ndarray:
    pad = width - x.shape[-1]
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _block(
    cfg: ModelConfig,
    p: dict,
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    kv_layer: tuple | None,
    mode: str,
    valid_len: jnp.ndarray | None,
    positions: jnp.ndarray | None,
    uniform_write: bool = False,
    mesh=None,
    collect_aux: bool = False,
    shared_prefix_len=None,
):
    """One transformer block.

    ``kv_layer``: this layer's cache leaves — (k, v) for the bf16 cache,
    (k_q, v_q, k_scale, v_scale) for the int8 cache (head-major). Returns
    (x, new_kv_layer_tuple_or_None).

    ``uniform_write`` (static): caller guarantees every row writes at
    the SAME position (self-consistency fan-out after shared prefill) —
    the decode cache write becomes one ``dynamic_update_slice`` instead
    of a per-row scatter, which XLA:TPU serializes badly.

    ``shared_prefix_len`` (traced scalar or None; decode mode only):
    rows share identical cache content in [0, shared_prefix_len) — the
    decode attention reads that region once for the whole batch via the
    shared-prefix kernels (see :func:`_attn_decode`).
    """
    h = _rms(cfg, x, p["attn_norm"], mesh)
    if cfg.is_mla:
        if mode != "full":
            raise NotImplementedError(
                f"{cfg.name}: latent attention runs through forward() and "
                "the paged step programs (serve --backend continuous); the "
                f"dense-cache {mode!r} path has no latent layout"
            )
        q, c, k_pe = _mla_project(cfg, p, h, cos, sin)
        attn = _mla_attn_full(cfg, p, q, c, k_pe, positions)
        x = x + _qmm(attn.reshape(*x.shape[:-1], -1), p["wo"])
        h2 = _rms(cfg, x, p["mlp_norm"], mesh)
        if collect_aux:
            y, aux = _mlp(cfg, p, h2, collect_aux=True)
            return x + y, None, aux
        return x + _mlp(cfg, p, h2), None
    q, k, v = _project_qkv(cfg, p, h)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    if mode == "full":
        attn = _attn_causal(cfg, q, k, v, positions, mesh=mesh)
        new_kv = None
    elif mode == "prefill":
        attn = _attn_causal(cfg, q, k, v, positions, mesh=mesh)
        s = k.shape[1]
        if len(kv_layer) == 2:
            k_l, v_l = kv_layer
            new_kv = (
                k_l.at[:, :s].set(k.astype(k_l.dtype)),
                v_l.at[:, :s].set(v.astype(v_l.dtype)),
            )
        else:
            kq_l, vq_l, ks_l, vs_l = kv_layer
            kq, ks = quantize_kv(k)  # [B,S,Hkv,D] / [B,S,Hkv]
            vq, vs = quantize_kv(v)
            new_kv = (
                kq_l.at[:, :, :s].set(kq.transpose(0, 2, 1, 3)),
                vq_l.at[:, :, :s].set(vq.transpose(0, 2, 1, 3)),
                ks_l.at[:, :, :s].set(ks.transpose(0, 2, 1)),
                vs_l.at[:, :, :s].set(vs.transpose(0, 2, 1)),
            )
    elif mode == "chunk":
        # K-token chunk (speculative verification / prefix-cached
        # continuation): write all K tokens' k/v at slots
        # [valid_len, valid_len + K) (ragged per row), then ragged-causal
        # attention over the cache.
        b, kq = x.shape[0], x.shape[1]
        batch_idx = jnp.arange(b)[:, None]  # [B, 1]
        pos = valid_len[:, None] + jnp.arange(kq)[None, :]  # [B, K]
        if len(kv_layer) == 2:
            k_l, v_l = kv_layer
            new_k = k_l.at[batch_idx, pos].set(k.astype(k_l.dtype))
            new_v = v_l.at[batch_idx, pos].set(v.astype(v_l.dtype))
            new_kv = (new_k, new_v)
            attn = chunk_decode_attention(
                q, new_k, new_v, valid_len, window=cfg.sliding_window
            )
        else:
            # int8 head-major cache (prefix-cached generation on
            # kv_quant engines). The chunk path is prefill-like, not
            # the decode hot loop: quantized writes keep the cache
            # layout canonical; attention reads a dequantized slab
            # (bf16) through the same ragged-causal rule — exactness
            # vs the bf16 path bounded only by int8 KV rounding.
            kq_l, vq_l, ks_l, vs_l = kv_layer
            kqn, ksn = quantize_kv(k)  # [B,K,Hkv,D] / [B,K,Hkv]
            vqn, vsn = quantize_kv(v)
            hidx = jnp.arange(kq_l.shape[1])[None, :, None]  # [1,Hkv,1]
            pos_h = pos[:, None, :]  # [B,1,K]
            bidx_h = batch_idx[:, :, None]  # [B,1,1]
            new_kq = kq_l.at[bidx_h, hidx, pos_h].set(kqn.transpose(0, 2, 1, 3))
            new_vq = vq_l.at[bidx_h, hidx, pos_h].set(vqn.transpose(0, 2, 1, 3))
            new_ks = ks_l.at[bidx_h, hidx, pos_h].set(ksn.transpose(0, 2, 1))
            new_vs = vs_l.at[bidx_h, hidx, pos_h].set(vsn.transpose(0, 2, 1))
            new_kv = (new_kq, new_vq, new_ks, new_vs)
            deq_k = (
                (new_kq.astype(jnp.float32) * new_ks[..., None])
                .astype(q.dtype)
                .transpose(0, 2, 1, 3)  # -> [B, S, Hkv, D]
            )
            deq_v = (
                (new_vq.astype(jnp.float32) * new_vs[..., None])
                .astype(q.dtype)
                .transpose(0, 2, 1, 3)
            )
            attn = chunk_decode_attention(
                q, deq_k, deq_v, valid_len, window=cfg.sliding_window
            )
    elif mode == "decode":
        b = x.shape[0]
        batch_idx = jnp.arange(b)
        # valid_len is the pre-write fill length; write the new token there.
        if isinstance(kv_layer[0], str) and kv_layer[0] == "stacked":
            # Quant cache, WHOLE stacked buffers + traced layer index:
            # the new token's k/v is written into the stack, and decode
            # attention reads the stack directly (scalar-prefetch kernel
            # — no per-layer cache slice materialization).
            _, (kq_f, vq_f, ks_f, vs_f), layer_idx = kv_layer
            kq1, ks1 = quantize_kv(k[:, 0])  # [B,Hkv,D] / [B,Hkv]
            vq1, vs1 = quantize_kv(v[:, 0])
            if uniform_write:
                pos0 = valid_len[0]
                zero = jnp.zeros((), pos0.dtype)
                li = layer_idx.astype(pos0.dtype)
                kq_f = jax.lax.dynamic_update_slice(
                    kq_f, kq1[None, :, :, None, :], (li, zero, zero, pos0, zero)
                )
                vq_f = jax.lax.dynamic_update_slice(
                    vq_f, vq1[None, :, :, None, :], (li, zero, zero, pos0, zero)
                )
                ks_f = jax.lax.dynamic_update_slice(
                    ks_f, ks1[None, :, :, None], (li, zero, zero, pos0)
                )
                vs_f = jax.lax.dynamic_update_slice(
                    vs_f, vs1[None, :, :, None], (li, zero, zero, pos0)
                )
            else:
                kq_f = kq_f.at[layer_idx, batch_idx, :, valid_len].set(kq1)
                vq_f = vq_f.at[layer_idx, batch_idx, :, valid_len].set(vq1)
                ks_f = ks_f.at[layer_idx, batch_idx, :, valid_len].set(ks1)
                vs_f = vs_f.at[layer_idx, batch_idx, :, valid_len].set(vs1)
            new_kv = (kq_f, vq_f, ks_f, vs_f)
            attn = _attn_decode_quant_stacked(
                cfg, q, kq_f, ks_f, vq_f, vs_f, valid_len + 1, layer_idx,
                shared_prefix_len=shared_prefix_len,
            )
        elif len(kv_layer) == 2:
            k_l, v_l = kv_layer
            if uniform_write:
                pos0 = valid_len[0]
                new_k = jax.lax.dynamic_update_slice(
                    k_l, k.astype(k_l.dtype), (0, pos0, 0, 0)
                )
                new_v = jax.lax.dynamic_update_slice(
                    v_l, v.astype(v_l.dtype), (0, pos0, 0, 0)
                )
            else:
                new_k = k_l.at[batch_idx, valid_len].set(
                    k[:, 0].astype(k_l.dtype)
                )
                new_v = v_l.at[batch_idx, valid_len].set(
                    v[:, 0].astype(v_l.dtype)
                )
            new_kv = (new_k, new_v)
            attn = _attn_decode(
                cfg, q, new_k, new_v, valid_len + 1,
                shared_prefix_len=shared_prefix_len,
            )
        else:
            kq_l, vq_l, ks_l, vs_l = kv_layer
            kq1, ks1 = quantize_kv(k[:, 0])  # [B,Hkv,D] / [B,Hkv]
            vq1, vs1 = quantize_kv(v[:, 0])
            if uniform_write:
                pos0 = valid_len[0]
                zero = jnp.zeros((), pos0.dtype)
                new_kq = jax.lax.dynamic_update_slice(
                    kq_l, kq1[:, :, None, :], (zero, zero, pos0, zero)
                )
                new_vq = jax.lax.dynamic_update_slice(
                    vq_l, vq1[:, :, None, :], (zero, zero, pos0, zero)
                )
                new_ks = jax.lax.dynamic_update_slice(
                    ks_l, ks1[:, :, None], (zero, zero, pos0)
                )
                new_vs = jax.lax.dynamic_update_slice(
                    vs_l, vs1[:, :, None], (zero, zero, pos0)
                )
            else:
                new_kq = kq_l.at[batch_idx, :, valid_len].set(kq1)
                new_vq = vq_l.at[batch_idx, :, valid_len].set(vq1)
                new_ks = ks_l.at[batch_idx, :, valid_len].set(ks1)
                new_vs = vs_l.at[batch_idx, :, valid_len].set(vs1)
            new_kv = (new_kq, new_vq, new_ks, new_vs)
            attn = _attn_decode_quant(
                cfg, q, new_kq, new_ks, new_vq, new_vs, valid_len + 1,
                shared_prefix_len=shared_prefix_len,
            )
    else:  # pragma: no cover
        raise ValueError(mode)

    x = x + _qmm(attn.reshape(*x.shape[:-1], -1), p["wo"])
    h2 = _rms(cfg, x, p["mlp_norm"], mesh)
    if collect_aux:
        y, aux = _mlp(cfg, p, h2, collect_aux=True)
        return x + y, new_kv, aux
    x = x + _mlp(cfg, p, h2)
    return x, new_kv


def layer_stacks(params: dict) -> list[tuple[dict, int]]:
    """The model's layer stacks in order, each with the absolute index
    of its first layer: leading dense layers of an MoE model
    (``dense_blocks``) and the rest (``blocks``). One stack holds
    layers of one shape, which is what a scan over it needs."""
    stacks = []
    first = 0
    for name in ("dense_blocks", "blocks"):
        if name in params:
            stacks.append((params[name], first))
            first += len(jax.tree_util.tree_leaves(params[name])[0])
    return stacks


def first_layers(cfg: ModelConfig, params: dict, n: int):
    """``(cfg, params)`` of the model's first ``n`` layers: the stacks
    cut to them (leading dense layers count), embedding, final norm and
    head kept (``serve --layers`` on a loaded checkpoint)."""
    cut = cfg.with_layers(n)
    out = dict(params)
    if cfg.layer_plan:
        for kind, name in PLAN_STACKS.items():
            if name not in params:
                continue
            keep = cut.n_of(kind)
            if keep:
                out[name] = jax.tree.map(lambda a: a[:keep], params[name])
            else:
                del out[name]
        return cut, out
    for name in ("dense_blocks", "blocks"):
        if name not in params:
            continue
        have = len(jax.tree_util.tree_leaves(params[name])[0])
        keep = min(n, have)
        n -= keep
        if keep:
            out[name] = jax.tree.map(lambda a: a[:keep], params[name])
        else:
            del out[name]
    return cut, out


def unstack_blocks(params: dict) -> dict:
    """Per-layer weight buffers: "blocks" [L, ...] -> tuple of L dicts.

    Makes :func:`_run_layers` unroll a python loop over separate
    per-layer buffers instead of scanning the stacked layer axis. In
    principle this avoids materializing each layer's weight slice as a
    Pallas-operand copy; MEASURED on v5e at bench shapes it is a net
    LOSS (default bench config 24.8k -> 22.8k tok/s/chip, bf16-cache
    pallas path ~10x worse): the scan pipelines weight streaming across
    layers, and per-layer cache slices still materialize. Kept as an
    opt-in experiment (``EngineConfig.unroll_layers``) for other
    topologies; the cache-copy problem the unroll targeted is fixed
    inside the scan itself (cache leaves ride the scan carry, see
    ``_run_layers``). Training and sharded paths always use the stacked
    layout (compile time, pspecs).
    """
    if isinstance(params["blocks"], (list, tuple)):
        return params
    out = {k: v for k, v in params.items() if k != "dense_blocks"}
    out["blocks"] = tuple(
        jax.tree.map(lambda a: a[i], blocks)
        for blocks, _ in layer_stacks(params)
        for i in range(jax.tree_util.tree_leaves(blocks)[0].shape[0])
    )
    return out


def _run_layers(
    cfg: ModelConfig,
    params: dict,
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    cache: KVCache | None,
    mode: str,
    valid_len: jnp.ndarray | None,
    positions: jnp.ndarray | None,
    remat: bool = False,
    uniform_write: bool = False,
    mesh=None,
    collect_aux: bool = False,
    shared_prefix_len=None,
):
    """lax.scan over the stacked layer axis (python-unrolled loop when
    ``params["blocks"]`` is a tuple of per-layer dicts — see
    :func:`unstack_blocks`).

    ``collect_aux`` (full mode only): also return the per-layer MoE
    router aux losses averaged over layers ({"load_balance", "z_loss"}).
    ``shared_prefix_len`` (decode mode): see :func:`_block`.
    """
    if cfg.layer_plan:
        if mode != "full":
            raise NotImplementedError(
                f"{cfg.name}: a model with recurrent (state-space) layers "
                "runs through forward() and the paged step programs (serve "
                f"--backend continuous); the contiguous-cache {mode!r} path "
                "keeps no recurrent state"
            )
        return _run_plan_full(
            cfg, params, x, cos, sin, positions, remat=remat, mesh=mesh,
            collect_aux=collect_aux,
        )
    blocks = params["blocks"]

    if isinstance(blocks, (list, tuple)):
        return _run_layers_unrolled(
            cfg, blocks, x, cos, sin, cache, mode, valid_len, positions,
            remat=remat, uniform_write=uniform_write, mesh=mesh,
            collect_aux=collect_aux, shared_prefix_len=shared_prefix_len,
        )

    if mode == "full":

        def body(carry, p):
            out = _block(
                cfg, p, carry, cos, sin, None, "full", None, positions,
                mesh=mesh, collect_aux=collect_aux,
            )
            if collect_aux:
                y, _, aux = out
                return y, aux
            y, _ = out
            return y, None

        if remat:
            body = jax.checkpoint(body)
        stacks = layer_stacks(params)
        if len(stacks) == 1:
            x, auxes = jax.lax.scan(body, x, blocks)
        else:
            per_stack = []
            for stack, _ in stacks:
                x, a = jax.lax.scan(body, x, stack)
                per_stack.append(a)
            auxes = (
                jax.tree.map(lambda *xs: jnp.concatenate(xs), *per_stack)
                if collect_aux
                else None
            )
        if collect_aux:
            aux = jax.tree.map(jnp.mean, auxes)
            return x, cache, aux
        return x, cache

    if cfg.is_mla or "dense_blocks" in params:
        raise NotImplementedError(
            f"{cfg.name}: only forward() and the paged step programs run "
            f"layers of two shapes or latent attention (mode {mode!r})"
        )
    if isinstance(cache, QuantKVCache):
        kv_leaves = (cache.k_q, cache.v_q, cache.k_scale, cache.v_scale)
    else:
        kv_leaves = (cache.k, cache.v)

    # Cache leaves ride in the scan CARRY and are updated in place at
    # the layer index — NOT as scanned xs with stacked ys outputs. The
    # ys form allocates a fresh stacked cache buffer every call, which
    # in the token-decode loop defeats the outer scan's carry aliasing
    # and copies the ENTIRE cache each step (profiler-measured ~1 GB of
    # pure copy per step at bench shapes on v5e). Weights are NOT
    # scanned either: per-layer views are built from the closed-over
    # stack — quantized matmul weights as lazy ``StackedQuant`` views
    # (the Pallas kernel indexes the resident stack via scalar prefetch
    # instead of forcing a per-layer slice copy), everything else as a
    # dynamic_index XLA fuses into its consumer.
    # Quant-cache decode via the WHOLE stacked cache + layer index (the
    # token write and attention read happen on the resident buffers with
    # no per-layer slice or write-back). Opt-in via set_stacked_decode:
    # not measured on the installed jax (0.9.0).
    stacked_decode = (
        _STACKED_DECODE and mode == "decode" and isinstance(cache, QuantKVCache)
    )

    def body(carry, layer_idx):
        y, *leaves = carry
        p = _layer_view(blocks, layer_idx)
        if stacked_decode:
            y, new_leaves = _block(
                cfg,
                p,
                y,
                cos,
                sin,
                ("stacked", tuple(leaves), layer_idx),
                mode,
                valid_len,
                positions,
                uniform_write=uniform_write,
                mesh=mesh,
                shared_prefix_len=shared_prefix_len,
            )
            return (y, *new_leaves), None
        layer_kv = tuple(
            jax.lax.dynamic_index_in_dim(
                leaf, layer_idx, axis=0, keepdims=False
            )
            for leaf in leaves
        )
        y, new_kv = _block(
            cfg,
            p,
            y,
            cos,
            sin,
            layer_kv,
            mode,
            valid_len,
            positions,
            uniform_write=uniform_write,
            mesh=mesh,
            shared_prefix_len=shared_prefix_len,
        )
        leaves = tuple(
            jax.lax.dynamic_update_index_in_dim(leaf, nk, layer_idx, axis=0)
            for leaf, nk in zip(leaves, new_kv)
        )
        return (y, *leaves), None

    if remat:
        body = jax.checkpoint(body)
    layer_ids = jnp.arange(len(jax.tree_util.tree_leaves(blocks)[0]))
    (x, *new_leaves), _ = jax.lax.scan(body, (x, *kv_leaves), layer_ids)
    if isinstance(cache, QuantKVCache):
        return x, QuantKVCache(*new_leaves, length=cache.length)
    return x, KVCache(k=new_leaves[0], v=new_leaves[1], length=cache.length)


def _plan_layers(cfg: ModelConfig):
    """(kind, index within its kind's stack) of each planned layer."""
    seen: dict[str, int] = {}
    out = []
    for kind in cfg.plan_kinds():
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def _run_plan_full(
    cfg, params, x, cos, sin, positions, remat=False, mesh=None,
    collect_aux=False,
):
    """Full causal pass of a planned model, no cache: a Python loop over
    the plan (tests, scoring, training parity — the serving path is
    :func:`_paged_layers`). Returns (x, None[, aux])."""

    def layer(x, p, kind):
        h = _rms(cfg, x, p["norm"], mesh)
        aux = _zero_aux()
        if kind == "ssm":
            y = _ssm_mix_full(cfg, p, h)
        elif kind == "attn":
            q, k, v = _project_qkv(cfg, p, h)
            if cfg.positions == "rope":
                q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            attn = _attn_causal(cfg, q, k, v, positions, mesh=mesh)
            y = _qmm(attn.reshape(*x.shape[:-1], -1), p["wo"])
        elif kind == "moe":
            y, logits, top_idx, _ = _moe_dropless(cfg, p, h)
            if collect_aux:
                aux = moe_router_aux(cfg, logits, top_idx)
        else:
            y = _ffn(cfg, h, p.get("w_gate"), p["w_up"], p["w_down"])
        return x + y.astype(x.dtype), aux

    if remat:
        layer = jax.checkpoint(layer, static_argnums=(2,))
    auxes = []
    for kind, i in _plan_layers(cfg):
        p = jax.tree.map(lambda a: a[i], params[PLAN_STACKS[kind]])
        x, aux = layer(x, p, kind)
        if kind == "moe":
            auxes.append(aux)
    if collect_aux:
        aux = (
            jax.tree.map(lambda *xs: jnp.mean(jnp.stack(xs)), *auxes)
            if auxes else _zero_aux()
        )
        return x, None, aux
    return x, None


def _layer_view(blocks: dict, layer_idx) -> dict:
    """One layer's params from the stacked blocks, sliced lazily.

    int8 ``QuantizedTensor`` stacks become :class:`StackedQuant` views
    (consumed by ``ops.quant.matmul``'s scalar-prefetch kernel without
    materializing the slice); every other leaf is a ``dynamic_index``
    that XLA fuses into its consumer.
    """
    from llm_consensus_tpu.ops.quant import QuantizedTensor, StackedQuant

    view = {}
    for name, leaf in blocks.items():
        if isinstance(leaf, QuantizedTensor) and leaf.q.ndim in (3, 4):
            # [L, K, N], or a layer's experts [L, E, K, N]: the grouped
            # expert matmul indexes the stack at layer * E + expert.
            view[name] = StackedQuant(full=leaf, layer=layer_idx)
        else:
            view[name] = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, layer_idx, 0, keepdims=False
                ),
                leaf,
            )
    return view


def _run_layers_unrolled(
    cfg: ModelConfig,
    blocks,
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    cache: KVCache | None,
    mode: str,
    valid_len: jnp.ndarray | None,
    positions: jnp.ndarray | None,
    remat: bool = False,
    uniform_write: bool = False,
    mesh=None,
    collect_aux: bool = False,
    shared_prefix_len=None,
):
    """Python-unrolled layer loop over per-layer weight buffers.

    Cache leaves are sliced/written at STATIC layer indices, so XLA
    keeps every update in place on the carried buffers (no per-step
    cache or weight copies — the point of :func:`unstack_blocks`).
    """
    step = _block
    if remat:
        step = jax.checkpoint(
            _block,
            static_argnums=(0, 6),
            static_argnames=("uniform_write", "collect_aux"),
        )

    if mode == "full":
        auxes = []
        for p in blocks:
            out = step(
                cfg, p, x, cos, sin, None, "full", None, positions,
                mesh=mesh, collect_aux=collect_aux,
            )
            if collect_aux:
                x, _, aux = out
                auxes.append(aux)
            else:
                x, _ = out
        if collect_aux:
            aux = jax.tree.map(
                lambda *xs: jnp.mean(jnp.stack(xs)), *auxes
            )
            return x, cache, aux
        return x, cache

    quant = isinstance(cache, QuantKVCache)
    leaves = (
        (cache.k_q, cache.v_q, cache.k_scale, cache.v_scale)
        if quant
        else (cache.k, cache.v)
    )
    for i, p in enumerate(blocks):
        layer_kv = tuple(leaf[i] for leaf in leaves)
        x, new_kv = step(
            cfg, p, x, cos, sin, layer_kv, mode, valid_len, positions,
            uniform_write=uniform_write, mesh=mesh,
            shared_prefix_len=shared_prefix_len,
        )
        leaves = tuple(
            leaf.at[i].set(nk) for leaf, nk in zip(leaves, new_kv)
        )
    if quant:
        return x, QuantKVCache(*leaves, length=cache.length)
    return x, KVCache(k=leaves[0], v=leaves[1], length=cache.length)


def _unembed(
    cfg: ModelConfig, params: dict, x: jnp.ndarray, mesh=None
) -> jnp.ndarray:
    x = _rms(cfg, x, params["norm_f"], mesh)
    if cfg.tie_embeddings:
        return jnp.einsum(
            "...d,dv->...v",
            x,
            params["embed"].T,
            preferred_element_type=jnp.float32,
        )
    return _qmm(x, params["lm_head"], out_dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def forward(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,
    positions: jnp.ndarray | None = None,
    remat: bool = False,
    mesh=None,
    return_moe_aux: bool = False,
) -> jnp.ndarray:
    """Full causal forward: tokens [B, S] -> logits [B, S, V] (float32).

    ``mesh``: pass a mesh with ``seq > 1`` (and ``cfg.use_ring``) to run
    attention as sequence-parallel ring attention — the long-context
    path; trace-time constant, so it composes with jit.

    ``return_moe_aux`` (static): also return the layer-averaged MoE
    router aux losses ({"load_balance", "z_loss"} — zeros for dense
    models) for the training loss.
    """
    x = params["embed"][tokens]
    if positions is None:
        positions_arr = jnp.broadcast_to(
            jnp.arange(tokens.shape[1]), tokens.shape
        )
    else:
        positions_arr = positions
    cos, sin = rope_cos_sin(
        positions_arr, cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling
    )
    out = _run_layers(
        cfg, params, x, cos, sin, None, "full", None, positions,
        remat=remat, mesh=mesh, collect_aux=return_moe_aux,
    )
    if return_moe_aux:
        x, _, aux = out
        return _unembed(cfg, params, x, mesh), aux
    x, _ = out
    return _unembed(cfg, params, x, mesh)


def prefill(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,
    lengths: jnp.ndarray,
    cache: KVCache,
    mesh=None,
) -> tuple[jnp.ndarray, KVCache]:
    """Prefill right-padded prompts.

    tokens: [B, S] right-padded; lengths: [B] true prompt lengths.
    Returns (last-valid-token logits [B, V] float32, cache with k/v written
    at slots [0, S) and length set to ``lengths``).

    Padded slots do write garbage k/v into the cache, but they sit at
    indices >= lengths[b] and are (a) masked out of every later decode
    step's attention (``valid_len`` masking) and (b) progressively
    overwritten by decode writes at slot ``length``.
    """
    x = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    cos, sin = rope_cos_sin(
        positions, cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling
    )
    x, cache = _run_layers(
        cfg, params, x, cos, sin, cache, "prefill", None, None, mesh=mesh
    )
    # Gather hidden state at the last real token of each sequence.
    b = tokens.shape[0]
    last = jnp.clip(lengths - 1, 0, tokens.shape[1] - 1)
    x_last = x[jnp.arange(b), last]  # [B, D]
    logits = _unembed(cfg, params, x_last, mesh)
    return logits, cache.with_length(lengths)


def ragged_mesh_shardable(cfg: ModelConfig, mesh, max_slots: int,
                          n_pages: int) -> bool:
    """Whether the Pallas ragged kernel can run under ``shard_map`` on
    this mesh (PR 13): kv heads must split over ``model`` and the
    decode rows / page pool over ``data``. When any axis fails to
    divide, the serving stack still engages every feature on the mesh
    — attention just takes the XLA reference (sharded by GSPMD) instead
    of the manually-partitioned kernel. This predicate is the ONE
    remaining kernel fallback condition on a mesh; callers surface it
    as the residual construction warning."""
    if mesh is None:
        return False
    dp = int(mesh.shape.get("data", 1))
    mp = int(mesh.shape.get("model", 1))
    return (
        cfg.n_kv_heads % mp == 0
        and max_slots % dp == 0
        and n_pages % dp == 0
    )


def _attn_paged(
    cfg: ModelConfig,
    q_dec,
    q_chunk,
    k_pools,
    v_pools,
    layer,
    tables,
    valid,
    chunk_table=None,
    chunk_start=None,
    groups=None,
    mesh=None,
):
    """Paged attention for one layer's decode rows (+ optional prefill
    chunk row) — THE kernel-selection seam of the serving stack, and
    deliberately a short one: ``cfg.use_pallas`` picks the ragged
    kernel, anything else the XLA gather reference with identical
    ragged semantics. Window, groups, and mixed rows are all cases of
    the one kernel — the old per-feature fallback matrix is gone.

    k_pools/v_pools are the WHOLE stacked pools [L, n_pages, page, Hkv,
    Dh] and ``layer`` a traced index. The single-device kernel indexes
    the stack itself (the layer rides scalar prefetch: a page's DMA
    starts from the resident buffer). A Pallas operand must be a whole
    buffer, so a layer sliced out for it would be copied every layer of
    every step. The mesh kernel (``shard_map`` wants the pool's own
    partitioning) and the XLA reference take that layer's view.

    ``mesh`` (trace-time constant, PR 13): on a dp×mp mesh the Pallas
    kernel runs under ``shard_map`` — kv heads partitioned over
    ``model``, decode rows and the page pool over ``data`` (the page
    allocator's slot→shard affinity keeps every row's table
    shard-local), group programs riding with their members' shard and
    the chunk lane resolved on its owner shard. When the mesh shapes
    don't divide (``ragged_mesh_shardable``) the XLA reference runs
    instead — GSPMD shards it — so every serving feature still engages.

    q_dec: [B, H, D]; q_chunk: [L, C, H, D] (L chunk lanes, with
    ``chunk_table`` [L, P] and ``chunk_start`` [L]; -C marks a dead
    lane) or None; returns out_dec [B, H, D] (and out_chunk [L, C, H, D]
    when q_chunk is given). The mesh kernel resolves ONE lane on its
    owner shard, so a mesh program carries one.
    """
    window = cfg.sliding_window
    # An MLA pool is one latent plane [L, n_pages, page, lanes]: q is in
    # latent space (padded to the pool's lanes), the value is the key's
    # first kv_lora_rank lanes and the outputs are that wide.
    latent = (
        dict(scale=cfg.attn_scale, latent_dv=cfg.kv_lora_rank)
        if cfg.is_mla
        else {}
    )
    gtuple = None
    if cfg.use_pallas and groups is not None:
        gtuple = (
            groups.group_id,
            groups.group_rep,
            groups.group_pages.astype(jnp.int32) * k_pools.shape[2],
            groups.shared_start,
        )
    if cfg.use_pallas and mesh is None:
        from llm_consensus_tpu.ops.pallas.attention import (
            ragged_paged_attention,
        )

        return ragged_paged_attention(
            q_dec, k_pools, v_pools, tables, valid, layer=layer,
            q_chunk=q_chunk, chunk_table=chunk_table,
            chunk_start=chunk_start, groups=gtuple, window=window, **latent,
        )
    k_pool = jax.lax.dynamic_index_in_dim(k_pools, layer, 0, keepdims=False)
    v_pool = jax.lax.dynamic_index_in_dim(v_pools, layer, 0, keepdims=False)
    if not cfg.is_mla and cfg.use_pallas and ragged_mesh_shardable(
        cfg, mesh, q_dec.shape[0], k_pool.shape[0]
    ):
        from llm_consensus_tpu.ops.pallas.attention import (
            ragged_paged_attention_sharded,
        )

        if q_chunk is None:
            lane = {}
        elif q_chunk.shape[0] == 1:
            lane = dict(
                q_chunk=q_chunk[0], chunk_table=chunk_table[0],
                chunk_start=chunk_start[0],
            )
        else:
            raise ValueError("the mesh kernel takes one chunk lane")
        out = ragged_paged_attention_sharded(
            mesh, q_dec, k_pool, v_pool, tables, valid,
            groups=gtuple, window=window, **lane,
        )
        return (out[0], out[1][None]) if lane else out
    from llm_consensus_tpu.ops.attention import (
        ragged_paged_attention_reference,
    )

    return ragged_paged_attention_reference(
        q_dec, k_pool, v_pool, tables, valid,
        q_chunk=q_chunk, chunk_table=chunk_table, chunk_start=chunk_start,
        window=window, **latent,
    )


def _mla_attend_paged(
    cfg, p, h, cos, sin, k_pools, v_pools, layer, pages, offs, attend
):
    """One layer's latent attention in a paged step program: write each
    token's latent ``[c | k_pe]`` (zero-padded to the pool's lanes) at
    ``[layer, page, offset]``, attend in the absorbed form — queries
    moved into latent space, values expanded after the sum — and return
    ([b, s, H, v] attention output, the pool)."""
    q, c, k_pe = _mla_project(cfg, p, h, cos, sin)
    lanes = k_pools.shape[-1]
    k_pools = k_pools.at[layer, pages, offs].set(
        _pad_lanes(jnp.concatenate([c, k_pe], axis=-1), lanes).astype(
            k_pools.dtype
        )
    )
    dn = cfg.qk_nope_head_dim
    q_lat = jnp.concatenate(
        [mla_absorb_q(cfg, p["w_kvb"], q[..., :dn]), q[..., dn:]], axis=-1
    )
    o_lat = attend(_pad_lanes(q_lat, lanes), k_pools, v_pools, layer)
    return mla_expand_o(cfg, p["w_kvb"], o_lat), k_pools


def _ssm_project(cfg: ModelConfig, p: dict, hf: jnp.ndarray):
    """A state-space layer's in-projection of hf [T, D]: the gate z
    [T, inner], the convolution's input xBC [T, conv_dim] (both through
    the int8 kernel where the leaves are int8), and the step Δ [T, H] =
    softplus(dt + dt_bias) in float32 (its 64 columns a float matmul)."""
    z = _qmm(hf, p["w_in_z"])
    xbc = _qmm(hf, p["w_in_xbc"])
    dt = jnp.einsum(
        "td,dh->th", hf, p["w_in_dt"], preferred_element_type=jnp.float32
    )
    return z, xbc, jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))


def _ssm_split(cfg: ModelConfig, xc: jnp.ndarray):
    """The convolved, activated [.., conv_dim] into x [.., H, P] and the
    groups' B and C [.., G, N]."""
    inner, gn = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
    lead = xc.shape[:-1]
    x = xc[..., :inner].reshape(*lead, cfg.ssm_heads, cfg.ssm_head_dim)
    b = xc[..., inner : inner + gn].reshape(*lead, cfg.ssm_groups, -1)
    c = xc[..., inner + gn :].reshape(*lead, cfg.ssm_groups, -1)
    return x, b, c


def _ssm_out(cfg: ModelConfig, p: dict, y, x, z, dtype):
    """From the scan's y [T, H, P]: the skip ``D·x``, the gated group
    norm against z [T, inner], and the out-projection -> [T, D]."""
    y = y + p["d_skip"].astype(jnp.float32)[:, None] * x
    g = _ssm.gated_group_norm(
        y.reshape(y.shape[0], -1), z, p["gate_norm"], cfg.ssm_groups,
        cfg.rms_norm_eps,
    )
    return _qmm(g.astype(dtype), p["w_out"])


def _ssm_mix_full(cfg: ModelConfig, p: dict, h: jnp.ndarray):
    """A state-space mixer over whole sequences h [b, s, D] from an
    empty state: :func:`forward`'s path (blocks of 64 tokens)."""
    b, s, d = h.shape
    z, xbc, dt = _ssm_project(cfg, p, h.reshape(b * s, d))
    k = cfg.ssm_conv
    xbc = xbc.reshape(b, s, -1)
    win = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
    xc = jax.nn.silu(_ssm.causal_conv(win, p["conv_w"], p["conv_b"]))
    x, bm, cm = _ssm_split(cfg, xc)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    s0 = jnp.zeros(
        (b, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32
    )
    y, _ = _ssm.ssd_scan(x, bm, cm, dt.reshape(b, s, -1), a, s0)
    out = _ssm_out(
        cfg, p, y.reshape(b * s, *y.shape[2:]),
        x.reshape(b * s, *x.shape[2:]), z, h.dtype,
    )
    return out.reshape(b, s, d)


def _ssm_scan_rows(cfg, terms, s_pool, layer, slot_in, slot_out):
    """The rows' blocks against the state pool: the kernel where the
    configuration runs kernels, else gather, :func:`ops.ssm.ssd_apply`,
    scatter — the same sums."""
    if cfg.use_pallas:
        from llm_consensus_tpu.ops.pallas.ssm_scan import ssm_scan

        return ssm_scan(terms, s_pool, layer, slot_in, slot_out)
    y, s1 = _ssm.ssd_apply(terms, s_pool[layer, slot_in])
    return y, s_pool.at[layer, slot_out].set(s1)


def _ssm_rows(cfg, p, z, xbc, dt, state, layer, slot_in, slot_out, n_real):
    """One group of rows of a state-space layer through the state pool.

    z / xbc / dt: [R, T, ..] — R rows of T tokens; row r starts from
    slot ``slot_in[r]`` (slot 0: an empty state) and leaves the state
    after its first ``n_real[r]`` tokens in ``slot_out[r]``: tokens past
    them get Δ = 0 and stay out of the convolution's rows, so padding
    changes nothing, and a row that carries no request (``n_real`` 0,
    ``slot_out`` 0) writes slot 0 what it read there. Returns (y
    [R, T, H, P] float32, x [R, T, H, P], (s_pool, conv_pool))."""
    s_pool, conv_pool = state
    r, t = dt.shape[:2]
    k = cfg.ssm_conv
    fresh = slot_in == 0
    conv0 = jnp.where(
        fresh[:, None, None], 0, conv_pool[layer, slot_in]
    )  # [R, K - 1, C]; slot 0's rows are whatever idle rows left there
    win = jnp.concatenate([conv0.astype(xbc.dtype), xbc], axis=1)
    xc = jax.nn.silu(_ssm.causal_conv(win, p["conv_w"], p["conv_b"]))
    conv_pool = conv_pool.at[layer, slot_out].set(
        _ssm.conv_rows_after(win, n_real, k).astype(conv_pool.dtype)
    )
    x, bm, cm = _ssm_split(cfg, xc)
    dt = jnp.where((jnp.arange(t)[None] < n_real[:, None])[..., None], dt, 0.0)
    pad = -t % 8  # the kernel's blocks are whole sublane tiles
    terms = _ssm.ssd_terms(
        *(
            jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            for v in (x, bm, cm, dt)
        ),
        -jnp.exp(p["a_log"].astype(jnp.float32)),
    )
    keep = (~fresh).astype(jnp.float32)[:, None, None, None]
    terms["ce"] = terms["ce"] * keep
    terms["f"] = terms["f"] * keep
    y, s_pool = _ssm_scan_rows(cfg, terms, s_pool, layer, slot_in, slot_out)
    return y.transpose(0, 2, 1, 3)[:, :t], x, (s_pool, conv_pool)


def _ssm_mix(cfg: ModelConfig, p: dict, h: jnp.ndarray, state, layer, rows):
    """A state-space mixer on a step program's token axis.

    h: [.., D] with ``rows.n_dec`` decode rows of one token first, then
    ``lanes`` chunk lanes of ``c`` tokens (either part may be empty);
    ``state`` = (S pool [Ls, slots, H, P, N] float32, conv pool [Ls,
    slots, K - 1, conv_dim]); ``layer`` this layer's index among the
    state layers; ``rows`` a :class:`SsmRows`. A decode row advances
    its slot's state by its token; a lane runs its chunk from
    ``lane_in`` (its own slot, a snapshot's, or 0 = empty), leaves the
    state after its last real token in ``lane_out`` and copies it to
    ``lane_snap`` (a registry snapshot; 0 = none). Returns (mixer
    output shaped as h, state)."""
    shape = h.shape
    hf = h.reshape(-1, shape[-1])
    z, xbc, dt = _ssm_project(cfg, p, hf)
    b, lanes = rows.n_dec, rows.lanes
    ys, xs = [], []
    if b:
        y, x, state = _ssm_rows(
            cfg, p, z[:b, None], xbc[:b, None], dt[:b, None], state, layer,
            rows.dec_slot, rows.dec_slot,
            (rows.dec_slot != 0).astype(jnp.int32),
        )
        ys.append(y[:, 0])
        xs.append(x[:, 0])
    if lanes:
        c = (hf.shape[0] - b) // lanes

        def grid(v):
            return v[b:].reshape(lanes, c, *v.shape[1:])

        y, x, state = _ssm_rows(
            cfg, p, grid(z), grid(xbc), grid(dt), state, layer,
            rows.lane_in, rows.lane_out, rows.lane_n,
        )
        ys.append(y.reshape(lanes * c, *y.shape[2:]))
        xs.append(x.reshape(lanes * c, *x.shape[2:]))
        # A snapshot is a copy of the lane's state as this program
        # leaves it; a lane that saves none copies slot 0 onto itself.
        src = jnp.where(rows.lane_snap != 0, rows.lane_out, 0)
        state = tuple(
            pool.at[layer, rows.lane_snap].set(pool[layer, src])
            for pool in state
        )
    out = _ssm_out(
        cfg, p, jnp.concatenate(ys), jnp.concatenate(xs), z, h.dtype
    )
    return out.reshape(shape), state


@jax.tree_util.register_dataclass
@dataclass
class SsmRows:
    """How a step program's token axis falls into rows of recurrent
    state (:func:`_ssm_mix`): slots are indices into the state pool, 0
    the empty slot that rows carrying no request read and write."""

    dec_slot: jnp.ndarray  # [n_dec] slot of each decode row (0: idle)
    lane_in: jnp.ndarray  # [lanes] slot a lane's chunk starts from
    lane_out: jnp.ndarray  # [lanes] the sequence's own slot (0: dead)
    lane_snap: jnp.ndarray  # [lanes] snapshot slot to copy into, or 0
    lane_n: jnp.ndarray  # [lanes] real tokens of the chunk

    @property
    def n_dec(self) -> int:
        return self.dec_slot.shape[0]

    @property
    def lanes(self) -> int:
        return self.lane_in.shape[0]


def _ssm_rows_of(cfg, cache, live_dec=None, chunk_state=None, lane_live=None):
    """The :class:`SsmRows` of a step program, or None for a model
    without recurrent layers. ``live_dec`` [B] bool marks the decode
    rows that advance (None: the program has no decode rows);
    ``chunk_state`` [L, 4] int32 is the host's (slot in, slot out,
    snapshot slot, real tokens) a lane, ``lane_live`` [L] bool."""
    if not cfg.is_recurrent:
        return None
    none = jnp.zeros((0,), jnp.int32)
    dec = none
    if live_dec is not None:
        dec = jnp.where(live_dec, cache.state.slot, 0)
    if chunk_state is None:
        if lane_live is not None:
            raise ValueError(
                f"{cfg.name}: a chunk program of a model with recurrent "
                "layers needs its lanes' state slots (chunk_state)"
            )
        return SsmRows(dec, none, none, none, none)
    cs = jnp.where(lane_live[:, None], chunk_state, 0)
    return SsmRows(dec, cs[:, 0], cs[:, 1], cs[:, 2], cs[:, 3])


def _plan_segments(kinds: tuple[str, ...]) -> list[tuple[tuple[str, ...], int]]:
    """A layer plan as (unit, repetitions) runs, greedily: at each
    position the unit whose immediate repetition covers most layers
    (``MEMEM*E`` x 2, then ``ME`` x 2, for the 18-layer cut), a single
    layer where nothing repeats. A run is one ``lax.scan`` over its
    unit."""
    out, i, n = [], 0, len(kinds)
    while i < n:
        best = (1, 1)
        for u in range(1, (n - i) // 2 + 1):
            r = 1
            while kinds[i + r * u : i + (r + 1) * u] == kinds[i : i + u]:
                r += 1
            if r > 1 and u * r > best[0] * best[1]:
                best = (u, r)
        u, r = best
        out.append((kinds[i : i + u], r))
        i += u * r
    return out


class _Mixer(NamedTuple):
    """One mixer of a run's unit (:func:`_plan_runs`): repetition ``j``
    reads layer ``first + j * step`` of ``stack`` (pre-norm ``norm``)
    and, where it keeps pages or state, layer ``pool_first`` + that of
    the pool."""

    kind: str  # "ssm" | "attn" | anything else: a feed-forward
    stack: dict
    norm: str
    first: int
    step: int
    pool_first: int


def _plan_runs(cfg: ModelConfig, params: dict) -> list[tuple[tuple, int]]:
    """The layer loop as runs of (unit of :class:`_Mixer`, repetitions).

    A model without a plan is the unit "attention, then MLP" — both off
    the same layer of a stack, the K/V pool indexed by ABSOLUTE layer —
    repeated over each of its stacks. A planned model's runs are
    :func:`_plan_segments`' with every mixer off its kind's stack,
    whose index is also its layer of the K/V or the state pool."""
    if not cfg.layer_plan:
        return [
            (
                (
                    _Mixer("attn", blocks, "attn_norm", 0, 1, first),
                    _Mixer("ffn", blocks, "mlp_norm", 0, 1, 0),
                ),
                len(jax.tree_util.tree_leaves(blocks)[0]),
            )
            for blocks, first in layer_stacks(params)
        ]
    runs, done = [], {}
    for unit, reps in _plan_segments(cfg.plan_kinds()):
        seen: dict[str, int] = {}
        mixers = []
        for kind in unit:
            mixers.append(_Mixer(
                kind, params[PLAN_STACKS[kind]], "norm",
                done.get(kind, 0) + seen.get(kind, 0), unit.count(kind), 0,
            ))
            seen[kind] = seen.get(kind, 0) + 1
        runs.append((tuple(mixers), reps))
        for kind, n in seen.items():
            done[kind] = done.get(kind, 0) + n * reps
    return runs


def _paged_attn(
    cfg, p, h, cos, sin, k_pools, v_pools, layer, pages, offs, attend
):
    """One layer's attention in a paged step program, from its normed
    input to the out-projection: (delta [b, s, D], k_pools, v_pools)."""
    if cfg.is_mla:
        attn, k_pools = _mla_attend_paged(
            cfg, p, h, cos, sin, k_pools, v_pools, layer, pages, offs,
            attend,
        )
    else:
        q, k, v = _project_qkv(cfg, p, h)
        if cfg.positions == "rope":
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        k_pools = k_pools.at[layer, pages, offs].set(
            k.astype(k_pools.dtype)
        )
        v_pools = v_pools.at[layer, pages, offs].set(
            v.astype(v_pools.dtype)
        )
        attn = attend(q, k_pools, v_pools, layer)
    return _qmm(attn.reshape(*h.shape[:-1], -1), p["wo"]), k_pools, v_pools


def _paged_layers(
    cfg: ModelConfig,
    params: dict,
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    cache,
    pages: jnp.ndarray,
    offs: jnp.ndarray,
    attend,
    mesh=None,
    mlp=None,
    active=None,
    ssm=None,
):
    """The layer loop of the four paged step programs.

    Scans over the layer INDEX with the weight stacks and both pools
    resident, as :func:`_run_layers` does for the engine's caches — not
    over the stacks themselves. The pools ride the carry and the new
    rows are scattered into them at ``[layer, page, offset]``, so
    nothing pool-shaped enters as ``xs`` or leaves as ``ys`` and the
    caller's donated cache is the buffer the result lives in; weights
    are :func:`_layer_view` views, so the int8 kernel reads its tiles
    from the resident stack. (Scanned slices would each be copied out
    for the Pallas calls, and stacked ``ys`` are a fresh pool a step.)

    x: [b, s, D] token grid; pages/offs: [b, s] destination page and
    in-page offset of each token's K/V; ``attend(q, k_pools, v_pools,
    layer)`` -> [b, s, H, Dh] is the program's own call of
    :func:`_attn_paged` on q [b, s, H, Dh]; ``mlp(p, h)`` replaces the
    plain :func:`_mlp` where the program splits it. Returns (x, k, v[,
    a recurrent model's S pool and conv pool][, stats]).

    ONE loop follows the model's plan (:func:`_plan_runs`): a run is a
    unit of mixers repeated, scanned over its repetitions (a single
    repetition too: the scan's carry is what keeps the pools in
    place), each mixer ``y + mixer(norm(y))`` off its stack. A model
    without a plan is the unit "attention, then MLP" repeated over
    each of its stacks — leading dense layers and expert layers are
    two runs over ONE pool, indexed by absolute layer. A planned model
    indexes the K/V pool by a layer's index among the attention layers
    and the state pool (``cache.state``, carried and written in place
    like the pages) among the state-space layers; ``ssm`` is the
    program's :class:`SsmRows`. An MLA model writes one latent a token
    into ``k`` (``v`` is its empty plane and rides along untouched) and
    attends in the absorbed form on every lane. A ``moe_dropless``
    model also returns int32 [experts reached, assignments] summed over
    its expert layers, for the batcher's counters; ``active`` ([b * s]
    bool or None) marks the rows that carry a request
    (:func:`_moe_dropless`).
    """
    stats0 = (jnp.zeros((2,), jnp.int32),) if cfg.moe_dropless else ()
    state0 = (
        (cache.state.s, cache.state.conv) if cfg.is_recurrent else ()
    )
    n_state = len(state0)

    def ffn(p, h2, stats):
        if stats and "router" in p:
            out, _, _, st = _moe_dropless(cfg, p, h2, active=active)
            return out, [stats[0] + st]
        return (_mlp(cfg, p, h2) if mlp is None else mlp(p, h2)), stats

    carry = (x, cache.k, cache.v, *state0, *stats0)
    for unit, reps in _plan_runs(cfg, params):

        def body(carry, j, unit=unit):
            y, k_pools, v_pools, *rest = carry
            state, stats = tuple(rest[:n_state]), rest[n_state:]
            for m in unit:
                # No multiply by 1 and no add of 0: the older models'
                # programs stay text-equal to the ones they had.
                i = j if m.step == 1 else j * m.step
                i = i + m.first if m.first else i
                p = _layer_view(m.stack, i)
                h = _rms(cfg, y, p[m.norm], mesh)
                if m.kind == "ssm":
                    delta, state = _ssm_mix(cfg, p, h, state, i, ssm)
                elif m.kind == "attn":
                    layer = i + m.pool_first if m.pool_first else i
                    delta, k_pools, v_pools = _paged_attn(
                        cfg, p, h, cos, sin, k_pools, v_pools, layer, pages,
                        offs, attend,
                    )
                else:
                    delta, stats = ffn(p, h, stats)
                y = y + delta.astype(y.dtype)
            return (y, k_pools, v_pools, *state, *stats), None

        carry, _ = jax.lax.scan(body, carry, jnp.arange(reps))
    return carry


def _run_paged(cfg, params, x, cos, sin, cache, pages, offs, attend, ssm=None,
               **kw):
    """:func:`_paged_layers` for a step program: (x, the cache with its
    pools — and a recurrent model's state pools — replaced, *stats)."""
    if ssm is not None:
        kw["ssm"] = ssm
    x, new_k, new_v, *rest = _paged_layers(
        cfg, params, x, cos, sin, cache, pages, offs, attend, **kw
    )
    new = {"k": new_k, "v": new_v}
    if cfg.is_recurrent:
        new["state"] = replace(cache.state, s=rest[0], conv=rest[1])
        rest = rest[2:]
    return (x, replace(cache, **new), *rest)


def _decoding_rows(cache, write_mask=None):
    """[rows] bool: decode rows that carry a request and decode a token
    this step. An idle slot's table row is all NULL pages, and a
    mid-prefill one stays so until its last chunk lands (its pages go
    through the host's explicit table): such a row, like one frozen by
    ``write_mask``, writes into the NULL page, keeps its ``length`` (0
    since its release) and gives the attention nothing to read — a
    length that grew with every step would have it fold the NULL page
    up to a whole table's width, every layer of every step."""
    from llm_consensus_tpu.models.paged_cache import NULL_PAGE

    live = cache.page_table[:, 0] != NULL_PAGE
    return live if write_mask is None else live & write_mask


def _live_rows(cfg: ModelConfig, cache, write_mask=None, repeat=1, lanes=None):
    """[rows * repeat (+ L * C)] bool: :func:`_decoding_rows`, each
    ``repeat`` times, then the chunk tokens ``lanes`` ([L * C] bool: a
    live lane's are all live). None unless the model has a dropless
    expert layer, the one thing that asks (:func:`_moe_dropless`)."""
    if not cfg.moe_dropless:
        return None
    live = _decoding_rows(cache, write_mask)
    if repeat > 1:
        live = jnp.repeat(live, repeat)
    if lanes is not None:
        live = jnp.concatenate([live, lanes])
    return live


def _chunk_lanes(cache, tokens, table, start):
    """The chunk lanes of a step program, normalised: ``tokens`` [L, C]
    with ``table`` [L, P] and ``start`` [L] (one lane may come as [P]
    and a scalar). Returns (table, start, pos [L, C] absolute
    positions, pages [L, C] and offs [L, C] of each token's K/V,
    live [L] bool, attn_start [L]).

    A lane with nothing to carry is a dead row, as an idle slot is: its
    table is all NULL pages, so its tokens write into the NULL page, and
    ``attn_start`` is -C for it, which is length 0 to the attention — it
    reads nothing (``ragged_paged_attention``)."""
    from llm_consensus_tpu.models.paged_cache import NULL_PAGE

    lanes, c = tokens.shape
    table = jnp.atleast_2d(table)
    start = jnp.asarray(start, jnp.int32).reshape(lanes)
    pos = start[:, None] + jnp.arange(c)[None]
    pages = jnp.take_along_axis(table, pos // cache.page_size, axis=1)
    live = table[:, 0] != NULL_PAGE
    return (
        table, start, pos, pages, pos % cache.page_size, live,
        jnp.where(live, start, -c),
    )


def decode_step_paged(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,
    cache,
    groups=None,
    write_mask=None,
    mesh=None,
) -> tuple[jnp.ndarray, object]:
    """One decode step for every cache sequence, paged layout.

    tokens: [max_seqs, 1]. Each row b writes its new K/V at
    ``page_table[b, length[b] // page]`` offset ``length[b] % page`` and
    attends over its gathered pages. Inactive rows (empty tables) write
    into the reserved NULL page, keep their length and attend over
    nothing (:func:`_decoding_rows`) — their outputs are garbage the
    serving layer discards. Returns (logits [max_seqs, V] fp32, new cache) —
    and, for a ``moe_dropless`` config (all four step programs alike),
    a third value: int32 [experts reached, assignments] summed over the
    expert layers (:func:`_paged_layers`).

    ``groups`` (a :class:`~llm_consensus_tpu.models.paged_cache.
    DecodeGroupArrays` or None): sequences sharing a prefix page run
    (the PrefixRegistry's CoW mappings) attend that run through the
    ragged kernel's group phase — one HBM read of the shared pages per
    GROUP per step instead of one per member, with per-row suffix pages
    read as before and the two partial softmaxes merged exactly.
    Grouped and ungrouped rows coexist in the one program (ungrouped
    rows carry group_id -1), and sliding-window configs group too (the
    window is per-row masking in the same kernel — the old fallback is
    gone). The jnp gather path ignores ``groups`` (outputs are
    identical either way — the callers' parity contract).

    ``write_mask`` ([max_seqs] bool or None): device-side early-exit
    masking for multi-round decode (PR 12). A False row is FROZEN: its
    K/V write is redirected into the reserved NULL page (the same sink
    inactive rows already decode into), its ``length`` does not
    advance, and its attention reads stay bounded by the unchanged
    length — so a row that hit a stop inside a multi-round window
    leaves zero trace in its real pages while its batch neighbors keep
    decoding. Frozen rows still flow through the matmuls (SIMD rows
    are not skippable); their logits are garbage the caller discards.
    None (default) = every row live, exactly the pre-PR-12 step.

    ``mesh`` (trace-time constant, PR 13): run the attention read
    through the mesh-partitioned kernel seam (see :func:`_attn_paged`).
    Everything else in the step — the QKV/WO/MLP GEMMs, the K/V pool
    scatter — is plain jnp that GSPMD shards from the operands'
    NamedShardings; only the pallas_call needs the explicit seam.
    """
    from llm_consensus_tpu.models.paged_cache import NULL_PAGE

    b = tokens.shape[0]
    pos = cache.length  # [B] current write position
    x = params["embed"][tokens]  # [B, 1, D]
    cos, sin = rope_cos_sin(
        pos[:, None], cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling
    )
    pg = cache.page_size
    pages_now = cache.page_table[jnp.arange(b), pos // pg]  # [B]
    offset = pos % pg
    if write_mask is not None:
        pages_now = jnp.where(write_mask, pages_now, NULL_PAGE)
    adv = _decoding_rows(cache, write_mask).astype(pos.dtype)
    tables = cache.page_table  # [B, P]

    def attend(q, k_pools, v_pools, layer):
        return _attn_paged(
            cfg, q[:, 0], None, k_pools, v_pools, layer, tables, pos + adv,
            groups=groups, mesh=mesh,
        )[:, None]  # [B, H, D] -> [B, 1, H, D] (seq axis restored)

    x, new_cache, *stats = _run_paged(
        cfg, params, x, cos, sin, cache, pages_now[:, None],
        offset[:, None], attend, mesh=mesh,
        active=_live_rows(cfg, cache, write_mask),
        ssm=_ssm_rows_of(cfg, cache, _decoding_rows(cache, write_mask)),
    )
    logits = _unembed(cfg, params, x[:, 0], mesh)
    return (logits, replace(new_cache, length=pos + adv), *stats)


def verify_step_paged(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,
    cache,
    groups=None,
    mesh=None,
) -> tuple[jnp.ndarray, object]:
    """Speculative VERIFY step: NQ tokens per cache sequence, one
    program (PR 9 — :func:`decode_step_paged` widened to k+1-token
    ragged rows).

    tokens: [max_seqs, NQ] — row b's previous committed token followed
    by NQ-1 draft proposals, at absolute positions ``length[b] + i``.
    Embedding, RoPE, the QKV/WO/MLP matmuls, and the K/V pool scatter
    all run over the [B, NQ] token grid (one weight read serves NQ
    tokens per row — the point of speculation), and attention is the
    ragged kernel's verify lane: queries at ``valid_len - NQ + i`` with
    the chunk lane's ragged-causal rule, so position j conditions on
    the row's committed tokens plus drafts[:j]. K/V for ALL NQ
    positions are written through the row's table (decode rows write
    only private pages — shared prefix pages cover prompts only);
    positions past the eventually-accepted prefix hold garbage the
    caller truncates by REWINDING ``length``, never by copying pages —
    slots past ``length`` are invisible to every later read and get
    overwritten by later writes, exactly like a mid-chunk retirement's
    overshoot tokens.

    Returns (logits [max_seqs, NQ, V] fp32 — one distribution per
    verify position, the accept rule's input — and the cache with
    ``length`` UNCHANGED: the caller advances it by each row's emitted
    count after the accept decision). ``groups`` as in
    :func:`decode_step_paged` (every verify query of a member stacks
    against one read of the shared run).
    """
    if cfg.is_recurrent:
        raise NotImplementedError(
            f"{cfg.name}: the verify lane writes positions it may rewind, "
            "and a recurrent state cannot be rewound"
        )
    b, nq = tokens.shape
    pos0 = cache.length  # [B] first write position per row
    pos = pos0[:, None] + jnp.arange(nq)[None]  # [B, NQ]
    x = params["embed"][tokens]  # [B, NQ, D]
    cos, sin = rope_cos_sin(
        pos, cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling
    )
    pg = cache.page_size
    pages = jnp.take_along_axis(
        cache.page_table, pos // pg, axis=1
    )  # [B, NQ] destination page per token
    offs = pos % pg
    tables = cache.page_table

    # An idle row verifies nothing and reads nothing.
    valid = jnp.where(_decoding_rows(cache), pos0 + nq, 0)

    def attend(q, k_pools, v_pools, layer):
        return _attn_paged(
            cfg, q, None, k_pools, v_pools, layer, tables, valid,
            groups=groups, mesh=mesh,
        )  # [B, NQ, H, D]

    x, new_cache, *stats = _run_paged(
        cfg, params, x, cos, sin, cache, pages, offs, attend, mesh=mesh,
        active=_live_rows(cfg, cache, repeat=nq),
    )
    logits = _unembed(cfg, params, x, mesh)  # [B, NQ, V]
    return (logits, new_cache, *stats)


def prefill_chunk_paged(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,
    table: jnp.ndarray,
    start: jnp.ndarray,
    cache,
    mesh=None,
    chunk_state=None,
) -> tuple[jnp.ndarray, object]:
    """One prompt chunk for each of L sequences (lanes), scattered into
    paged K/V.

    ``chunk_state`` ([L, 4] int32; a model with recurrent layers): a
    lane's (state slot it starts from — its own, a registry snapshot's,
    or 0 for an empty state —, its own slot, the snapshot slot to copy
    its end state into or 0, its chunk's REAL tokens); see
    :func:`_ssm_mix`.

    tokens: [L, C] — lane l's chunk token ids at absolute positions
    ``start[l] + i``; table: [L, pages_per_seq] int32 page ids (position
    p lives in ``table[l, p // page_size]`` at offset ``p % page_size``);
    start: [L] int32 (one lane may come as a [pages_per_seq] table and
    a scalar). Writes each chunk token's K/V through its lane's table
    and attends over that table's content so far plus the chunk itself
    — the same ragged-causal rule as :func:`decode_chunk`, so a
    sequence of chunk calls writes the identical cache a dense
    :func:`prefill` + scatter would. Lanes are different sequences:
    they write disjoint pages and a row of a matmul does not depend on
    its neighbours, so L lanes in one call leave what L calls would.
    A lane whose table is all NULL pages is dead (:func:`_chunk_lanes`).

    The table rides as an ARGUMENT, not through ``cache.page_table``:
    a mid-prefill sequence must stay invisible to the concurrently
    running decode program (its device table row stays NULL until the
    last chunk lands — see serving/continuous). This is also what lets
    chunk positions start past zero: a shared page-aligned prefix (and
    an optionally copied boundary page) already populates the table's
    head, and this program only ever writes positions >= ``start``, so
    refcount-shared pages are read, never written.

    Returns ([L, C, D] hidden states, cache). ``cache.page_table`` and
    ``cache.length`` are untouched. The serving layer gathers the
    last-valid position's hidden state of each lane from the FINAL
    chunk and unembeds those rows (see :func:`unembed_rows`) — never a
    [C, V] logits buffer per chunk.
    """
    table, start, pos, pages, offs, live, attn_start = _chunk_lanes(
        cache, tokens, table, start
    )
    x = params["embed"][tokens]  # [L, C, D]
    cos, sin = rope_cos_sin(
        pos, cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling
    )
    nb = 1 if mesh is None else int(mesh.shape.get("data", 1))

    def attend(q, k_pools, v_pools, layer):
        # Chunk-only ragged call through the SAME kernel seam as the
        # fused step (dead decode rows: NULL table, valid 0) — a
        # standalone chunk and a fused chunk must write bit-identical
        # cache bytes, which means one attention arithmetic for both
        # (on use_pallas configs the kernel and the XLA reference only
        # agree to tolerance, so mixing them would break the
        # ragged_attention on/off byte-parity contract mid-prefill).
        # On a mesh the dummy decode batch is sized to the data axis:
        # a 1-row batch cannot shard over dp > 1, which would silently
        # route the STANDALONE chunk to the reference while fused
        # chunks run the sharded kernel — the same mixed-arithmetic
        # hazard, reintroduced by topology instead of by feature flag.
        return _attn_paged(
            cfg,
            jnp.zeros((nb, cfg.n_heads, q.shape[-1]), q.dtype),
            q,
            k_pools,
            v_pools,
            layer,
            jnp.zeros((nb, table.shape[1]), jnp.int32),
            jnp.zeros((nb,), jnp.int32),
            chunk_table=table,
            chunk_start=attn_start,
            mesh=mesh,
        )[1]  # out_chunk [L, C, H, D]

    x, new_cache, *stats = _run_paged(
        cfg, params, x, cos, sin, cache, pages, offs, attend, mesh=mesh,
        active=(
            jnp.repeat(live, tokens.shape[1]) if cfg.moe_dropless else None
        ),
        ssm=_ssm_rows_of(
            cfg, cache, chunk_state=chunk_state, lane_live=live
        ),
    )
    return (x, new_cache, *stats)


def fused_step_paged(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,
    cache,
    chunk_tokens: jnp.ndarray,
    chunk_table: jnp.ndarray,
    chunk_start: jnp.ndarray,
    groups=None,
    cfg_chunk: ModelConfig | None = None,
    mesh=None,
    chunk_state=None,
) -> tuple[jnp.ndarray, jnp.ndarray, object]:
    """One decode step for every cache sequence PLUS the next prefill
    chunk of up to L other sequences — a single device program (the
    fused scheduler step).

    tokens: [B, 1] decode inputs; chunk_tokens: [L, C] — lane l is one
    sequence's prompt chunk at absolute positions ``chunk_start[l] + i``,
    written through the explicit host-side ``chunk_table[l]`` ([L, P];
    one lane may come as [P] and a scalar start) exactly as
    :func:`prefill_chunk_paged` (a mid-prefill row stays invisible to
    the decode rows — its device table row is still NULL). The decode
    rows and the lanes share ONE token axis: embedding, RoPE, the
    QKV/WO/MLP matmuls, the K/V pool scatter and a dropless expert
    layer's router and grouped matmul all run over the [B + L * C]
    concatenation (bigger GEMMs, one scatter: a weight is read once for
    all of them), and attention is the ragged kernel with each lane
    riding as one more row — chunked prefill stops being a separate
    device program serializing against decode. A lane whose table is all
    NULL pages is dead (:func:`_chunk_lanes`).

    The workloads are independent by construction: decode rows write
    only their own private pages, a lane writes only positions
    >= ``chunk_start[l]`` of its own table (shared prefix pages are
    read, never written; lanes are different sequences), so each side's
    outputs equal the split programs'.
    ``cfg_chunk`` (default ``cfg``): the MoE-pinned config the
    standalone chunk program would have used — when it differs (MoE
    configs), the MLP runs split per side so each side's dispatch path
    matches its parity baseline; dense models share one MLP call.

    Returns (decode logits [B, V] fp32, chunk hidden [L, C, D], cache).
    ``cache.length`` advances for the decode rows only, and of those
    for the ones that carry a request (:func:`_decoding_rows`).
    ``chunk_state``: as :func:`prefill_chunk_paged`.
    """
    if cfg_chunk is None:
        cfg_chunk = cfg
    b = tokens.shape[0]
    lanes, c = chunk_tokens.shape
    pos = cache.length  # [B] decode write positions
    chunk_table, chunk_start, chunk_pos, chunk_pages, chunk_offs, live, (
        attn_start
    ) = _chunk_lanes(cache, chunk_tokens, chunk_table, chunk_start)
    all_pos = jnp.concatenate([pos, chunk_pos.reshape(-1)])
    x = params["embed"][
        jnp.concatenate([tokens[:, 0], chunk_tokens.reshape(-1)])
    ][None]  # [1, B + L*C, D]
    cos, sin = rope_cos_sin(
        all_pos[None], cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling
    )
    pg = cache.page_size
    pages_dec = cache.page_table[jnp.arange(b), pos // pg]  # [B]
    pages_all = jnp.concatenate([pages_dec, chunk_pages.reshape(-1)])
    offs_all = jnp.concatenate([pos % pg, chunk_offs.reshape(-1)])
    tables = cache.page_table
    adv = _decoding_rows(cache).astype(pos.dtype)
    mlp_split = cfg.is_moe and cfg_chunk is not cfg

    def attend(q, k_pools, v_pools, layer):
        attn_dec, attn_ch = _attn_paged(
            cfg, q[0, :b], q[0, b:].reshape(lanes, c, *q.shape[2:]),
            k_pools, v_pools, layer, tables, pos + adv,
            chunk_table=chunk_table, chunk_start=attn_start,
            groups=groups, mesh=mesh,
        )
        return jnp.concatenate(
            [attn_dec, attn_ch.reshape(lanes * c, *attn_ch.shape[2:])]
        )[None]  # [1, B + L*C, H, Dh]

    def mlp_by_side(p, h2):
        return jnp.concatenate(
            [_mlp(cfg, p, h2[:, :b]), _mlp(cfg_chunk, p, h2[:, b:])], axis=1
        )

    # One scatter over DISJOINT real pages: decode rows write their
    # private pages, each lane positions >= its chunk_start of its own
    # table.
    x, new_cache, *stats = _run_paged(
        cfg, params, x, cos, sin, cache, pages_all[None], offs_all[None],
        attend, mesh=mesh, mlp=mlp_by_side if mlp_split else None,
        active=_live_rows(cfg, cache, lanes=jnp.repeat(live, c)),
        ssm=_ssm_rows_of(
            cfg, cache, _decoding_rows(cache), chunk_state, live
        ),
    )
    logits = _unembed(cfg, params, x[0, :b], mesh)
    hidden_chunk = x[0, b:].reshape(lanes, c, -1)  # [L, C, D]
    return (
        logits, hidden_chunk, replace(new_cache, length=pos + adv), *stats
    )


def unembed_one(
    cfg: ModelConfig, params: dict, h: jnp.ndarray, mesh=None
) -> jnp.ndarray:
    """Logits [V] fp32 for ONE hidden state [D] — the final-chunk
    unembed of the chunked-prefill path (a D x V matvec, not C x V)."""
    return unembed_rows(cfg, params, h[None], mesh)[0]


def unembed_rows(
    cfg: ModelConfig, params: dict, h: jnp.ndarray, mesh=None
) -> jnp.ndarray:
    """Logits [N, V] fp32 for N hidden states [N, D]: one a chunk lane
    of the fused step."""
    return _unembed(cfg, params, h, mesh)


def decode_chunk(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,
    cache: KVCache,
) -> tuple[jnp.ndarray, KVCache]:
    """Score K tokens per row against the cache in ONE forward.

    tokens: [B, K]. Token (b, i) sits at position ``cache.length[b] + i``
    and attends everything before it plus the chunk prefix — the
    speculative-decoding verification step (a whole draft's target
    logits from one pass instead of K sequential decode_steps).

    Returns (logits [B, K, V] float32, cache with the K tokens' k/v
    written). ``cache.length`` is NOT advanced: the caller decides how
    many chunk tokens were actually consumed (accepted) and sets the
    length via ``cache.with_length`` — rejected tokens' k/v stay as
    masked-out garbage past the fill, exactly like prefill padding.
    Sliding-window configs (Mistral) mask per the same rule as
    :func:`llm_consensus_tpu.ops.attention.decode_attention`.
    """
    x, cache = _chunk_hidden(cfg, params, tokens, cache)
    logits = _unembed(cfg, params, x)  # [B, K, V]
    return logits, cache


def _chunk_hidden(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,
    cache: KVCache,
) -> tuple[jnp.ndarray, KVCache]:
    """The chunk forward without the unembed: ([B, K, D] hidden, cache).

    Callers that need only a few positions' logits (chunked prefill
    keeps one per row) gather from the hidden states and unembed those
    — skipping the B*K*V logits matmul per chunk."""
    kq = tokens.shape[1]
    x = params["embed"][tokens]  # [B, K, D]
    positions = cache.length[:, None] + jnp.arange(kq)[None, :]
    cos, sin = rope_cos_sin(
        positions, cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling
    )
    x, cache = _run_layers(
        cfg, params, x, cos, sin, cache, "chunk", cache.length, None
    )
    return x, cache


def prefill_chunked(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,
    lengths: jnp.ndarray,
    cache: KVCache,
    chunk: int = 512,
) -> tuple[jnp.ndarray, KVCache]:
    """Prefill in fixed-size chunks — bounded activation memory.

    One-shot :func:`prefill` materializes activations for the whole
    [B, S] prompt at once; for long contexts this chunks the prompt into
    ``ceil(S / chunk)`` :func:`decode_chunk` passes (each chunk attends
    the cache so far plus itself — same ragged-causal rule), keeping
    peak activation memory at O(B * chunk) while writing the identical
    cache. Returns (last-valid-token logits [B, V] fp32, cache with
    length = ``lengths``) — same contract as :func:`prefill`, and
    exactness-tested against it.
    """
    b, s = tokens.shape
    # Pin each chunk's MoE dispatch path to the one a ONE-SHOT prefill
    # of this prompt would trace (the b*s total decides), not the
    # chunk's own token count — otherwise a prompt above the
    # dense-fallback threshold whose chunks sit below it would mix
    # paths across the two prefill entry points. Dense side covers
    # b*chunk: padding can widen a chunk past s. Residual capacity-side
    # caveat: ModelConfig.moe_pin_for.
    cfg = cfg.moe_pin_for(b * s, b * chunk)
    if s % chunk:
        pad = chunk - s % chunk
        tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
        s += pad
    cache = cache.with_length(jnp.zeros((b,), jnp.int32))
    last = jnp.clip(lengths - 1, 0, s - 1)
    batch = jnp.arange(b)
    x_last = jnp.zeros((b, cfg.d_model), jnp.float32)
    for c0 in range(0, s, chunk):
        hidden, cache = _chunk_hidden(
            cfg, params, tokens[:, c0 : c0 + chunk], cache
        )
        cache = cache.with_length(cache.length + chunk)
        # Keep only each row's last-valid hidden state; the unembed (a
        # B*V matmul, not B*chunk*V) happens ONCE after the loop.
        in_chunk = (last >= c0) & (last < c0 + chunk)
        got = hidden[batch, jnp.clip(last - c0, 0, chunk - 1)]
        x_last = jnp.where(in_chunk[:, None], got.astype(jnp.float32), x_last)
    cache = cache.with_length(lengths)
    logits = _unembed(cfg, params, x_last.astype(hidden.dtype))
    return logits, cache


def decode_step(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,
    cache: KVCache,
    uniform_write: bool = False,
    shared_prefix_len=None,
) -> tuple[jnp.ndarray, KVCache]:
    """One decode step: tokens [B, 1] -> (logits [B, V] float32, new cache).

    The new token's k/v is written at slot ``cache.length`` and the fill
    length advances by one. ``uniform_write`` (static): all rows share
    one fill length (shared-prefill fan-out) — the cache write compiles
    to a slice update instead of a scatter.

    ``shared_prefix_len`` (traced scalar or None): rows hold IDENTICAL
    K/V in cache slots [0, shared_prefix_len) — the shared-prefill
    fan-out invariant — so decode attention reads that region once for
    the whole batch through the two-phase shared-prefix kernels (one
    HBM read per step instead of one per row; exact LSE merge with each
    row's suffix). Only the Pallas non-windowed non-stacked paths
    engage; every other path ignores it (same outputs either way).
    """
    x = params["embed"][tokens]  # [B, 1, D]
    positions = cache.length[:, None]  # [B, 1]
    cos, sin = rope_cos_sin(
        positions, cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling
    )
    x, cache = _run_layers(
        cfg,
        params,
        x,
        cos,
        sin,
        cache,
        "decode",
        cache.length,
        None,
        uniform_write=uniform_write,
        shared_prefix_len=shared_prefix_len,
    )
    logits = _unembed(cfg, params, x[:, 0])
    return logits, cache.advanced(1)
