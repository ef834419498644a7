"""Weight-only int8 quantization (ops/quant.py).

The reference has no local compute to quantize (its model is a remote
API, ``src/main.rs:82-86``); quantization is part of this framework's
own decode-throughput work (BASELINE.json north-star floor).
"""

import jax
import jax.numpy as jnp
import pytest

from llm_consensus_tpu.engine.engine import EngineConfig, InferenceEngine
from llm_consensus_tpu.engine.generate import generate
from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.transformer import forward, init_params
from llm_consensus_tpu.ops.quant import (
    QuantizedTensor,
    dequantize,
    quantize_params,
    quantize_tensor,
    quantized_bytes,
)


def test_quantize_roundtrip_error_bound():
    """Per-channel symmetric int8: reconstruction error <= scale/2 + eps."""
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    qt = quantize_tensor(w, axis=0)
    assert qt.q.dtype == jnp.int8
    assert qt.scale.shape == (1, 128)
    err = jnp.abs(dequantize(qt, jnp.float32) - w)
    assert float(jnp.max(err - qt.scale / 2)) < 1e-6


@pytest.mark.parametrize("preset", ["test-tiny", "test-tiny-moe"])
def test_quantized_forward_close(preset):
    """Quantized logits stay close to the full-precision logits."""
    cfg = get_config(preset)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qp = quantize_params(params)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size
    )
    ref = forward(cfg, params, tokens)
    out = forward(cfg, qp, tokens)
    rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.05


def test_quantized_params_shrink_and_skip_small_leaves():
    cfg = get_config("test-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qp = quantize_params(params)
    assert quantized_bytes(qp) < 0.5 * quantized_bytes(params)
    # Matmul weights quantized; norms/embed untouched; idempotent.
    assert isinstance(qp["blocks"]["wq"], QuantizedTensor)
    assert isinstance(qp["blocks"]["w_down"], QuantizedTensor)
    assert not isinstance(qp["blocks"]["attn_norm"], QuantizedTensor)
    assert not isinstance(qp["embed"], QuantizedTensor)
    qp2 = quantize_params(qp)
    assert qp2["blocks"]["wq"] is qp["blocks"]["wq"]


def test_quantized_generate_runs():
    """The jitted generate loop accepts a quantized param tree."""
    cfg = get_config("test-tiny")
    params = quantize_params(
        init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    )
    tokens = jnp.ones((2, 8), jnp.int32)
    out = generate(
        cfg,
        params,
        tokens,
        jnp.full((2,), 8, jnp.int32),
        jax.random.PRNGKey(0),
        jnp.zeros((2,), jnp.float32),
        max_new_tokens=4,
    )
    assert out.tokens.shape == (2, 4)


def test_quantized_params_shard_tensor_parallel(cpu_devices):
    """int8 scales (size-1 contraction dim) must replicate, not inherit
    the row-parallel spec — TP sharding of quantized params must work."""
    from llm_consensus_tpu.parallel.mesh import MeshConfig, make_mesh
    from llm_consensus_tpu.parallel.partitioning import shard_params

    cfg = get_config("test-tiny")
    qp = quantize_params(
        init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    )
    mesh = make_mesh(MeshConfig(data=2, model=4), cpu_devices)
    sharded = shard_params(qp, mesh)
    wo = sharded["blocks"]["wo"]
    assert wo.q.sharding.spec == ("model",) or wo.q.sharding.spec[1] == "model"
    assert "model" not in tuple(wo.scale.sharding.spec)
    tokens = jnp.ones((2, 8), jnp.int32)
    out = forward(cfg, sharded, tokens)
    assert out.shape == (2, 8, cfg.vocab_size)


def test_quant_matmul_kernel_matches_fallback():
    """ops.quant.matmul: Pallas int8 kernel (interpret) == XLA dequant."""
    from llm_consensus_tpu.ops import quant as quant_mod
    from llm_consensus_tpu.ops.pallas.quant_matmul import (
        quant_matmul_supported,
    )

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 1, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 384), jnp.float32)
    qt = quantize_tensor(w, axis=0)
    assert quant_matmul_supported(2, 256, 384)
    ref = quant_mod.matmul(x, qt)  # CPU default: XLA fallback
    quant_mod._FORCE_KERNEL = True
    try:
        out = quant_mod.matmul(x, qt)
    finally:
        quant_mod._FORCE_KERNEL = None
    assert out.shape == ref.shape == (2, 1, 384)
    rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.02


def test_quantized_decode_with_kernel_matches_xla_path():
    """End-to-end decode step with the kernel forced on (interpret)."""
    from llm_consensus_tpu.models.cache import KVCache
    from llm_consensus_tpu.models.transformer import decode_step, prefill
    from llm_consensus_tpu.ops import quant as quant_mod

    cfg = get_config("test-tiny")  # d_model=64 < 128: unsupported shapes
    cfg = cfg.with_(d_model=128, n_heads=4, n_kv_heads=2, d_ff=256)
    params = quantize_params(
        init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    )
    tokens = jnp.ones((2, 8), jnp.int32)
    lengths = jnp.full((2,), 8, jnp.int32)

    def run():
        cache = KVCache.create(cfg, 2, 16, dtype=jnp.float32)
        logits, cache = prefill(cfg, params, tokens, lengths, cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        logits2, _ = decode_step(cfg, params, tok[:, None], cache)
        return logits2

    ref = run()
    quant_mod._FORCE_KERNEL = True
    try:
        out = run()
    finally:
        quant_mod._FORCE_KERNEL = None
    rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.05


def test_engine_quant_config():
    """EngineConfig(quant='int8') quantizes at init; bad mode rejected."""
    cfg = get_config("test-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = InferenceEngine(
        cfg, params, engine_config=EngineConfig(quant="int8")
    )
    assert isinstance(eng.params["blocks"]["wq"], QuantizedTensor)
    results = eng.generate_texts(["hello"], max_new_tokens=4)
    assert len(results) == 1 and isinstance(results[0].text, str)
    with pytest.raises(ValueError):
        InferenceEngine(cfg, params, engine_config=EngineConfig(quant="fp4"))


# ---------------------------------------------------------------------------
# int4 (packed nibbles)
# ---------------------------------------------------------------------------


def test_int4_pack_unpack_exact():
    """Values on the int4 grid survive pack -> unpack exactly."""
    from llm_consensus_tpu.ops.quant import quantize_tensor4, unpack4

    w = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    qt = quantize_tensor4(w, axis=0)
    assert qt.q.shape == (32, 128)  # contraction dim halved
    assert qt.shape == (64, 128)  # logical shape
    grid = unpack4(qt.q, jnp.float32)
    assert float(jnp.min(grid)) >= -8 and float(jnp.max(grid)) <= 7
    # Re-quantizing the dequantized weight reproduces the same nibbles.
    from llm_consensus_tpu.ops.quant import dequantize4

    qt2 = quantize_tensor4(dequantize4(qt, jnp.float32), axis=0)
    assert jnp.array_equal(qt.q, qt2.q)


def test_int4_roundtrip_error_bound():
    from llm_consensus_tpu.ops.quant import dequantize4, quantize_tensor4

    w = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    qt = quantize_tensor4(w, axis=0)
    err = jnp.abs(dequantize4(qt, jnp.float32) - w)
    assert float(jnp.max(err - qt.scale / 2)) < 1e-6


def test_int4_rejects_bad_axis_or_odd_dim():
    from llm_consensus_tpu.ops.quant import quantize_tensor4

    w = jnp.zeros((64, 128))
    with pytest.raises(ValueError, match="axis -2"):
        quantize_tensor4(w, axis=1)
    with pytest.raises(ValueError, match="even"):
        quantize_tensor4(jnp.zeros((63, 128)), axis=0)


def test_quant4_matmul_kernel_matches_dequant():
    """Fused int4 kernel (interpret) == unpack + XLA dot."""
    from llm_consensus_tpu.ops.pallas.quant_matmul import (
        quant4_matmul_2d,
        quant4_matmul_supported,
    )
    from llm_consensus_tpu.ops.quant import dequantize4, quantize_tensor4

    k, n, m = 256, 384, 8
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.bfloat16)
    qt = quantize_tensor4(w, axis=0)
    assert quant4_matmul_supported(m, k, n)
    got = quant4_matmul_2d(x, qt.q, qt.scale, interpret=True)
    want = (x.astype(jnp.float32) @ dequantize4(qt, jnp.float32)).astype(
        jnp.bfloat16
    )
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))) < 0.5


@pytest.mark.parametrize("preset", ["test-tiny", "test-tiny-moe"])
def test_int4_forward_close(preset):
    """int4 logits stay reasonably close to full precision (coarser grid
    than int8, so a looser bound)."""
    from llm_consensus_tpu.ops.quant import Quantized4Tensor

    cfg = get_config(preset)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qp = quantize_params(params, bits=4)
    assert isinstance(qp["blocks"]["wq"], Quantized4Tensor)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size
    )
    ref = forward(cfg, params, tokens)
    out = forward(cfg, qp, tokens)
    rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.35


def test_int4_bytes_half_of_int8():
    cfg = get_config("test-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    q8 = quantize_params(params, bits=8)
    q4 = quantize_params(params, bits=4)

    def block_bytes(p):
        return sum(
            leaf.size * leaf.dtype.itemsize
            for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
            for leaf in [getattr(p["blocks"][name], "q")]
        )

    assert block_bytes(q4) == block_bytes(q8) // 2


def test_int4_engine_generates():
    """End-to-end: the engine decodes with int4 weights."""
    cfg = get_config("test-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = InferenceEngine(
        cfg,
        params,
        engine_config=EngineConfig(
            max_new_tokens=4, seq_buckets=(16,), batch_buckets=(1, 2),
            quant="int4",
        ),
    )
    out = eng.generate_texts(["hello", "world"])
    assert len(out) == 2
    assert all(r.num_tokens >= 1 for r in out)


def test_int4_params_shard_on_mesh():
    """Packed int4 leaves place under the same partitioning rules."""
    from jax.sharding import PartitionSpec as P

    from llm_consensus_tpu.parallel.mesh import MeshConfig, make_mesh
    from llm_consensus_tpu.parallel.partitioning import shard_params

    cfg = get_config("test-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    qp = quantize_params(params, bits=4)
    mesh = make_mesh(MeshConfig(data=2, model=2, expert=2))
    sharded = shard_params(qp, mesh)
    assert sharded["blocks"]["wq"].q.sharding.spec == P(None, None, "model")


@pytest.mark.parametrize("n_layers", [0, 3])
def test_quant_matmul_kernels_match_dequant_dot(n_layers):
    """The int8 matmul kernel — plain and stacked (layer index by
    scalar prefetch, read on the last layer) — against dequantize + dot:
    the comparison chip_smoke.py runs compiled at K = 4096 / 14336."""
    from llm_consensus_tpu.ops.pallas import parity

    err = parity.quant_matmul_error(
        seed=0, m=8, k=128, n=256, n_layers=n_layers, interpret=True
    )
    parity.check("quant_matmul", err, parity.QUANT_MATMUL_TOL)


def test_matmul_stacked_quant_view_matches_sliced():
    """ops.quant.matmul on a StackedQuant view == matmul on the slice,
    with the kernel both forced on and forced off."""
    import numpy as np

    from llm_consensus_tpu.ops.quant import (
        StackedQuant,
        matmul,
        quantize_tensor,
        set_kernel_enabled,
    )

    key = jax.random.PRNGKey(2)
    stack = jax.random.normal(key, (2, 128, 256), jnp.float32)
    qt = quantize_tensor(stack, axis=1)  # [2,128,256] int8, [2,1,256] scale
    x = jax.random.normal(jax.random.fold_in(key, 3), (4, 128), jnp.bfloat16)
    from llm_consensus_tpu.ops.quant import QuantizedTensor

    for force in (True, False):
        set_kernel_enabled(force)
        try:
            for layer in range(2):
                sliced = QuantizedTensor(
                    q=qt.q[layer], scale=qt.scale[layer]
                )
                want = matmul(x, sliced)
                got = matmul(
                    x, StackedQuant(full=qt, layer=jnp.asarray(layer))
                )
                np.testing.assert_allclose(
                    np.asarray(got, np.float32),
                    np.asarray(want, np.float32),
                    rtol=2e-2,
                    atol=2e-2,
                )
        finally:
            set_kernel_enabled(None)
