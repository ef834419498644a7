"""Pallas kernels vs jnp reference ops (interpret mode on CPU).

The same kernels compile via Mosaic on real TPUs; these tests pin the
numerics against the reference implementations in
:mod:`llm_consensus_tpu.ops`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.ops.attention import causal_attention, decode_attention
from llm_consensus_tpu.ops.pallas import (
    flash_causal_attention,
    flash_decode_attention,
    parity,
)


def _qkv(b=2, s=64, h=4, hkv=2, d=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("blk_q", [16, 32, 64])
def test_flash_causal_matches_reference(blk_q):
    q, k, v = _qkv()
    ref = causal_attention(q, k, v)
    got = flash_causal_attention(q, k, v, blk_q=blk_q, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_flash_causal_mha_no_gqa():
    q, k, v = _qkv(h=4, hkv=4)
    ref = causal_attention(q, k, v)
    got = flash_causal_attention(q, k, v, blk_q=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_flash_causal_rejects_ragged_block():
    q, k, v = _qkv(s=48)
    with pytest.raises(ValueError):
        flash_causal_attention(q, k, v, blk_q=32, interpret=True)


def test_flash_decode_matches_reference():
    b, h, hkv, d, max_len = 3, 4, 2, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    k_cache = jax.random.normal(ks[1], (b, max_len, hkv, d), jnp.float32)
    v_cache = jax.random.normal(ks[2], (b, max_len, hkv, d), jnp.float32)
    valid = jnp.array([1, 17, 32], jnp.int32)  # ragged fills incl. edges

    ref = decode_attention(q, k_cache, v_cache, valid)
    got = flash_decode_attention(q, k_cache, v_cache, valid, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_fused_rms_norm_matches_reference():
    """Rows that do not fill the last block (33 = 2 * 16 + 1)."""
    err = parity.rms_norm_error(
        seed=2, shape=(2, 33, 64), blk=16, interpret=True
    )
    parity.check("fused_rms_norm f32", err, parity.NORM_TOL)


def test_pallas_model_matches_jnp_model_end_to_end():
    """Greedy generate with use_pallas=True must equal the jnp-op model."""
    from llm_consensus_tpu.engine.generate import generate
    from llm_consensus_tpu.models.configs import get_config
    from llm_consensus_tpu.models.transformer import init_params

    cfg = get_config("test-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = jnp.array([[5, 9, 13, 17, 21, 2, 7, 3]], jnp.int32)
    lengths = jnp.array([8], jnp.int32)
    kw = dict(max_new_tokens=4, eos_id=-1)

    ref = generate(
        cfg, params, prompt, lengths, jax.random.PRNGKey(0), jnp.zeros(1), **kw
    )
    got = generate(
        cfg.with_(use_pallas=True),
        params,
        prompt,
        lengths,
        jax.random.PRNGKey(0),
        jnp.zeros(1),
        **kw,
    )
    assert got.tokens.tolist() == ref.tokens.tolist()


def test_fused_rms_norm_bf16_output_dtype():
    """bf16 in, bf16 out (the comparison raises on a dtype change)."""
    err = parity.rms_norm_error(
        seed=4, shape=(8, 64), dtype=jnp.bfloat16, interpret=True
    )
    parity.check("fused_rms_norm bf16", err, parity.NORM_BF16_TOL)
