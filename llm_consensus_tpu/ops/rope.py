"""Rotary position embeddings (RoPE), rotate-half convention.

Replaces the RoPE the BASELINE.json north star attributes to the target's
CUDA path; here it is jnp (XLA fuses the elementwise rotation into the
surrounding projections on TPU). Frequencies are computed on the fly from
integer positions so decode steps with per-sequence offsets need no
precomputed table.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def _llama3_rescale(inv_freq: jnp.ndarray, scaling) -> jnp.ndarray:
    """Llama-3.1 'llama3' rope_scaling: long wavelengths divide by
    ``factor``, short ones stay, a smooth ramp interpolates between
    (matches transformers' _compute_llama3_parameters)."""
    orig = scaling.original_max_position_embeddings
    low_wavelen = orig / scaling.low_freq_factor
    high_wavelen = orig / scaling.high_freq_factor
    wavelen = 2.0 * math.pi / inv_freq
    scaled = inv_freq / scaling.factor
    smooth = (orig / wavelen - scaling.low_freq_factor) / (
        scaling.high_freq_factor - scaling.low_freq_factor
    )
    mid = (1 - smooth) * scaled + smooth * inv_freq
    out = jnp.where(wavelen > low_wavelen, scaled, inv_freq)
    return jnp.where(
        (wavelen <= low_wavelen) & (wavelen >= high_wavelen), mid, out
    )


def yarn_ramp_bounds(scaling, dim: int, theta: float) -> tuple[int, int]:
    """``(low, high)`` rotary pair indices between which YaRN blends:
    the pairs that turn ``beta_fast`` and ``beta_slow`` times over the
    original context (transformers' ``yarn_find_correction_range``)."""
    orig = scaling.original_max_position_embeddings

    def pair_of(rotations: float) -> float:
        return (
            dim
            * math.log(orig / (rotations * 2.0 * math.pi))
            / (2.0 * math.log(theta))
        )

    low = max(math.floor(pair_of(scaling.beta_fast)), 0)
    high = min(math.ceil(pair_of(scaling.beta_slow)), dim - 1)
    return low, high


def yarn_inv_freq(scaling, dim: int, theta: float) -> jnp.ndarray:
    """YaRN's blended frequencies over ``dim`` rotary dims: pair i keeps
    ``theta^(-2i/dim)`` below ``low``, takes it over ``factor`` above
    ``high``, and a linear ramp of the two between."""
    half = dim // 2
    base = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    low, high = yarn_ramp_bounds(scaling, dim, theta)
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 1e-3),
        0.0,
        1.0,
    )
    return base * (1.0 - ramp) + (base / scaling.factor) * ramp


def _yarn_mscale(factor: float, mscale: float) -> float:
    if factor <= 1.0 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def rope_cos_sin(
    positions: jnp.ndarray,
    head_dim: int,
    theta: float = 10000.0,
    scaling=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for given integer positions. ``head_dim`` is the
    ROTATED width (``ModelConfig.rope_dim``): all of a GQA head, the
    rotary part of an MLA head.

    positions: [...] int array (any shape, e.g. [B, S]).
    Returns cos, sin of shape [..., head_dim] (half-frequencies duplicated,
    matching the rotate-half convention). ``scaling``: optional
    :class:`llm_consensus_tpu.models.configs.RopeScaling` (Llama-3.1)
    or ``YarnScaling`` (blended frequencies; cos and sin times the
    ratio of its two ``mscale`` terms).
    """
    half = head_dim // 2
    amp = 1.0
    if hasattr(scaling, "beta_fast"):  # YarnScaling
        inv_freq = yarn_inv_freq(scaling, head_dim, theta)
        amp = _yarn_mscale(scaling.factor, scaling.mscale) / _yarn_mscale(
            scaling.factor, scaling.mscale_all_dim
        )
    else:
        freq_exponents = jnp.arange(half, dtype=jnp.float32) / half
        inv_freq = 1.0 / (theta**freq_exponents)  # [half]
        if scaling is not None:
            inv_freq = _llama3_rescale(inv_freq, scaling)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., half]
    angles = jnp.concatenate([angles, angles], axis=-1)  # [..., head_dim]
    if amp != 1.0:
        return jnp.cos(angles) * amp, jnp.sin(angles) * amp
    return jnp.cos(angles), jnp.sin(angles)


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """Apply rotary embedding to q or k.

    x: [B, S, H, D]; cos/sin: [B, S, D] (broadcast over the head axis).
    Rotation runs in float32 and is cast back to x.dtype.
    """
    xf = x.astype(jnp.float32)
    c = cos[..., None, :]  # [B, S, 1, D]
    s = sin[..., None, :]
    return (xf * c + _rotate_half(xf) * s).astype(x.dtype)


def apply_rope_tail(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """Rotate the LAST ``cos.shape[-1]`` dims of every head and leave
    the leading ones as they are (MLA: ``[q_nope | q_pe]``).

    x: [B, S, H, D]; cos/sin: [B, S, R] with R <= D."""
    r = cos.shape[-1]
    if r == x.shape[-1]:
        return apply_rope(x, cos, sin)
    return jnp.concatenate(
        [x[..., :-r], apply_rope(x[..., -r:], cos, sin)], axis=-1
    )
