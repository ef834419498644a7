"""Block-diffusion decoding (SDAR): the unmask rule of one denoising step.

A block of ``block_length`` positions starts as the prompt's tail (if it
has one) followed by ``mask_token_id``s. Each of ``denoising_steps``
forwards reads the block's logits; at every still-masked position the
greedy token and its softmax probability are taken, and the step
commits:

- every masked position whose probability passes ``unmask_threshold``,
  if at least the step's share of the block does;
- else its share's most confident masked positions.

The shares split the block's masked count over the steps, the first
``masked % steps`` steps one more (SDAR's ``get_num_transfer_tokens``).
Everything here is traced inside the batcher's step program: the block
never visits the host between its denoising steps.
"""

from __future__ import annotations

import jax.numpy as jnp

from llm_consensus_tpu.models.configs import ModelConfig


def step_share(masked0: jnp.ndarray, step, steps: int) -> jnp.ndarray:
    """[R] int32: how many positions step ``step`` of ``steps`` commits
    at the least, for blocks that started with ``masked0`` [R] masks."""
    return masked0 // steps + (step < masked0 % steps).astype(masked0.dtype)


def unmask(
    cfg: ModelConfig, logits: jnp.ndarray, x: jnp.ndarray,
    masked0: jnp.ndarray, step,
) -> jnp.ndarray:
    """One denoising step's commit, greedily: ``logits`` [R, B, V]
    float32 of blocks ``x`` [R, B] that started with ``masked0`` [R]
    masks; ``step`` may be traced. Returns the blocks after the step.
    Ties in confidence go to the lower position."""
    masked = x == cfg.mask_token_id
    guess = jnp.argmax(logits, axis=-1).astype(x.dtype)
    peak = jnp.max(logits, axis=-1, keepdims=True)
    conf = 1.0 / jnp.sum(jnp.exp(logits - peak), axis=-1)  # p(guess)
    conf = jnp.where(masked, conf, -jnp.inf)
    share = step_share(masked0, step, cfg.denoising_steps)[:, None]
    high = masked & (conf > cfg.unmask_threshold)
    b = x.shape[1]
    i = jnp.arange(b)
    # rank[r, i]: masked positions more confident than i (ties: lower
    # position first) — i is among the ``share`` most confident iff
    # its rank is below it.
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None]) & (i[None, :] < i[:, None])
    )
    rank = jnp.sum(ahead & masked[:, None, :], axis=-1)
    top = masked & (rank < share)
    take = jnp.where(jnp.sum(high, axis=-1, keepdims=True) >= share, high, top)
    return jnp.where(take, guess, x)


def emitted(
    cfg: ModelConfig, block: jnp.ndarray, masked0: jnp.ndarray, eos_id: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """A finished block's generated tokens, left-aligned ([R, B]: the
    positions after the prompt's tail first), and how many of them
    belong to the request ([R]): up to and including the first EOS."""
    b = block.shape[1]
    i = jnp.arange(b)
    tail = b - masked0
    new = jnp.take_along_axis(block, (i[None] + tail[:, None]) % b, axis=1)
    eos = (new == eos_id) & (i[None] < masked0[:, None])
    n = jnp.where(jnp.any(eos, axis=1), jnp.argmax(eos, axis=1) + 1, masked0)
    return new, n.astype(jnp.int32)

