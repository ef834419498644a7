"""Mamba-2 state-space mixing, in the two forms the serving path needs.

One head h of group g keeps a state ``S`` [P, N] (P channels of the
head, N state values each) and sees, a token t, its input ``x_t`` [P],
the group's ``B_t`` and ``C_t`` [N] and a step ``Δ_t`` > 0:

    S_t = exp(Δ_t A) · S_{t-1} + Δ_t · x_t ⊗ B_t        (A < 0, a head)
    y_t = S_t C_t                                        (+ D x_t, outside)

:func:`sequential_scan` is that recurrence, token by token — what the
tests hold everything else to. :func:`ssd_terms` + :func:`ssd_apply` is
the same sum regrouped over a block of T tokens (the "SSD" form): with
``cs_t = Σ_{u<=t} Δ_u A`` the block's outputs are

    Y = M X + (C ∘ exp(cs)) S_0ᵀ,   M[t, s] = (C_t·B_s) exp(cs_t - cs_s) Δ_s  (s <= t)
    S_T = exp(cs_T) S_0 + Xᵀ (B ∘ exp(cs_T - cs) Δ)

— three matrix products a head, which is what a chunk lane of 64 tokens
wants; a decode row is the block of one token. A token with Δ = 0 (a
row that carries no request, the padding of a last partial chunk)
neither decays the state nor adds to it, so the state after the block
is the state after its last REAL token. ``ssd_terms`` is plain
``jax.numpy`` either way; ``ssd_apply`` touches the state and is the
part a kernel replaces (:mod:`llm_consensus_tpu.ops.pallas.ssm_scan`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def sequential_scan(x, b, c, dt, a, s0):
    """The recurrence itself. x [T, H, P], b / c [T, G, N], dt [T, H],
    a [H], s0 [H, P, N] -> (y [T, H, P], s_T [H, P, N]), float32."""
    heads, groups = x.shape[1], b.shape[1]
    rep = heads // groups

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        b_h = jnp.repeat(b_t, rep, axis=0)  # [H, N]
        c_h = jnp.repeat(c_t, rep, axis=0)
        s = (
            jnp.exp(dt_t * a)[:, None, None] * s
            + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        )
        return s, jnp.einsum("hpn,hn->hp", s, c_h, precision=_HI)

    s_t, y = jax.lax.scan(
        step, s0.astype(F32),
        (x.astype(F32), b.astype(F32), c.astype(F32), dt.astype(F32)),
    )
    return y, s_t


def ssd_terms(x, b, c, dt, a):
    """What a block of T tokens needs of its inputs, state apart.

    x [R, T, H, P], b / c [R, T, G, N], dt [R, T, H] (0 where a token
    is not to count), a [H]; R independent rows (decode rows, chunk
    lanes). Returns float32, head-major so that a head's operands are
    whole trailing matrices: ``x`` [R, H, T, P], ``m`` [R, H, T, T],
    ``ce`` [R, H, T, N], ``bw`` [R, H, T, N], ``f`` [R, H, 1, N] (the
    block's total decay, repeated along the state's lanes)."""
    x, b, c, dt = (v.astype(F32) for v in (x, b, c, dt))
    r, t, heads, _ = x.shape
    groups, n = b.shape[2], b.shape[3]
    rep = heads // groups
    cs = jnp.cumsum(dt * a.astype(F32), axis=1)  # [R, T, H], <= 0
    cs_h = cs.transpose(0, 2, 1)  # [R, H, T]
    dt_h = dt.transpose(0, 2, 1)
    diff = cs_h[:, :, :, None] - cs_h[:, :, None, :]  # [R, H, t, s]
    causal = jnp.tril(jnp.ones((t, t), bool))
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf)) * dt_h[:, :, None, :]
    cb = jnp.einsum("rtgn,rsgn->rgts", c, b, precision=_HI)  # [R, G, t, s]
    m = jnp.repeat(cb, rep, axis=1) * decay
    b_h = jnp.repeat(b.transpose(0, 2, 1, 3), rep, axis=1)  # [R, H, T, N]
    c_h = jnp.repeat(c.transpose(0, 2, 1, 3), rep, axis=1)
    ce = c_h * jnp.exp(cs_h)[..., None]
    last = cs_h[:, :, -1:]
    bw = b_h * (jnp.exp(last - cs_h) * dt_h)[..., None]
    f = jnp.broadcast_to(jnp.exp(last)[..., None], (r, heads, 1, n))
    return {"x": x.transpose(0, 2, 1, 3), "m": m, "ce": ce, "bw": bw, "f": f}


def ssd_apply(terms, s0):
    """A block's outputs and end state from :func:`ssd_terms` and the
    rows' start states s0 [R, H, P, N]: (y [R, H, T, P], s_T)."""
    x = terms["x"]
    y = jnp.einsum("rhts,rhsp->rhtp", terms["m"], x, precision=_HI)
    y = y + jnp.einsum("rhtn,rhpn->rhtp", terms["ce"], s0, precision=_HI)
    s_t = terms["f"] * s0 + jnp.einsum(
        "rhtp,rhtn->rhpn", x, terms["bw"], precision=_HI
    )
    return y, s_t


def ssd_scan(x, b, c, dt, a, s0, block: int = 64):
    """A whole sequence a row through blocks of ``block`` tokens, the
    state carried from block to block: x [R, T, H, P] etc. as
    :func:`ssd_terms`, s0 [R, H, P, N] -> (y [R, T, H, P], s_T). The
    full-sequence ``forward``'s path; a last partial block is padded
    with Δ = 0 tokens."""
    r, t, heads, p = x.shape
    pad = -t % block
    if pad:
        x, b, c, dt = (
            jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            for v in (x, b, c, dt)
        )
    nb = (t + pad) // block

    def blocks(v):
        return v.reshape(r, nb, block, *v.shape[2:]).swapaxes(0, 1)

    def step(s, inp):
        y, s = ssd_apply(ssd_terms(*inp, a), s)
        return s, y

    s_t, y = jax.lax.scan(
        step, s0.astype(F32), tuple(blocks(v) for v in (x, b, c, dt))
    )
    # y: [nb, R, H, block, P] -> [R, T, H, P]
    y = y.transpose(1, 0, 3, 2, 4).reshape(r, nb * block, heads, p)
    return y[:, :t], s_t


def causal_conv(window, w, bias):
    """Depthwise causal convolution over a window that already holds
    the K - 1 rows before the block: window [R, K - 1 + T, C], w [K, C]
    (tap K - 1 multiplies the current token), bias [C] -> [R, T, C]
    float32, before the activation."""
    k = w.shape[0]
    t = window.shape[1] - (k - 1)
    win = window.astype(F32)
    out = sum(w[j].astype(F32) * win[:, j : j + t] for j in range(k))
    return out + bias.astype(F32)


def conv_rows_after(window, n_real, k: int):
    """The K - 1 rows a row's next block must see: those ending at its
    last REAL token. window [R, K - 1 + T, C], n_real [R] -> [R, K - 1,
    C]; ``n_real`` 0 returns the rows it came with."""
    return jax.vmap(
        lambda w_, n: jax.lax.dynamic_slice_in_dim(w_, n, k - 1, axis=0)
    )(window, n_real)


def gated_group_norm(y, z, w, groups: int, eps: float):
    """Mamba-2's gated norm: RMS-normalise ``y · silu(z)`` within each
    of ``groups`` equal groups of the last axis, times ``w``."""
    g = (y.astype(F32) * jax.nn.silu(z.astype(F32)))
    shape = g.shape
    g = g.reshape(*shape[:-1], groups, shape[-1] // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(shape) * w.astype(F32)
