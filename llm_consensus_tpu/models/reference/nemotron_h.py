"""Nemotron-H (Mamba-2 + attention + ungated experts) forward pass, plainly.

The reference the served path is compared with: the published layer
equations in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``. Every layer is ONE mixer,
``x <- x + mixer(RMSNorm(x))``, chosen by the configuration's plan:

- ``M`` Mamba-2: the SEQUENTIAL recurrence, token by token (one
  ``lax.scan``, no chunking, no blocked form):
  ``S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = S_t C_t + D x_t``,
  after a causal depthwise convolution of 4 taps (with bias) and SiLU
  over [x | B | C], then the gated group RMS norm and the
  out-projection;
- ``*`` attention: GQA, one full causal softmax, NO position signal;
- ``E`` experts: sigmoid scores in float32, the top k of score + bias
  chosen, weights the chosen scores renormalised and scaled; a Python
  loop over the experts with masks, each ``W_down relu(W_up x)²``; one
  shared expert of the same form;
- ``-`` a dense MLP of the same form.

No kernel, cache, batching or quantised arithmetic: quantised leaves of
the system's parameter tree are dequantised to float32 first, one layer
(and, in an expert layer, one expert) at a time so that a 16 GB chip
holds the int8 tree beside it.

It depends on jax alone and reads the configuration through plain
attributes, so ``benchmark/reference/nemotron_h.py`` is a byte-identical
copy that the benchmark runs on its own.

Departures from the published model (HF ``modeling_nemotron_h.py``):

- ``in_proj`` arrives split. The published matrix is one ``d -> [z |
  xBC | dt]``; the system stores its three column ranges as
  ``w_in_z``, ``w_in_xbc`` and ``w_in_dt`` (each part a kernel shape),
  which the HF loader cuts; the product is the same numbers.
- Expert width. The system stores an expert's 1856 columns zero-padded
  to 1920 (whole 128-lane tiles): ``relu(0)² = 0`` through zero rows of
  ``W_down`` adds nothing, so this file multiplies the padded matrices
  as they are.
- ``chunk_size`` is the published kernel's blocking and changes no
  result; ``time_step_*`` and ``rescale_prenorm_residual`` are
  initialisation only. The tokenizer is the caller's (bytes, here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32

STACKS = {"M": "ssm_blocks", "*": "attn_blocks", "E": "moe_blocks",
          "-": "mlp_blocks"}


def dequant(leaf) -> jnp.ndarray:
    """A parameter leaf in float32: plain arrays cast, int8 weight-only
    leaves (``.q`` int8, ``.scale`` per output column) multiplied out."""
    if hasattr(leaf, "q"):
        if type(leaf).__name__ != "QuantizedTensor":
            raise ValueError("the reference reads int8 leaves only")
        return leaf.q.astype(F32) * leaf.scale.astype(F32)
    return jnp.asarray(leaf, F32)


def _index(leaf, i):
    """Layer (or expert) ``i`` of a stacked leaf, still quantised."""
    return jax.tree_util.tree_map(lambda a: a[i], leaf)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def relu2(x, w_up, w_down):
    up = jax.nn.relu(x @ w_up)
    return (up * up) @ w_down


def _round_to(x, dtype):
    """x rounded to ``dtype``'s precision, still float32. Through
    ``lax.reduce_precision``: a cast there and back is an identity the
    compiler may (and on a TPU does) remove."""
    info = jnp.finfo(dtype)
    if info.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def mamba(cfg, p, h, state_dtype=F32):
    """One Mamba-2 mixer over one sequence: h [S, D] -> [S, D].

    ``state_dtype``: the dtype the recurrent state is ROUNDED to after
    every token (float32: not at all) — what keeping the state in a
    lower precision would give, which a comparison's tolerances must
    reject."""
    s = h.shape[0]
    heads, hd, n, groups, k = (
        cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
        cfg.ssm_conv,
    )
    inner = heads * hd
    z = h @ dequant(p["w_in_z"])
    xbc = h @ dequant(p["w_in_xbc"])
    dt = jax.nn.softplus(h @ dequant(p["w_in_dt"]) + dequant(p["dt_bias"]))
    # Causal depthwise convolution: tap k - 1 multiplies the current
    # token, tap 0 the one three before it; zeros before the sequence.
    w, bias = dequant(p["conv_w"]), dequant(p["conv_b"])
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
    conv = sum(w[j] * padded[j : j + s] for j in range(k)) + bias
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(s, heads, hd)
    b = xbc[:, inner : inner + groups * n].reshape(s, groups, n)
    c = xbc[:, inner + groups * n :].reshape(s, groups, n)
    rep = heads // groups
    a = -jnp.exp(dequant(p["a_log"]))  # [H]

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        b_h = jnp.repeat(b_t, rep, axis=0)  # [H, N]: a group's B, a head
        c_h = jnp.repeat(c_t, rep, axis=0)
        state = (
            jnp.exp(dt_t * a)[:, None, None] * state
            + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        )
        state = _round_to(state, state_dtype)
        return state, jnp.einsum("hpn,hn->hp", state, c_h)

    _, y = jax.lax.scan(step, jnp.zeros((heads, hd, n), F32), (x, b, c, dt))
    y = y + dequant(p["d_skip"])[:, None] * x
    g = y.reshape(s, inner) * jax.nn.silu(z)
    g = g.reshape(s, groups, inner // groups)
    g = g * jax.lax.rsqrt(
        jnp.mean(g * g, axis=-1, keepdims=True) + cfg.rms_norm_eps
    )
    g = g.reshape(s, inner) * dequant(p["gate_norm"])
    return g @ dequant(p["w_out"])


def attention(cfg, p, h):
    """GQA over one sequence, no positions: h [S, D] -> [S, D]."""
    s = h.shape[0]
    n_h, n_kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ dequant(p["wq"])).reshape(s, n_kv, n_h // n_kv, d)
    k = (h @ dequant(p["wk"])).reshape(s, n_kv, d)
    v = (h @ dequant(p["wv"])).reshape(s, n_kv, d)
    scores = jnp.einsum("ikgd,jkd->kgij", q, k) * d**-0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("kgij,jkd->ikgd", probs, v).reshape(s, n_h * d)
    return out @ dequant(p["wo"])


def route(cfg, router, bias, h):
    """[S, E] float32: the weight each token gives each expert — its
    sigmoid score where the expert is among the k largest of score +
    bias, divided by the chosen scores' sum and scaled; else zero."""
    scores = jax.nn.sigmoid(h @ dequant(router))  # float32
    _, top_idx = jax.lax.top_k(scores + dequant(bias), cfg.n_experts_per_token)
    chosen = jnp.any(
        top_idx[..., None] == jnp.arange(cfg.n_experts), axis=1
    )  # [S, E]
    w = jnp.where(chosen, scores, 0.0)
    if cfg.moe_renormalize:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg.moe_routed_scale


def add_expert(y, h, weight, w_up, w_down, e=None):
    """y + weight[:, None] * relu2_e(h): one expert over EVERY token,
    masked by its routing weight. ``e`` picks the expert out of a
    layer's stacked [E, ..] leaves; None: the leaves are one MLP's."""
    if e is not None:
        w_up, w_down = _index(w_up, e), _index(w_down, e)
    return y + weight[:, None] * relu2(h, dequant(w_up), dequant(w_down))


def experts(cfg, p, h):
    """Routed + shared experts of one layer: h [S, D] -> [S, D]. A
    Python loop over the experts, each a program of its own, so that
    one expert's float32 matrices are all that is ever dequantised."""
    weights = _jit(route, cfg)(p["router"], p["router_bias"], h)
    step = _jit(add_expert)
    y = jnp.zeros_like(h)
    for e in range(cfg.n_experts):
        y = step(y, h, weights[:, e], p["w_up"], p["w_down"], jnp.int32(e))
    if cfg.n_shared_experts:
        y = step(y, h, jnp.ones_like(weights[:, 0]), p["ws_up"], p["ws_down"])
    return y


_PROGRAMS: dict = {}


def _jit(fn, cfg=None, **static):
    """``fn`` compiled (with ``cfg`` bound, where it takes one): the
    same plain ``jax.numpy``, as one program a call instead of one an
    operation."""
    key = (fn, cfg, tuple(sorted(static.items())))
    if key not in _PROGRAMS:
        bound = (
            (lambda *a, _f=fn: _f(*a, **static)) if cfg is None
            else (lambda *a, _f=fn: _f(cfg, *a, **static))
        )
        _PROGRAMS[key] = jax.jit(bound)
    return _PROGRAMS[key]


def _normed(cfg, norm, x):
    return rms_norm(x, dequant(norm), cfg.rms_norm_eps)


def layer(cfg, kind, p, x, state_dtype=F32):
    """One layer on the residual stream x [S, D]; ``p`` is that layer's
    slice of its kind's stack, leaves still quantised."""
    h = _jit(_normed, cfg)(p["norm"], x)
    if kind == "M":
        return x + _jit(mamba, cfg, state_dtype=state_dtype)(p, h)
    if kind == "*":
        return x + _jit(attention, cfg)(p, h)
    if kind == "E":
        return x + experts(cfg, p, h)
    return _jit(add_expert)(
        x, h, jnp.ones_like(h[:, 0]), p["w_up"], p["w_down"]
    )


def layers_of(cfg, params):
    """(kind, parameter slice) of every layer in the plan's order."""
    seen: dict = {}
    for kind in cfg.layer_plan:
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        yield kind, _index(params[STACKS[kind]], i)


def head(cfg, norm_f, lm_head, x):
    return rms_norm(x, dequant(norm_f), cfg.rms_norm_eps) @ dequant(lm_head)


def forward(cfg, params, tokens, at=None, round_to=None,
            state_dtype=F32) -> jnp.ndarray:
    """Logits [len(at), V] float32 of ONE sequence ``tokens`` [S] at the
    positions ``at`` (default: all), each conditioned on every token
    before it: the full forward pass, no cache.

    Two ways to degrade it below the served precision, which a
    comparison's tolerances must reject: ``round_to`` (a dtype) rounds
    the residual stream to it after every layer; ``state_dtype`` keeps
    the recurrent state in it (:func:`mamba`)."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = jnp.asarray(params["embed"][tokens], F32)
        for kind, p in layers_of(cfg, params):
            x = layer(cfg, kind, p, x, state_dtype)
            if round_to is not None:
                x = _round_to(x, round_to)
        if at is not None:
            x = x[jnp.asarray(at)]
        return _jit(head, cfg)(params["norm_f"], params["lm_head"], x)


def compare(have, want) -> dict:
    """How far logits ``have`` [positions, V] lie from the reference's
    ``want``: max |delta| and where, and the root-mean-square of the
    difference over that of the reference's logits about their mean —
    over all positions (``rel_rms``) and at the best single position
    (``rel_rms_min``)."""
    import numpy as np

    have, want = np.asarray(have, np.float32), np.asarray(want, np.float32)
    diff = np.abs(have - want)
    where = np.unravel_index(int(diff.argmax()), diff.shape)
    spread = np.sqrt(np.mean((want - want.mean(-1, keepdims=True)) ** 2))
    per_position = np.sqrt(np.mean(diff**2, axis=-1)) / spread
    return {
        "positions": int(have.shape[0]),
        "max_abs": float(diff.max()),
        "at_position": int(where[0]),
        "at_token": int(where[1]),
        "rel_rms": float(np.sqrt(np.mean(diff**2)) / spread),
        "rel_rms_min": float(per_position.min()),
        "rel_rms_median": float(np.median(per_position)),
        "logit_rms": float(spread),
        "argmax_agree": int((have.argmax(-1) == want.argmax(-1)).sum()),
    }


def main(argv=None) -> int:
    """Judge served logits: ``python nemotron_h.py --in F --out G``.

    ``F`` holds the server's ``model`` / ``layers`` / ``quant`` and the
    ``requests``: each a prompt and the float32 logits the served path
    returned for its first generated positions (``"logits": n`` of
    ``/v1/generate``, base64). The weights are regenerated through the
    program's own ``random_params(cfg, PRNGKey(0), quant)``, the prompt
    is encoded by the program's tokenizer, the generated tokens are the
    served rows' argmax (the requests are greedy) and the reference is
    teacher-forced on them. ``G`` gets, for each request, max |delta|,
    where it lies, and the root-mean-square of the difference over that
    of the reference's logits about their mean."""
    import argparse
    import base64
    import json
    import sys
    import time

    import numpy as np

    ap = argparse.ArgumentParser()
    ap.add_argument("--in", dest="src", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.src) as f:
        job = json.load(f)

    from llm_consensus_tpu.cli import random_params
    from llm_consensus_tpu.engine.tokenizer import ByteTokenizer
    from llm_consensus_tpu.models.configs import get_config

    t0 = time.monotonic()
    cfg = get_config(job["model"])
    if job.get("layers"):
        cfg = cfg.with_layers(int(job["layers"]))
    params = random_params(cfg, jax.random.PRNGKey(0), job["quant"])
    jax.block_until_ready(params)
    print(f"reference: weights in {time.monotonic() - t0:.1f} s",
          file=sys.stderr)
    tok = ByteTokenizer()
    dev = jax.devices()[0]
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "requests": [],
    }
    done: dict = {}
    for r in job["requests"]:
        served = np.frombuffer(
            base64.b64decode(r["b64"]), dtype="<f4"
        ).reshape(r["positions"], r["vocab"])
        ids = list(tok.encode(r["prompt"]))
        gen = served.argmax(axis=-1).tolist()
        key = (r["prompt"], tuple(gen[:-1]))
        if key not in done:
            full = np.asarray(ids + gen[:-1], np.int32)
            at = np.arange(len(ids) - 1, len(full))
            t0 = time.monotonic()
            done[key] = np.asarray(forward(cfg, params, full, at=at))
            print(f"reference: {len(full)} tokens in "
                  f"{time.monotonic() - t0:.1f} s", file=sys.stderr)
        out["requests"].append({
            "tag": r.get("tag", ""),
            "prompt_tokens": len(ids),
            **compare(served, done[key]),
        })
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
