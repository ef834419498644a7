"""DeepSeek-V2 (MLA + shared-expert MoE) forward pass, plainly.

The reference the served path is compared with: the published layer
equations in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``, EXPANDED attention (every
head's keys and values rebuilt from the latent, one full causal
softmax), a Python loop over the experts with masks. No kernel, cache,
batching or quantised arithmetic: quantised leaves of the system's
parameter tree are dequantised to float32 first, one layer (and, in an
expert layer, one expert) at a time so that a 16 GB chip holds the int8
tree beside it.

It depends on jax alone and reads the configuration through plain
attributes, so ``benchmark/reference/deepseek_v2_lite.py`` is a
byte-identical copy that the benchmark runs on its own.

Departures from the published model (HF ``modeling_deepseek.py``):

- Rotary columns. The published rotation pairs lanes (2t, 2t + 1); this
  file, like the system, pairs (t, t + rope/2) (``rotate_half``). The two
  differ by a fixed permutation of the rotary columns of ``q_proj`` (per
  head) and ``kv_a_proj_with_mqa``, which the HF loader applies
  (``models/hf_loader.py``); with it the two conventions give the same
  logits.
- ``max_position_embeddings``: positions are whatever the caller sends;
  YaRN's blend depends only on ``original_max_position_embeddings``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


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


def yarn_inv_freq(cfg) -> jnp.ndarray:
    """theta_i blended as YaRN prescribes over the rotary dims."""
    ys, dim, theta = cfg.rope_scaling, cfg.qk_rope_head_dim, cfg.rope_theta
    half = dim // 2
    base = theta ** (-jnp.arange(half, dtype=F32) / half)
    if ys is None:
        return base

    def pair_of(rotations):
        return (
            dim
            * math.log(
                ys.original_max_position_embeddings / (rotations * 2 * math.pi)
            )
            / (2 * math.log(theta))
        )

    low = max(math.floor(pair_of(ys.beta_fast)), 0)
    high = min(math.ceil(pair_of(ys.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=F32) - low) / (high - low), 0, 1)
    return base * (1 - ramp) + (base / ys.factor) * ramp


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if m and factor > 1 else 1.0


def softmax_scale(cfg) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        scale *= _mscale(ys.factor, ys.mscale_all_dim) ** 2
    return scale


def rope(x, pos, cfg):
    """Rotate x [S, ..., rope] at positions pos [S], pairing lane t with
    lane t + rope/2."""
    ang = pos.astype(F32)[:, None] * yarn_inv_freq(cfg)[None, :]
    ys = cfg.rope_scaling
    amp = 1.0
    if ys is not None:
        amp = _mscale(ys.factor, ys.mscale) / _mscale(
            ys.factor, ys.mscale_all_dim
        )
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * amp
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * amp
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def attention(cfg, p, h, pos):
    """Expanded MLA over one sequence: h [S, D] -> [S, H * v]."""
    s = h.shape[0]
    n_h, dn, dr, dv = (
        cfg.n_heads,
        cfg.qk_nope_head_dim,
        cfg.qk_rope_head_dim,
        cfg.v_head_dim,
    )
    r = cfg.kv_lora_rank
    q = (h @ dequant(p["wq"])).reshape(s, n_h, dn + dr)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], pos, cfg)
    kva = h @ dequant(p["w_kva"])
    c = rms_norm(kva[:, :r], dequant(p["kv_a_norm"]), cfg.rms_norm_eps)
    k_pe = rope(kva[:, r:], pos, cfg)  # ONE rotary key a token
    kv = (c @ dequant(p["w_kvb"])).reshape(s, n_h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (
        jnp.einsum("ihd,jhd->hij", q_nope, k_nope)
        + jnp.einsum("ihd,jd->hij", q_pe, k_pe)
    ) * softmax_scale(cfg)
    causal = pos[None, :] <= pos[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hij,jhd->ihd", probs, v).reshape(s, n_h * dv)


def route(cfg, router, h):
    """[S, E] float32: the weight each token gives each expert — its
    softmax probability where the expert is among the token's k
    largest (as it is: renormalised only if the config says so), else
    zero."""
    probs = jax.nn.softmax(h @ dequant(router), axis=-1)  # float32
    top_w, top_idx = jax.lax.top_k(probs, cfg.n_experts_per_token)
    if cfg.moe_renormalize:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    top_w = top_w * cfg.moe_routed_scale
    chosen = top_idx[..., None] == jnp.arange(cfg.n_experts)  # [S, k, E]
    return jnp.sum(jnp.where(chosen, top_w[..., None], 0.0), axis=1)


def add_expert(y, h, weight, w_gate, w_up, w_down, e=None):
    """y + weight[:, None] * SwiGLU_e(h): one expert over EVERY token,
    masked by its routing weight (zero where the token did not take
    it). ``e`` picks the expert out of a layer's stacked [E, ..] leaves
    (inside the program: one program serves all of a layer's experts);
    None: the leaves are one MLP's already."""
    if e is not None:
        w_gate, w_up, w_down = (_index(w, e) for w in (w_gate, w_up, w_down))
    out = swiglu(h, dequant(w_gate), dequant(w_up), dequant(w_down))
    return y + weight[:, None] * out


def experts(cfg, p, h):
    """Routed + shared experts of one layer: h [S, D] -> [S, D]. A
    Python loop over the experts, each a program of its own, so that
    one expert's float32 matrices are all that is ever dequantised."""
    weights = _jit(route, cfg)(p["router"], h)
    step = _jit(add_expert)
    y = jnp.zeros_like(h)
    for e in range(cfg.n_experts):
        y = step(
            y, h, weights[:, e], p["w_gate"], p["w_up"], p["w_down"],
            jnp.int32(e),
        )
    if cfg.n_shared_experts:
        y = step(
            y, h, jnp.ones_like(weights[:, 0]),
            p["ws_gate"], p["ws_up"], p["ws_down"],
        )
    return y


def attention_block(cfg, p, x, pos):
    """x + Attention(RMSNorm(x)), and RMSNorm of that for the MLP."""
    eps = cfg.rms_norm_eps
    h = rms_norm(x, dequant(p["attn_norm"]), eps)
    x = x + attention(cfg, p, h, pos) @ dequant(p["wo"])
    return x, rms_norm(x, dequant(p["mlp_norm"]), eps)


_PROGRAMS: dict = {}


def _jit(fn, cfg=None):
    """``fn`` compiled (with ``cfg`` bound, where it takes one): the
    same plain ``jax.numpy``, as one program a call instead of one an
    operation."""
    key = (fn, cfg)
    if key not in _PROGRAMS:
        bound = fn if cfg is None else (lambda *a, _f=fn: _f(cfg, *a))
        _PROGRAMS[key] = jax.jit(bound)
    return _PROGRAMS[key]


_ATTENTION_LEAVES = (
    "attn_norm", "mlp_norm", "wq", "w_kva", "kv_a_norm", "w_kvb", "wo",
)


def layer(cfg, p, x, pos):
    """One block on the residual stream x [S, D]; ``p`` is that layer's
    slice of its stack, leaves still quantised."""
    x, h2 = _jit(attention_block, cfg)(
        {k: p[k] for k in _ATTENTION_LEAVES}, x, pos
    )
    if "router" in p:
        return x + experts(cfg, p, h2)
    return _jit(add_expert)(
        x, h2, jnp.ones_like(h2[:, 0]), p["w_gate"], p["w_up"], p["w_down"]
    )


def layers_of(params):
    """Every layer's parameter slice, in order: the leading dense stack
    (``dense_blocks``), then the expert stack (``blocks``)."""
    for name in ("dense_blocks", "blocks"):
        stack = params.get(name)
        if stack is None:
            continue
        n = jax.tree_util.tree_leaves(stack)[0].shape[0]
        for i in range(n):
            yield _index(stack, i)


def head(cfg, norm_f, lm_head, x):
    return rms_norm(x, dequant(norm_f), cfg.rms_norm_eps) @ dequant(lm_head)


def forward(cfg, params, tokens, at=None, round_to=None) -> jnp.ndarray:
    """Logits [len(at), V] float32 of ONE sequence ``tokens`` [S] at the
    positions ``at`` (default: all), each conditioned on every token
    before it: the full forward pass, no cache.

    ``round_to`` (a dtype; default none) rounds the residual stream to
    it after every layer: what a lower precision than the served one
    would give, which a comparison's tolerances must reject."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0])
        x = jnp.asarray(params["embed"][tokens], F32)
        for p in layers_of(params):
            x = layer(cfg, p, x, pos)
            if round_to is not None:
                x = x.astype(round_to).astype(F32)
        if at is not None:
            x = x[jnp.asarray(at)]
        return _jit(head, cfg)(params["norm_f"], params["lm_head"], x)


def main(argv=None) -> int:
    """Judge served logits: ``python deepseek_v2_lite.py --in F --out G``.

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
        want = done[key]
        diff = np.abs(served - want)
        where = np.unravel_index(int(diff.argmax()), diff.shape)
        spread = np.sqrt(np.mean((want - want.mean(-1, keepdims=True)) ** 2))
        out["requests"].append({
            "tag": r.get("tag", ""),
            "prompt_tokens": len(ids),
            "positions": int(served.shape[0]),
            "max_abs": float(diff.max()),
            "at_position": int(where[0]),
            "at_token": int(where[1]),
            "rel_rms": float(np.sqrt(np.mean(diff**2)) / spread),
            "logit_rms": float(spread),
            "argmax_agree": int(
                (served.argmax(-1) == want.argmax(-1)).sum()
            ),
        })
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
