"""SDAR (Qwen3-MoE layers, block-diffusion generation), plainly.

The reference the served path is compared with: the published layer
equations in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``, over ONE whole sequence:

- attention: RMSNorm, then q = RMSNorm_q(W_q n) over ``n_heads`` heads
  and k = RMSNorm_k(W_k n), v = W_v n over ``n_kv_heads`` (the per-head
  norms act over a head's ``head_dim`` values), RoPE over every dim
  (``rotate_half``), one softmax under the BLOCK-CAUSAL rule — a query
  at i sees a key at j iff ``j // block_length <= i // block_length``,
  the prompt included — and the out-projection;
- experts: RMSNorm, a float32 softmax router over all experts, the top
  k renormalised, and for each expert a SwiGLU over the tokens routed
  to it (a Python loop over the experts, gathering those tokens); no
  shared expert;
- a final RMSNorm and the untied head.

Generation (:func:`generate`) is SDAR's: the prompt's whole blocks are
the context, the first block is the prompt's tail followed by masks, and
each of ``denoising_steps`` steps reads the block's logits (at a masked
position, the logit read for that same position), takes the greedy
token and its probability at every masked position and commits those
above ``unmask_threshold`` — or, if fewer pass than the step's share of
the block's masks (the first ``masks % steps`` steps one more), the
share's most confident ones (ties to the lower position). A finished
block joins the context; generation stops at ``max_new_tokens`` or at a
committed EOS, and what follows the EOS is dropped. :func:`teacher_forced`
follows served logits through the same rule instead of its own, and
compares its own logits with them at every masked position.

Under the block rule no position sees a later block, so the full forward
of a block's state (its context, then the block) is unchanged by what
follows it. :func:`teacher_forced` therefore reads every state of a
request in ONE forward over the longest context followed by each state's
block at its own positions, where a state's positions see the context
before their block and their own block, and no other state: the logits
of each state are those of its own full forward.

No kernel, cache or batch of sequences (the packed states above are one
sequence under an explicit rule): quantised leaves of the system's tree are
dequantised to float32 one layer (and one expert) at a time, so that a
16 GB chip holds the int8 tree beside it. It depends on jax alone and
reads the configuration through plain attributes, so
``benchmark/reference/sdar_moe.py`` is a byte-identical copy that the
benchmark runs on its own.

Departures from the published model and generation (JetLM
``modeling_sdar_moe.py``, SDAR's ``generate``):

- The block length, the denoising steps, the threshold and the mask id
  are not in the published config: the configuration assumes SDAR's
  released defaults (4, 4, 0.9, low-confidence dynamic remasking) and a
  mask id the byte tokenizer never emits.
- Greedy only (temperature 0), and the logit a masked position reads is
  its own position's (no shift).
- The tokenizer is the caller's (bytes, here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

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


def rope(x, theta, pos):
    """x [S, heads, D] rotated by its positions ``pos`` [S], pairs
    (t, t + D/2)."""
    d = x.shape[-1]
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def visible(cfg, pos, state):
    """[S, S] bool, query i sees key j: the block-causal rule on the
    positions ``pos`` [S] for ``state`` -1 (a sequence's own tokens);
    a token of a state s >= 0 sees the -1 tokens of earlier blocks and
    the tokens of its own block in state s; a token of state < -1
    (padding, one state each) sees itself alone."""
    blk = pos // cfg.block_length
    before = (blk[None, :] < blk[:, None]) & (state[None, :] == -1)
    same = (blk[None, :] == blk[:, None]) & (state[None, :] == state[:, None])
    return before | same


def attention(cfg, p, h, pos=None, state=None, qk_norm=True):
    """GQA under the block-causal rule over one sequence: h [S, D] ->
    [S, D], positions ``pos`` (default 0..S-1) and states ``state``
    (default all -1: one plain sequence) as :func:`visible` reads them.
    ``qk_norm`` False leaves out the QK norms (a fault the comparison
    must reject)."""
    s = h.shape[0]
    pos = jnp.arange(s) if pos is None else pos
    state = jnp.full((s,), -1) if state is None else state
    n_h, n_kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ dequant(p["wq"])).reshape(s, n_h, d)
    k = (h @ dequant(p["wk"])).reshape(s, n_kv, d)
    v = (h @ dequant(p["wv"])).reshape(s, n_kv, d)
    if qk_norm:
        q = rms_norm(q, dequant(p["q_norm"]), cfg.rms_norm_eps)
        k = rms_norm(k, dequant(p["k_norm"]), cfg.rms_norm_eps)
    q = rope(q, cfg.rope_theta, pos).reshape(s, n_kv, n_h // n_kv, d)
    k = rope(k, cfg.rope_theta, pos)
    scores = jnp.einsum("ikgd,jkd->kgij", q, k) * d**-0.5
    seen = visible(cfg, pos, state)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("kgij,jkd->ikgd", probs, v).reshape(s, n_h * d)
    return out @ dequant(p["wo"])


def route(cfg, router, h):
    """[S, E] float32: the weight each token gives each expert — its
    softmax probability among the token's k largest, renormalised —
    else zero."""
    probs = jax.nn.softmax(h @ dequant(router), axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, cfg.n_experts_per_token)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_idx
    ].set(top_w)


def add_expert(y, h, rows, weight, w_gate, w_up, w_down, e):
    """y + weight[rows] * SwiGLU_e(h[rows]) at ``rows`` (-1: padding)."""
    w_gate, w_up, w_down = (_index(w, e) for w in (w_gate, w_up, w_down))
    x = h[jnp.maximum(rows, 0)]
    out = (jax.nn.silu(x @ dequant(w_gate)) * (x @ dequant(w_up))) @ dequant(
        w_down
    )
    w = jnp.where(rows >= 0, weight[jnp.maximum(rows, 0)], 0.0)
    return y.at[jnp.maximum(rows, 0)].add(w[:, None] * out)


def experts(cfg, p, h, rows=None):
    """The routed experts of one layer: h [S, D] -> [S, D]. A Python
    loop over the experts, each over the tokens routed to it (padded
    to a power of two, so a handful of programs serve every count).
    ``rows`` (a host bool mask [S]) limits the experts to those tokens;
    the others get zeros."""
    weights = _jit(route, cfg)(p["router"], h)
    chosen = np.array(weights > 0)
    if rows is not None:
        chosen[~rows] = False
    step = _jit(add_expert)
    y = jnp.zeros_like(h)
    for e in range(cfg.n_experts):
        rows = np.flatnonzero(chosen[:, e])
        if not len(rows):
            continue
        width = 1 << int(len(rows) - 1).bit_length()
        padded = np.full((width,), -1, np.int32)
        padded[: len(rows)] = rows
        y = step(y, h, jnp.asarray(padded), weights[:, e], p["w_gate"],
                 p["w_up"], p["w_down"], jnp.int32(e))
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


def head(cfg, norm_f, lm_head, x):
    return rms_norm(x, dequant(norm_f), cfg.rms_norm_eps) @ dequant(lm_head)


def forward(cfg, params, tokens, at=None, qk_norm=True, pos=None,
            state=None) -> jnp.ndarray:
    """Logits [len(at), V] float32 of ONE sequence ``tokens`` [S] at the
    rows ``at`` (default: all), under the block-causal rule: the full
    forward pass, no cache. ``pos`` and ``state`` (host int arrays [S])
    place the tokens as :func:`visible` reads them (default: 0..S-1, one
    plain sequence); padding (state < -1) reaches no expert. ``qk_norm``
    False leaves the QK norms out."""
    blocks = params["blocks"]
    n_layers = jax.tree_util.tree_leaves(blocks["attn_norm"])[0].shape[0]
    placed = () if pos is None else (
        jnp.asarray(pos, jnp.int32), jnp.asarray(state, jnp.int32))
    rows = None if state is None else np.asarray(state) >= -1
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"][jnp.asarray(tokens, jnp.int32)], F32)
        for i in range(n_layers):
            p = _index(blocks, i)
            h = _jit(_normed, cfg)(p["attn_norm"], x)
            x = x + _jit(attention, cfg, qk_norm=qk_norm)(p, h, *placed)
            h = _jit(_normed, cfg)(p["mlp_norm"], x)
            x = x + experts(cfg, p, h, rows)
        if at is not None:
            x = x[jnp.asarray(at)]
        return _jit(head, cfg)(params["norm_f"], params["lm_head"], x)


def unmask(cfg, block, logits, masked0, step):
    """One denoising step on the host: ``block`` (list of B ids),
    ``logits`` [B, V] read for it, ``masked0`` masks when the block
    started. Returns the block after the step's commits."""
    logits = np.asarray(logits, np.float64)
    masked = [t == cfg.mask_token_id for t in block]
    guess = logits.argmax(-1)
    peak = logits.max(-1, keepdims=True)
    conf = 1.0 / np.exp(logits - peak).sum(-1)
    steps = cfg.denoising_steps
    share = masked0 // steps + (1 if step < masked0 % steps else 0)
    high = [i for i, m in enumerate(masked)
            if m and conf[i] > cfg.unmask_threshold]
    if len(high) >= share:
        take = high
    else:
        order = sorted((i for i, m in enumerate(masked) if m),
                       key=lambda i: (-conf[i], i))
        take = order[:share]
    out = list(block)
    for i in take:
        out[i] = int(guess[i])
    return out


# The sequences a generation reads grow a block at a time; a forward of
# one is padded to a multiple of this with id 0, which under the block
# rule no earlier position sees, so that a few shapes serve every step.
PAD_TO = 64


def _block_logits(cfg, params, seq, block, **kw):
    """Logits [B, V] of ``block`` after the context ``seq``: the full
    forward over both, padded (``PAD_TO``)."""
    n = len(seq) + len(block)
    tokens = seq + block + [0] * (-n % PAD_TO)
    return forward(cfg, params, tokens, at=range(len(seq), n), **kw)


def _split(cfg, ids):
    b = cfg.block_length
    whole = len(ids) - len(ids) % b
    return list(ids[:whole]), list(ids[whole:])


def generate(cfg, params, ids, max_new_tokens, eos_id) -> list[int]:
    """SDAR's block-diffusion generation from prompt ``ids``, greedily:
    the generated ids (an EOS included, nothing after it)."""
    b, mask = cfg.block_length, cfg.mask_token_id
    seq, tail = _split(cfg, ids)
    out: list[int] = []
    while len(out) < max_new_tokens:
        block = tail + [mask] * (b - len(tail))
        masked0 = b - len(tail)
        for step in range(cfg.denoising_steps):
            if mask not in block:
                break
            logits = _block_logits(cfg, params, seq, block)
            block = unmask(cfg, block, logits, masked0, step)
        new = block[len(tail):]
        seq, tail = seq + block, []
        for t in new:
            out.append(t)
            if t == eos_id or len(out) >= max_new_tokens:
                return out
    return out


def served_states(cfg, ids, served):
    """The block states of the served run's first n denoising steps
    (``served`` [n * B, V]: B rows a step; a block runs
    ``denoising_steps`` steps whatever it commits), each rebuilt from
    the prompt and the served logits by the unmask rule. Returns the
    longest context (the prompt's whole blocks and the finished blocks)
    and, for each step, (its block's first position, the block)."""
    b, mask, steps = cfg.block_length, cfg.mask_token_id, cfg.denoising_steps
    served = np.asarray(served, np.float32).reshape(-1, b, served.shape[-1])
    seq, tail = _split(cfg, ids)
    block = tail + [mask] * (b - len(tail))
    masked0 = b - len(tail)
    states = []
    for n in range(served.shape[0]):
        step = n % steps
        if n and not step:
            seq, block, masked0 = seq + block, [mask] * b, b
        states.append((len(seq), list(block)))
        block = unmask(cfg, block, served[n], masked0, step)
    return seq, states


def states_logits(cfg, params, context, states, width=0, qk_norm=True):
    """Logits [len(states), B, V] of each state's block after its
    context (``context[:start]``), from one forward over ``context``
    followed by every state's block at its own positions, padded to
    ``width`` tokens (padding: id 0, a state of its own)."""
    b = cfg.block_length
    tokens = list(context) + [t for _, blk in states for t in blk]
    pos = list(range(len(context))) + [
        start + i for start, _ in states for i in range(b)]
    state = [-1] * len(context) + [s for s in range(len(states))
                                   for _ in range(b)]
    pad = max(width - len(tokens), 0)
    tokens += [0] * pad
    pos += [0] * pad
    state += list(range(-2, -2 - pad, -1))
    rows = range(len(context), len(context) + len(states) * b)
    logits = forward(cfg, params, tokens, at=rows, qk_norm=qk_norm,
                     pos=np.asarray(pos), state=np.asarray(state))
    return np.asarray(logits).reshape(len(states), b, -1)


def packed_width(cfg, context, states) -> int:
    """Tokens of :func:`states_logits`'s forward, before padding."""
    return len(context) + len(states) * cfg.block_length


def teacher_forced(cfg, params, ids, served, qk_norm=True, memo=None,
                   width=0):
    """The served run's own denoising steps, judged: each step's block
    is rebuilt from the served logits (:func:`served_states`), the
    reference's logits are computed for every state (one forward, see
    :func:`states_logits`), and both are kept at the block's masked
    positions. Returns (served rows, reference rows, the blocks at each
    step); ``memo`` (a dict) shares the forward between requests that
    reach the same states."""
    b, mask = cfg.block_length, cfg.mask_token_id
    served = np.asarray(served, np.float32).reshape(-1, b, served.shape[-1])
    memo = {} if memo is None else memo
    context, states = served_states(cfg, ids, served)
    key = (tuple(context), tuple((s, tuple(blk)) for s, blk in states),
           qk_norm)
    if key not in memo:
        memo[key] = states_logits(cfg, params, context, states, width,
                                  qk_norm=qk_norm)
    have, want = [], []
    for n, (_, block) in enumerate(states):
        at = [i for i, t in enumerate(block) if t == mask]
        have.append(served[n][at])
        want.append(memo[key][n][at])
    return np.concatenate(have), np.concatenate(want), [
        blk for _, blk in states]


def compare(have, want) -> dict:
    """How far logits ``have`` [positions, V] lie from the reference's
    ``want``: max |delta| and where, and the root-mean-square of the
    difference over that of the reference's logits about their mean —
    over all positions (``rel_rms``) and at the best single position
    (``rel_rms_min``)."""
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


def _die_with_parent() -> None:
    """Ask the kernel (Linux) to end this process when the one that
    started it ends, so that a judge killed mid-run leaves no child
    holding the chip; elsewhere, nothing."""
    import ctypes
    import signal

    try:
        ctypes.CDLL(None).prctl(1, signal.SIGKILL)  # PR_SET_PDEATHSIG
    except (OSError, AttributeError):
        pass


def main(argv=None) -> int:
    """Judge served logits: ``python sdar_moe.py --in F --out G``.

    ``F`` holds the server's ``model`` / ``layers`` / ``quant`` and the
    ``requests``: each a prompt and the float32 logits the served path
    returned for its first denoising steps (``"logits": n`` of
    ``/v1/generate``, base64, B rows a step). The weights are
    regenerated through the program's own ``random_params(cfg,
    PRNGKey(0), quant)``, the prompt is encoded by the program's
    tokenizer, and :func:`teacher_forced` follows the served steps. ``G``
    gets, for each request, max |delta| over the masked positions, where
    it lies, and the root-mean-square of the difference over that of the
    reference's logits about their mean. ``--no-qk-norm`` judges a
    reference without the QK norms (a fault the limits must reject)."""
    import argparse
    import base64
    import json
    import sys
    import time

    ap = argparse.ArgumentParser()
    ap.add_argument("--in", dest="src", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--no-qk-norm", action="store_true")
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
    todo = []
    for r in job["requests"]:
        served = np.frombuffer(
            base64.b64decode(r["b64"]), dtype="<f4"
        ).reshape(r["positions"], r["vocab"])
        todo.append((r, list(tok.encode(r["prompt"])), served))
    # One width for every forward of the job: one program a shape.
    width = max(packed_width(cfg, *served_states(cfg, ids, served))
                for _, ids, served in todo)
    width += -width % PAD_TO
    memo: dict = {}
    for r, ids, served in todo:
        t0 = time.monotonic()
        have, want, _ = teacher_forced(
            cfg, params, ids, served, qk_norm=not args.no_qk_norm, memo=memo,
            width=width,
        )
        print(f"reference: {len(ids)} prompt tokens, "
              f"{r['positions'] // cfg.block_length} steps in "
              f"{time.monotonic() - t0:.1f} s", file=sys.stderr)
        out["requests"].append({
            "tag": r.get("tag", ""),
            "prompt_tokens": len(ids),
            **compare(have, want),
        })
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    _die_with_parent()
    raise SystemExit(main())
