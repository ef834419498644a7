"""DeepSeek-V2-Lite's layers against the plain reference
(``models/reference/deepseek_v2.py``), on ``test-tiny-mla`` with seeded
random weights: the non-paged forward, absorbed against expanded
attention, the router's rule, the dropless expert layer against the
masked loop, YaRN's numbers at the published sizes, the HF loader with
its rotary permutation, ``--layers``, and the ``"logits": n`` read-out.
The paged programs are in ``tests/test_mla_paged.py``.

Tolerances: the system runs float32 here under the test process's
``highest`` matmul precision, so it differs from the reference only by
the order of float32 sums: 1e-4 on values of magnitude ~1 (observed
~2e-6 to 2e-5). The reference in bfloat16 misses by ~2e-2.
"""

from __future__ import annotations

import base64
import filecmp
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.reference import deepseek_v2 as ref
from llm_consensus_tpu.models.transformer import (
    _mla_attn_full,
    _mla_project,
    _moe_dropless,
    first_layers,
    forward,
    init_params,
    mla_absorb_q,
    mla_expand_o,
    moe_route,
)
from llm_consensus_tpu.ops.quant import quantize_params
from llm_consensus_tpu.ops.rope import rope_cos_sin, yarn_inv_freq, yarn_ramp_bounds

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4


def _model(quant: bool = False):
    cfg = get_config("test-tiny-mla")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    params = jax.tree.map(lambda a: a * 3 if a.ndim > 1 else a, params)
    return cfg, quantize_params(params) if quant else params


def _tokens(n: int) -> np.ndarray:
    return (np.arange(n) * 7 + 3) % 259


# (a) ------------------------------------------------------------------


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_forward_matches_reference(quant):
    cfg, params = _model(quant)
    ids = _tokens(40)
    got = np.asarray(forward(cfg, params, jnp.asarray(ids)[None])[0])
    want = np.asarray(ref.forward(cfg, params, ids))
    assert np.abs(want).max() > 0.5  # logits of magnitude ~1
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_reference_in_bf16_fails_the_tolerance():
    cfg, params = _model()
    ids = _tokens(40)
    want = np.asarray(ref.forward(cfg, params, ids))
    low = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params
    )
    assert np.abs(np.asarray(ref.forward(cfg, low, ids)) - want).max() > 20 * TOL


# (c) ------------------------------------------------------------------


def test_absorbed_attention_equals_expanded():
    """One layer: scores against the cached latent with queries moved
    into latent space, values expanded after the sum — against per-head
    keys and values rebuilt from the latent."""
    cfg, params = _model()
    p = jax.tree.map(lambda a: a[0], params["blocks"])
    s = 24
    h = jax.random.normal(jax.random.PRNGKey(1), (1, s, cfg.d_model))
    pos = jnp.arange(s)[None]
    cos, sin = rope_cos_sin(pos, cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling)
    q, c, k_pe = _mla_project(cfg, p, h, cos, sin)
    want = _mla_attn_full(cfg, p, q, c, k_pe, None)  # [1, s, H, v]
    dn = cfg.qk_nope_head_dim
    q_lat = jnp.concatenate(
        [mla_absorb_q(cfg, p["w_kvb"], q[..., :dn]), q[..., dn:]], -1
    )
    key = jnp.concatenate([c, k_pe], -1)[0]  # [s, latent]: what a page holds
    scores = jnp.einsum("qhd,kd->hqk", q_lat[0], key) * cfg.attn_scale
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    o_lat = jnp.einsum("hqk,kr->qhr", probs, key[:, : cfg.kv_lora_rank])
    got = mla_expand_o(cfg, p["w_kvb"], o_lat[None])
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


# (d) ------------------------------------------------------------------


def test_router_is_softmax_then_topk_unrenormalised():
    cfg, params = _model()
    router = params["blocks"]["router"][0]
    x = jax.random.normal(jax.random.PRNGKey(2), (9, cfg.d_model))
    _, w, idx = moe_route(cfg, router, x)
    probs = jax.nn.softmax(np.asarray(x) @ np.asarray(router), -1)
    order = np.argsort(-probs, -1)[:, : cfg.n_experts_per_token]
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(order, -1))
    np.testing.assert_allclose(
        np.sort(w, -1), np.sort(np.take_along_axis(probs, order, -1), -1),
        atol=1e-6,
    )
    assert float(w.sum(-1).max()) < 0.999  # the six are NOT renormalised
    renorm = moe_route(cfg.with_(moe_renormalize=True), router, x)[1]
    np.testing.assert_allclose(renorm.sum(-1), 1.0, atol=1e-6)


def test_mixtral_rule_is_the_parents():
    """``test-tiny-moe`` keeps top-k-then-softmax, and its logits are
    bit for bit what the parent commit (20b0665) computes for the same
    seed: five of them, copied from a run of that commit."""
    cfg = get_config("test-tiny-moe")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    logits = forward(cfg, params, jnp.asarray(_tokens(20))[None])[0]
    got = np.asarray(logits)[[0, 5, 9, 14, 19], [0, 17, 101, 258, 383]]
    want = np.asarray(PARENT_MOE_LOGITS, np.float32)
    np.testing.assert_array_equal(got, want)
    x = jax.random.normal(jax.random.PRNGKey(3), (5, cfg.d_model))
    _, w, _ = moe_route(cfg, params["blocks"]["router"][0], x)
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)


PARENT_MOE_LOGITS = [
    float.fromhex(x)
    for x in (
        "-0x1.463a920000000p-7", "-0x1.d100a60000000p-4",
        "-0x1.d09bd20000000p-5", "-0x1.ff20a00000000p-11",
        "-0x1.7e5f940000000p-8",
    )
]


# (e) ------------------------------------------------------------------


def _masked_loop(cfg, p, h):
    return np.asarray(ref.experts(cfg, p, h))


@pytest.mark.parametrize(
    "routing", ["as_routed", "all_to_one", "one_starved", "idle_rows"]
)
def test_dropless_layer_equals_masked_loop(routing):
    """Skewed routings through the sorted, tiled, grouped path: every
    token to one expert (one group of many tiles), an expert nobody
    reaches (an empty group), rows that carry no request."""
    cfg, params = _model()
    p = dict(jax.tree.map(lambda a: a[0], params["blocks"]))
    t = 37
    h = jax.random.normal(jax.random.PRNGKey(4), (1, t, cfg.d_model))
    if routing in ("all_to_one", "one_starved"):
        # Feature 0 is constant, so the router's row 0 is a bias: the
        # first k experts win for every token, or expert 2 never does.
        h = h.at[..., 0].set(4.0)
        if routing == "all_to_one":
            p["router"] = (p["router"] * 0.01).at[
                0, : cfg.n_experts_per_token
            ].set(5.0)
        else:
            p["router"] = p["router"].at[0, 2].set(-50.0)
    active = None
    if routing == "idle_rows":
        active = (jnp.arange(t) % 3) != 0
    y, _, idx, stats = _moe_dropless(cfg, p, h, active=active)
    want = _masked_loop(cfg, p, h[0])
    got = np.asarray(y[0])
    if active is not None:
        live = np.asarray(active)
        # An idle row takes the shared experts alone.
        shared = np.asarray(
            ref.swiglu(h[0], p["ws_gate"], p["ws_up"], p["ws_down"])
        )
        np.testing.assert_allclose(got[~live], shared[~live], atol=TOL, rtol=0)
        got, want = got[live], want[live]
        idx = np.asarray(idx)[live]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    reached = len(np.unique(np.asarray(idx)))
    assert stats.tolist() == [reached, idx.size]
    if routing == "all_to_one":
        assert reached == cfg.n_experts_per_token
    if routing == "one_starved":
        assert 2 not in np.asarray(idx)


# (f) ------------------------------------------------------------------


def test_yarn_at_published_sizes():
    cfg = get_config("deepseek-v2-lite")
    assert yarn_ramp_bounds(cfg.rope_scaling, 64, 10000.0) == (10, 23)
    assert cfg.attn_scale == pytest.approx(0.114722, rel=1e-5)
    inv = np.asarray(yarn_inv_freq(cfg.rope_scaling, 64, 10000.0))
    # Below the ramp: theta_i; on it: the blend; above: theta_i / 40.
    assert inv[4] == pytest.approx(10000.0 ** (-4 / 32), rel=1e-6)
    theta16 = 10000.0 ** (-16 / 32)
    ramp = (16 - 10) / 13
    assert inv[16] == pytest.approx(
        theta16 * (1 - ramp) + theta16 / 40 * ramp, rel=1e-6
    )
    assert inv[31] == pytest.approx(10000.0 ** (-31 / 32) / 40, rel=1e-6)
    np.testing.assert_allclose(inv, np.asarray(ref.yarn_inv_freq(cfg)), rtol=1e-6)
    assert ref.softmax_scale(cfg) == pytest.approx(cfg.attn_scale, rel=1e-9)
    # mscale == mscale_all_dim: cos and sin are not rescaled.
    cos, _ = rope_cos_sin(jnp.zeros((1,), jnp.int32), 64, 10000.0, cfg.rope_scaling)
    np.testing.assert_allclose(cos, 1.0)


# (g) ------------------------------------------------------------------


def _hf_state_dict(cfg, params):
    """A synthetic ``DeepseekV2ForCausalLM`` state dict that holds
    ``params`` in the PUBLISHED rotary convention: torch [out, in]
    weights, rotary columns interleaved (2t, 2t + 1)."""
    from llm_consensus_tpu.models.hf_loader import (
        _DEEPSEEK_MOE_MAP,
        _DENSE_MAP,
        _MLA_MAP,
        rotary_column_permutation,
    )

    perm = rotary_column_permutation(cfg.qk_rope_head_dim)
    inv = np.argsort(perm)

    def publish(ours, w):  # [in, out] in the repo's order -> published
        w = np.asarray(w)
        if ours == "wq":
            w = w.reshape(w.shape[0], cfg.n_heads, cfg.head_dim).copy()
            w[..., cfg.qk_nope_head_dim :] = w[..., cfg.qk_nope_head_dim :][..., inv]
            w = w.reshape(w.shape[0], -1)
        if ours == "w_kva":
            w = w.copy()
            w[:, cfg.kv_lora_rank :] = w[:, cfg.kv_lora_rank :][:, inv]
        return w.T if w.ndim == 2 else w

    out = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.norm.weight": np.asarray(params["norm_f"]),
        "lm_head.weight": np.asarray(params["lm_head"]).T,
    }
    layer = 0
    for stack, mlp_map in (
        ("dense_blocks", {k: _DENSE_MAP[k] for k in ("w_gate", "w_up", "w_down")}),
        ("blocks", _DEEPSEEK_MOE_MAP),
    ):
        n = params[stack]["attn_norm"].shape[0]
        for i in range(n):
            for ours, template in {**_MLA_MAP, **mlp_map}.items():
                w = params[stack][ours][i]
                if "{e}" in template:
                    for e in range(cfg.n_experts):
                        out[template.format(i=layer, e=e)] = publish(ours, w[e])
                else:
                    out[template.format(i=layer)] = publish(ours, w)
            layer += 1
    return out


def test_hf_loader_reads_deepseek_v2_with_rotary_permutation(tmp_path):
    safetensors = pytest.importorskip("safetensors.numpy")
    from llm_consensus_tpu.models.hf_loader import config_from_hf, load_hf_params

    cfg, params = _model()
    state = _hf_state_dict(cfg, params)
    safetensors.save_file(
        {k: np.ascontiguousarray(v, np.float32) for k, v in state.items()},
        str(tmp_path / "model.safetensors"),
    )
    ys = cfg.rope_scaling
    (tmp_path / "config.json").write_text(json.dumps({
        "architectures": ["DeepseekV2ForCausalLM"], "model_type": "deepseek_v2",
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.d_model,
        "num_hidden_layers": cfg.n_layers, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_heads, "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": cfg.moe_d_ff, "n_routed_experts": cfg.n_experts,
        "n_shared_experts": cfg.n_shared_experts,
        "num_experts_per_tok": cfg.n_experts_per_token,
        "first_k_dense_replace": cfg.n_dense_layers, "moe_layer_freq": 1,
        "kv_lora_rank": cfg.kv_lora_rank, "q_lora_rank": None,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "max_position_embeddings": cfg.max_seq_len,
        "norm_topk_prob": False, "routed_scaling_factor": 1.0,
        "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
        "tie_word_embeddings": False,
        "rope_scaling": {
            "type": "yarn", "factor": ys.factor, "beta_fast": ys.beta_fast,
            "beta_slow": ys.beta_slow, "mscale": ys.mscale,
            "mscale_all_dim": ys.mscale_all_dim,
            "original_max_position_embeddings":
                ys.original_max_position_embeddings,
        },
    }))
    got_cfg = config_from_hf(tmp_path, name=cfg.name)
    assert got_cfg == cfg
    loaded = load_hf_params(got_cfg, tmp_path, dtype=jnp.float32)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # The permutation is what makes the repo's rotation the published one:
    # the PUBLISHED interleaved rotation of the published columns gives
    # the same scores as the repo's rotation of the loaded ones.
    wq_pub = state["model.layers.0.self_attn.q_proj.weight"].T
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (6, cfg.d_model)))
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q_pub = (h @ wq_pub).reshape(6, cfg.n_heads, dn + dr)[..., dn:]
    q_ours = (h @ np.asarray(loaded["dense_blocks"]["wq"][0])).reshape(
        6, cfg.n_heads, dn + dr
    )[..., dn:]
    pos = jnp.arange(6)
    ang = np.asarray(pos)[:, None] * np.asarray(ref.yarn_inv_freq(cfg))[None]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    even, odd = q_pub[..., 0::2], q_pub[..., 1::2]
    rot_pub = np.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    rot_pub = rot_pub.reshape(q_pub.shape)  # interleaved back
    rot_ours = np.asarray(ref.rope(jnp.asarray(q_ours), pos, cfg))
    half = dr // 2
    np.testing.assert_allclose(rot_ours[..., :half], rot_pub[..., 0::2], atol=1e-5)
    np.testing.assert_allclose(rot_ours[..., half:], rot_pub[..., 1::2], atol=1e-5)


# (h) ------------------------------------------------------------------


def test_layers_flag_serves_the_first_n_layers():
    from llm_consensus_tpu import cli

    args = cli.build_serve_parser().parse_args(
        ["--model", "test-tiny-mla", "--layers", "2"]
    )
    assert args.layers == 2
    cfg, params = _model()
    cut_cfg, cut = first_layers(cfg, params, 2)
    assert (cut_cfg.n_layers, cut_cfg.n_moe_layers) == (2, 1)
    assert cut["dense_blocks"]["wq"].shape[0] == 1
    assert cut["blocks"]["router"].shape[0] == 1
    ids = _tokens(16)
    got = np.asarray(forward(cut_cfg, cut, jnp.asarray(ids)[None])[0])
    want = np.asarray(ref.forward(cut_cfg, cut, ids))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # Random weights are born at that depth.
    born = init_params(cut_cfg, jax.random.PRNGKey(0), jnp.float32)
    assert jax.tree.map(jnp.shape, born) == jax.tree.map(jnp.shape, cut)
    with pytest.raises(ValueError, match="leading dense"):
        cfg.with_layers(1)
    dense_cfg, dense = first_layers(
        get_config("test-tiny"),
        init_params(get_config("test-tiny"), jax.random.PRNGKey(0)),
        1,
    )
    assert dense_cfg.n_layers == 1 and dense["blocks"]["wq"].shape[0] == 1


# (i) ------------------------------------------------------------------


def test_logits_readout_returns_the_samplers_logits():
    """``submit(logits=n)``: the rows are what the step programs handed
    the sampler — a greedy request's tokens are their argmax — and they
    are the reference's logits up to the bf16 latent pool (3e-2 here;
    the float32 pool of ``test_mla_paged`` is held to 2e-4)."""
    from llm_consensus_tpu.serving.continuous import (
        ContinuousBatcher,
        ContinuousConfig,
        _meta_with_logits,
    )

    cfg, params = _model()
    batcher = ContinuousBatcher(
        cfg, params,
        config=ContinuousConfig(
            max_slots=4, n_pages=64, page_size=8, pages_per_seq=20,
            seq_buckets=(32, 64, 128), prefill_chunk=16, max_new_tokens=6,
        ),
    )
    try:
        head = "The quick brown fox jumps over the lazy dog again and again. "
        prompts = [head + "alpha", head + "beta beta", "an unrelated prompt"]
        futs = [batcher.submit(p, max_new_tokens=6, logits=4) for p in prompts]
        plain = batcher.submit(prompts[0], max_new_tokens=6)
        outs = [f.result(timeout=600) for f in futs]
        assert plain.result(timeout=600).logits is None
        with pytest.raises(ValueError, match="greedy"):
            batcher.submit("x", temperature=0.7, logits=2)
    finally:
        batcher.close()
    tok = batcher.tokenizer
    for prompt, out in zip(prompts, outs):
        n = min(4, out.num_tokens)
        assert out.logits.shape == (n, cfg.vocab_size)
        assert out.logits.dtype == np.float32
        gen = out.logits.argmax(-1)
        # Greedy: the served text is the argmax of the served rows.
        assert tok.decode(gen.tolist()) == out.text[: len(tok.decode(gen.tolist()))]
        ids = list(tok.encode(prompt))
        full = np.asarray(ids + gen[:-1].tolist())
        want = np.asarray(
            ref.forward(cfg, params, full, at=np.arange(len(ids) - 1, len(full)))
        )
        np.testing.assert_allclose(out.logits, want, atol=3e-2, rtol=0)
        meta = _meta_with_logits({"id": "r"}, out.logits)
        rows = np.frombuffer(
            base64.b64decode(meta["logits"]["b64"]), "<f4"
        ).reshape(meta["logits"]["positions"], meta["logits"]["vocab"])
        np.testing.assert_array_equal(rows, out.logits)


# satellites -----------------------------------------------------------


def test_benchmark_reference_is_the_packages_copy():
    assert filecmp.cmp(
        os.path.join(ROOT, "llm_consensus_tpu/models/reference/deepseek_v2.py"),
        os.path.join(ROOT, "benchmark/reference/deepseek_v2_lite.py"),
        shallow=False,
    )


def test_coordinator_log_records_are_utf8_encodable(caplog):
    """A byte-level answer with an invalid byte decodes to a lone
    surrogate; the coordinator's log records of it must still encode
    (pytest-xdist ships captured logs as UTF-8: ROADMAP C1)."""
    import asyncio

    from llm_consensus_tpu.backends import FakeBackend
    from llm_consensus_tpu.consensus import Coordinator, default_panel
    from llm_consensus_tpu.consensus.parsing import parse_evaluation
    from llm_consensus_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    text = tok.decode(tok.encode("ok", add_bos=False) + [0xA3 + 4])
    assert any(0xDC80 <= ord(c) <= 0xDCFF for c in text)

    class Bytes(FakeBackend):
        async def generate_batch(self, requests):
            outs = await super().generate_batch(requests)
            for o in outs:
                o.text = o.text + " " + text
            return outs

    with caplog.at_level(logging.DEBUG, "llm_consensus_tpu"):
        coord = Coordinator(panel=default_panel(), backend=Bytes())
        result = asyncio.run(coord.run("why?"))
        parse_evaluation("neither verdict " + text)  # logs the reply
    assert any(0xDC80 <= ord(c) <= 0xDCFF for c in result.answer)
    assert any("Final answer" in r.getMessage() for r in caplog.records)
    assert any("Unexpected response" in r.getMessage() for r in caplog.records)
    for r in caplog.records:
        r.getMessage().encode("utf-8")
