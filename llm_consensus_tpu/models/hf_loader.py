"""Load HuggingFace safetensors checkpoints into the stacked param tree.

The reference has no weights at all (its model is the remote Gemini API,
``src/main.rs:82-86``); this loader is how the TPU build gets real
Llama-3 / Mistral / Qwen2 / Mixtral weights (the model families named by
BASELINE.json's configs) into :mod:`llm_consensus_tpu.models.transformer`'s
layout:

- HF stores one ``[out, in]`` torch Linear weight per layer per proj;
  ours are ``[in, out]`` matmul weights stacked on a leading layer axis
  (one ``lax.scan`` block, SURVEY.md §7 step 1) — so each proj is
  transposed and the per-layer tensors stacked.
- HF RoPE uses the rotate-half convention, as does
  :mod:`llm_consensus_tpu.ops.rope` — weights map 1:1, no permutation.
  The exception is ``DeepseekV2ForCausalLM``, whose rotation pairs
  neighbouring lanes: its rotary columns are permuted on the way in
  (:func:`rotary_column_permutation`).
- bf16 tensors cross torch→numpy via a uint16 view (numpy itself has no
  bfloat16; ml_dtypes supplies the dtype on the jax side).

Memory: tensors are read on demand through mmap'd shard handles (closed
when loading finishes) and cast to the target dtype as each stacked
tensor is assembled, so peak host memory stays ~1 model copy at target
dtype plus the transiently-mapped shards.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from llm_consensus_tpu.models.configs import (
    ModelConfig,
    RopeScaling,
    YarnScaling,
)

# name templates: ours -> HF (dense). {i} = layer index.
_DENSE_MAP = {
    "attn_norm": "model.layers.{i}.input_layernorm.weight",
    "mlp_norm": "model.layers.{i}.post_attention_layernorm.weight",
    "wq": "model.layers.{i}.self_attn.q_proj.weight",
    "wk": "model.layers.{i}.self_attn.k_proj.weight",
    "wv": "model.layers.{i}.self_attn.v_proj.weight",
    "wo": "model.layers.{i}.self_attn.o_proj.weight",
    "bq": "model.layers.{i}.self_attn.q_proj.bias",
    "bk": "model.layers.{i}.self_attn.k_proj.bias",
    "bv": "model.layers.{i}.self_attn.v_proj.bias",
    "w_gate": "model.layers.{i}.mlp.gate_proj.weight",
    "w_up": "model.layers.{i}.mlp.up_proj.weight",
    "w_down": "model.layers.{i}.mlp.down_proj.weight",
}
_MOE_MAP = {
    "router": "model.layers.{i}.block_sparse_moe.gate.weight",
    # experts get an extra {e} axis; HF w1=gate, w3=up, w2=down.
    "w_gate": "model.layers.{i}.block_sparse_moe.experts.{e}.w1.weight",
    "w_up": "model.layers.{i}.block_sparse_moe.experts.{e}.w3.weight",
    "w_down": "model.layers.{i}.block_sparse_moe.experts.{e}.w2.weight",
}
# DeepseekV2ForCausalLM (MLA, no q_lora): attention, then a dense MLP on
# the leading layers (_DENSE_MAP's names) or routed + shared experts.
_MLA_MAP = {
    "attn_norm": _DENSE_MAP["attn_norm"],
    "mlp_norm": _DENSE_MAP["mlp_norm"],
    "wq": "model.layers.{i}.self_attn.q_proj.weight",
    "w_kva": "model.layers.{i}.self_attn.kv_a_proj_with_mqa.weight",
    "kv_a_norm": "model.layers.{i}.self_attn.kv_a_layernorm.weight",
    "w_kvb": "model.layers.{i}.self_attn.kv_b_proj.weight",
    "wo": "model.layers.{i}.self_attn.o_proj.weight",
}
_DEEPSEEK_MOE_MAP = {
    "router": "model.layers.{i}.mlp.gate.weight",
    "w_gate": "model.layers.{i}.mlp.experts.{e}.gate_proj.weight",
    "w_up": "model.layers.{i}.mlp.experts.{e}.up_proj.weight",
    "w_down": "model.layers.{i}.mlp.experts.{e}.down_proj.weight",
    "ws_gate": "model.layers.{i}.mlp.shared_experts.gate_proj.weight",
    "ws_up": "model.layers.{i}.mlp.shared_experts.up_proj.weight",
    "ws_down": "model.layers.{i}.mlp.shared_experts.down_proj.weight",
}
# Linear weights stored [out, in] by torch; transpose to our [in, out].
_TRANSPOSED = {
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "router", "lm_head",
    "w_kva", "w_kvb", "ws_gate", "ws_up", "ws_down",
}


def rotary_column_permutation(rope_dim: int) -> np.ndarray:
    """DeepSeek-V2 rotates lane pairs (2t, 2t + 1); :mod:`ops.rope`
    pairs (t, t + rope_dim / 2). Taking the published rotary columns in
    this order — evens, then odds — makes the repo's rotation of the
    result the published rotation of the original: the same permutation
    on queries and on the shared rotary key leaves every score as it
    was."""
    return np.concatenate(
        [np.arange(0, rope_dim, 2), np.arange(1, rope_dim, 2)]
    )


def _permute_rotary(cfg: ModelConfig, ours: str, w: np.ndarray) -> np.ndarray:
    """Apply :func:`rotary_column_permutation` to the rotary output
    columns of ``wq`` (each head's last ``rope`` of ``nope + rope``)
    and ``w_kva`` (the last ``rope`` of ``latent + rope``); [in, out]."""
    perm = rotary_column_permutation(cfg.qk_rope_head_dim)
    if ours == "wq":
        per = cfg.head_dim
        cols = np.arange(cfg.n_heads * per).reshape(cfg.n_heads, per)
        cols[:, cfg.qk_nope_head_dim :] = cols[:, cfg.qk_nope_head_dim :][
            :, perm
        ]
        return w[:, cols.reshape(-1)]
    if ours == "w_kva":
        cols = np.arange(cfg.latent_dim)
        cols[cfg.kv_lora_rank :] = cols[cfg.kv_lora_rank :][perm]
        return w[:, cols]
    return w


def _to_numpy(t) -> np.ndarray:
    """torch tensor (possibly bf16) -> numpy, zero-copy where possible."""
    import ml_dtypes
    import torch

    if t.dtype == torch.bfloat16:
        return t.view(torch.uint16).numpy().view(ml_dtypes.bfloat16)
    return t.numpy()


class _ShardedCheckpoint:
    """Random access over one or more .safetensors files in a directory."""

    def __init__(self, path: Path):
        self.path = path
        files = sorted(path.glob("*.safetensors"))
        if not files:
            raise FileNotFoundError(f"no .safetensors under {path}")
        index_file = path / "model.safetensors.index.json"
        self._name_to_file: dict[str, Path] = {}
        if index_file.exists():
            weight_map = json.loads(index_file.read_text())["weight_map"]
            for name, fname in weight_map.items():
                self._name_to_file[name] = path / fname
        else:
            from safetensors import safe_open

            for f in files:
                with safe_open(f, framework="pt") as sf:
                    for name in sf.keys():
                        self._name_to_file[name] = f
        self._open: dict[Path, object] = {}

    def __contains__(self, name: str) -> bool:
        return name in self._name_to_file

    def names(self):
        return self._name_to_file.keys()

    def get(self, name: str) -> np.ndarray:
        from safetensors import safe_open

        f = self._name_to_file[name]
        if f not in self._open:
            self._open[f] = safe_open(f, framework="pt")
        return _to_numpy(self._open[f].get_tensor(name))

    def close(self) -> None:
        """Release shard handles (and their mmaps)."""
        self._open.clear()


def _fetch(ckpt: _ShardedCheckpoint, name: str, ours: str, dtype):
    arr = ckpt.get(name).astype(dtype)
    if ours in _TRANSPOSED:
        arr = arr.T
    return arr


def load_hf_params(
    cfg: ModelConfig, path: str | Path, dtype=jnp.bfloat16
) -> dict:
    """Build an ``init_params``-shaped tree from an HF checkpoint dir.

    ``cfg`` must structurally match the checkpoint (layer count, dims,
    MoE-ness, qkv bias); mismatches raise with the offending tensor name.
    """
    path = Path(path)
    ckpt = _ShardedCheckpoint(path)
    try:
        return _load_hf_params(cfg, ckpt, dtype)
    finally:
        ckpt.close()


def _load_mla_params(cfg: ModelConfig, ckpt: _ShardedCheckpoint, dtype) -> dict:
    """DeepseekV2ForCausalLM -> the two-stack tree of
    ``transformer.init_params``: ``dense_blocks`` (the leading
    ``n_dense_layers``) and ``blocks`` (the expert layers)."""
    np_dtype = jnp.dtype(dtype)

    def one(template: str, ours: str, **at) -> np.ndarray:
        name = template.format(**at)
        if name not in ckpt:
            raise KeyError(f"checkpoint missing {name!r} (for param {ours!r})")
        return _permute_rotary(cfg, ours, _fetch(ckpt, name, ours, np_dtype))

    def stack(layers, mlp_map, expert_names=()) -> dict:
        blocks = {
            ours: np.stack([one(t, ours, i=i) for i in layers])
            for ours, t in {**_MLA_MAP, **mlp_map}.items()
            if ours not in expert_names
        }
        for ours in expert_names:
            blocks[ours] = np.stack([
                np.stack([
                    one(mlp_map[ours], ours, i=i, e=e)
                    for e in range(cfg.n_experts)
                ])
                for i in layers
            ])
        return blocks

    nd = cfg.n_dense_layers if cfg.is_moe else cfg.n_layers
    dense_map = {k: _DENSE_MAP[k] for k in ("w_gate", "w_up", "w_down")}
    params: dict = {}
    if nd:
        params["dense_blocks"] = stack(range(nd), dense_map)
    if cfg.n_layers > nd:
        moe_map = dict(_DEEPSEEK_MOE_MAP)
        if not cfg.n_shared_experts:
            for k in ("ws_gate", "ws_up", "ws_down"):
                del moe_map[k]
        params["blocks"] = stack(
            range(nd, cfg.n_layers), moe_map, ("w_gate", "w_up", "w_down")
        )
    else:
        params["blocks"] = params.pop("dense_blocks")
    params["embed"] = ckpt.get("model.embed_tokens.weight").astype(np_dtype)
    params["norm_f"] = ckpt.get("model.norm.weight").astype(np_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = _fetch(ckpt, "lm_head.weight", "lm_head", np_dtype)
    return jax.tree_util.tree_map(jnp.asarray, params)


# nemotron_h (NemotronHForCausalLM): every layer is ``backbone.layers.N``
# with a ``norm`` and ONE ``mixer``, whose kind the config's
# ``hybrid_override_pattern`` gives. Ours <- theirs, ``{p}`` the layer's
# prefix; linear weights are [out, in] there and [in, out] here.
_NEMOTRON_MAPS = {
    "attn": {
        "wq": "{p}.mixer.q_proj.weight", "wk": "{p}.mixer.k_proj.weight",
        "wv": "{p}.mixer.v_proj.weight", "wo": "{p}.mixer.o_proj.weight",
    },
    "mlp": {
        "w_up": "{p}.mixer.up_proj.weight",
        "w_down": "{p}.mixer.down_proj.weight",
    },
    "moe": {
        "router": "{p}.mixer.gate.weight",
        "ws_up": "{p}.mixer.shared_experts.up_proj.weight",
        "ws_down": "{p}.mixer.shared_experts.down_proj.weight",
    },
    "ssm": {"w_out": "{p}.mixer.out_proj.weight"},
}


def _load_plan_params(cfg: ModelConfig, ckpt: _ShardedCheckpoint, dtype) -> dict:
    """NemotronHForCausalLM -> the stack-a-kind tree of
    ``transformer.init_params`` for a planned model: ``in_proj`` cut
    into its z | xBC | dt column ranges, the convolution's [C, 1, K]
    weight as [K, C], an expert's width zero-padded to the stored one,
    ``A_log`` / ``D`` / ``dt_bias`` / the router's bias kept float32."""
    from llm_consensus_tpu.models.transformer import PLAN_STACKS, _plan_layers

    np_dtype = jnp.dtype(dtype)

    def get(name: str) -> np.ndarray:
        if name not in ckpt:
            raise KeyError(f"checkpoint missing {name!r}")
        return ckpt.get(name)

    def linear(name: str) -> np.ndarray:
        return get(name).astype(np_dtype).T

    def padded(w: np.ndarray, axis: int) -> np.ndarray:
        pad = [(0, 0)] * w.ndim
        pad[axis] = (0, cfg.expert_d_ff_stored - w.shape[axis])
        return np.pad(w, pad)

    def layer(kind: str, p: str) -> dict:
        out = {"norm": get(f"{p}.norm.weight").astype(np_dtype)}
        for ours, theirs in _NEMOTRON_MAPS[kind].items():
            out[ours] = linear(theirs.format(p=p))
        if kind == "moe":
            out["router_bias"] = get(
                f"{p}.mixer.gate.e_score_correction_bias"
            ).astype(np.float32)
            experts = [f"{p}.mixer.experts.{e}" for e in range(cfg.n_experts)]
            out["w_up"] = np.stack(
                [padded(linear(f"{e}.up_proj.weight"), 1) for e in experts]
            )
            out["w_down"] = np.stack(
                [padded(linear(f"{e}.down_proj.weight"), 0) for e in experts]
            )
        elif kind == "ssm":
            inner, cx = cfg.ssm_inner, cfg.ssm_conv_dim
            w_in = linear(f"{p}.mixer.in_proj.weight")  # [D, z | xBC | dt]
            if w_in.shape[1] != inner + cx + cfg.ssm_heads:
                raise ValueError(
                    f"{p}.mixer.in_proj.weight has {w_in.shape[1]} columns, "
                    f"the config gives {inner} + {cx} + {cfg.ssm_heads}"
                )
            out["w_in_z"] = w_in[:, :inner]
            out["w_in_xbc"] = w_in[:, inner : inner + cx]
            out["w_in_dt"] = w_in[:, inner + cx :]
            out["conv_w"] = (
                get(f"{p}.mixer.conv1d.weight")[:, 0, :].T.astype(np_dtype)
            )
            out["conv_b"] = get(f"{p}.mixer.conv1d.bias").astype(np_dtype)
            out["gate_norm"] = get(f"{p}.mixer.norm.weight").astype(np_dtype)
            for ours, theirs in (
                ("dt_bias", "dt_bias"), ("a_log", "A_log"), ("d_skip", "D"),
            ):
                out[ours] = get(f"{p}.mixer.{theirs}").astype(np.float32)
        return out

    stacks: dict = {}
    for n, (kind, _) in enumerate(_plan_layers(cfg)):
        stacks.setdefault(kind, []).append(layer(kind, f"backbone.layers.{n}"))
    params = {
        PLAN_STACKS[kind]: {
            name: np.stack([one[name] for one in layers])
            for name in layers[0]
        }
        for kind, layers in stacks.items()
    }
    params["embed"] = get("backbone.embeddings.weight").astype(np_dtype)
    params["norm_f"] = get("backbone.norm_f.weight").astype(np_dtype)
    params["lm_head"] = linear("lm_head.weight")
    return jax.tree_util.tree_map(jnp.asarray, params)


def _load_hf_params(cfg: ModelConfig, ckpt: _ShardedCheckpoint, dtype) -> dict:
    if cfg.layer_plan:
        return _load_plan_params(cfg, ckpt, dtype)
    if cfg.is_mla:
        return _load_mla_params(cfg, ckpt, dtype)
    np_dtype = jnp.dtype(dtype)

    def stack_layers(ours: str, template: str) -> np.ndarray:
        per_layer = []
        for i in range(cfg.n_layers):
            name = template.format(i=i)
            if name not in ckpt:
                raise KeyError(
                    f"checkpoint missing {name!r} (for param {ours!r})"
                )
            per_layer.append(_fetch(ckpt, name, ours, np_dtype))
        return np.stack(per_layer)

    def stack_experts(ours: str, template: str) -> np.ndarray:
        per_layer = []
        for i in range(cfg.n_layers):
            per_layer.append(
                np.stack(
                    [
                        _fetch(
                            ckpt, template.format(i=i, e=e), ours, np_dtype
                        )
                        for e in range(cfg.n_experts)
                    ]
                )
            )
        return np.stack(per_layer)

    blocks: dict = {}
    for ours in ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo"):
        blocks[ours] = stack_layers(ours, _DENSE_MAP[ours])
    if cfg.qkv_bias:
        for ours in ("bq", "bk", "bv"):
            blocks[ours] = stack_layers(ours, _DENSE_MAP[ours])
    if cfg.is_moe:
        blocks["router"] = stack_layers("router", _MOE_MAP["router"])
        for ours in ("w_gate", "w_up", "w_down"):
            blocks[ours] = stack_experts(ours, _MOE_MAP[ours])
    else:
        for ours in ("w_gate", "w_up", "w_down"):
            blocks[ours] = stack_layers(ours, _DENSE_MAP[ours])

    params: dict = {
        "embed": ckpt.get("model.embed_tokens.weight").astype(np_dtype),
        "blocks": blocks,
        "norm_f": ckpt.get("model.norm.weight").astype(np_dtype),
    }
    if "lm_head.weight" in ckpt:
        if cfg.tie_embeddings:
            raise ValueError(
                "checkpoint has lm_head.weight but cfg.tie_embeddings=True"
            )
        params["lm_head"] = _fetch(
            ckpt, "lm_head.weight", "lm_head", np_dtype
        )
    elif not cfg.tie_embeddings:
        raise ValueError(
            "checkpoint has no lm_head.weight; set cfg.tie_embeddings=True"
        )

    _validate_shapes(cfg, params)
    return jax.tree_util.tree_map(jnp.asarray, params)


def _validate_shapes(cfg: ModelConfig, params: dict) -> None:
    L, D = cfg.n_layers, cfg.d_model
    Dh = cfg.head_dim
    expect = {
        ("blocks", "wq"): (L, D, cfg.n_heads * Dh),
        ("blocks", "wk"): (L, D, cfg.n_kv_heads * Dh),
        ("blocks", "wo"): (L, cfg.n_heads * Dh, D),
        ("embed",): (cfg.vocab_size, D),
    }
    for keys, shape in expect.items():
        node = params
        for k in keys:
            node = node[k]
        if tuple(node.shape) != shape:
            raise ValueError(
                f"{'.'.join(keys)}: checkpoint shape {tuple(node.shape)} != "
                f"config {shape} — wrong ModelConfig for this checkpoint?"
            )


def config_from_hf(path: str | Path, name: str = "hf") -> ModelConfig:
    """Derive a ModelConfig from an HF ``config.json``.

    Raises on config features we would otherwise silently mis-compute
    (unknown rope_scaling types).
    """
    hf = json.loads((Path(path) / "config.json").read_text())
    arch = (hf.get("architectures") or [""])[0]
    if "DeepseekV2" in arch or hf.get("model_type") == "deepseek_v2":
        return _deepseek_v2_config(hf, name)
    if "NemotronH" in arch or hf.get("model_type") == "nemotron_h":
        return _nemotron_h_config(hf, name)
    is_moe = "Mixtral" in arch or "num_local_experts" in hf

    rope_scaling = None
    rs = hf.get("rope_scaling")
    if rs:
        rs_type = rs.get("rope_type") or rs.get("type")
        if rs_type != "llama3":
            raise ValueError(
                f"unsupported rope_scaling type {rs_type!r} — only 'llama3' "
                "(Llama-3.1) frequency rescaling is implemented"
            )
        rope_scaling = RopeScaling(
            factor=float(rs["factor"]),
            low_freq_factor=float(rs["low_freq_factor"]),
            high_freq_factor=float(rs["high_freq_factor"]),
            original_max_position_embeddings=int(
                rs["original_max_position_embeddings"]
            ),
        )

    # Mistral: sliding_window set => windowed attention. Qwen2 ships a
    # sliding_window value but gates it off with use_sliding_window.
    sliding_window = int(hf.get("sliding_window") or 0)
    if "Qwen2" in arch and not hf.get("use_sliding_window", False):
        sliding_window = 0

    return ModelConfig(
        name=name,
        vocab_size=hf["vocab_size"],
        d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        d_ff=hf.get("moe_intermediate_size") or hf["intermediate_size"],
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rope_scaling=rope_scaling,
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        max_seq_len=int(hf.get("max_position_embeddings", 8192)),
        sliding_window=sliding_window,
        qkv_bias="Qwen2" in arch,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        n_experts=int(hf.get("num_local_experts", 0)) if is_moe else 0,
        n_experts_per_token=int(hf.get("num_experts_per_tok", 2)),
    )


def _nemotron_h_config(hf: dict, name: str) -> ModelConfig:
    """NemotronHForCausalLM's ``config.json`` -> ModelConfig. What the
    layer equations here do not cover raises: grouped routing, biases
    other than the convolution's, an activation other than relu²."""
    unsupported = {
        "n_group/topk_group": hf.get("n_group", 1) != 1
        or hf.get("topk_group", 1) != 1,
        "mlp_hidden_act": hf.get("mlp_hidden_act", "relu2") != "relu2",
        "bias": any(
            hf.get(k, False)
            for k in ("attention_bias", "mlp_bias", "mamba_proj_bias", "use_bias")
        ),
        "use_conv_bias": not hf.get("use_conv_bias", True),
        "sliding_window": bool(hf.get("sliding_window")),
    }
    bad = [k for k, v in unsupported.items() if v]
    if bad:
        raise ValueError(
            f"nemotron_h config uses {bad}, which the layers here do not "
            "implement (NVIDIA-Nemotron-3-Nano-30B-A3B's values are supported)"
        )
    n_experts = int(hf.get("n_routed_experts") or 0)
    return ModelConfig(
        name=name,
        vocab_size=hf["vocab_size"],
        d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["intermediate_size"],
        rms_norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
        max_seq_len=min(int(hf.get("max_position_embeddings", 8192)), 8192),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        layer_plan=hf["hybrid_override_pattern"][: hf["num_hidden_layers"]],
        attn_head_dim=int(hf["head_dim"]),
        # nemotron_h's attention applies no rotary embedding: the
        # config's rope_theta / partial_rotary_factor are unread there.
        positions="none",
        mlp_form="relu2",
        n_experts=n_experts,
        n_experts_per_token=int(hf.get("num_experts_per_tok", 2)),
        moe_d_ff=int(hf.get("moe_intermediate_size") or 0),
        n_shared_experts=int(hf.get("n_shared_experts") or 0),
        moe_shared_d_ff=int(hf.get("moe_shared_expert_intermediate_size") or 0),
        moe_router="sigmoid_topk",
        moe_renormalize=bool(hf.get("norm_topk_prob", True)),
        moe_routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
        moe_dropless=n_experts > 0,
        ssm_heads=int(hf["mamba_num_heads"]),
        ssm_head_dim=int(hf["mamba_head_dim"]),
        ssm_state=int(hf["ssm_state_size"]),
        ssm_groups=int(hf["n_groups"]),
        ssm_conv=int(hf["conv_kernel"]),
    )


def _deepseek_v2_config(hf: dict, name: str) -> ModelConfig:
    """DeepseekV2ForCausalLM's ``config.json`` -> ModelConfig. What the
    layer equations here do not cover raises: a query latent
    (``q_lora_rank``), grouped or non-softmax routing, expert layers
    other than every layer after the leading dense ones."""
    unsupported = {
        "q_lora_rank": hf.get("q_lora_rank") is not None,
        "scoring_func": hf.get("scoring_func", "softmax") != "softmax",
        "topk_method": hf.get("topk_method", "greedy") != "greedy"
        or hf.get("n_group", 1) not in (None, 1),
        "moe_layer_freq": hf.get("moe_layer_freq", 1) != 1,
    }
    bad = [k for k, v in unsupported.items() if v]
    if bad:
        raise ValueError(
            f"DeepseekV2 config uses {bad}, which the MLA/MoE layers here "
            "do not implement (DeepSeek-V2-Lite's values are supported)"
        )
    rope_scaling = None
    rs = hf.get("rope_scaling")
    if rs:
        if (rs.get("rope_type") or rs.get("type")) != "yarn":
            raise ValueError(f"unsupported rope_scaling {rs!r}: want 'yarn'")
        rope_scaling = YarnScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=int(
                rs["original_max_position_embeddings"]
            ),
            beta_fast=float(rs.get("beta_fast", 32)),
            beta_slow=float(rs.get("beta_slow", 1)),
            mscale=float(rs.get("mscale", 1.0)),
            mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
        )
    n_experts = int(hf.get("n_routed_experts") or 0)
    return ModelConfig(
        name=name,
        vocab_size=hf["vocab_size"],
        d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=1,
        d_ff=hf["intermediate_size"],
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rope_scaling=rope_scaling,
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        max_seq_len=int(hf.get("max_position_embeddings", 8192)),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        attention="mla",
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        n_experts=n_experts,
        n_experts_per_token=int(hf.get("num_experts_per_tok") or 2),
        n_dense_layers=int(hf.get("first_k_dense_replace", 0))
        if n_experts
        else 0,
        moe_d_ff=int(hf.get("moe_intermediate_size") or 0),
        n_shared_experts=int(hf.get("n_shared_experts") or 0),
        moe_router="softmax_topk",
        moe_renormalize=bool(hf.get("norm_topk_prob", False)),
        moe_routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
        moe_dropless=bool(n_experts),
    )
