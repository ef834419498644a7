"""Model configurations for the persona-panel model families.

The reference has no model code (its model is the remote Gemini API,
``src/main.rs:82-86``). The families here are the ones BASELINE.md's target
configs name: Llama-3-8B (north star), Mistral-7B and Qwen2-7B
(heterogeneous panel, config[3]), Mixtral-8x7B MoE (config[2]), plus small
test/bench presets. Those are one architecture family — pre-norm transformer,
GQA attention, RoPE, SwiGLU — differing in dims and two flags (qkv bias for
Qwen2, MoE for Mixtral), so one functional implementation serves all.
DeepSeek-V2-Lite (PR 28) is the first that is not: latent attention (MLA), a
leading dense layer before the expert layers, shared experts, a
softmax-then-top-k router and YaRN — each a field below, read by the same
functions. Nemotron-3-Nano (PR 32) is the first whose layers are not
"attention then MLP": a per-layer plan of ONE-mixer layers (Mamba-2
state-space, GQA attention without positions, ungated relu² experts
behind a sigmoid router), read by the same functions again.
SDAR-30B-A3B-Chat is the first that does not decode one token
after another: a Qwen3-MoE stack (per-head QK norm, 128 experts top-8)
generating by block diffusion — a block of ``block_length`` tokens
denoised in place under a block-causal attention rule.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace


@dataclass(frozen=True)
class RopeScaling:
    """Llama-3.1-style frequency rescaling (HF rope_scaling type
    'llama3'). Hashable so ModelConfig stays a valid jit static arg."""

    factor: float
    low_freq_factor: float
    high_freq_factor: float
    original_max_position_embeddings: int


@dataclass(frozen=True)
class YarnScaling:
    """YaRN frequency blending (HF rope_scaling type 'yarn', as
    DeepSeek-V2 applies it): rotary pairs whose wavelength outlasts the
    original context divide their frequency by ``factor``, short ones
    stay, a linear ramp between ``low`` and ``high`` (pair indices
    found from ``beta_fast``/``beta_slow`` rotations over the original
    context) blends the two. ``mscale_all_dim`` also scales the
    attention logits (:attr:`ModelConfig.attn_scale`)."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


# ``ModelConfig.layer_plan``'s characters and the kinds they name.
LAYER_KINDS = {"M": "ssm", "*": "attn", "E": "moe", "-": "mlp"}


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    rope_theta: float = 10000.0
    # Llama-3.1 rescaling (RopeScaling) or YaRN (YarnScaling).
    rope_scaling: RopeScaling | YarnScaling | None = None
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 8192
    # Sliding-window attention (Mistral): 0 = full causal.
    sliding_window: int = 0
    qkv_bias: bool = False  # Qwen2 uses bias on q/k/v projections
    tie_embeddings: bool = False
    # Attention kind. "gqa": per-head K/V pairs of ``d_model //
    # n_heads``. "mla" (DeepSeek-V2): one compressed latent of
    # ``kv_lora_rank`` values plus ONE rotary key of ``qk_rope_head_dim``
    # a token, shared by all heads; queries are ``qk_nope_head_dim``
    # unrotated + ``qk_rope_head_dim`` rotated dims a head, values
    # ``v_head_dim``. The cache holds the latent only and attention runs
    # in the absorbed form (models.transformer._mla_block).
    attention: str = "gqa"
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # MoE: 0 experts = dense MLP.
    n_experts: int = 0
    n_experts_per_token: int = 2
    # Layers [0, n_dense_layers) of an MoE model are dense SwiGLU of
    # width ``d_ff`` and live in their own stack (params["dense_blocks"]);
    # the expert layers' width is ``moe_d_ff`` (0 = ``d_ff``).
    n_dense_layers: int = 0
    moe_d_ff: int = 0
    # Shared experts every token takes beside its routed ones: one
    # SwiGLU of width ``n_shared_experts * expert width``.
    n_shared_experts: int = 0
    # Router: "topk_softmax" (Mixtral) takes the top k logits and
    # softmaxes those; "softmax_topk" (DeepSeek-V2) softmaxes all
    # experts in float32, takes the top k probabilities as they are
    # (``moe_renormalize`` divides them by their sum) and multiplies
    # them by ``moe_routed_scale``. "sigmoid_topk" (DeepSeek-V3's form):
    # sigmoid scores in float32; the top k of score + the layer's
    # ``router_bias`` are chosen, the weights are the chosen SCORES
    # (no bias), renormalised and scaled the same way.
    moe_router: str = "topk_softmax"
    moe_renormalize: bool = False
    moe_routed_scale: float = 1.0
    # Dropless expert layer (models.transformer._moe_dropless): every
    # token's k experts computed exactly through the grouped matmul,
    # which reads only the experts a step's tokens reach. The presets
    # that predate it keep the dense / capacity paths below.
    moe_dropless: bool = False
    # > 0 enables capacity-bounded GShard-style dispatch (compute only
    # routed tokens, capacity = ceil(T*k/E * factor)); 0 = dense
    # all-experts compute (exact, E/k x the FLOPs).
    moe_capacity_factor: float = 0.0
    # Token count at or below which an MoE layer takes the dense
    # all-experts path even when moe_capacity_factor > 0. At decode
    # shapes every expert's weights stream from HBM regardless of
    # routing (any batch of >= E tokens touches all E experts), so the
    # capacity dispatch saves no bandwidth there — it only adds the
    # [T, E, C] mask-build chain (top_k/cumsum/one_hot/scatter) to a
    # memory-bound step (measured 10x off the weight-read roofline on
    # v5e, PERF.md r5). Shapes are static under jit, so the switch is
    # trace-time Python with zero runtime cost; prefill/training token
    # counts exceed the threshold and keep the capacity path. 0 pins
    # the capacity path at every shape (tests / A-B benches). The two
    # paths differ numerically when capacity binds, so call sites that
    # promise cross-program identity pin one path for all their
    # programs (speculative_generate, prefill_chunked).
    moe_dense_decode_tokens: int = 256
    # Router auxiliary loss weights for MoE TRAINING (Switch-style
    # load-balance + router z-loss, models/transformer.moe_router_aux);
    # inference ignores them.
    moe_aux_loss_weight: float = 0.01
    moe_z_loss_weight: float = 1e-3
    # Fused Pallas kernels (ops/pallas) for attention + RMSNorm on the
    # hot path, or the jnp reference ops. None = not decided yet: the
    # engine/batcher fills it from the platform and its mesh
    # (ops.kernels.resolve_kernels); code that traces a config nobody
    # resolved (training, plain forward) takes the references.
    use_pallas: bool | None = None
    # Route full/prefill attention through ring attention
    # (parallel/ring.py) when a mesh with seq > 1 is passed to
    # forward/prefill — sequence-parallel long-context support.
    use_ring: bool = False
    # The per-layer plan (nemotron_h's ``hybrid_override_pattern``), one
    # character a layer, each layer ONE mixer ``x + mixer(norm(x))``:
    # ``M`` Mamba-2 state-space, ``*`` attention, ``E`` routed experts,
    # ``-`` dense MLP. Empty: every layer is attention then MLP (every
    # older preset), and ``n_layers`` counts those.
    layer_plan: str = ""
    # A GQA head's width where it is not ``d_model // n_heads``.
    attn_head_dim: int = 0
    # "rope", or "none": attention sees no position signal at all (the
    # state-space layers carry order).
    positions: str = "rope"
    # Feed-forward form, dense and expert alike: "swiglu" (gate, up,
    # down) or "relu2", ungated: ``W_down · relu(W_up x)²``.
    mlp_form: str = "swiglu"
    # Width of the shared expert where it is not ``n_shared_experts *
    # expert width``.
    moe_shared_d_ff: int = 0
    # Mamba-2: ``ssm_heads`` heads of ``ssm_head_dim`` (their product is
    # the inner width, not ``expand * d_model``), a state of
    # ``ssm_state`` values a head channel, B and C shared by the heads
    # of one of ``ssm_groups`` groups, a causal depthwise convolution of
    # ``ssm_conv`` taps over [x | B | C].
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    # Per-head RMSNorm of every query and key head over its
    # ``head_dim`` values, before the rotation (Qwen3-MoE's layer):
    # learned weights ``q_norm`` / ``k_norm`` [head_dim] a layer.
    qk_norm: bool = False
    # Generation: "autoregressive" (one token a row a step), or
    # "block_diffusion" (SDAR): a block of ``block_length`` positions
    # starts as the prompt's tail and ``mask_token_id``s, and is
    # denoised in place over ``denoising_steps`` forwards — each commits
    # the masked positions whose greedy probability passes
    # ``unmask_threshold``, or the most confident ones where fewer pass
    # than the step's share of the block — then written to the cache by
    # one more forward. Attention is block-causal: a query at i sees a
    # key at j iff j // block_length <= i // block_length.
    generation: str = "autoregressive"
    block_length: int = 1
    denoising_steps: int = 1
    unmask_threshold: float = 0.9
    mask_token_id: int = -1

    def __post_init__(self):
        if self.layer_plan:
            bad = set(self.layer_plan) - set(LAYER_KINDS)
            if bad or len(self.layer_plan) != self.n_layers:
                raise ValueError(
                    f"{self.name}: layer_plan {self.layer_plan!r} must be "
                    f"{self.n_layers} characters of {''.join(LAYER_KINDS)}"
                )
        if self.is_diffusion and not (
            self.block_length > 1
            and not self.block_length & (self.block_length - 1)
            and not self.sliding_window
            and 1 <= self.denoising_steps <= self.block_length
            and 0 <= self.mask_token_id < self.vocab_size
        ):
            raise ValueError(
                f"{self.name}: block diffusion needs a block_length that "
                "is a power of two > 1, no sliding window, 1 <= "
                "denoising_steps <= block_length and a mask token id "
                "inside the vocabulary"
            )

    @property
    def is_diffusion(self) -> bool:
        return self.generation == "block_diffusion"

    @property
    def attn_block(self) -> int:
        """The attention rule's block: a query sees the keys of every
        block up to its own. 1 is the plain causal rule."""
        return self.block_length if self.is_diffusion else 1

    @property
    def is_mla(self) -> bool:
        return self.attention == "mla"

    @property
    def head_dim(self) -> int:
        """Width of one query head's key: what a head's dot product
        contracts over, and the rotary width of a GQA model."""
        if self.is_mla:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.attn_head_dim or self.d_model // self.n_heads

    def plan_kinds(self) -> tuple[str, ...]:
        """A planned model's layer kinds in order (``LAYER_KINDS``
        values); empty for the attention-then-MLP models."""
        return tuple(LAYER_KINDS[c] for c in self.layer_plan)

    def n_of(self, kind: str) -> int:
        return self.plan_kinds().count(kind)

    @property
    def n_attn_layers(self) -> int:
        """Layers that keep K/V pages: the page pool's layer axis."""
        return self.n_of("attn") if self.layer_plan else self.n_layers

    @property
    def n_ssm_layers(self) -> int:
        """Layers that keep recurrent state: the state pool's."""
        return self.n_of("ssm")

    @property
    def is_recurrent(self) -> bool:
        return self.n_ssm_layers > 0

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the convolution runs over: x, then B and C of
        every group."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def expert_d_ff_stored(self) -> int:
        """Expert width as a one-mixer expert layer stores it: rounded
        up to the grouped expert matmul's rule, whole 128-lane tiles
        (``moe_grouped_matmul_supported``: 1856 -> 1920), with zero
        columns of ``w_up`` and zero rows of ``w_down`` — exact for
        relu² and for SwiGLU (both map 0 to 0)."""
        return -(-self.expert_d_ff // 128) * 128

    @property
    def shared_d_ff(self) -> int:
        return self.moe_shared_d_ff or self.n_shared_experts * self.expert_d_ff

    @property
    def rope_dim(self) -> int:
        """Rotated dims of a head: all of a GQA head, the rotary part
        of an MLA head."""
        return self.qk_rope_head_dim if self.is_mla else self.head_dim

    @property
    def latent_dim(self) -> int:
        """What one token costs an MLA pool a layer: the compressed
        latent followed by the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_pool_dim(self) -> int:
        """Lane width of an MLA pool: ``latent_dim`` rounded up to whole
        128-lane tiles (576 -> 640). The TPU keeps a 576-wide minor
        axis in 640 lanes anyway, and for an array whose minor axis is
        no multiple of 128 XLA prefers another axis order than the
        kernel's, so every kernel call would copy the whole pool (1.6
        GB, seen in the compiled program); padded, the pool has the
        one layout both want. Pad lanes hold zeros."""
        return -(-self.latent_dim // 128) * 128

    @property
    def attn_scale(self) -> float:
        """Softmax scale: ``head_dim ** -0.5``, times YaRN's
        ``mscale_all_dim`` correction squared where the config has it."""
        scale = self.head_dim**-0.5
        ys = self.rope_scaling
        if isinstance(ys, YarnScaling) and ys.mscale_all_dim:
            m = 0.1 * ys.mscale_all_dim * math.log(ys.factor) + 1.0
            scale *= m * m
        return scale

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def n_moe_layers(self) -> int:
        if self.layer_plan:
            return self.n_of("moe")
        return self.n_layers - self.n_dense_layers if self.is_moe else 0

    def with_layers(self, n: int) -> "ModelConfig":
        """The first ``n`` layers of this model (leading dense layers
        count): ``serve --layers``, a pipeline's first stage."""
        if not 0 < n <= self.n_layers:
            raise ValueError(
                f"--layers {n}: {self.name} has {self.n_layers} layers"
            )
        if self.is_moe and self.n_dense_layers and n <= self.n_dense_layers:
            raise ValueError(
                f"--layers {n}: {self.name} has {self.n_dense_layers} leading "
                "dense layer(s); keep at least one expert layer"
            )
        if self.layer_plan:
            return self.with_(n_layers=n, layer_plan=self.layer_plan[:n])
        return self.with_(n_layers=n)

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)

    # -- MoE dispatch-path selection (single source of truth) ----------
    # _mlp picks its path with moe_dense_at; call sites that promise
    # cross-program numeric identity pin one path for all their
    # programs with the two helpers below.

    def moe_dense_at(self, n_tokens: int) -> bool:
        """True when an MoE layer at this per-program token count traces
        the dense all-experts path (capacity factor 0, or at/below the
        trace-time dense-fallback threshold)."""
        return (
            self.moe_capacity_factor == 0
            or n_tokens <= self.moe_dense_decode_tokens
        )

    def with_moe_capacity_pinned(self) -> "ModelConfig":
        """Capacity dispatch at EVERY program shape (threshold 0)."""
        return self.with_(moe_dense_decode_tokens=0)

    def with_moe_dense_up_to(self, n_tokens: int) -> "ModelConfig":
        """Dense path for every program of <= n_tokens tokens (raises
        the threshold; never lowers it)."""
        return self.with_(
            moe_dense_decode_tokens=max(
                self.moe_dense_decode_tokens, n_tokens
            )
        )

    def moe_pin_for(
        self, ref_tokens: int, dense_up_to: int
    ) -> "ModelConfig":
        """Pin the dispatch path for a FAMILY of programs to the choice
        a reference program of ``ref_tokens`` tokens makes: dense for
        every program up to ``dense_up_to`` tokens when the reference
        side is dense, capacity at every shape otherwise. No-op for
        non-MoE / capacity-disabled configs.

        Pinning aligns the PATH only. When capacity genuinely binds,
        capacity dispatch remains approximate across program shapes
        (capacity C = ceil(T*k/E*factor) is per-program, so programs of
        different T can drop different tokens — GShard semantics);
        bitwise cross-program contracts hold on the dense side and at
        capacity factors generous enough that nothing drops."""
        if not (self.is_moe and self.moe_capacity_factor > 0):
            return self
        return (
            self.with_moe_dense_up_to(dense_up_to)
            if self.moe_dense_at(ref_tokens)
            else self.with_moe_capacity_pinned()
        )


PRESETS: dict[str, ModelConfig] = {
    # North-star flagship (BASELINE.json).
    "llama3-8b": ModelConfig(
        name="llama3-8b",
        vocab_size=128256,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        rope_theta=500000.0,
        max_seq_len=8192,
    ),
    "mistral-7b": ModelConfig(
        name="mistral-7b",
        vocab_size=32000,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        rope_theta=10000.0,
        max_seq_len=8192,
        sliding_window=4096,  # Mistral-7B-v0.1 windowed attention
    ),
    "qwen2-7b": ModelConfig(
        name="qwen2-7b",
        vocab_size=152064,
        d_model=3584,
        n_layers=28,
        n_heads=28,
        n_kv_heads=4,
        d_ff=18944,
        rope_theta=1000000.0,
        qkv_bias=True,
        max_seq_len=8192,
    ),
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        rope_theta=1000000.0,
        n_experts=8,
        n_experts_per_token=2,
        # Capacity-bounded dispatch by default: the dense all-experts
        # path would spend E/k = 4x the needed FLOPs at this scale.
        moe_capacity_factor=1.25,
        max_seq_len=8192,
    ),
    # ~100M draft model sharing llama-1b's vocab: a speculative-
    # decoding draft for it (same tokenizer/vocab is the only hard
    # requirement for speculation).
    "llama-draft-100m": ModelConfig(
        name="llama-draft-100m",
        vocab_size=32000,
        d_model=768,
        n_layers=8,
        n_heads=12,
        n_kv_heads=4,
        d_ff=2048,
        rope_theta=10000.0,
        max_seq_len=4096,
    ),
    # ~1.1B dense config for single-chip benchmarking (fits v5e HBM in bf16
    # with a large candidate batch).
    "llama-1b": ModelConfig(
        name="llama-1b",
        vocab_size=32000,
        d_model=2048,
        n_layers=16,
        n_heads=16,
        n_kv_heads=8,
        d_ff=5632,
        rope_theta=10000.0,
        max_seq_len=4096,
    ),
    # ~14M byte-level model for the end-to-end accuracy loop
    # (examples/train_arith_em.py): small enough to train to high EM on
    # the synthetic arithmetic task in minutes on one chip, big enough
    # to actually learn two-step chain-of-thought arithmetic.
    "arith-14m": ModelConfig(
        name="arith-14m",
        vocab_size=384,
        d_model=384,
        n_layers=6,
        n_heads=6,
        n_kv_heads=6,
        d_ff=1536,
        max_seq_len=512,
    ),
    # ~25M byte-level model (6 x (4*512^2 + 3*512*2048) = 25.2M
    # non-embedding) for the MULTI-STEP accuracy loop (eval/arith2.py:
    # 2-4 chained ops, 6 narrative frames, distractor quantities).
    # Bigger than arith-14m because the task is genuinely harder, and
    # max_seq_len 768 because multi-step prompts+CoT reach ~650 bytes
    # (arith-14m's 512 truncates them).
    "arith-25m": ModelConfig(
        name="arith-25m",
        vocab_size=384,
        d_model=512,
        n_layers=6,
        n_heads=8,
        n_kv_heads=8,
        d_ff=2048,
        max_seq_len=768,
    ),
    # ~5.4M model with arith2's 768 context: the draft-scale sibling of
    # arith-25m (speculative decoding on the multi-step task needs a
    # draft whose context fits the ~650-byte prompts+CoT — arith-3m's
    # is too short). Measured 22 s/step on the 1-core host: NOT a CPU
    # training fallback; train it on chip (~1-2 min).
    "arith-6m": ModelConfig(
        name="arith-6m",
        vocab_size=384,
        d_model=256,
        n_layers=5,
        n_heads=4,
        n_kv_heads=4,
        d_ff=1024,
        max_seq_len=768,
    ),
    # ~0.94B-total-param MoE sized to run on ONE chip (VERDICT r4 item
    # 5: no MoE had ever touched real silicon — Mixtral-8x7B needs an
    # expert>=4 mesh, PERF.md). 4 experts top-2, Mixtral-style routing
    # and capacity bound; bf16 weights ~1.9 GiB, int8 ~0.95 GiB, so
    # decode at N=64 fits v5e HBM with room for the KV cache. Exercises
    # _moe_dispatch + the capacity-bounded path under REAL sampling.
    "moe-1b-4e": ModelConfig(
        name="moe-1b-4e",
        vocab_size=32000,
        d_model=1024,
        n_layers=16,
        n_heads=16,
        n_kv_heads=8,
        d_ff=4096,
        rope_theta=10000.0,
        n_experts=4,
        n_experts_per_token=2,
        moe_capacity_factor=1.25,
        max_seq_len=4096,
    ),
    # ~2.5M draft for arith-14m: trained on the same corpus it gives a
    # REAL speculative-decoding acceptance rate (examples/
    # spec_arith_demo.py), between a self-draft's ceiling and a
    # random-weight draft's floor.
    "arith-3m": ModelConfig(
        name="arith-3m",
        vocab_size=384,
        d_model=192,
        n_layers=4,
        n_heads=4,
        n_kv_heads=4,
        d_ff=768,
        max_seq_len=512,
    ),
    # Tiny configs for tests (CPU-simulated meshes). vocab 384 >= the
    # ByteTokenizer's 259 ids so end-to-end text tests can run on them.
    "test-tiny": ModelConfig(
        name="test-tiny",
        vocab_size=384,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=128,
    ),
    # Draft-sized sibling of test-tiny (same vocab — the one hard
    # requirement for speculation): the continuous batcher's
    # draft/verify tests run this as the cheap proposal model.
    "test-tiny-draft": ModelConfig(
        name="test-tiny-draft",
        vocab_size=384,
        d_model=32,
        n_layers=1,
        n_heads=2,
        n_kv_heads=1,
        d_ff=64,
        max_seq_len=128,
    ),
    # DeepSeek-V2-Lite at its published sizes
    # (huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json): latent
    # attention, one leading dense layer, 64 routed experts top-6 plus
    # 2 shared, YaRN over the 64 rotary dims. max_seq_len is the repo's
    # cap, not the published 163840.
    "deepseek-v2-lite": ModelConfig(
        name="deepseek-v2-lite",
        vocab_size=102400,
        d_model=2048,
        n_layers=27,
        n_heads=16,
        n_kv_heads=1,
        d_ff=10944,
        rope_theta=10000.0,
        rope_scaling=YarnScaling(
            factor=40.0,
            original_max_position_embeddings=4096,
            beta_fast=32.0,
            beta_slow=1.0,
            mscale=0.707,
            mscale_all_dim=0.707,
        ),
        rms_norm_eps=1e-6,
        max_seq_len=8192,
        attention="mla",
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        n_experts=64,
        n_experts_per_token=6,
        n_dense_layers=1,
        moe_d_ff=1408,
        n_shared_experts=2,
        moe_router="softmax_topk",
        moe_dropless=True,
    ),
    # The same shape at CPU-test size: 3 layers of which 1 dense, 8
    # experts top-3 plus 2 shared, latent 32 + rotary 8.
    "test-tiny-mla": ModelConfig(
        name="test-tiny-mla",
        vocab_size=384,
        d_model=64,
        n_layers=3,
        n_heads=4,
        n_kv_heads=1,
        d_ff=128,
        rope_scaling=YarnScaling(
            factor=40.0,
            original_max_position_embeddings=32,
            mscale=0.707,
            mscale_all_dim=0.707,
        ),
        rms_norm_eps=1e-6,
        max_seq_len=256,
        attention="mla",
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        n_experts=8,
        n_experts_per_token=3,
        n_dense_layers=1,
        moe_d_ff=32,
        n_shared_experts=2,
        moe_router="softmax_topk",
        moe_dropless=True,
    ),
    # NVIDIA-Nemotron-3-Nano-30B-A3B at its published sizes
    # (huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
    # config.json, model_type nemotron_h): 52 one-mixer layers — 23
    # Mamba-2, 23 expert (128 ungated relu² experts of 1856, top-6 by a
    # sigmoid router with a choice-only bias, one shared expert of
    # 3712), 6 GQA attention layers of 32 / 2 heads of 128 with no
    # position signal. max_seq_len is the repo's cap, not 262144.
    "nemotron-3-nano-30b-a3b": ModelConfig(
        name="nemotron-3-nano-30b-a3b",
        vocab_size=131072,
        d_model=2688,
        n_layers=52,
        n_heads=32,
        n_kv_heads=2,
        d_ff=1856,
        rms_norm_eps=1e-5,
        max_seq_len=8192,
        layer_plan="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        attn_head_dim=128,
        positions="none",
        mlp_form="relu2",
        n_experts=128,
        n_experts_per_token=6,
        moe_d_ff=1856,
        n_shared_experts=1,
        moe_shared_d_ff=3712,
        moe_router="sigmoid_topk",
        moe_renormalize=True,
        moe_routed_scale=2.5,
        moe_dropless=True,
        ssm_heads=64,
        ssm_head_dim=64,
        ssm_state=128,
        ssm_groups=8,
        ssm_conv=4,
    ),
    # The same shapes at CPU-test size: the plan MEM*EME, 4 experts
    # top-2 of a width (40) that is no multiple of any tile, 2 groups.
    "test-tiny-nemotron": ModelConfig(
        name="test-tiny-nemotron",
        vocab_size=384,
        d_model=64,
        n_layers=7,
        n_heads=4,
        n_kv_heads=2,
        d_ff=40,
        rms_norm_eps=1e-5,
        max_seq_len=256,
        layer_plan="MEM*EME",
        attn_head_dim=32,
        positions="none",
        mlp_form="relu2",
        n_experts=4,
        n_experts_per_token=2,
        moe_d_ff=40,
        n_shared_experts=1,
        moe_shared_d_ff=80,
        moe_router="sigmoid_topk",
        moe_renormalize=True,
        moe_routed_scale=2.5,
        moe_dropless=True,
        ssm_heads=4,
        ssm_head_dim=16,
        ssm_state=16,
        ssm_groups=2,
        ssm_conv=4,
    ),
    # SDAR-30B-A3B-Chat at its published sizes
    # (huggingface.co/JetLM/SDAR-30B-A3B-Chat config.json, model_type
    # sdar_moe, Qwen3-MoE's layer): 48 layers alike, 32 query heads and
    # 4 KV heads of 128 (queries 4096 wide over a 2048 stream), per-head
    # QK RMSNorm, 128 SwiGLU experts of 768, top-8 of a float32 softmax
    # renormalised, no shared expert. Every layer is an expert layer,
    # so the published dense intermediate_size (6144) is read by
    # nothing; d_ff is the experts' width. Block diffusion with SDAR's
    # released generation defaults (the config publishes none): blocks
    # of 4, 4 denoising steps, threshold 0.9; the mask id is one the
    # byte tokenizer never emits. max_seq_len is the repo's cap.
    "sdar-30b-a3b-chat": ModelConfig(
        name="sdar-30b-a3b-chat",
        vocab_size=151936,
        d_model=2048,
        n_layers=48,
        n_heads=32,
        n_kv_heads=4,
        d_ff=768,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        max_seq_len=8192,
        attn_head_dim=128,
        qk_norm=True,
        n_experts=128,
        n_experts_per_token=8,
        moe_d_ff=768,
        moe_router="softmax_topk",
        moe_renormalize=True,
        moe_dropless=True,
        generation="block_diffusion",
        block_length=4,
        denoising_steps=4,
        unmask_threshold=0.9,
        mask_token_id=151669,
    ),
    # The same shapes at CPU-test size: 2 layers, 8 experts top-2 of
    # 32, heads of 16 over a 64 stream (queries 128 wide), blocks of 4.
    "test-tiny-sdar": ModelConfig(
        name="test-tiny-sdar",
        vocab_size=384,
        d_model=64,
        n_layers=2,
        n_heads=8,
        n_kv_heads=2,
        d_ff=32,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        max_seq_len=256,
        attn_head_dim=16,
        qk_norm=True,
        n_experts=8,
        n_experts_per_token=2,
        moe_d_ff=32,
        moe_router="softmax_topk",
        moe_renormalize=True,
        moe_dropless=True,
        generation="block_diffusion",
        block_length=4,
        denoising_steps=4,
        unmask_threshold=0.9,
        mask_token_id=383,
    ),
    "test-tiny-moe": ModelConfig(
        name="test-tiny-moe",
        vocab_size=384,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        n_experts=4,
        n_experts_per_token=2,
        max_seq_len=128,
    ),
}


def get_config(name: str) -> ModelConfig:
    try:
        return PRESETS[name]
    except KeyError:
        raise KeyError(
            f"unknown model preset {name!r}; available: {sorted(PRESETS)}"
        ) from None
