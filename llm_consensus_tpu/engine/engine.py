"""InferenceEngine: text-in/text-out over the compiled generate loop.

The host-side runtime around :func:`llm_consensus_tpu.engine.generate`:
tokenization, right-padding, shape bucketing (so repeat calls hit the jit
cache instead of recompiling), PRNG key management, and detokenization.
This object is what :class:`llm_consensus_tpu.backends.local.LocalBackend`
exposes through the ``Backend`` seam — i.e. it stands exactly where the
reference's ``call_gemini`` stood (``src/main.rs:82-86``), but batched.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from llm_consensus_tpu.engine.generate import GenerateOutput, generate
from llm_consensus_tpu.engine.sampler import SamplerConfig
from llm_consensus_tpu.engine.tokenizer import ByteTokenizer, Tokenizer
from llm_consensus_tpu.models.configs import ModelConfig

log = logging.getLogger(__name__)

# Jitted prefix-prefill entry points (engine.prefix_cache misses). Module
# level so repeat misses at the same shapes hit the jit cache.
from llm_consensus_tpu.models.transformer import (  # noqa: E402
    prefill as _prefill_raw,
    prefill_chunked as _prefill_chunked_raw,
)

_jit_prefill = jax.jit(_prefill_raw, static_argnames=("cfg", "mesh"))
_jit_prefill_chunked = jax.jit(
    _prefill_chunked_raw, static_argnames=("cfg", "chunk")
)

from llm_consensus_tpu.engine.sampler import sample_token as _sample_raw  # noqa: E402

_jit_sample = jax.jit(_sample_raw, static_argnames=("config",))


def _next_bucket(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _kv_cache_bytes(
    cfg: ModelConfig,
    batch: int,
    cache_len: int,
    quant: bool,
    slack: int = 0,
    shared_len: int = 0,
) -> int:
    """KV-cache bytes for a generate call — the ONE copy of the cache
    capacity formula (memory_estimate and plan_memory both call it, so
    a cache-layout change cannot silently drift between them).

    ``shared_len``: prompt-prefix tokens STORED ONCE for the whole
    batch instead of once per row — the paged serving path's CoW page
    sharing (PR 2) dedups an N-fanout's common prompt in memory, so a
    post-PR-2 footprint prediction must count prefix + N*suffix, not
    N*(prefix + suffix). 0 (the default) models the dense per-row
    cache, which still duplicates.
    """
    shared_len = max(0, min(shared_len, cache_len))
    tokens = batch * (cache_len + slack) - (batch - 1) * shared_len
    slots = cfg.n_layers * tokens * cfg.n_kv_heads
    if quant:
        # int8 k+v + one f32 scale each per (slot, head)
        return slots * (2 * cfg.head_dim + 2 * 4)
    return slots * 2 * cfg.head_dim * 2  # bf16 k+v


def _logits_bytes(cfg: ModelConfig, batch: int) -> int:
    return batch * cfg.vocab_size * 4


@dataclass
class EngineConfig:
    max_new_tokens: int = 256
    # Prompt-length buckets (right-padded up; keeps the jit cache small).
    seq_buckets: tuple[int, ...] = (64, 128, 256, 512, 1024, 2048)
    # Batch-size buckets (padded up with dummy rows).
    batch_buckets: tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)
    sampler: SamplerConfig = field(default_factory=SamplerConfig)
    # Weight-only per-channel quantization at engine init (ops/quant.py):
    # "int8" halves weight HBM traffic on the decode hot loop, "int4"
    # (packed nibbles) halves it again at reduced precision.
    quant: str = "none"
    # int8 KV cache (models/cache.QuantKVCache): halves cache HBM
    # traffic per decode step (the dominant term at large N).
    kv_quant: bool = False
    # > 0: prefill prompts longer than this in fixed-size chunks
    # (models/transformer.prefill_chunked) — bounded activation memory
    # for long contexts. Composes with kv_quant: each chunk's K/V is
    # quantized at scatter time with the same per-(token, head) scale
    # granularity as the one-shot quant prefill, so the written cache is
    # bit-identical; only the chunk's attention reads go through the
    # dequantized slab (first-token logits differ from one-shot by int8
    # rounding only).
    prefill_chunk: int = 0
    # Host-side prefix cache (engine/prefix_cache.py): shared prompt
    # prefixes (few-shot headers, debate transcripts) are prefilled once
    # and their K/V reused across calls. Entry/byte budgets bound HBM.
    prefix_cache_entries: int = 8
    prefix_cache_bytes: int = 1 << 30
    # Decode-steps-per-host-check when a call carries MULTI-token stop
    # sequences: the device can only terminate single-token stops, so
    # the engine decodes in chunks this long and checks texts between
    # chunks — a '\n\n'-style stop ends decoding within one chunk
    # instead of running every row to EOS/max_new_tokens.
    stop_check_chunk: int = 16
    # Single-chip experiment: per-layer weight buffers + python-unrolled
    # layer loop (models.transformer.unstack_blocks). Measured SLOWER
    # than the stacked scan on v5e at bench shapes (the scan pipelines
    # weight streaming; 162 sequential pallas calls don't) — off by
    # default, kept for experimentation on other topologies.
    unroll_layers: bool = False


@dataclass
class EngineResult:
    text: str
    num_tokens: int
    logprob: float
    token_ids: list[int]


class InferenceEngine:
    """Batched local text generation on one model's weights.

    Pass ``mesh`` to run sharded (BASELINE.json north star): params are
    placed per :func:`llm_consensus_tpu.parallel.partitioning.param_pspecs`
    (TP over ``model``, EP over ``expert``, replicated over ``data``) and
    every batch shards its candidate axis over ``data`` — the N-way
    fan-out becomes one GSPMD program whose KV cache lives sharded in HBM.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        tokenizer: Tokenizer | None = None,
        engine_config: EngineConfig | None = None,
        mesh=None,
        draft: tuple[ModelConfig, dict] | None = None,
    ):
        # Kernel choice is observed here, once (ops.kernels): compiled
        # Pallas on a TPU with no multi-device mesh — these kernels have
        # no shard_map lowering — and the jnp references anywhere else.
        from llm_consensus_tpu.ops.kernels import resolve_kernels

        cfg = resolve_kernels(cfg, mesh)
        if draft is not None:
            draft = (resolve_kernels(draft[0], mesh), draft[1])
        self.cfg = cfg
        self.params = params
        self.tokenizer = tokenizer or ByteTokenizer()
        if self.tokenizer.vocab_size > cfg.vocab_size:
            raise ValueError(
                f"tokenizer vocab {self.tokenizer.vocab_size} exceeds model "
                f"vocab {cfg.vocab_size}"
            )
        self.config = engine_config or EngineConfig()
        if self.config.quant in ("int8", "int4"):
            from llm_consensus_tpu.ops.quant import quantize_params

            self.params = quantize_params(
                self.params, bits=8 if self.config.quant == "int8" else 4
            )
        elif self.config.quant != "none":
            raise ValueError(f"unknown quant mode {self.config.quant!r}")
        # Optional draft model for generate_texts_speculative: a
        # (config, params) pair sharing this model's tokenizer/vocab.
        self.draft = draft
        if mesh is None and self.config.unroll_layers:
            from llm_consensus_tpu.models.transformer import unstack_blocks

            self.params = unstack_blocks(self.params)
            if self.draft is not None:
                d_cfg, d_params = self.draft
                self.draft = (d_cfg, unstack_blocks(d_params))
        from llm_consensus_tpu.engine.prefix_cache import PrefixCache

        self.prefix_cache = PrefixCache(
            max_entries=self.config.prefix_cache_entries,
            max_bytes=self.config.prefix_cache_bytes,
        )
        # Lifetime counters; see stats().
        self._calls = {"generate": 0, "speculative": 0, "stream": 0, "score": 0}
        self._tokens_generated = 0
        from llm_consensus_tpu.utils.stops import VisibleIdFilter

        # Empty-id-aware tail window for incremental stop checks (memo
        # persists across generate calls).
        self._vis_filter = VisibleIdFilter(
            self.tokenizer, skip_ids=(self.tokenizer.eos_id,)
        )
        self.mesh = mesh
        self._data_sharding = None
        if mesh is not None:
            from dataclasses import replace

            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from llm_consensus_tpu.parallel.partitioning import shard_params

            self.params = shard_params(self.params, mesh)
            if self.draft is not None:
                # The draft rides the same mesh as the target (its own
                # tp sharding over `model`; batch over `data` inside
                # speculative_generate).
                d_cfg, d_params = self.draft
                self.draft = (d_cfg, shard_params(d_params, mesh))
            self._data_sharding = NamedSharding(mesh, P("data"))
            # Batch buckets must tile the data axis evenly.
            dp = int(mesh.shape.get("data", 1))
            if dp > 1:
                bb = tuple(
                    b for b in self.config.batch_buckets if b % dp == 0
                ) or (dp,)
                self.config = replace(self.config, batch_buckets=bb)

    # ------------------------------------------------------------------

    def _prepare(
        self, prompts: list[str], add_bos: bool = True, max_cap: int | None = None
    ) -> tuple[np.ndarray, np.ndarray, int]:
        tok = self.tokenizer
        # Left-truncate over-long prompts (keep the question tail); the cap
        # is the model context, not just the largest bucket.
        max_prompt = min(self.config.seq_buckets[-1], self.cfg.max_seq_len - 1)
        if max_cap is not None:
            max_prompt = min(max_prompt, max_cap)
        native = self._native_encode(prompts, max_prompt, add_bos=add_bos)
        if native is not None:
            enc_tokens, enc_lengths = native
        else:
            encoded = [
                tok.encode(p, add_bos=add_bos)[-max_prompt:] for p in prompts
            ]
            enc_lengths = np.array([len(ids) for ids in encoded], np.int32)
            enc_tokens = np.full((len(prompts), max_prompt), tok.pad_id, np.int32)
            for i, ids in enumerate(encoded):
                enc_tokens[i, : len(ids)] = ids
        longest = int(enc_lengths.max())
        s = _next_bucket(longest, self.config.seq_buckets)
        s = min(s, self.cfg.max_seq_len)
        b = _next_bucket(len(prompts), self.config.batch_buckets)
        tokens = np.full((b, s), tok.pad_id, np.int32)
        w = min(s, enc_tokens.shape[1])  # bucket may exceed the prompt cap
        tokens[: len(prompts), :w] = enc_tokens[:, :w]
        lengths = np.zeros((b,), np.int32)
        lengths[: len(prompts)] = enc_lengths
        # Dummy pad rows get length 1 so gather/clip stay in range.
        lengths[len(prompts) :] = 1
        return tokens, lengths, len(prompts)

    def _native_encode(self, prompts, max_prompt, add_bos: bool = True):
        """Batch-encode via the native runtime when the tokenizer is the
        byte tokenizer and libconsensus_rt is available (one C pass
        instead of a Python loop per request)."""
        if type(self.tokenizer) is not ByteTokenizer:
            return None
        try:
            from llm_consensus_tpu.native import available, batch_encode

            if not available():
                return None
            return batch_encode(
                prompts, max_len=max_prompt, add_bos=add_bos
            )
        except Exception:  # noqa: BLE001 - any native issue -> python path
            return None

    def generate_texts(
        self,
        prompts: list[str],
        temperatures: list[float] | None = None,
        seed: int = 0,
        max_new_tokens: int | None = None,
        sampler: SamplerConfig | None = None,
        prefix: str | None = None,
        stop: list[str] | None = None,
        _outer: bool = True,
    ) -> list[EngineResult]:
        """Generate one completion per prompt.

        One device program per chunk of ``batch_buckets[-1]`` prompts;
        most calls fit a single chunk. ``sampler`` overrides the engine's
        default top-k/top-p config for this call.

        ``prefix``: a shared prompt prefix — the effective prompt for row
        i is ``prefix + prompts[i]``. The prefix's K/V is prefilled once
        and cached on device (``self.prefix_cache``), so later calls with
        the same prefix skip its prefill entirely — including on sharded
        engines (batch over ``data``, B=1 prefix broadcast) and quant-KV
        engines (stored bf16 header quantized into the int8 cache on
        entry). Prefix and suffix are tokenized separately (the universal
        prefix-caching caveat: for merge-based tokenizers, split at a
        whitespace/newline boundary).

        ``stop``: stop sequences. Generation text is trimmed at the
        earliest occurrence of any stop string (the stop itself is
        removed); stops that tokenize to a single id also terminate the
        device decode loop early for their row, like EOS.
        """
        if not prompts:
            return []
        if _outer:
            self._calls["generate"] += 1
        chunk = self.config.batch_buckets[-1]
        if len(prompts) > chunk:
            out: list[EngineResult] = []
            for i in range(0, len(prompts), chunk):
                temps_i = (
                    temperatures[i : i + chunk]
                    if temperatures is not None
                    else None
                )
                out.extend(
                    self.generate_texts(
                        prompts[i : i + chunk],
                        temperatures=temps_i,
                        seed=seed + i,
                        max_new_tokens=max_new_tokens,
                        sampler=sampler,
                        prefix=prefix,
                        stop=stop,
                        _outer=False,
                    )
                )
            return out
        if prefix:
            # Mesh engines shard the continuation batch over `data`
            # (GSPMD broadcasts the B=1 prefix); kv_quant engines
            # quantize the stored bf16 prefix into the int8 cache on
            # entry — the prefix cache works on exactly the north-star
            # sharded/quantized configs that reuse headers the most.
            return self._generate_with_prefix(
                prompts, prefix, temperatures, seed, max_new_tokens,
                sampler, stop,
            )
        tokens, lengths, n_real = self._prepare(prompts)
        with self._span(
            "engine.generate",
            batch=tokens.shape[0],
            seq=tokens.shape[1],
            n_real=n_real,
        ):
            return self._generate_prepared(
                prompts, tokens, lengths, n_real, temperatures, seed,
                max_new_tokens, sampler, stop=stop,
            )

    # -- prefix-cached generation --------------------------------------

    def _cache_sharding(self, cache):
        """NamedSharding pytree for a KV cache on this engine's mesh:
        batch over ``data``, kv heads over ``model`` (the
        ``partitioning.cache_pspecs`` layout, covering both cache
        classes — the quant cache is head-major so ``model`` rides
        axis 2)."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from llm_consensus_tpu.models.cache import QuantKVCache

        mesh = self.mesh
        ln = NamedSharding(mesh, P("data"))
        if isinstance(cache, QuantKVCache):
            s5 = NamedSharding(mesh, P(None, "data", "model"))
            return QuantKVCache(
                k_q=s5, v_q=s5, k_scale=s5, v_scale=s5, length=ln
            )
        from llm_consensus_tpu.models.cache import KVCache

        s5 = NamedSharding(mesh, P(None, "data", None, "model"))
        return KVCache(k=s5, v=s5, length=ln)

    def _stop_ids(self, stop: list[str] | None) -> tuple[int, ...]:
        """Stops that tokenize to exactly one id terminate on device —
        the single-round path's share of the derived-stop machinery in
        :mod:`llm_consensus_tpu.utils.stops` (the multi-round batcher's
        conservative screen lives next to it)."""
        if not stop:
            return ()
        from llm_consensus_tpu.utils.stops import single_token_stop_ids

        return single_token_stop_ids(self.tokenizer, stop)

    @staticmethod
    def _trim_stops(results: list[EngineResult], stop: list[str] | None):
        """Cut each text at the earliest stop occurrence (stop removed).

        ``num_tokens``/``logprob`` keep the device-loop accounting here;
        the chunked multi-token-stop path follows up with
        :meth:`_exact_stop_accounting` so its reported counts match the
        device path's stop-token-inclusive accounting exactly.
        """
        if not stop:
            return results
        from llm_consensus_tpu.utils.stops import earliest_stop_cut

        for r in results:
            cut = earliest_stop_cut(r.text, stop)
            if cut >= 0:
                r.text = r.text[:cut]
        return results

    def _prefix_kv(self, ids: list[int]):
        """(k, v) for the prefilled prefix token ids (cached).

        The stored buffers are right-padded to the pow2 bucket of the
        true length (bounds distinct compiled programs at log2(ctx) and
        makes repeat cache hits zero-copy); pad-slot garbage is never
        attended — ``generate_from_prefix`` masks by the traced true
        length.
        """
        from llm_consensus_tpu.models.cache import KVCache

        max_prefix = self.cfg.max_seq_len - 2  # room for >=1 suffix token
        key = tuple(ids)
        p = len(ids)
        hit = self.prefix_cache.get(key)
        if hit is not None:
            return hit
        pb = min(1 << max(p - 1, 0).bit_length(), max_prefix)
        cache = KVCache.create(self.cfg, 1, pb)
        tokens = jnp.asarray(
            [ids + [self.tokenizer.pad_id] * (pb - p)], jnp.int32
        )
        lengths = jnp.asarray([p], jnp.int32)
        if self.config.prefill_chunk and pb > self.config.prefill_chunk:
            _, cache = _jit_prefill_chunked(
                self.cfg, self.params, tokens, lengths, cache,
                chunk=self.config.prefill_chunk,
            )
        else:
            _, cache = _jit_prefill(
                self.cfg, self.params, tokens, lengths, cache
            )
        entry = (cache.k, cache.v)
        self.prefix_cache.put(key, *entry)
        return entry

    def _generate_with_prefix(
        self, prompts, prefix, temperatures, seed, max_new_tokens, sampler,
        stop,
    ) -> list[EngineResult]:
        from llm_consensus_tpu.engine.generate import generate_from_prefix

        # One encode pass for everything: prefix ids feed both the fit
        # check and the prefix cache; suffix encodings feed both the fit
        # check and the batch (native byte-tokenizer batch path when
        # available). Suffixes that cannot sit whole after the prefix
        # (or that exceed the configured chunked-prefill bound) take the
        # plain concatenated path instead: it left-truncates keeping the
        # tail of prefix+question and honors prefill_chunk — silently
        # crushing the question to fit a long header would be worse than
        # losing the cache reuse.
        ctx = self.cfg.max_seq_len
        prefix_ids = self.tokenizer.encode(prefix)[-(ctx - 2) :]
        p = len(prefix_ids)

        def _fallback():
            log.debug("prefix cache bypassed (suffix does not fit)")
            return self.generate_texts(
                [prefix + q for q in prompts],
                temperatures=temperatures,
                seed=seed,
                max_new_tokens=max_new_tokens,
                sampler=sampler,
                stop=stop,
                _outer=False,
            )

        native = self._native_encode(prompts, ctx, add_bos=False)
        if native is not None:
            enc_tokens, enc_lengths = native
            suf = None
        else:
            suf = [self.tokenizer.encode(q, add_bos=False)[:ctx] for q in prompts]
            enc_lengths = np.array([len(x) for x in suf], np.int32)
        longest = int(enc_lengths.max()) if len(prompts) else 0
        if min(int(enc_lengths.min()), longest) < 1:
            return _fallback()  # an empty suffix: prefix alone, plain path
        if p + longest + 1 > ctx:
            return _fallback()
        s = min(_next_bucket(longest, self.config.seq_buckets), ctx - p - 1)
        s = max(s, longest)
        if self.config.prefill_chunk and s > self.config.prefill_chunk:
            return _fallback()  # suffix chunk would unbound prefill memory
        pk, pv = self._prefix_kv(prefix_ids)
        b = _next_bucket(len(prompts), self.config.batch_buckets)
        tokens = np.full((b, s), self.tokenizer.pad_id, np.int32)
        if suf is None:
            w = min(s, enc_tokens.shape[1])
            tokens[: len(prompts), :w] = enc_tokens[:, :w]
        else:
            for i, ids in enumerate(suf):
                tokens[i, : len(ids)] = ids
        lengths = np.ones((b,), np.int32)  # dummy rows: length 1
        lengths[: len(prompts)] = enc_lengths
        n_real = len(prompts)
        # The stored prefix is padded to the pow2 bucket of its true
        # length (zero-copy on hit); the true length rides as a traced
        # scalar, and the token budget below is charged at the TRUE
        # prefix length — only the suffix term carries bucket slack,
        # the same conservatism as the plain path.
        pb = pk.shape[2]
        if pb + s > ctx:
            pb = ctx - s
            if pb < p:
                return _fallback()  # bucket rounding left no room
            pk, pv = pk[:, :, :pb], pv[:, :, :pb]
        temps = np.zeros((b,), np.float32)
        if temperatures is not None:
            temps[:n_real] = np.asarray(temperatures, np.float32)
        mnt = max_new_tokens or self.config.max_new_tokens
        mnt = max(1, min(mnt, ctx - p - s))
        # Identical suffixes (self-consistency fan-out under a cached
        # header): chunk the suffix once at B=1 and broadcast.
        shared = n_real == b and len(set(prompts)) == 1 and b > 1
        # MoE dispatch-path alignment: resolve dense-vs-capacity for the
        # suffix chunk from the count the plain CONCATENATED path would
        # trace — batch x seq-bucket of the true concat length (B=1 when
        # its shared prefill collapses the batch, mirrored by `shared`
        # here). The prefix KV bucket width pb plays no part: it can
        # overshoot moe_dense_decode_tokens for a prompt whose concat
        # bucket sits under it (the round-5 divergence). Rides as a
        # static BOOL so the compiled-program count stays bounded by the
        # buckets. Only capacity-routed MoE configs pass it; everything
        # else keeps the jit key untouched with None. See
        # _prefix_prefill_impl.
        moe_dense = None
        if self.cfg.is_moe and self.cfg.moe_capacity_factor > 0:
            s_plain = min(
                _next_bucket(p + longest, self.config.seq_buckets),
                self.cfg.max_seq_len,
            )
            moe_dense = self.cfg.moe_dense_at((1 if shared else b) * s_plain)
        tokens_j = jnp.asarray(tokens)
        lengths_j = jnp.asarray(lengths)
        temps_j = jnp.asarray(temps)
        if self._data_sharding is not None:
            tokens_j = jax.device_put(tokens_j, self._data_sharding)
            lengths_j = jax.device_put(lengths_j, self._data_sharding)
            temps_j = jax.device_put(temps_j, self._data_sharding)
        multi_stop = stop and any(
            len(self.tokenizer.encode(x, add_bos=False)) > 1 for x in stop
        )
        if multi_stop:
            # Prefix-cached generation with multi-token stops rides the
            # same chunked host-checked decode as the plain path: the
            # header reuse and the early exit compose instead of the
            # prefix workload silently decoding to EOS/max_new_tokens.
            from llm_consensus_tpu.engine.generate import prefill_from_prefix

            with self._span(
                "engine.generate_prefix_chunked_stops",
                batch=b,
                prefix=p,
                seq=s,
                n_real=n_real,
            ):
                logits, cache = prefill_from_prefix(
                    self.cfg,
                    self.params,
                    pk,
                    pv,
                    jnp.asarray(p, jnp.int32),
                    tokens_j,
                    lengths_j,
                    cache_len=pb + s + mnt,
                    shared_suffix=shared,
                    kv_quant=self.config.kv_quant,
                    moe_suffix_dense=moe_dense,
                )
                return self._chunked_stop_decode(
                    logits, cache, temps_j, n_real, seed, mnt, sampler,
                    stop,
                )
        with self._span(
            "engine.generate_prefix",
            batch=b,
            prefix=p,
            seq=s,
            n_real=n_real,
        ):
            out = generate_from_prefix(
                self.cfg,
                self.params,
                pk,
                pv,
                jnp.asarray(p, jnp.int32),
                tokens_j,
                lengths_j,
                jax.random.PRNGKey(seed),
                temps_j,
                max_new_tokens=mnt,
                sampler=sampler if sampler is not None else self.config.sampler,
                eos_id=self.tokenizer.eos_id,
                pad_id=self.tokenizer.pad_id,
                stop_ids=self._stop_ids(stop),
                shared_suffix=shared,
                kv_quant=self.config.kv_quant,
                moe_suffix_dense=moe_dense,
            )
        return self._trim_stops(self._collect(out, n_real), stop)

    def memory_estimate(
        self,
        n_candidates: int = 1,
        prompt_len: int = 128,
        new_tokens: int | None = None,
        hbm_bytes: int | None = None,
        shared_prefix_len: int = 0,
    ) -> dict:
        """HBM budget estimate for a generate call at the given shapes.

        Returns PER-CHIP bytes for resident params (target + any draft
        model), the KV cache(s) a call would allocate (post-bucketing,
        honoring ``kv_quant``; speculative decoding's draft cache
        included when a draft is attached), the fp32 logits buffer, and
        their total — plus ``fits`` when ``hbm_bytes`` is given (e.g.
        16 GiB for one v5e chip). On a mesh, each term is divided by the
        axes it shards over (params over model x expert, replicated
        over data; cache/logits over data x model per ``cache_pspecs``).
        Capacity planning for the N-way fan-out: "does N=64 at 4k
        context fit?" without OOMing a real chip to find out.

        ``shared_prefix_len``: prompt-prefix tokens shared by every
        candidate and STORED ONCE — the paged serving path's CoW page
        sharing (PR 2/3), where an N-fanout's KV footprint is
        prefix + N*suffix. The default 0 models the engine's dense
        per-row cache, which duplicates the prefix (the pre-PR-2
        worst case; capped at the bucketed prompt length since decode
        suffixes are never shared).
        """
        from llm_consensus_tpu.ops.quant import quantized_bytes

        cfg = self.cfg
        s = min(
            _next_bucket(prompt_len, self.config.seq_buckets),
            cfg.max_seq_len,
        )
        mnt = new_tokens or self.config.max_new_tokens
        mnt = max(1, min(mnt, cfg.max_seq_len - s))
        b = _next_bucket(n_candidates, self.config.batch_buckets)
        cache_len = s + mnt

        kv = _kv_cache_bytes(
            cfg, b, cache_len, self.config.kv_quant,
            shared_len=min(shared_prefix_len, s),
        )
        if self.draft is not None:
            d_cfg, d_params = self.draft
            # Speculative decoding holds bf16 target + draft caches.
            kv += _kv_cache_bytes(d_cfg, b, cache_len, quant=False)
        logits = _logits_bytes(cfg, b)
        # Per-chip residency on a mesh: each param leaf divides by the
        # axes its OWN PartitionSpec names (replicated leaves — embeds,
        # norms, and on MoE models all non-expert weights — do not
        # shrink); the cache and batch shard over data and kv heads
        # over model.
        c_div = 1
        if self.mesh is not None:
            from llm_consensus_tpu.parallel.partitioning import (
                sharded_param_bytes,
            )

            shape = dict(self.mesh.shape)
            params_bytes = sharded_param_bytes(self.params, shape)
            if self.draft is not None:
                params_bytes += sharded_param_bytes(self.draft[1], shape)
            c_div = shape.get("data", 1) * shape.get("model", 1)
        else:
            params_bytes = quantized_bytes(self.params)
            if self.draft is not None:
                params_bytes += quantized_bytes(self.draft[1])
        kv //= c_div
        logits //= max(1, c_div)
        total = params_bytes + kv + logits
        out = {
            "params_bytes": params_bytes,
            "kv_cache_bytes": kv,
            "logits_bytes": logits,
            "total_bytes": total,
            "batch": b,
            "cache_len": cache_len,
        }
        if hbm_bytes is not None:
            out["fits"] = total <= hbm_bytes
        return out

    def stats(self) -> dict:
        """Lifetime engine counters (observability surface).

        Calls per API, total generated tokens, and the prefix cache's
        hit/miss/eviction counts + resident bytes — the numbers a
        serving dashboard or an eval report wants without tracing.
        """
        pc = self.prefix_cache
        return {
            "calls": dict(self._calls),
            "tokens_generated": self._tokens_generated,
            "prefix_cache": {
                "hits": pc.stats.hits,
                "misses": pc.stats.misses,
                "evictions": pc.stats.evictions,
                "entries": len(pc),
                "bytes": pc.nbytes,
            },
        }

    def _collect(self, out: GenerateOutput, n_real: int) -> list[EngineResult]:
        toks = np.asarray(out.tokens)
        nums = np.asarray(out.num_tokens)
        lps = np.asarray(out.logprob_sum)
        self._tokens_generated += int(nums[:n_real].sum())
        results = []
        for i in range(n_real):
            n = int(nums[i])
            ids = [int(t) for t in toks[i, :n] if t != self.tokenizer.eos_id]
            results.append(
                EngineResult(
                    text=self.tokenizer.decode(ids),
                    num_tokens=n,
                    logprob=float(lps[i]),
                    token_ids=ids,
                )
            )
        return results

    def _span(self, name: str, **meta):
        """Engine instrumentation site: the span lands on the caller's
        request-scoped trace (propagated here through
        asyncio.to_thread's context copy) — gateway-driven engine calls
        show up in ``GET /debug/traces`` with no per-call plumbing, and
        ``request_span`` is a no-op when no trace is active."""
        from llm_consensus_tpu.utils import tracing as _tracing

        return _tracing.request_span(name, **meta)

    def _generate_prepared(
        self,
        prompts,
        tokens,
        lengths,
        n_real,
        temperatures,
        seed,
        max_new_tokens,
        sampler,
        stop=None,
    ) -> list[EngineResult]:
        b = tokens.shape[0]
        temps = np.zeros((b,), np.float32)
        if temperatures is not None:
            temps[:n_real] = np.asarray(temperatures, np.float32)
        mnt = max_new_tokens or self.config.max_new_tokens
        # Clamp so prompt + generation fits the model context.
        mnt = max(1, min(mnt, self.cfg.max_seq_len - tokens.shape[1]))

        # Identical prompts (self-consistency fan-out) prefill once and
        # broadcast the cache instead of prefetching B copies.
        shared = n_real == b and len(set(prompts)) == 1 and b > 1
        tokens_j, lengths_j, temps_j = (
            jnp.asarray(tokens),
            jnp.asarray(lengths),
            jnp.asarray(temps),
        )
        if self._data_sharding is not None:
            tokens_j = jax.device_put(tokens_j, self._data_sharding)
            lengths_j = jax.device_put(lengths_j, self._data_sharding)
            temps_j = jax.device_put(temps_j, self._data_sharding)
        multi_stop = stop and any(
            len(self.tokenizer.encode(x, add_bos=False)) > 1 for x in stop
        )
        if multi_stop:
            return self._generate_chunked_stops(
                tokens_j, lengths_j, temps_j, n_real, seed, mnt, sampler,
                stop, shared,
            )
        out: GenerateOutput = generate(
            self.cfg,
            self.params,
            tokens_j,
            lengths_j,
            jax.random.PRNGKey(seed),
            temps_j,
            max_new_tokens=mnt,
            sampler=sampler if sampler is not None else self.config.sampler,
            eos_id=self.tokenizer.eos_id,
            pad_id=self.tokenizer.pad_id,
            shared_prefill=shared,
            kv_quant=self.config.kv_quant,
            # Ring prefill (long-context sequence parallelism) when the
            # model opts in and the mesh has a seq axis.
            mesh=self.mesh if self.cfg.use_ring else None,
            prefill_chunk=self.config.prefill_chunk,
            stop_ids=self._stop_ids(stop),
        )
        return self._trim_stops(self._collect(out, n_real), stop)

    def _generate_chunked_stops(
        self, tokens_j, lengths_j, temps_j, n_real, seed, mnt, sampler,
        stop, shared,
    ) -> list[EngineResult]:
        """Batch generation with MULTI-token stop sequences: decode in
        ``stop_check_chunk``-step device calls with host text checks
        between them, so stops like ``"\\n\\n"`` (several ids under any
        tokenizer) end decoding within one chunk instead of every row
        burning steps to EOS/max_new_tokens.

        Greedy output text matches the one-shot path exactly (modulo the
        earlier cutoff); sampled rows draw per-chunk PRNG subkeys (the
        ``generate_stream`` convention) — deterministic per seed, but a
        different stream than the no-stop program. A row whose text
        contains a stop is marked done on device at the next chunk
        boundary; the final :meth:`_exact_stop_accounting` pass then
        realigns ``num_tokens``/``logprob``/``token_ids`` to the prefix
        through the stop, so both stop paths report identical
        accounting (no chunk-granularity overshoot in vote weights)."""
        from llm_consensus_tpu.engine.generate import prefill_into_cache

        b, s = tokens_j.shape
        with self._span(
            "engine.generate_chunked_stops", batch=b, seq=s, n_real=n_real
        ):
            logits, cache = prefill_into_cache(
                self.cfg,
                self.params,
                tokens_j,
                lengths_j,
                cache_len=s + mnt,
                shared_prefill=shared,
                kv_quant=self.config.kv_quant,
                mesh=self.mesh if self.cfg.use_ring else None,
                prefill_chunk=self.config.prefill_chunk,
            )
            return self._chunked_stop_decode(
                logits, cache, temps_j, n_real, seed, mnt, sampler, stop
            )

    def _chunked_stop_decode(
        self, logits, cache, temps_j, n_real, seed, mnt, sampler, stop
    ) -> list[EngineResult]:
        """The decode half of the chunked multi-token-stop path, from
        first-token logits + a filled cache onward — shared by the plain
        batch path and the prefix-cached path (both prefill differently
        but stop identically)."""
        from llm_consensus_tpu.engine.generate import (
            GenerateOutput,
            decode_steps,
        )

        tok_ = self.tokenizer
        b = logits.shape[0]
        sampler_cfg = sampler if sampler is not None else self.config.sampler
        stop_ids = self._stop_ids(stop)
        terminal = {tok_.eos_id, *stop_ids}
        with self._span(
            "engine.chunked_stop_decode", batch=b, n_real=n_real
        ):
            key = jax.random.PRNGKey(seed)
            tok, lp0 = _jit_sample(
                logits, jax.random.fold_in(key, 0), temps_j, sampler_cfg
            )
            toks0 = np.asarray(tok)
            done_np = np.array([int(t) in terminal for t in toks0])
            lp_sum = np.asarray(lp0, np.float32).copy()
            cols_toks = [toks0[:, None].astype(np.int32)]
            cols_live = [np.ones((b, 1), bool)]
            cols_lp = [np.asarray(lp0, np.float32)[:, None]]
            stop_hit = np.zeros((b,), bool)
            done = jnp.asarray(done_np)
            if self._data_sharding is not None:
                done = jax.device_put(done, self._data_sharding)
            produced = 1
            chunk = max(1, self.config.stop_check_chunk)
            chunk_i = 0
            # Per-row incremental id streams + tail-window stop checks:
            # decoding each row's full history every chunk would be
            # O(T^2/chunk) host work (the continuous batcher's _hit_stop
            # learned the same lesson). The final _trim_stops pass
            # guarantees exact text regardless of the window.
            from llm_consensus_tpu.utils.stops import stop_tail_window

            win = stop_tail_window(tok_, stop)
            vis = self._vis_filter
            row_ids: list[list[int]] = [
                [] if done_np[r] else [int(toks0[r])] for r in range(n_real)
            ]

            def _row_stopped(r: int) -> bool:
                # Shared window-then-confirm shape (stops.py): a false
                # positive here would silently truncate a row that
                # _trim_stops then finds no stop in.
                ids = row_ids[r]
                return vis.confirmed_stop_hit(
                    ids, stop, win, lambda: tok_.decode(ids)
                )

            while produced < mnt:
                active = [
                    r
                    for r in range(n_real)
                    if not done_np[r] and not stop_hit[r]
                ]
                if not active:
                    break
                k = min(chunk, mnt - produced)
                chunk_i += 1
                out, live, cache, done, tok, lp = decode_steps(
                    self.cfg,
                    self.params,
                    cache,
                    tok,
                    done,
                    jax.random.fold_in(key, chunk_i),
                    temps_j,
                    steps=chunk,
                    sampler=sampler_cfg,
                    eos_id=tok_.eos_id,
                    pad_id=tok_.pad_id,
                    stop_ids=stop_ids,
                )
                out_np = np.asarray(out)[:, :k].astype(np.int32)
                live_np = np.asarray(live)[:, :k]
                cols_toks.append(out_np)
                cols_live.append(live_np)
                # Per-step logprobs, truncated to the consumed prefix —
                # tail-chunk overshoot must not inflate the sum.
                lp_np = np.asarray(lp, np.float32)[:, :k]
                cols_lp.append(lp_np)
                lp_sum += lp_np.sum(axis=1)
                produced += k
                done_np = np.asarray(done).copy()
                for r in active:
                    row_ids[r].extend(
                        int(t)
                        for t, alive in zip(out_np[r], live_np[r])
                        if alive and int(t) not in terminal
                    )
                    if not done_np[r] and _row_stopped(r):
                        stop_hit[r] = True
                if stop_hit.any():
                    # Stopped rows go done on device: they stop burning
                    # logprob accumulation and emit pad from here on.
                    done = jnp.asarray(done_np | stop_hit)
                    if self._data_sharding is not None:
                        done = jax.device_put(done, self._data_sharding)

        tokens_arr = np.concatenate(cols_toks, axis=1)
        live_arr = np.concatenate(cols_live, axis=1)
        lp_arr = np.concatenate(cols_lp, axis=1)
        out = GenerateOutput(
            tokens=jnp.asarray(tokens_arr),
            num_tokens=jnp.asarray(live_arr.sum(axis=1).astype(np.int32)),
            logprob_sum=jnp.asarray(lp_sum),
        )
        results = self._trim_stops(self._collect(out, n_real), stop)
        return self._exact_stop_accounting(results, tokens_arr, lp_arr, stop)

    def _exact_stop_accounting(
        self, results, toks_np, lp_np, stop
    ) -> list[EngineResult]:
        """Align the chunked multi-token-stop path's accounting with
        the device single-token-stop path: ``num_tokens`` / ``logprob``
        / ``token_ids`` cover exactly the prefix through the first
        complete stop occurrence (the stop's own tokens counted, like
        EOS) instead of including up to one ``stop_check_chunk`` of
        overshoot. Without this, the SAME stop reported different
        logit_pool/rescore vote weights depending on whether it
        tokenized to one id (device path, exact) or several (chunked
        path) — aggregation weights must not depend on tokenizer
        granularity. The prefix search assumes decoded-prefix
        containment is monotone in token count (exact for byte-level
        tokenizers; merge-based boundary effects can shift the cut by
        a token, never the text, which was already trimmed exactly).
        """
        from llm_consensus_tpu.utils.stops import earliest_stop_cut

        eos = self.tokenizer.eos_id
        for i, r in enumerate(results):
            n = r.num_tokens
            if n <= 1:
                continue

            def ids(m: int) -> list[int]:
                # Mirrors _collect's id construction (eos excluded) —
                # one predicate, shared by the probe and the result.
                return [int(t) for t in toks_np[i, :m] if int(t) != eos]

            if earliest_stop_cut(self.tokenizer.decode(ids(n)), stop) < 0:
                continue
            lo, hi = 1, n
            while lo < hi:
                mid = (lo + hi) // 2
                pref = self.tokenizer.decode(ids(mid))
                if earliest_stop_cut(pref, stop) >= 0:
                    hi = mid
                else:
                    lo = mid + 1
            if lo < n:
                # Keep the engine-wide generated-token counter honest
                # too (it was bumped with the overshoot included).
                self._tokens_generated -= n - lo
                r.num_tokens = lo
                r.logprob = float(lp_np[i, :lo].sum())
                r.token_ids = ids(lo)
        return results

    def generate_stream(
        self,
        prompt: str,
        *,
        temperature: float = 0.0,
        seed: int = 0,
        max_new_tokens: int | None = None,
        chunk: int = 16,
        sampler: SamplerConfig | None = None,
        stop: list[str] | None = None,
    ):
        """Yield text increments for one prompt as tokens decode.

        The streaming surface of the engine: prefill once, then decode
        in device calls of ``chunk`` steps, yielding the newly decoded
        text after each (REPL/interactive serving — the reference's UX
        blocks on the whole remote answer, ``src/main.rs:448-463``).
        Greedy streaming concatenates to exactly ``generate_texts``'s
        output; sampled streams draw per-chunk PRNG subkeys. Stop
        sequences are honored across chunk boundaries. Sharded engines
        stream incrementally too: the single request pads to the data
        axis (dummy greedy rows beyond row 0) and the cache/batch shard
        as in ``generate_texts`` — the REPL sees tokens as they decode
        on the north-star config, not one blocking yield.
        """
        self._calls["stream"] += 1
        from llm_consensus_tpu.engine.generate import decode_steps
        from llm_consensus_tpu.models.cache import KVCache, QuantKVCache

        tok_ = self.tokenizer
        tokens, lengths, _ = self._prepare([prompt])
        if self.mesh is None:
            # _prepare pads to the batch bucket; the stream decodes one
            # row. On a mesh the bucketed batch stays (it tiles `data`).
            tokens, lengths = tokens[:1], lengths[:1]
        b = tokens.shape[0]
        s = tokens.shape[1]
        mnt = max_new_tokens or self.config.max_new_tokens
        mnt = max(1, min(mnt, self.cfg.max_seq_len - s))
        chunk = max(1, chunk)
        sampler_cfg = sampler if sampler is not None else self.config.sampler
        stop = stop or []
        stop_ids = self._stop_ids(stop)
        terminal = {tok_.eos_id, *stop_ids}

        make_cache = (
            QuantKVCache.create if self.config.kv_quant else KVCache.create
        )
        cache = make_cache(self.cfg, b, s + mnt)
        tokens_j = jnp.asarray(tokens)
        lengths_j = jnp.asarray(lengths)
        temps_np = np.zeros((b,), np.float32)
        temps_np[0] = temperature
        temps = jnp.asarray(temps_np)
        if self._data_sharding is not None:
            tokens_j = jax.device_put(tokens_j, self._data_sharding)
            lengths_j = jax.device_put(lengths_j, self._data_sharding)
            temps = jax.device_put(temps, self._data_sharding)
            cache = jax.device_put(cache, self._cache_sharding(cache))
        if self.config.prefill_chunk and s > self.config.prefill_chunk:
            logits, cache = _jit_prefill_chunked(
                self.cfg, self.params, tokens_j, lengths_j, cache,
                chunk=self.config.prefill_chunk,
            )
        else:
            logits, cache = _jit_prefill(
                self.cfg, self.params, tokens_j, lengths_j, cache
            )
        key = jax.random.PRNGKey(seed)
        tok, _ = _jit_sample(
            logits, jax.random.fold_in(key, 0), temps, sampler_cfg
        )
        toks_np = np.asarray(tok)
        first = int(toks_np[0])
        ids: list[int] = [] if first in terminal else [first]
        done = jnp.asarray([int(t) in terminal for t in toks_np])
        if self._data_sharding is not None:
            done = jax.device_put(done, self._data_sharding)
        self._tokens_generated += 1
        yielded = 0

        def _flush(final: bool):
            """(increment, finished): emit decoded text past what was
            already yielded, holding back (a) any tail that is a partial
            match of a stop string (it may complete next chunk and must
            then be trimmed, never emitted) and (b) trailing replacement
            chars from split multi-byte sequences."""
            nonlocal yielded
            from llm_consensus_tpu.utils.stops import earliest_stop_cut

            t = tok_.decode(ids)
            cut = earliest_stop_cut(t, stop)
            finished = cut >= 0
            if finished:
                t = t[:cut]
            emit_to = len(t)
            if not finished and not final:
                hold = 0
                for x in stop:
                    for k in range(min(len(x) - 1, len(t)), 0, -1):
                        if t.endswith(x[:k]):
                            hold = max(hold, k)
                            break
                emit_to = len(t) - hold
                while emit_to > yielded and t[emit_to - 1] == "�":
                    emit_to -= 1
            inc = t[yielded:emit_to]
            yielded = max(yielded, emit_to)
            return inc, finished

        inc, finished = _flush(final=False)
        if inc:
            yield inc
        if finished:
            return
        produced = 1
        chunk_i = 0
        while produced < mnt and not bool(done[0]):
            # Always run a full `chunk` of steps — `steps` is a static
            # jit arg, so a shorter tail would compile a second decode
            # program mid-stream. Overshoot tokens past the budget are
            # discarded (their cache writes past capacity are dropped
            # by scatter OOB semantics, and the loop ends this chunk).
            k = min(chunk, mnt - produced)
            chunk_i += 1
            out, live, cache, done, tok, _ = decode_steps(
                self.cfg,
                self.params,
                cache,
                tok,
                done,
                jax.random.fold_in(key, chunk_i),
                temps,
                steps=chunk,
                sampler=sampler_cfg,
                eos_id=tok_.eos_id,
                pad_id=tok_.pad_id,
                stop_ids=stop_ids,
            )
            produced += k
            self._tokens_generated += int(np.asarray(live[0, :k]).sum())
            # A genuinely sampled pad id while live stays in the text
            # (matching generate_texts); only post-termination padding
            # and terminal tokens (eos / device stops) are dropped.
            ids.extend(
                t
                for t, alive in zip(out[0, :k].tolist(), live[0, :k].tolist())
                if alive and t not in terminal
            )
            inc, finished = _flush(final=False)
            if inc:
                yield inc
            if finished:
                return
        inc, _ = _flush(final=True)
        if inc:
            yield inc

    def score_texts(
        self,
        prompt: str,
        completions: list[str],
        *,
        normalize: bool = False,
        _outer: bool = True,
    ) -> list[float]:
        """Log-probability of each completion given ``prompt``.

        Teacher-forced scoring — no sampling: the prompt prefills once,
        its cache broadcasts, and every completion's tokens score in one
        ragged chunk forward. ``normalize``: divide by token count
        (length-normalized, for comparing completions of different
        lengths). Candidates can come from anywhere — another model of
        a heterogeneous panel, a debate round, a human draft — making
        this the reranking/logit-pooling half of answer aggregation.
        bf16 cache. On a mesh the completion rows shard over ``data``
        (the prompt and its B=1 prefill replicate; GSPMD broadcasts the
        cache into the sharded batch) — judge rescoring works on the
        north-star sharded config, same numbers as single-device.
        """
        if not completions:
            return []
        if _outer:
            self._calls["score"] += 1
        # Batches beyond the largest bucket score in chunks.
        max_b = self.config.batch_buckets[-1]
        if len(completions) > max_b:
            out: list[float] = []
            for i in range(0, len(completions), max_b):
                out.extend(
                    self.score_texts(
                        prompt,
                        completions[i : i + max_b],
                        normalize=normalize,
                        _outer=False,
                    )
                )
            return out
        from llm_consensus_tpu.engine.generate import score_completions

        tok = self.tokenizer
        ctx = self.cfg.max_seq_len
        p_ids = tok.encode(prompt)[-(ctx - 2) :]
        p = len(p_ids)
        # Prompt pads to a seq bucket (the true length rides as data) so
        # repeat calls with different prompt lengths share one compiled
        # program — the engine-wide bucketing contract.
        sp = max(p, min(_next_bucket(p, self.config.seq_buckets), ctx - 1))
        comp_cap = min(ctx - p, self.config.seq_buckets[-1])
        comp = [
            tok.encode(c, add_bos=False)[:comp_cap] for c in completions
        ]
        if any(len(c) < 1 for c in comp):
            raise ValueError("cannot score an empty completion")
        k = min(
            _next_bucket(max(len(c) for c in comp), self.config.seq_buckets),
            comp_cap,
        )
        k = max(k, max(len(c) for c in comp))
        b = _next_bucket(len(comp), self.config.batch_buckets)
        ctoks = np.full((b, k), tok.pad_id, np.int32)
        for i, ids in enumerate(comp):
            ctoks[i, : len(ids)] = ids
        clens = np.ones((b,), np.int32)
        clens[: len(comp)] = [len(c) for c in comp]
        ptoks = np.full((1, sp), tok.pad_id, np.int32)
        ptoks[0, :p] = p_ids
        ptoks_j = jnp.asarray(ptoks)
        plen_j = jnp.asarray([p], jnp.int32)
        ctoks_j = jnp.asarray(ctoks)
        clens_j = jnp.asarray(clens)
        if self._data_sharding is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            rep = NamedSharding(self.mesh, P())
            ptoks_j = jax.device_put(ptoks_j, rep)
            plen_j = jax.device_put(plen_j, rep)
            ctoks_j = jax.device_put(ctoks_j, self._data_sharding)
            clens_j = jax.device_put(clens_j, self._data_sharding)
        with self._span(
            "engine.score", batch=b, prompt=p, k=k, n_real=len(comp)
        ):
            sums, _ = score_completions(
                self.cfg,
                self.params,
                ptoks_j,
                plen_j,
                ctoks_j,
                clens_j,
                cache_len=sp + k,
            )
        out = np.asarray(sums)[: len(comp)].tolist()
        if normalize:
            out = [s / max(len(c), 1) for s, c in zip(out, comp)]
        return out

    def generate_texts_speculative(
        self,
        prompts: list[str],
        max_new_tokens: int | None = None,
        k_spec: int = 4,
        _outer: bool = True,
    ) -> list[EngineResult]:
        """Greedy generation accelerated by the draft model.

        Requires ``draft=(cfg, params)`` at engine construction. Output
        text is IDENTICAL to greedy ``generate_texts`` (speculation only
        changes speed — tested); greedy-only, bf16 KV, one-shot
        prefill. On a mesh engine the whole speculative program runs
        sharded (batch over ``data``, target+draft params over
        ``model`` — dp-mesh exactness tested). Logprobs follow the same
        convention as the plain path (target log_softmax of emitted
        tokens).
        """
        if self.draft is None:
            raise ValueError("engine was built without a draft model")
        if not prompts:
            return []
        if _outer:
            self._calls["speculative"] += 1
        chunk = self.config.batch_buckets[-1]
        if len(prompts) > chunk:
            out: list[EngineResult] = []
            for i in range(0, len(prompts), chunk):
                out.extend(
                    self.generate_texts_speculative(
                        prompts[i : i + chunk],
                        max_new_tokens=max_new_tokens,
                        k_spec=k_spec,
                        _outer=False,
                    )
                )
            return out
        from llm_consensus_tpu.engine.speculative import speculative_generate

        draft_cfg, draft_params = self.draft
        tokens, lengths, n_real = self._prepare(prompts)
        tokens_j, lengths_j = jnp.asarray(tokens), jnp.asarray(lengths)
        if self._data_sharding is not None:
            tokens_j = jax.device_put(tokens_j, self._data_sharding)
            lengths_j = jax.device_put(lengths_j, self._data_sharding)
        # Same clamp as generate_texts — the k_spec+1 chunk slack lives
        # in speculative_generate's cache_len, NOT in the token budget,
        # so outputs stay identical to the greedy path.
        mnt = max_new_tokens or self.config.max_new_tokens
        mnt = max(1, min(mnt, self.cfg.max_seq_len - tokens.shape[1]))
        with self._span(
            "engine.generate_speculative",
            batch=tokens.shape[0],
            seq=tokens.shape[1],
            n_real=n_real,
            k_spec=k_spec,
        ):
            out = speculative_generate(
                self.cfg,
                self.params,
                draft_cfg,
                draft_params,
                tokens_j,
                lengths_j,
                max_new_tokens=mnt,
                k_spec=k_spec,
                eos_id=self.tokenizer.eos_id,
                pad_id=self.tokenizer.pad_id,
                mesh=self.mesh,
            )
        return self._collect(out, n_real)


def plan_memory(
    cfg: ModelConfig,
    *,
    quant: str = "none",
    kv_quant: bool = False,
    n_candidates: int = 1,
    prompt_len: int = 128,
    new_tokens: int = 256,
    mesh_shape: dict | None = None,
    hbm_bytes: int | None = None,
    seq_buckets: tuple[int, ...] | None = None,
    batch_buckets: tuple[int, ...] | None = None,
    shared_prefix_len: int = 0,
    host_cache_bytes: int = 0,
    page_size: int = 64,
) -> dict:
    """Config-only HBM plan — no weights are ever allocated.

    The capacity-planning companion to :meth:`InferenceEngine.
    memory_estimate` for models too large to instantiate first (the
    question "can Mixtral-8x7B fit one v5e chip?" must be answerable
    without OOMing one). Param bytes come from ``jax.eval_shape`` over
    ``init_params`` + ``quantize_params`` — exact leaf-for-leaf sizes,
    zero allocation. KV/logit math matches ``memory_estimate``,
    INCLUDING the engine's shape bucketing: ``n_candidates``/
    ``prompt_len`` round up to ``batch_buckets``/``seq_buckets``
    (defaults = ``EngineConfig``'s) exactly as a real generate call
    would, so the ``fits`` verdict reflects what the engine actually
    allocates, not the raw request. Pass ``buckets=()``-style overrides
    to mirror a custom engine config. ``mesh_shape`` (e.g.
    ``{"data": 4, "model": 2}``) divides each term by the axes it
    shards over. ``shared_prefix_len``: prompt tokens stored once for
    the whole fan-out (the paged serving path's prefix sharing) — see
    :meth:`InferenceEngine.memory_estimate`.

    ``host_cache_bytes`` > 0 adds the hierarchical-cache tier (PR 4,
    ``ContinuousConfig.host_cache_bytes``) to the plan: how many
    ``page_size``-token KV pages — in this config's KV dtype,
    ``kv_quant`` scales included — the host-RAM tier can keep warm,
    and the prefix-token capacity that buys. Host bytes never count
    against ``hbm_bytes`` (pinned host RAM, not device memory); the
    tier changes how much RECOMPUTE eviction costs, not whether the
    device footprint fits.
    """
    from llm_consensus_tpu.models.transformer import init_params
    from llm_consensus_tpu.ops.quant import quantize_params, quantized_bytes

    tree = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    )
    if quant in ("int8", "int4"):
        bits = 8 if quant == "int8" else 4
        tree = jax.eval_shape(lambda t: quantize_params(t, bits=bits), tree)

    dflt = EngineConfig()
    sb = seq_buckets if seq_buckets is not None else dflt.seq_buckets
    bb = batch_buckets if batch_buckets is not None else dflt.batch_buckets
    s = min(_next_bucket(prompt_len, sb), cfg.max_seq_len)
    b = _next_bucket(n_candidates, bb)
    mnt = max(1, min(new_tokens, cfg.max_seq_len - s))
    cache_len = s + mnt
    kv = _kv_cache_bytes(
        cfg, b, cache_len, kv_quant, shared_len=min(shared_prefix_len, s)
    )
    logits = _logits_bytes(cfg, b)

    shape = dict(mesh_shape or {})
    if any(v > 1 for v in shape.values()):
        # Per-leaf division by the axes each leaf's PartitionSpec names:
        # on MoE models only the expert FFN stacks shard over `expert`;
        # attention/embeds/norms replicate and must count at full size
        # per chip (a global model*expert divide understates residency
        # and can claim a config fits when it OOMs).
        from llm_consensus_tpu.parallel.partitioning import (
            sharded_param_bytes,
        )

        params_bytes = sharded_param_bytes(tree, shape)
    else:
        params_bytes = quantized_bytes(tree)
    c_div = shape.get("data", 1) * shape.get("model", 1)
    kv //= c_div
    logits //= max(1, c_div)
    total = params_bytes + kv + logits
    out = {
        "params_bytes": params_bytes,
        "kv_cache_bytes": kv,
        "logits_bytes": logits,
        "total_bytes": total,
        "batch": b,
        "cache_len": cache_len,
    }
    if host_cache_bytes > 0:
        # One page of KV in this config's dtype, scales included — the
        # same _kv_cache_bytes formula the device terms use, so a cache
        # layout change cannot drift the two tiers apart.
        page_bytes = _kv_cache_bytes(cfg, 1, page_size, kv_quant)
        host_pages = host_cache_bytes // max(1, page_bytes)
        out["host_cache_bytes"] = host_cache_bytes
        out["host_page_bytes"] = page_bytes
        out["host_capacity_pages"] = host_pages
        out["host_capacity_tokens"] = host_pages * page_size
    if hbm_bytes is not None:
        out["fits"] = total <= hbm_bytes
    return out
