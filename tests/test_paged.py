"""Paged KV cache + continuous batching (models/paged_cache.py,
serving/continuous.py).

Parity anchor: paged decode must produce exactly the greedy tokens of
the dense-cache generate loop — same model, same prompts.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.engine.engine import EngineConfig, InferenceEngine
from llm_consensus_tpu.engine.tokenizer import ByteTokenizer
from llm_consensus_tpu.models.cache import KVCache
from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.paged_cache import (
    NULL_PAGE,
    PagedKVCache,
    assign_pages,
    gather_seq_kv,
    release_seq,
    write_decode_kv,
    write_prefill_kv,
)
from llm_consensus_tpu.models.transformer import (
    decode_step,
    decode_step_paged,
    init_params,
    prefill,
)
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)

CFG = get_config("test-tiny")


def _params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def test_page_write_gather_roundtrip():
    cache = PagedKVCache.create(
        CFG, n_pages=8, page_size=4, max_seqs=2, pages_per_seq=3,
        dtype=jnp.float32,
    )
    cache = assign_pages(cache, jnp.int32(0), jnp.asarray([2, 5, 7]))
    L, h, d = CFG.n_layers, CFG.n_kv_heads, CFG.head_dim
    k_seq = jnp.arange(L * 8 * h * d, dtype=jnp.float32).reshape(L, 8, h, d)
    cache = write_prefill_kv(cache, jnp.int32(0), k_seq, k_seq, jnp.int32(7))
    k_g, v_g = gather_seq_kv(cache, jnp.asarray([0]))
    assert k_g.shape == (L, 1, 12, h, d)
    np.testing.assert_array_equal(np.asarray(k_g[:, 0, :8]), np.asarray(k_seq))
    # Decode write lands at position 7 = page 1 (id 5), offset 3.
    k_new = jnp.ones((L, 1, h, d), jnp.float32) * 99.0
    cache = write_decode_kv(cache, jnp.asarray([0]), k_new, k_new)
    assert int(cache.length[0]) == 8
    np.testing.assert_array_equal(
        np.asarray(cache.k[:, 5, 3]), np.asarray(k_new[:, 0])
    )
    cache = release_seq(cache, jnp.int32(0))
    assert int(cache.length[0]) == 0
    assert int(cache.page_table[0, 0]) == NULL_PAGE


def test_paged_decode_attention_kernel_matches_gather():
    """The paged Pallas kernel (scalar-prefetched page walk, online
    softmax across pages) must match the jnp gather path — ragged
    lengths, NULL pages, out-of-order tables, GQA groups included."""
    from llm_consensus_tpu.ops.attention import decode_attention
    from llm_consensus_tpu.ops.pallas.attention import paged_decode_attention

    key = jax.random.PRNGKey(0)
    b, h, hkv, d = 3, 4, 2, 128
    n_pages, pg, p_per = 10, 8, 4
    q = jax.random.normal(key, (b, h, d), jnp.float32)
    k_pool = jax.random.normal(jax.random.PRNGKey(1), (n_pages, pg, hkv, d))
    v_pool = jax.random.normal(jax.random.PRNGKey(2), (n_pages, pg, hkv, d))
    # Out-of-order page lists, unused slots on the NULL page; ragged
    # lengths incl. a page-boundary case and a minimal 1-token row.
    tables = jnp.asarray([[7, 2, 9, 0], [3, 1, 0, 0], [5, 0, 0, 0]])
    valid = jnp.asarray([19, 16, 1], jnp.int32)

    got = paged_decode_attention(
        q, k_pool, v_pool, tables, valid, interpret=True
    )
    k_seq = k_pool[tables].reshape(b, p_per * pg, hkv, d)
    v_seq = v_pool[tables].reshape(b, p_per * pg, hkv, d)
    want = decode_attention(q[:, None], k_seq, v_seq, valid)[:, 0]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_paged_decode_attention_kernel_mqa_edge():
    """MQA (one kv head, all query heads in one group) — the extreme
    GQA ratio must still match the gather path."""
    from llm_consensus_tpu.ops.attention import decode_attention
    from llm_consensus_tpu.ops.pallas.attention import paged_decode_attention

    b, h, hkv, d = 2, 4, 1, 128
    n_pages, pg, p_per = 6, 8, 3
    q = jax.random.normal(jax.random.PRNGKey(4), (b, h, d), jnp.float32)
    k_pool = jax.random.normal(jax.random.PRNGKey(5), (n_pages, pg, hkv, d))
    v_pool = jax.random.normal(jax.random.PRNGKey(6), (n_pages, pg, hkv, d))
    tables = jnp.asarray([[4, 1, 0], [2, 0, 0]])
    valid = jnp.asarray([13, 8], jnp.int32)
    got = paged_decode_attention(
        q, k_pool, v_pool, tables, valid, interpret=True
    )
    k_seq = k_pool[tables].reshape(b, p_per * pg, hkv, d)
    v_seq = v_pool[tables].reshape(b, p_per * pg, hkv, d)
    want = decode_attention(q[:, None], k_seq, v_seq, valid)[:, 0]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_decode_step_paged_kernel_matches_gather_path():
    """decode_step_paged with cfg.use_pallas routes through the paged
    kernel and must produce the same logits as the gather path."""
    from llm_consensus_tpu.models.transformer import decode_step_paged

    cache = PagedKVCache.create(
        CFG, n_pages=12, page_size=4, max_seqs=2, pages_per_seq=4
    )
    cache = assign_pages(cache, jnp.int32(0), jnp.asarray([2, 5, 7, 9]))
    cache = assign_pages(cache, jnp.int32(1), jnp.asarray([1, 3, 0, 0]))
    params = _params()
    L, hkv, d = CFG.n_layers, CFG.n_kv_heads, CFG.head_dim
    k_seq = jax.random.normal(jax.random.PRNGKey(3), (L, 8, hkv, d))
    cache = write_prefill_kv(cache, jnp.int32(0), k_seq, k_seq, jnp.int32(6))
    cache = write_prefill_kv(
        cache, jnp.int32(1), k_seq[:, :4], k_seq[:, :4], jnp.int32(3)
    )
    toks = jnp.asarray([[9], [17]], jnp.int32)
    logits_ref, _ = decode_step_paged(CFG, params, toks, cache)
    logits_krn, cache_krn = decode_step_paged(
        CFG.with_(use_pallas=True), params, toks, cache
    )
    np.testing.assert_allclose(
        np.asarray(logits_krn),
        np.asarray(logits_ref),
        rtol=2e-4,
        atol=2e-4,
    )
    assert int(cache_krn.length[0]) == 7  # write still advanced


def test_paged_decode_matches_dense():
    """Greedy decode over the paged cache == dense-cache decode_step."""
    params = _params()
    prompt = jnp.asarray(
        [[5, 6, 7, 8, 9, 10, 11, 12]], jnp.int32
    )  # [1, 8]
    steps = 6

    dense = KVCache.create(CFG, 1, 32, dtype=jnp.float32)
    logits, dense = prefill(CFG, params, prompt, jnp.asarray([8]), dense)
    dense_toks = []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(steps):
        dense_toks.append(int(tok[0]))
        logits, dense = decode_step(CFG, params, tok[:, None], dense)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)

    paged = PagedKVCache.create(
        CFG, n_pages=16, page_size=4, max_seqs=2, pages_per_seq=8,
        dtype=jnp.float32,
    )
    paged = assign_pages(
        paged, jnp.int32(1), jnp.asarray([3, 9, 4, 11, 0, 0, 0, 0])
    )
    d2 = KVCache.create(CFG, 1, 8, dtype=jnp.float32)
    logits2, d2 = prefill(CFG, params, prompt, jnp.asarray([8]), d2)
    paged = write_prefill_kv(
        paged, jnp.int32(1), d2.k[:, 0], d2.v[:, 0], jnp.int32(8)
    )
    tok2 = jnp.argmax(logits2, -1).astype(jnp.int32)
    paged_toks = []
    for _ in range(steps):
        paged_toks.append(int(tok2[0]))
        full = jnp.zeros((2,), jnp.int32).at[1].set(tok2[0])
        logits2, paged = decode_step_paged(CFG, params, full[:, None], paged)
        tok2 = jnp.argmax(logits2[1:2], -1).astype(jnp.int32)

    assert paged_toks == dense_toks


@pytest.fixture
def batcher():
    b = ContinuousBatcher(
        CFG,
        _params(),
        config=ContinuousConfig(
            max_slots=4,
            page_size=16,
            n_pages=64,
            pages_per_seq=8,
            max_new_tokens=8,
            seq_buckets=(16, 32, 64),
        ),
    )
    yield b
    b.close()


def test_continuous_matches_engine_greedy(batcher):
    """Staggered continuous-batch requests == one-shot engine results."""
    prompts = ["hello world", "the quick brown fox", "abc"]
    futures = []
    for p in prompts:
        futures.append(batcher.submit(p, max_new_tokens=8))
        time.sleep(0.02)  # arrive mid-flight
    got = [f.result(timeout=120).text for f in futures]

    eng = InferenceEngine(
        CFG,
        _params(),
        engine_config=EngineConfig(
            max_new_tokens=8, seq_buckets=(16, 32, 64)
        ),
    )
    want = [
        r.text for r in eng.generate_texts(prompts, max_new_tokens=8)
    ]
    assert got == want


def test_continuous_moe_matches_engine_greedy():
    """An MoE model (Mixtral-style capacity config) serves through the
    continuous batcher and matches the engine path exactly: at serving
    shapes every program sits at/below the dense-fallback threshold
    (ModelConfig.moe_dense_decode_tokens), so both substrates trace the
    dense all-experts path consistently."""
    cfg = get_config("test-tiny-moe").with_(moe_capacity_factor=1.25)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    b = ContinuousBatcher(
        cfg,
        params,
        config=ContinuousConfig(
            max_slots=4,
            page_size=16,
            n_pages=64,
            pages_per_seq=8,
            max_new_tokens=8,
            seq_buckets=(16, 32, 64),
        ),
    )
    try:
        prompts = ["hello world", "abc"]
        futures = [b.submit(p, max_new_tokens=8) for p in prompts]
        got = [f.result(timeout=120).text for f in futures]
    finally:
        b.close()
    eng = InferenceEngine(
        cfg,
        params,
        engine_config=EngineConfig(max_new_tokens=8, seq_buckets=(16, 32, 64)),
    )
    want = [r.text for r in eng.generate_texts(prompts, max_new_tokens=8)]
    assert got == want


def test_backend_stop_parity_local_vs_continuous(batcher):
    """Protocol matrix with stops: LocalBackend (engine path) and
    ContinuousBackend must serve IDENTICAL text for the same greedy
    requests carrying stop sequences — the seam contract the consensus
    protocol relies on when swapping substrates."""
    import asyncio

    from llm_consensus_tpu.backends.base import (
        GenerationRequest,
        SamplingParams,
    )
    from llm_consensus_tpu.backends.local import LocalBackend
    from llm_consensus_tpu.serving.continuous import ContinuousBackend

    eng = InferenceEngine(
        CFG,
        _params(),
        engine_config=EngineConfig(max_new_tokens=8, seq_buckets=(16, 32, 64)),
    )
    # Carve stops out of real greedy output so they actually trigger;
    # keep only prompts whose output is long enough to carve from.
    probe_prompts = ["hello world", "abc", "the quick", "zed"]
    base = [
        r.text
        for r in eng.generate_texts(probe_prompts, max_new_tokens=8)
    ]
    usable = [(p, t) for p, t in zip(probe_prompts, base) if len(t) >= 4]
    if not usable:
        pytest.skip("all outputs too short to carve stops from")
    reqs = [
        GenerationRequest(
            prompt=p,
            params=SamplingParams(max_new_tokens=8, stop=(t[2:4],)),
        )
        for p, t in usable
    ]
    local = asyncio.run(LocalBackend(eng).generate_batch(reqs))
    cont = asyncio.run(ContinuousBackend(batcher).generate_batch(reqs))
    assert [r.text for r in local] == [r.text for r in cont]
    assert all(s not in r.text for r, q in zip(local, reqs) for s in q.params.stop)


def test_continuous_pool_exhaustion_recovers():
    """More requests than pool pages: later ones wait, all complete."""
    b = ContinuousBatcher(
        CFG,
        _params(),
        config=ContinuousConfig(
            max_slots=2,
            page_size=16,
            n_pages=5,  # 4 usable pages; each request needs 2
            pages_per_seq=4,
            max_new_tokens=4,
            seq_buckets=(16,),
        ),
    )
    try:
        futures = [b.submit(f"q{i}", max_new_tokens=4) for i in range(5)]
        outs = [f.result(timeout=120) for f in futures]
        assert len(outs) == 5
        assert all(isinstance(o.text, str) and o.num_tokens >= 1 for o in outs)
    finally:
        b.close()


def test_batcher_stats(batcher):
    before = batcher.stats()
    assert before["completed_requests"] == 0
    assert before["total_pages"] == 63
    batcher.submit("count me", max_new_tokens=4).result(timeout=120)
    after = batcher.stats()
    assert after["completed_requests"] == 1
    assert after["generated_tokens"] >= 1
    assert after["free_pages"] == before["free_pages"]  # pages returned
    assert after["active_slots"] == 0


def test_seed_reproducible_across_batch_states(batcher):
    """Same (prompt, seed, temperature) gives the same text whether it
    runs alone or alongside other requests."""
    alone = batcher.submit("xyz", temperature=1.0, seed=7).result(timeout=120)
    futs = [
        batcher.submit(p, temperature=1.0, seed=7 + i)
        for i, p in enumerate(["aaa", "xyz", "bbb"], start=0)
    ]
    # The "xyz" row used seed 8 here; resubmit with seed 7 amid traffic.
    crowd = batcher.submit("xyz", temperature=1.0, seed=7)
    [f.result(timeout=120) for f in futs]
    assert crowd.result(timeout=120) == alone
    # Different seeds should (overwhelmingly) differ on a tiny random
    # model with temperature 1.
    other = batcher.submit("xyz", temperature=1.0, seed=8).result(timeout=120)
    assert other != alone


def test_impossible_pool_request_fails_fast():
    """pages_per_seq would allow it but the pool can never satisfy it."""
    b = ContinuousBatcher(
        CFG,
        _params(),
        config=ContinuousConfig(
            max_slots=2,
            page_size=16,
            n_pages=4,  # 3 usable
            pages_per_seq=8,
            max_new_tokens=64,
            seq_buckets=(16,),
        ),
    )
    try:
        with pytest.raises(ValueError, match="pool"):
            b.submit("hi", max_new_tokens=64).result(timeout=60)
    finally:
        b.close()


def test_zero_max_new_tokens_rejected(batcher):
    with pytest.raises(ValueError, match="max_new_tokens"):
        batcher.submit("hi", max_new_tokens=0)


def test_oversized_request_rejected():
    b = ContinuousBatcher(
        CFG,
        _params(),
        config=ContinuousConfig(
            max_slots=2,
            page_size=16,
            n_pages=32,
            pages_per_seq=2,  # max 32 tokens total
            max_new_tokens=64,
            seq_buckets=(16,),
        ),
    )
    try:
        with pytest.raises(ValueError, match="pages"):
            b.submit("hi", max_new_tokens=64).result(timeout=60)
    finally:
        b.close()


# ---------------------------------------------------------------------------
# ContinuousBackend: the consensus protocol over token-level batching
# (VERDICT r2 #8 — serving exposed through the Backend seam)
# ---------------------------------------------------------------------------


def test_continuous_backend_generate_batch(batcher):
    """generate_batch over the batcher returns per-request results."""
    import asyncio

    from llm_consensus_tpu.backends.base import (
        Backend,
        GenerationRequest,
        SamplingParams,
    )
    from llm_consensus_tpu.serving.continuous import ContinuousBackend

    backend = ContinuousBackend(batcher)
    assert isinstance(backend, Backend)
    reqs = [
        GenerationRequest(
            prompt=p, params=SamplingParams(max_new_tokens=6)
        )
        for p in ["one", "two", "three"]
    ]
    results = asyncio.run(backend.generate_batch(reqs))
    assert len(results) == 3
    assert all(r.num_tokens >= 1 for r in results)


def test_continuous_backend_per_request_sampling_passthrough(batcher):
    """Per-request top_k/top_p ride as decode-step data now — a request
    with its own sampler settings must serve (no recompile-guard
    rejection), and top_k=1 must reduce to the greedy result."""
    import asyncio

    from llm_consensus_tpu.backends.base import (
        GenerationRequest,
        SamplingParams,
    )
    from llm_consensus_tpu.serving.continuous import ContinuousBackend

    backend = ContinuousBackend(batcher)
    greedy, k1 = asyncio.run(
        backend.generate_batch(
            [
                GenerationRequest(
                    prompt="same prompt",
                    params=SamplingParams(max_new_tokens=6),
                ),
                GenerationRequest(
                    prompt="same prompt",
                    params=SamplingParams(
                        max_new_tokens=6, temperature=0.9, top_k=1, seed=5
                    ),
                ),
            ]
        )
    )
    # top_k=1 sampling == greedy, regardless of temperature/seed.
    assert k1.text == greedy.text


def test_continuous_batcher_stop_sequences(batcher):
    """The engine stop contract on the continuous batcher: text trims at
    the earliest stop, and the row retires as soon as the stop appears
    (multi-token stops end decoding immediately — every token is
    host-checked)."""
    full = batcher.submit("tell me a fact", max_new_tokens=8).result(60)
    if len(full.text) < 4:
        pytest.skip("output too short to carve a stop from")
    stop = full.text[2:4]  # a MULTI-char stop that lands mid-output
    r = batcher.submit(
        "tell me a fact", max_new_tokens=8, stop=[stop]
    ).result(60)
    assert r.text == full.text[: full.text.find(stop)]
    assert stop not in r.text
    assert r.num_tokens <= full.num_tokens  # retired early, not trimmed late


def test_coordinator_protocol_over_continuous_backend(batcher):
    """The full consensus protocol rides token-level batching: panel
    fan-outs arrive as generate_batch lists and interleave at decode-step
    granularity. A random-weight tiny model never produces parseable
    verdicts, so every round dissents and the round cap terminates —
    exercising propose -> evaluate -> refine end to end."""
    import asyncio

    from llm_consensus_tpu.backends.base import SamplingParams
    from llm_consensus_tpu.consensus.coordinator import (
        Coordinator,
        CoordinatorConfig,
    )
    from llm_consensus_tpu.consensus.personas import default_panel
    from llm_consensus_tpu.serving.continuous import ContinuousBackend

    backend = ContinuousBackend(batcher)
    coord = Coordinator(
        panel=default_panel(),
        backend=backend,
        config=CoordinatorConfig(
            max_rounds=2,
            seed=0,
            sampling=SamplingParams(max_new_tokens=4),
        ),
    )
    res = asyncio.run(coord.run("What is 2+2?"))
    assert res.answer  # some text was produced
    assert res.rounds <= 2
    assert res.endorsed is False  # garbage verdicts parse as dissent


def test_overlong_prompt_rejected_when_truncation_disabled():
    """truncate_prompts=False surfaces over-long prompts instead of
    silently dropping their head (ADVICE r1)."""
    b = ContinuousBatcher(
        CFG,
        _params(),
        config=ContinuousConfig(
            max_slots=2,
            page_size=16,
            n_pages=32,
            pages_per_seq=8,
            max_new_tokens=4,
            seq_buckets=(16,),
            truncate_prompts=False,
        ),
    )
    try:
        with pytest.raises(ValueError, match="bucket"):
            b.submit("x" * 100)  # ~100 byte tokens > 16-token bucket
    finally:
        b.close()


def test_paged_decode_attention_kernel_sliding_window():
    """window > 0 (Mistral): only the last `window` slots attend — the
    kernel must match the gather path's windowed mask, including a
    window that starts mid-page and a row shorter than the window."""
    from llm_consensus_tpu.ops.attention import decode_attention
    from llm_consensus_tpu.ops.pallas.attention import paged_decode_attention

    b, h, hkv, d = 2, 4, 2, 128
    n_pages, pg, p_per = 8, 8, 4
    q = jax.random.normal(jax.random.PRNGKey(7), (b, h, d), jnp.float32)
    k_pool = jax.random.normal(jax.random.PRNGKey(8), (n_pages, pg, hkv, d))
    v_pool = jax.random.normal(jax.random.PRNGKey(9), (n_pages, pg, hkv, d))
    tables = jnp.asarray([[3, 6, 1, 2], [5, 4, 0, 0]])
    valid = jnp.asarray([27, 6], jnp.int32)  # window mid-page / short row
    for window in (10, 4):
        got = paged_decode_attention(
            q, k_pool, v_pool, tables, valid, window=window, interpret=True
        )
        k_seq = k_pool[tables].reshape(b, p_per * pg, hkv, d)
        v_seq = v_pool[tables].reshape(b, p_per * pg, hkv, d)
        want = decode_attention(
            q[:, None], k_seq, v_seq, valid, window=window
        )[:, 0]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
            err_msg=f"window={window}",
        )


def test_decode_step_paged_kernel_sliding_window_config():
    """A sliding-window config (Mistral-style) routed through the paged
    kernel must match the gather path's logits."""
    from llm_consensus_tpu.models.transformer import decode_step_paged

    wcfg = CFG.with_(sliding_window=6)
    cache = PagedKVCache.create(
        wcfg, n_pages=10, page_size=4, max_seqs=2, pages_per_seq=4
    )
    cache = assign_pages(cache, jnp.int32(0), jnp.asarray([2, 5, 7, 9]))
    cache = assign_pages(cache, jnp.int32(1), jnp.asarray([1, 3, 0, 0]))
    params = _params()
    L, hkv, d = wcfg.n_layers, wcfg.n_kv_heads, wcfg.head_dim
    k_seq = jax.random.normal(jax.random.PRNGKey(10), (L, 8, hkv, d))
    cache = write_prefill_kv(cache, jnp.int32(0), k_seq, k_seq, jnp.int32(8))
    cache = write_prefill_kv(
        cache, jnp.int32(1), k_seq[:, :4], k_seq[:, :4], jnp.int32(3)
    )
    toks = jnp.asarray([[11], [23]], jnp.int32)
    logits_ref, _ = decode_step_paged(wcfg, params, toks, cache)
    logits_krn, _ = decode_step_paged(
        wcfg.with_(use_pallas=True), params, toks, cache
    )
    np.testing.assert_allclose(
        np.asarray(logits_krn), np.asarray(logits_ref),
        rtol=2e-4, atol=2e-4,
    )


def test_hit_stop_confirms_window_hit_against_full_text():
    """r4 advisor: a merge-based tokenizer can decode a TAIL WINDOW
    differently from the full text at the window head, so a window-only
    stop check can false-positive and retire the row early — output
    silently truncated while the final earliest_stop_cut finds no stop.
    _hit_stop must confirm candidate hits against the full decode."""
    from types import SimpleNamespace

    from llm_consensus_tpu.serving.continuous import ContinuousBatcher
    from llm_consensus_tpu.utils.stops import VisibleIdFilter

    class MergeTok:
        """Context-sensitive decode: id 2 alone is "b", but after id 1
        the pair [1, 2] merges to "aX" (no "b" anywhere)."""

        eos_id = 99

        def decode(self, ids):
            out = []
            prev = None
            for t in ids:
                if prev == 1 and t == 2:
                    out[-1] = "aX"
                else:
                    out.append({1: "a", 2: "b"}.get(t, "?"))
                prev = t
            return "".join(out)

    tok = MergeTok()
    host = SimpleNamespace(
        tokenizer=tok,
        _vis_filter=VisibleIdFilter(tok, skip_ids=(tok.eos_id,)),
    )
    host._decoded_text = lambda s: ContinuousBatcher._decoded_text(host, s)
    slot = SimpleNamespace(
        generated=[1, 2],
        request=SimpleNamespace(stop=("b",), stop_window=1),
    )
    # Window [2] decodes "b" (candidate hit); full text "aX" has no
    # stop -> must NOT retire.
    assert not ContinuousBatcher._hit_stop(host, slot)
    # A genuine stop (newest token decodes "b" in the full text too)
    # still hits.
    slot2 = SimpleNamespace(
        generated=[1, 2, 2],
        request=SimpleNamespace(stop=("b",), stop_window=1),
    )
    assert ContinuousBatcher._hit_stop(host, slot2)


def test_continuous_batcher_on_mesh_matches_single_device():
    """Mesh batcher (slots + page pool over `data`, kv heads over
    `model`, slot-affinity page allocation) serves byte-identical text
    to the single-device batcher for the same greedy burst (round-4
    verdict item 4)."""
    from llm_consensus_tpu.parallel.mesh import MeshConfig, make_mesh

    params = _params()
    ccfg = ContinuousConfig(
        max_slots=4,
        page_size=16,
        n_pages=64,
        pages_per_seq=8,
        max_new_tokens=8,
        seq_buckets=(16, 32, 64),
    )
    prompts = ["hello world", "the quick brown fox", "abc", "mesh", "zed"]

    plain = ContinuousBatcher(CFG, params, config=ccfg)
    try:
        want = [
            f.result(timeout=120).text
            for f in [plain.submit(p) for p in prompts]
        ]
    finally:
        plain.close()

    mesh = make_mesh(MeshConfig(data=4, model=2))
    sharded = ContinuousBatcher(CFG, params, config=ccfg, mesh=mesh)
    try:
        got = [
            f.result(timeout=120).text
            for f in [sharded.submit(p) for p in prompts]
        ]
        stats = sharded.stats()
    finally:
        sharded.close()
    assert got == want
    assert stats["completed_requests"] == len(prompts)
    assert stats["free_pages"] == 63  # all pages returned (page 0 reserved)


def test_mesh_batcher_rejects_indivisible_shapes():
    from llm_consensus_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(data=4, model=2))
    with pytest.raises(ValueError, match="multiples of the mesh"):
        ContinuousBatcher(
            CFG,
            _params(),
            config=ContinuousConfig(
                max_slots=3, page_size=16, n_pages=64, pages_per_seq=8
            ),
            mesh=mesh,
        )


@pytest.mark.parametrize("rounds", [1, 4])
def test_continuous_chunk_size_invariance(rounds):
    """decode_rounds AND pipeline_depth are pure throughput knobs:
    rounds 1/4 x depth 1/2 all serve identical text for the same greedy
    AND sampled requests (the per-token PRNG stream is (seed, index),
    independent of how many rounds ride one program or how many
    programs ride in flight)."""
    params = _params()

    def run(rounds, depth):
        b = ContinuousBatcher(
            CFG,
            params,
            config=ContinuousConfig(
                max_slots=4,
                page_size=16,
                n_pages=64,
                pages_per_seq=8,
                max_new_tokens=8,
                seq_buckets=(16, 32, 64),
                decode_rounds=rounds,
                pipeline_depth=depth,
            ),
        )
        try:
            futs = [
                b.submit("hello world"),
                b.submit("the quick", temperature=0.9, seed=7),
                b.submit("abc", temperature=1.3, seed=11),
            ]
            return [f.result(timeout=120).text for f in futs]
        finally:
            b.close()

    want = run(1, 1)
    if rounds > 1:
        assert run(rounds, 1) == want
    assert run(rounds, 2) == want
