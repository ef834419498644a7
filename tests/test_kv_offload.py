"""Hierarchical KV cache: host-RAM offload tier (PR 4).

Covers the byte-budgeted :class:`HostPageStore` (LRU, verbatim planes,
overflow drops), the ``install_page`` promote primitive (demote→restore
round trips bit-identical to fresh prefill, bf16 AND int8-with-scales),
the ``reclaimable_pages`` invariant repair, and the ContinuousBatcher
end to end: eviction demotes, a later same-prefix admission restores
instead of re-prefilling (byte-identical pages, identical text), a
concurrent burst dedups against the in-flight restore.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.engine.engine import plan_memory
from llm_consensus_tpu.models.cache import quantize_kv
from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.paged_cache import (
    PagedKVCache,
    PagePool,
    PrefixRegistry,
    install_page,
)
from llm_consensus_tpu.models.transformer import init_params
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)
from llm_consensus_tpu.serving.offload import HostPageStore, page_planes

CFG = get_config("test-tiny")


def _params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def _planes(*shapes_dtypes, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for shape, dt in shapes_dtypes:
        a = rng.standard_normal(shape) * 10
        out.append(a.astype(dt))
    return tuple(out)


# ---------------------------------------------------------------------------
# HostPageStore: LRU + byte budget + verbatim planes
# ---------------------------------------------------------------------------


def test_host_store_budget_drops_lru_cleanly():
    page = _planes(((4, 8), np.float32))  # 128 B/page
    store = HostPageStore(budget_bytes=3 * 128)
    for i in range(3):
        assert store.put(("chain", i), _planes(((4, 8), np.float32), seed=i))
    assert len(store) == 3 and store.bytes_used == 3 * 128
    # Refresh chain 0 (MRU), then overflow: chain 1 is now LRU and drops.
    assert store.get(("chain", 0)) is not None
    assert store.put(("chain", 3), page)
    assert len(store) == 3 and store.bytes_used == 3 * 128
    assert ("chain", 1) not in store
    assert ("chain", 0) in store and ("chain", 2) in store
    assert store.dropped_pages == 1
    # A page bigger than the whole budget is refused, not thrashed in.
    assert not store.put(("huge",), _planes(((40, 80), np.float32)))
    assert ("huge",) not in store and len(store) == 3
    assert store.dropped_pages == 2
    # touch() refreshes recency without re-fetching content.
    store.touch(("chain", 2))
    store.put(("chain", 4), page)
    assert ("chain", 0) not in store and ("chain", 2) in store
    assert store.demoted_pages == 6  # 5 puts that landed + 1 touch


def test_host_store_roundtrips_int8_planes_with_scales_verbatim():
    """int8-KV pages spill VERBATIM with their scales: same dtype, same
    bytes back — the store never recompresses or casts."""
    k = np.random.default_rng(0).standard_normal((2, 8, 2, 4))
    kq, ks = quantize_kv(jnp.asarray(k, jnp.float32))
    planes = (
        np.asarray(kq),
        np.asarray(kq)[::-1].copy(),
        np.asarray(ks),
        np.asarray(ks) + 1,
    )
    store = HostPageStore(budget_bytes=1 << 20)
    assert store.put(("q",), planes)
    got = store.get(("q",))
    assert store.hits == 1 and store.lookups == 1
    for a, b in zip(planes, got):
        assert a.dtype == b.dtype
        assert a.tobytes() == b.tobytes()
    assert got[0].dtype == np.int8 and got[2].dtype == np.float32
    assert store.get(("missing",)) is None
    assert store.lookups == 2 and store.hits == 1


# ---------------------------------------------------------------------------
# Demote → restore round trip at the cache level (bf16 and int8 pools)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
def test_page_round_trip_bit_identical_to_fresh(dtype):
    """A page that leaves through page_planes and returns through
    install_page must be BIT-identical to the freshly-written one —
    bf16 pools and int8-KV pools alike (int8 values pass verbatim; a
    quant pool's scale planes ride the store unchanged, covered
    above)."""
    cache = PagedKVCache.create(
        CFG, n_pages=4, page_size=8, max_seqs=2, pages_per_seq=2,
        dtype=dtype,
    )
    rng = np.random.default_rng(1)
    fresh = rng.standard_normal(cache.k.shape) * 100
    cache = PagedKVCache(
        k=jnp.asarray(fresh).astype(dtype),
        v=jnp.asarray(fresh[::-1].copy()).astype(dtype),
        page_table=cache.page_table,
        length=cache.length,
    )
    want_k = np.asarray(cache.k[:, 2])
    want_v = np.asarray(cache.v[:, 2])

    store = HostPageStore(budget_bytes=1 << 24)
    store.put(("c",), page_planes(cache, 2))
    # Destroy the device copy (eviction), then promote back from host.
    zero = jnp.zeros_like(cache.k[:, 2])
    cache = PagedKVCache(
        k=cache.k.at[:, 2].set(zero),
        v=cache.v.at[:, 2].set(zero),
        page_table=cache.page_table,
        length=cache.length,
    )
    pk, pv = store.get(("c",))
    cache = install_page(cache, jnp.int32(2), jnp.asarray(pk), jnp.asarray(pv))
    assert np.asarray(cache.k[:, 2]).tobytes() == want_k.tobytes()
    assert np.asarray(cache.v[:, 2]).tobytes() == want_v.tobytes()


# ---------------------------------------------------------------------------
# reclaimable_pages invariant (the PR-3 stats drift)
# ---------------------------------------------------------------------------


def test_reclaimable_counts_only_what_evict_can_free():
    """The drift: a registry-only parent above a child some live
    sequence still maps is NOT freeable (evict drops childless leaves
    only) and must not be counted — reclaimable_pages() must equal
    exactly what evict(∞) frees, and pool accounting must balance."""
    pool = PagePool(range(1, 8))
    reg = PrefixRegistry(pool, 4)
    ids = list(range(100, 112))  # 3 full pages: chain A -> B -> C
    pages = pool.alloc(3)
    created = reg.register(ids, pages)
    for node, _ in created:
        reg.mark_ready(node)
    # The "sequence" keeps only B mapped; A and C are registry-only.
    pool.release(pages[0])
    pool.release(pages[2])
    # C (leaf, rc 1) is evictable; A (rc 1) sits ABOVE pinned B and is
    # not reachable by leaf eviction while B lives.
    want = reg.reclaimable_pages()
    assert want == 1
    total = 7
    pinned = pool.held - want  # pages some holder other than evict() pins
    assert pool.available + pinned + want == total
    assert reg.evict(999) == want
    assert reg.reclaimable_pages() == 0
    # The sequence retires B: now B (leaf) then A (exposed parent) free.
    pool.release(pages[1])
    want2 = reg.reclaimable_pages()
    assert want2 == 2
    assert reg.evict(999) == want2
    assert pool.available == total


# ---------------------------------------------------------------------------
# ContinuousBatcher end to end
# ---------------------------------------------------------------------------

_HEADER = "Panel shared header for every persona, forty ch: "  # 49 chars
_FILLERS = [
    f"{i} unique filler prompt with plenty of padding text."
    for i in range(3)
]
# Starved pool: 10 usable pages vs a 5-page unshared request — cached
# prefixes cannot survive a filler round device-side.
_OCFG = dict(
    max_slots=2,
    page_size=16,
    n_pages=11,
    pages_per_seq=8,
    max_new_tokens=4,
    seq_buckets=(16, 32, 64),
    prefill_chunk=16,
    share_prefix=True,
)


def _serve(batcher, prompts, **kw):
    futs = [batcher.submit(p, **kw) for p in prompts]
    return [f.result(timeout=120).text for f in futs]


def _rounds(batcher):
    """The multi-round panel shape: header burst, unique-prefix filler
    burst (forces eviction of the header's registry pages), header
    re-vote burst."""
    out = [_serve(batcher, [_HEADER + f"q{i}" for i in range(3)])]
    out.append(_serve(batcher, _FILLERS))
    out.append(_serve(batcher, [_HEADER + f"r{i}" for i in range(3)]))
    return out


def test_offload_restore_matches_fresh_prefill_end_to_end():
    """The acceptance criterion: the same 3-round traffic served with
    the host tier ON (round 3 RESTORES the demoted header) and OFF
    (round 3 re-prefills it) produces byte-identical text — and the
    restored device page holds exactly the bytes the fresh prefill
    wrote (compared via the spilled host copy, which install_page
    writes back verbatim)."""
    params = _params()
    b_off = ContinuousBatcher(
        CFG, params, config=ContinuousConfig(**_OCFG, host_cache_bytes=0)
    )
    try:
        want = _rounds(b_off)
        s_off = b_off.stats()
    finally:
        b_off.close()
    assert s_off["offload_demoted_pages"] == 0
    assert s_off["prefix_evictions"] > 0  # the pool really is starved

    b_on = ContinuousBatcher(
        CFG, params,
        config=ContinuousConfig(**_OCFG, host_cache_bytes=64 << 20),
    )
    try:
        got = [_serve(b_on, [_HEADER + f"q{i}" for i in range(3)])]
        # Fresh-prefill bytes of the header's first page, read before
        # the filler round evicts it.
        reg = b_on._registries[0]
        ids = b_on.tokenizer.encode(_HEADER + "q0")
        key0 = tuple(int(t) for t in ids[:16])
        node0 = reg._root.children[key0]
        fresh = page_planes(b_on.cache, node0.page)
        got.append(_serve(b_on, _FILLERS))
        got.append(_serve(b_on, [_HEADER + f"r{i}" for i in range(3)]))
        s_on = b_on.stats()
        # Round 3 re-registered the restored header page under a fresh
        # page id; its device content must be bit-equal to the fresh
        # prefill's.
        node1 = reg._root.children[key0]
        restored = page_planes(b_on.cache, node1.page)
    finally:
        b_on.close()

    assert got == want
    assert s_on["offload_demoted_pages"] > 0
    assert s_on["offload_restored_pages"] >= 3  # the header's full pages
    assert s_on["offload_dropped_pages"] == 0
    assert s_on["offload_host_bytes"] > 0
    # Restores replaced prefill work: fewer chunks than the off leg.
    assert s_on["prefill_chunks"] < s_off["prefill_chunks"]
    assert s_on["free_pages"] == s_on["total_pages"]
    for a, b in zip(fresh, restored):
        assert a.tobytes() == b.tobytes()


def test_concurrent_burst_dedups_against_inflight_restore():
    """The panel re-vote submitted ALL AT ONCE after the header was
    demoted: the first admission schedules the restore; burst-mates
    must dedup against the IN-FLIGHT restore through the same
    readiness gates as an in-flight prefill — the header's pages
    restore exactly once, not once per request."""
    from llm_consensus_tpu.server.metrics import (
        KV_OFFLOAD_RESTORED,
        KV_RESTORE_SECONDS,
    )

    params = _params()
    b = ContinuousBatcher(
        CFG, params,
        config=ContinuousConfig(**_OCFG, host_cache_bytes=64 << 20),
    )
    try:
        seq = [_serve(b, [_HEADER + f"q{i}"])[0] for i in range(3)]
        _serve(b, _FILLERS)
        assert b.stats()["offload_demoted_pages"] > 0
        before = b.stats()["offload_restored_pages"]
        m_before = KV_OFFLOAD_RESTORED.value
        h_before = KV_RESTORE_SECONDS.count
        # Same prompts, same seeds, submitted concurrently this time.
        burst = _serve(b, [_HEADER + f"q{i}" for i in range(3)])
        stats = b.stats()
    finally:
        b.close()
    assert burst == seq
    restored = stats["offload_restored_pages"] - before
    # 3 full header pages of the q0 prompt (49+1 ids, page 16) restore
    # ONCE; the other 2 burst-mates map them (shared, not restored).
    assert restored == 3
    # Prometheus families moved in lockstep with the batcher's stats.
    assert KV_OFFLOAD_RESTORED.value - m_before == restored
    assert KV_RESTORE_SECONDS.count - h_before == restored


def test_offload_disabled_without_sharing_or_chunking():
    """The tier needs prefix sharing (restores ride the registry's
    readiness gates): a share_prefix=False batcher silently runs
    without it rather than half-engaging."""
    params = _params()
    b = ContinuousBatcher(
        CFG, params,
        config=ContinuousConfig(
            **{**_OCFG, "share_prefix": False},
            host_cache_bytes=64 << 20,
        ),
    )
    try:
        assert b._offload is None
        out = _serve(b, [_HEADER + "legacy"])
    finally:
        b.close()
    assert len(out) == 1 and isinstance(out[0], str)


def test_plan_memory_includes_host_tier():
    plan = plan_memory(
        CFG, kv_quant=False, host_cache_bytes=1 << 20, page_size=64
    )
    assert plan["host_cache_bytes"] == 1 << 20
    assert plan["host_capacity_pages"] == (1 << 20) // plan["host_page_bytes"]
    assert plan["host_capacity_tokens"] == plan["host_capacity_pages"] * 64
    # int8-KV pages (scales included) are smaller, so more fit.
    q = plan_memory(
        CFG, kv_quant=True, host_cache_bytes=1 << 20, page_size=64
    )
    assert q["host_page_bytes"] < plan["host_page_bytes"]
    assert q["host_capacity_pages"] > plan["host_capacity_pages"]
    # Host RAM never changes the device-fit verdict.
    base = plan_memory(CFG, hbm_bytes=16 << 30)
    tiered = plan_memory(
        CFG, hbm_bytes=16 << 30, host_cache_bytes=1 << 30
    )
    assert tiered["fits"] == base["fits"]
    assert tiered["total_bytes"] == base["total_bytes"]
    assert "host_cache_bytes" not in base  # opt-in output
