"""Mesh-native serving hot path (PR 13, serving/continuous.py +
models/transformer.py + ops/pallas/attention.py).

The contract: a dp2×mp2 mesh batcher serves BYTE-IDENTICAL text to the
single-device batcher with every serving feature engaged — fused ragged
dispatch, grouped prefix attention, multi-round decode, speculative
decoding, and the host KV tier all lost their mesh fallbacks. The
parity grid sweeps {ragged on/off} × {decode_rounds 1,4} × {spec
on/off} × {pipeline_depth 1,2}; the kernel-level test drives the
shard_map'd Pallas ragged program against the XLA reference; the cost
model's multi-round accounting (R rounds of KV reads, ONE weight read
per program) is pinned on and off mesh.
"""

import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.transformer import (
    init_params,
    kv_plane_token_bytes,
    model_param_bytes,
    ragged_mesh_shardable,
)
from llm_consensus_tpu.parallel.mesh import MeshConfig, make_mesh
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)

CFG = get_config("test-tiny")

# Panel-shaped burst: two shared headers (prefix groups + shared draft
# streams form) plus unique prompts (ungrouped rows coexist). Headers
# are EXACTLY 2 pages at page_size 16 and the questions diverge at
# their first token, so the share plan is deterministic — 2 full pages
# mapped, no boundary-page candidate whose readiness (a race against
# the donor's prefill) could flip the plan between shared and
# unshared across runs.
_HEADER_A = "shared mesh panel header alpha!!"  # 32 chars = 2 pages
_HEADER_B = "other shared panel header beta!!"
assert len(_HEADER_A) == len(_HEADER_B) == 32
PROMPTS = [
    _HEADER_A + "one?",
    _HEADER_A + "two?",
    "a unique short prompt",
    _HEADER_B + "three?",
    _HEADER_B + "four?",
    "another unique tail prompt?",
]


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def _mesh22():
    return make_mesh(MeshConfig(data=2, model=2), devices=jax.devices()[:4])


def _ccfg(**kw):
    base = dict(
        max_slots=4,
        page_size=16,
        n_pages=64,
        pages_per_seq=8,
        max_new_tokens=8,
        seq_buckets=(16, 32, 64),
    )
    base.update(kw)
    return ContinuousConfig(**base)


def _quiesce(b, timeout=30.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        s = b.stats()
        if (
            not s["dispatch_inflight"]
            and not s["active_slots"]
            and not s["prefilling_slots"]
            and not s["waiting"]
        ):
            return s
        time.sleep(0.02)
    raise AssertionError("batcher did not quiesce")


def _serve(params, mesh=None, draft=False, prompts=PROMPTS, **kw):
    b = ContinuousBatcher(
        CFG,
        params,
        config=_ccfg(**kw),
        mesh=mesh,
        draft=(CFG, params) if draft else None,
    )
    try:
        futs = [b.submit(p) for p in prompts]
        texts = [f.result(timeout=300).text for f in futs]
        stats = _quiesce(b)
    finally:
        b.close()
    return texts, stats


@pytest.fixture(scope="module")
def reference(params):
    """Single-device default-config texts — the byte-parity oracle for
    every grid cell (ragged/rounds/spec/depth are all byte-invariant
    contracts, so one reference covers the whole grid)."""
    texts, _ = _serve(params)
    return texts


# ---------------------------------------------------------------------------
# Parity grid: dp2×mp2 vs single device, byte-identical text
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("rounds", [1, 4])
@pytest.mark.parametrize("ragged", [False, True])
def test_mesh_parity_grid_plain(params, reference, ragged, rounds, depth):
    texts, stats = _serve(
        params,
        mesh=_mesh22(),
        ragged_attention=ragged,
        decode_rounds=rounds,
        pipeline_depth=depth,
    )
    assert texts == reference
    assert stats["mesh_data_shards"] == 2
    assert stats["mesh_model_shards"] == 2
    if rounds == 4:
        # Multi-round decode really engaged on the mesh: at least one
        # dispatched program held more than one decode round.
        assert stats["decode_rounds_sum"] > stats["decode_rounds_count"]
    if ragged:
        assert stats["device_programs_fused"] > 0


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("rounds", [1, 4])
@pytest.mark.parametrize("ragged", [False, True])
def test_mesh_parity_grid_spec(params, reference, ragged, rounds, depth):
    """Speculative decoding on the mesh (self-draft: acceptance ~1.0,
    text must equal plain greedy for ANY draft). decode_rounds rides
    along: spec windows stay one verify round per dispatch, so the R
    cells prove composition, not R-round programs."""
    texts, stats = _serve(
        params,
        mesh=_mesh22(),
        draft=True,
        spec_k=2,
        ragged_attention=ragged,
        decode_rounds=rounds,
        pipeline_depth=depth,
    )
    assert texts == reference
    assert stats["device_programs_spec"] > 0
    assert stats["spec_accepted_tokens"] > 0


# ---------------------------------------------------------------------------
# Engagement: no fallback warnings, one program per iteration
# ---------------------------------------------------------------------------


def test_mesh_full_config_constructs_without_fallback_warnings(
    params, caplog
):
    """The acceptance criterion's warning half: a dp2×mp2 batcher with
    EVERY feature configured (ragged fusion, R=4, spec draft, host
    tier) must not emit any engage-fallback warning — the old blanket
    spec-on-mesh / rounds-on-mesh warnings are gone, and nothing else
    fires for this config."""
    with caplog.at_level(
        logging.WARNING, logger="llm_consensus_tpu.serving.continuous"
    ):
        b = ContinuousBatcher(
            CFG,
            params,
            config=_ccfg(
                decode_rounds=4, spec_k=2, host_cache_bytes=32 << 20
            ),
            mesh=_mesh22(),
            draft=(CFG, params),
        )
        try:
            assert b._fused_ok
            assert b._rounds == 4 or b._spec_ok  # spec wins the window
            assert b._spec_ok
            assert b._offload is not None
        finally:
            b.close()
    assert not [r for r in caplog.records if r.levelno >= logging.WARNING]


def test_mesh_fused_one_program_per_iteration(params):
    """On the mesh with fusion on (spec
    off so chunks ride decode dispatches), the mixed burst runs EXACTLY
    one device program per scheduler work iteration."""
    b = ContinuousBatcher(CFG, params, config=_ccfg(), mesh=_mesh22())
    try:
        # Warm one request through so compile time doesn't stretch the
        # measured burst (the ratio is count-based, but keep the burst
        # representative).
        b.submit(_HEADER_A + "warm").result(timeout=300)
        _quiesce(b)
        s0 = b.stats()
        futs = [b.submit(p) for p in PROMPTS]
        [f.result(timeout=300) for f in futs]
        s1 = _quiesce(b)
    finally:
        b.close()
    programs = sum(
        s1[k] - s0[k]
        for k in (
            "device_programs_fused",
            "device_programs_decode",
            "device_programs_prefill",
        )
    )
    iters = s1["work_iterations"] - s0["work_iterations"]
    assert iters > 0
    assert programs == iters  # ratio == 1.0 exactly
    assert s1["device_programs_fused"] > s0["device_programs_fused"]


def test_mesh_grouped_prefix_attention_engages_with_pallas(params):
    """Grouped prefix attention on the mesh: a use_pallas config (the
    kernel runs interpreted on CPU, shard_map'd over dp2×mp2) forms
    groups, counts shared-KV savings, and still serves the exact text
    of the single-device Pallas batcher."""
    cfg = CFG.with_(use_pallas=True)
    assert ragged_mesh_shardable(cfg, _mesh22(), 4, 64)
    prompts = PROMPTS[:4]

    def run(mesh):
        b = ContinuousBatcher(cfg, params, config=_ccfg(), mesh=mesh)
        try:
            futs = [b.submit(p) for p in prompts]
            texts = [f.result(timeout=600).text for f in futs]
            stats = _quiesce(b)
        finally:
            b.close()
        return texts, stats

    want, s_one = run(None)
    got, s_mesh = run(_mesh22())
    assert got == want
    assert s_one["shared_kv_bytes_saved"] > 0
    # Groups form per data shard (pages never share across shards);
    # with the panel split over two shards the savings shrink but the
    # grouped read really runs on the mesh.
    assert s_mesh["shared_kv_bytes_saved"] > 0
    assert s_mesh["decode_group_peak"] >= 2


# ---------------------------------------------------------------------------
# Sharded ragged kernel vs XLA reference (kernel level)
# ---------------------------------------------------------------------------


def test_sharded_ragged_kernel_matches_reference():
    """shard_map'd Pallas ragged program (heads over model, rows/pages
    over data, rebased tables) vs the ungrouped XLA reference: decode
    rows, the chunk lane on its owner shard, shard-local groups, the
    sliding window, and the NQ-query verify lane."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from llm_consensus_tpu.ops.attention import (
        ragged_paged_attention_reference,
    )
    from llm_consensus_tpu.ops.pallas.attention import (
        ragged_paged_attention_sharded,
    )

    mesh = _mesh22()
    key = jax.random.PRNGKey(0)
    b, h, hkv, d = 4, 4, 2, 128
    n_pages, pg, p_per = 16, 8, 4  # 8 pages per data shard
    ks = jax.random.split(key, 8)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (n_pages, pg, hkv, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (n_pages, pg, hkv, d), jnp.float32)
    # Rows 0,1 draw pages from shard 0's range [0, 8); rows 2,3 from
    # shard 1's [8, 16) — the allocator's slot→shard affinity. Rows
    # 0/1 share page 1, rows 2/3 share page 8 (two shard-local
    # groups). Every row's valid length stays within its mapped pages
    # (the serving invariant the rebase clamp relies on).
    table = np.zeros((b, p_per), np.int32)
    table[0] = [1, 2, 3, 0]
    table[1] = [1, 4, 0, 0]
    table[2] = [8, 9, 0, 0]
    table[3] = [8, 10, 11, 0]
    valid = np.asarray([22, 13, 11, 23], np.int32)
    gid = np.asarray([0, 0, 1, 1], np.int32)
    rep = np.asarray([0, 2], np.int32)
    gend = np.asarray([8, 8], np.int32)
    sstart = np.asarray([8, 8, 8, 8], np.int32)
    cq = 4
    q_chunk = jax.random.normal(ks[3], (cq, h, d), jnp.float32)
    chunk_table = np.zeros((p_per,), np.int32)
    chunk_table[:2] = [12, 13]  # owner: shard 1
    chunk_start = jnp.int32(8)

    pool_sh = NamedSharding(mesh, P("data", None, "model", None))
    sq = jax.device_put(q, NamedSharding(mesh, P("data", "model", None)))
    skp = jax.device_put(k_pool, pool_sh)
    svp = jax.device_put(v_pool, pool_sh)
    stab = jax.device_put(
        jnp.asarray(table), NamedSharding(mesh, P("data", None))
    )
    sval = jax.device_put(
        jnp.asarray(valid), NamedSharding(mesh, P("data"))
    )

    ref_dec, ref_ch = ragged_paged_attention_reference(
        q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(valid),
        q_chunk=q_chunk, chunk_table=jnp.asarray(chunk_table),
        chunk_start=chunk_start,
    )
    out_dec, out_ch = jax.jit(
        lambda *a: ragged_paged_attention_sharded(
            mesh, *a,
            q_chunk=q_chunk, chunk_table=jnp.asarray(chunk_table),
            chunk_start=chunk_start,
            groups=(
                jnp.asarray(gid), jnp.asarray(rep),
                jnp.asarray(gend), jnp.asarray(sstart),
            ),
        )
    )(sq, skp, svp, stab, sval)
    np.testing.assert_allclose(
        np.asarray(out_dec), np.asarray(ref_dec), atol=2e-5, rtol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(out_ch), np.asarray(ref_ch), atol=2e-5, rtol=2e-5
    )

    # Sliding window, no groups/chunk.
    ref_w = ragged_paged_attention_reference(
        q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(valid), window=9
    )
    out_w = jax.jit(
        lambda *a: ragged_paged_attention_sharded(mesh, *a, window=9)
    )(sq, skp, svp, stab, sval)
    np.testing.assert_allclose(
        np.asarray(out_w), np.asarray(ref_w), atol=2e-5, rtol=2e-5
    )

    # NQ-query verify lane (the spec path's attention shape).
    nq = 3
    qv = jax.random.normal(ks[4], (b, nq, h, d), jnp.float32)
    sqv = jax.device_put(
        qv, NamedSharding(mesh, P("data", None, "model", None))
    )
    ref_v = ragged_paged_attention_reference(
        qv, k_pool, v_pool, jnp.asarray(table), jnp.asarray(valid)
    )
    out_v = jax.jit(
        lambda *a: ragged_paged_attention_sharded(mesh, *a)
    )(sqv, skp, svp, stab, sval)
    np.testing.assert_allclose(
        np.asarray(out_v), np.asarray(ref_v), atol=2e-5, rtol=2e-5
    )


def test_ragged_mesh_shardable_predicate(params, caplog):
    mesh = _mesh22()
    assert ragged_mesh_shardable(CFG, mesh, 4, 64)  # Hkv=2 % mp=2 == 0
    draft = get_config("test-tiny-draft")
    assert not ragged_mesh_shardable(draft, mesh, 4, 64)  # Hkv=1 % 2
    assert not ragged_mesh_shardable(CFG, mesh, 3, 64)  # slots % dp
    assert not ragged_mesh_shardable(CFG, None, 4, 64)
    # A non-shardable use_pallas mesh config: the remaining-reason
    # warning fires once, and grouped decode stays OFF (the reference
    # fallback ignores groups — telemetry must not claim savings the
    # program doesn't perform).
    dparams = init_params(draft, jax.random.PRNGKey(0), dtype=jnp.float32)
    with caplog.at_level(
        logging.WARNING, logger="llm_consensus_tpu.serving.continuous"
    ):
        b = ContinuousBatcher(
            draft.with_(use_pallas=True), dparams, config=_ccfg(),
            mesh=mesh,
        )
        try:
            assert not b._group_decode
        finally:
            b.close()
    assert any(
        "cannot shard over this mesh" in r.message for r in caplog.records
    )


# ---------------------------------------------------------------------------
# Host tier on the mesh: demote → restore bit identity
# ---------------------------------------------------------------------------


def test_mesh_host_tier_demote_restore_bit_identity(params):
    """The PR-4 round-trip contract on SHARDED planes: the demote
    device_get assembles the page's shard slices, the restore
    install_page scatters them back through the pool's NamedSharding,
    and the restored device page holds exactly the fresh prefill's
    bytes — text unchanged across the eviction."""
    from llm_consensus_tpu.serving.offload import page_planes

    header = "mesh offload header payload " * 3  # > 1 full page
    b = ContinuousBatcher(
        CFG,
        params,
        config=_ccfg(n_pages=32, max_new_tokens=6,
                     host_cache_bytes=64 << 20),
        mesh=_mesh22(),
    )
    try:
        t1 = b.submit(header + "Q?").result(timeout=300).text
        _quiesce(b)
        # Fresh-prefill bytes of the header's first full page (the
        # admitting slot's shard registry holds the chain). The
        # registry keys on the ADMITTED ids — the prompt left-truncates
        # to the largest bucket.
        ids = b.tokenizer.encode(header + "Q?")[-64:]
        key0 = tuple(int(t) for t in ids[:16])
        reg = next(
            r for r in b._registries if key0 in r._root.children
        )
        node0 = reg._root.children[key0]
        fresh = page_planes(b.cache, node0.page)
        # Filler storm starves the pool → the header's registry pages
        # demote to the host tier.
        fills = [
            b.submit(f"filler {i} " * 6 + "?") for i in range(10)
        ]
        [f.result(timeout=300) for f in fills]
        t2 = b.submit(header + "Q?").result(timeout=300).text
        _quiesce(b)
        s = b.stats()
        node1 = reg._root.children[key0]
        restored = page_planes(b.cache, node1.page)
    finally:
        b.close()
    assert t1 == t2
    assert s["offload_demoted_pages"] > 0
    assert s["offload_restored_pages"] > 0
    for a, bb in zip(fresh, restored):
        assert a.tobytes() == bb.tobytes()


# ---------------------------------------------------------------------------
# Cost model at R > 1 (PR-12 residual): R rounds of KV reads, ONE
# weight read per program — on and off mesh
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("on_mesh", [False, True])
def test_rounds_cost_model_lockstep(params, on_mesh):
    """gateway_program_mbu{decode}'s inputs at R=4 vs R=1: the SAME
    generation reads the SAME KV token total (sum over steps of L+j is
    window-invariant) and writes the same count, while the weight term
    is counted once per PROGRAM — so hbm bytes drop by exactly
    (programs_R1 - programs_R4) × weight_bytes. max_new_tokens chosen
    so 8 decoded tokens split into exact windows (no early-exit slack
    inflating the planned R)."""
    weight_bytes, _ = model_param_bytes(params)
    kv_tok = kv_plane_token_bytes(CFG, jnp.bfloat16)
    mesh = _mesh22() if on_mesh else None
    prompt = ["one lone cost-model request?"]

    def run(rounds):
        _, s = _serve(
            params,
            mesh=mesh,
            prompts=prompt,
            pipeline_depth=1,
            max_new_tokens=9,  # 1 prefill-sampled + 8 decoded = 2×R4
            decode_rounds=rounds,
        )
        return s

    s1, s4 = run(1), run(4)
    assert s1["device_programs_decode"] == 8
    assert s4["device_programs_decode"] == 2
    assert s4["device_rounds_total"] == 8
    # KV totals are window-invariant; the weight term is per program.
    assert (
        s1["mbu_kv_read_tokens_decode"] == s4["mbu_kv_read_tokens_decode"]
    )
    assert (
        s1["mbu_kv_write_tokens_decode"]
        == s4["mbu_kv_write_tokens_decode"]
        == 8
    )
    for s in (s1, s4):
        kv_bytes = (
            s["mbu_kv_read_tokens_decode"] + s["mbu_kv_write_tokens_decode"]
        ) * kv_tok
        assert (
            s["mbu_hbm_bytes_decode"] - kv_bytes
            == s["device_programs_decode"] * weight_bytes
        )


# ---------------------------------------------------------------------------
# Metrics lockstep for the new family
# ---------------------------------------------------------------------------


def test_mesh_shards_gauge_lockstep(params):
    from llm_consensus_tpu.server.metrics import MESH_SHARDS

    b = ContinuousBatcher(CFG, params, config=_ccfg(), mesh=_mesh22())
    try:
        s = b.stats()
        assert s["mesh_data_shards"] == 2
        assert s["mesh_model_shards"] == 2
        assert MESH_SHARDS.labels(axis="data").value == 2
        assert MESH_SHARDS.labels(axis="model").value == 2
    finally:
        b.close()
    b = ContinuousBatcher(CFG, params, config=_ccfg())
    try:
        s = b.stats()
        assert s["mesh_data_shards"] == 1
        assert s["mesh_model_shards"] == 1
        assert MESH_SHARDS.labels(axis="data").value == 1
    finally:
        b.close()
