"""The paged step programs' layer loop (PR 27).

The four paged programs (decode, verify, chunk, fused) share one layer
loop, ``transformer._paged_layers``: a scan over the layer INDEX with
the weight stacks and both KV pools resident. Two contracts, on the CPU
with ``test-tiny``:

- STRUCTURE: in each program's jaxpr (and in the batcher's multi-round
  program, which nests the decode step in an outer scan of the rounds;
  its one-step program keeps a scan of length 1 there) the layer scan
  carries both pools, scans nothing but the layer ids and stacks no
  output; compiled, the program's temp stays under one pool's size. A
  pool that enters as ``xs`` is copied out layer by layer for the
  kernels, and one that leaves as ``ys`` is a fresh buffer that the
  donated cache cannot alias.
- PARITY: each program returns the bytes — logits or hidden states, and
  both pools — of a plain Python loop over per-layer slices of the same
  weights and pools, a frozen row's write landing in the NULL page.

And of the batcher that jits them: a bucket's fused program is built
while nothing decodes, before a chunk first rides a dispatch; "last
chunk or not" is data, not a program of its own; and a resized group cap
is served by a program of the new shape.
"""

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.models import transformer as T
from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.paged_cache import NULL_PAGE, PagedKVCache
from llm_consensus_tpu.ops.quant import quantize_params
from llm_consensus_tpu.ops.rope import apply_rope
from llm_consensus_tpu.serving.continuous import (
    _SCREEN_W,
    ContinuousBatcher,
    ContinuousConfig,
)

# Three layers and two rounds: the layer scan is the one of length 3.
CFG = get_config("test-tiny").with_(n_layers=3)
ROUNDS = 2
PAGE, SLOTS, PPS, CHUNK, NQ = 16, 4, 8, 16, 3


@pytest.fixture(scope="module")
def params():
    """int8 weight-only, as the cells serve them."""
    return quantize_params(
        T.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    )


def _cache(n_pages: int, dtype, seed: int = 0) -> PagedKVCache:
    """Pools of noise, every slot on pages of its own at a mid-page
    fill; the chunk's pages are the next ``PPS`` ones."""
    rng = np.random.default_rng(seed)
    shape = (CFG.n_layers, n_pages, PAGE, CFG.n_kv_heads, CFG.head_dim)
    tables = 1 + np.arange(SLOTS * PPS, dtype=np.int32).reshape(SLOTS, PPS)
    return PagedKVCache(
        k=jnp.asarray(rng.standard_normal(shape), dtype),
        v=jnp.asarray(rng.standard_normal(shape), dtype),
        page_table=jnp.asarray(tables),
        length=jnp.asarray([21, 5, 47, 30], jnp.int32),
    )


def _chunk_args():
    table = 1 + SLOTS * PPS + np.arange(PPS, dtype=np.int32)
    rng = np.random.default_rng(1)
    tokens = rng.integers(1, CFG.vocab_size, (1, CHUNK)).astype(np.int32)
    return jnp.asarray(tokens), jnp.asarray(table), jnp.int32(19)


def _tokens(width: int):
    rng = np.random.default_rng(2)
    return jnp.asarray(
        rng.integers(1, CFG.vocab_size, (SLOTS, width)).astype(np.int32)
    )


def _program(name: str, cfg, params, cache):
    """(function of (params, cache), donated argument) for one of the
    four programs, with every other argument bound."""
    chunk_tokens, chunk_table, chunk_start = _chunk_args()
    if name == "decode":
        return lambda p, c: T.decode_step_paged(cfg, p, _tokens(1), c)
    if name == "decode-frozen-row":
        mask = jnp.asarray([True, False, True, True])
        return lambda p, c: T.decode_step_paged(
            cfg, p, _tokens(1), c, write_mask=mask
        )
    if name == "verify":
        return lambda p, c: T.verify_step_paged(cfg, p, _tokens(NQ), c)
    if name == "chunk":
        return lambda p, c: T.prefill_chunk_paged(
            cfg, p, chunk_tokens, chunk_table, chunk_start, c
        )
    if name == "fused":
        return lambda p, c: T.fused_step_paged(
            cfg, p, _tokens(1), c, chunk_tokens, chunk_table, chunk_start
        )
    raise ValueError(name)


# ---------------------------------------------------------------------------
# Structure
# ---------------------------------------------------------------------------


def _scans(jaxpr, depth: int = 0) -> list:
    """``(equation, number of scans around it)`` for every ``scan`` of
    ``jaxpr``, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        is_scan = eqn.primitive.name == "scan"
        if is_scan:
            found.append((eqn, depth))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found += _scans(inner, depth + is_scan)
    return found


def _scans_around(jaxpr, length: int) -> list:
    """For every scan of ``length`` in ``jaxpr``: how many scans
    enclose it."""
    return [d for e, d in _scans(jaxpr) if e.params["length"] == length]


def _batcher_trace(params, cache, rounds: int):
    """The batcher's multi-round program (``rounds`` > 1) or its
    one-step decode program, traced with arguments shaped as
    ``_dispatch`` builds them. The batcher serves nothing here."""
    b = ContinuousBatcher(
        CFG, params,
        config=ContinuousConfig(
            max_slots=SLOTS, page_size=PAGE, n_pages=64, pages_per_seq=PPS,
            max_new_tokens=8, seq_buckets=(16, 32, 64), prefill_chunk=CHUNK,
            decode_rounds=ROUNDS,
        ),
    )
    try:
        i32 = partial(jnp.zeros, dtype=jnp.int32)
        rows = (
            params, cache, i32((SLOTS,)),
            jnp.zeros((SLOTS,), jnp.uint32), i32((SLOTS,)),
            jnp.ones((SLOTS,), jnp.float32), i32((SLOTS,)),
            jnp.ones((SLOTS,), jnp.float32), False,
        )
        if rounds == 1:
            return b._jit_decode.trace(*rows, None)
        return b._jit_rounds.trace(
            rounds, *rows,
            jnp.full((SLOTS,), rounds, jnp.int32),
            jnp.full((SLOTS, _SCREEN_W), -1, jnp.int32), None,
        )
    finally:
        b.close()


def test_decode_step_program_is_one_step_under_a_scan_of_length_one(params):
    """``jit_decode_step`` applies the decode step once, and does so
    under a ``lax.scan`` of length 1: a measured decision, not a
    leftover (without it the chat cell's set-up took ~2 s longer on the
    chip's host though XLA compiles the same program: PERF.md, Findings
    PR 30). Whoever removes it measures ``setup_s`` beside it. The
    multi-round program's enclosing scan is the rounds'."""
    cache = _cache(64, jnp.float32)
    step = _batcher_trace(params, cache, 1)
    assert step.lower().as_text().startswith("module @jit_decode_step")
    assert _scans_around(step.jaxpr.jaxpr, 1) == [0]
    assert _scans_around(step.jaxpr.jaxpr, CFG.n_layers) == [1]
    rounds = _batcher_trace(params, cache, ROUNDS)
    assert _scans_around(rounds.jaxpr.jaxpr, CFG.n_layers) == [1]
    assert _scans_around(rounds.jaxpr.jaxpr, ROUNDS) == [0]


@pytest.mark.parametrize(
    "name", ["decode", "verify", "chunk", "fused", "rounds_step"]
)
def test_layer_scan_carries_the_pools_and_stacks_nothing(params, name):
    # float32 pools for the compiler's sake: XLA's CPU backend widens a
    # bfloat16 scatter's whole operand to float32 and back, a temp of
    # the backend's own making that the TPU compiler does not have.
    cache = _cache(1024, jnp.float32)
    if name == "rounds_step":
        traced = _batcher_trace(params, cache, ROUNDS)
    else:
        traced = jax.jit(
            _program(name, CFG, params, cache), donate_argnums=(1,)
        ).trace(params, cache)
    layer_scans = [
        e for e, _ in _scans(traced.jaxpr.jaxpr)
        if e.params["length"] == CFG.n_layers
    ]
    assert len(layer_scans) == 1, [e.params["length"] for e in layer_scans]
    eqn = layer_scans[0]
    n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
    carry = [v.aval.shape for v in eqn.invars[n_consts : n_consts + n_carry]]
    xs = [v.aval for v in eqn.invars[n_consts + n_carry :]]
    ys = [v.aval.shape for v in eqn.outvars[n_carry:]]
    assert carry.count(cache.k.shape) == 2, carry
    assert [(a.shape, a.dtype) for a in xs] == [
        ((CFG.n_layers,), jnp.int32)
    ], xs  # the layer ids and nothing else
    assert not [s for s in ys if s and s[0] == CFG.n_layers], ys

    stats = traced.lower().compile().memory_analysis()
    if stats is None:
        pytest.skip("this backend reports no memory analysis")
    assert stats.temp_size_in_bytes < cache.k.nbytes, (
        stats.temp_size_in_bytes, cache.k.nbytes,
    )


# ---------------------------------------------------------------------------
# Parity with a loop over per-layer slices
# ---------------------------------------------------------------------------


def _loop_over_slices(
    cfg, params, x, cos, sin, cache, pages, offs, attend, mesh=None, mlp=None,
    active=None,
):
    """``_paged_layers``' contract, the plain way: a Python loop that
    slices layer l's weights and pools out, writes the rows into the
    slice and attends over it (as a stack of one)."""
    new_k, new_v = [], []
    for l in range(cache.k.shape[0]):
        p = jax.tree.map(lambda a: a[l], params["blocks"])
        k_pool, v_pool = cache.k[l], cache.v[l]
        h = T._rms(cfg, x, p["attn_norm"], mesh)
        q, k, v = T._project_qkv(cfg, p, h)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        k_pool = k_pool.at[pages, offs].set(k.astype(k_pool.dtype))
        v_pool = v_pool.at[pages, offs].set(v.astype(v_pool.dtype))
        attn = attend(q, k_pool[None], v_pool[None], 0)
        x = x + T._qmm(attn.reshape(*x.shape[:-1], -1), p["wo"])
        h2 = T._rms(cfg, x, p["mlp_norm"], mesh)
        x = x + (T._mlp(cfg, p, h2) if mlp is None else mlp(p, h2))
        new_k.append(k_pool)
        new_v.append(v_pool)
    return x, jnp.stack(new_k), jnp.stack(new_v)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize(
    "name", ["decode", "decode-frozen-row", "verify", "chunk", "fused"]
)
def test_step_programs_match_a_loop_over_layer_slices(
    params, monkeypatch, name, use_pallas
):
    """``kernel``: the Pallas ragged kernel (interpreted) indexing the
    stacked pools against the same kernel on each layer's slice."""
    cfg = CFG.with_(use_pallas=use_pallas)
    cache = _cache(64, jnp.bfloat16)
    got = jax.jit(_program(name, cfg, params, cache))(params, cache)
    monkeypatch.setattr(T, "_paged_layers", _loop_over_slices)
    want = jax.jit(_program(name, cfg, params, cache))(params, cache)

    got_l, want_l = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got_l) == len(want_l)
    for a, b in zip(got_l, want_l):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        )
    new_cache = got[-1]
    before = np.asarray(cache.k, np.float32)
    after = np.asarray(new_cache.k, np.float32)
    assert not np.array_equal(before, after)  # rows were written
    if name == "decode-frozen-row":
        # Row 1 is frozen: its own page keeps its bytes, its length
        # stands, and the write went to the NULL page at its offset.
        page, off = int(cache.page_table[1, 0]), int(cache.length[1])
        assert np.array_equal(before[:, page], after[:, page])
        assert not np.array_equal(
            before[:, NULL_PAGE, off], after[:, NULL_PAGE, off]
        )
        assert int(new_cache.length[1]) == int(cache.length[1])
        assert int(new_cache.length[0]) == int(cache.length[0]) + 1


# ---------------------------------------------------------------------------
# Chunk lanes (PR 31): L sequences' chunks on one token axis
# ---------------------------------------------------------------------------


def _lane_args(cfg, lanes: int, dead: tuple = ()):
    """``lanes`` chunks at different starts over tables of their own
    (past the decode rows' pages); the ``dead`` ones an all-NULL table."""
    rng = np.random.default_rng(3)
    tokens = rng.integers(1, cfg.vocab_size, (lanes, CHUNK)).astype(np.int32)
    tables = np.full((lanes, PPS), NULL_PAGE, np.int32)
    starts = np.zeros((lanes,), np.int32)
    for lane in range(lanes):
        if lane not in dead:
            first = 1 + (SLOTS + lane) * PPS
            tables[lane] = np.arange(first, first + PPS)
            starts[lane] = (19, 0, 35, 50)[lane]
    return jnp.asarray(tokens), jnp.asarray(tables), jnp.asarray(starts)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("model", ["test-tiny", "test-tiny-mla"])
@pytest.mark.parametrize("dead", [(), (1,)], ids=["all-live", "one-dead"])
def test_fused_step_with_lanes_equals_single_lane_calls(
    params, model, use_pallas, dead
):
    """One ``fused_step_paged`` with three lanes leaves what three
    single-lane calls in sequence leave — the fused step with the first
    lane, then a standalone chunk for each other: the decode logits,
    each lane's hidden states and the pools. A row of a matmul does not
    depend on its neighbours, lanes write disjoint pages, and a dead
    lane writes the NULL page alone. Dense and latent/expert."""
    if model == "test-tiny":
        cfg = CFG.with_(use_pallas=use_pallas)
    else:
        cfg = get_config(model).with_(use_pallas=use_pallas)
        params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    base = _cache(96, jnp.float32)
    if cfg.is_mla:
        rng = np.random.default_rng(5)
        shape = (cfg.n_layers, 96, PAGE, cfg.latent_pool_dim)
        base = PagedKVCache(
            k=jnp.asarray(rng.standard_normal(shape), jnp.float32),
            v=jnp.zeros((*shape[:-1], 0), jnp.float32),
            page_table=base.page_table, length=base.length,
        )
    tokens, tables, starts = _lane_args(cfg, 3, dead)
    logits, hidden, cache, *stats = jax.jit(
        partial(T.fused_step_paged, cfg)
    )(params, _tokens(1), base, tokens, tables, starts)

    logits1, hidden0, cache1, *stats1 = jax.jit(
        partial(T.fused_step_paged, cfg)
    )(params, _tokens(1), base, tokens[:1], tables[:1], starts[:1])
    hiddens = [hidden0[0]]
    chunk = jax.jit(partial(T.prefill_chunk_paged, cfg))
    for lane in (1, 2):
        h, cache1, *st = chunk(
            params, tokens[lane : lane + 1], tables[lane], starts[lane], cache1
        )
        hiddens.append(h[0])
        stats1 = [a + b for a, b in zip(stats1, st)]

    tol = dict(rtol=0, atol=2e-5)
    np.testing.assert_allclose(logits, logits1, **tol)
    for lane in range(3):
        if lane not in dead:
            np.testing.assert_allclose(hidden[lane], hiddens[lane], **tol)
    live_pages = np.ones((96,), bool)
    live_pages[NULL_PAGE] = False  # dead rows and lanes write garbage there
    for a, b in ((cache.k, cache1.k), (cache.v, cache1.v)):
        np.testing.assert_allclose(
            np.asarray(a)[:, live_pages], np.asarray(b)[:, live_pages], **tol
        )
    np.testing.assert_array_equal(cache.length, cache1.length)
    if stats:
        # [experts reached, assignments]: the assignments add up, lane
        # by lane; a dead lane's tokens take no expert.
        assert int(stats[0][1]) == int(stats1[0][1])


# ---------------------------------------------------------------------------
# The batcher's fused programs: built ahead, one a bucket and grouping
# ---------------------------------------------------------------------------

_CCFG = dict(
    max_slots=SLOTS, page_size=PAGE, n_pages=96, pages_per_seq=PPS,
    max_new_tokens=24, seq_buckets=(16, 32, 64), prefill_chunk=CHUNK,
    share_prefix=True,
)
_HEADER = "Panel shared header for every persona, forty ch: "


def _serve(batcher, prompts, **kw):
    futs = [batcher.submit(p, **kw) for p in prompts]
    return [f.result(timeout=120) for f in futs]


def _quiesce(batcher, timeout=10.0):
    """Stats once the loop has nothing left in flight."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        st = batcher.stats()
        if not (st["active_slots"] or st["prefilling_slots"]
                or st["dispatch_inflight"] or st["waiting"]):
            return st
        time.sleep(0.01)
    return batcher.stats()


def _count_fused_traces(batcher) -> list:
    """Every trace of the batcher's fused step lands in the list."""
    traces, traced = [], batcher._fused_sample

    def counting(*args, **kw):
        traces.append(args[10] is None)  # groups
        return traced(*args, **kw)

    batcher._fused_sample = counting
    return traces


def _until(what: str, holds, timeout=120.0):
    deadline = time.monotonic() + timeout
    while not holds():
        assert time.monotonic() < deadline, f"never: {what}"
        time.sleep(0.005)


def test_fused_program_is_built_before_its_first_use(params):
    """The fused programs — one lane and the wide one (PR 31) — are
    traced and compiled when a chunk runs alone on a batcher that has
    dispatched before: nothing decodes then, and chunks that later ride
    a dispatch, one or several, of this bucket or another, on their last
    step or not, trace nothing (PR 27: a program first met under load
    stalled every row, and "last chunk" was a program of its own; until
    PR 31 every bucket built the same program again)."""
    b = ContinuousBatcher(
        CFG, params, config=ContinuousConfig(**_CCFG)
    )
    traces = _count_fused_traces(b)
    try:
        # The first prompt's decode step gives the shapes to build from.
        _serve(b, ["the process's first prompt"], max_new_tokens=2)
        assert traces == []
        _serve(b, ["a prompt served alone, 2 chunks"], max_new_tokens=2)
        assert traces == [True, True]  # one lane, and SLOTS of them
        assert len(b._jit_fused) == 2 and len(b._jit_chunk) == 2
        assert _quiesce(b)["device_programs_fused"] == 0
        # The second prompt's chunks ride the first one's decode steps.
        texts = [r.text for r in _serve(
            b, ["a companion that keeps decoding", "the one that rides"]
        )]
        assert _quiesce(b)["device_programs_fused"] >= 1
        # Two more, of another bucket, ride together beside a third.
        _serve(b, [
            "a companion that keeps decoding",
            "the first of two that ride together, four chunks of them",
            "the second of two that ride together, four chunks too...",
        ])
        st = _quiesce(b)
        assert st.get("chunk_lanes_fused_2", 0) >= 1, st
        assert traces == [True, True]
        assert len(b._jit_fused) == 2 and len(b._jit_chunk) == 2
    finally:
        b.close()
    # The first token of the one that rode comes off the fused
    # program's last-chunk branch: the same text as served alone.
    alone = ContinuousBatcher(
        CFG, params, config=ContinuousConfig(**_CCFG)
    )
    try:
        assert [r.text for r in _serve(alone, ["the one that rides"])] == [
            texts[1]
        ]
    finally:
        alone.close()


def test_fused_grouped_program_follows_a_resized_group_cap(params):
    """Three mates share a full page and decode grouped; a fourth
    prompt's chunks ride beside them: one more program, whatever the
    chunk. The fleet controller then resizes the group cap, which is
    the length of the group arrays: the next chunk that rides is served
    by a program of the new shape. (Rows group only where the kernel
    reads a group's pages once: interpreted.)"""
    b = ContinuousBatcher(
        CFG.with_(use_pallas=True), params,
        config=ContinuousConfig(**{**_CCFG, "max_new_tokens": 48}),
    )
    traces = _count_fused_traces(b)
    try:
        mates = [b.submit(_HEADER + "by three") for _ in range(3)]
        _until("the mates grouped",
               lambda: b.stats()["decode_group_size"] >= 2)
        _serve(b, ["a fourth one, that rides"], max_new_tokens=2)
        assert traces.count(False) == 1
        cap = b.group_cap()
        b.request_group_cap(cap + 1)
        _until("the cap was resized", lambda: b.group_cap() == cap + 1)
        assert b.stats()["decode_group_size"] >= 2
        fused = b.stats()["device_programs_fused"]
        _serve(b, ["a fifth, after the resize"], max_new_tokens=2)
        assert b.stats()["device_programs_fused"] > fused
        assert traces.count(False) == 2
        for f in mates:
            f.result(timeout=120)
        _quiesce(b)
        assert "failed" not in b.heartbeat()
    finally:
        b.close()
