"""Nemotron-H on the serving path (PR 32): one-mixer layers by a plan,
Mamba-2 state beside pages in one cache manager, ungated relu² experts
behind a sigmoid router, attention without positions — each against the
plain reference or the equation it implements, on the CPU at a tiny
size (plan ``MEM*EME``, 4 experts top-2, 2 groups, an expert width that
is no multiple of any tile)."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.models import transformer as T
from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.paged_cache import (
    PagedKVCache,
    PagePool,
    PrefixRegistry,
    StatePool,
    install_seq,
)
from llm_consensus_tpu.models.reference import nemotron_h as R
from llm_consensus_tpu.ops import ssm

PG = 16


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _model(quant: bool = False, **kw):
    cfg = get_config("test-tiny-nemotron").with_(**kw)
    key = jax.random.PRNGKey(0)
    if quant:
        return cfg, T.init_params_quantized(cfg, key, dtype=jnp.float32)
    return cfg, T.init_params(cfg, key, jnp.float32)


def _tokens(n: int, salt: int = 0) -> np.ndarray:
    return np.random.RandomState(salt).randint(3, 259, size=n).astype(np.int32)


# The float32 path agrees with the reference to rounding; the reference
# with its state rounded to bfloat16 lies an order of magnitude further.
TOL = 1.5e-6


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_forward_matches_reference(quant):
    cfg, params = _model(quant)
    ids = _tokens(45)
    want = np.asarray(R.forward(cfg, params, ids))
    got = np.asarray(T.forward(cfg, params, jnp.asarray(ids)[None]))[0]
    assert np.abs(got - want).max() < TOL
    assert want.std() > 0.05  # logits of a size the tolerance means something at


def test_reference_with_bf16_state_fails_the_tolerance():
    cfg, params = _model()
    ids = _tokens(45)
    want = np.asarray(R.forward(cfg, params, ids))
    bad = np.asarray(R.forward(cfg, params, ids, state_dtype=jnp.bfloat16))
    assert np.abs(bad - want).max() > 2 * TOL
    worse = np.asarray(R.forward(cfg, params, ids, round_to=jnp.float8_e4m3fn))
    assert np.abs(worse - want).max() > 100 * TOL


_JITS: dict = {}


def _jit(fn, cfg):
    """A step program compiled once a configuration: eager, each of its
    few hundred ops is a dispatch of its own."""
    from functools import partial

    if (fn, cfg) not in _JITS:
        _JITS[fn, cfg] = jax.jit(partial(fn, cfg))
    return _JITS[fn, cfg]


def _prefill(cfg, params, cache, ids, table, slot, chunk=PG, lanes=2,
             src=0, start=0, snap_at=None, snap_slot=0):
    """``ids`` from position ``start`` through chunk programs of
    ``lanes`` lanes (lane 0 live); returns (last real hidden, cache)."""
    n, pos, last = len(ids), start, None
    while pos < n:
        m = min(chunk, n - pos)
        toks = np.zeros((lanes, chunk), np.int32)
        toks[0, :m] = ids[pos : pos + m]
        toks[0, m:] = 7  # padding is whatever the host left there
        tables = np.zeros((lanes, len(table)), np.int32)
        tables[0] = table
        state = np.zeros((lanes, 4), np.int32)
        snap = snap_slot if snap_at == pos + m else 0
        state[0] = (src if pos == start else slot, slot, snap, m)
        hidden, cache, *_ = _jit(T.prefill_chunk_paged, cfg)(
            params, jnp.asarray(toks), jnp.asarray(tables),
            jnp.asarray([pos] + [0] * (lanes - 1), jnp.int32), cache,
            chunk_state=jnp.asarray(state),
        )
        last, pos = hidden[0, m - 1], pos + chunk
    return last, cache


def _cache(cfg, dtype=jnp.float32):
    return PagedKVCache.create(cfg, 24, PG, 4, 8, dtype=dtype, state_slots=8)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_chunked_prefill_then_decode_matches_reference(quant, use_pallas):
    cfg, params = _model(quant, use_pallas=use_pallas)
    ids = _tokens(45, 1)
    table = np.zeros((8,), np.int32)
    table[:5] = [3, 4, 5, 6, 7]
    last, cache = _prefill(cfg, params, _cache(cfg), ids, table, slot=2)
    logits = np.asarray(T.unembed_one(cfg, params, last))
    want = np.asarray(R.forward(cfg, params, ids))
    assert np.abs(logits - want[-1]).max() < TOL
    cache = install_seq(
        cache, jnp.int32(1), jnp.asarray(table), jnp.int32(len(ids)),
        jnp.int32(2),
    )
    seq = list(ids)
    for _ in range(3):
        seq.append(int(np.argmax(logits)))
        toks = np.zeros((4, 1), np.int32)
        toks[1, 0] = seq[-1]
        out, cache, *_ = _jit(T.decode_step_paged, cfg)(
            params, jnp.asarray(toks), cache
        )
        logits = np.asarray(out[1])
        want = np.asarray(R.forward(cfg, params, np.asarray(seq, np.int32)))[-1]
        assert np.abs(logits - want).max() < TOL
    # Slot 0 is the empty state: idle rows and the dead lane wrote it
    # what they read there.
    assert not np.asarray(cache.state.s[:, 0]).any()


def test_fused_step_carries_decode_rows_and_lanes_from_their_own_states():
    """One program: a decode row, an idle row, a lane restoring a
    snapshot mid-prompt, a dead lane — each equals its own program."""
    cfg, params = _model()
    a, b = _tokens(40, 2), _tokens(48, 3)
    ta = np.zeros((8,), np.int32)
    ta[:4] = [1, 2, 3, 4]
    tb = np.zeros((8,), np.int32)
    tb[:4] = [5, 6, 7, 8]
    last, cache = _prefill(cfg, params, _cache(cfg), a, ta, slot=1)
    # b's first two pages, a snapshot of its state after them in slot 5.
    _, cache = _prefill(
        cfg, params, cache, b[:32], tb, slot=2, snap_at=32, snap_slot=5
    )
    cache = install_seq(
        cache, jnp.int32(0), jnp.asarray(ta), jnp.int32(len(a)), jnp.int32(1)
    )
    tok_a = int(np.argmax(np.asarray(T.unembed_one(cfg, params, last))))
    toks = np.zeros((4, 1), np.int32)
    toks[0, 0] = tok_a
    chunk = np.zeros((2, PG), np.int32)
    chunk[0] = b[32:48]
    tables = np.zeros((2, 8), np.int32)
    tables[0] = tb
    # The lane starts from the SNAPSHOT (slot 5) and lands in slot 3.
    state = np.asarray([[5, 3, 0, PG], [0, 0, 0, 0]], np.int32)
    before = np.asarray(cache.state.s)
    logits, hidden, cache, *_ = _jit(T.fused_step_paged, cfg)(
        params, jnp.asarray(toks), cache, jnp.asarray(chunk),
        jnp.asarray(tables), jnp.asarray([32, 0], jnp.int32),
        chunk_state=jnp.asarray(state),
    )
    want_a = np.asarray(
        R.forward(cfg, params, np.asarray(list(a) + [tok_a], np.int32))
    )[-1]
    assert np.abs(np.asarray(logits[0]) - want_a).max() < TOL
    want_b = np.asarray(R.forward(cfg, params, b))[-1]
    got_b = np.asarray(T.unembed_one(cfg, params, hidden[0, PG - 1]))
    assert np.abs(got_b - want_b).max() < TOL
    after = np.asarray(cache.state.s)
    # Rows 1-3 are idle, lane 1 is dead: every slot but the decode
    # row's (1) and the lane's own (3) is bit for bit what it was —
    # the snapshot (5), b's first slot (2), the empty slot (0) too.
    for slot in (0, 2, 4, 5, 6, 7):
        assert np.array_equal(after[:, slot], before[:, slot]), slot
    assert not np.array_equal(after[:, 1], before[:, 1])
    assert np.asarray(cache.length).tolist() == [len(a) + 1, 0, 0, 0]


def test_padding_of_a_last_partial_chunk_changes_no_state():
    cfg, params = _model()
    ids = _tokens(25, 4)
    table = np.zeros((8,), np.int32)
    table[:3] = [1, 2, 3]
    states = []
    for chunk in (PG, 32):  # 9 real tokens of 16, then 25 of 32
        _, cache = _prefill(
            cfg, params, _cache(cfg), ids, table, slot=2, chunk=chunk
        )
        states.append((np.asarray(cache.state.s[:, 2]),
                       np.asarray(cache.state.conv[:, 2])))
    np.testing.assert_allclose(states[0][0], states[1][0], atol=2e-6)
    np.testing.assert_allclose(states[0][1], states[1][1], atol=2e-6)
    # The convolution's carried rows are the last three REAL inputs.
    assert states[0][1].any()


@pytest.mark.parametrize("block", [1, 5, 8, 64], ids=lambda b: f"block{b}")
def test_chunked_scan_equals_the_sequential_recurrence(block):
    """Several chunkings, a partial last chunk among them (37 tokens)."""
    r, t, h, p, g, n = 2, 37, 4, 8, 2, 16
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(k[0], (r, t, h, p))
    b = jax.random.normal(k[1], (r, t, g, n))
    c = jax.random.normal(k[2], (r, t, g, n))
    dt = jax.nn.softplus(jax.random.normal(k[3], (r, t, h)) - 2)
    a = -jnp.exp(jax.random.normal(k[4], (h,)))
    s0 = jax.random.normal(k[5], (r, h, p, n))
    y, s = ssm.ssd_scan(x, b, c, dt, a, s0, block=block)
    for row in range(r):
        y_seq, s_seq = ssm.sequential_scan(
            x[row], b[row], c[row], dt[row], a, s0[row]
        )
        assert float(jnp.abs(y[row] - y_seq).max()) < 2e-5
        assert float(jnp.abs(s[row] - s_seq).max()) < 2e-5


def test_scan_kernel_reads_and_writes_the_pool_in_place():
    from llm_consensus_tpu.ops.pallas.ssm_scan import ssm_scan

    r, t, h, p, g, n = 3, 16, 4, 16, 2, 16
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    x = jax.random.normal(k[0], (r, t, h, p))
    b = jax.random.normal(k[1], (r, t, g, n))
    c = jax.random.normal(k[2], (r, t, g, n))
    dt = jax.nn.softplus(jax.random.normal(k[3], (r, t, h)) - 2)
    a = -jnp.exp(jax.random.normal(k[4], (h,)))
    s0 = jax.random.normal(k[5], (r, h, p, n))
    terms = ssm.ssd_terms(x, b, c, dt, a)
    want_y, want_s = ssm.ssd_apply(terms, s0)
    pool = jnp.zeros((2, 6, h, p, n)).at[1, jnp.array([1, 2, 3])].set(s0)
    y, out = ssm_scan(
        terms, pool, jnp.int32(1), jnp.array([1, 2, 3]), jnp.array([1, 4, 5]),
        interpret=True,
    )
    assert float(jnp.abs(y - want_y).max()) < 1e-5
    for row, slot in enumerate((1, 4, 5)):
        assert float(jnp.abs(out[1, slot] - want_s[row]).max()) < 1e-5
    # Slots nobody wrote, and the other layer, are untouched.
    assert np.array_equal(np.asarray(out[1, 2]), np.asarray(s0[1]))
    assert not np.asarray(out[0]).any()


def test_router_is_sigmoid_then_topk_with_a_choice_only_bias():
    cfg, params = _model()
    p = jax.tree.map(lambda a: a[0], params["moe_blocks"])
    x = jax.random.normal(jax.random.PRNGKey(3), (32, cfg.d_model))
    bias0 = jnp.zeros((cfg.n_experts,))
    logits, w, idx = T.moe_route(cfg, p["router"], x, bias0)
    scores = np.asarray(jax.nn.sigmoid(logits))
    np.testing.assert_array_equal(
        np.sort(np.asarray(idx), -1), np.sort(np.argsort(-scores, -1)[:, :2], -1)
    )
    # Renormalised, then times the scale.
    np.testing.assert_allclose(np.asarray(w).sum(-1), cfg.moe_routed_scale, rtol=1e-6)
    chosen = np.take_along_axis(scores, np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(w), 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6
    )
    # A bias moves the CHOICE (expert 3 is now always taken) and leaves
    # the weights the chosen scores, not score + bias.
    bias = bias0.at[3].set(10.0)
    _, w2, idx2 = T.moe_route(cfg, p["router"], x, bias)
    assert (np.asarray(idx2) == 3).any(-1).all()
    chosen2 = np.take_along_axis(scores, np.asarray(idx2), -1)
    np.testing.assert_allclose(
        np.asarray(w2), 2.5 * chosen2 / chosen2.sum(-1, keepdims=True), rtol=1e-6
    )


@pytest.mark.parametrize(
    "width,stored", [(40, 128), (136, 256), (256, 256)],
    ids=["w40-stored-128", "w136-stored-256", "w256-whole-tiles"],
)
def test_ungated_expert_layer_equals_masked_loop(width, stored):
    """A width is stored at the grouped matmul's rule, whole 128-lane
    tiles, padded with zero columns / rows: exact."""
    from llm_consensus_tpu.ops.pallas.moe_matmul import (
        moe_grouped_matmul_supported,
    )

    cfg, params = _model(moe_d_ff=width, d_ff=width)
    p = jax.tree.map(lambda a: a[0], params["moe_blocks"])
    assert p["w_up"].shape[-1] == cfg.expert_d_ff_stored == stored
    assert moe_grouped_matmul_supported(stored, 128)
    assert not moe_grouped_matmul_supported(stored - 64, 128)
    assert not np.asarray(p["w_up"][..., width:]).any()
    assert not np.asarray(p["w_down"][:, width:]).any()
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 24, cfg.d_model))
    got, *_ = T._moe_dropless(cfg, p, h)
    want = R.experts(cfg, p, h[0])
    assert float(jnp.abs(got[0] - want).max()) < 2e-6
    # The same layer at the published width, unpadded, gives the same.
    cut = dict(p, w_up=p["w_up"][..., :width], w_down=p["w_down"][:, :width])
    np.testing.assert_allclose(
        np.asarray(R.experts(cfg, cut, h[0])), np.asarray(want), atol=1e-7
    )


def test_plan_runs_are_scanned_units():
    kinds = get_config("nemotron-3-nano-30b-a3b").with_layers(18).plan_kinds()
    runs = T._plan_segments(kinds)
    assert [("".join(k[0] for k in u), r) for u, r in runs] == [
        ("smsmsam", 2), ("sm", 2),
    ]
    assert sum(len(u) * r for u, r in runs) == 18
    assert T._plan_segments(("ssm", "moe", "attn")) == [
        (("ssm",), 1), (("moe",), 1), (("attn",), 1),
    ]


def _run_shapes(cfg, params):
    return [
        ([(m.kind, m.norm, m.first, m.step, m.pool_first) for m in unit], reps)
        for unit, reps in T._plan_runs(cfg, params)
    ]


def test_one_layer_loop_runs_a_planned_model_and_the_two_mixer_models():
    """``_paged_layers`` has ONE loop body: a planned model's runs take
    every mixer off its kind's stack (whose index is its pool layer); a
    model without a plan is the unit attention + MLP over each stack,
    the pool indexed by absolute layer."""
    cfg, params = _model()  # MEM*EME
    assert _run_shapes(cfg, params) == [
        ([(kind, "norm", i, 1, 0)], 1)
        for kind, i in (("ssm", 0), ("moe", 0), ("ssm", 1), ("attn", 0),
                        ("moe", 1), ("ssm", 2), ("moe", 2))
    ]
    long = cfg.with_(n_layers=8, layer_plan="MMEMME*E")
    shapes = _run_shapes(long, T.init_params(long, jax.random.PRNGKey(0)))
    assert shapes[0] == (
        [("ssm", "norm", 0, 2, 0), ("ssm", "norm", 1, 2, 0),
         ("moe", "norm", 0, 1, 0)], 2,
    )
    assert shapes[1:] == [
        ([("attn", "norm", 0, 1, 0)], 1), ([("moe", "norm", 2, 1, 0)], 1),
    ]
    mla = get_config("test-tiny-mla")  # one dense layer, then expert layers
    stacks = _run_shapes(mla, T.init_params(mla, jax.random.PRNGKey(0)))
    assert stacks == [
        ([("attn", "attn_norm", 0, 1, 0), ("ffn", "mlp_norm", 0, 1, 0)],
         mla.n_dense_layers),
        ([("attn", "attn_norm", 0, 1, mla.n_dense_layers),
          ("ffn", "mlp_norm", 0, 1, 0)], mla.n_layers - mla.n_dense_layers),
    ]


def test_routed_out_projection_width_follows_the_router_form():
    """Random weights: a sigmoid_topk router's renormalised, scaled
    weights get the routed out-projection drawn 1/16 as wide; the other
    router forms keep theirs (and so their parameters)."""
    cfg, params = _model()
    assert T._routed_out_scale(cfg) == 1 / 16
    assert T._routed_out_scale(get_config("test-tiny-mla")) == 1.0
    assert T._routed_out_scale(get_config("test-tiny-moe")) == 1.0
    moe = params["moe_blocks"]
    routed = float(jnp.std(moe["w_down"][..., : cfg.expert_d_ff, :]))
    shared = float(jnp.std(moe["ws_down"]))
    assert 0.8 / 16 < routed / shared < 1.25 / 16


def test_layers_flag_takes_the_plans_first_n():
    cfg, params = _model()
    cut, kept = T.first_layers(cfg, params, 4)
    assert cut.layer_plan == "MEM*" and cut.n_layers == 4
    assert kept["ssm_blocks"]["norm"].shape[0] == 2
    assert kept["moe_blocks"]["norm"].shape[0] == 1
    ids = _tokens(20)
    np.testing.assert_allclose(
        np.asarray(T.forward(cut, kept, jnp.asarray(ids)[None]))[0],
        np.asarray(R.forward(cut, kept, ids)), atol=TOL,
    )


def test_pools_are_sized_by_layer_kind():
    cfg, _ = _model()
    cache = _cache(cfg)
    assert cache.k.shape[0] == 1  # one attention layer of seven
    assert cache.state.s.shape == (3, 8, 4, 16, 16)
    assert cache.state.conv.shape == (3, 8, 3, cfg.ssm_conv_dim)
    assert PagedKVCache.create(get_config("test-tiny"), 4, PG, 2, 2).state is None
    with pytest.raises(ValueError, match="state_slots"):
        PagedKVCache.create(cfg, 4, PG, 2, 2)


def test_contiguous_cache_engine_refuses_a_recurrent_model():
    from llm_consensus_tpu.models.cache import KVCache

    cfg, params = _model()
    cache = KVCache.create(cfg, 1, 32, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="recurrent"):
        T.prefill(cfg, params, jnp.zeros((1, 8), jnp.int32), jnp.array([8]), cache)
    with pytest.raises(NotImplementedError, match="rewound"):
        T.verify_step_paged(cfg, params, jnp.zeros((4, 2), jnp.int32), _cache(cfg))


# -- one cache manager, two kinds of state ---------------------------------


def test_registry_snapshots_go_with_their_pages_and_lru_under_pressure():
    pool, states = PagePool(range(1, 16)), StatePool(4)
    reg = PrefixRegistry(pool, 4, states=states)
    ids = list(range(13))
    pages = pool.alloc(3)
    nodes = [n for n, _ in reg.register(ids, pages)]
    assert reg.promise_state(nodes[0]) == 1 and not nodes[0].state_ready
    nodes[0].state_ready = True
    nodes[1].state, nodes[1].state_ready = states.alloc(1)[0], True
    nodes[1].last_used = nodes[0].last_used + 1
    assert states.available == 1
    # A match capped at the snapshot's depth maps that many pages, and
    # offers no boundary page.
    m = reg.match(ids, depth=1)
    assert len(m.pages) == 1 and m.boundary_page is None
    pool.release(m.pages[0])
    # Under pressure the least recently used idle snapshot goes first;
    # one an admission shares (refcount 2) is not idle.
    states.share(nodes[0].state)
    assert states.alloc(1) and reg.alloc_state() == 2  # node 1's slot
    assert nodes[1].state is None and nodes[0].state == 1
    assert reg.alloc_state() is None
    states.release(1)
    # Evicting a page releases its snapshot with it.
    for p in pages:
        pool.release(p)
    assert reg.evict(3) == 3 and nodes[0].evicted and nodes[0].state is None
    assert reg.snapshots_evicted == 2


def _batcher(cfg, params, **kw):
    from llm_consensus_tpu.serving.continuous import (
        ContinuousBatcher,
        ContinuousConfig,
    )

    return ContinuousBatcher(cfg, params, config=ContinuousConfig(
        max_slots=4, page_size=PG, n_pages=64, pages_per_seq=12,
        seq_buckets=(32, 64, 128), prefill_chunk=PG, max_new_tokens=8, **kw,
    ))


def _serve(b, prompts):
    futs = [
        b.submit(p, max_new_tokens=4, temperature=0.0, logits=4)
        for p in prompts
    ]
    return [f.result(timeout=600) for f in futs]


def test_mappers_restore_recompute_and_survive_eviction():
    """A mapper that restores a snapshot, one whose match no snapshot
    covers (it recomputes), and one after the snapshots were evicted:
    all return the unshared run's logits."""
    cfg, params = _model()
    head = ("The panel shares this header; it is long enough to span pages. " * 2)[:100]
    a, b, c = head + "first tail", head + "second tail!", head[:40] + "elsewhere"
    base = _batcher(cfg, params, share_prefix=False)
    want = {p: r for p, r in zip((a, b, c), _serve(base, [a, b, c]))}
    base.close()

    def same(got, p):
        assert got.text == want[p].text
        assert np.abs(got.logits - want[p].logits).max() < 1e-5

    bt = _batcher(cfg, params)
    try:
        ra, rb = _serve(bt, [a, b])  # b is promised a's branch snapshot
        same(ra, a), same(rb, b)
        st = bt.stats()
        assert rb.timing["header_pages_shared"] == 6
        assert st["state_snapshots_restored"] == 1 and st["state_snapshots_saved"] >= 2
        # c's match ends inside the header (2 pages), where no snapshot
        # is and nobody is prefilling: from token 0, counted as missed.
        (rc,) = _serve(bt, [c])
        same(rc, c)
        assert rc.timing["header_pages_shared"] == 0
        assert rc.timing["header_pages_matched"] == 2
        st = bt.stats()
        assert st["state_snapshots_missed"] == 1
        assert st["prefix_tokens_recomputed"] == 2 * PG
        # ... and c's pass SAVED the snapshot it wanted: a twin restores it.
        (rc2,) = _serve(bt, [c])
        same(rc2, c)
        assert rc2.timing["header_pages_shared"] == 3  # c's last full page
        # The DEPTH of that miss is remembered: a new chain's prefill
        # saves a snapshot two pages in, so a prompt that branches off
        # it there restores instead of missing.
        assert bt._miss_depths == [2]
        d = "Another header entirely, which also runs on over a few pages. ok"
        _serve(bt, [d])
        (re_,) = _serve(bt, [d[:40] + "and then its own way"])
        assert re_.timing["header_pages_shared"] == 2
        assert bt.stats()["state_snapshots_missed"] == 1
        # A depth that stops recurring is forgotten: after misses at as
        # many other depths as are kept, a chain gets no snapshot there.
        for other in (5, 6, 7, 5, 8):
            bt._remember_miss_depth(other)
        assert bt._miss_depths == [6, 7, 5, 8]
        e = "A third header, as long as the others, that nobody has seen yet."
        _serve(bt, [e])
        (rf,) = _serve(bt, [e[:40] + "and off on its own again"])
        assert rf.timing["header_pages_shared"] == 0
        assert rf.timing["header_pages_matched"] == 2
        assert bt.stats()["state_snapshots_missed"] == 2
        # Snapshots evicted (slot pressure): pages match, no state: recompute.
        with bt._lock:
            assert bt._registries[0].evict_states(99) >= 3
        (ra2,) = _serve(bt, [a])
        same(ra2, a)
        assert ra2.timing["header_pages_shared"] == 0
        st = bt.stats()
        assert st["state_snapshots_evicted"] >= 3
        assert st["state_slots_held"] == len(bt._registries[0].snapshot_nodes())
        assert st["ssm_tokens_prefill"] + st["ssm_tokens_fused"] > 0
    finally:
        bt.close()


def test_a_one_token_request_gives_back_its_pages_and_state_slot():
    """A request one token long, its last chunk riding a companion's
    decode step or not, is installed and released by the same fetch's
    patch program (PR 36): the release wins, the companion's state slot
    stays its own until it ends, and at rest every row of the device's
    tables, every page and every state slot is back."""
    import time

    cfg, params = _model()
    bt = _batcher(cfg, params, share_prefix=False)
    try:
        pages0, slots0 = bt._pools[0].available, bt._states.available
        alone = bt.submit("a companion's prompt", max_new_tokens=8).result(
            timeout=600
        )
        long = bt.submit("a companion's prompt", max_new_tokens=8)
        while not bt.stats()["active_slots"]:
            time.sleep(0.002)
        one = bt.submit("ends at its first token", max_new_tokens=1)
        assert one.result(timeout=600).num_tokens == 1
        assert long.result(timeout=600).text == alone.text
        while bt.stats()["dispatch_inflight"]:
            time.sleep(0.002)
        assert bt._pools[0].available == pages0
        assert bt._states.available == slots0
        assert not np.asarray(bt.cache.state.slot).any()
        assert not np.asarray(bt.cache.length).any()
        assert not np.asarray(bt.cache.page_table).any()
    finally:
        bt.close()


@pytest.mark.parametrize(
    "kw,why",
    [
        (dict(decode_rounds=2), "jit_rounds_step"),
        (dict(host_cache_bytes=1 << 20), "host tier"),
        (dict(prefill_chunk=24, seq_buckets=(48, 96)), "page"),
        (dict(draft=True), "draft"),
        (dict(mesh=True), "mesh"),
    ],
    ids=["rounds", "host-tier", "chunk-off-pages", "draft", "mesh"],
)
def test_refuses_what_moves_pages_only_or_rolls_back(kw, why):
    from llm_consensus_tpu.serving.continuous import (
        ContinuousBatcher,
        ContinuousConfig,
    )

    cfg, params = _model()
    extra = {}
    if kw.pop("draft", False):
        extra["draft"] = (get_config("test-tiny"), None)
        kw["spec_k"] = 2
    if kw.pop("mesh", False):
        from jax.sharding import Mesh

        extra["mesh"] = Mesh(
            np.array(jax.devices()[:2]).reshape(2, 1), ("data", "model")
        )
    config = ContinuousConfig(
        **{**dict(max_slots=4, page_size=PG, n_pages=64, pages_per_seq=12,
                  seq_buckets=(32, 64), prefill_chunk=PG), **kw}
    )
    with pytest.raises(ValueError, match=f"recurrent.*{why}"):
        ContinuousBatcher(cfg, params, config=config, **extra)


def _hf_state_dict(cfg, params) -> dict:
    """``params`` under NemotronHForCausalLM's names and layouts: linear
    weights [out, in], ``in_proj`` one matrix, the convolution [C, 1, K],
    experts at the PUBLISHED width."""
    seen: dict = {}
    out = {
        "backbone.embeddings.weight": params["embed"],
        "backbone.norm_f.weight": params["norm_f"],
        "lm_head.weight": params["lm_head"].T,
    }
    stacks = {"M": "ssm_blocks", "*": "attn_blocks", "E": "moe_blocks"}
    for n, kind in enumerate(cfg.layer_plan):
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        p = {k: np.asarray(v[i]) for k, v in params[stacks[kind]].items()}
        pre = f"backbone.layers.{n}"
        out[f"{pre}.norm.weight"] = p["norm"]
        m = f"{pre}.mixer"
        if kind == "M":
            out[f"{m}.in_proj.weight"] = np.concatenate(
                [p["w_in_z"], p["w_in_xbc"], p["w_in_dt"]], axis=1
            ).T
            out[f"{m}.conv1d.weight"] = p["conv_w"].T[:, None, :]
            out[f"{m}.conv1d.bias"] = p["conv_b"]
            out[f"{m}.dt_bias"], out[f"{m}.A_log"] = p["dt_bias"], p["a_log"]
            out[f"{m}.D"], out[f"{m}.norm.weight"] = p["d_skip"], p["gate_norm"]
            out[f"{m}.out_proj.weight"] = p["w_out"].T
        elif kind == "*":
            for ours, theirs in (("wq", "q"), ("wk", "k"), ("wv", "v"), ("wo", "o")):
                out[f"{m}.{theirs}_proj.weight"] = p[ours].T
        else:
            f = cfg.expert_d_ff
            out[f"{m}.gate.weight"] = p["router"].T
            out[f"{m}.gate.e_score_correction_bias"] = p["router_bias"]
            for e in range(cfg.n_experts):
                out[f"{m}.experts.{e}.up_proj.weight"] = p["w_up"][e, :, :f].T
                out[f"{m}.experts.{e}.down_proj.weight"] = p["w_down"][e, :f].T
            out[f"{m}.shared_experts.up_proj.weight"] = p["ws_up"].T
            out[f"{m}.shared_experts.down_proj.weight"] = p["ws_down"].T
    return out


def test_hf_loader_reads_nemotron_h_names(tmp_path):
    """A synthetic state dict in the published names (the published
    checkpoint is not on this machine): split ``in_proj``, the
    convolution's layout, experts padded to the stored width."""
    import json

    safetensors = pytest.importorskip("safetensors.numpy")
    from llm_consensus_tpu.models.hf_loader import config_from_hf, load_hf_params

    cfg, params = _model(moe_d_ff=136, d_ff=136)
    state = _hf_state_dict(cfg, params)
    safetensors.save_file(
        {k: np.ascontiguousarray(v, np.float32) for k, v in state.items()},
        str(tmp_path / "model.safetensors"),
    )
    (tmp_path / "config.json").write_text(json.dumps({
        "architectures": ["NemotronHForCausalLM"], "model_type": "nemotron_h",
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.d_model,
        "num_hidden_layers": cfg.n_layers,
        "hybrid_override_pattern": cfg.layer_plan,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "intermediate_size": cfg.d_ff, "moe_intermediate_size": cfg.moe_d_ff,
        "moe_shared_expert_intermediate_size": cfg.moe_shared_d_ff,
        "n_routed_experts": cfg.n_experts, "n_shared_experts": 1,
        "num_experts_per_tok": cfg.n_experts_per_token,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "n_group": 1, "topk_group": 1, "mlp_hidden_act": "relu2",
        "mamba_num_heads": cfg.ssm_heads, "mamba_head_dim": cfg.ssm_head_dim,
        "ssm_state_size": cfg.ssm_state, "n_groups": cfg.ssm_groups,
        "conv_kernel": cfg.ssm_conv, "use_conv_bias": True,
        "layer_norm_epsilon": cfg.rms_norm_eps,
        "max_position_embeddings": cfg.max_seq_len,
        "rope_theta": 10000, "partial_rotary_factor": 1,
        "tie_word_embeddings": False,
    }))
    got_cfg = config_from_hf(tmp_path, name=cfg.name)
    assert got_cfg == cfg
    loaded = load_hf_params(got_cfg, tmp_path, dtype=jnp.float32)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_benchmark_reference_is_the_packages_copy():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark/reference/nemotron_h.py"), "rb") as f:
        copy = f.read()
    with open(R.__file__, "rb") as f:
        assert f.read() == copy
