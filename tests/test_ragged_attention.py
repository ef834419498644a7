"""One ragged paged attention kernel + the fused scheduler step (PR 8).

Two layers of contract:

- KERNEL: :func:`llm_consensus_tpu.ops.pallas.ragged_paged_attention`
  serves mixed decode + prefill-chunk rows, shared-prefix groups, and
  sliding windows in ONE program, parity-checked against the XLA
  reference (`ops.attention.ragged_paged_attention_reference`) across
  the ragged shapes: mid-block lengths, MQA, degenerate one-member
  groups, all-decode, all-prefill, int8 KV (head-major AND stacked).
- BATCHER: with ``ContinuousConfig.ragged_attention`` (default on) a
  ready prefill chunk rides the decode dispatch as one more ragged
  row — ONE device program per scheduler iteration — with generated
  text byte-identical to the split-program path across pipeline
  depths, chunk widths, stops landing mid-flight, eviction +
  host-restore in flight, and sliding-window configs (which used to
  fall back out of the grouped kernel entirely).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.transformer import init_params
from llm_consensus_tpu.ops.attention import (
    decode_attention_shared_prefix_quant,
)
from llm_consensus_tpu.ops.pallas import parity
from llm_consensus_tpu.ops.pallas.attention import (
    flash_decode_attention_shared_prefix_q8_stacked,
)
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)

CFG = get_config("test-tiny")

_CCFG = dict(
    max_slots=4,
    page_size=16,
    n_pages=96,
    pages_per_seq=8,
    max_new_tokens=8,
    seq_buckets=(16, 32, 64),
    prefill_chunk=16,
    share_prefix=True,
)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Kernel vs XLA reference (CPU interpret). The comparison body lives in
# ops/pallas/parity.py: chip_smoke.py runs the same one compiled, at the
# smoke model's shapes.
# ---------------------------------------------------------------------------

_TOY = dict(pg=8, hkv=2, d=32, p_per=6, n_pages=40, interpret=True)


def _check(got, want, rtol=2e-2, atol=2e-2):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=rtol, atol=atol,
    )


def _within_tol(errs: dict):
    for lane, err in errs.items():
        parity.check(lane, err, parity.ATTENTION_TOL)


# Stacked: the same rows read out of layer l of stacked pools through a
# traced ``layer=`` (what the step programs' layer scan passes), at the
# first layer and the last; each must return the bytes the call on
# ``pool[l]`` returns, or ``ragged_attention_error`` raises.
_SLICE = (None,)
_STACKED = ((0, 3), (2, 3))


@pytest.mark.parametrize(
    "window, nq, layers",
    [
        (0, 1, _SLICE), (9, 1, _SLICE),
        (0, 1, _STACKED), (9, 1, _STACKED), (9, 3, _STACKED),
    ],
)
def test_ragged_mixed_rows_match_reference(window, nq, layers):
    """Decode rows (``nq`` > 1: verify rows) at mid-block lengths + one
    chunk row (starting mid-block too), one program."""
    for layer in layers:
        errs = parity.ragged_attention_error(
            **_TOY, seed=0, g=3, valid_len=[13, nq, 40, 23], cq=16,
            chunk_start=11, window=window, nq=nq, layer=layer,
        )
        assert set(errs) == {"decode", "chunk"}
        _within_tol(errs)


def test_ragged_mqa_single_kv_head():
    _within_tol(
        parity.ragged_attention_error(
            **{**_TOY, "hkv": 1, "p_per": 4}, seed=1, g=4,
            valid_len=[7, 30, 12],
        )
    )


@pytest.mark.parametrize(
    "window, layers",
    [(0, _SLICE), (9, _SLICE), (0, _STACKED), (9, _STACKED)],
)
def test_ragged_grouped_rows_with_chunk(window, layers):
    """Groups + ungrouped rows + a chunk lane in the same program —
    grouping is a bandwidth optimization, output must equal the
    ungrouped reference (including under a sliding window, the config
    that used to fall back). Rows 0, 2, 3 share their first page."""
    for layer in layers:
        _within_tol(
            parity.ragged_attention_error(
                **_TOY, seed=2, g=3, valid_len=[13, 9, 40, 23], cq=16,
                chunk_start=11, group_rows=(0, 2, 3), window=window,
                layer=layer,
            )
        )


def test_ragged_degenerate_single_member_group():
    """A one-member group must not change that row's output (the
    tracker never emits one, but the kernel tolerates it)."""
    _within_tol(
        parity.ragged_attention_error(
            **{**_TOY, "p_per": 4}, seed=3, g=2, valid_len=[20, 11, 30],
            group_rows=(1,),
        )
    )


def test_ragged_all_prefill_and_dead_decode_rows():
    """kv_len 0 decode rows (an idle batcher's slots) stay finite while
    the chunk row — the only live work — still matches the reference."""
    errs = parity.ragged_attention_error(
        **{**_TOY, "p_per": 4}, seed=4, g=2, valid_len=[0, 0, 0], cq=8,
        chunk_start=0, null_tables=True,
    )
    assert set(errs) == {"chunk"}
    _within_tol(errs)


# Chunk lanes (PR 31): rows b .. b + nc - 1 are prefill chunks of
# different sequences, each over its own table at its own fill.
_LANES = {
    "two lanes": dict(chunk_start=[11, 24]),
    "three lanes, one at position 0": dict(chunk_start=[11, 0, 29]),
    "three lanes, the middle one dead": dict(chunk_start=[11, -16, 24]),
    "three lanes, only the last live": dict(chunk_start=[-16, -16, 5]),
}


@pytest.mark.parametrize("grouped", [False, True], ids=["plain", "grouped"])
@pytest.mark.parametrize("case", list(_LANES))
def test_ragged_chunk_lanes_match_reference(case, grouped):
    """Several chunk lanes in one program, with and without a group
    present and under a window, sliced and stacked pools: each lane
    equals the reference's own fold over its table; a dead lane owes a
    finite output and moves nothing else."""
    groups = dict(group_rows=(0, 2, 3)) if grouped else {}
    for layer, window in ((None, 0), ((2, 3), 9)):
        errs = parity.ragged_attention_error(
            **{**_TOY, "n_pages": 64}, seed=7, g=3,
            valid_len=[13, 9, 40, 23], cq=16, window=window, layer=layer,
            **groups, **_LANES[case],
        )
        assert set(errs) == {"decode", "chunk"}
        _within_tol(errs)


def test_ragged_lanes_equal_single_lane_calls():
    """Lane l of a three-lane call returns the bytes a one-lane call
    on that lane alone returns: a lane does not see its neighbours."""
    from llm_consensus_tpu.ops.pallas.attention import ragged_paged_attention

    rng = np.random.default_rng(11)
    pg, hkv, g, d, p_per, n_pages, cq = 8, 2, 3, 32, 6, 64, 16
    pool = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((n_pages, pg, hkv, d)), jnp.bfloat16
    )
    kp, vp = pool(), pool()
    q = jnp.asarray(rng.standard_normal((2, hkv * g, d)), jnp.bfloat16)
    perm = rng.permutation(np.arange(1, n_pages)).astype(np.int32)
    tbl = jnp.asarray(perm[: 2 * p_per].reshape(2, p_per))
    ctbl = jnp.asarray(perm[2 * p_per : 5 * p_per].reshape(3, p_per))
    qc = jnp.asarray(rng.standard_normal((3, cq, hkv * g, d)), jnp.bfloat16)
    starts = jnp.asarray([11, 0, 29], jnp.int32)
    vl = jnp.asarray([13, 30], jnp.int32)
    call = lambda qc, ct, cs: ragged_paged_attention(  # noqa: E731
        q, kp, vp, tbl, vl, q_chunk=qc, chunk_table=ct, chunk_start=cs,
        interpret=True,
    )
    dec, chunks = call(qc, ctbl, starts)
    for lane in range(3):
        dec1, one = call(qc[lane], ctbl[lane], starts[lane])
        np.testing.assert_array_equal(np.asarray(one), np.asarray(chunks[lane]))
        np.testing.assert_array_equal(np.asarray(dec1), np.asarray(dec))


# The walk (PR 29): the kernel's steps follow the pages its rows hold,
# not the table's width. Each case names what its rows hold.
_WALK = {
    "rows of 0, 1 and p_per pages": dict(
        g=3, valid_len=[0, 5, 48], cq=16, chunk_start=32,
    ),
    "48 columns, 3 live": dict(
        p_per=48, n_pages=160, g=2, valid_len=[21, 24],
    ),
    "48 columns, 3 live, stacked": dict(
        p_per=48, n_pages=160, g=2, valid_len=[21, 24], layer=(1, 2),
    ),
    "window's low edge mid-table": dict(
        p_per=12, n_pages=64, g=3, valid_len=[90, 61, 7], window=20,
        cq=16, chunk_start=50,
    ),
    "shared_start > 0, run shorter than its members": dict(
        g=3, valid_len=[37, 9, 45, 48], group_rows=(0, 2, 3),
        shared_pages=2,
    ),
    "shared run under a window that has passed it": dict(
        g=3, valid_len=[37, 9, 45], group_rows=(0, 2), shared_pages=2,
        window=9,
    ),
    "verify row, nq 4": dict(
        g=3, valid_len=[13, 4, 40, 48], nq=4, cq=16, chunk_start=11,
    ),
    "verify rows grouped, nq 4": dict(
        g=3, valid_len=[29, 4, 40], nq=4, group_rows=(0, 2),
        shared_pages=3,
    ),
    "latent pool, rows of 0, 2 and many pages": dict(
        hkv=1, g=4, d=128, latent_dv=64, valid_len=[0, 13, 45],
        group_rows=(1, 2), shared_pages=1, cq=16, chunk_start=16,
    ),
}


@pytest.mark.parametrize("case", list(_WALK))
def test_ragged_walk_matches_reference(case):
    _within_tol(
        parity.ragged_attention_error(**{**_TOY, "seed": 6, **_WALK[case]})
    )


def _eqns_named(jaxpr, name):
    """Every equation of primitive ``name`` in a jaxpr, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _eqns_named(sub, name)
    return found


# The fold's operands (PR 33): on a bfloat16 pool with bfloat16 queries
# the kernel's two products take bf16 operands — the pages' own values,
# the softmax weights as ``_P_TERMS`` bf16 terms — where a float32 pool
# or query keeps the f32 products. Both are held to ONE float64 oracle
# on the same bf16 values, by the kernel's float32 outputs. Here the
# kept path's f32 products are exact (the test process pins "highest");
# on the chip they round both operands to bf16 and read 1e-3 (PERF.md
# §6, PR 33) — so the new path is held to what its two terms carry
# (``parity.RAGGED_ORACLE_TOL``) beside the kept path's own error. One
# term reads ~1e-3 and fails by two orders.
_OPERANDS = {
    "decode rows": dict(g=3, valid_len=[13, 1, 40, 23]),
    "one chunk lane": dict(
        g=3, valid_len=[13, 1, 40, 23], cq=16, chunk_start=11,
    ),
    "three chunk lanes, one dead": dict(
        g=3, valid_len=[13, 1, 40, 23], cq=16, chunk_start=[11, -16, 29],
    ),
    "grouped rows beside three lanes": dict(
        g=3, valid_len=[37, 9, 45, 48], group_rows=(0, 2, 3),
        shared_pages=2, cq=16, chunk_start=[11, 0, 29],
    ),
    "a window's edge": dict(
        p_per=12, g=3, valid_len=[90, 61, 7], window=20, cq=16,
        chunk_start=50,
    ),
    "verify rows, nq 4": dict(
        g=3, valid_len=[29, 4, 40], nq=4, group_rows=(0, 2),
        shared_pages=3,
    ),
    "latent pool": dict(
        hkv=1, g=4, d=128, latent_dv=64, valid_len=[5, 13, 45],
        group_rows=(1, 2), shared_pages=1, cq=16, chunk_start=[16, 3],
    ),
    "stacked": dict(
        g=3, valid_len=[13, 1, 40, 23], cq=16, chunk_start=[11, 20],
        layer=(1, 2),
    ),
    "group of 7, an odd page count": dict(
        hkv=2, g=7, valid_len=[24, 33], cq=16, chunk_start=24,
    ),
}


def _operand_errors(case):
    kw = {**_TOY, "n_pages": 80, "seed": 7, **_OPERANDS[case]}
    return (
        parity.ragged_attention_oracle_error(**kw, dtype=jnp.bfloat16),
        parity.ragged_attention_oracle_error(**kw, dtype=jnp.float32),
    )


@pytest.mark.parametrize("case", list(_OPERANDS))
def test_ragged_bf16_operands_are_as_near_the_oracle_as_f32s(case):
    new, kept = _operand_errors(case)
    assert set(new) == set(kept) and new
    for lane, err in new.items():
        assert kept[lane] < 1e-6, (lane, kept[lane])
        assert err <= 2 * kept[lane] + parity.RAGGED_ORACLE_TOL, (lane, err, kept[lane])


def test_ragged_oracle_bound_tells_a_p_of_one_bf16_term(monkeypatch):
    """The bound above is sharp enough to refuse softmax weights
    rounded to bfloat16: a precision decision, not a speed one."""
    from llm_consensus_tpu.ops.pallas import attention

    assert attention._P_TERMS > 1
    monkeypatch.setattr(attention, "_P_TERMS", 1)
    new, kept = _operand_errors("one chunk lane")
    for lane, err in new.items():
        assert err > 10 * (2 * kept[lane] + parity.RAGGED_ORACLE_TOL), (lane, err)


@pytest.mark.parametrize(
    "latent, dtype, operands",
    [
        (False, jnp.bfloat16, {"bfloat16"}),
        (True, jnp.bfloat16, {"bfloat16"}),
        (False, jnp.float32, {"float32"}),
    ],
    ids=["pool", "latent pool", "float32 pool"],
)
def test_ragged_products_take_the_pools_own_values(latent, dtype, operands):
    """On a bfloat16 pool no product sees a float32 copy of a query, a
    key or a value (the softmax weights arrive as bf16 terms), each
    states the float32 it accumulates in and its precision; a float32
    pool keeps float32 operands. Decode rows, lanes and groups alike."""
    from llm_consensus_tpu.ops.pallas.attention import ragged_paged_attention

    b, gm, hkv, g, d, pg, p_per = 3, 2, 1 if latent else 2, 3, 32, 8, 6
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    page = (pg, d) if latent else (pg, hkv, d)
    pool = jnp.zeros((2, 64, *page), dtype)
    kw = dict(latent_dv=16) if latent else {}
    jaxpr = jax.make_jaxpr(
        lambda layer: ragged_paged_attention(
            jnp.zeros((b, hkv * g, d), dtype), pool, pool, i32(b, p_per),
            i32(b), q_chunk=jnp.zeros((2, 16, hkv * g, d), dtype),
            chunk_table=i32(2, p_per), chunk_start=i32(2),
            groups=(i32(b), i32(gm), i32(gm), i32(b)), layer=layer,
            interpret=True, **kw,
        )
    )(jnp.int32(1))
    (call,) = _eqns_named(jaxpr.jaxpr, "pallas_call")
    dots = _eqns_named(call.params["jaxpr"], "dot_general")
    # q.k and p.v (a bf16 term each), for a row and for a group.
    assert len(dots) >= 4
    for dot in dots:
        assert {str(v.aval.dtype) for v in dot.invars} == operands
        assert dot.params["preferred_element_type"] == jnp.float32
        if dtype == jnp.bfloat16:
            assert dot.params["precision"] is not None


def _pallas_grids(jaxpr):
    """The grid of every ``pallas_call`` in a jaxpr, nested ones too."""
    return [
        tuple(eqn.params["grid_mapping"].grid)
        for eqn in _eqns_named(jaxpr, "pallas_call")
    ]


@pytest.mark.parametrize("p_per", [8, 48])
def test_ragged_grid_is_one_step_a_row_whatever_the_table_width(p_per):
    """b decode rows + the chunk lane + gm group programs, and no page
    axis: the pages are walked inside a step, by the lengths."""
    from llm_consensus_tpu.ops.pallas.attention import ragged_paged_attention

    b, gm, hkv, g, d, pg = 3, 2, 2, 3, 32, 8
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    pool = jnp.zeros((2, 64, pg, hkv, d), jnp.bfloat16)
    q = jnp.zeros((b, hkv * g, d), jnp.bfloat16)

    def call(chunk, groups):
        kw = {}
        if chunk:
            kw.update(
                q_chunk=jnp.zeros((16, hkv * g, d), jnp.bfloat16),
                chunk_table=i32(p_per), chunk_start=jnp.int32(0),
            )
        if groups:
            kw["groups"] = (i32(b), i32(gm), i32(gm), i32(b))
        return jax.make_jaxpr(
            lambda layer: ragged_paged_attention(
                q, pool, pool, i32(b, p_per), i32(b), layer=layer,
                interpret=True, **kw,
            )
        )(jnp.int32(1))

    assert _pallas_grids(call(False, False).jaxpr) == [(b,)]
    assert _pallas_grids(call(True, False).jaxpr) == [(b + 1,)]
    assert _pallas_grids(call(True, True).jaxpr) == [(b + 1 + gm,)]


def test_ragged_stacked_q8_shared_prefix_matches_reference():
    """The stacked int8 cache case that used to FALL BACK to the
    ungrouped stacked kernel: shared-prefix attention through the
    ragged kernel's stacked layout, vs the dequantizing reference."""
    rng = np.random.default_rng(5)
    L, b, hkv, s_len, d, g = 3, 4, 2, 64, 32, 3
    h = hkv * g
    kq = jnp.asarray(rng.integers(-127, 127, (L, b, hkv, s_len, d)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 127, (L, b, hkv, s_len, d)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.02, (L, b, hkv, s_len)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.02, (L, b, hkv, s_len)), jnp.float32)
    plen = 21  # mid-block boundary
    kq = kq.at[:, :, :, :plen].set(
        jnp.broadcast_to(kq[:, :1, :, :plen], (L, b, hkv, plen, d))
    )
    vq = vq.at[:, :, :, :plen].set(
        jnp.broadcast_to(vq[:, :1, :, :plen], (L, b, hkv, plen, d))
    )
    ks = ks.at[:, :, :, :plen].set(
        jnp.broadcast_to(ks[:, :1, :, :plen], (L, b, hkv, plen))
    )
    vs = vs.at[:, :, :, :plen].set(
        jnp.broadcast_to(vs[:, :1, :, :plen], (L, b, hkv, plen))
    )
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.bfloat16)
    vl = jnp.asarray([30, 25, 64, 40], jnp.int32)
    layer = jnp.int32(1)
    got = flash_decode_attention_shared_prefix_q8_stacked(
        q, kq, ks, vq, vs, vl, jnp.int32(plen), layer, interpret=True
    )
    ref = decode_attention_shared_prefix_quant(
        q, kq[1], ks[1], vq[1], vs[1], vl, jnp.int32(plen)
    )
    _check(got, ref)


# ---------------------------------------------------------------------------
# Batcher: the fused scheduler step
# ---------------------------------------------------------------------------

_HEADER = "Panel shared header for every persona, forty ch: "


def _serve(batcher, prompts, **kw):
    futs = [batcher.submit(p, **kw) for p in prompts]
    return [f.result(timeout=120) for f in futs]


def _quiesce(batcher, timeout=10.0):
    """Wait for the scheduler loop to go fully idle: futures resolve
    at fetch time, but the loop can still be draining in-flight
    programs/overshoot — counter reads across that tail would smear
    iterations between measurement windows."""
    import time

    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        s = batcher.stats()
        if (
            s["active_slots"] == 0
            and s["prefilling_slots"] == 0
            and s["dispatch_inflight"] == 0
            and s["waiting"] == 0
        ):
            return s
        time.sleep(0.01)
    return batcher.stats()


def _burst_texts(params, ragged, depth=2, chunk=16, cfg=CFG, cfgkw=None,
                 prompts=None, **submit_kw):
    ccfg = dict(_CCFG, prefill_chunk=chunk)
    ccfg.update(cfgkw or {})
    b = ContinuousBatcher(
        cfg,
        params,
        config=ContinuousConfig(
            **ccfg, pipeline_depth=depth, ragged_attention=ragged
        ),
    )
    prompts = prompts or [
        _HEADER + "alpha tail one",
        _HEADER + "beta tail two",
        "unrelated prompt entirely",
        _HEADER + "gamma tail three",
    ]
    try:
        return [r.text for r in _serve(b, prompts, **submit_kw)], b.stats()
    finally:
        b.close()


@pytest.mark.parametrize("ragged", [True, False], ids=["fused", "split"])
def test_idle_slots_hold_nothing_while_others_decode(params, ragged):
    """A slot without a request keeps length 0 through every step
    program that runs beside it (decode and fused alike), so the
    attention kernel has no page to walk for it: a length that grew
    with each step had an idle row fold the NULL page up to a whole
    table's width, every layer of every step."""
    b = ContinuousBatcher(
        CFG, params,
        config=ContinuousConfig(**_CCFG, ragged_attention=ragged),
    )
    try:
        outs = _serve(
            b, [_HEADER + "one", "a second, unshared prompt"],
            max_new_tokens=8,
        )
        _quiesce(b)
        lengths = np.asarray(b.cache.length)
    finally:
        b.close()
    assert all(o.num_tokens == 8 for o in outs)
    # Two of four slots served; retirement released them too.
    assert lengths.tolist() == [0, 0, 0, 0]


def test_fused_text_parity_across_depths_and_chunks(params):
    """THE acceptance contract: generated text is byte-identical with
    the fused scheduler step on vs off, across pipeline depths {1, 2}
    and prefill-chunk widths — the fused program is a pure
    restructuring of the same math."""
    want, _ = _burst_texts(params, ragged=False, depth=1, chunk=16)
    for depth in (1, 2):
        for chunk in (16, 32):
            for ragged in (True, False):
                got, _ = _burst_texts(
                    params, ragged=ragged, depth=depth, chunk=chunk
                )
                assert got == want, (ragged, depth, chunk)


def test_fused_stop_mid_chunk_parity(params):
    """A multi-token string stop landing while a later request's chunk
    rides the pipeline: stop-trim and retirement must stay
    byte-identical to the split-program path."""
    prompts = [_HEADER + "one", _HEADER + "two", _HEADER + "three"]
    kw = dict(prompts=prompts, temperature=0.9, seed=3, stop=["\x00", "ab"])
    want, _ = _burst_texts(params, ragged=False, depth=1, **kw)
    for ragged, depth in ((True, 1), (True, 2), (False, 2)):
        got, _ = _burst_texts(params, ragged=ragged, depth=depth, **kw)
        assert got == want, (ragged, depth)


def test_fused_sliding_window_config_parity(params):
    """Sliding-window configs used to fall back out of the grouped
    kernel AND the fused path did not exist; now both ride the same
    ragged program — text parity on a windowed model config."""
    wcfg = CFG.with_(sliding_window=24)
    want, _ = _burst_texts(params, ragged=False, depth=1, cfg=wcfg)
    for ragged, depth in ((True, 1), (True, 2)):
        got, _ = _burst_texts(params, ragged=ragged, depth=depth, cfg=wcfg)
        assert got == want, (ragged, depth)


def test_fused_eviction_and_host_restore_in_flight(params):
    """Host-tier demote/restore (flush-first stable-cache operations)
    interleaved with fused dispatches: text parity holds and the tier
    still engages. Pool sized so the second round's header must come
    back from the host store."""
    cfgkw = dict(
        max_slots=2,
        page_size=16,
        n_pages=13,  # 12 usable vs a 2x6-page unshared working set
        pages_per_seq=8,
        max_new_tokens=6,
        seq_buckets=(16, 32, 64),
        prefill_chunk=16,
        share_prefix=True,
        host_cache_bytes=8 << 20,
    )
    rounds = [
        [_HEADER + f"p{i} proposes" for i in range(2)],
        [
            f"{i} unique filler storm with plenty of padding text {i}"
            for i in range(4)
        ],
        [_HEADER + f"r{i} re-votes" for i in range(2)],
    ]

    def run(ragged):
        b = ContinuousBatcher(
            CFG,
            params,
            config=ContinuousConfig(**cfgkw, ragged_attention=ragged),
        )
        try:
            texts = []
            for r in rounds:
                texts.append([x.text for x in _serve(b, r)])
            return texts, b.stats()
        finally:
            b.close()

    want, st_off = run(False)
    got, st_on = run(True)
    assert got == want
    assert st_on["offload_restored_pages"] >= 1
    assert st_on["offload_restored_pages"] == st_off["offload_restored_pages"]


def test_device_programs_one_per_iteration_and_metrics_lockstep(params):
    """Fused leg: every scheduler iteration that ran device work ran
    exactly ONE program; unfused leg: chunk+decode iterations ran two.
    The Prometheus families move by the batcher's own deltas."""
    from llm_consensus_tpu.server.metrics import DEVICE_PROGRAMS, RAGGED_ROWS

    prompts = [_HEADER + f"req {i}" for i in range(6)] + [
        f"unique header {i} " * 4 for i in range(6)
    ]

    def run(ragged):
        before = {
            k: DEVICE_PROGRAMS.labels(kind=k).value
            for k in ("fused", "decode", "prefill")
        }
        rows0 = (RAGGED_ROWS.sum, RAGGED_ROWS.count)
        b = ContinuousBatcher(
            CFG,
            params,
            config=ContinuousConfig(**_CCFG, ragged_attention=ragged),
        )
        try:
            texts = [r.text for r in _serve(b, prompts)]
            st = _quiesce(b)
        finally:
            b.close()
        for k in ("fused", "decode", "prefill"):
            assert (
                DEVICE_PROGRAMS.labels(kind=k).value - before[k]
                == st[f"device_programs_{k}"]
            )
        assert RAGGED_ROWS.sum - rows0[0] == st["ragged_rows_sum"]
        assert RAGGED_ROWS.count - rows0[1] == st["ragged_rows_count"]
        return texts, st

    texts_on, st_on = run(True)
    texts_off, st_off = run(False)
    assert texts_on == texts_off
    on_programs = sum(
        st_on[f"device_programs_{k}"] for k in ("fused", "decode", "prefill")
    )
    off_programs = sum(
        st_off[f"device_programs_{k}"] for k in ("fused", "decode", "prefill")
    )
    # Fusion engaged and collapsed chunk+decode iterations to ONE
    # program; the old path needed more programs than iterations.
    assert st_on["device_programs_fused"] >= 1
    assert on_programs == st_on["work_iterations"]
    assert off_programs > st_off["work_iterations"]
    assert st_off["device_programs_fused"] == 0
    # Ragged-row occupancy counts decode rows + the fused chunk lane.
    assert st_on["ragged_rows_count"] >= st_on["device_programs_fused"]


def test_prefill_chunks_and_stall_lockstep_under_fusion(params):
    """Fused chunks observe a 0 stall (they ride the dispatch) but the
    histogram count stays in lockstep with ``prefill_chunks`` — the
    PR 2 contract survives the fusion."""
    from llm_consensus_tpu.server.metrics import PREFILL_STALL_SECONDS

    before = PREFILL_STALL_SECONDS.count
    _, st = _burst_texts(params, ragged=True)
    assert st["prefill_chunks"] >= 2
    assert PREFILL_STALL_SECONDS.count - before == st["prefill_chunks"]


# ---------------------------------------------------------------------------
# Batcher: chunk lanes (PR 31)
# ---------------------------------------------------------------------------

# The cells' own rule: 8 slots and 64-token chunks leave the 256-row
# token axis room for three lanes.
_LCFG = dict(
    max_slots=8, page_size=16, n_pages=256, pages_per_seq=20,
    max_new_tokens=6, seq_buckets=(64, 128, 256), prefill_chunk=64,
    share_prefix=True,
)
# 127 bytes behind the BOS token: eight full pages. The tails part at
# their first byte, so no partly shared page is there to copy whether a
# donor has finished or not.
_LONG_HEADER = (("Panel header shared by every evaluation, one page a line"
                 + "." * 8) * 2)[:127]
_LANE_PROMPTS = [
    _LONG_HEADER + f"{i} evaluates the answer of a persona " + "x" * (5 + 7 * i)
    for i in range(4)
] + [f"{i + 5} is the number of an unshared prompt: " + "yz" * (60 + 9 * i)
     for i in range(2)]


def _lane_counters():
    from llm_consensus_tpu.server.metrics import (
        CHUNK_LANES, DEVICE_PROGRAMS, PREFILL_TOKENS,
    )

    lanes = {
        (kind, n): CHUNK_LANES.labels(kind=kind, lanes=str(n)).value
        for kind in ("prefill", "fused") for n in range(1, 9)
    }
    return {
        "tokens": PREFILL_TOKENS.value,
        "programs": {
            k: DEVICE_PROGRAMS.labels(kind=k).value
            for k in ("prefill", "fused")
        },
        "lanes": lanes,
    }


def _lane_run(params, waves, cfg=CFG):
    """Serve ``waves`` (lists of prompts sent together) one after the
    other on one batcher; results, stats and counter deltas."""
    before = _lane_counters()
    b = ContinuousBatcher(cfg, params, config=ContinuousConfig(**_LCFG))
    try:
        assert b._lanes_for(64) == 3
        outs = []
        for wave in waves:
            outs += _serve(b, wave)
            _quiesce(b)
        st = _quiesce(b)
    finally:
        b.close()
    after = _lane_counters()
    delta = {
        "tokens": after["tokens"] - before["tokens"],
        "programs": {k: after["programs"][k] - before["programs"][k]
                     for k in after["programs"]},
        "lanes": {k: after["lanes"][k] - before["lanes"][k]
                  for k in after["lanes"] if after["lanes"][k] != before["lanes"][k]},
    }
    return outs, st, delta


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "kernel"])
def test_chunk_lanes_burst_equals_one_lane_serving(params, use_pallas):
    """Four prompts that share a page-aligned header and two that share
    nothing, sent together (their ready chunks ride three to a program)
    against the same prompts sent one after another (one lane, as
    before): byte-identical greedy text and token counts, the same
    prefix hits and prompt tokens computed — in a third of the chunk
    programs."""
    cfg = CFG.with_(use_pallas=use_pallas)
    burst, st_b, d_b = _lane_run(params, [_LANE_PROMPTS], cfg)
    alone, st_a, d_a = _lane_run(params, [[p] for p in _LANE_PROMPTS], cfg)
    assert [(o.text, o.num_tokens) for o in burst] == [
        (o.text, o.num_tokens) for o in alone
    ]
    for key in ("prefix_hits", "prefix_pages_shared", "prefix_pages_copied",
                "prefill_chunks"):
        assert st_b[key] == st_a[key], key
    assert st_b["prefix_pages_shared"] == 3 * 8  # three mappers, 8 pages
    assert d_b["tokens"] == d_a["tokens"] == sum(
        o.timing["prompt_tokens"] for o in burst
    ) - 3 * 8 * 16

    chunks = st_b["prefill_chunks"]
    assert chunks == sum(
        -(-(o.timing["prompt_tokens"] - 16 * o.timing["header_pages_shared"])
          // 64)
        for o in burst
    ) == 3 + 3 * 1 + 2 * 3  # donor, mappers, the unshared
    # One after another: a program a chunk, every one the narrow one.
    programs_a = d_a["programs"]["prefill"] + d_a["programs"]["fused"]
    assert programs_a == chunks
    assert set(n for _, n in d_a["lanes"]) == {1}
    # Together: three lanes a program, but for the donor's head start
    # (its two header chunks, which the mappers wait for).
    programs_b = d_b["programs"]["prefill"] + d_b["programs"]["fused"]
    assert programs_b <= -(-chunks // 3) + 2, d_b
    assert max(n for _, n in d_b["lanes"]) == 3
    # The lanes counter: by kind it sums to the chunk programs, and
    # weighted by its lanes to the chunks.
    for d in (d_a, d_b):
        for kind in ("prefill", "fused"):
            assert sum(
                v for (k, _), v in d["lanes"].items() if k == kind
            ) == d["programs"][kind]
        assert sum(n * v for (_, n), v in d["lanes"].items()) == chunks
    assert sum(
        v for k, v in st_b.items() if k.startswith("chunk_lanes_")
    ) == programs_b


def test_lone_prompt_takes_the_narrow_program(params):
    """One ready slot: the one-lane programs, as many as it has chunks,
    and no wide program is ever called."""
    prompt = "a lone prompt of three chunks: " + "ab" * 70
    outs, st, d = _lane_run(params, [[prompt]])
    assert st["prefill_chunks"] == 3
    assert d["programs"]["prefill"] + d["programs"]["fused"] == 3
    assert d["lanes"] == {("prefill", 1): 3}
