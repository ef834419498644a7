"""The serving path's kernels, compiled for a TPU v5e at real widths:
the ragged attention walk at the three configurations' sizes and over
the engine's int8 caches, and the grouped expert matmul
DeepSeek-V2-Lite adds — by the chip's own compiler, for a chip that is
described and not attached (no chip time, ~2 s a case). Interpret mode cannot show what Mosaic refuses (tiling, VMEM) or
what XLA copies around a kernel; a compile that passes is still not a
chip run.

All in one file and behind one fixture: only one process may load the
TPU library, and the worker that is given this file is the one that
does (the ``on-chip-measurement`` guide, section 2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# The cell's sizes: 19 layers, 1,024 pages of 64 tokens, 16 slots, 48
# pages a sequence, 64-token chunks, 4 groups; 18 layers of 64 experts.
L, PAGES, PG, LANES, DV, H = 19, 1024, 64, 640, 512, 16
B, P, C, G = 16, 48, 64, 4


# The three configurations' real widths: (layers, pool pages, Hkv, query
# heads a kv head, key lanes, latent value lanes, slots, group programs).
# A table of 48 columns, a 64-query chunk lane.
_RAGGED_WIDTHS = {
    "mistral-7b": (32, 512, 8, 4, 128, 0, 8, 4),
    "qwen2-7b": (28, 1024, 4, 7, 128, 0, 16, 8),
    "deepseek-v2-lite": (L, PAGES, 1, H, LANES, DV, B, 8),
}


@pytest.mark.parametrize("lanes", [1, 3])
@pytest.mark.parametrize("model", list(_RAGGED_WIDTHS))
def test_ragged_attention_walk_compiles_without_copying_the_pool(
    one_chip, model, lanes
):
    """The kernel that walks each row's live pages by its own DMAs, with
    every lane it serves (decode rows, the one or three chunk lanes of a
    step program, group programs) over the stacked pool in HBM — bf16
    queries on bf16 pages, so the fold's operands are the pages' own
    (PR 33): a lane's [64 x G, D] bf16 query block, sub-tile [G, D]
    blocks for the decode rows, two pages a tile in four page slots."""
    from llm_consensus_tpu.ops.pallas.attention import ragged_paged_attention

    layers, pages, hkv, g, d, dv, b, gm = _RAGGED_WIDTHS[model]
    h = hkv * g
    latent = dict(scale=0.1147, latent_dv=dv) if dv else {}

    def call(q, k, v, tbl, valid, qc, ct, cs, gid, rep, gend, ss, layer):
        return ragged_paged_attention(
            q, k, v, tbl, valid, q_chunk=qc, chunk_table=ct,
            chunk_start=cs, groups=(gid, rep, gend, ss), layer=layer,
            interpret=False, **latent,
        )

    i32 = lambda *s: _shape(one_chip, s, jnp.int32)  # noqa: E731
    page = (PG, d) if dv else (PG, hkv, d)
    pool = _shape(one_chip, (layers, pages, *page), jnp.bfloat16)
    compiled = jax.jit(call).lower(
        _shape(one_chip, (b, h, d), jnp.bfloat16),
        pool, None if dv else pool,
        i32(b, P), i32(b), _shape(one_chip, (lanes, C, h, d), jnp.bfloat16),
        i32(lanes, P), i32(lanes), i32(b), i32(gm), i32(gm), i32(b), i32(),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # The pools are 1.6-4.3 GB: any temporary near that is a copy of one
    # (what a 576-lane pool costs: ModelConfig.latent_pool_dim).
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_ragged_attention_walk_compiles_under_shard_map(topo):
    """The mesh kernel (``--mesh data=2,model=2``, no cell): mistral's
    pool over four described chips, kv heads over ``model``, rows and
    pages over ``data``, the chunk lane (the mesh lowering carries one)
    and the groups riding along; a shard folds 4 kv heads of bf16
    operands, two pages a tile."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as Ps

    from llm_consensus_tpu.ops.pallas.attention import (
        ragged_paged_attention_sharded,
    )

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    _, pages, hkv, g, d, _, b, gm = _RAGGED_WIDTHS["mistral-7b"]
    h = hkv * g

    def on(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, Ps(*spec))
        )

    def call(q, k, v, tbl, valid, qc, ct, cs, gid, rep, gend, ss):
        return ragged_paged_attention_sharded(
            mesh, q, k, v, tbl, valid, q_chunk=qc, chunk_table=ct,
            chunk_start=cs, groups=(gid, rep, gend, ss), window=4096,
            interpret=False,
        )

    pool = on((pages, PG, hkv, d), jnp.bfloat16, "data", None, "model", None)
    compiled = jax.jit(call).lower(
        on((b, h, d), jnp.bfloat16, "data", "model", None), pool, pool,
        on((b, P), jnp.int32, "data", None), on((b,), jnp.int32, "data"),
        on((C, h, d), jnp.bfloat16, None, "model", None),
        on((P,), jnp.int32, None), on((), jnp.int32),
        on((b,), jnp.int32, "data"), on((gm,), jnp.int32, None),
        on((gm,), jnp.int32, None), on((b,), jnp.int32, "data"),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("stacked", [False, True], ids=["cache", "stacked"])
def test_int8_cache_shared_prefix_walk_compiles(one_chip, stacked):
    """The engine's int8 head-major caches take the same walk over their
    virtual pages: a page is a strided copy of [Hkv, 128, D] int8 and its
    [Hkv, 128] scales, at a slot offset that is data."""
    from llm_consensus_tpu.ops.pallas.attention import (
        flash_decode_attention_shared_prefix_q8,
        flash_decode_attention_shared_prefix_q8_stacked,
    )

    b, hkv, g, s_len, d = 4, 8, 4, 1024, 128
    lead = (32,) if stacked else ()
    kv = _shape(one_chip, (*lead, b, hkv, s_len, d), jnp.int8)
    sc = _shape(one_chip, (*lead, b, hkv, s_len), jnp.float32)
    i32 = lambda *s: _shape(one_chip, s, jnp.int32)  # noqa: E731
    fn = (
        flash_decode_attention_shared_prefix_q8_stacked
        if stacked
        else flash_decode_attention_shared_prefix_q8
    )
    compiled = jax.jit(
        lambda *a: fn(*a, window=512, interpret=False)
    ).lower(
        _shape(one_chip, (b, 1, hkv * g, d), jnp.bfloat16),
        kv, sc, kv, sc, i32(b), *([i32()] * (2 if stacked else 1)),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize(
    "k,n,assignments",
    [(2048, 1408, 480), (1408, 2048, 480), (2048, 1408, 6),
     (2688, 1920, 1248), (1920, 2688, 1248)],
    ids=["gate-fused-step", "down-fused-step", "gate-one-row",
         "relu2-up-208-rows", "relu2-down-208-rows"],
)
def test_moe_grouped_matmul_compiles_on_the_resident_stack(
    one_chip, k, n, assignments
):
    from llm_consensus_tpu.ops.pallas.moe_matmul import (
        MOE_TILE,
        moe_grouped_matmul,
        n_tiles_for,
    )

    layers, experts = 18, 64
    tiles = n_tiles_for(assignments, experts)

    def call(x, w, s, tile_expert, n_live, layer):
        return moe_grouped_matmul(
            x, w.reshape(-1, k, n), s.reshape(-1, 1, n),
            tile_expert + layer * experts, n_live, interpret=False,
        )

    compiled = jax.jit(call).lower(
        _shape(one_chip, (tiles * MOE_TILE, k), jnp.bfloat16),
        _shape(one_chip, (layers, experts, k, n), jnp.int8),
        _shape(one_chip, (layers, experts, 1, n), jnp.float32),
        _shape(one_chip, (tiles,), jnp.int32),
        _shape(one_chip, (1,), jnp.int32),
        _shape(one_chip, (), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # 3.3 GB of expert matrices: merging the stack's leading axes and
    # indexing it must not materialise any of it.
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


# The cells' fused step (PR 31): (layers served, slots, pool pages, group
# programs) and three lanes of 64 tokens — 8 + 192 and 16 + 192 rows.
_FUSED_CELLS = {
    "mistral-7b": (32, 8, 512, 4),
    "qwen2-7b": (28, 16, 1024, 8),
    "deepseek-v2-lite": (L, B, PAGES, 8),
}


@pytest.mark.parametrize("grouped", [False, True], ids=["plain", "grouped"])
@pytest.mark.parametrize("model", list(_FUSED_CELLS))
def test_fused_step_with_three_lanes_compiles_at_the_cells_shapes(
    one_chip, monkeypatch, model, grouped
):
    """``jit_fused_step`` with L = 3 at each configuration's real widths
    and depth, int8 weights as the cells serve them: ISSUE 25's probe saw
    16 rows beside ONE 256-wide chunk refused for 16.08 of 16.00 MiB of
    scoped VMEM; three 64-wide lanes are a block a lane, and pass."""
    from functools import partial

    from llm_consensus_tpu.models import transformer as T
    from llm_consensus_tpu.models.configs import PRESETS
    from llm_consensus_tpu.models.paged_cache import (
        DecodeGroupArrays,
        PagedKVCache,
    )

    # The kernels ask the backend whether to compile or interpret.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layers, slots, pages, gm = _FUSED_CELLS[model]
    cfg = PRESETS[model]
    if layers != cfg.n_layers:
        cfg = cfg.with_layers(layers)
    cfg = cfg.with_(use_pallas=True)

    def described(tree):
        return jax.tree.map(
            lambda a: _shape(one_chip, a.shape, a.dtype), tree
        )

    params = described(jax.eval_shape(
        lambda: T.init_params_quantized(cfg, jax.random.PRNGKey(0))
    ))
    cache = described(jax.eval_shape(
        lambda: PagedKVCache.create(cfg, pages, PG, slots, P)
    ))
    i32 = lambda *s: _shape(one_chip, s, jnp.int32)  # noqa: E731
    groups = (
        DecodeGroupArrays(i32(slots), i32(gm), i32(gm), i32(slots))
        if grouped else None
    )
    lanes = 3
    # The serving process's matmul precision, not the test process's
    # ``highest`` (Mosaic refuses a bf16 dot under it).
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(
            partial(T.fused_step_paged, cfg), donate_argnums=(2,)
        ).lower(
            params, i32(slots, 1), cache, i32(lanes, C), i32(lanes, P),
            i32(lanes), groups,
        ).compile()
    text = compiled.as_text()
    # The ragged attention, the int8 matrices and (deepseek) the grouped
    # expert matmul are all still kernels at 200 / 208 rows.
    assert text.count("tpu_custom_call") >= 10
    assert "quant_matmul_stacked" in text
    # Both pools alias the donated cache; nothing pool-sized is copied.
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


# -- a recurrent model's programs (PR 32) -----------------------------------
# nemotron-3-nano-30b-a3b's cell: the plan's first 18 layers, 16 slots,
# 1,024 pages, 96 state slots of 8 layers x [64, 64, 128] float32.


@pytest.mark.parametrize("rows,tokens", [(16, 8), (3, 64)],
                         ids=["decode-rows", "chunk-lanes"])
def test_ssm_scan_compiles_on_the_resident_state_pool(one_chip, rows, tokens):
    """The scan kernel with its float32 products at "highest" and the
    state pool aliased through it: Mosaic takes it, and nothing
    pool-sized (1.6 GB) is a temporary."""
    from llm_consensus_tpu.ops.pallas.ssm_scan import ssm_scan

    h, p, n = 64, 64, 128
    f32 = lambda *s: _shape(one_chip, s, jnp.float32)  # noqa: E731
    i32 = lambda *s: _shape(one_chip, s, jnp.int32)  # noqa: E731

    def call(x, m, ce, bw, f, pool, layer, sin, sout):
        return ssm_scan(
            dict(x=x, m=m, ce=ce, bw=bw, f=f), pool, layer, sin, sout,
            interpret=False,
        )

    compiled = jax.jit(call, donate_argnums=(5,)).lower(
        f32(rows, h, tokens, p), f32(rows, h, tokens, tokens),
        f32(rows, h, tokens, n), f32(rows, h, tokens, n), f32(rows, h, 1, n),
        f32(8, 96, h, p, n), i32(), i32(rows), i32(rows),
    ).compile()
    assert "ssm_scan" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("program", ["fused", "decode", "chunk"])
def test_recurrent_models_step_programs_compile_without_copying_a_pool(
    one_chip, monkeypatch, program
):
    """``jit_fused_step`` (3 lanes, grouped), ``jit_decode_step`` and the
    wide ``jit_prefill_chunk`` of the 18-layer cut at the cell's sizes:
    the K/V pool, the state pool and every weight stack alias or stay
    where they are — a copy of any would be a temporary of 0.13-5 GB."""
    from functools import partial

    from llm_consensus_tpu.models import transformer as T
    from llm_consensus_tpu.models.configs import PRESETS
    from llm_consensus_tpu.models.paged_cache import (
        DecodeGroupArrays,
        PagedKVCache,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = PRESETS["nemotron-3-nano-30b-a3b"].with_layers(18).with_(
        use_pallas=True
    )
    slots, lanes, gm = 16, 3, 8

    def described(tree):
        return jax.tree.map(
            lambda a: _shape(one_chip, a.shape, a.dtype), tree
        )

    params = described(jax.eval_shape(
        lambda: T.init_params_quantized(cfg, jax.random.PRNGKey(0))
    ))
    cache = described(jax.eval_shape(
        lambda: PagedKVCache.create(cfg, PAGES, PG, slots, P, state_slots=96)
    ))
    i32 = lambda *s: _shape(one_chip, s, jnp.int32)  # noqa: E731
    groups = DecodeGroupArrays(i32(slots), i32(gm), i32(gm), i32(slots))
    lane = (i32(lanes, C), i32(lanes, P), i32(lanes))
    with jax.default_matmul_precision("default"):
        if program == "fused":
            lowered = jax.jit(
                partial(T.fused_step_paged, cfg), donate_argnums=(2,)
            ).lower(params, i32(slots, 1), cache, *lane, groups,
                    chunk_state=i32(lanes, 4))
        elif program == "decode":
            lowered = jax.jit(
                partial(T.decode_step_paged, cfg), donate_argnums=(2,)
            ).lower(params, i32(slots, 1), cache, groups)
        else:
            lowered = jax.jit(
                partial(T.prefill_chunk_paged, cfg), donate_argnums=(4,)
            ).lower(params, *lane, cache, chunk_state=i32(lanes, 4))
        compiled = lowered.compile()
    text = compiled.as_text()
    for kernel in ("ssm_scan", "moe_grouped_matmul", "quant_matmul_stacked"):
        assert kernel in text, kernel
    assert compiled.memory_analysis().temp_size_in_bytes < 96 * 2**20
