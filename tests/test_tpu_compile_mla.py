"""The two kernels DeepSeek-V2-Lite adds to the serving path, compiled
for a TPU v5e at the cell's real widths — by the chip's own compiler,
for a chip that is described and not attached (no chip time, ~2 s a
case). Interpret mode cannot show what Mosaic refuses (tiling, VMEM) or
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
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# The cell's sizes: 19 layers, 1,024 pages of 64 tokens, 16 slots, 48
# pages a sequence, 64-token chunks, 4 groups; 18 layers of 64 experts.
L, PAGES, PG, LANES, DV, H = 19, 1024, 64, 640, 512, 16
B, P, C, G = 16, 48, 64, 4


def test_latent_ragged_attention_compiles_without_copying_the_pool(one_chip):
    from llm_consensus_tpu.ops.pallas.attention import ragged_paged_attention

    def call(q, pool, tbl, valid, qc, ct, cs, gid, rep, gend, ss, layer):
        return ragged_paged_attention(
            q, pool, None, tbl, valid, q_chunk=qc, chunk_table=ct,
            chunk_start=cs, groups=(gid, rep, gend, ss), layer=layer,
            scale=0.1147, latent_dv=DV, interpret=False,
        )

    i32 = lambda *s: _shape(one_chip, s, jnp.int32)  # noqa: E731
    compiled = jax.jit(call).lower(
        _shape(one_chip, (B, H, LANES), jnp.bfloat16),
        _shape(one_chip, (L, PAGES, PG, LANES), jnp.bfloat16),
        i32(B, P), i32(B), _shape(one_chip, (C, H, LANES), jnp.bfloat16),
        i32(P), i32(), i32(B), i32(G), i32(G), i32(B), i32(),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # The pool is 1.6 GB: any temporary near that is a copy of it (what
    # a 576-lane pool costs: ModelConfig.latent_pool_dim).
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize(
    "k,n,assignments",
    [(2048, 1408, 480), (1408, 2048, 480), (2048, 1408, 6)],
    ids=["gate-fused-step", "down-fused-step", "gate-one-row"],
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
