"""Multi-host helpers (parallel/multihost.py) on the single-process
CPU mesh — the functions must degrade exactly to the single-host path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from llm_consensus_tpu.parallel.mesh import MeshConfig, make_mesh
from llm_consensus_tpu.parallel.multihost import (
    DistributedConfig,
    host_array_to_global,
    initialize_distributed,
    local_batch_slice,
    make_multislice_mesh,
)


def test_initialize_noop_single_host(monkeypatch):
    monkeypatch.delenv("COORDINATOR_ADDRESS", raising=False)
    monkeypatch.delenv("NUM_PROCESSES", raising=False)
    assert initialize_distributed(DistributedConfig()) is False


def test_local_batch_slice_single_process():
    per, off = local_batch_slice(32)
    assert (per, off) == (32, 0)


def test_multislice_mesh_falls_back_single_slice(cpu_devices):
    mesh = make_multislice_mesh(MeshConfig(data=4, model=2), n_slices=1)
    assert mesh.shape["data"] == 4 and mesh.shape["model"] == 2


def test_multislice_mesh_rejects_ici_critical_axes():
    with pytest.raises(ValueError, match="DCN"):
        make_multislice_mesh(MeshConfig(model=8), dcn_axis="model")
    with pytest.raises(ValueError, match="DCN"):
        make_multislice_mesh(MeshConfig(expert=8), dcn_axis="expert")
    with pytest.raises(ValueError, match="not in"):
        make_multislice_mesh(MeshConfig(data=8), dcn_axis="batch")


def test_host_array_to_global_single_process(cpu_devices):
    mesh = make_mesh(MeshConfig(data=8), cpu_devices)
    x = np.arange(64, dtype=np.int32).reshape(8, 8)
    arr = host_array_to_global(x, mesh, P("data", None))
    assert isinstance(arr, jax.Array)
    np.testing.assert_array_equal(np.asarray(arr), x)
    assert arr.sharding.spec == P("data", None)


def test_dcn_axis_divisibility_check():
    with pytest.raises(ValueError, match="divisible"):
        make_multislice_mesh(MeshConfig(data=3), dcn_axis="data", n_slices=2)


def test_initialize_raises_on_explicit_config_failure(monkeypatch):
    """An explicitly configured multi-process job must NOT silently fall
    back to single-host (divergent replicas); it raises (ADVICE r1)."""
    from llm_consensus_tpu.parallel.multihost import (
        DistributedConfig,
        initialize_distributed,
    )

    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: False)

    def boom(**kw):
        raise ConnectionError("coordinator unreachable")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    with pytest.raises(RuntimeError, match="explicitly"):
        initialize_distributed(
            DistributedConfig(
                coordinator_address="10.0.0.1:1234",
                num_processes=2,
                process_id=0,
            )
        )
