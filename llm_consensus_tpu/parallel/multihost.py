"""Multi-host / multi-slice initialization and mesh construction.

The reference's "distributed backend" is in-process actix mailboxes —
single process, single machine (SURVEY.md §2, §5). The TPU-native
counterpart spans hosts two ways:

- **One slice, many hosts** (e.g. v5e-64 = 16 hosts): ``jax.distributed``
  connects the processes; ``jax.devices()`` then returns the *global*
  device list and every jitted program is automatically SPMD across all
  chips — the framework's meshes/shardings work unchanged.
- **Many slices** (DCN between slices, ICI within): the mesh must place
  its outermost axis across slices so only that axis's collectives ride
  DCN. ``make_multislice_mesh`` uses
  ``jax.experimental.mesh_utils.create_hybrid_device_mesh`` for exactly
  that; put ``data`` (gradient psums, amortized per step) or ``pipe``
  (point-to-point microbatch hops) on DCN, never ``model``/``seq``.

All functions degrade to single-process no-ops so the same launch script
runs on a laptop, one TPU VM, or a full pod — and the CPU-simulated
8-device tests exercise the same code paths.
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh

from llm_consensus_tpu.parallel.mesh import AXES, MeshConfig

log = logging.getLogger(__name__)


@dataclass(frozen=True)
class DistributedConfig:
    """Connection info for ``jax.distributed.initialize``.

    Every field defaults to "let JAX auto-detect" — on Cloud TPU the
    runtime discovers coordinator/process_id/num_processes from the
    metadata server, so ``initialize_distributed()`` with no arguments is
    the common path. Env vars (``COORDINATOR_ADDRESS``, ``PROCESS_ID``,
    ``NUM_PROCESSES``) override for manual launches.
    """

    coordinator_address: str | None = None
    num_processes: int | None = None
    process_id: int | None = None

    @staticmethod
    def from_env() -> "DistributedConfig":
        return DistributedConfig(
            coordinator_address=os.environ.get("COORDINATOR_ADDRESS"),
            num_processes=_int_env("NUM_PROCESSES"),
            process_id=_int_env("PROCESS_ID"),
        )


def _int_env(name: str) -> int | None:
    v = os.environ.get(name)
    return int(v) if v is not None else None


def initialize_distributed(config: DistributedConfig | None = None) -> bool:
    """Connect this process to the multi-host job (idempotent).

    Returns True if a multi-process runtime is active afterwards. With no
    config and no env hints on a single machine this is a no-op returning
    False — safe to call unconditionally at program start.
    """
    config = config or DistributedConfig.from_env()
    # NOTE: must not touch jax.devices()/process_count() before
    # jax.distributed.initialize() — any backend-initializing call makes
    # the real initialize raise. The initialized check is safe.
    if jax.distributed.is_initialized():
        return jax.process_count() > 1
    explicit = config.coordinator_address or config.num_processes
    if not explicit and not _on_cloud_tpu():
        return False
    try:
        jax.distributed.initialize(
            coordinator_address=config.coordinator_address,
            num_processes=config.num_processes,
            process_id=config.process_id,
        )
    except Exception as e:  # noqa: BLE001
        if explicit:
            # The caller configured a real multi-process job; silently
            # proceeding single-host would train divergent replicas.
            raise RuntimeError(
                "jax.distributed.initialize failed for explicitly "
                f"configured job {config}: {e}"
            ) from e
        log.warning("jax.distributed.initialize failed (%s); single host", e)
        return False
    log.info(
        "distributed: process %d/%d, %d local / %d global devices",
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
    )
    return jax.process_count() > 1


def _on_cloud_tpu() -> bool:
    return bool(
        os.environ.get("TPU_WORKER_HOSTNAMES")
        or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")
    )


def make_multislice_mesh(
    config: MeshConfig,
    dcn_axis: str = "data",
    n_slices: int | None = None,
) -> Mesh:
    """Build a mesh whose ``dcn_axis`` spans slices over DCN and whose
    remaining axes stay within each slice's ICI.

    ``config`` describes the *global* mesh; ``config.axis_sizes()[dcn_axis]``
    must be divisible by the slice count. Falls back to a plain
    :func:`llm_consensus_tpu.parallel.mesh.make_mesh` when there is only
    one slice (or on CPU test meshes).
    """
    from jax.experimental import mesh_utils

    if dcn_axis not in AXES:
        raise ValueError(f"dcn_axis {dcn_axis!r} not in {AXES}")
    if dcn_axis in ("model", "seq", "expert"):
        raise ValueError(
            f"refusing to put {dcn_axis!r} on DCN: its collectives "
            "(TP gathers/psums, ring-attention permutes, MoE dispatch "
            "all-to-alls) are latency/bandwidth-critical per layer — put "
            "'data' or 'pipe' across slices instead"
        )
    sizes = config.axis_sizes()
    if n_slices is None:
        n_slices = _slice_count()
    if n_slices <= 1:
        from llm_consensus_tpu.parallel.mesh import make_mesh

        return make_mesh(config)
    if sizes[dcn_axis] % n_slices != 0:
        raise ValueError(
            f"{dcn_axis}={sizes[dcn_axis]} not divisible by "
            f"{n_slices} slices"
        )
    ici_sizes = dict(sizes)
    dcn_sizes = {a: 1 for a in AXES}
    dcn_sizes[dcn_axis] = n_slices
    ici_sizes[dcn_axis] = sizes[dcn_axis] // n_slices
    devices = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=[ici_sizes[a] for a in AXES],
        dcn_mesh_shape=[dcn_sizes[a] for a in AXES],
        devices=jax.devices(),
    )
    return Mesh(devices, AXES)


def _slice_count() -> int:
    devices = jax.devices()
    slice_ids = {getattr(d, "slice_index", 0) for d in devices}
    return len(slice_ids)


def local_batch_slice(global_batch: int) -> tuple[int, int]:
    """(per-process batch size, this process's row offset) for feeding a
    ``data``-sharded global batch from per-host input pipelines."""
    n = jax.process_count()
    if global_batch % n:
        raise ValueError(
            f"global batch {global_batch} not divisible by {n} processes"
        )
    per = global_batch // n
    return per, per * jax.process_index()


def host_array_to_global(x: np.ndarray, mesh: Mesh, pspec) -> jax.Array:
    """Assemble a globally-sharded array from per-host shards
    (``jax.make_array_from_process_local_data``) — the multi-host feed
    path for token batches; single-process it is a plain device_put."""
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, pspec)
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    return jax.make_array_from_process_local_data(sharding, x)
