"""Which implementation of the hot-path ops runs — observed, not set.

One place decides between the compiled Pallas kernels
(:mod:`llm_consensus_tpu.ops.pallas`) and their ``jax.numpy``
references: the platform JAX runs on and the mesh the engine or batcher
was given. A TPU compiles the kernels; anything else runs the
references. Interpret mode exists for tests only — a caller that forces
the kernels on off-TPU (``use_pallas=True``) or passes
``interpret=True`` itself.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """``interpret=`` for a kernel call that did not pass one: compiled
    on a TPU, interpreted where a test forced the kernel on without
    one."""
    return not on_tpu()


def single_device(mesh) -> bool:
    """True when programs traced for ``mesh`` are not partitioned — the
    only place a ``pallas_call`` without its own ``shard_map`` may run
    (GSPMD cannot see inside one and would gather its operands)."""
    return mesh is None or mesh.size == 1


def resolve_kernels(cfg, mesh=None, *, shard_mapped: bool = False):
    """Fill ``cfg.use_pallas`` where the caller left it unset (None).

    ``shard_mapped``: the caller's kernels carry their own ``shard_map``
    lowering (the serving batcher's ragged attention), so a multi-device
    mesh does not rule them out; the engine's kernels do not, and on
    such a mesh it runs the references under GSPMD. An explicit
    True/False passes through unchanged.
    """
    if cfg.use_pallas is not None:
        return cfg
    return cfg.with_(
        use_pallas=on_tpu() and (shard_mapped or single_device(mesh))
    )

