"""PartitionSpec rules: how every param/activation maps onto the mesh.

The sharding recipe (scaling-book style): pick a mesh
(:mod:`llm_consensus_tpu.parallel.mesh`), annotate every array with a
``PartitionSpec`` against the named axes, and let GSPMD insert the
collectives — all-gathers/psums ride ICI. No hand-written NCCL-equivalent
calls anywhere (the reference has none to port either; its comms layer is
in-process actix mailboxes, SURVEY.md §2).

Tensor-parallel layout (Megatron-style, expressed declaratively):
- qkv projections column-sharded over ``model`` (heads split);
- attention output row-sharded over ``model`` (GSPMD inserts the psum);
- MLP gate/up column-sharded, down row-sharded;
- MoE experts sharded over ``expert`` with each expert's FFN additionally
  TP-sharded over ``model``;
- lm_head vocab-sharded; logits gather at the end.
The KV cache shards batch over ``data`` and kv heads over ``model``
(BASELINE.json north star: per-candidate cache sharding in HBM).
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# Rules keyed by param-leaf name. Each value is the PartitionSpec for that
# leaf in the ``init_params`` tree (llm_consensus_tpu.models.transformer).
# Dense (non-MoE) block weights:
_DENSE_RULES: dict[str, P] = {
    "embed": P(None, None),  # gather table; replicate (V small vs FLOPs)
    "norm_f": P(None),
    "lm_head": P(None, "model"),  # vocab-sharded logits
    "attn_norm": P(None, None),
    "mlp_norm": P(None, None),
    "wq": P(None, None, "model"),
    "wk": P(None, None, "model"),
    "wv": P(None, None, "model"),
    "wo": P(None, "model", None),
    "bq": P(None, "model"),
    "bk": P(None, "model"),
    "bv": P(None, "model"),
    "w_gate": P(None, None, "model"),
    "w_up": P(None, None, "model"),
    "w_down": P(None, "model", None),
}
# MoE block weights override (leading expert axis after the layer axis).
_MOE_RULES: dict[str, P] = {
    "router": P(None, None, None),
    "w_gate": P(None, "expert", None, "model"),
    "w_up": P(None, "expert", None, "model"),
    "w_down": P(None, "expert", "model", None),
}


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return entry.key
    raise ValueError(f"no named key in path {path}")


def param_pspecs(params) -> dict:
    """PartitionSpec tree mirroring an ``init_params`` tree."""

    def rule(path, leaf):
        name = _leaf_name(path)
        if name in _MOE_RULES and leaf.ndim == len(_MOE_RULES[name]):
            spec = _MOE_RULES[name]
        elif name in _DENSE_RULES:
            spec = _DENSE_RULES[name]
            if leaf.ndim != len(spec):
                raise ValueError(
                    f"param {name!r} rank {leaf.ndim} != rule rank {len(spec)}"
                )
        else:
            raise ValueError(f"no sharding rule for param {name!r}")
        # Size-1 axes replicate: int8 scale tensors (ops/quant.py) keep
        # the contraction dim as size 1 and would otherwise inherit a
        # sharded spec on an unsplittable axis.
        return P(
            *(
                None if leaf.shape[i] == 1 else spec[i]
                for i in range(len(spec))
            )
        )

    return jax.tree_util.tree_map_with_path(rule, params)


def cache_pspecs() -> "object":
    """Specs for a KVCache pytree: batch over ``data``, kv heads over
    ``model`` — per-candidate cache sharding (BASELINE.json north star)."""
    from llm_consensus_tpu.models.cache import KVCache

    return KVCache(
        k=P(None, "data", None, "model", None),
        v=P(None, "data", None, "model", None),
        length=P("data"),
    )


def batch_pspec() -> P:
    """Token/length batches shard their leading axis over ``data``."""
    return P("data")


def shard_params(params, mesh: Mesh):
    """Place a param tree on the mesh per :func:`param_pspecs`.

    On a multi-device mesh quantized leaves are marked ``gspmd``: their
    matmuls are the partitioner's, so the fused Pallas kernel (which it
    cannot see into) stays off for them (``ops.quant``)."""
    from dataclasses import replace

    from llm_consensus_tpu.ops.quant import Quantized4Tensor, QuantizedTensor

    specs = param_pspecs(params)
    placed = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )
    if mesh.size == 1:
        return placed
    qtypes = (QuantizedTensor, Quantized4Tensor)
    return jax.tree_util.tree_map(
        lambda x: replace(x, gspmd=True) if isinstance(x, qtypes) else x,
        placed,
        is_leaf=lambda x: isinstance(x, qtypes),
    )


def sharded_param_bytes(tree, mesh_shape: dict) -> int:
    """Per-chip resident bytes of a param tree under this module's rules.

    Walks :func:`param_pspecs` leaf-for-leaf and divides each leaf's
    bytes by the product of the mesh-axis sizes its spec actually names
    — NOT a global model*expert divide, which would pretend replicated
    leaves (embeddings, norms, and on MoE models ALL attention weights,
    which replicate over ``expert``) shard too and understate per-chip
    residency. Accepts concrete arrays or ``jax.eval_shape`` structs
    (capacity planning without allocation).
    """
    specs = param_pspecs(tree)

    def leaf_bytes(leaf, spec) -> int:
        div = 1
        for entry in spec:
            axes = entry if isinstance(entry, tuple) else (entry,)
            for ax in axes:
                if ax is not None:
                    div *= int(mesh_shape.get(ax, 1))
        return leaf.size * leaf.dtype.itemsize // max(div, 1)

    return sum(
        leaf_bytes(leaf, spec)
        for leaf, spec in zip(
            jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(specs)
        )
    )
