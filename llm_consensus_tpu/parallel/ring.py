"""Ring attention: causal attention over a sequence sharded across devices.

Long-context support (first-class per the build spec; the reference has no
model code at all — SURVEY.md §5 "Long-context: NOT PRESENT"). Each device
holds a contiguous sequence chunk of q/k/v. K/V chunks rotate around the
``seq`` mesh axis via ``lax.ppermute`` (ICI neighbour exchange) while each
device accumulates its queries' attention with the numerically stable
streaming-softmax update (running max + denominator), so the full [S, S]
score matrix never materializes and comm overlaps compute ring-step by
ring-step.

Layout contract: chunk d of the sequence lives on mesh position d of the
``seq`` axis; global position = chunk_index * chunk_len + local offset.
Causality is enforced against *global* positions, so results equal
single-device causal attention exactly (up to fp reordering).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


_NEG_INF = -1e30


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    axis_size: int,
    varying_axes: tuple[str, ...] | None = None,
) -> jnp.ndarray:
    """Causal ring attention over one sequence-sharded axis.

    Call from inside ``shard_map``/``pjit`` with ``axis_name`` mapped.
    q: [B, S_loc, H, D]; k/v: [B, S_loc, Hkv, D] (GQA: H = Hkv * G).
    ``axis_size`` is the static number of ring participants.
    ``varying_axes``: every manual mesh axis the inputs are sharded over
    (the scan-carry accumulators must be marked varying over all of
    them); defaults to just the ring axis.
    Returns [B, S_loc, H, D] in q's dtype.
    """
    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    idx = jax.lax.axis_index(axis_name)
    scale = d**-0.5

    qg = q.reshape(b, s, hkv, g, d).astype(jnp.float32)
    q_pos = idx * s + jnp.arange(s)  # [S_loc] global query positions

    # The accumulators are per-shard state, varying over the ring axis —
    # mark them so the scan carry type matches its updated value.
    def _varying(x):
        return jax.lax.pcast(
            x, tuple(varying_axes or (axis_name,)), to="varying"
        )

    m0 = _varying(jnp.full((b, hkv, g, s), _NEG_INF, jnp.float32))
    l0 = _varying(jnp.zeros((b, hkv, g, s), jnp.float32))
    o0 = _varying(jnp.zeros((b, hkv, g, s, d), jnp.float32))

    def body(carry, step):
        k_blk, v_blk, m, l, o = carry
        origin = (idx - step) % axis_size  # which chunk we hold this step
        k_pos = origin * s + jnp.arange(s)  # [S_loc] global key positions

        scores = (
            jnp.einsum(
                "bqkgd,bskd->bkgqs",
                qg,
                k_blk.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [B, Hkv, G, Sq, Sk]
        mask = (k_pos[None, :] <= q_pos[:, None])[None, None, None]
        scores = jnp.where(mask, scores, _NEG_INF)

        blk_max = scores.max(axis=-1)  # [B, Hkv, G, Sq]
        new_m = jnp.maximum(m, blk_max)
        correction = jnp.exp(m - new_m)
        p = jnp.exp(scores - new_m[..., None])  # masked -> ~0
        p = jnp.where(mask, p, 0.0)
        new_l = l * correction + p.sum(axis=-1)
        new_o = o * correction[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd",
            p,
            v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

        # Rotate k/v one hop around the ring (ICI neighbour exchange).
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_next, v_next, new_m, new_l, new_o), None

    (_, _, _, l, o), _ = jax.lax.scan(
        body, (k, v, m0, l0, o0), jnp.arange(axis_size)
    )
    out = o / jnp.maximum(l[..., None], 1e-30)  # [B, Hkv, G, Sq, D]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, d).astype(q.dtype)


def ring_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "seq",
) -> jnp.ndarray:
    """Convenience wrapper: shard q/k/v over ``axis_name`` and run the ring.

    q/k/v: full [B, S, H|Hkv, D] arrays; S must divide evenly by the axis
    size. The batch axis shards over ``data`` and heads over ``model``
    when those mesh axes exist and divide evenly — so the ring composes
    with dp/tp instead of forcing a reshard at its boundary.
    """
    axis_size = mesh.shape[axis_name]
    if q.shape[1] % axis_size:
        raise ValueError(
            f"sequence {q.shape[1]} not divisible by {axis_name}={axis_size}"
        )
    b, _, h, _ = q.shape
    hkv = k.shape[2]
    batch_ax = None
    if "data" in mesh.axis_names and b % mesh.shape["data"] == 0:
        batch_ax = "data"
    head_ax = None
    if (
        "model" in mesh.axis_names
        and h % mesh.shape["model"] == 0
        and hkv % mesh.shape["model"] == 0
        # per-shard GQA grouping must stay integral
        and (h // mesh.shape["model"]) % max(hkv // mesh.shape["model"], 1)
        == 0
    ):
        head_ax = "model"
    spec = P(batch_ax, axis_name, head_ax, None)
    varying = tuple(a for a in (batch_ax, axis_name, head_ax) if a)
    fn = jax.shard_map(
        partial(
            ring_attention,
            axis_name=axis_name,
            axis_size=axis_size,
            varying_axes=varying,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
