"""GPipe-style pipeline parallelism over the ``pipe`` mesh axis.

The reference has no model-parallel execution of any kind (SURVEY.md §2,
"Parallelism strategies — NOT PRESENT"); this module supplies the PP part
of the framework's dp/tp/pp/sp/ep matrix, TPU-first:

- The layer axis of the stacked-params tree (``init_params`` puts layers
  on a leading ``L`` axis) is sharded over ``pipe``: each stage holds
  ``L / n_stages`` contiguous layers and scans them locally.
- Microbatched schedule: the batch splits into ``M`` microbatches; one
  device program runs ``M + n_stages - 1`` ticks of a ``lax.scan``. Each
  tick every stage runs its layer chunk, then activations hop to the next
  stage with a single ``lax.ppermute`` — point-to-point neighbour traffic
  on the ``pipe`` ring, no all-to-all.
- Implemented with ``jax.shard_map`` manual over ``("data", "pipe")``
  only; the ``model``/``expert``/``seq`` axes stay *auto*, so tensor/
  expert-parallel GSPMD sharding composes inside each pipeline stage
  without hand-written collectives.
- Differentiable end-to-end: ``ppermute`` transposes to the reverse
  permutation and replicated in-specs transpose to psums, so
  ``jax.value_and_grad`` of the shard_mapped loss is the 1F1B-equivalent
  backward schedule, derived by AD instead of hand-scheduling.

Embedding/unembedding are computed redundantly per stage (cheap relative
to the block stack); the loss is reduced on the last stage and ``psum``
broadcast so every stage returns the same scalar.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from llm_consensus_tpu.models.configs import ModelConfig
from llm_consensus_tpu.models.transformer import _block, _unembed
from llm_consensus_tpu.ops.rope import rope_cos_sin
from llm_consensus_tpu.parallel.partitioning import param_pspecs


def pp_param_pspecs(params) -> dict:
    """Param specs for pipeline runs: like :func:`param_pspecs` but the
    stacked layer axis of every block leaf shards over ``pipe``."""
    specs = param_pspecs(params)

    def pipe_leading(spec: P) -> P:
        return P("pipe", *spec[1:])

    specs["blocks"] = jax.tree_util.tree_map(
        pipe_leading, specs["blocks"], is_leaf=lambda x: isinstance(x, P)
    )
    return specs


def _check_microbatching(b: int, m: int, mesh: Mesh) -> None:
    """Fail fast (named constraint, like make_mesh) instead of an opaque
    reshape/sharding error inside jit."""
    if b % m != 0:
        raise ValueError(
            f"batch {b} not divisible by n_microbatches={m}"
        )
    dp = mesh.shape["data"]
    if (b // m) % dp != 0:
        raise ValueError(
            f"microbatch rows {b}//{m}={b // m} not divisible by "
            f"data axis size {dp}"
        )


def _stage_chunk(cfg: ModelConfig, blocks, x, cos, sin, remat: bool):
    """Scan this stage's local layer chunk over activations ``x``."""

    def body(carry, p):
        y, _ = _block(cfg, p, carry, cos, sin, None, "full", None, None)
        return y, None

    if remat:
        body = jax.checkpoint(body)
    y, _ = jax.lax.scan(body, x, blocks)
    return y


def _pipeline_logits_local(
    cfg: ModelConfig,
    n_stages: int,
    n_micro: int,
    remat: bool,
    stage: jnp.ndarray,  # scalar int32: jax.lax.axis_index("pipe")
    params: dict,
    tokens_mb: jnp.ndarray,  # [M, mb, S] local shard (mb = B/M/dp)
) -> jnp.ndarray:
    """Inside-shard_map pipeline: returns logits [M, mb, S, V] (valid on
    the last stage; garbage elsewhere — callers must mask by stage)."""
    m, mb, s = tokens_mb.shape

    x_mb = params["embed"][tokens_mb]  # [M, mb, S, D] — embed per stage
    positions = jnp.broadcast_to(jnp.arange(s), (mb, s))
    cos, sin = rope_cos_sin(
        positions, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
    )

    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        state, out = carry
        inp = jax.lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, m - 1), axis=0, keepdims=False
        )
        state = jnp.where(stage == 0, inp, state)
        state = _stage_chunk(cfg, params["blocks"], state, cos, sin, remat)
        # Drain: the last stage finishes microbatch (t - n_stages + 1).
        oidx = jnp.clip(t - (n_stages - 1), 0, m - 1)
        cur = jax.lax.dynamic_index_in_dim(out, oidx, axis=0, keepdims=False)
        new = jnp.where((t >= n_stages - 1) & (stage == n_stages - 1), state, cur)
        out = jax.lax.dynamic_update_index_in_dim(out, new, oidx, axis=0)
        state = jax.lax.ppermute(state, "pipe", perm)
        return (state, out), None

    # The carry becomes pipe-varying after the first ppermute; mark the
    # (replicated) zero initials as varying so the scan carry type is
    # stable under shard_map's VMA check.
    state0 = jax.lax.pcast(jnp.zeros_like(x_mb[0]), ("pipe",), to="varying")
    out0 = jax.lax.pcast(jnp.zeros_like(x_mb), ("pipe",), to="varying")
    (_, out), _ = jax.lax.scan(
        tick, (state0, out0), jnp.arange(m + n_stages - 1)
    )
    return _unembed(cfg, params, out)  # [M, mb, S, V] fp32


def make_pipeline_forward(
    cfg: ModelConfig,
    mesh: Mesh,
    n_microbatches: int,
    remat: bool = False,
):
    """Jitted pipelined forward: tokens [B, S] -> logits [B, S, V].

    Params must be placed per :func:`pp_param_pspecs` (use
    :func:`place_pipeline_params`). ``B`` must divide into
    ``n_microbatches * mesh.shape['data']`` microbatch rows.

    Note: returning replicated logits requires broadcasting the last
    stage's [B, S, V] tensor over ``pipe`` (a vocab-sized psum) — fine
    over ICI, but do not map ``pipe`` to DCN for this entry point. The
    training path (:func:`pipeline_causal_lm_loss`) reduces to a scalar
    instead and has no such traffic.
    """
    n_stages = mesh.shape["pipe"]
    m = n_microbatches

    def run(params, tokens):
        b, s = tokens.shape
        _check_microbatching(b, m, mesh)
        tokens_mb = tokens.reshape(m, b // m, s)

        def f(params, tokens_mb):
            stage = jax.lax.axis_index("pipe")
            logits = _pipeline_logits_local(
                cfg, n_stages, m, remat, stage, params, tokens_mb
            )
            # Broadcast the last stage's logits to every stage so the
            # output is pipe-invariant.
            logits = jnp.where(stage == n_stages - 1, logits, 0.0)
            return jax.lax.psum(logits, "pipe")

        logits_mb = jax.shard_map(
            f,
            mesh=mesh,
            in_specs=(_param_in_specs(params), P(None, "data", None)),
            out_specs=P(None, "data"),
            axis_names={"data", "pipe"},
        )(params, tokens_mb)
        return logits_mb.reshape(b, s, -1)

    return jax.jit(run)


def _param_in_specs(params):
    """shard_map in-specs for params: blocks split over ``pipe`` on the
    layer axis, everything else replicated w.r.t. the manual axes."""
    specs = jax.tree_util.tree_map(lambda _: P(), params)
    specs["blocks"] = jax.tree_util.tree_map(
        lambda _: P("pipe"), params["blocks"]
    )
    return specs


def pipeline_causal_lm_loss(
    cfg: ModelConfig,
    mesh: Mesh,
    n_microbatches: int,
    params: dict,
    tokens: jnp.ndarray,
    loss_mask: jnp.ndarray,
    remat: bool = True,
    compute_dtype: str | None = None,
) -> jnp.ndarray:
    """Masked next-token CE over a pipelined forward (matches
    ``training.train.causal_lm_loss`` numerics: sum(nll)/sum(mask),
    including its mixed-precision ``compute_dtype`` cast)."""
    from llm_consensus_tpu.training.train import _cast_params

    params = _cast_params(params, compute_dtype)
    n_stages = mesh.shape["pipe"]
    m = n_microbatches
    b, s = tokens.shape
    _check_microbatching(b, m, mesh)
    tokens_mb = tokens.reshape(m, b // m, s)
    mask_mb = loss_mask.reshape(m, b // m, s)

    def f(params, tokens_mb, mask_mb):
        stage = jax.lax.axis_index("pipe")
        logits = _pipeline_logits_local(
            cfg, n_stages, m, remat, stage, params, tokens_mb
        )  # [M, mb, S, V]
        targets = tokens_mb[..., 1:]
        lp = jax.nn.log_softmax(logits[..., :-1, :], axis=-1)
        nll = -jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0]
        mask = mask_mb[..., :-1].astype(jnp.float32)
        last = stage == n_stages - 1
        nll_sum = jnp.where(last, jnp.sum(nll * mask), 0.0)
        mask_sum = jnp.where(last, jnp.sum(mask), 0.0)
        nll_sum = jax.lax.psum(nll_sum, ("data", "pipe"))
        mask_sum = jax.lax.psum(mask_sum, ("data", "pipe"))
        return nll_sum / jnp.maximum(mask_sum, 1.0)

    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=(
            _param_in_specs(params),
            P(None, "data", None),
            P(None, "data", None),
        ),
        out_specs=P(),
        axis_names={"data", "pipe"},
    )(params, tokens_mb, mask_mb)


def make_pipeline_train_step(cfg, tcfg, mesh: Mesh, n_microbatches: int):
    """Pipelined train step + placement helper.

    Same contract as ``training.train.make_sharded_train_step`` but the
    layer stack is stage-sharded over ``pipe`` and the forward/backward
    run the GPipe microbatch schedule. TP/EP still apply within each
    stage via the auto axes.
    """
    from llm_consensus_tpu.training.train import TrainState, make_optimizer

    opt = make_optimizer(tcfg)

    def step(state, tokens, loss_mask):
        def loss_fn(p):
            return pipeline_causal_lm_loss(
                cfg,
                mesh,
                n_microbatches,
                p,
                tokens,
                loss_mask,
                tcfg.remat,
                tcfg.compute_dtype,
            )

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return (
            TrainState(params=params, opt_state=opt_state, step=state.step + 1),
            loss,
        )

    def place(state, tokens, loss_mask):
        from llm_consensus_tpu.training.train import place_train_state

        return place_train_state(
            state,
            mesh,
            pp_param_pspecs(state.params),
            batch_spec=P("data", None),
            batches=(tokens, loss_mask),
        )

    return jax.jit(step, donate_argnums=(0,)), place


def place_pipeline_params(params, mesh: Mesh):
    """Place a param tree on the mesh per :func:`pp_param_pspecs`."""
    specs = pp_param_pspecs(params)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )
