"""Pipeline parallelism (parallel/pipeline.py) on the simulated mesh.

The reference has no model-parallel execution (SURVEY.md §2 "Parallelism
strategies — NOT PRESENT"); PP is part of the framework's scale-out
matrix (SURVEY.md §7 step 6). All tests run on the 8 simulated CPU
devices from conftest.
"""

import jax
import jax.numpy as jnp
import pytest

from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.transformer import forward, init_params
from llm_consensus_tpu.parallel.mesh import MeshConfig, make_mesh
from llm_consensus_tpu.parallel.pipeline import (
    make_pipeline_forward,
    make_pipeline_train_step,
    place_pipeline_params,
    pp_param_pspecs,
)
from llm_consensus_tpu.training.train import (
    TrainConfig,
    init_train_state,
    make_train_step,
)

CFG = get_config("test-tiny").with_(n_layers=4)
TCFG = TrainConfig(warmup_steps=1, total_steps=10, remat=True)


def _batch(b=8, s=16, seed=1):
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed), (b, s), 0, CFG.vocab_size
    )
    mask = jnp.ones((b, s), jnp.float32)
    return tokens, mask


def _params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


@pytest.mark.parametrize(
    "meshcfg,micro",
    [
        (MeshConfig(data=2, pipe=4), 2),
        (MeshConfig(pipe=4, model=2), 4),
        (MeshConfig(data=2, pipe=2, model=2), 2),
    ],
)
def test_pipeline_forward_matches_reference(cpu_devices, meshcfg, micro):
    """Pipelined logits == plain forward logits for dp/pp/tp combos."""
    mesh = make_mesh(meshcfg, cpu_devices[: meshcfg.size])
    params = _params()
    tokens, _ = _batch()
    out = make_pipeline_forward(CFG, mesh, n_microbatches=micro)(
        place_pipeline_params(params, mesh), tokens
    )
    ref = forward(CFG, params, tokens)
    assert out.shape == ref.shape
    assert jnp.max(jnp.abs(out - ref)) < 1e-4


def test_pipeline_train_step_matches_unsharded(cpu_devices):
    """One GPipe train step == one unsharded train step (same init/batch)."""
    mesh = make_mesh(MeshConfig(data=2, pipe=2, model=2), cpu_devices)
    tokens, mask = _batch()

    step_u = make_train_step(CFG, TCFG)
    su, loss_u = step_u(init_train_state(CFG, _params(), TCFG), tokens, mask)

    pstep, place = make_pipeline_train_step(CFG, TCFG, mesh, n_microbatches=2)
    ps, ptok, pmask = place(
        init_train_state(CFG, _params(), TCFG), tokens, mask
    )
    ps2, loss_p = pstep(ps, ptok, pmask)

    assert abs(float(loss_u) - float(loss_p)) < 1e-4
    for a, b in zip(
        jax.tree_util.tree_leaves(su.params),
        jax.tree_util.tree_leaves(jax.device_get(ps2.params)),
    ):
        assert jnp.max(jnp.abs(a - b)) < 1e-4


def test_pipeline_loss_decreases(cpu_devices):
    """A few pipelined steps reduce the loss on a fixed batch."""
    mesh = make_mesh(MeshConfig(data=2, pipe=4), cpu_devices)
    tokens, mask = _batch()
    pstep, place = make_pipeline_train_step(CFG, TCFG, mesh, n_microbatches=4)
    state, ptok, pmask = place(
        init_train_state(CFG, _params(), TCFG), tokens, mask
    )
    losses = []
    for _ in range(4):
        state, loss = pstep(state, ptok, pmask)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_pp_param_pspecs_shard_layer_axis():
    """Block leaves get 'pipe' on the stacked layer axis; the rest of the
    tree keeps the TP rules."""
    specs = pp_param_pspecs(_params())
    assert specs["blocks"]["wq"][0] == "pipe"
    assert specs["blocks"]["wq"][2] == "model"
    assert specs["embed"][0] is None


def test_pipeline_rejects_indivisible_layers(cpu_devices):
    """L not divisible by n_stages fails fast at placement."""
    mesh = make_mesh(MeshConfig(pipe=8), cpu_devices)
    with pytest.raises(ValueError):
        place_pipeline_params(_params(), mesh)  # 4 layers, 8 stages
