"""Training loop driver (training/loop.py): run, checkpoint, resume.

Resume determinism is the anchor: train 6 steps straight vs train 3 +
"crash" + resume for 3 — identical final params and data order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.transformer import init_params
from llm_consensus_tpu.parallel.mesh import MeshConfig, make_mesh
from llm_consensus_tpu.training.data import TokenBatchLoader, write_token_shard
from llm_consensus_tpu.training.loop import (
    LoopConfig,
    run_training,
    TrainReport,
)
from llm_consensus_tpu.training.train import TrainConfig

CFG = get_config("test-tiny")
TCFG = TrainConfig(warmup_steps=1, total_steps=10, remat=False)


@pytest.fixture
def shard(tmp_path):
    path = tmp_path / "tokens.bin"
    rng = np.random.default_rng(0)
    write_token_shard(path, rng.integers(0, CFG.vocab_size, 4096))
    return path


def _loader(shard, seed=0):
    return TokenBatchLoader(shard, batch=4, seq=16, seed=seed, prefer_native=False)


def _params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def test_loop_runs_and_loss_decreases(shard):
    state, report = run_training(
        CFG,
        TCFG,
        _loader(shard),
        LoopConfig(total_steps=8, log_every=4),
        params=_params(),
    )
    assert report.final_step == 8
    assert len(report.losses) == 2
    assert report.losses[-1].loss < report.losses[0].loss + 0.5
    assert report.losses[-1].tokens_per_sec > 0


def test_loader_seek_reproduces_stream(shard):
    a = _loader(shard)
    batches = [a.next()[0] for _ in range(5)]
    b = _loader(shard)
    b.seek(3)
    assert b.position == 3
    np.testing.assert_array_equal(b.next()[0], batches[3])
    # Seek backwards restarts the stream.
    b.seek(0)
    np.testing.assert_array_equal(b.next()[0], batches[0])


def test_resume_matches_straight_run(shard, tmp_path):
    straight, _ = run_training(
        CFG,
        TCFG,
        _loader(shard),
        LoopConfig(total_steps=6),
        params=_params(),
    )

    ckpt = str(tmp_path / "ckpt")
    run_training(
        CFG,
        TCFG,
        _loader(shard),
        LoopConfig(total_steps=3, ckpt_every=3, ckpt_dir=ckpt),
        params=_params(),
    )
    resumed_state, report = run_training(
        CFG,
        TCFG,
        _loader(shard),  # fresh loader: seek() must restore position
        LoopConfig(total_steps=6, ckpt_every=0, ckpt_dir=ckpt),
        params=_params(),
    )
    assert report.resumed_from == 3
    for a, b in zip(
        jax.tree_util.tree_leaves(straight.params),
        jax.tree_util.tree_leaves(resumed_state.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        )


def test_loop_on_sharded_mesh(shard, cpu_devices):
    mesh = make_mesh(MeshConfig(data=2, model=2, seq=2), cpu_devices)
    state, report = run_training(
        CFG,
        TCFG,
        _loader(shard),
        LoopConfig(total_steps=4, log_every=2),
        mesh=mesh,
        params=_params(),
    )
    assert report.final_step == 4
    assert all(np.isfinite(e.loss) for e in report.losses)


def test_loop_on_pipeline_mesh(shard, cpu_devices):
    cfg = CFG.with_(n_layers=4)
    mesh = make_mesh(MeshConfig(data=2, pipe=2, model=2), cpu_devices)
    state, report = run_training(
        cfg,
        TCFG,
        _loader(shard),
        LoopConfig(total_steps=3, log_every=3, n_microbatches=2),
        mesh=mesh,
        params=init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32),
    )
    assert report.final_step == 3
    assert all(np.isfinite(e.loss) for e in report.losses)
