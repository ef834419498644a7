"""What PR 22 changed about starting on the chip, checked on the CPU.

- ``chip_smoke.py`` refuses a machine without a TPU (naming the platform
  and printing no result) and, with ``--dry-run``, runs every one of its
  phases on a tiny preset without ever calling that a pass;
- the compile cache is where ``JAX_COMPILATION_CACHE_DIR`` says, else a
  fixed path inside the checkout — and with the variable set nothing
  touches ``jax_compilation_cache_dir``;
- kernel choice follows the platform and the mesh the engine or batcher
  was given, never ``jax.device_count()``;
- a device program that raises in the batcher's worker fails its
  requests and flips ``/readyz`` instead of hanging them;
- a device backend refuses to start off-TPU unless ``--cpu`` pins the CPU;
- random weights reach int8 without the bf16 tree, and fleet replicas
  land on distinct devices.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.transformer import (
    init_params,
    init_params_quantized,
)
from llm_consensus_tpu.ops import kernels
from llm_consensus_tpu.serving.continuous import (
    BatcherFailed,
    ContinuousBatcher,
    ContinuousConfig,
)

ROOT = Path(__file__).resolve().parent.parent
CFG = get_config("test-tiny")
_CCFG = dict(
    max_slots=2, page_size=16, n_pages=32, pages_per_seq=8,
    max_new_tokens=4, seq_buckets=(16, 32), prefill_chunk=16,
)


def _smoke(*args, env=None):
    return subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), *args],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, **(env or {})},
    )


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------


def test_chip_smoke_refuses_a_machine_without_a_tpu():
    r = _smoke(env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "no TPU" in r.stderr
    # No result line: nothing on stdout parses as the result object.
    assert not any(
        line.startswith("{") for line in r.stdout.splitlines()
    ), r.stdout


def test_chip_smoke_alone_is_not_the_program(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text()
    )
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_chip_smoke_dry_run_runs_every_phase_and_is_not_a_pass():
    r = _smoke("--dry-run")
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert result == {
        "ok": False, "dry_run": True, "device": "cpu", "phases_passed": True,
    }
    out = r.stdout
    for phase in ("A-serve: drained rc 0", "kernels: all within tolerance",
                  "C-mesh: drained rc 0"):
        assert phase in out, out
    assert "'mesh_data': 2.0, 'mesh_model': 2.0" in out
    assert " FAIL " not in out


def test_chip_smoke_parent_never_imports_jax():
    """The parent holds no chip: it is standard library only."""
    import ast

    tree = ast.parse((ROOT / "chip_smoke.py").read_text())
    top = {
        (n.module if isinstance(n, ast.ImportFrom) else a.name).split(".")[0]
        for n in tree.body
        if isinstance(n, (ast.Import, ast.ImportFrom))
        for a in n.names
    }
    assert not top & {"jax", "jaxlib", "numpy", "llm_consensus_tpu"}, top


# ---------------------------------------------------------------------------
# Compile cache placement
# ---------------------------------------------------------------------------


def test_compile_cache_env_wins_and_nothing_is_set_in_code(monkeypatch):
    from llm_consensus_tpu.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")

    def boom(*a, **k):
        raise AssertionError(f"jax.config.update{a} with the variable set")

    monkeypatch.setattr(jax.config, "update", boom)
    assert compile_cache.enable_compilation_cache() == "/somewhere/else"


def test_compile_cache_defaults_to_a_fixed_checkout_path(monkeypatch):
    from llm_consensus_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("HOME", "/nonexistent-home")
    seen = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: seen.__setitem__(k, v)
    )
    got = compile_cache.enable_compilation_cache()
    assert got == str(ROOT / ".jax_cache")
    assert seen == {"jax_compilation_cache_dir": got}
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


# ---------------------------------------------------------------------------
# Kernel choice: platform + mesh
# ---------------------------------------------------------------------------


def _mesh(n):
    from llm_consensus_tpu.parallel.mesh import MeshConfig, make_mesh

    return make_mesh(MeshConfig(data=n), devices=jax.devices()[:n])


def test_kernel_choice_follows_platform_and_mesh(monkeypatch):
    """This process has 8 devices; none of the answers below may depend
    on that."""
    assert jax.device_count() == 8
    assert CFG.use_pallas is None
    # Off-TPU: the references, whatever the mesh.
    assert kernels.resolve_kernels(CFG).use_pallas is False
    assert kernels.resolve_kernels(CFG, _mesh(2)).use_pallas is False
    # On a TPU: kernels with no mesh or a one-device mesh; on a larger
    # mesh only for a caller whose kernels carry their own shard_map.
    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    assert kernels.resolve_kernels(CFG).use_pallas is True
    assert kernels.resolve_kernels(CFG, _mesh(1)).use_pallas is True
    assert kernels.resolve_kernels(CFG, _mesh(2)).use_pallas is False
    assert (
        kernels.resolve_kernels(CFG, _mesh(2), shard_mapped=True).use_pallas
        is True
    )
    # An explicit choice (tests forcing interpret mode) passes
    # through.
    forced = CFG.with_(use_pallas=False)
    assert kernels.resolve_kernels(forced).use_pallas is False


def test_int8_matmul_kernel_gates_on_the_weights_mesh(monkeypatch):
    from llm_consensus_tpu.ops import quant
    from llm_consensus_tpu.parallel.partitioning import shard_params

    params = init_params_quantized(CFG, jax.random.PRNGKey(0))
    wq = params["blocks"]["wq"]
    assert not wq.gspmd
    monkeypatch.setattr(quant, "on_tpu", lambda: True)
    assert quant._use_kernel(wq)
    assert quant._use_kernel(shard_params(params, _mesh(1))["blocks"]["wq"])
    sharded = shard_params(params, _mesh(2))["blocks"]["wq"]
    assert sharded.gspmd and not quant._use_kernel(sharded)
    monkeypatch.setattr(quant, "on_tpu", lambda: False)
    assert not quant._use_kernel(wq)


def test_norm_kernel_stays_off_a_partitioned_program():
    from llm_consensus_tpu.models.transformer import _local_kernels

    on = CFG.with_(use_pallas=True)
    assert _local_kernels(on, None) and _local_kernels(on, _mesh(1))
    assert not _local_kernels(on, _mesh(2))
    assert not _local_kernels(CFG, None)  # unresolved: references


def test_batcher_reports_device_and_kernels():
    params = init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    b = ContinuousBatcher(CFG, params, config=ContinuousConfig(**_CCFG))
    try:
        hb = b.heartbeat()
        assert b.cfg.use_pallas is False
        assert hb["kernels"] == "reference"
        assert hb["device"] == {
            "platform": "cpu", "kind": jax.devices()[0].device_kind,
            "count": 8, "pool_on": [0],
        }
        assert "failed" not in hb
    finally:
        b.close()


# ---------------------------------------------------------------------------
# A device program that raises
# ---------------------------------------------------------------------------


def test_failed_device_program_fails_requests_and_readiness():
    """The first Mosaic lowering error must look like a 502 with the
    message and a 503 /readyz — not like a hung server."""
    from llm_consensus_tpu.server.client import (
        GatewayClient,
        GatewayHTTPError,
    )
    from llm_consensus_tpu.server.gateway import (
        Gateway,
        GatewayConfig,
        GatewayThread,
    )
    from llm_consensus_tpu.serving.continuous import ContinuousBackend

    params = init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    b = ContinuousBatcher(CFG, params, config=ContinuousConfig(**_CCFG))

    def boom(*a, **k):
        raise ValueError("Mosaic failed to lower the kernel")

    b._prefill_step = boom  # the first device program of any request
    gw = GatewayThread(
        Gateway(ContinuousBackend(b), config=GatewayConfig(port=0))
    ).start()
    try:
        client = GatewayClient("127.0.0.1", gw.port, timeout=30)
        t0 = time.monotonic()
        futs = [b.submit("direct one"), b.submit("direct two")]
        for f in futs:
            with pytest.raises(BatcherFailed, match="Mosaic failed"):
                f.result(timeout=30)
        assert time.monotonic() - t0 < 30
        hb = b.heartbeat()
        assert hb["alive"] is False
        assert hb["failed"].startswith("ValueError: Mosaic failed")
        with pytest.raises(RuntimeError, match="serving loop failed"):
            b.submit("after the failure")
        with pytest.raises(GatewayHTTPError) as e:
            client.readyz()
        assert e.value.status == 503
        doc = json.loads(e.value.body)
        assert doc["ready"] is False and "Mosaic failed" in doc["reason"]
        with pytest.raises(GatewayHTTPError) as e:
            client.generate("through the gateway")
        assert e.value.status == 502 and "Mosaic failed" in e.value.body
    finally:
        gw.drain()
        b.close()


# ---------------------------------------------------------------------------
# Start-up: platform refusal, quantized init, replica placement
# ---------------------------------------------------------------------------


def test_device_backend_refuses_to_start_off_tpu_without_cpu_pin():
    from llm_consensus_tpu.cli import require_tpu

    with pytest.raises(SystemExit, match="no TPU.*'cpu'.*--cpu"):
        require_tpu(cpu_pinned=False)
    require_tpu(cpu_pinned=True)
    r = subprocess.run(
        [sys.executable, "-m", "llm_consensus_tpu", "serve", "--backend",
         "continuous", "--model", "test-tiny", "--port", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode != 0 and "no TPU" in r.stderr


@pytest.mark.parametrize("preset,bits", [("test-tiny", 8), ("test-tiny", 4),
                                         ("test-tiny-moe", 8)])
def test_init_params_quantized_matches_the_quantized_tree(preset, bits):
    """Same tree structure, shapes and dtypes as quantizing the float
    init — it only never builds that float tree."""
    from llm_consensus_tpu.models.transformer import forward
    from llm_consensus_tpu.ops.quant import quantize_params

    cfg = get_config(preset)
    got = init_params_quantized(cfg, jax.random.PRNGKey(0), bits=bits)
    want = quantize_params(init_params(cfg, jax.random.PRNGKey(0)), bits=bits)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
    logits = forward(cfg, got, jnp.ones((1, 8), jnp.int32))
    assert bool(jnp.isfinite(logits).all())
    # Quantizing again (the CLI does, for checkpoints' sake) is a no-op.
    again = quantize_params(got, bits=bits)
    assert again["blocks"]["wq"] is got["blocks"]["wq"]


def test_fleet_replicas_land_on_distinct_devices():
    from llm_consensus_tpu.serving.fleet import FleetConfig, ReplicaSet

    params = init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    fleet = ReplicaSet(
        CFG, params, config=ContinuousConfig(**_CCFG),
        fleet=FleetConfig(replicas=3),
    )
    try:
        pools = [
            r["device"]["pool_on"] for r in fleet.heartbeat()["replicas"]
        ]
        assert pools == [[0], [1], [2]]
        assert all(b.kernels == "reference" for b in fleet.batchers)
        out = fleet.submit("placed", max_new_tokens=2).result(timeout=120)
        assert out.num_tokens > 0
    finally:
        fleet.close()
