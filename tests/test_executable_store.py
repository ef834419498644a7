"""The store of serialized executables (``utils.compile_cache``): a
program built once is loaded by every later holder with the same key —
no trace, no lowering — and a key that misses, a damaged entry or two
writers at once never cost more than a build."""

from __future__ import annotations

import logging
import os
import shutil
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.transformer import init_params
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)
from llm_consensus_tpu.utils import compile_cache, tracing
from llm_consensus_tpu.utils.compile_cache import StoredJit

CFG = get_config("test-tiny")
_CCFG = dict(
    max_slots=4, page_size=16, n_pages=64, pages_per_seq=8,
    max_new_tokens=8, seq_buckets=(16, 32, 64), prefill_chunk=16,
)
_HEADER = "a header the evaluate prompts share, a page and more of it: "
# One at a time, so every holder meets the same programs in the same
# order: chunks alone, decode, then the build-ahead (a chunk runs alone
# after a plain dispatch) and the fused steps.
PROMPTS = ["x: alone", _HEADER + "A tail", _HEADER + "B tail", "z" * 40]


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


@pytest.fixture(scope="module")
def metrics():
    from llm_consensus_tpu.server import metrics as M

    tracing.install_compile_listener(
        M.COMPILE_SECONDS, M.COMPILES, M.EXECUTABLES
    )
    return M


@pytest.fixture
def store(tmp_path):
    """A compile cache of the test's own, so the store is on and empty."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = jax.config.jax_compilation_cache_dir
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        yield tmp_path / compile_cache.STORE
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)
        cc.reset_cache()


def _executables(M) -> dict:
    return {
        (dict(k)["program"], dict(k)["source"]): c.value
        for k, c in list(M.EXECUTABLES._children.items())
    }


def _grew(before: dict, after: dict, source: str) -> dict:
    return {
        program: v - before.get((program, src), 0.0)
        for (program, src), v in after.items()
        if src == source and v != before.get((program, src), 0.0)
    }


def _serve(params, mesh=None) -> list:
    b = ContinuousBatcher(
        CFG, params, config=ContinuousConfig(**_CCFG), mesh=mesh
    )
    try:
        return [
            (r.text, r.num_tokens)
            for r in (b.submit(p).result(timeout=300) for p in PROMPTS)
        ]
    finally:
        b.close()


def _edited_package(tmp_path, monkeypatch) -> None:
    """The key's source digest read from a copy of the package with one
    byte of one file changed."""
    copy = tmp_path / "package"
    shutil.copytree(
        compile_cache._PACKAGE, copy,
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    edited = copy / "version.py"
    edited.write_bytes(edited.read_bytes() + b"#")
    monkeypatch.setattr(compile_cache, "_PACKAGE", copy)
    compile_cache._environment.cache_clear()


def test_a_fresh_batcher_loads_every_program_the_first_built(
    params, metrics, store, tmp_path, monkeypatch
):
    """A second holder with the same key builds nothing: every program
    the first one built (and wrote) is loaded, and it serves the same
    greedy tokens. An edit to any file of the package makes the next
    holder build every one of them again."""
    compile_cache._environment.cache_clear()
    s0 = _executables(metrics)
    first = _serve(params)
    s1 = _executables(metrics)
    built = _grew(s0, s1, "built")
    assert {"decode_step", "prefill_chunk", "fused_step", "apply_rows"} <= (
        set(built)
    ), built
    assert _grew(s0, s1, "loaded") == {}
    assert len(os.listdir(store)) == sum(built.values())

    again = _serve(params)
    s2 = _executables(metrics)
    assert again == first
    assert _grew(s1, s2, "loaded") == built
    assert _grew(s1, s2, "built") == {}

    try:
        _edited_package(tmp_path, monkeypatch)
        edited = _serve(params)
        s3 = _executables(metrics)
    finally:
        monkeypatch.undo()
        compile_cache._environment.cache_clear()
    assert edited == first
    assert _grew(s2, s3, "built") == built
    assert _grew(s2, s3, "loaded") == {}


def test_a_mesh_batcher_loads_every_program_onto_its_own_devices(
    params, metrics, store
):
    """On a 2x2 mesh over four devices in an order of their own, a second
    holder loads every program the first built — each onto the devices,
    in the order, it was built for — and serves the same greedy
    tokens."""
    from llm_consensus_tpu.parallel.mesh import MeshConfig, make_mesh

    d = jax.devices()
    mesh = make_mesh(
        MeshConfig(data=2, model=2), devices=[d[2], d[0], d[3], d[1]]
    )
    compile_cache._environment.cache_clear()
    s0 = _executables(metrics)
    first = _serve(params, mesh)
    s1 = _executables(metrics)
    built = _grew(s0, s1, "built")
    assert {"decode_step", "prefill_chunk", "fused_step"} <= set(built), built
    assert len(os.listdir(store)) == sum(built.values())

    again = _serve(params, mesh)
    s2 = _executables(metrics)
    assert again == first
    assert _grew(s1, s2, "loaded") == built
    assert _grew(s1, s2, "built") == {}


def _scaled(x, n, scale):
    return x * scale + n


def test_a_call_computes_no_signature_until_its_executable_refuses_it(
    metrics, store, monkeypatch
):
    """A call goes to the executable last used with its static
    arguments; only one that executable refuses (another shape, another
    tree) computes its signature, and a real error of the call's own
    executable is raised, not retried."""
    f = StoredJit(_scaled, static_argnums=(2,))
    x, n = jnp.ones((4, 8)), jnp.int32(1)
    f(x, n, 2.0)
    counted = []
    signature = compile_cache._signature

    def counting(*a):
        counted.append(a)
        return signature(*a)

    monkeypatch.setattr(compile_cache, "_signature", counting)
    for _ in range(5):
        np.testing.assert_array_equal(f(x, n, 2.0), 3.0)
    assert counted == []
    np.testing.assert_array_equal(f(jnp.ones((2, 8)), n, 2.0), 3.0)
    np.testing.assert_array_equal(f(x, n, 3.0), 4.0)
    assert len(counted) == 2
    np.testing.assert_array_equal(f(jnp.ones((2, 8)), n, 2.0), 3.0)
    assert len(counted) == 2  # the last one used is the (2, 8) one
    np.testing.assert_array_equal(f(x, n, 2.0), 3.0)
    assert len(counted) == 3  # held: looked up, not built again
    assert f._cache_size() == 3

    gone = jnp.ones((4, 8))
    gone.delete()
    with pytest.raises(RuntimeError, match="deleted"):
        f(gone, n, 2.0)


def _pool_step(pool, rows):
    return pool.at[rows].add(1.0), rows.sum()


def test_a_loaded_program_donates_its_pool(metrics, store):
    """``donate_argnums`` survives the store: the loaded program deletes
    the pool it is given, as the jitted one does."""
    rows = jnp.arange(4)
    StoredJit(_pool_step, donate_argnums=(0,))(jnp.zeros((8, 4)), rows)
    s0 = _executables(metrics)
    pool = jnp.zeros((8, 4))
    out, total = StoredJit(_pool_step, donate_argnums=(0,))(pool, rows)
    assert _grew(s0, _executables(metrics), "loaded") == {"_pool_step": 1}
    assert pool.is_deleted()
    np.testing.assert_array_equal(out[:4], 1.0)
    assert int(total) == 6


def _tanh_scaled(a, b):
    return jnp.tanh(a) * b


def test_a_load_is_timed_as_a_load_and_nothing_else(metrics, store):
    """A program the store holds costs its holder a ``load`` span in
    ``gateway_compile_seconds_total`` and no trace, lowering or backend
    compile; a build costs those three and no load."""

    def stages() -> dict:
        return {
            dict(k)["stage"]: c.value
            for k, c in list(metrics.COMPILE_SECONDS._children.items())
            if dict(k)["program"] == "_tanh_scaled"
        }

    shape = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    s0 = stages()
    StoredJit(_tanh_scaled).build(shape, shape)
    s1 = stages()
    grew = {k for k, v in s1.items() if v != s0.get(k, 0.0)}
    assert grew == {"trace", "lower", "backend"}, grew
    StoredJit(_tanh_scaled).build(shape, shape)
    s2 = stages()
    assert {k for k, v in s2.items() if v != s1.get(k, 0.0)} == {"load"}
    assert s2["load"] > 0


def _step(x, n):
    return x * n


def _name(program="step", key=("k",), args=None) -> str:
    args = args or (jnp.zeros((4, 8)), np.int32(3))
    return StoredJit(_step, program=program, key=key).entry(*args)


@pytest.mark.parametrize(
    "change",
    ["source", "batcher_config", "shape", "program_key", "jax_version"],
)
def test_the_key_misses_when_one_thing_it_holds_changes(
    change, params, tmp_path, monkeypatch
):
    compile_cache._environment.cache_clear()
    try:
        if change == "batcher_config":
            keys = []
            for n in (8, 9):
                b = ContinuousBatcher(
                    CFG, params,
                    config=ContinuousConfig(**{**_CCFG, "max_new_tokens": n}),
                )
                b.close()
                keys.append(b._key)
            before, after = _name(key=keys[0]), _name(key=keys[1])
        else:
            before = _name()
            if change == "source":
                _edited_package(tmp_path, monkeypatch)
                after = _name()
            elif change == "shape":
                after = _name(args=(jnp.zeros((4, 9)), np.int32(3)))
            elif change == "program_key":
                after = _name(key=("k", 2))
            else:
                monkeypatch.setattr(jax, "__version__", jax.__version__ + "x")
                compile_cache._environment.cache_clear()
                after = _name()
        assert before != after
    finally:
        monkeypatch.undo()
        compile_cache._environment.cache_clear()
    assert _name() == _name()


def _double(x):
    return x * 2.0


@pytest.mark.parametrize("damage", ["truncated", "garbage"])
def test_a_damaged_entry_is_rebuilt_and_rewritten(
    damage, metrics, store, caplog
):
    x = jnp.arange(16.0)
    StoredJit(_double)(x)
    (entry,) = store.iterdir()
    blob = entry.read_bytes()
    entry.write_bytes(
        blob[: len(blob) // 2] if damage == "truncated" else b"\0" * 64
    )
    s0 = _executables(metrics)
    with caplog.at_level(logging.WARNING, "llm_consensus_tpu.utils"):
        np.testing.assert_array_equal(StoredJit(_double)(x), x * 2.0)
        np.testing.assert_array_equal(StoredJit(_double)(x), x * 2.0)
    grew = _executables(metrics)
    assert _grew(s0, grew, "built") == {"_double": 1}
    assert _grew(s0, grew, "loaded") == {"_double": 1}
    assert entry.read_bytes() != blob[: len(blob) // 2]
    warned = [r for r in caplog.records if "unreadable" in r.getMessage()]
    assert len(warned) == 1


def _fused_chain(x, y):
    for _ in range(8):
        x = jnp.tanh(-(x**2) + y) * jnp.exp(-x)
    return x.sum(), -(y**3)


def test_a_program_in_jaxs_own_cache_is_stored_whole(metrics, store):
    """XLA:CPU cannot serialize again an executable it loaded from jax's
    persistent cache (the loaded copy runs, its serialized form misses
    its kernels), so the store compiles its programs outside that cache:
    a program jax already holds is stored as a fresh build, and loads
    and runs in the next holder."""
    names = (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    saved = {n: getattr(jax.config, n) for n in names}
    x, y = jnp.ones((64, 64)), jnp.full((64, 64), 0.5)
    try:
        jax.config.update(names[0], 0)
        jax.config.update(names[1], -1)
        want = jax.jit(_fused_chain)(x, y)  # into jax's own cache
        jax.clear_caches()
        StoredJit(_fused_chain).build(x, y)
        s0 = _executables(metrics)
        got = StoredJit(_fused_chain)(x, y)
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
    assert _grew(s0, _executables(metrics), "loaded") == {"_fused_chain": 1}
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def _sum_rows(x):
    return x.sum(axis=0)


def test_two_writers_of_one_key_leave_one_whole_entry(metrics, store):
    """Writers of one key at once (replicas starting together) leave one
    whole entry and no temporary file; the next holder loads it."""
    x = jnp.ones((32, 8))
    writers = 2 * (os.cpu_count() or 4)
    start = threading.Barrier(writers)
    errors = []

    def build():
        try:
            start.wait(timeout=60)
            StoredJit(_sum_rows).build(x)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=build) for _ in range(writers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert len(list(store.iterdir())) == 1  # no temporary file left
    s0 = _executables(metrics)
    np.testing.assert_array_equal(StoredJit(_sum_rows)(x), 32.0)
    assert _grew(s0, _executables(metrics), "loaded") == {"_sum_rows": 1}
