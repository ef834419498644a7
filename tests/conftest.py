"""Test configuration: force JAX onto a simulated 8-device CPU mesh.

The reference has no tests at all (SURVEY.md §4). Our multi-device tests
(DP/TP/EP shardings, ring attention collectives) run on CPU-simulated
devices via ``--xla_force_host_platform_device_count`` so they need no TPU
(SURVEY.md §4's prescription).

Must run before the first ``import jax`` anywhere in the test process.
"""

import os

# Force CPU: tests never take a chip, and the multi-device tests need the
# 8 simulated CPU devices below. The live config is flipped as well, for
# a process that imported jax before this file ran.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# This environment's default matmul precision truncates fp32 matmuls to
# bf16 passes; numerics tests compare against exact numpy references, so
# pin full precision for the test process only (production keeps the fast
# default — bf16 on the MXU is the intended TPU path).
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(scope="session")
def cpu_devices():
    import jax

    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 simulated devices, got {len(devices)}"
    return devices


def pytest_collection_modifyitems(config, items):
    """Schedule the heaviest modules FIRST.

    The GPipe pipeline tests compile shard_map programs whose peak
    process memory exceeds what's left after an xdist worker has
    accumulated several other modules' XLA:CPU state — the worker
    aborts ("worker crashed") even though every test passes in
    isolation. Heavy modules first means they land on fresh workers;
    the light tail fills in afterwards. Stable sort preserves
    within-module order.
    """
    heavy = (
        "test_pipeline.py",
        "test_train_loop.py",
        "test_training.py",
        "test_parallel.py",
    )
    items.sort(
        key=lambda it: 0 if any(h in it.nodeid for h in heavy) else 1
    )
    # Smoke-tier marking (see _SMOKE_TESTS at the bottom of this file).
    for item in items:
        if item.name.split("[")[0] in _SMOKE_TESTS:
            item.add_marker(pytest.mark.smoke)


@pytest.fixture(autouse=True, scope="module")
def _bounded_xla_arena():
    """Clear JAX compile caches between test modules.

    XLA:CPU keeps every compiled executable alive for the process; an
    xdist worker that accumulates several heavy modules' programs can
    hit the process arena limit and abort (the round-2 monolithic-run
    failure mode, which grows back as the suite grows). Clearing per
    module bounds each worker at its heaviest single module; cross-
    module cache hits are rare (different shapes), so the runtime cost
    is small.
    """
    import jax

    jax.clear_caches()
    yield


# ---------------------------------------------------------------------------
# Smoke tier: one-or-two fast tests per subsystem, selected centrally so
# the list is auditable in one place. `pytest -m smoke` runs in <2 min
# (gate iteration / future-round triage); the FULL suite stays the merge
# gate. Names, not nodeids: parametrized variants all count.
# ---------------------------------------------------------------------------

_SMOKE_TESTS = {
    # protocol: messages/parsing/prompts/personas/coordinator/debate
    "test_good_verdict",
    "test_answer_prompt_shape",
    "test_default_panel_matches_reference",
    "test_unanimous_first_round",
    "test_debate_validates_before_generating",
    "test_faults_are_seeded_and_counted",
    # voting / eval
    "test_majority_vote_basic",
    "test_bundled_dataset_loads_and_golds_extract",
    # ops / model / quant
    "test_rms_norm_matches_numpy",
    "test_forward_shapes_and_dtype",
    "test_quantize_roundtrip_error_bound",
    "test_quantize_kv_roundtrip",
    # engine / tokenizer / backends
    "test_byte_tokenizer_roundtrip",
    "test_engine_text_roundtrip",
    "test_generate_batch_returns_aligned_results",
    # training / data / checkpoint
    "test_sft_loader_mask_and_resume",
    "test_loss_is_finite_and_near_uniform_at_init",
    "test_params_roundtrip",
    # parallel / multihost
    "test_make_mesh_default_all_data",
    "test_param_pspecs_cover_dense_and_moe",
    "test_pp_param_pspecs_shard_layer_axis",
    "test_initialize_noop_single_host",
    # serving / paged
    "test_page_write_gather_roundtrip",
    "test_submit_after_close_raises",
    # native runtime / utils / cli
    "test_batch_encode_matches_python_tokenizer",
    "test_request_spans_tree_and_summary",
    "test_parser_defaults",
    "test_one_shot_question_fake_backend",
}
