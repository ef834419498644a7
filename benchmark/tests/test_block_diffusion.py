"""Checks of the block-diffusion readers' arithmetic; no server, no jax.

    python3 -m pytest benchmark/tests -q      (or run this file)

Not part of the repo's tier-1 tests (those collect ``tests/`` only).
"""

import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import kernel_costs_block_diffusion as costs  # noqa: E402
from layer_metrics import block_diffusion, counter_ratio  # noqa: E402

with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-chat.json")) as f:
    CONFIG = json.load(f)
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _text(counters: dict) -> str:
    return "".join(
        f"{name}{{{labels}}} {value}\n" if labels else f"{name} {value}\n"
        for (name, labels), value in counters.items()
    )


def _run(after: dict, trace: dict | None):
    return types.SimpleNamespace(
        config=CONFIG, metrics_before="", metrics_after=_text(after),
        trace=trace,
    )


# Two block programs in the window (one with chunk lanes), one prefill.
COUNTERS = {
    ("gateway_device_programs_total", 'kind="denoise"'): 1,
    ("gateway_device_programs_total", 'kind="fused"'): 1,
    ("gateway_device_programs_total", 'kind="prefill"'): 1,
    ("gateway_attention_pages_read_total", 'kind="denoise"'): 400,
    ("gateway_attention_pages_read_total", 'kind="fused"'): 600,
    ("gateway_attention_pages_read_total", 'kind="prefill"'): 100,
    ("gateway_attention_pairs_total", 'kind="denoise"'): 2_000_000,
    ("gateway_attention_pairs_total", 'kind="fused"'): 4_000_000,
    ("gateway_attention_pairs_total", 'kind="prefill"'): 500_000,
    ("gateway_moe_layer_programs_total", 'kind="denoise"'): 90,
    ("gateway_moe_layer_programs_total", 'kind="fused"'): 90,
    ("gateway_moe_layer_programs_total", 'kind="prefill"'): 18,
    ("gateway_moe_assignments_total", 'kind="denoise"'): 90 * 512,
    ("gateway_moe_assignments_total", 'kind="fused"'): 90 * 1024,
    ("gateway_moe_assignments_total", 'kind="prefill"'): 18 * 512,
    ("gateway_moe_experts_touched_total", 'kind="denoise"'): 90 * 128,
    ("gateway_moe_experts_touched_total", 'kind="fused"'): 90 * 128,
    ("gateway_moe_experts_touched_total", 'kind="prefill"'): 18 * 100,
    ("gateway_denoise_steps_total", 'kind="denoise"'): 80,
    ("gateway_denoise_steps_total", 'kind="fused"'): 80,
    ("gateway_diffusion_tokens_total", ""): 128,
    ("gateway_diffusion_blocks_total", ""): 32,
}
TRACE = {
    "busy_s": 1.0,
    "programs": {"jit_denoise_step": {"count": 2},
                 "jit_prefill_chunk": {"count": 1}},
    "kernels": {"attn": {"seconds": 0.01}},
    "breakdown": {"device_ops": [["moe_grouped_matmul", 0.5]]},
}


def test_block_attention_least_is_pages_and_pairs():
    c = costs.block_attention(10, 1000, 18, 64, 2048, 32, 128)
    assert c["bytes"] == 10 * 64 * 2048 * 18
    assert c["ops"] == 4 * 1000 * 32 * 128 * 18


def test_attention_roofline_from_counters_and_trace():
    got = block_diffusion.read(
        _run(COUNTERS, TRACE), what="attention_roofline_pct")
    # 2 block programs of 500 pages / 3e6 pairs on average, 1 prefill
    # of 100 pages / 5e5 pairs; each its larger of bytes and ops.
    def least(pages, pairs):
        c = costs.block_attention(pages, pairs, 18, 64, 2048, 32, 128)
        return max(c["bytes"] / PEAK["hbm_bytes_per_s"],
                   c["ops"] / PEAK["bf16_flops"])

    want = 2 * least(500, 3e6) + least(100, 5e5)
    assert abs(got - 100 * want / 0.01) < 1e-9


def test_moe_roofline_reads_the_grouped_matmul():
    got = block_diffusion.read(_run(COUNTERS, TRACE), what="moe_roofline_pct")
    assert got is not None and 0 < got < 100


def test_nothing_to_read_on_a_program_without_blocks():
    plain = {k: v for k, v in COUNTERS.items()
             if k[0] != "gateway_diffusion_blocks_total"}
    for what in ("attention_roofline_pct", "moe_roofline_pct"):
        assert block_diffusion.read(_run(plain, TRACE), what=what) is None
        assert block_diffusion.read(_run(COUNTERS, None), what=what) is None


def test_denoise_steps_per_token():
    spec_path = os.path.join(BENCH, "layer_metrics",
                             "denoise_steps_per_token.json")
    with open(spec_path) as f:
        spec = json.load(f)
    got = counter_ratio.read(_run(COUNTERS, None), **spec["args"])
    assert got == 160 / 128
    # the parent's program has no such counters: nothing, no error
    assert counter_ratio.read(_run({}, None), **spec["args"]) is None


if __name__ == "__main__":
    for name, fn in list(globals().items()):
        if name.startswith("test_"):
            fn()
            print("ok", name)
