"""``trace_reduce.reduce`` on a second sample recorded on the chip, after
PR 26 named the step programs and the serving kernels and put the
batcher loop's phases on the profiler's host plane
(``recorded_rows_named.json.gz``: one fused step and the two decode steps
after it, with the idle gap that follows a prompt's last chunk, out of a
``mistral-7b.chat`` trace, PR 26; cut by ``trace_reduce.py --rows-out``
and trimmed to those three steps, with the HLO texts shortened).

    python3 -m pytest benchmark/tests -q      (or run this file)
"""

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce as tr  # noqa: E402

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
LAYERS = 32


def sample() -> list:
    path = os.path.join(HERE, "recorded_rows_named.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_step_programs_by_name():
    out = tr.reduce(sample(), PEAK)
    programs = out["programs"]
    assert "jit__unknown" not in programs
    assert programs["jit_fused_step"]["count"] == 1
    assert programs["jit_decode_step"]["count"] == 2
    # a decode step of 8 rows takes ~59 ms, the fused step beside it ~65
    assert 58.5 < programs["jit_decode_step"]["median_ms"] < 59.5
    assert 64 < programs["jit_fused_step"]["median_ms"] < 67
    # step_dev_ms is still one median over the three, whatever their kind
    assert 58.5 < out["step_dev_ms"] < 60


def test_named_kernels_are_classed_as_before():
    rows = sample()
    by_name: dict[str, set] = {}
    for _plane, line, name, _start, _dur in rows:
        kernel = tr.kernel_of(name) if line == tr.OPS_LINE else None
        if kernel:
            by_name.setdefault(tr.base_name(name), set()).add(kernel)
    # the trace carries each kernel's own name, and the HLO text still
    # says which one it is
    assert by_name == {
        "quant_matmul": {"qmm"},
        "ragged_attention": {"attn"},
        "rms_norm": {"norm"},
    }
    out = tr.reduce(rows, PEAK)
    # 3 steps x 32 layers: 7 int8 matmuls, 1 attention, 2 norms a layer;
    # a step adds the head's matmul and norm, the fused one the chunk
    # lane's too
    assert out["kernels"]["qmm"]["calls"] == 3 * (7 * LAYERS + 1) + 1
    assert out["kernels"]["attn"]["calls"] == 3 * LAYERS
    assert out["kernels"]["norm"]["calls"] == 3 * (2 * LAYERS + 1) + 1
    assert 19 < out["kernel_time_pct"] < 21
    ops = dict(out["breakdown"]["device_ops"])
    assert "closed_call" not in ops
    assert ops["quant_matmul"] > ops["ragged_attention"] > 0
    # the copies around the kernels still take more time than the kernels
    assert ops["dynamic-slice_bitcast_fusion"] > ops["quant_matmul"]


def test_gaps_are_charged_to_batcher_phases():
    out = tr.reduce(sample(), PEAK)
    gaps = dict(out["breakdown"]["idle_gaps"])
    phases = {k: v for k, v in gaps.items() if k.startswith("batcher.")}
    assert phases, gaps
    # the one long gap (~12 ms: the host between a prompt's last chunk
    # and the next dispatch, most of it the first token's sample) belongs
    # to phases, not to "no host event"
    assert max(gaps, key=gaps.get) == "batcher.device_wait"
    assert sum(phases.values()) > 0.8 * sum(gaps.values())
    assert "no host event" not in gaps
    assert 0.90 < out["busy_s"] / out["window_s"] < 0.97


if __name__ == "__main__":
    for name, fn in list(globals().items()):
        if name.startswith("test_"):
            fn()
            print("ok", name)
