"""``trace_reduce.reduce`` on rows small enough to check by hand, and on
a sample recorded on the chip (``recorded_rows.json.gz``: the first two
fused steps of a ``mistral-7b.panel`` trace, PR 25, cut by
``trace_reduce.py --rows-out`` and with the HLO texts shortened).

    python3 -m pytest benchmark/tests -q      (or run this file)
"""

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce as tr  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


QMM = ("%closed_call.3 = bf16[8,4096]{1,0} custom-call(bf16[8,14336]{1,0} %x, "
       "s8[14336,4096]{1,0} %w, f32[1,4096]{1,0} %s), "
       'custom_call_target="tpu_custom_call"')
ATTN = ("%closed_call.7 = (f32[9,8,4,1]{3,2,1,0}, f32[9,8,4,1]{3,2,1,0}, "
        "f32[9,8,4,128]{3,2,1,0}) custom-call(bf16[8,32,128]{2,1,0} %q), "
        'custom_call_target="tpu_custom_call"')
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_by_hand():
    ms = 1_000_000
    rows = [
        # a fused step of 3 ms: a while of 2 ms that holds two ops, then
        # the int8 matmul for 1 ms
        [DEV, "XLA Modules", "jit__unknown(123)", 0, 3 * ms],
        [DEV, "XLA Ops", "%while.8 = (s32[]) while((s32[]) %t)", 0, 2 * ms],
        [DEV, "XLA Ops", "%fusion.1 = bf16[8]{0} fusion(bf16[8] %a)", 0, ms],
        [DEV, "XLA Ops", "%fusion.2.remat = bf16[8]{0} fusion(bf16[8] %a)",
         ms, ms // 2],
        [DEV, "XLA Ops", QMM, 2 * ms, ms],
        # idle 3..6 ms while the host fetches, then a decode step of 4 ms
        [DEV, "XLA Modules", "jit__decode_sample(9)", 6 * ms, 4 * ms],
        [DEV, "XLA Ops", "%copy.50 = bf16[32,512]{1,0} copy(bf16[32,512] %p)",
         6 * ms, 3 * ms],
        [DEV, "XLA Ops", ATTN, 9 * ms, ms],
        # a helper jit of microseconds is not a step program
        [DEV, "XLA Modules", "jit_add(5)", 5 * ms, 1000],
        [HOST, "batcher", "whole loop", 0, 10 * ms],
        [HOST, "batcher", "fetch", 3 * ms, 2 * ms],
        # other lines of the device plane count for the window only
        [DEV, "Steps", "1", 0, 10 * ms],
    ]
    out = tr.reduce(rows, PEAK)
    assert out["window_s"] == 10e-3
    assert abs(out["busy_s"] - 7e-3) < 1e-12
    assert out["programs"]["jit__unknown"]["count"] == 1
    assert out["step_dev_ms"] == 3.5  # median of 3 and 4 ms, jit_add left out
    assert out["kernels"]["qmm"]["calls"] == 1
    assert out["kernels"]["attn"]["seconds"] == 1e-3
    assert abs(out["kernel_time_pct"] - 100 * 2 / 7) < 1e-9
    # 14336 x 4096 int8 bytes (+ scales, x, out) at 819 GB/s over 1 ms
    least = (14336 * 4096 + 4 * 4096 + 2 * 8 * 14336 + 2 * 8 * 4096) / 819e9
    assert abs(out["qmm_roofline_pct"] - 100 * least / 1e-3) < 1e-9
    ops = dict(out["breakdown"]["device_ops"])
    assert abs(ops["fusion"] - 1.5e-3) < 1e-12
    assert abs(ops["while"] - 0.5e-3) < 1e-12  # its own time, not its body's
    assert abs(ops["copy"] - 3e-3) < 1e-12
    # the 3 ms hole goes to the shortest host event that covers at least
    # half of it, not to the loop that covers everything
    assert out["breakdown"]["idle_gaps"] == [["fetch", 3e-3]]


def test_no_device_plane_reads_as_nothing():
    out = tr.reduce([[HOST, "t", "x", 0, 10]])
    assert out["busy_s"] == 0.0 and out["step_dev_ms"] is None


def test_recorded_sample():
    with gzip.open(os.path.join(HERE, "recorded_rows.json.gz"), "rt") as f:
        rows = json.load(f)
    out = tr.reduce(rows, PEAK)
    # two fused steps of ~63.7 ms with a ~4 ms gap between them
    assert out["programs"]["jit__unknown"]["count"] == 2
    assert abs(out["step_dev_ms"] - 63.725) < 0.01
    assert abs(out["window_s"] - 0.1318) < 1e-4
    assert 0.96 < out["busy_s"] / out["window_s"] < 0.97
    # 32 layers x 2 steps: 7 int8 matmuls, 1 attention, 2 norms a layer
    assert out["kernels"]["qmm"]["calls"] == 448
    assert out["kernels"]["attn"]["calls"] == 64
    assert out["kernels"]["norm"]["calls"] == 128
    assert 18 < out["kernel_time_pct"] < 19
    ops = dict(out["breakdown"]["device_ops"])
    # the copies around the kernels take more time than the kernels
    assert ops["dynamic-slice_bitcast_fusion"] > ops["closed_call"]
    assert len(out["breakdown"]["idle_gaps"]) <= 10


if __name__ == "__main__":
    for name, fn in list(globals().items()):
        if name.startswith("test_"):
            fn()
            print("ok", name)
