"""What a kernel call must do at the least, from its shapes alone.

The operations and bytes an algorithm needs for one call of a Pallas
kernel on the serving path, kept here so that no PR that speeds a kernel
up can also change what it is measured against. (The ragged attention
has no function yet: its bytes depend on each row's cached length, which
the trace does not carry — PERF.md, Open questions.) A call's least time on
a device is the larger of operations over the peak rate and bytes over
the peak bandwidth (``benchmark/peaks.json``); a kernel's roofline share
is that least time over its device time in the trace.
"""

from __future__ import annotations


def quant_matmul(m: int, k: int, n: int, out_bytes: int = 2) -> dict:
    """``x[m, k] (bf16) @ int8 w[k, n]`` with one f32 scale per column.
    Every weight byte is read once, x once, the result written once; the
    products run on the bf16 units (the kernel widens int8 in registers)."""
    return {
        "ops": 2 * m * k * n,
        "bytes": k * n + 4 * n + 2 * m * k + out_bytes * m * n,
    }


def least_seconds(cost: dict, peak: dict) -> tuple[float, str]:
    """The least time the device could take, and which bound applies."""
    by_ops = cost["ops"] / peak["bf16_flops"]
    by_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (by_ops, "compute") if by_ops > by_bytes else (by_bytes, "memory")
