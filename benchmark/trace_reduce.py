#!/usr/bin/env python3
"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports about the device.

    python3 benchmark/trace_reduce.py <trace.xplane.pb> --out trace.json
    python3 benchmark/trace_reduce.py <trace.xplane.pb> --dump   # look by hand

Two steps, so that the second can be checked on a small recorded sample
(``benchmark/tests``): ``load`` turns the trace into plain rows
``[plane, line, name, start_ns, duration_ns]``; ``reduce`` turns rows
into

- ``window_s``: first to last event on the device planes;
- ``busy_s``: the union of the intervals in which an XLA op ran, per
  device plane, averaged over the planes that ran any;
- ``programs``: per XLA module (one jitted program), count, median and
  total device seconds; ``step_dev_ms`` is the median over all runs of
  step programs, which are the module runs of ``STEP_MIN_NS`` or longer
  (the batcher's decode, fused and chunk programs; its other jits are
  index updates of microseconds — told apart by length because jax names
  a jitted ``functools.partial`` ``jit__unknown``);
- ``kernels``: per Pallas kernel, calls and device seconds, and
  ``kernel_time_pct``, their share of ``busy_s``. The trace names every
  Pallas call ``closed_call.N``, so ``kernel_of`` tells them apart by
  what the HLO text of the ``tpu_custom_call`` shows: an int8 operand is
  the int8 matmul, a tuple result is the ragged attention (m, l, o),
  what is left is the fused RMS norm. For the int8 matmul,
  ``least_seconds`` is the least time its shapes allow
  (``kernel_costs.py``, ``peaks.json``) and ``qmm_roofline_pct`` that
  over its device time — recorded, and not yet a metric: the small
  weight slices reach the kernel already in fast memory (``S(1)`` in
  the HLO layout), put there by the copy before it, so the call's own
  time leaves out part of the bytes' way (PERF.md, Open questions);
- ``breakdown``: the ten kinds of op with most device time of their own
  (an op's time less the ops nested in it, so a ``while`` does not count
  its body twice), and the ten largest totals of idle gaps by the host
  event they are charged to (``gap_owners``).

Run with ``JAX_PLATFORMS=cpu``: reading a trace needs jax's reader, not
a chip.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import kernel_costs  # noqa: E402

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = re.compile(r"^/host:")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_MIN_NS = 1_000_000
MIN_GAP_NS = 20_000  # shorter gaps are launch spacing, not idleness
QMM_SHAPES = re.compile(r" = bf16\[(\d+),(\d+)\].*?s8\[(\d+),(\d+)\]")


def load(path: str) -> list[list]:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        if not (DEVICE_PLANE.match(plane.name) or HOST_PLANE.match(plane.name)):
            continue
        for line in plane.lines:
            for ev in line.events:
                rows.append([plane.name, line.name, ev.name,
                             int(ev.start_ns), int(ev.duration_ns)])
    return rows


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def base_name(name: str) -> str:
    """``%fusion.123 = ...`` and ``fusion.7`` are one kind of op; a
    module's ``jit_f(123)`` is ``jit_f``."""
    name = name.split(" = ")[0].lstrip("%")
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"(\.remat\d*|\.clone|[.\d])+$", "", name) or name


def kernel_of(text: str) -> str | None:
    """Which Pallas kernel an op's HLO text is, or None for other ops
    (XLA's own custom calls, such as ``AllocateBuffer``, among them)."""
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    if "s8[" in text:
        return "qmm"
    if text.split(" = ", 1)[1].startswith("("):
        return "attn"
    return "norm"


def self_times(events: list[tuple[int, int, str]]) -> list[tuple[str, int]]:
    """(name, own ns) per op: its duration less that of the ops nested
    inside it on the same line."""
    out, stack = [], []  # stack: [end, name, own]
    for s, e, name in sorted(events, key=lambda x: (x[0], -(x[1] - x[0]))):
        while stack and stack[-1][0] <= s:
            end, n, own = stack.pop()
            out.append((n, own))
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, name, e - s])
    out.extend((n, own) for _, n, own in stack)
    return out


def gap_owners(gaps: list[tuple[int, int]], host_events: list) -> list[str]:
    """For each idle gap (sorted), the host event it is charged to: the
    shortest of those that cover at least half of it — the most specific
    account of what the host was doing — else the one that covers most."""
    events = sorted(host_events, key=lambda e: e[1])
    active: list = []
    nxt, owners = 0, []
    for gs, ge in gaps:
        while nxt < len(events) and events[nxt][1] < ge:
            active.append(events[nxt])
            nxt += 1
        active = [e for e in active if e[2] > gs]
        best, best_key = "no host event", None
        for name, s, e in active:
            overlap = min(ge, e) - max(gs, s)
            if overlap <= 0:
                continue
            covers = 2 * overlap >= ge - gs
            key = (covers, -(e - s)) if covers else (covers, overlap)
            if best_key is None or key > best_key:
                best, best_key = name, key
        owners.append(best)
    return owners


def reduce(rows: list[list], peak: dict | None = None) -> dict:
    ops = defaultdict(list)  # device plane -> [(start, end, name)]
    modules = defaultdict(list)  # module name -> [duration_ns]
    host_events = []
    first, last = None, None
    for plane, line, name, start, dur in rows:
        if DEVICE_PLANE.match(plane):
            first = start if first is None else min(first, start)
            last = start + dur if last is None else max(last, start + dur)
            if line == OPS_LINE:
                ops[plane].append((start, start + dur, name))
            elif line == MODULES_LINE:
                modules[base_name(name)].append(dur)
        elif dur > 0:
            host_events.append((name, start, start + dur))
    if first is None or not ops:
        return {"window_s": 0.0, "busy_s": 0.0, "breakdown": {
            "device_ops": [], "idle_gaps": []}, "programs": {}, "kernels": {},
            "step_dev_ms": None, "kernel_time_pct": None,
            "qmm_roofline_pct": None}
    busy_ns, by_op, kernels = [], defaultdict(int), {}
    gaps = defaultdict(int)
    for plane, evs in ops.items():
        merged = union([(s, e) for s, e, _ in evs])
        busy_ns.append(sum(e - s for s, e in merged))
        for name, own in self_times(evs):
            by_op[base_name(name)] += max(own, 0)
        for s, e, name in evs:
            kernel = kernel_of(name)
            if kernel is None:
                continue
            k = kernels.setdefault(
                kernel, {"calls": 0, "seconds": 0.0, "least_seconds": 0.0})
            k["calls"] += 1
            k["seconds"] += (e - s) / 1e9
            shapes = QMM_SHAPES.search(name) if kernel == "qmm" else None
            if shapes and peak:
                m_, n_, k_, n2 = map(int, shapes.groups())
                if n_ == n2:
                    k["least_seconds"] += kernel_costs.least_seconds(
                        kernel_costs.quant_matmul(m_, k_, n_), peak)[0]
        edges = [(first, first)] + merged + [(last, last)]
        holes = [(e0, s1) for (_, e0), (s1, _) in zip(edges, edges[1:])
                 if s1 - e0 >= MIN_GAP_NS]
        for (gs, ge), owner in zip(holes, gap_owners(holes, host_events)):
            gaps[owner] += ge - gs
    n = len(busy_ns)
    busy_s = sum(busy_ns) / n / 1e9
    programs = {
        name: {"count": len(d), "median_ms": statistics.median(d) / 1e6,
               "total_s": sum(d) / 1e9}
        for name, d in modules.items()
    }
    steps = [x for d in modules.values() for x in d if x >= STEP_MIN_NS]
    qmm = kernels.get("qmm", {})
    kernel_s = sum(k["seconds"] for k in kernels.values()) / n

    def top(table: dict) -> list:
        rows_ = sorted(table.items(), key=lambda kv: -kv[1])[:10]
        return [[k, v / n / 1e9] for k, v in rows_]

    return {
        "window_s": (last - first) / 1e9,
        "busy_s": busy_s,
        "step_dev_ms": statistics.median(steps) / 1e6 if steps else None,
        "kernel_time_pct": 100.0 * kernel_s / busy_s if kernels else None,
        "qmm_roofline_pct": (
            100.0 * qmm["least_seconds"] / qmm["seconds"]
            if qmm.get("least_seconds") else None),
        "programs": programs,
        "kernels": kernels,
        "breakdown": {"device_ops": top(by_op), "idle_gaps": top(gaps)},
    }


def dump(rows: list[list]) -> None:
    """What to look at by hand: planes, lines, and the names that took
    most time on each line."""
    lines = defaultdict(lambda: defaultdict(lambda: [0, 0]))
    for plane, line, name, _start, dur in rows:
        cell = lines[(plane, line)][base_name(name)]
        cell[0] += 1
        cell[1] += dur
    for (plane, line), names in sorted(lines.items()):
        total = sum(v[1] for v in names.values())
        print(f"== {plane} | {line}: {len(names)} names, {total / 1e6:.1f} ms")
        for name, (count, dur) in sorted(
                names.items(), key=lambda kv: -kv[1][1])[:25]:
            print(f"   {dur / 1e6:10.3f} ms  x{count:<7d} {name[:110]}")
    seen = set()
    for _plane, _line, name, _start, _dur in rows:
        if kernel_of(name) and base_name(name) not in seen and len(seen) < 40:
            seen.add(name.split(" = ")[0])
            print("custom-call:", kernel_of(name), name[:600])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--out")
    ap.add_argument("--dump", action="store_true")
    ap.add_argument("--device-kind", help="the row of peaks.json to use")
    ap.add_argument("--rows-out", help="write the first N ms of rows as JSON")
    ap.add_argument("--rows-ms", type=float, default=60.0)
    args = ap.parse_args()
    rows = load(args.trace)
    if args.dump:
        dump(rows)
    if args.rows_out:
        t0 = min(r[3] for r in rows)
        cut = [r for r in rows if r[3] - t0 < args.rows_ms * 1e6]
        with open(args.rows_out, "w") as f:
            json.dump(cut, f)
    if args.out:
        with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
            peak = json.load(f).get(args.device_kind)
        with open(args.out, "w") as f:
            json.dump(reduce(rows, peak), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
