#!/usr/bin/env python3
"""How often the device ran dry in a traced stretch, and after what.

    JAX_PLATFORMS=cpu python3 scripts/drain_count.py <trace.xplane.pb> [--min-ms 1.0]

A *drain* is a hole of ``--min-ms`` or longer between two busy stretches
of the device (launch spacing between queued programs is tens of
microseconds); busy stretches under 0.3 ms — the sampler's eager ops, an
index update — do not end a hole, and their time is taken off its idle
seconds. Each is charged to a *first-token fetch* where one ends at the
hole — a ``batcher.device_wait`` nested in a ``batcher.retire`` (up to
PR 35 that is ``_sample_first``, the only wait the retire phase makes)
that ends from 2 ms before the hole's start to its end — and otherwise
to the host event ``benchmark/trace_reduce.py`` would charge it to
(``gap_owners``). Also counted: the ``batcher.dispatch`` events that
begin inside a hole (a program enqueued to an empty device).

Prints one JSON line. Reads a trace; measures nothing itself.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict

sys.path.insert(
    0,
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "benchmark"),
)

import trace_reduce as tr  # noqa: E402

SLACK_NS = 2_000_000
SMALL_NS = 300_000


def drains(rows: list[list], min_ns: int) -> dict:
    ops = defaultdict(list)
    host = []
    for plane, line, name, start, dur in rows:
        if tr.DEVICE_PLANE.match(plane):
            if line == tr.OPS_LINE:
                ops[plane].append((start, start + dur))
        elif dur > 0:
            host.append((name, start, start + dur, (plane, line)))
    if not ops:
        return {"window_s": 0.0, "drains": 0}
    plane = sorted(ops)[0]
    merged = tr.union(ops[plane])
    first, last = merged[0][0], merged[-1][1]
    busy = sum(e - s for s, e in merged)
    big = [iv for iv in merged if iv[1] - iv[0] >= SMALL_NS]
    holes = [(e0, s1) for (_, e0), (s1, _) in zip(big, big[1:])
             if s1 - e0 >= tr.MIN_GAP_NS]
    small = [iv for iv in merged if iv[1] - iv[0] < SMALL_NS]

    def idle(hole):
        return hole[1] - hole[0] - sum(
            e - s for s, e in small if hole[0] <= s and e <= hole[1])

    long_holes = [h for h in holes if idle(h) >= min_ns]
    retires = [(s, e, ln) for n, s, e, ln in host if n == "batcher.retire"]
    waits = [(s, e, ln) for n, s, e, ln in host if n == "batcher.device_wait"]
    first_token_waits = sorted(
        e for s, e, ln in waits
        if any(rs <= s and e <= re_ and ln == rl for rs, re_, rl in retires)
    )
    dispatches = [(s, e) for n, s, e, _ in host if n == "batcher.dispatch"]
    owners = tr.gap_owners(long_holes, [h[:3] for h in host])
    after_first, by_owner = [], defaultdict(lambda: [0, 0])
    for (gs, ge), owner in zip(long_holes, owners):
        if any(gs - SLACK_NS <= t <= ge for t in first_token_waits):
            after_first.append(idle((gs, ge)))
        else:
            by_owner[owner][0] += 1
            by_owner[owner][1] += idle((gs, ge))
    idle_long = sum(idle(h) for h in long_holes)
    return {
        "window_s": (last - first) / 1e9,
        "busy_s": busy / 1e9,
        "idle_s": sum(idle(h) for h in holes) / 1e9,
        "min_ms": min_ns / 1e6,
        "drains": len(long_holes),
        "drain_idle_s": idle_long / 1e9,
        "drain_mean_ms": (idle_long / len(long_holes) / 1e6
                          if long_holes else 0.0),
        "after_first_token": len(after_first),
        "after_first_token_idle_s": sum(after_first) / 1e9,
        "first_token_waits": len(first_token_waits),
        "other_by_owner": {
            k: [n, ns / 1e9]
            for k, (n, ns) in sorted(by_owner.items(), key=lambda kv: -kv[1][1])
        },
        "dispatches": len(dispatches),
        "dispatches_to_an_empty_device": sum(
            1 for s, _ in dispatches
            if any(gs <= s < ge for gs, ge in holes)
        ),
        "drain_ms": sorted(round(idle(h) / 1e6, 2) for h in long_holes),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--min-ms", type=float, default=1.0)
    args = ap.parse_args()
    print(json.dumps(drains(tr.load(args.trace), int(args.min_ms * 1e6))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
