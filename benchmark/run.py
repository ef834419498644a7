#!/usr/bin/env python3
"""One run of one benchmark cell, as ``BENCHMARK.json`` describes it.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --dry-run [--workload <name>]   # CPU, never a pass

A cell is a configuration (``benchmark/configs/<config>.json``) under a
traffic mix (``benchmark/traffic/<traffic>.json``, which names its loop
in ``benchmark/generators/``). Each per-layer metric is
``benchmark/layer_metrics/<metric>.json``: a reader module beside it and
its arguments. Everything is found by the names in ``BENCHMARK.json``, so
a later PR adds cells and metrics as files and edits nothing here.

The run starts the program's own ``serve`` as a child (this process
never imports jax: the chip belongs to the child), warms the cell's own
device programs, measures for ``--seconds`` with the load offered as the
traffic file says, checks the results, drains the server and prints one
JSON object as the last line of stdout. ``--trace 1`` also records a
device trace through the gateway's ``X-Profile`` header mid-window and
reports the per-layer metrics instead of the end-to-end ones.

Exit status: 0 with a result line; non-zero and no result line when the
server cannot run as the cell asks (no TPU, fewer chips, a failed
start, an unclean drain) or the program is not beside this directory.
"""

from __future__ import annotations

import argparse
import asyncio
import glob
import importlib
import json
import os
import random
import shutil
import subprocess
import sys
import time
from dataclasses import dataclass, field

T_START = time.monotonic()

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

import aclient  # noqa: E402
from server import (  # noqa: E402
    BenchFailure,
    Server,
    cache_entries,
    get_json,
    http,
)
from stats import filler_text  # noqa: E402
from warm import WarmFailure, warm_shapes  # noqa: E402

REDUCE_LIMIT_S = 240


def load(kind: str, name: str) -> dict:
    path = os.path.join(BENCH_DIR, kind, name + ".json")
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise BenchFailure(f"no {kind} file for {name!r}: {e}") from None


@dataclass
class Window:
    seconds: float
    t0: float = 0.0  # monotonic
    t1: float = 0.0
    wall0: float = 0.0  # time.time(), the clock of the server's stamps
    wall1: float = 0.0


@dataclass
class RunData:
    """What one run observed; the per-layer readers take what they need."""

    config: dict
    traffic: dict
    window: Window
    records: list = field(default_factory=list)  # the generator's, per request
    summaries: list = field(default_factory=list)  # server's, finished in window
    slots: int = 0  # the configuration's decode rows
    metrics_before: str = ""  # /metrics text at the window's edges
    metrics_after: str = ""
    trace: dict | None = None  # trace_reduce's output, --trace 1 only


class Ctx:
    """What a generator may use: the server's address, one way to send,
    and the two calls that open and shut the window."""

    def __init__(self, srv: Server, run: RunData, carrier):
        self.srv, self.run, self.window = srv, run, run.window
        self._carrier, self._carrier_task = carrier, None
        self.cache_before = self.cache_after = None
        self.ring_held_the_window = False

    async def post(self, path, body, *, headers=None, timeout=600.0):
        return await aclient.request(
            self.srv.host, self.srv.port, "POST", path, body,
            headers=headers, timeout=timeout,
        )

    async def get(self, path) -> aclient.Reply:
        return await aclient.request(
            self.srv.host, self.srv.port, "GET", path, timeout=60
        )

    async def _metrics_text(self) -> str:
        # /metrics is text, not JSON: read it off the event loop.
        status, text = await asyncio.to_thread(
            http, "GET", self.srv.base + "/metrics", None, 60
        )
        if status != 200:
            raise BenchFailure(f"GET /metrics -> {status}")
        return text

    async def open_window(self) -> None:
        self.run.metrics_before = await self._metrics_text()
        self.cache_before = cache_entries(self.srv.cache_dir)
        w = self.window
        w.t0, w.wall0 = time.monotonic(), time.time()
        if self._carrier is not None:
            self._carrier_task = asyncio.ensure_future(self._carrier(self))

    async def close_window(self) -> None:
        w = self.window
        w.t1, w.wall1 = time.monotonic(), time.time()
        self.cache_after = cache_entries(self.srv.cache_dir)
        self.run.metrics_after = await self._metrics_text()
        r = await self.get("/debug/requests?limit=512")
        if r.status != 200:
            raise BenchFailure(f"GET /debug/requests -> {r.status} {r.error}")
        got = r.doc["requests"]
        # The server keeps its newest 512: all of the window's, unless
        # the oldest it still has already lies inside the window.
        self.ring_held_the_window = (
            len(got) < 512 or min(s["finished_at"] for s in got) < w.wall0
        )
        self.run.summaries = [
            s for s in got if w.wall0 <= s["finished_at"] < w.wall1
        ]
        if self._carrier_task is not None:
            await self._carrier_task


def trace_carrier(spec: dict, seconds: float):
    """The one request that carries ``X-Profile: 1``: the gateway traces
    the device for as long as it runs, beside the cell's traffic."""
    async def carry(ctx: Ctx) -> None:
        await asyncio.sleep(spec["at"] * seconds)
        r = await ctx.post("/v1/generate", {
            "prompt": filler_text(
                spec["prompt_bytes"], random.Random(3), "[carrier]"),
            "max_new_tokens": spec["max_new_tokens"], "temperature": 0,
        }, headers={"X-Profile": "1"})
        if r.status != 200:
            raise BenchFailure(f"trace carrier -> {r.status} {r.error}")

    return carry


def reduce_trace(profile_dir: str, out_dir: str, keep: bool,
                 device_kind: str) -> dict:
    """Run ``trace_reduce.py`` on the recorded trace, in a child held to
    the CPU (reading a trace needs jax's reader, not a chip)."""
    found = sorted(glob.glob(
        os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise BenchFailure(f"the server wrote no trace under {profile_dir}")
    out = os.path.join(out_dir, "trace.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "trace_reduce.py"),
         found[-1], "--out", out, "--device-kind", device_kind],
        env=env, capture_output=True, text=True, timeout=REDUCE_LIMIT_S,
    )
    if r.returncode != 0:
        raise BenchFailure(f"trace_reduce: rc {r.returncode}\n{r.stderr[-3000:]}")
    if not keep:
        shutil.rmtree(profile_dir, ignore_errors=True)
    with open(out) as f:
        return json.load(f)


def layer_metrics(names: list[str], run: RunData) -> dict:
    """Each metric from its own reader; one that finds nothing to read
    returns None and is left out."""
    out = {}
    for name in names:
        spec = load("layer_metrics", name)
        reader = importlib.import_module("layer_metrics." + spec["reader"])
        value = reader.read(run, **spec.get("args", {}))
        if value is not None:
            out[name] = value
    return out


def run_cell(bench: dict, cell: dict, seed: int, seconds: float, trace: bool,
             dry_run: bool, keep_trace: bool, t_start: float) -> dict:
    config_name = "test-tiny" if dry_run else cell["config"]
    config = load("configs", config_name)
    traffic = load("traffic", cell["traffic"])
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    generator = importlib.import_module("generators." + traffic["generator"])
    out_dir = os.path.join(
        ROOT, "chiprun_out", "benchmark",
        f"{cell['name']}.s{seed}.t{int(trace)}" + (".dry" if dry_run else ""),
    )
    profile_dir = os.path.join(out_dir, "profile")
    shutil.rmtree(profile_dir, ignore_errors=True)
    extra = ["--cpu"] if dry_run else []
    if trace:
        extra += ["--profile-dir", profile_dir]
    run = RunData(config, traffic, Window(seconds),
                  slots=config["serve"]["serve-slots"])
    srv = Server(os.path.join(BENCH_DIR, "configs", config_name + ".json"),
                 out_dir, extra)
    try:
        listening_s = srv.wait_listening()
        backend = get_json(srv.base + "/readyz")["backend"]
        dev, kernels = backend["device"], backend["kernels"]
        if not dry_run:
            if dev["platform"] != "tpu":
                raise srv.fail(f"platform is {dev['platform']!r}, not 'tpu'")
            if dev["count"] < cell["chips"]:
                raise srv.fail(
                    f"{dev['count']} chip(s), the cell asks for {cell['chips']}")
            if dev["kind"] not in peaks:
                raise srv.fail(f"no peaks for device kind {dev['kind']!r}")
        carrier = (trace_carrier(traffic["trace_carrier"], seconds)
                   if trace else None)
        ctx = Ctx(srv, run, carrier)

        async def drive():
            t = time.monotonic()
            mates = await warm_shapes(ctx, traffic["warm"])
            warm_s = time.monotonic() - t
            records = await generator.run(ctx, traffic, seed, seconds)
            return mates, warm_s, records

        try:
            mates, warm_s, run.records = asyncio.run(drive())
        except WarmFailure as e:
            raise srv.fail(str(e)) from e
        setup_s = run.window.t0 - t_start
        log = srv.log_text()
        exit_doc = srv.drain()
    finally:
        srv.kill()
    if trace:
        run.trace = reduce_trace(profile_dir, out_dir, keep_trace, dev["kind"])

    e2e = generator.reduce(run)
    checks = {
        "platform_and_kernels": dry_run or (
            dev["platform"] == "tpu" and kernels == "pallas"),
        "identical_greedy_requests_agree": (
            mates["compared"] >= 1 and mates["same_path_agree"]),
        "no_failed_request": e2e["attempted"] > 0 and e2e["failed"] == 0,
        "no_prompt_truncated": "left-truncated" not in log,
        "no_compile_in_window": ctx.cache_before == ctx.cache_after,
        "every_generation_counted": ctx.ring_held_the_window,
        "token_counts_agree": e2e["token_counts_agree"],
        "drain_rc_0": exit_doc["rc"] == 0,
    }
    names = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}

    def reported(group: str) -> list[str]:
        return [
            m["name"] for m in bench[group]
            if cell["name"] in m.get("workloads", [cell["name"]])
        ]

    values = {**{k: e2e.get(k) for k in generator.END_TO_END},
              "setup_s": setup_s}
    layers = layer_metrics(reported("per_layer"), run)
    chosen = (layers if trace
              else {k: values.get(k) for k in reported("end_to_end")})
    device = {
        "platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
        "memory_peak_bytes": exit_doc["memory_peak_bytes"],
    }
    result = {
        "correct": all(checks.values()) and not dry_run,
        "attempted": e2e["attempted"],
        "failed": e2e["failed"],
        "metrics": {
            k: {"value": v, "unit": names[k]["unit"]}
            for k, v in chosen.items() if v is not None
        },
        "device": device,
        "workload": cell["name"], "seed": seed, "trace": int(trace),
        "checks": checks, "errors": e2e["errors"], "kernels": kernels,
        "mates": mates,
        "setup": {"listening_s": listening_s, "warm_s": warm_s,
                  "ramp_s": traffic["ramp_s"], "setup_s": setup_s,
                  "cache_entries": [ctx.cache_before, ctx.cache_after]},
        "generations_in_window": len(run.summaries),
        **{k: e2e[k] for k in ("halves_p50_ms",) if k in e2e},
    }
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = run.trace["breakdown"]
    else:
        result["layers"] = layers
    if dry_run:
        result["dry_run"] = True
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU, test-tiny, a short traced window of every cell "
                    "(or the one named): tests this harness; its lines say "
                    '"correct": false and are never a pass')
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the recorded .xplane.pb in the output directory")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "llm_consensus_tpu")):
        print(f"benchmark: no llm_consensus_tpu/ beside {BENCH_DIR}: this "
              "measures the program, it is not the program", file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.dry_run:
        todo = [cells[args.workload]] if args.workload else list(cells.values())
        seconds, trace = args.seconds or 4.0, True
    else:
        if args.workload not in cells:
            ap.error(f"--workload must be one of {sorted(cells)}")
        todo = [cells[args.workload]]
        seconds, trace = args.seconds or bench["run_seconds"], bool(args.trace)
    try:
        t_start = T_START
        for cell in todo:
            result = run_cell(bench, cell, args.seed, seconds, trace,
                              args.dry_run, args.keep_trace, t_start)
            t_start = time.monotonic()  # only a dry run walks several
            print(json.dumps(result), flush=True)
    except BenchFailure as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
