#!/usr/bin/env python3
"""Where ``setup_s`` goes, one cell run at a time (stdlib only: the chip
belongs to each run's own server child).

    python3 scripts/setup_table.py [--seconds N] <tag> <side>:<cell>:<seed>:<trace> ...

on a host with the chip. ``side`` as in ``scripts/chip_ab.py`` (``parent``, ``change``, ``tree``).
Each run is that side's own ``benchmark/run.py``, unchanged, watched from
outside: the ``/metrics`` text at the window's edges is kept beside the
server log (``metrics_open.txt``, ``metrics_close.txt``), and the server
child starts through a launcher that runs the side's own
``benchmark/launcher.py`` after wrapping two things it only reads — the
compile seconds inside each ``setup.<phase>`` and the compile listener's
own seconds and events (``probe.json``). A side without the spans (the
parent) runs unwatched inside. One row a run goes to
``chiprun_out/<tag>/setup.jsonl`` and to stdout.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOTS = {
    "parent": os.path.join(ROOT, ".bench_checkout", "parent"),
    "tree": os.path.join(ROOT, ".bench_checkout", "tree"),
    "change": ROOT,
}
SECONDS = "51"

_LAUNCHER = """\
import runpy, sys
sys.path.insert(0, {here!r})
import setup_table
setup_table.watch_server()
runpy.run_path({real!r}, run_name="__main__")
"""


def watch_server() -> None:
    """In the server child, before ``cli.main``: time the compile
    listener's callbacks and read the compile seconds at each setup
    phase's edges; write them at exit beside ``--exit-file``."""
    import atexit
    import contextlib
    import functools
    import time

    from llm_consensus_tpu import cli
    from llm_consensus_tpu.utils import tracing

    listener = getattr(tracing, "_CompileListener", None)
    if listener is None:
        return
    from llm_consensus_tpu.server.metrics import COMPILE_SECONDS

    rec = {"listener_s": 0.0, "events": 0, "compile_in": {}}
    for name in ("start", "end", "event"):
        def timed(self, *a, _f=getattr(listener, name), **kw):
            t = time.perf_counter()
            try:
                return _f(self, *a, **kw)
            finally:
                rec["listener_s"] += time.perf_counter() - t
                rec["events"] += 1

        setattr(listener, name, timed)

    def compiled() -> float:
        return sum(c.value for c in list(COMPILE_SECONDS._children.values()))

    @contextlib.contextmanager
    def watched(clock, name, **meta):
        c0 = compiled()
        with tracing.PhaseClock.phase(clock, name, **meta):
            yield
        rec["compile_in"][name] = (
            rec["compile_in"].get(name, 0.0) + compiled() - c0)

    make = cli._setup_clock

    def setup_clock():
        clock = make()
        clock.phase = functools.partial(watched, clock)
        return clock

    cli._setup_clock = setup_clock
    exit_file = sys.argv[sys.argv.index("--exit-file") + 1]
    out = os.path.join(os.path.dirname(exit_file), "probe.json")
    atexit.register(lambda: json.dump(rec, open(out, "w")))


def one(root: str, cell: str, seed: str, trace: str, seconds: str) -> int:
    """One run of ``root``'s harness, with the two observers."""
    bench = os.path.join(root, "benchmark")
    sys.path.insert(0, bench)
    os.chdir(root)
    import run
    import server

    probe_dir = os.path.join(root, "chiprun_out", "setup_probe")
    os.makedirs(probe_dir, exist_ok=True)
    with open(os.path.join(probe_dir, "launcher.py"), "w") as f:
        f.write(_LAUNCHER.format(
            here=os.path.dirname(os.path.abspath(__file__)),
            real=os.path.join(bench, "launcher.py")))
    server.BENCH_DIR = probe_dir

    def keep(method, name, attr):
        async def kept(self):
            await method(self)
            path = os.path.join(os.path.dirname(self.srv.log_path), name)
            with open(path, "w") as f:
                f.write(getattr(self.run, attr))
        return kept

    run.Ctx.open_window = keep(
        run.Ctx.open_window, "metrics_open.txt", "metrics_before")
    run.Ctx.close_window = keep(
        run.Ctx.close_window, "metrics_close.txt", "metrics_after")
    sys.argv = ["run.py", "--workload", cell, "--seed", seed,
                "--seconds", seconds, "--trace", trace]
    return run.main()


def _samples(text: str, family: str) -> list:
    """(labels dict, value) of each sample of ``family``."""
    out = []
    for line in text.splitlines():
        if line.startswith(family + "{"):
            labels, value = line[len(family) + 1:].rsplit("} ", 1)
            pairs = [p.split("=", 1) for p in labels.split('",')]
            out.append(({k: v.strip('"') for k, v in pairs}, float(value)))
    return out


def row(result: dict, log_dir: str) -> dict:
    """The run's setup, split: the harness's stretches, the program's
    phases at the window's open, compile inside each, the top programs
    by seconds (``trace`` / ``lower`` / ``backend`` / ``load``: read from
    the store of executables) with how many of each the store ``built``
    and ``loaded``."""
    setup = result["setup"]
    r = {"setup_s": setup["setup_s"], "listening_s": setup["listening_s"],
         "warm_s": setup["warm_s"], "ramp_s": setup["ramp_s"]}
    try:
        with open(os.path.join(log_dir, "metrics_open.txt")) as f:
            text = f.read()
    except OSError:
        return r
    phases = {k["phase"]: v for k, v in
              _samples(text, "gateway_setup_seconds_total")}
    compile_s = _samples(text, "gateway_compile_seconds_total")
    if not phases:
        return r  # a side without the spans
    r["phases"] = phases
    r["pre_main_s"] = setup["listening_s"] - sum(
        phases.get(p, 0.0) for p in ("runtime", "weights", "batcher",
                                     "gateway"))
    r["compile_open_s"] = sum(v for _, v in compile_s)
    try:
        with open(os.path.join(log_dir, "probe.json")) as f:
            probe = json.load(f)
    except (OSError, ValueError):
        probe = {}
    r["probe"] = probe
    before = sum(probe.get("compile_in", {}).values())
    r["warm_compile_s"] = r["compile_open_s"] - before
    r["warm_rest_s"] = setup["warm_s"] - r["warm_compile_s"]
    r["remainder_s"] = (setup["setup_s"] - setup["listening_s"]
                        - setup["warm_s"] - setup["ramp_s"])
    programs: dict = {}
    for labels, v in compile_s:
        programs.setdefault(labels["program"], {})[labels["stage"]] = v
    for labels, v in _samples(text, "gateway_compiles_total"):
        programs.setdefault(labels["program"], {})[labels["cache"]] = v
    for labels, v in _samples(text, "gateway_executables_total"):
        programs.setdefault(labels["program"], {})[labels["source"]] = v
    top = sorted(programs.items(), key=lambda kv: -sum(
        kv[1].get(s, 0.0) for s in ("trace", "lower", "backend", "load")))
    r["top_programs"] = top[:5]
    r["programs"] = len(programs)
    for name, count in (("misses", "miss"), ("hits", "hit"),
                        ("built", "built"), ("loaded", "loaded")):
        r[name] = sum(p.get(count, 0) for p in programs.values())
    r["stages"] = {
        stage: sum(p.get(stage, 0.0) for p in programs.values())
        for stage in ("trace", "lower", "backend", "load")
    }
    try:
        with open(os.path.join(log_dir, "metrics_close.txt")) as f:
            after = _samples(f.read(), "gateway_compile_seconds_total")
    except OSError:
        after = []
    opened = {(k["program"], k["stage"]): v for k, v in compile_s}
    r["in_window"] = {
        f"{k['program']}/{k['stage']}": v - opened.get(
            (k["program"], k["stage"]), 0.0)
        for k, v in after
        if v != opened.get((k["program"], k["stage"]), 0.0)
    }
    return r


def main() -> int:
    if sys.argv[1] == "--one":
        return one(*sys.argv[2:7])
    args, seconds = sys.argv[1:], SECONDS
    if args[0] == "--seconds":
        seconds, args = args[1], args[2:]
    tag, specs = args[0], args[1:]
    out = os.path.join(ROOT, "chiprun_out", tag)
    os.makedirs(out, exist_ok=True)
    rc_all = 0
    for n, spec in enumerate(specs):
        side, cell, seed, trace = spec.split(":")
        root = ROOTS[side]
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one", root, cell,
             seed, trace, seconds], capture_output=True, text=True)
        lines = p.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1])
        except (ValueError, IndexError):
            result = None
        log_dir = os.path.join(root, "chiprun_out", "benchmark",
                               f"{cell}.s{seed}.t{trace}")
        rec = {"n": n, "side": side, "cell": cell, "seed": int(seed),
               "trace": int(trace), "rc": p.returncode}
        if result is None:
            rc_all = 1
            rec["stderr"] = p.stderr[-3000:]
        else:
            rec["correct"] = result["correct"]
            rec["bad_checks"] = [k for k, v in result["checks"].items()
                                 if not v]
            rec["metrics"] = {k: v["value"]
                              for k, v in result["metrics"].items()}
            rec["row"] = row(result, log_dir)
            rec["device"] = result["device"]
        for name in ("server.log", "metrics_open.txt", "metrics_close.txt",
                     "probe.json"):
            src = os.path.join(log_dir, name)
            if os.path.exists(src):
                with open(src, "rb") as f, open(os.path.join(
                        out, f"{n}.{side}.{cell}.{name}"), "wb") as g:
                    g.write(f.read())
        with open(os.path.join(out, "setup.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec)[:4000], flush=True)
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
