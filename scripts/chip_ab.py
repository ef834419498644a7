#!/usr/bin/env python3
"""Parent against change on one chip, in one call (stdlib only: the chip
belongs to each run's own server child).

    chiprun -- python3 scripts/chip_ab.py <tag> <side>:<cell>:<seed>:<trace> ...

``side`` is ``parent`` (``.bench_checkout/parent``: ``git archive
<parent> | tar -x -C`` there first), ``change`` (the repo root) or
``tree`` (``.bench_checkout/tree``: ``git archive $(git write-tree)``).
``trace`` 2 is a traced run that keeps its ``.xplane.pb`` for
``scripts/drain_count.py`` (this checkout's), whose line rides the record
as ``drains``; the trace itself is deleted.
Each run's result line goes to ``chiprun_out/<tag>/results.jsonl`` with
its side, its server log's "built in" lines, the entries it added to the
compile cache the runs share and ``left``: the processes a run starts
(the server, its launcher, a reference child) still alive after it,
which must be none; the server logs are copied beside it. Measures
nothing itself: ``benchmark/run.py`` does.
"""

import glob
import json
import os
import re
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOTS = {
    "parent": os.path.join(ROOT, ".bench_checkout", "parent"),
    "tree": os.path.join(ROOT, ".bench_checkout", "tree"),
    "change": ROOT,
}


def cache_listing() -> list:
    """(name, bytes, mtime) of every entry of the persistent compile
    cache the runs share, where the machine sets one."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d or not os.path.isdir(d):
        return []
    out = []
    for name in sorted(os.listdir(d)):
        st = os.stat(os.path.join(d, name))
        out.append((name, st.st_size, st.st_mtime))
    return out


def left_alive() -> list:
    """Processes of a run (server, launcher, reference child) alive."""
    time.sleep(2)
    r = subprocess.run(
        ["pgrep", "-af", r"launcher\.py|llm_consensus_tpu serve|reference/"],
        capture_output=True, text=True,
    )
    return [line for line in r.stdout.splitlines() if "pgrep" not in line]


def drains(profile_dir: str):
    """``scripts/drain_count.py`` on the trace a run kept, then the
    trace goes: it is too large to bring back."""
    found = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return None
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "drain_count.py"),
         found[-1]],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True,
    )
    shutil.rmtree(profile_dir, ignore_errors=True)
    try:
        return json.loads(r.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": r.stderr[-2000:]}


def main() -> int:
    tag, specs = sys.argv[1], sys.argv[2:]
    out = os.path.join(ROOT, "chiprun_out", tag)
    os.makedirs(out, exist_ok=True)
    rc_all = 0
    for n, spec in enumerate(specs):
        side, cell, seed, trace = spec.split(":")
        keep = ["--keep-trace"] if trace == "2" else []
        trace = "1" if keep else trace
        root = ROOTS[side]
        t0 = time.time()
        r = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", cell,
             "--seed", seed, "--seconds", "51", "--trace", trace, *keep],
            cwd=root, capture_output=True, text=True,
        )
        wall = time.time() - t0
        lines = r.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if lines else None
        except ValueError:
            result = None
        log_dir = os.path.join(
            root, "chiprun_out", "benchmark", f"{cell}.s{seed}.t{trace}"
        )
        built = []
        log_path = os.path.join(log_dir, "server.log")
        if os.path.exists(log_path):
            with open(log_path, errors="replace") as f:
                for line in f:
                    if re.search(r"built in|ompil", line):
                        built.append(line.strip()[-200:])
            shutil.copy(log_path, os.path.join(out, f"{n}.{side}.{cell}.server.log"))
        wrote = sorted(
            name.rsplit("-", 1)[0] for name, _, mtime in cache_listing()
            if mtime >= t0 and not name.endswith("-atime")
        )
        rec = {
            "t0": t0, "cache_wrote": wrote, "left": left_alive(),
            "n": n, "side": side, "cell": cell, "seed": int(seed),
            "trace": int(trace), "rc": r.returncode, "wall_s": round(wall, 1),
            "built": built, "result": result,
        }
        if keep:
            rec["drains"] = drains(os.path.join(log_dir, "profile"))
        if r.returncode != 0 or result is None:
            rc_all = 1
            rec["stderr"] = r.stderr[-3000:]
        with open(os.path.join(out, "results.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
        short = {}
        if result:
            short = {
                "correct": result.get("correct"),
                "metrics": {k: v["value"]
                            for k, v in result.get("metrics", {}).items()},
                "setup": result.get("setup"),
                "bad_checks": [k for k, v in result.get("checks", {}).items()
                               if not v],
                "layers": result.get("layers"),
            }
        print(json.dumps({"n": n, "side": side, "cell": cell, "seed": seed,
                          "trace": trace, "rc": r.returncode,
                          "wall_s": round(wall, 1), "left": rec["left"],
                          **short})[:3000],
              flush=True)
        if keep:
            print(json.dumps({"n": n, "drains": rec["drains"]}), flush=True)
    with open(os.path.join(out, "cache_listing.json"), "w") as f:
        json.dump(cache_listing(), f)
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
