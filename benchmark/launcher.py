#!/usr/bin/env python3
"""Start the program's own ``serve`` with a configuration's sizes.

    python3 benchmark/launcher.py <config.json> --exit-file F [serve flags]

Runs in the server child (the one process that touches the chip). It
calls ``llm_consensus_tpu.cli.main(["serve", ...])`` — the same path as
``python -m llm_consensus_tpu serve`` — after three things the plain
command line cannot do yet:

- the configuration's ``batcher`` sizes (pool pages, pages per
  sequence, sequence buckets) replace ``ContinuousConfig``'s defaults,
  because ``serve`` has no flag for them (PERF.md lists the flags whose
  arrival makes this file unnecessary);
- a device trace taken through ``X-Profile: 1`` is recorded without the
  Python tracer, which would slow the very host loop whose idle gaps
  the trace is read for;
- on the way out it writes the device's memory peak to ``--exit-file``,
  since the benchmark's parent stays off jax and the program exposes no
  such number.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sized_config(base, sizes: dict):
    """A subclass of ``ContinuousConfig`` whose defaults are ``sizes``."""
    known = {f.name: f for f in dataclasses.fields(base)}
    unknown = sorted(set(sizes) - set(known))
    if unknown:
        raise SystemExit(f"launcher: ContinuousConfig has no field {unknown}")
    fields = [
        (k, known[k].type,
         dataclasses.field(default=tuple(v) if isinstance(v, list) else v))
        for k, v in sizes.items()
    ]
    return dataclasses.make_dataclass(base.__name__, fields, bases=(base,))


def main(argv: list[str]) -> int:
    config_path, rest = argv[0], argv[1:]
    exit_file = rest[rest.index("--exit-file") + 1]
    rest = [a for i, a in enumerate(rest)
            if a != "--exit-file" and rest[i - 1] != "--exit-file"]
    with open(config_path) as f:
        config = json.load(f)
    sys.path.insert(0, ROOT)

    import jax

    from llm_consensus_tpu import cli
    from llm_consensus_tpu.serving import continuous

    continuous.ContinuousConfig = sized_config(
        continuous.ContinuousConfig, config["batcher"]
    )
    start_trace = jax.profiler.start_trace

    def start_trace_host_only(log_dir, **kw):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        return start_trace(log_dir, profiler_options=options, **kw)

    jax.profiler.start_trace = start_trace_host_only

    flags = ["--port", "0"]
    for name, value in config["serve"].items():
        flags += [f"--{name}", str(value)]
    rc = cli.main(["serve", *flags, *rest])

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    with open(exit_file, "w") as f:
        json.dump({"rc": rc, "memory_peak_bytes": max(peaks, default=0)}, f)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
