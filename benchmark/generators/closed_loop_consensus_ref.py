"""The closed panel loop, with the served logits held to a reference.

The window is ``closed_loop_consensus``'s, unchanged: the same clients,
questions and reduction. Beside it, during the ramp and so outside the
window, this loop sends the traffic file's ``reference`` requests:
greedy ``/v1/generate`` calls of the panel's three prompt sizes (two
copies of each, so that the second maps the first's pages and both ride
grouped rows) with ``"logits": n``. They run while the ramp's questions
are in flight, so fused and grouped step programs produce them, and
their replies carry the float32 logits of the first ``n`` generated
positions as the timed programs handed them to the sampler.

``reduce`` runs after the server has drained, when the chip is free: it
hands the replies to the configuration's plain reference
(``benchmark/reference/…``, named by the configuration's ``reference``
block) in a child process on the device, which regenerates the same
weights, teacher-forces the reference on the served rows' argmax and
reports how far the served logits lie from its own. A reply that is
missing, a child that fails, or a difference past the traffic file's
``tolerance`` counts as a failed request, with its numbers in
``errors``: ``correct`` is then false. A configuration without a
``reference`` block (the dry run's) sends the requests and skips the
comparison.
"""

from __future__ import annotations

import asyncio
import json
import os
import random
import subprocess
import sys
import time

from generators import closed_loop_consensus as base
from stats import filler_text

END_TO_END = base.END_TO_END
CHILD_LIMIT_S = 900


async def _references(ctx, spec: dict, seed: int) -> list[dict]:
    await asyncio.sleep(spec["at_s"])
    rng = random.Random(seed ^ 0x5EED)
    bodies = []
    for size in spec["prompt_bytes"]:
        prompt = filler_text(size, rng, f"[ref.{seed:x}.{size}]")
        bodies += [(f"{size}.{c}", prompt) for c in range(spec["copies"])]

    async def one(tag: str, prompt: str) -> dict:
        r = await ctx.post("/v1/generate", {
            "prompt": prompt, "max_new_tokens": spec["positions"],
            "temperature": 0, "logits": spec["positions"],
        })
        got = (r.doc.get("meta") or {}).get("logits") or {}
        return {
            "reference": tag, "prompt": prompt, "status": r.status,
            "error": r.error, "t_done": r.t_done, **got,
        }

    return list(await asyncio.gather(*(one(t, p) for t, p in bodies)))


async def run(ctx, traffic: dict, seed: int, seconds: float) -> list[dict]:
    refs = asyncio.ensure_future(_references(ctx, traffic["reference"], seed))
    records = await base.run(ctx, traffic, seed, seconds)
    out_dir = os.path.dirname(ctx.srv.log_path)
    # Reference replies end before the window opens, so the base
    # reduction, which keeps what finished inside it, never sees them.
    return records + [{**r, "out_dir": out_dir} for r in await refs]


def _judge(run, replies: list[dict]) -> list[str]:
    """One line for every reference request that failed; none if all
    lie within the tolerance."""
    spec, tol = run.config["reference"], run.traffic["reference"]["tolerance"]
    bad = [
        f"reference {r['reference']}: status {r['status']} {r['error']}, "
        f"{r.get('positions', 0)} positions of logits"
        for r in replies if r["status"] != 200 or not r.get("b64")
    ]
    good = [r for r in replies if r["status"] == 200 and r.get("b64")]
    if not good:
        return bad or ["no reference request was sent"]
    out_dir = good[0]["out_dir"]
    job, verdict = (os.path.join(out_dir, n)
                    for n in ("reference_job.json", "reference.json"))
    serve = run.config["serve"]
    with open(job, "w") as f:
        json.dump({
            "model": serve["model"], "layers": serve.get("layers", 0),
            "quant": serve["quant"],
            "requests": [
                {"tag": r["reference"], "prompt": r["prompt"],
                 "positions": r["positions"], "vocab": r["vocab"],
                 "b64": r["b64"]} for r in good
            ],
        }, f)
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    root = os.path.dirname(bench_dir)
    env = dict(os.environ)
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    t0 = time.monotonic()
    try:
        child = subprocess.run(
            [sys.executable, os.path.join(bench_dir, spec["script"]),
             "--in", job, "--out", verdict],
            cwd=root, env=env, capture_output=True, text=True,
            timeout=CHILD_LIMIT_S,
        )
    except subprocess.TimeoutExpired:
        return bad + [f"reference child: no end after {CHILD_LIMIT_S} s"]
    os.remove(job)  # the replies again, tens of MB
    if child.returncode != 0:
        return bad + [
            f"reference child: rc {child.returncode}: {child.stderr[-600:]}"]
    with open(verdict) as f:
        doc = json.load(f)
    doc["seconds"] = time.monotonic() - t0
    doc["tolerance"] = tol
    for r in doc["requests"]:
        over = [
            f"{k} {r[k]:.4g} > {limit}" for k, limit in tol.items()
            if limit is not None and r[k] > limit
        ]
        r["within_tolerance"] = not over
        if over:
            bad.append(
                f"reference {r['tag']} ({r['prompt_tokens']} prompt tokens): "
                + ", ".join(over)
                + f" (position {r['at_position']}, token {r['at_token']})")
    with open(verdict, "w") as f:
        json.dump(doc, f, indent=1)
    print("reference: " + json.dumps(doc), file=sys.stderr)
    return bad


def reduce(run) -> dict:
    replies = [r for r in run.records if "reference" in r]
    out = base.reduce(run)  # keeps what finished inside the window
    if "reference" not in run.config:
        return out
    bad = _judge(run, replies)
    out["attempted"] += len(replies)
    out["failed"] += len(bad)
    out["errors"] = (bad + out["errors"])[:6]
    return out
