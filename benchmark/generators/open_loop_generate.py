"""Open loop: ``/v1/generate`` requests sent on a schedule.

Independent users do not wait for each other, so requests go out when
they are due whether or not earlier ones have finished, and each is
timed from the instant it was due. The schedule is a Poisson process of
a fixed rate. Every seed gets the same multiset of gaps, prompt lengths
and output lengths, each in its own order, so the offered work is the
same to the byte. The loop runs ``ramp_s`` before the window opens, on a
smaller draw of its own.
"""

from __future__ import annotations

import asyncio
import random
import time

from stats import (
    exponential_gaps,
    filler_text,
    log_uniform_quantiles,
    percentile,
    shuffled,
    window_tokens,
)

END_TO_END = ("tokens_per_s", "request_p95_ms")


def _segment(traffic: dict, rng: random.Random, span: float, tag: str,
             offset: float) -> list[dict]:
    """``rate x span`` requests whose gaps fill ``span`` exactly."""
    n = max(1, round(traffic["rate_per_s"] * span))
    gaps = shuffled(exponential_gaps(traffic["rate_per_s"], n), rng)
    scale = span / sum(gaps)
    prompts = shuffled(log_uniform_quantiles(*traffic["prompt_bytes"], n), rng)
    outputs = shuffled(log_uniform_quantiles(*traffic["max_new_tokens"], n), rng)
    due, out = offset, []
    for i in range(n):
        due += gaps[i] * scale
        out.append({
            "due": due,
            "body": {
                "prompt": filler_text(prompts[i], rng, f"[{tag}.{i}]"),
                "max_new_tokens": outputs[i],
                "seed": rng.getrandbits(31),
                **traffic["payload"],
            },
        })
    return out


def schedule(traffic: dict, seed: int, seconds: float) -> list[dict]:
    """The requests of one run: due offset from the loop's start (s),
    prompt and sampling. Pure, so that the tests can check it. The ramp
    and the window are drawn apart, so that every seed puts the same
    multiset of requests *inside the window*, the last of them due just
    as it shuts."""
    rng = random.Random(seed)
    ramp = _segment(traffic, rng, traffic["ramp_s"], f"{seed:x}r", 0.0)
    # the ramp's last request is due as the window opens: keep it outside
    ramp[-1]["due"] -= 1e-3
    return ramp + _segment(
        traffic, rng, seconds, f"{seed:x}w", float(traffic["ramp_s"]))


async def run(ctx, traffic: dict, seed: int, seconds: float) -> list[dict]:
    plan = schedule(traffic, seed, seconds)
    records: list[dict] = []
    start = time.monotonic() + 0.05

    async def one(item: dict) -> None:
        due = start + item["due"]
        await asyncio.sleep(max(0.0, due - time.monotonic()))
        r = await ctx.post("/v1/generate", item["body"],
                           timeout=traffic["tail_s"] + 60)
        meta = r.doc.get("meta") or {}
        records.append({
            "due": due, "t_done": r.t_done, "status": r.status,
            "ok": r.status == 200 and r.doc.get("num_tokens", 0) > 0,
            "error": r.error,
            "late_s": r.t_sent - due if r.t_sent else None,
            "first_s": (r.t_first - due) if r.t_first else None,
            "seconds": r.t_done - due,
            "num_tokens": r.doc.get("num_tokens", 0) if r.status == 200 else 0,
            "token_events": r.events,
            "queue_wait_s": (meta.get("hops") or {}).get("admission_wait"),
        })

    tasks = [asyncio.ensure_future(one(item)) for item in plan]
    await asyncio.sleep(max(0.0, start + traffic["ramp_s"] - time.monotonic()))
    await ctx.open_window()
    await asyncio.sleep(max(0.0, ctx.window.t0 + seconds - time.monotonic()))
    await ctx.close_window()
    # Requests due inside the window run to their end, or to the limit.
    done, pending = await asyncio.wait(tasks, timeout=traffic["tail_s"])
    for t in pending:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    for t, item in zip(tasks, plan):
        if t in pending:
            records.append({
                "due": start + item["due"], "t_done": time.monotonic(),
                "status": 0, "ok": False, "error": "unfinished at the limit",
                "late_s": None, "first_s": None, "num_tokens": 0,
                "seconds": time.monotonic() - (start + item["due"]),
                "token_events": 0, "queue_wait_s": None,
            })
    return records


def reduce(run) -> dict:
    window = run.window
    due_inside = [r for r in run.records if window.t0 <= r["due"] < window.t1]
    good = [r["seconds"] for r in due_inside if r["ok"]]
    # A failed or shed request counts as the worst one.
    worst = max([r["seconds"] for r in due_inside], default=0.0)
    latencies = good + [worst] * (len(due_inside) - len(good))
    tokens, agree = window_tokens(run)
    p95 = percentile(latencies, 95)
    mid = (window.t0 + window.t1) / 2
    halves = [
        percentile([r["seconds"] * 1000.0 for r in due_inside
                    if r["ok"] and (r["due"] < mid) == first], 50)
        for first in (True, False)
    ]
    return {
        # a queue that grows through the window shows as a later half
        # slower than the earlier one (read by the rate sweep, not a metric)
        "halves_p50_ms": halves,
        "attempted": len(due_inside),
        "failed": len(due_inside) - len(good),
        "errors": [r["error"] for r in due_inside if not r["ok"]][:3],
        "token_counts_agree": agree,
        "tokens_per_s": tokens / window.seconds,
        "request_p95_ms": None if p95 is None else p95 * 1000.0,
    }
