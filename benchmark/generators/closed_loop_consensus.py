"""Closed loop: each client sends ``/v1/consensus`` back to back.

A panel's callers wait for the decision before they ask again, so the
loop is closed: ``clients`` questions are in flight at all times. The
clients start one after another across ``ramp_s``, so that they do not
march in step, and the loop is running when the window opens. Every seed
draws from the same set of question lengths, in its own order, behind a
nonce that keeps any two questions off a shared cache page.
"""

from __future__ import annotations

import asyncio
import random
import time

from stats import (
    filler_text,
    percentile,
    shuffled,
    uniform_steps,
    window_tokens,
)

END_TO_END = ("tokens_per_s", "question_p50_s")


async def run(ctx, traffic: dict, seed: int, seconds: float) -> list[dict]:
    rng = random.Random(seed)
    lo, hi = traffic["question_bytes"]
    sizes = uniform_steps(lo, hi, traffic["question_sizes"])
    records: list[dict] = []
    stop = asyncio.Event()

    async def client(i: int, order: list[int], texts: random.Random) -> None:
        k = 0
        await asyncio.sleep(i * traffic["ramp_s"] / traffic["clients"])
        while not stop.is_set():
            size = order[k % len(order)]
            question = filler_text(size, texts, f"[{seed:x}.{i}.{k}]")
            k += 1
            t0 = time.monotonic()
            r = await ctx.post(
                "/v1/consensus", {"question": question, **traffic["payload"]}
            )
            ok = (
                r.status == 200
                and r.doc.get("rounds") == traffic["payload"]["max_rounds"]
                and len(r.doc.get("feedback", {}))
                == traffic["evaluations_per_round"]
            )
            records.append({
                "t_start": t0, "t_done": r.t_done, "status": r.status,
                "ok": ok, "error": r.error or ("" if ok else str(r.doc)[:200]),
                "seconds": r.t_done - t0,
            })

    tasks = [
        asyncio.ensure_future(client(
            i, shuffled(sizes, rng), random.Random(rng.getrandbits(32))
        ))
        for i in range(traffic["clients"])
    ]
    await asyncio.sleep(traffic["ramp_s"])
    await ctx.open_window()
    await asyncio.sleep(seconds)
    await ctx.close_window()
    stop.set()  # questions in flight run to their end, outside the window
    await asyncio.gather(*tasks)
    return records


def reduce(run) -> dict:
    """End-to-end numbers of one window."""
    window = run.window
    inside = [r for r in run.records if window.t0 <= r["t_done"] < window.t1]
    good = [r["seconds"] for r in inside if r["ok"]]
    tokens, agree = window_tokens(run)
    return {
        "attempted": len(inside),
        "failed": len(inside) - len(good),
        "errors": [r["error"] for r in inside if not r["ok"]][:3],
        "token_counts_agree": agree,
        "tokens_per_s": tokens / window.seconds,
        "question_p50_s": percentile(good, 50),
    }
