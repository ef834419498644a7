"""Warm every device program a cell's traffic will use, and check that
identical greedy prompts agree, before the window opens.

The batcher jits one program per (kind, sequence bucket, last chunk or
not, rows grouped on a shared prefix or not). Real traffic reaches the
rarer of those only by chance, so a warm-up made of real traffic would
now and then leave one to compile inside the window. This walks them by
construction, for each prompt length the traffic file lists (one per
bucket the cell reaches):

1. alone on an idle server: standalone chunk programs, the unembed, and
   plain decode;
2. beside one decoding companion: the chunk rides the decode dispatch
   (fused program, last chunk and not), rows ungrouped;
3. where the cell shares prefixes, beside two identical decoding
   companions: the same with grouped rows, the grouped decode program
   and the copy of a partly shared page.

The identical companions are also the check on the outputs. Of three
identical greedy prompts sent together, the first computes the prompt
(the donor) and the other two map its pages and copy its last, partly
filled one (the mappers). The two mappers take the same path, so they
must return the same text and token count: that is what ``correct``
requires. Whether the donor agrees with them is reported and not
required: its first token comes out of a different chunk shape than
theirs, and with random weights (nearly flat logits) a rounding
difference there flips the greedy token — on the chip it does for
mistral-7b (PERF.md, Findings PR 25). Telling that from a fault needs
logits against a reference, which the repo lacks.
"""

from __future__ import annotations

import asyncio
import random

from stats import filler_text

COMPANION_HEAD_START_S = 0.3  # companions must be decoding first


class WarmFailure(Exception):
    pass


async def _generate(ctx, prompt: str, max_new_tokens: int) -> dict:
    r = await ctx.post("/v1/generate", {
        "prompt": prompt, "max_new_tokens": max_new_tokens, "temperature": 0,
    }, timeout=1200)
    if r.status != 200 or not r.doc.get("num_tokens", 0) > 0:
        raise WarmFailure(f"warm-up generate -> {r.status} {r.error} {r.doc}")
    return r.doc


async def _beside(ctx, companions: list[str], target: str, tokens: int):
    """Run ``target`` while ``companions`` decode; return their docs."""
    tasks = [asyncio.ensure_future(_generate(ctx, p, tokens))
             for p in companions]
    await asyncio.sleep(COMPANION_HEAD_START_S)
    await _generate(ctx, target, 2)
    return [await t for t in tasks]


def _same(a: dict, b: dict) -> bool:
    return (a["text"], a["num_tokens"]) == (b["text"], b["num_tokens"])


async def warm_shapes(ctx, spec: dict) -> dict:
    """Returns how many sets of identical prompts were compared, whether
    the two mappers of each agreed, and whether the donor agreed too."""
    rng = random.Random(7)  # warm-up is the same work whatever the seed
    sizes = spec["prompt_bytes"]
    n = 0

    def text(n_bytes: int) -> str:
        nonlocal n
        n += 1
        return filler_text(n_bytes, rng, f"[warm.{n}]")

    mates = {"compared": 0, "same_path_agree": True, "donor_agrees": True}

    def compare(donor: dict, first: dict, second: dict) -> None:
        mates["compared"] += 1
        mates["same_path_agree"] &= _same(first, second)
        mates["donor_agrees"] &= _same(donor, first)

    for size in sizes:
        await _generate(ctx, text(size), 2)
        tokens = size // 64 + 12  # outlasts the target's chunks
        await _beside(ctx, [text(sizes[0])], text(size), tokens)
        if spec.get("grouped"):
            mate = text(sizes[0])
            compare(*await _beside(ctx, [mate] * 3, text(size), tokens))
    if not mates["compared"]:
        mate = text(sizes[0])
        compare(*await asyncio.gather(
            *(_generate(ctx, mate, 8) for _ in range(3))))
    return mates
