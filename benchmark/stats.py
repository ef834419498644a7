"""The arithmetic every metric shares: percentiles and fixed draws.

Kept apart so that ``benchmark/tests`` can check it without a server.
"""

from __future__ import annotations

import math
import random


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default); None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def window_tokens(run) -> tuple[float, bool]:
    """Output tokens the server emitted inside the window, and whether
    that agrees with its per-generation summaries.

    Counting only generations that *finished* in the window moves in
    steps of a whole generation (64 to 256 tokens of a few thousand), so
    the count is taken per token: ``gateway_tbt_seconds_count`` grows by
    one for every output token past a generation's first, read from
    ``/metrics`` at the window's two edges, plus one first token for each
    generation that finished inside. The summaries' own ``new_tokens``
    must agree to within what can be in flight at the two edges."""
    from server import metric

    name = "gateway_tbt_seconds_count"
    tokens = (metric(run.metrics_after, name) - metric(run.metrics_before, name)
              + len(run.summaries))
    finished = sum(s["new_tokens"] for s in run.summaries)
    in_flight = run.slots * max(
        [s["new_tokens"] for s in run.summaries] or [0])
    return tokens, abs(tokens - finished) <= in_flight


def log_uniform_quantiles(lo: float, hi: float, n: int) -> list[int]:
    """``n`` whole numbers at the mid-quantiles of a log-uniform law on
    [lo, hi]: the same set whatever the seed, which then only orders it."""
    return [
        round(lo * (hi / lo) ** ((i + 0.5) / n)) for i in range(n)
    ]


def uniform_steps(lo: int, hi: int, n: int) -> list[int]:
    """``n`` whole numbers evenly spaced over [lo, hi], ends included."""
    if n == 1:
        return [round((lo + hi) / 2)]
    return [round(lo + (hi - lo) * i / (n - 1)) for i in range(n)]


def exponential_gaps(rate_per_s: float, n: int) -> list[float]:
    """``n`` inter-arrival gaps of a Poisson process of ``rate_per_s``,
    drawn from a FIXED stream: every seed gets the same multiset of gaps
    (so the same offered load to the last request) in its own order."""
    fixed = random.Random(20250925)
    return [fixed.expovariate(rate_per_s) for _ in range(n)]


def shuffled(items, rng: random.Random) -> list:
    out = list(items)
    rng.shuffle(out)
    return out


_WORDS = (
    "panel answer question reason evidence claim compare because "
    "history market river engine council harvest signal theory policy "
    "village measure account travel winter letter bridge forest island"
).split()


def filler_text(n_bytes: int, rng: random.Random, nonce: str) -> str:
    """ASCII text of exactly ``n_bytes`` bytes that starts with ``nonce``
    (so that no two prompts share a first cache page)."""
    parts = [nonce]
    size = len(nonce)
    while size < n_bytes:
        w = " " + rng.choice(_WORDS)
        parts.append(w)
        size += len(w)
    return "".join(parts)[:n_bytes].ljust(n_bytes, "?")
