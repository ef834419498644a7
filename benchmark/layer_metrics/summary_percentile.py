"""A percentile, over the generations that finished inside the window,
of one field of the batcher's own per-request summary
(``/debug/requests``): times on the batcher's clock, from submit."""

from stats import percentile


def read(run, field: str, q: float, scale: float = 1.0):
    xs = [s[field] for s in run.summaries if s.get(field) is not None]
    p = percentile(xs, q)
    return None if p is None else p * scale
