"""A percentile of one field of the load generator's own records,
over the requests that were due inside the window."""

from stats import percentile


def read(run, field: str, q: float, scale: float = 1.0):
    w = run.window
    xs = [
        r[field] for r in run.records
        if w.t0 <= r.get("due", r.get("t_done")) < w.t1
        and r.get(field) is not None
    ]
    p = percentile(xs, q)
    return None if p is None else p * scale
