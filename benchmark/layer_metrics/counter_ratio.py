"""The growth of some ``/metrics`` counters across the window over the
growth of others, or over the output tokens emitted inside it
(``stats.window_tokens``). A counter is ``[family, label-substring]``."""

from server import metric
from stats import window_tokens


def _delta(run, counters) -> float:
    return sum(
        metric(run.metrics_after, name, labels)
        - metric(run.metrics_before, name, labels)
        for name, labels in counters
    )


def read(run, over, per="output_tokens", scale: float = 1.0):
    bottom = (
        window_tokens(run)[0] if per == "output_tokens" else _delta(run, per)
    )
    if not bottom:
        return None
    return scale * _delta(run, over) / bottom
