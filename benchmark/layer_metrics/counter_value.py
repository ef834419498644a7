"""A sum of ``/metrics`` counters where the window opens (``at="open"``:
what start-up left in them) or their growth across it (``at="window"``).
A counter is ``[family, label-substring]``, as in ``counter_ratio``.

Nothing where a family's ``# TYPE`` line is missing from the text: that
is a program without the family, which ``server.metric`` would read as
0, and 0 would be false there. A family that is present and did not
move reads 0."""

import re

from server import metric


def _declared(text: str, family: str) -> bool:
    return re.search(rf"^# TYPE {re.escape(family)} ", text, re.M) is not None


def _sum(text: str, counters) -> float:
    return sum(metric(text, name, labels) for name, labels in counters)


def read(run, counters, at: str):
    texts = ([run.metrics_before] if at == "open"
             else [run.metrics_before, run.metrics_after])
    if not all(_declared(t, name) for t in texts for name, _ in counters):
        return None
    opened = _sum(run.metrics_before, counters)
    return opened if at == "open" else _sum(run.metrics_after, counters) - opened
