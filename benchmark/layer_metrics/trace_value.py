"""One number of the reduced device trace (``trace_reduce.py``), by its
dotted path; nothing where the run recorded no trace or the trace holds
no such thing."""


def read(run, path: str, scale: float = 1.0):
    node = run.trace
    for key in path.split("."):
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    return None if node is None else node * scale
