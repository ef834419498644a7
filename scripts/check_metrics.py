#!/usr/bin/env python
"""Metrics-drift gate (PR 5): the canonical surface must stay canonical.

Three invariants, enforced in CI (scripts/ci_tier1.sh) and by a tier-1
test:

1. Every metric family name REFERENCED by the serving stack
   (continuous batcher, batch scheduler, offload tier, gateway,
   admission, coordinator) — i.e. every string literal passed to
   ``.counter( / .gauge( / .histogram( / .get(`` — must be DECLARED in
   ``llm_consensus_tpu/server/metrics.py`` (module-level family or the
   ``INSTANCE_FAMILIES`` manifest for per-instance-registry families).
2. Every declared family must appear (backticked) in the README's
   "### Observability" table.
3. Nothing in the README observability table claims a family that no
   longer exists.

Imports only ``llm_consensus_tpu.server.metrics`` (stdlib-only by
contract) — never jax — so this runs anywhere in < 1 s.

``--table`` prints the markdown rows for the README table (name, kind,
help) to regenerate it after adding a family.
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

# Files whose metric references must resolve to declared families.
SCANNED = (
    "llm_consensus_tpu/serving/continuous.py",
    "llm_consensus_tpu/serving/scheduler.py",
    "llm_consensus_tpu/serving/offload.py",
    "llm_consensus_tpu/serving/flight.py",
    "llm_consensus_tpu/serving/fleet.py",
    "llm_consensus_tpu/serving/fleet_control.py",
    "llm_consensus_tpu/serving/control.py",
    "llm_consensus_tpu/serving/disagg.py",
    "llm_consensus_tpu/serving/remote_store.py",
    "llm_consensus_tpu/serving/modelset.py",
    "llm_consensus_tpu/serving/vocab_align.py",
    "llm_consensus_tpu/server/gateway.py",
    "llm_consensus_tpu/server/admission.py",
    "llm_consensus_tpu/consensus/coordinator.py",
)

# A family registration with a literal name — reg.counter("name", ...)
# / _REG.histogram(\n    "name", ...) — or a registry lookup; the .get
# pattern is anchored to registry-shaped receivers so plain dict .get
# calls don't count.
_REF = re.compile(
    r"\.(?:counter|gauge|histogram)\(\s*[\"']([A-Za-z_][A-Za-z0-9_]*)[\"']"
)
_REF_GET = re.compile(
    r"[A-Za-z_]*(?:REG(?:ISTRY)?|[Rr]egistry)\.get\("
    r"\s*[\"']([A-Za-z_][A-Za-z0-9_]*)[\"']"
)
_BACKTICKED = re.compile(r"`([A-Za-z_][A-Za-z0-9_]*)`")


def declared_families() -> dict[str, tuple[str, str]]:
    """name -> (kind, help) for every canonical family."""
    from llm_consensus_tpu.server import metrics as M

    out: dict[str, tuple[str, str]] = {}
    for name, fam in M.REGISTRY._families.items():
        out[name] = (fam.kind, fam.help)
    for name, kind in M.INSTANCE_FAMILIES.items():
        out.setdefault(name, (kind, "(per-instance registry family)"))
    return out


def referenced_names() -> dict[str, list[str]]:
    """name -> [files referencing it]."""
    refs: dict[str, list[str]] = {}
    for rel in SCANNED:
        text = (ROOT / rel).read_text()
        for name in _REF.findall(text) + _REF_GET.findall(text):
            refs.setdefault(name, []).append(rel)
    return refs


def readme_table_names(readme: Path) -> set[str]:
    text = readme.read_text()
    m = re.search(
        r"^### Observability$(.*?)(?=^#{1,3} )", text, re.M | re.S
    )
    if not m:
        return set()
    return set(_BACKTICKED.findall(m.group(1)))


def main(argv: list[str]) -> int:
    declared = declared_families()
    if "--table" in argv:
        for name in sorted(declared):
            kind, help_ = declared[name]
            print(f"| `{name}` | {kind} | {help_} |")
        return 0
    refs = referenced_names()
    readme = readme_table_names(ROOT / "README.md")
    failures: list[str] = []
    for name, files in sorted(refs.items()):
        if name not in declared:
            failures.append(
                f"referenced but not declared in server/metrics.py: "
                f"{name!r} (from {', '.join(sorted(set(files)))})"
            )
    if not readme:
        failures.append(
            "README.md has no '### Observability' section (or it is "
            "empty) — the metrics table must live there"
        )
    for name in sorted(declared):
        if name not in readme:
            failures.append(
                f"declared but missing from the README observability "
                f"table: {name!r}"
            )
    for name in sorted(readme - set(declared)):
        # Only flag things that LOOK like metric families: the section
        # also backticks endpoints, config knobs, and module paths.
        if re.search(
            r"_(total|seconds|bytes|size|depth|inflight|rounds|"
            r"occupancy|waiting|slots|second)$",
            name,
        ):
            failures.append(
                f"README observability table names an undeclared "
                f"family: {name!r}"
            )
    if failures:
        print("METRICS DRIFT:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        print(
            f"\n{len(failures)} problem(s). Declare families in "
            "llm_consensus_tpu/server/metrics.py (module-level or "
            "INSTANCE_FAMILIES) and document them in README "
            "'### Observability' (scripts/check_metrics.py --table "
            "prints the rows).",
            file=sys.stderr,
        )
        return 1
    print(
        f"metrics surface consistent: {len(declared)} declared, "
        f"{len(refs)} referenced, {len(readme)} documented tokens"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
