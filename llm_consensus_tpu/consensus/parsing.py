"""Evaluation-response parsing as pure, unit-testable functions.

Parity target: the reference parses the judge's reply inline in the actor
handler (``src/main.rs:139-153``): split on newlines, drop empty lines, take
the first line with all spaces removed, map ``Good``/``NeedsRefinement``;
anything else logs an error and counts as ``NeedsRefinement`` (SURVEY.md §5
quirk #4). Remaining lines (joined with blank lines) are the reasoning.
"""

from __future__ import annotations

import logging

from llm_consensus_tpu.consensus.messages import Feedback

log = logging.getLogger(__name__)


def loggable(text: str) -> str:
    """Model text for a log record. A byte-level tokenizer decodes
    invalid UTF-8 to lone surrogates (``errors="surrogateescape"``, kept:
    it is what makes decode reversible), and a record that holds one
    cannot be encoded: pytest-xdist's workers die shipping the captured
    log (ROADMAP C1), and a strict UTF-8 log handler raises. Escaped
    here (``\\udcXX``); the text itself goes on as it is."""
    return text.encode("utf-8", "backslashreplace").decode("utf-8")


def parse_evaluation(text: str) -> tuple[Feedback, str]:
    """Parse a judge's raw reply into (verdict, reasoning).

    Mirrors reference ``src/main.rs:139-153``: first non-empty line,
    space-stripped, must be exactly ``Good`` or ``NeedsRefinement``; an
    unrecognized verdict is logged and treated as ``NeedsRefinement``.
    An entirely empty reply is likewise ``NeedsRefinement``.
    """
    lines = [ln for ln in text.split("\n") if ln != ""]
    if not lines:
        log.error("Empty response from EvaluateAnswer")
        return Feedback.NEEDS_REFINEMENT, ""
    verdict_raw = lines[0].replace(" ", "")
    reasoning = "\n\n".join(lines[1:])
    if verdict_raw == "Good":
        return Feedback.GOOD, reasoning
    if verdict_raw == "NeedsRefinement":
        return Feedback.NEEDS_REFINEMENT, reasoning
    log.error("Unexpected response from EvaluateAnswer: %s", loggable(text))
    return Feedback.NEEDS_REFINEMENT, reasoning
