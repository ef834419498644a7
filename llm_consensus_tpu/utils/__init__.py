"""Utilities: logging, tracing/profiling, deterministic RNG streams.

The reference's observability is ``log``+``env_logger`` only, and its
only timing is the REPL poll pacing (SURVEY.md §5). Here: request-scoped
span tracing with wall-clock + optional JAX profiler integration, and
RUST_LOG-convention logging setup.
"""

from llm_consensus_tpu.utils.logging import setup_logging
from llm_consensus_tpu.utils.tracing import (
    Trace,
    TraceStore,
    current_trace,
    request_span,
    trace_jax_profile,
    trace_store,
    use_trace,
)

__all__ = [
    "Trace",
    "TraceStore",
    "current_trace",
    "request_span",
    "setup_logging",
    "trace_jax_profile",
    "trace_store",
    "use_trace",
]
