"""Persistent XLA compilation cache.

A cold start compiles every serving program; a persistent cache makes the
next start of the same code read them back. JAX keys an entry on the
cache directory's path among other things, so the directory must not
move: it is wherever ``JAX_COMPILATION_CACHE_DIR`` says — JAX reads that
variable itself and this module then sets nothing — and otherwise a fixed
path inside the checkout. Never ``$HOME``, a temp name, a pid or a time.
"""

from __future__ import annotations

import logging
import os
from pathlib import Path

log = logging.getLogger(__name__)

_CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent compile cache on (idempotent); returns the
    directory in use. Every entry point that compiles for the device —
    the CLI, ``chip_smoke.py``'s children — calls this one
    function before its first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        log.info("compile cache: %s (JAX_COMPILATION_CACHE_DIR)", placed)
        return placed
    import jax

    cache_dir = str(_CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    log.info("compile cache: %s", cache_dir)
    return cache_dir
