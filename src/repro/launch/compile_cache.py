"""Where JAX keeps compiled programs between processes.

A run on the chip compiles prefill and decode programs that take seconds
to minutes each; JAX's persistent compilation cache lets the next process
in the same checkout load them instead.  Every entry point calls
:func:`enable_compile_cache` before its first compile (tests never do).
The directory is fixed, so a second run finds what the first one wrote:
``$JAX_COMPILATION_CACHE_DIR`` where it is set, and otherwise
``.jax_cache`` at the root of the checkout (ignored by git).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
