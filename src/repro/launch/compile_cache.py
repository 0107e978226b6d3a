"""Persistent compilation cache for the entry points.

Only entry points (``chip_smoke.py``, ``launch/serve.py``) call
:func:`use_compile_cache`, before their first compile; importing
``repro`` never touches the cache, and the tests never enable it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path inside the checkout: the cache key includes nothing of
# the run, so a second run of the same programs finds them here
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Keep compiled programs across runs and return the cache directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as is (JAX reads
    it itself) and no other directory is set; otherwise the cache lives
    in ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
