"""Where the program keeps what it generates: under ``.cache/`` of the
checkout it runs from.

Paths are computed from this file's location, never from the working
directory, so a run reads back only what a run of the same checkout wrote.

* ``CACHE_ROOT / "rar_system"`` holds the trained-system checkpoints
  (:mod:`repro.experiments.setup`).
* ``CACHE_ROOT / "jax_compile"`` is JAX's persistent compilation cache,
  unless ``JAX_COMPILATION_CACHE_DIR`` places it elsewhere
  (:func:`enable_compile_cache`).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]     # <checkout>/src/repro/
CACHE_ROOT = CHECKOUT / ".cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses that
    directory and nothing is set here. Otherwise the cache goes to
    ``<checkout>/.cache/jax_compile``: a fixed path, since the path is
    part of what a cached entry is found by. Entry points call this
    before their first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CACHE_ROOT / "jax_compile")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
