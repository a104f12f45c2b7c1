"""Persistent XLA compilation cache for the entry points.

A cold TPU round compiles for tens of seconds; the persistent cache
lets a later process with the same programs skip that.  JAX keys the
cache by the directory too, so the directory must not move between
runs: it is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads
the variable itself, so nothing is set here), and otherwise the fixed
``<repo>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Call first thing in an entry point."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
