"""Process-level runtime context: the persistent compile cache, and the mesh
used by sharded decode.

`decode_step` consults the serve mesh to choose the sequence-sharded
(flash-combine) attention path; unset (the CPU test default) it runs the
purely local path.
"""
from __future__ import annotations

import pathlib

import jax

# src/repro/launch/runtime.py -> the checkout root
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]

_SERVE_MESH = None


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is what JAX already uses and
    nothing is overridden. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the directory is part of what a later
    process must find again, so it is never a tempdir, pid or time-based
    path. Call before the first compile."""
    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def set_serve_mesh(mesh) -> None:
    global _SERVE_MESH
    _SERVE_MESH = mesh


def get_serve_mesh():
    return _SERVE_MESH
