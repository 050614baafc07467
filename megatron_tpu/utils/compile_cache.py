"""Where JAX's persistent compilation cache lives.

Every entry point calls `ensure_compile_cache()` before its first
compile. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself
and nothing here sets another directory. Where it is not, the cache
goes to `<checkout>/.jax_cache` (git-ignored): the directory's path is
part of every entry's key, so it is a fixed path — never one built from
a temporary name, a pid or the time — and a second run of the same
command from the same checkout finds what the first compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def ensure_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
