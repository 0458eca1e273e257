"""JAX's persistent compilation cache, placed the same way by every entry
point (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``).

A run on a fresh machine starts with no compiled code, and a Pallas kernel
compiles in less than JAX's default one-second threshold for caching, so
without this every kernel and step program compiles again on every run.
The cache directory is part of where a later run looks, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself; no other
directory is set in code), else ``.jax_cache`` at the root of the checkout
(listed in ``.gitignore``).
"""

from __future__ import annotations

import os
import pathlib

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}
_counts = {name: 0 for name in _EVENTS.values()}
_listening = False


def _on_event(event: str, **_kwargs) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        _counts[name] += 1


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on for this process and return its
    directory. Call before the first compilation. Every compiled program
    is cached, however quick its compile (the threshold is lowered to 0),
    and the cache's requests, hits and writes are counted from here on
    (:func:`compile_cache_stats`). Off the TPU it does nothing and returns
    None: CPU compiles are cheap, and XLA:CPU warns on every entry it loads
    back."""
    import jax
    from jax import monitoring

    global _listening
    if jax.default_backend() != "tpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _listening:
        monitoring.register_event_listener(_on_event)
        _listening = True
    return path


def compile_cache_stats() -> dict[str, int]:
    """Compilations that consulted the cache (``requests``), found their
    program there (``hits``) and wrote a new entry (``writes``)."""
    return dict(_counts)
