"""JAX's persistent compilation cache, placed the same way by every entry
point (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``).

A run on a fresh machine starts with no compiled code, and a Pallas kernel
compiles in less than JAX's default one-second threshold for caching, so
without this every kernel and step program compiles again on every run.
The cache directory is part of where a later run looks, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself; no other
directory is set in code), else ``.jax_cache`` at the root of the checkout
(listed in ``.gitignore``).

A second listener counts real XLA compiles, on any backend: a program
loaded from the cache is not one. Recording spans (``core/tracing.py``),
each compile is also the counter ``repro.xla.compiles``.
"""

from __future__ import annotations

import os
import pathlib
import threading

from repro.core import tracing

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_counts = {name: 0 for name in _EVENTS.values()}
_compiles = {"compiles": 0, "compile_s": 0.0}
_listening = False
_counting_compiles = False
_loading = threading.local()


def _on_event(event: str, **_kwargs) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        _counts[name] += 1


def _on_duration(event: str, seconds: float, **_kwargs) -> None:
    # JAX times a load from the persistent cache as a compile too; the
    # load's own event comes first, in the same thread
    if event == _LOAD_EVENT:
        _loading.hit = True
    elif event == _COMPILE_EVENT:
        if getattr(_loading, "hit", False):
            _loading.hit = False
            return
        _compiles["compiles"] += 1
        _compiles["compile_s"] += seconds
        tracing.count("repro.xla.compiles")


def _count_compiles() -> None:
    from jax import monitoring

    global _counting_compiles
    if not _counting_compiles:
        monitoring.register_event_duration_secs_listener(_on_duration)
        _counting_compiles = True


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on for this process and return its
    directory. Call before the first compilation. Every compiled program
    is cached, however quick its compile (the threshold is lowered to 0),
    and the cache's requests, hits and writes are counted from here on
    (:func:`compile_cache_stats`). Off the TPU it only counts compiles and
    returns None: CPU compiles are cheap, and XLA:CPU warns on every entry
    it loads back."""
    import jax
    from jax import monitoring

    global _listening
    _count_compiles()
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


def compile_cache_stats() -> dict[str, float]:
    """Compilations that consulted the cache (``requests``), found their
    program there (``hits``) and wrote a new entry (``writes``); XLA
    compiles (``compiles``) and their seconds (``compile_s``), counted
    from the first call of this function or :func:`enable_compile_cache`
    on, on any backend."""
    _count_compiles()
    return {**_counts, **_compiles}
