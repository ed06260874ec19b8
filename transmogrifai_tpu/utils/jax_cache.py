"""Persistent XLA compilation cache setup.

First compilation of each jitted program costs seconds to minutes; the
reference has no analog cost because Spark plans interpret immediately.
jax's persistent compilation cache lets every run after the first skip
straight to execution for unchanged program shapes. Applied once, lazily,
from the modules that first touch jax.

The rule: a cache placed from outside wins — when
``JAX_COMPILATION_CACHE_DIR`` (or ``jax.config.jax_compilation_cache_dir``)
is set, nothing is set here. Otherwise the cache lives in ONE fixed
directory inside the checkout (``CACHE_DIR``, git-ignored): the directory
is part of the cache key's life — a cache that moves never hits.
"""
from __future__ import annotations

import logging
import os
import threading
from typing import Dict

logger = logging.getLogger(__name__)

#: the in-checkout cache directory used when none is placed from outside
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_done = False

# -- compile-cache hit/miss accounting ---------------------------------------
# jax announces persistent-cache outcomes through its monitoring events
# ('/jax/compilation_cache/cache_hits' / 'cache_misses'); a listener folds
# them into plain process counters that StageProfiler.app_metrics() and
# observability.summarize() report, and that sweep spans diff to tag each
# family branch hit/miss.
_CACHE_EVENTS: Dict[str, int] = {"hits": 0, "misses": 0}
_listener_lock = threading.Lock()
_listener_done = False


def record_cache_event(hit: bool) -> None:
    """Count one compile-cache outcome (the listener's target; also the
    deterministic entry point for tests)."""
    _CACHE_EVENTS["hits" if hit else "misses"] += 1


def _install_listener() -> None:
    global _listener_done
    with _listener_lock:
        if _listener_done:
            return
        _listener_done = True
    import jax.monitoring

    def _on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            record_cache_event(True)
        elif event == "/jax/compilation_cache/cache_misses":
            record_cache_event(False)

    jax.monitoring.register_event_listener(_on_event)


def cache_stats() -> Dict[str, int]:
    """Process-wide persistent compile-cache ``{"hits": n, "misses": n}``."""
    _install_listener()
    return dict(_CACHE_EVENTS)


def ensure_compilation_cache() -> None:
    global _done
    if _done:
        return
    _done = True
    _install_listener()
    import jax
    if jax.config.jax_compilation_cache_dir:
        return  # placed from outside (environment or jax.config)
    try:
        os.makedirs(CACHE_DIR, exist_ok=True)
    except OSError as e:  # read-only checkout: cacheless is only slower
        logger.warning("compile cache directory %s is not writable (%s); "
                       "running without a persistent cache", CACHE_DIR, e)
        return
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
