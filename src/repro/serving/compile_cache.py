"""Persistent XLA compilation cache wiring + disk-hit accounting.

Three small, process-global facilities behind the executor's AOT compile
boundary:

* :func:`cache_dir` is the one rule for where the cache lives:
  ``$JAX_COMPILATION_CACHE_DIR`` when it is set (no code here names another
  path then), otherwise the fixed ``.jax_cache`` directory at the root of
  the checkout.  A fixed path matters because the directory is part of
  what a later process must find again: a temporary or per-run name never
  hits.
* :func:`configure_persistent_cache` points ``jax.config`` at that
  directory so a redeployed replica's warmup re-loads yesterday's
  executables from disk instead of paying fresh XLA compiles.  JAX's own
  defaults only persist compiles slower than 1s — far above the small
  serving shapes here — so the engine defaults both persistence
  thresholds to "persist everything".
* :func:`disk_cache_hits` counts compiles that were served from that
  cache, via JAX's ``jax.monitoring`` event stream.  The executor
  snapshots this counter across each ``lower().compile()`` call to label
  the compile ``source="disk"`` vs ``"fresh"`` — XLA offers no per-call
  return channel for "this came from the cache".

Both are process-global because the underlying state is: ``jax.config``
flags and the monitoring listener registry apply to every compile in the
process, not to one engine instance.
"""

from __future__ import annotations

import os
import pathlib
import threading

import jax
from jax import monitoring
from jax._src import compilation_cache as _jax_compilation_cache

#: monitoring event XLA's compiler records on a persistent-cache read hit
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: the environment variable that places the cache from outside
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: where the cache lives when the environment does not say: the checkout root
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

_lock = threading.Lock()
_disk_hits = 0
_listening = False


def _on_event(event: str, **kwargs) -> None:
    global _disk_hits
    if event == CACHE_HIT_EVENT:
        with _lock:
            _disk_hits += 1


def _ensure_listener() -> None:
    # register exactly once per process; the listener registry has no
    # dedup, so a double registration would double-count every hit
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    monitoring.register_event_listener(_on_event)


def disk_cache_hits() -> int:
    """Process-wide count of XLA compiles served from the persistent
    compilation cache (always 0 when no cache dir is configured).

    First call registers the monitoring listener, so take a baseline
    reading *before* the compile being classified.
    """
    _ensure_listener()
    with _lock:
        return _disk_hits


def cache_dir() -> str:
    """The persistent compilation cache directory: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed in-checkout :data:`DEFAULT_CACHE_DIR`."""
    return os.environ.get(CACHE_DIR_ENV) or str(DEFAULT_CACHE_DIR)


def configure_persistent_cache() -> str:
    """Enable the on-disk XLA compilation cache at :func:`cache_dir`;
    returns the directory.

    The dir is created on first write and is safe to share across
    processes and boots — that sharing is the point: entries are keyed by
    the lowered computation + compile options + jax/XLA versions, so the
    second boot of an identical engine turns every warmup compile into a
    disk hit.

    Both ``jax_persistent_cache_*`` thresholds are set to persist
    everything (-1 bytes / 0.0 s): serving-bucket programs at ~10 NFE can
    compile in well under JAX's 1s default threshold, which would silently
    persist nothing.

    Safe to call after compiles have already run: JAX latches its cache
    handle at the first compile of the process (``_initialize_cache`` is
    once-only), so this resets that latch to pick up the new dir.
    """
    _ensure_listener()  # count disk hits from the very first compile on
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # un-latch jax's once-per-process cache init: if any compile ran before
    # this call (engine build, bench baseline, test setup), the cache handle
    # was initialized to "no dir" and every later compile would silently
    # skip the disk
    _jax_compilation_cache.reset_cache()
    return path
