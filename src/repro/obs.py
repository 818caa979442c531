"""Compile counters of the process, from ``jax.monitoring``.

Importing ``repro`` registers the listeners once; :func:`snapshot` returns
the totals since then, and a caller that wants the compiles of one stretch
of work takes the difference of two snapshots.  The counts are
process-wide because JAX compiles process-wide: every jitted program of
every caller lands here.

Keys of a snapshot (``_n`` a count of events, ``_s`` their seconds):

* ``trace_n``/``trace_s``: jaxprs traced (``jaxpr_trace_duration``);
* ``lower_n``/``lower_s``: jaxprs lowered to MLIR modules;
* ``backend_compile_n``/``backend_compile_s``: executables obtained from
  the backend.  JAX records this event around its persistent-cache lookup
  as well, so it counts loads from the cache besides fresh compiles, and
  its seconds include the cache's retrieval time;
* ``cache_hits``: of those, the executables loaded from the persistent
  compilation cache;
* ``cache_retrieval_s``: seconds spent reading them.

Nothing here is called while a program runs: the listeners fire only
while JAX traces, lowers or compiles.
"""
from __future__ import annotations

import threading

from jax import monitoring

_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_CACHE_HITS = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

_lock = threading.Lock()
_totals = dict.fromkeys(
    [f"{k}_{u}" for k in _DURATIONS.values() for u in ("n", "s")]
    + ["cache_hits", "cache_retrieval_s"], 0)


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == _CACHE_RETRIEVAL:
        with _lock:
            _totals["cache_retrieval_s"] += seconds
        return
    key = _DURATIONS.get(event)
    if key is not None:
        with _lock:
            _totals[key + "_n"] += 1
            _totals[key + "_s"] += seconds


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HITS:
        with _lock:
            _totals["cache_hits"] += 1


def snapshot() -> dict:
    """The compile totals of the process since ``repro`` was imported."""
    with _lock:
        return dict(_totals)


monitoring.register_event_duration_secs_listener(_on_duration)
monitoring.register_event_listener(_on_event)
