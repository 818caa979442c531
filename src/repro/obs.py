"""Compile counters of the process, from ``jax.monitoring``, and the
measured-curve lookups and Monte-Carlo recombinations counted as programs
are lowered.

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
* ``cache_retrieval_s``: seconds spent reading them;
* ``lut_mxu_traced``/``lut_gather_traced``: measured-curve lookups
  (``repro.core.kernels``) put into a program through the MXU one-hot
  contraction or through the XLA gather.  ``jax.lax.platform_dependent``
  traces both implementations into every jaxpr and keeps one when the
  program is lowered for its platform, so these are counted by
  :func:`mark_lowered`, for the branch kept, as it is lowered (lookups
  of one shape in one program share a lowering) and on each eager run;
* ``recombine_table_traced``/``recombine_votes_traced``: streaming
  Monte-Carlo steps lowered with the packed-encoder table recombination
  or, past ``MAX_TABLE_BITS`` pairs, the pair-chunked votes
  (``StreamingMCMachine._chunk_acc``), counted the same way;
* ``mc_lanes_restricted_traced``: streaming Monte-Carlo steps lowered
  with restricted lanes, only the analog pairs the assignments select
  (``StreamingMCMachine._scores``), counted the same way.

Nothing here is called while a compiled program runs: the listeners and
the lowering rule fire only while JAX traces, lowers or compiles.
"""
from __future__ import annotations

import threading

from jax import monitoring
from jax.extend import core as jex_core
from jax.interpreters import ad, batching, mlir

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
    + ["cache_hits", "cache_retrieval_s", "lut_mxu_traced",
       "lut_gather_traced", "recombine_table_traced",
       "recombine_votes_traced", "mc_lanes_restricted_traced"], 0)


def _bump(key: str) -> None:
    with _lock:
        _totals[key] += 1


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
        _bump("cache_hits")


def _mark_impl(x, *, counter):
    _bump(counter)
    return x


def _mark_lowering(ctx, x, *, counter):
    _bump(counter)
    return [x]


_mark_p = jex_core.Primitive("repro_obs_mark")
_mark_p.def_impl(_mark_impl)
_mark_p.def_abstract_eval(lambda x, *, counter: x)
mlir.register_lowering(_mark_p, _mark_lowering)
batching.primitive_batchers[_mark_p] = lambda args, dims, *, counter: (
    _mark_p.bind(args[0], counter=counter), dims[0])
ad.primitive_jvps[_mark_p] = lambda primals, tangents, *, counter: (
    _mark_p.bind(primals[0], counter=counter), tangents[0])


def mark_lowered(x, counter: str):
    """``x`` unchanged; counts ``counter`` in :func:`snapshot` each time a
    program that holds this value is lowered, or it runs eagerly.  It
    lowers to nothing: the compiled program is the same without it."""
    return _mark_p.bind(x, counter=counter)


def snapshot() -> dict:
    """The compile totals of the process since ``repro`` was imported."""
    with _lock:
        return dict(_totals)


monitoring.register_event_duration_secs_listener(_on_duration)
monitoring.register_event_listener(_on_event)
