"""Process-wide counters — the PR 6 compat surface of the typed
registry.

PR 9 (pamon) replaced this module's private counter dict with
`telemetry.registry.Registry` (typed counters/gauges/histograms behind
ONE shared lock); the functions here keep their exact PR 6 signatures
and semantics so every existing call site and test holds:

* ``bump``/``get``/``snapshot``/``reset`` operate on the registry's
  COUNTERS (``snapshot`` returns the flat name->int dict it always
  did; labeled counters are out of scope of this view — read them via
  ``registry().snapshot()``).
* Counters are always on (a guarded int increment); the ``PA_METRICS``
  kill switch gates the record/event layer only, and the new ``PA_MON``
  switch gates only the histogram/gauge instrumentation — neither
  reaches these.
* The thread-safety fix rides along: counter increments, the record
  history ring (record.py), and the service worker's metric updates
  all serialize on `registry().lock` — previously this module and
  record.py carried separate locks and the per-record event lists were
  appended without one (hammer-tested in tests/test_pamon.py).

Counter namespaces in use: see `telemetry.registry.CATALOG` (the
reviewed metric surface, machine-checked against the
docs/observability.md catalog table).
"""
from __future__ import annotations

import threading
from typing import Dict, Optional

from .registry import registry

__all__ = [
    "bump",
    "whole_us",
    "get",
    "snapshot",
    "reset",
    "install_jax_cache_listeners",
]


def bump(name: str, n: int = 1) -> int:
    """Increment counter ``name`` by ``n`` and return the new value."""
    return registry().counter(name).inc(n)


def whole_us(seconds: float) -> int:
    """A stretch of time as the ``*_us`` counters keep it: whole
    microseconds, never negative."""
    return int(round(1e6 * max(0.0, seconds)))


def get(name: str) -> int:
    return registry().counter_value(name)


def snapshot(prefix: Optional[str] = None) -> Dict[str, int]:
    """A copy of the current (unlabeled) counters, optionally filtered
    by prefix — the flat PR 6 view."""
    snap = registry().snapshot(prefix)
    return {k: v for k, v in snap["counters"].items() if "{" not in k}


def reset(prefix: Optional[str] = None) -> None:
    """Zero the registry (tests); with ``prefix``, only that namespace.
    Resets EVERY metric kind under the prefix, not just counters — the
    PR 6 semantics generalized."""
    registry().reset(prefix)


_jax_listeners_installed = False

#: jax.monitoring event names -> our counters (jax 0.9.0: both arrive
#: via `record_event`; a miss is recorded when the compiled executable
#: is WRITTEN to the cache, so compiles under the compile-time floor
#: count as neither).
_JAX_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "persistent_cache.hit",
    "/jax/compilation_cache/cache_misses": "persistent_cache.miss",
}

#: jax.monitoring events (jax 0.9.0) -> the ``compile.*_us`` counters:
#: the TIME SPANS JAX reports (`record_event_time_span`) around the
#: tracing of a jitted function to a jaxpr (`pjit.py`, `pxla.py`), the
#: lowering of a jaxpr to an MLIR module (`pxla.py`) and the backend's
#: compilation (`pxla.py`, around `compile_or_get_cached`), and the
#: DURATION it reports, inside that last one, for the retrieval of an
#: executable from the persistent cache (`compiler.py`, on a hit only).
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_JAX_SPAN_COUNTERS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace_us",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower_us",
    _BACKEND_EVENT: "compile.backend_us",
}


def install_jax_cache_listeners() -> bool:
    """Bridge JAX's monitoring events into the registry: the
    persistent-compilation-cache events into
    ``persistent_cache.{hit,miss}``, and what JAX reports around
    tracing, lowering to MLIR, backend compilation and a cache retrieval
    into ``compile.{trace,lower,backend,cache_load}_us`` (whole
    microseconds, summed over the process) with ``compile.programs``
    (backend compile events, loads included). JAX's spans NEST (a jit
    traced inside a jit reports inside the outer trace, a retrieval
    inside the backend event that made it): each counter takes a span's
    SELF time, what no span inside it has counted already, so that the
    four add up to the time spent. Idempotent (a second registration
    would double-count); returns True."""
    global _jax_listeners_installed
    if _jax_listeners_installed:
        return True
    _jax_listeners_installed = True
    import jax.monitoring as jm

    def _on_event(event: str, **kw) -> None:
        name = _JAX_EVENT_COUNTERS.get(event)
        if name:
            bump(name)

    # a compile runs on the thread that asked for it, and so do its
    # events, each as it ENDS: ``mine.spans`` holds the disjoint
    # ``(start, end)`` counted so far on this thread, in time order (one
    # entry a top-level event: as many as the process compiles programs),
    # ``mine.loaded`` the retrieval waiting for its backend event
    mine = threading.local()

    def _on_load(event: str, secs: float, **kw) -> None:
        if event == _CACHE_LOAD_EVENT:
            mine.loaded = getattr(mine, "loaded", 0.0) + secs
            bump("compile.cache_load_us", whole_us(secs))

    def _on_span(event: str, start: float, end: float, **kw) -> None:
        name = _JAX_SPAN_COUNTERS.get(event)
        if name is None:
            return
        spans = mine.__dict__.setdefault("spans", [])
        secs = end - start
        while spans and spans[-1][0] >= start:  # ended inside this one
            s, e = spans.pop()
            secs -= e - s
        spans.append((start, end))
        if event == _BACKEND_EVENT:
            secs -= getattr(mine, "loaded", 0.0)
            mine.loaded = 0.0
            bump("compile.programs")
        bump(name, whole_us(secs))

    jm.register_event_listener(_on_event)
    jm.register_event_duration_secs_listener(_on_load)
    jm.register_event_time_span_listener(_on_span)
    return True
