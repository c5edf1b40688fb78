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

from typing import Dict, Optional

from .registry import registry

__all__ = [
    "bump",
    "get",
    "snapshot",
    "reset",
    "install_jax_cache_listeners",
]


def bump(name: str, n: int = 1) -> int:
    """Increment counter ``name`` by ``n`` and return the new value."""
    return registry().counter(name).inc(n)


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


def install_jax_cache_listeners() -> bool:
    """Bridge JAX's persistent-compilation-cache monitoring events into
    ``persistent_cache.{hit,miss}``. Idempotent (a second registration
    would double-count every hit); returns True."""
    global _jax_listeners_installed
    if _jax_listeners_installed:
        return True
    _jax_listeners_installed = True
    import jax.monitoring as jm

    def _on_event(event: str, **kw) -> None:
        name = _JAX_EVENT_COUNTERS.get(event)
        if name:
            bump(name)

    jm.register_event_listener(_on_event)
    return True
