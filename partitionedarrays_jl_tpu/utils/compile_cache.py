"""Persistent XLA compilation cache.

The compiled one-program solvers (`make_cg_fn`, `make_gmg_pcg_fn`,
`make_fgmres_gmg_fn`, ...) are plain `jax.jit` programs, so JAX's
persistent compilation cache serializes their XLA executables to disk
keyed by the HLO fingerprint — which already folds in everything our
`_lowering_env_key` tracks (the lowering env modes change the traced
HLO) plus shapes, dtypes, mesh and compiler flags. A second process
that builds the same program pays tracing only.

This mirrors the reference's headline that *setup* scales
(/root/reference/README.md:49-63): with the cache on, warm
time-to-first-solution drops the dominant compile line item.

Where the cache lives is decided OUTSIDE the program:

* ``JAX_COMPILATION_CACHE_DIR`` set — that directory, verbatim, and no
  other: an argument passed in code never moves it, so whoever launches
  the process (a scheduler, a CI job, the chip tool) owns the placement.
* unset — ``<checkout>/.jax_cache`` (git-ignored), a fixed path: a
  directory that moves between runs never hits, so nothing here derives
  a name from `tempfile`, a pid or the clock.

Usage (every entry point that touches the chip calls this first)::

    import partitionedarrays_jl_tpu as pa
    pa.enable_compilation_cache()
"""
from __future__ import annotations

import os

__all__ = ["enable_compilation_cache", "compilation_cache_dir"]

#: ``<checkout>/.jax_cache`` — the package directory's parent.
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_enabled_dir: str | None = None


def compilation_cache_dir() -> str | None:
    """The directory of the currently-enabled persistent compilation
    cache, or None when the cache is off."""
    return _enabled_dir


def resolve_cache_dir(path: str | None = None) -> str:
    """Where `enable_compilation_cache(path)` would put the cache:
    ``JAX_COMPILATION_CACHE_DIR`` verbatim when set (``path`` is then
    ignored), else ``path``, else `DEFAULT_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if path is None:
        return DEFAULT_DIR
    return os.path.abspath(os.path.expanduser(path))


def enable_compilation_cache(path: str | None = None) -> str:
    """Turn on JAX's persistent compilation cache (directory created if
    missing; see `resolve_cache_dir` for which one) and return the
    directory used.

    Every XLA compile that takes >= 1 s is written to disk; later
    compiles of byte-identical HLO (same program, shapes, dtypes, mesh,
    lowering env modes) load the executable instead of recompiling —
    including across processes. Safe to call more than once. Calling
    this AFTER programs were already compiled only affects subsequent
    compiles.
    """
    global _enabled_dir
    import jax
    from jax._src import compilation_cache as _cc

    # bridge jax's cache-hit/miss monitoring events into the telemetry
    # counters (persistent_cache.{hit,miss}) — the deterministic signal
    # tests/test_compile_cache.py asserts on instead of wall-clock
    from ..telemetry import install_jax_cache_listeners

    install_jax_cache_listeners()

    path = resolve_cache_dir(path)
    # cache dirs usually live on a shared filesystem (that is the point:
    # one host compiles, every host loads) — N processes race to create
    # the same directory tree and NFS/overlay mounts surface transient
    # errors even under exist_ok; retry before giving up
    from ..parallel.health import retry_with_backoff

    retry_with_backoff(
        lambda: os.makedirs(path, exist_ok=True),
        exceptions=(OSError,),
        describe=f"compilation-cache dir create ({path})",
    )
    jax.config.update("jax_compilation_cache_dir", path)
    # solver programs are large; cache them all (no size floor), but
    # keep the 1 s compile-time floor so the cache isn't littered with
    # the trivial convert/broadcast programs staging emits
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    # the cache object is a lazily-created singleton: once the first
    # compile has initialized it (possibly with the cache OFF), a config
    # update alone never reaches it — drop the instance so the next
    # compile rebuilds it against the new directory
    _cc.reset_cache()
    _enabled_dir = path
    return path
