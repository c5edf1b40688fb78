"""Persistent XLA compilation cache (round-5 directive 1).

The warm-setup headline rests on two properties: (a) enabling the cache
writes the compiled solver programs to disk, and (b) rebuilding the same
program after the in-process executable caches are cleared produces
IDENTICAL iterates (the disk-served executable is the same program, not
a recompile drift). Both are cheap to pin on the CPU mesh; the timing
claim itself lives in SCALE_BENCH.json (first_solve_cold_s /
first_solve_warm_s) measured on the real chip.

Round 9 (patrace): cache behavior is asserted on the telemetry
COUNTERS (``persistent_cache.{hit,miss}`` bridged from jax.monitoring,
``lowering_cache.{hit,miss,stale_rekey}`` / ``program_cache.{hit,miss}``
from the package's own caches) — a deterministic signal, unlike the
wall-clock compile-time floors such assertions used to lean on.
"""
import os

import jax
import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu.models import assemble_poisson
from partitionedarrays_jl_tpu.parallel.tpu import (
    DeviceVector,
    TPUBackend,
    _b_on_cols_layout,
    device_matrix,
    make_cg_fn,
)


@pytest.fixture
def restore_cache_config():
    """Put the process-global cache configuration back after a test that
    points it at a (pytest-pruned) tmp dir — a cache left aimed there
    poisons later >=1s compiles."""
    import partitionedarrays_jl_tpu.utils.compile_cache as cc

    prev_dir = cc.compilation_cache_dir()
    prev_cfg = jax.config.jax_compilation_cache_dir
    prev_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    yield cc
    jax.config.update("jax_compilation_cache_dir", prev_cfg)
    cc._enabled_dir = prev_dir
    from jax._src import compilation_cache as jcc

    jcc.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_secs)


def test_enable_populates_dir_and_warm_rebuild_matches(
    tmp_path, monkeypatch, restore_cache_config
):
    # placed from outside, the way a launcher does it
    cache_dir = str(tmp_path / "xla")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    got = pa.enable_compilation_cache()
    assert got == cache_dir == pa.compilation_cache_dir()
    assert os.path.isdir(cache_dir)
    # compile-time floor would skip tiny CPU programs; drop it so the
    # test exercises the write+read path deterministically
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    backend = TPUBackend(devices=jax.devices()[:8])
    from partitionedarrays_jl_tpu import telemetry

    def driver(parts):
        Ah, bh, xe, x0 = assemble_poisson(
            parts, (12, 12, 12), dtype=np.float64
        )
        dA = device_matrix(Ah, backend)
        db = _b_on_cols_layout(bh, dA)
        dx0 = DeviceVector.from_pvector(
            pa.PVector.full(0.0, Ah.cols, dtype=np.float64),
            backend, dA.col_layout,
        )
        base = telemetry.counters("persistent_cache")
        solve = make_cg_fn(dA, tol=1e-10, maxiter=500)
        out = solve(db.data, dx0.data, None)
        x_cold = np.asarray(out[0])
        it_cold = int(out[3])
        assert it_cold > 0
        # cold compile against the fresh cache dir: misses only —
        # the counters are the deterministic signal (no wall-clock)
        cold = telemetry.counters("persistent_cache")
        assert (
            cold.get("persistent_cache.miss", 0)
            > base.get("persistent_cache.miss", 0)
        )
        assert cold.get("persistent_cache.hit", 0) == base.get(
            "persistent_cache.hit", 0
        )

        # warm rebuild: executables dropped, program rebuilt — the
        # persistent cache serves the XLA executable from disk
        jax.clear_caches()
        solve2 = make_cg_fn(dA, tol=1e-10, maxiter=500)
        out2 = solve2(db.data, dx0.data, None)
        assert int(out2[3]) == it_cold
        np.testing.assert_array_equal(np.asarray(out2[0]), x_cold)
        warm = telemetry.counters("persistent_cache")
        assert (
            warm.get("persistent_cache.hit", 0)
            > cold.get("persistent_cache.hit", 0)
        ), "warm rebuild did not hit the persistent cache"
        return True

    assert pa.prun(driver, backend, (2, 2, 2))
    entries = os.listdir(cache_dir)
    assert entries, "persistent cache wrote no entries"


def test_lowering_and_program_cache_counters(monkeypatch):
    """The package's own two caches are observable: `device_matrix`'s
    per-matrix staging cache bumps ``lowering_cache.{hit,miss,
    stale_rekey}`` (stale_rekey = a matrix staged before under a
    DIFFERENT `_lowering_env_key` — an env flip re-ran staging
    admission, the palint bug class, now a measurable counter) and
    `_krylov_fn_for` bumps ``program_cache.{hit,miss}``."""
    from partitionedarrays_jl_tpu import telemetry
    from partitionedarrays_jl_tpu.parallel.tpu import _krylov_fn_for

    backend = TPUBackend(devices=jax.devices()[:4])

    def delta(after, before, name):
        return after.get(name, 0) - before.get(name, 0)

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8))
        t0 = telemetry.counters("lowering_cache")
        dA = device_matrix(A, backend)
        assert device_matrix(A, backend) is dA
        t1 = telemetry.counters("lowering_cache")
        assert delta(t1, t0, "lowering_cache.miss") == 1
        assert delta(t1, t0, "lowering_cache.hit") == 1
        assert delta(t1, t0, "lowering_cache.stale_rekey") == 0

        # a lowering-env flip re-keys: staging admission re-runs,
        # visibly (PA_TPU_ABFT is in _lowering_env_key; PA_TRACE_ITERS
        # would NOT trip this — it keys the compiled program, not the
        # staging cache)
        monkeypatch.setenv("PA_TPU_ABFT", "1")
        device_matrix(A, backend)
        t2 = telemetry.counters("lowering_cache")
        assert delta(t2, t1, "lowering_cache.stale_rekey") == 1
        assert delta(t2, t1, "lowering_cache.miss") == 0
        monkeypatch.delenv("PA_TPU_ABFT")

        p0 = telemetry.counters("program_cache")
        solve = _krylov_fn_for(dA, "cg", 1e-9, 50)
        assert _krylov_fn_for(dA, "cg", 1e-9, 50) is solve
        p1 = telemetry.counters("program_cache")
        assert delta(p1, p0, "program_cache.miss") == 1
        assert delta(p1, p0, "program_cache.hit") == 1
        return True

    assert pa.prun(driver, backend, (2, 2))


def test_placement_env_wins_over_argument(
    tmp_path, monkeypatch, restore_cache_config
):
    """``JAX_COMPILATION_CACHE_DIR`` set: that directory, verbatim, and
    no other reaches `jax_compilation_cache_dir` — an explicit argument
    does not move it."""
    cc = restore_cache_config
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    got = cc.enable_compilation_cache(str(tmp_path / "argument"))
    assert got == placed == cc.compilation_cache_dir()
    assert jax.config.jax_compilation_cache_dir == placed
    assert os.path.isdir(placed)
    assert not os.path.exists(tmp_path / "argument")


def test_default_dir_is_fixed_under_the_checkout(
    monkeypatch, restore_cache_config
):
    """Unset: ``<checkout>/.jax_cache``, the same path on every call —
    the directory is part of what makes a second run hit."""
    cc = restore_cache_config
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(checkout, ".jax_cache")
    assert cc.resolve_cache_dir() == want == cc.resolve_cache_dir()
    assert cc.enable_compilation_cache() == want
    assert cc.enable_compilation_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    ignored = open(os.path.join(checkout, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored


def test_no_tempfile_name_reaches_the_cache_dir():
    """No entry point that configures the compile cache builds a
    directory name from `tempfile` (a fresh name per run never hits)."""
    import glob
    import re

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = (
        glob.glob(os.path.join(checkout, "*.py"))
        + glob.glob(os.path.join(checkout, "tools", "*.py"))
        + glob.glob(
            os.path.join(checkout, "partitionedarrays_jl_tpu", "**", "*.py"),
            recursive=True,
        )
    )
    assert len(files) > 50
    cache_use = re.compile(
        r"enable_compilation_cache|jax_compilation_cache_dir"
        r"|JAX_COMPILATION_CACHE_DIR"
    )
    temp_name = re.compile(r"mkdtemp|gettempdir|mkstemp|TemporaryDirectory")
    for path in files:
        src = open(path).read()
        if not cache_use.search(src):
            continue
        for m in cache_use.finditer(src):
            # a tempfile name within the same statement neighbourhood
            window = src[max(0, m.start() - 600) : m.end() + 600]
            assert not temp_name.search(window), (
                f"{os.path.relpath(path, checkout)}: compile cache "
                "configured next to a tempfile name"
            )
