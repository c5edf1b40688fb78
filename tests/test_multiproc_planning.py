"""The multiprocess planning path (tools/plan_multiproc.py) must compute
the SAME per-part matrices as the in-process assembly fast path — the
testable form of the "planning is embarrassingly parallel per part"
claim (round-4 directive 3; reference analog: per-rank local assembly,
test/test_fdm.jl:52-81)."""
import os

import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import native


@pytest.mark.skipif(not native.available(), reason="native layer required")
def test_multiproc_planning_matches_inprocess():
    import sys, os

    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools")
    )
    from plan_multiproc import run

    ns, pshape = (20, 18, 16), (2, 2, 1)
    w1, f1 = run(ns, pshape, 1, dtype="float64", decoupled=False)
    w2, f2 = run(ns, pshape, 2, dtype="float64", decoupled=False)
    # process count cannot change the matrices (last slot is wall time)
    assert [r[:5] for r in f1] == [r[:5] for r in f2]
    assert len(f2) == 4 and sorted(r[0] for r in f2) == [0, 1, 2, 3]

    # pin the checksums to the real API's per-part CSR blocks
    def driver(parts):
        A, b, xe, x0 = pa.assemble_poisson(parts, ns)
        out = []
        for p, M in enumerate(A.values.part_values()):
            out.append(
                (
                    p,
                    int(M.nnz),
                    float(M.data.sum(dtype=np.float64)),
                    int(M.indices.sum(dtype=np.int64)),
                    int(M.indptr[-1]),
                )
            )
        return out

    api = pa.prun(driver, pa.sequential, pshape)
    assert [r[:5] for r in f2] == api


@pytest.mark.skipif(not native.available(), reason="native layer required")
def test_parallel_emit_byte_identical():
    """K spawned workers over row slabs write the SAME CSR (and b) as
    the one-shot native emission — the zero-stitch property that makes
    PA_TPU_PLAN_PROCS safe to flip on (round-5 directive 6)."""
    from partitionedarrays_jl_tpu.models.poisson_fdm import (
        stencil_ghost_slabs,
    )
    from partitionedarrays_jl_tpu.native.parallel_emit import (
        slab_nnz,
        stencil_emit_parallel,
    )

    ns = (20, 18, 16)
    lo, hi = (3, 0, 2), (17, 9, 16)
    arms = np.array([-1.0] * 6)
    gg = stencil_ghost_slabs(lo, hi, ns)
    xtab = np.concatenate(
        [
            np.sin(0.5 + (d + 1.0) * np.arange(ns[d]) / (ns[d] + 1.0))
            for d in range(3)
        ]
    )
    ser = native.stencil_emit(
        ns, lo, hi, 6.0, arms, gg, np.float64, decouple=True, xtab=xtab
    )
    par = stencil_emit_parallel(
        ns, lo, hi, 6.0, arms, gg, np.float64, 2, decouple=True, xtab=xtab
    )
    assert ser is not None and par is not None
    for a, b in zip(ser, par):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the closed-form nnz the shm layout is sized from must match the
    # emission's actual nnz
    assert slab_nnz(ns, lo, hi, 0, hi[0] - lo[0]) == len(ser[1])


@pytest.mark.skipif(not native.available(), reason="native layer required")
def test_plan_procs_env_flag_matches_default(monkeypatch):
    """PA_TPU_PLAN_PROCS=2 routes the box fast path's emission through
    the spawned workers; the assembled operator must be identical."""
    ns = (14, 12, 10)

    def driver(parts):
        A, b, xe, x0 = pa.assemble_poisson(parts, ns, decoupled=True)
        return [
            (
                int(M.nnz),
                float(M.data.sum(dtype=np.float64)),
                int(M.indices.sum(dtype=np.int64)),
            )
            for M in A.values.part_values()
        ] + [float(np.asarray(v, dtype=np.float64).sum()) for v in b.values]

    base = pa.prun(driver, pa.sequential, (2, 1, 1))
    monkeypatch.setenv("PA_TPU_PLAN_PROCS", "2")
    multi = pa.prun(driver, pa.sequential, (2, 1, 1))
    assert base == multi


def test_planning_workers_never_import_jax():
    """A spawned planning worker imports this package; the parent may
    hold the chip, which one process owns at a time — so importing the
    package (and the emission module the workers run) must not import
    jax, let alone initialize a backend."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "import partitionedarrays_jl_tpu\n"
        "from partitionedarrays_jl_tpu.native import parallel_emit\n"
        "assert parallel_emit._worker\n"
        "assert 'jax' not in sys.modules, 'package import pulled in jax'\n"
    )
    subprocess.run(
        [sys.executable, "-c", code], check=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
