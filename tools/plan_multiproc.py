"""Multiprocess planning: the per-part assembly loop run across real OS
processes (round-4 directive 3 — make the "embarrassingly parallel
planning" claim TESTABLE, not rhetorical).

Planning in this framework is per-part by construction (the reference's
per-rank local assembly, /root/reference/test/test_fdm.jl:52-81): each
part's owned-rows CSR depends only on its own box geometry, so K
processes can each emit a disjoint subset of parts with zero
communication. This tool does exactly that for the Dirichlet-identity
Poisson stencil — box split via the SAME `_cartesian_box` arithmetic the
real partition constructor uses, ghosts via `stencil_ghost_slabs`, CSR
via the fused native `stencil_emit` — and reports per-process wall times
plus per-part checksums. On a 1-core host the speedup is ~1x (the
documented no-op); on a real multi-core planning host the same command
scales. `tests/test_multiproc_planning.py` pins the checksums to the
in-process `assemble_poisson` fast path, so the parallel planning path
provably computes the SAME matrices.

    python tools/plan_multiproc.py            # 192^3, K=2 processes
    PA_MP_N=128 PA_MP_PROCS=4 python tools/plan_multiproc.py
"""
from __future__ import annotations

import json
import math
import multiprocessing as mp
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def plan_parts(args):
    """Worker: emit the owned-rows CSR of each assigned part and return
    (part, nnz, checksums, seconds) tuples — no cross-part state."""
    ns, pshape, part_ids, dtype_name, decoupled = args
    from partitionedarrays_jl_tpu import native
    from partitionedarrays_jl_tpu.models.poisson_fdm import (
        stencil_ghost_slabs,
    )
    from partitionedarrays_jl_tpu.parallel.prange import (
        _cartesian_box,
        _part_coords,
    )

    dim = len(ns)
    center = 2.0 * dim
    arms = np.array([-1.0, -1.0] * dim)
    out = []
    for p in part_ids:
        t0 = time.perf_counter()
        lo, hi = _cartesian_box(_part_coords(p, pshape), ns, pshape)
        gg = stencil_ghost_slabs(lo, hi, ns)
        res = native.stencil_emit(
            ns, lo, hi, center, arms, gg, np.dtype(dtype_name),
            decouple=decoupled,
        )
        assert res is not None, "native stencil_emit unavailable"
        indptr, cols, vals = res
        out.append(
            (
                int(p),
                int(len(vals)),
                float(vals.sum(dtype=np.float64)),
                int(cols.sum(dtype=np.int64)),
                int(indptr[-1]),
                round(time.perf_counter() - t0, 3),
            )
        )
    return out


def run(ns, pshape, procs, dtype="float32", decoupled=True):
    nparts = math.prod(pshape)
    assign = [list(range(k, nparts, procs)) for k in range(procs)]
    args = [(ns, pshape, a, dtype, decoupled) for a in assign if a]
    t0 = time.perf_counter()
    if procs == 1:
        results = [plan_parts(args[0])]
    else:
        # spawn, not fork: a parent that has used JAX has live threads,
        # and forking a multithreaded process is deadlock-prone (round-4
        # advisor). Workers import fresh interpreters and never
        # initialize a JAX backend — planning is NumPy/C++ only.
        with mp.get_context("spawn").Pool(len(args)) as pool:
            results = pool.map(plan_parts, args)
    wall = time.perf_counter() - t0
    flat = sorted(r for rs in results for r in rs)
    return wall, flat


def main():
    n = int(os.environ.get("PA_MP_N", "192"))
    procs = int(os.environ.get("PA_MP_PROCS", "2"))
    px = int(os.environ.get("PA_MP_PARTS", "8"))
    ns, pshape = (n, n, n), (px, 1, 1)
    w1, f1 = run(ns, pshape, 1)
    wk, fk = run(ns, pshape, procs)
    # compare the checksum fields only (the last tuple slot is wall time)
    assert [r[:5] for r in f1] == [r[:5] for r in fk], (
        "multiprocess planning changed the matrices"
    )
    print(
        json.dumps(
            {
                "metric": f"planning_multiproc_{n}cube_{px}parts",
                "value": round(wk, 2),
                "unit": "s",
                "vs_baseline": round(w1 / max(wk, 1e-9), 2),
                "procs": procs,
                "single_process_s": round(w1, 2),
                "note": "vs_baseline is the K-process speedup over 1 "
                "process on THIS host (1-core boxes measure ~1x; the "
                "path itself is communication-free per part)",
            }
        )
    )


if __name__ == "__main__":
    main()
