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

ISSUE-18 leg (``--twolevel``): the same real-OS-process discipline
applied to the NODE-AWARE exchange plan. Every controller in a
multi-host job must construct the identical two-level schedule from
the identical replicated inputs (node map + exchanger) — a forked
schedule would deadlock the paired `ppermute`s at runtime. The harness
makes that testable today: K spawned processes each build the
two-level plan host-side (pure NumPy — no JAX backend, exactly like
the planning workers), run the full plan-verifier battery (the five
flat checks on the logical view plus the staged-schedule simulation),
and return a structural digest (`plan_fingerprint` +
`canonical_exchange_fingerprint`); the parent asserts all digests
agree. `tests/test_multihost.py` routes its plan-soundness legs
through this harness, so they RUN on every host instead of skipping on
the jaxlib CPU-runtime collective limitation (which only the true
execution legs need).

    python tools/plan_multiproc.py            # 192^3, K=2 processes
    PA_MP_N=128 PA_MP_PROCS=4 python tools/plan_multiproc.py
    python tools/plan_multiproc.py --twolevel # cross-process plan digests
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


def plan_twolevel(args):
    """Worker: build the two-level exchange plan of the shared probe
    under the given node map, verify it (five flat checks on the
    logical view + the staged-schedule simulation), and return its
    structural digest plus the schedule/decision summary. Host-side
    NumPy planning only — no JAX backend is ever initialized."""
    ns, pshape, nmap = args
    os.environ["PA_TPU_BOX"] = "0"
    os.environ["PA_TPU_TWOLEVEL"] = "1"
    os.environ["PA_TPU_NODE_MAP"] = nmap
    import hashlib

    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.analysis import plan_verifier as pv
    from partitionedarrays_jl_tpu.models import assemble_poisson
    from partitionedarrays_jl_tpu.parallel.tpu import device_exchange_plan

    out = {}

    def driver(parts):
        A, _b, _xe, _x0 = assemble_poisson(parts, ns)
        rows = A.cols
        plan = device_exchange_plan(rows)
        assert hasattr(plan, "tl_rounds"), type(plan).__name__
        defects = pv.verify_plan(
            plan, referenced=pv.referenced_ghosts(A)
        )
        assert defects == [], [str(d) for d in defects]
        canon = pv.canonical_exchange_fingerprint(
            rows.exchanger, rows.partition
        )
        fp = pv.plan_fingerprint(plan)
        out.update(
            pid=os.getpid(),
            digest=hashlib.sha256(
                repr((canon, fp)).encode()
            ).hexdigest()[:16],
            rounds=len(plan.tl_rounds),
            wire_rounds=plan.wire_rounds,
            tiers=[rd.tier for rd in plan.tl_rounds],
            slow_edges_flat=plan.decision["slow_edges_flat"],
            node_pairs=plan.decision["node_pair_edges"],
            use=plan.decision["use"],
        )
        return True

    assert pa.prun(driver, pa.sequential, pshape)
    return out


def run_twolevel(ns=(8, 8), pshape=(2, 4),
                 nmap="0,0,0,0,1,1,1,1", procs=2):
    """K >= 2 REAL OS processes each build and verify the identical
    two-level plan; returns ``(results, agree)`` where ``agree`` is
    cross-process digest equality (see module docstring — the
    replicated-planning invariant a multi-host job depends on)."""
    assert procs >= 2, "the cross-process leg needs >= 2 processes"
    args = (tuple(ns), tuple(pshape), nmap)
    # spawn, not fork — same rationale as `run`
    with mp.get_context("spawn").Pool(procs) as pool:
        results = pool.map(plan_twolevel, [args] * procs)
    digests = {r["digest"] for r in results}
    assert len({os.getpid()} | {r["pid"] for r in results}) == (
        procs + 1
    ), "workers did not run in distinct OS processes"
    return results, len(digests) == 1


def main():
    if "--twolevel" in sys.argv[1:]:
        procs = int(os.environ.get("PA_MP_PROCS", "2"))
        results, agree = run_twolevel(procs=procs)
        assert agree, "cross-process two-level plan digests diverged"
        print(
            json.dumps(
                {
                    "metric": "twolevel_plan_cross_process_agreement",
                    "procs": procs,
                    "digest": results[0]["digest"],
                    "rounds": results[0]["rounds"],
                    "wire_rounds": results[0]["wire_rounds"],
                    "tiers": results[0]["tiers"],
                    "slow_edges_flat": results[0]["slow_edges_flat"],
                    "node_pairs": results[0]["node_pairs"],
                    "agree": agree,
                }
            )
        )
        return
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
