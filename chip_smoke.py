"""chip_smoke.py — the quickest proof that the solve path still starts on
the chip.

    python chip_smoke.py            # no arguments, one process, needs a TPU

Drives the normal entry points (`pa.prun` -> `assemble_*` ->
`PSparseMatrix` -> `pa.cg` / `pa.pcg` / `SolveService`) once, on every
chip JAX finds (one part per device), at 192^3 cells per chip in
float32, and checks every answer against NumPy float64 on the host CSR
parts — not against the device program or the solver's own recurrence.

Legs, in order:

1. coded-DIA CG        Poisson, `pa.cg`, fused body, Mosaic coded kernel
2. compiled GMG-PCG    same operator, `pa.gmg_hierarchy` -> `pa.pcg`
3. served solves       `SolveService(A, kmax=4)`, 8 requests, block body
4. streaming-DIA CG    `pa.assemble_diffusion_fv`, Mosaic streaming kernel
5. irregular graph     tet elasticity at 64^3 nodes, SD lowering, Jacobi-PCG

Exits non-zero, printing no result, unless `jax.devices()[0].platform` is
"tpu": there is no flag or environment variable that makes it pass
anywhere else. A leg that fails fails the run. Legs 4 and 5 are cut
(`not run: time`, never `passed`) when the time limit would not hold
them. The per-leg record goes out on a `chip_smoke: record {...}` line;
the last line of stdout is exactly
`{"ok": ..., "device": {"platform", "kind", "count"}}`, the device as JAX
reports it. Every second printed here is a smoke's, not a benchmark's.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time
import traceback
import warnings

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

CELLS_PER_CHIP = 192  # 192^3 = 7.08 M DOFs per chip
ELASTICITY_NODES = 64  # 64^3 nodes = 786 k DOFs
TOL = 1e-5
RESIDUAL_MAX = 1e-4  # independent float64 host residual, relative to ||b||
PARITY_MAX = 1e-5  # device-vs-host SpMV, relative to max|A v|
GMG_MAX_ITERATIONS = 15
SERVED_REQUESTS = 8
SERVED_KMAX = 4
PART_GRIDS = {1: (1, 1, 1), 4: (2, 2, 1), 8: (2, 2, 2)}

#: The contract's limit, and what a late leg must have left to start
#: (host assembly + compile + solve, from the PR 21 chip runs, rounded
#: up generously; see CHANGES.md).
LIMIT_S = 1200.0
LEG_NEEDS_S = {"stream_dia_cg": 200.0, "irregular_pcg": 420.0}


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def require(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def timed(f):
    """``(f(), seconds)``. Every call timed here ends on the host (the
    solvers return host vectors), so the clock covers the device work."""
    t0 = time.perf_counter()
    out = f()
    return out, round(time.perf_counter() - t0, 3)


def hpgmg_beta(x, y, z):
    """HPGMG-FV's coefficient, as the `varcoef7_192` cell sets it: 1
    inside a sphere of radius 0.25 about the cube's centre, 10 outside,
    a smooth tanh jump between."""
    r = np.sqrt((x - 0.5) ** 2 + (y - 0.5) ** 2 + (z - 0.5) ** 2)
    return 5.5 + 4.5 * np.tanh(10.0 * (r - 0.25))


# ---------------------------------------------------------------------------
# checks shared by the legs
# ---------------------------------------------------------------------------


def independent_residual(pa, A, x, b) -> float:
    """``||b - A x|| / ||b||`` over the owned rows, in float64, with
    NumPy on the host CSR parts: x is gathered to one global host array
    and every part's rows are applied to it by a plain bincount — no
    device program, no halo exchange, none of the solver's recurrence."""
    xg = pa.gather_pvector(x).astype(np.float64)
    num = den = 0.0
    for ri, ci, M, bv in zip(
        A.rows.partition.part_values(),
        A.cols.partition.part_values(),
        A.values.part_values(),
        b.values.part_values(),
    ):
        xl = xg[np.asarray(ci.lid_to_gid)]
        row = np.repeat(np.arange(M.shape[0]), np.diff(M.indptr))
        y = np.bincount(
            row, weights=M.data.astype(np.float64) * xl[M.indices],
            minlength=M.shape[0],
        )
        own = np.asarray(ri.oid_to_lid)
        bo = np.asarray(bv, dtype=np.float64)[own]
        num += float(np.sum((bo - y[own]) ** 2))
        den += float(np.sum(bo**2))
    return float(np.sqrt(num) / np.sqrt(den))


def seeded_vector(pa, cols, seed: int, dtype):
    """A PVector over ``cols`` whose value at a gid depends on the gid
    and the seed only, so ghosts agree with their owners by construction."""
    table = np.random.default_rng(seed).standard_normal(cols.ngids)
    return pa.scatter_pvector_values(table.astype(dtype), cols)


def lowering_of(dA) -> str:
    if dA.dia_mode == "coded":
        return "coded-dia/" + (
            "pallas-padded-frame" if dA.pallas_plan is not None else "xla"
        )
    if dA.dia_mode == "stream":
        return "stream-dia/" + (
            "pallas" if dA.pallas_plan is not None else "xla"
        )
    if dA.sd_bs is not None:
        return f"sd(bs={dA.sd_bs})"
    if dA.bsr_bs is not None:
        return f"bsr(bs={dA.bsr_bs})"
    return "ell"


def device_state(pa, A, seed: int) -> dict:
    """The device side of ``A``, observed: which lowering staged, one
    compiled ``A @ v`` on a seeded v against the host oracle (the
    tools/scale_check.py check), where the shards of the product live,
    what dtype they hold, and what each device has allocated."""
    from partitionedarrays_jl_tpu.parallel.tpu import (
        DeviceVector, _lift_on_device, device_matrix, make_spmv_fn,
    )

    backend = A.values.backend
    dA = device_matrix(A, backend)
    v = seeded_vector(pa, A.cols, seed, A.dtype)
    host = pa.gather_pvector(A @ v)
    dv = DeviceVector.from_pvector(v, backend, dA.col_layout)
    # what `tpu._as_callers_array` rests on (jax 0.9.0, no documented
    # contract): a part fetched from the chip is an array of its own, so an
    # answer changes hands without a copy
    fetched = [
        np.asarray(part)
        for part in _lift_on_device(dv.data, dA.col_layout, backend)
    ]
    fetched_owned = all(f.flags.owndata and f.base is None for f in fetched)
    require(
        fetched_owned or not on_tpu(A),
        "a part fetched from the chip does not own its data: every answer "
        "is copied once more (tpu._as_callers_array)",
    )
    y = make_spmv_fn(dA)(dv.data)
    got = pa.gather_pvector(
        DeviceVector(y, A.rows, dA.row_layout, backend).to_pvector()
    )
    parity = float(np.max(np.abs(host - got)) / np.max(np.abs(host)))
    require(
        parity <= PARITY_MAX,
        f"SpMV parity {parity:.3e} > {PARITY_MAX:g} ({lowering_of(dA)})",
    )
    devices = backend.devices()[: dA.row_layout.P]
    shard_ids = [int(s.device.id) for s in y.addressable_shards]
    require(
        sorted(shard_ids) == sorted(int(d.id) for d in devices),
        f"shards on devices {shard_ids}, parts on "
        f"{[int(d.id) for d in devices]}: not one shard per device",
    )
    in_use = {}
    for d in devices:
        stats = d.memory_stats()
        if stats is not None:  # the CPU client reports none
            in_use[int(d.id)] = int(stats["bytes_in_use"])
            require(in_use[int(d.id)] > 0, f"device {d.id} holds nothing")
    return {
        "lowering": lowering_of(dA),
        "exchange_plan": type(dA.col_plan).__name__,
        "spmv_parity": parity,
        "device_dtype": str(y.dtype),
        "shard_devices": shard_ids,
        "bytes_in_use": in_use,
        "fetched_part_owns_its_data": fetched_owned,
    }


def mosaic_in_solo_programs(A) -> bool:
    """Whether every K=1 CG program the solves on ``A`` built carries a
    Mosaic kernel (`tpu_custom_call`) in its lowered text — i.e. the
    Pallas form was compiled for the chip, not interpreted and not
    traded for the XLA form of the same arithmetic."""
    import jax

    from partitionedarrays_jl_tpu.parallel.tpu import device_matrix

    backend = A.values.backend
    dA = device_matrix(A, backend)
    solo = [
        fn for fn in dA._cg_cache.values()
        if fn.comms_kwargs["rhs_batch"] is None
    ]
    require(solo, "no K=1 CG program was built on this operator")
    L = dA.col_layout
    vec = jax.ShapeDtypeStruct(
        (L.P, L.W), np.dtype(A.dtype), sharding=backend.sharding(L.P)
    )
    return all(
        "tpu_custom_call" in fn.jit_fn.lower(vec, vec, vec, fn.operands).as_text()
        for fn in solo
    )


def on_tpu(A) -> bool:
    return A.values.backend.devices()[0].platform == "tpu"


def solve_twice(solve):
    """First call (stages, traces, compiles) and second call (warm)."""
    (x, info), first = timed(solve)
    (x2, info2), second = timed(solve)
    require(
        info2["iterations"] == info["iterations"],
        "the warm solve took a different number of iterations",
    )
    for a, a2 in zip(x.values.part_values(), x2.values.part_values()):
        a, a2 = np.asarray(a), np.asarray(a2)
        require(
            a.flags.writeable and a.flags.owndata and a.base is None
            and a2.flags.writeable and a2.flags.owndata and a2.base is None,
            "a part of an answer is not a writable array of its own",
        )
        require(
            not np.shares_memory(a, a2),
            "the answers of two solves share memory",
        )
    return x, info, {"first_call_s": first, "second_call_s": second}


# ---------------------------------------------------------------------------
# the legs
# ---------------------------------------------------------------------------


def build_poisson(pa, parts, ns) -> dict:
    (A, b, _xe, x0), setup = timed(
        lambda: pa.assemble_poisson(
            parts, ns, dtype=np.float32, decoupled=True
        )
    )
    return {"A": A, "b": b, "x0": x0, "ns": tuple(ns), "parts": parts,
            "setup_s": setup}


def leg_coded_cg(pa, system) -> dict:
    A, b, x0 = system["A"], system["b"], system["x0"]
    x, info, rec = solve_twice(lambda: pa.cg(A, b, x0=x0, tol=TOL))
    require(info["converged"], f"cg did not converge: {info['status']}")
    require(
        info.get("cg_body") == "fused",
        f"cg ran the {info.get('cg_body')!r} body, not the fused one",
    )
    res = independent_residual(pa, A, x, b)
    require(res <= RESIDUAL_MAX, f"residual {res:.3e} > {RESIDUAL_MAX:g}")
    rec.update(device_state(pa, A, seed=1))
    require(
        rec["lowering"] == "coded-dia/pallas-padded-frame",
        f"operator lowered to {rec['lowering']}, not the coded padded frame",
    )
    rec["mosaic_call"] = mosaic_in_solo_programs(A)
    require(
        rec["mosaic_call"] or not on_tpu(A),
        "the CG program holds no tpu_custom_call: the coded kernel was "
        "traded for its XLA form",
    )
    rec.update(
        setup_s=system["setup_s"], iterations=info["iterations"],
        residual=res, cg_body=info["cg_body"],
    )
    return rec


def leg_gmg_pcg(pa, system) -> dict:
    A, b = system["A"], system["b"]
    h, setup = timed(
        lambda: pa.gmg_hierarchy(system["parts"], A, system["ns"])
    )
    x, info, rec = solve_twice(lambda: pa.pcg(A, b, minv=h, tol=TOL))
    require(info["converged"], f"gmg-pcg did not converge: {info['status']}")
    require(
        info["iterations"] <= GMG_MAX_ITERATIONS,
        f"gmg-pcg took {info['iterations']} > {GMG_MAX_ITERATIONS} iterations",
    )
    res = independent_residual(pa, A, x, b)
    require(res <= RESIDUAL_MAX, f"residual {res:.3e} > {RESIDUAL_MAX:g}")
    # what each level of the compiled V-cycle staged (a cache hit: the
    # solve above built it): the level operator's lowering and the form
    # its transfers took
    from partitionedarrays_jl_tpu.parallel.tpu_gmg import _device_hierarchy

    def transfer_of(level):
        if "stencil" in level:
            return "matrix-free stencil"
        if "dS" in level:
            return "S=" + lowering_of(level["dS"])
        return "assembled P/R"

    staged = _device_hierarchy(h, A.values.backend)["levels"]
    rec.update(
        setup_s=setup, iterations=info["iterations"], residual=res,
        lowering=f"compiled V-cycle, {len(h.levels)} levels",
        levels=[
            f"{lowering_of(lv['dA'])}; transfer {transfer_of(lv)}"
            for lv in staged
        ],
    )
    return rec


def leg_served(pa, system) -> dict:
    A = system["A"]

    def make_requests():
        return [
            A @ seeded_vector(pa, A.cols, 100 + k, A.dtype)
            for k in range(SERVED_REQUESTS)
        ]

    bs, setup = timed(make_requests)
    svc = pa.SolveService(A, kmax=SERVED_KMAX)
    reqs = [svc.submit(bk, tol=TOL, tag=f"smoke-{k}") for k, bk in enumerate(bs)]
    slab_s = []
    while True:
        done, s = timed(svc.step)
        if not done:
            break
        slab_s.append(s)
    stats = svc.shutdown()
    require(
        stats["completed"] == SERVED_REQUESTS
        and stats["ejected"] == 0 and stats["rejected"] == 0
        and stats["failed"] == 0,
        f"service stats {stats}",
    )
    require(
        stats["slabs"] == SERVED_REQUESTS // SERVED_KMAX,
        f"{stats['slabs']} slabs, expected full K={SERVED_KMAX} slabs",
    )
    residuals, iterations = [], []
    for req, bk in zip(reqs, bs):
        x, info = req.result()
        require(info["converged"], f"{req.tag}: {info['status']}")
        res = independent_residual(pa, A, x, bk)
        require(
            res <= RESIDUAL_MAX,
            f"{req.tag}: residual {res:.3e} > {RESIDUAL_MAX:g}",
        )
        residuals.append(res)
        iterations.append(int(info["iterations"]))
    return {
        "setup_s": setup, "first_call_s": slab_s[0],
        "second_call_s": slab_s[1], "iterations": max(iterations),
        "residual": max(residuals), "completed": stats["completed"],
        "slabs": stats["slabs"],
        # the Pallas kernels take one column; a K-column slab decodes
        # the same codebooks through the XLA form (tpu.py:_aoo)
        "lowering": f"block body K={SERVED_KMAX}, coded-dia/xla",
    }


def leg_stream_dia(pa, parts, ns) -> dict:
    A, setup = timed(
        lambda: pa.assemble_diffusion_fv(parts, ns, hpgmg_beta, np.float32)
    )
    rec = device_state(pa, A, seed=2)
    want = "stream-dia/pallas" if on_tpu(A) else "stream-dia/xla"
    require(
        rec["lowering"] == want,
        f"operator lowered to {rec['lowering']}, expected {want}",
    )
    b = A @ seeded_vector(pa, A.cols, 3, A.dtype)
    # tol=0: exactly 50 trips; the question is whether they stay finite
    x, info, times = solve_twice(lambda: pa.cg(A, b, tol=0.0, maxiter=50))
    require(info["iterations"] == 50, f"{info['iterations']} iterations")
    require(
        np.isfinite(info["residuals"]).all()
        and np.isfinite(pa.gather_pvector(x)).all(),
        "non-finite values after 50 CG iterations",
    )
    rec["mosaic_call"] = mosaic_in_solo_programs(A)
    require(
        rec["mosaic_call"] or not on_tpu(A),
        "the CG program holds no tpu_custom_call: dia_spmv_pallas is not "
        "in it",
    )
    rec.update(times)
    rec.update(
        setup_s=setup, iterations=50,
        residual=independent_residual(pa, A, x, b),
    )
    return rec


def leg_irregular(pa, parts, nodes: int) -> dict:
    from partitionedarrays_jl_tpu.parallel.tpu import (
        ELL_MAX_GATHER, DeviceMatrix, ELLFootprintError, _env_overrides,
    )

    def assemble():
        # the chip has no float64: assemble in float32, in the open (the
        # sums run in float64 and the result is rounded once)
        A, b, _xe, x0 = pa.assemble_elasticity_tet(
            parts, (nodes,) * 3, dtype=np.float32
        )
        return A, b, x0

    (A, b, x0), setup = timed(assemble)
    rec = {"nodes_per_dim": nodes, "dofs": int(A.rows.ngids)}
    # The ELL gather program once faulted a device worker at this
    # operator on one part. The guard is per part (rows x padded width):
    # where that footprint is past its ceiling and the SD and BSR
    # lowerings are switched off, it must refuse to stage ELL rather
    # than try it; where the parts are small enough it has nothing to
    # refuse, and the smoke says which case it saw.
    oo = A.owned_owned_values.part_values()
    footprint = max(m.shape[0] for m in oo) * max(
        int(m.row_lengths().max()) for m in oo
    )
    rec["ell_footprint_per_part"] = footprint
    if footprint <= ELL_MAX_GATHER:
        rec["ell_refused"] = "not asked: footprint under the ceiling"
    elif on_tpu(A):
        with _env_overrides({"PA_TPU_SD": "0", "PA_TPU_BSR": "0"}):
            try:
                DeviceMatrix(A, A.values.backend)
            except ELLFootprintError:
                rec["ell_refused"] = True
            else:
                raise SmokeFailure("the ELL guard staged the refused program")
    x, info, times = solve_twice(
        lambda: pa.pcg(A, b, x0=x0, tol=TOL, maxiter=5000)
    )
    require(info["converged"], f"jacobi-pcg: {info['status']}")
    res = independent_residual(pa, A, x, b)
    require(res <= RESIDUAL_MAX, f"residual {res:.3e} > {RESIDUAL_MAX:g}")
    rec.update(device_state(pa, A, seed=4))
    require(
        rec["lowering"].startswith("sd("),
        f"operator lowered to {rec['lowering']}, not SD",
    )
    require(
        rec["exchange_plan"] == "DeviceExchangePlan",
        f"exchange plan {rec['exchange_plan']}, not the generic index plan",
    )
    rec.update(times)
    rec.update(setup_s=setup, iterations=info["iterations"], residual=res)
    return rec


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def run_legs(pa, backend, grid, cells: int, nodes: int, deadline: float):
    """Run the five legs; returns ``{name: record}`` in order. A leg
    that raises is recorded as failed with its traceback and the
    remaining legs still run (one chip call then shows every defect)."""
    ns = tuple(cells * g for g in grid)
    legs = {}

    def attempt(name, f):
        needs = LEG_NEEDS_S.get(name)
        if needs is not None and time.monotonic() + needs > deadline:
            legs[name] = {"status": "not run: time"}
        else:
            try:
                legs[name] = {"status": "passed", **f()}
            except Exception as e:  # leg boundary: report, keep going
                traceback.print_exc()
                legs[name] = {
                    "status": "failed", "error": f"{type(e).__name__}: {e}",
                }
        print(f"[{name}] {json.dumps(legs[name])}", flush=True)
        gc.collect()

    def poisson_legs(parts):
        system = build_poisson(pa, parts, ns)
        attempt("coded_cg", lambda: leg_coded_cg(pa, system))
        attempt("gmg_pcg", lambda: leg_gmg_pcg(pa, system))
        attempt("served", lambda: leg_served(pa, system))

    pa.prun(poisson_legs, backend, grid)
    pa.prun(
        lambda parts: attempt(
            "stream_dia_cg", lambda: leg_stream_dia(pa, parts, ns)
        ),
        backend, grid,
    )
    pa.prun(
        lambda parts: attempt(
            "irregular_pcg", lambda: leg_irregular(pa, parts, nodes)
        ),
        backend, len(backend.devices()),
    )
    return legs


def device_record(devices) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def verdict_line(ok: bool, devices) -> str:
    """The last line of stdout: the verdict and the device as JAX reports
    it, and no other key — the driver reads exactly this object."""
    return json.dumps({"ok": bool(ok), "device": device_record(devices)})


def main() -> int:
    t0 = time.monotonic()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, and JAX found {devices}. It does "
            "not run anywhere else.",
            file=sys.stderr,
        )
        return 2
    if len(devices) not in PART_GRIDS:
        print(
            f"chip_smoke: no part grid for {len(devices)} devices "
            f"(knows {sorted(PART_GRIDS)})",
            file=sys.stderr,
        )
        return 2
    sys.path.insert(0, HERE)
    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu import native, telemetry

    grid = PART_GRIDS[len(devices)]
    header = {
        "jax": jax.__version__,
        "device": device_record(devices),
        "part_grid": list(grid),
        "cells_per_chip": CELLS_PER_CHIP**3,
        "dtype": "float32",
        "compile_cache_dir": pa.enable_compilation_cache(),
        "native": native.available(),
    }
    print(f"chip_smoke: {json.dumps(header)}", flush=True)
    if not header["native"]:
        print("chip_smoke: the native planning library did not build",
              file=sys.stderr)
        return 1

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        backend = pa.TPUBackend(devices=devices)
        legs = run_legs(
            pa, backend, grid, CELLS_PER_CHIP, ELASTICITY_NODES,
            deadline=t0 + LIMIT_S,
        )
    notes = sorted({f"{w.category.__name__}: {w.message}" for w in caught})
    for n in notes:
        print(f"[warning] {n}", flush=True)
    # a device order the topology did not choose is a failure here
    misplaced = [n for n in notes if "TPUBackend:" in n]
    # only legs 4 and 5 can be cut for time (LEG_NEEDS_S); 1-3 pass or fail
    ok = not misplaced and all(
        leg["status"] in ("passed", "not run: time") for leg in legs.values()
    )
    record = {
        **header, "legs": legs, "warnings": notes,
        "persistent_cache": telemetry.counters("persistent_cache"),
        "wall_s": round(time.monotonic() - t0, 1),
    }
    print(f"chip_smoke: record {json.dumps(record)}", flush=True)
    print(verdict_line(ok, devices), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
