"""End-to-end solve cost on one real chip: multigrid-preconditioned CG
vs plain CG at 192³ (f32).

Methodology (docs/performance.md): per-iteration marginal cost by
differencing two compiled maxiter-pinned runs (each solve is one
dependency chain ending in host scalars), median of three rounds; the
iteration counts to tolerance come from real converged solves. The
product of the two is the honest derived solve time.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax

    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.models import assemble_poisson
    from partitionedarrays_jl_tpu.parallel.tpu import (
        DeviceVector, TPUBackend, _b_on_cols_layout, device_matrix,
        make_cg_fn,
    )
    from partitionedarrays_jl_tpu.parallel.tpu_gmg import (
        _device_hierarchy, _gmg_operands, make_gmg_pcg_fn,
    )

    n = int(os.environ.get("PA_BENCH_N", "192"))
    # PA_GMG_PERIODIC=1 benches the TORUS problem instead (round-5
    # directive 4's done-criterion: periodic V-cycle transfer cost at
    # the equal-box level — the Galerkin levels must take stencil_fast
    # with the wrapped-segment mask, not the assembled-matrix path)
    periodic = os.environ.get("PA_GMG_PERIODIC", "0") == "1"
    pa.enable_compilation_cache()
    backend = TPUBackend(devices=jax.devices()[:1])

    def driver(parts):
        if periodic:
            from partitionedarrays_jl_tpu.models import (
                assemble_poisson_periodic,
            )

            Ah, bh, x_exact, x0 = assemble_poisson_periodic(
                parts, (n, n, n), shift=1.0, dtype=np.float32
            )
            # 1/16 scaling like the Dirichlet leg: bounded under the
            # maxiter-pinned timing chains
            Ah.values = pa.map_parts(
                lambda M: pa.CSRMatrix(
                    M.indptr, M.indices,
                    (M.data / 16.0).astype(np.float32), M.shape,
                ),
                Ah.values,
            )
            Ah.invalidate_blocks()
            bh = pa.PVector(
                pa.map_parts(
                    lambda v: (np.asarray(v) / 16.0).astype(np.float32),
                    bh.values,
                ),
                bh.rows,
            )
            t0 = time.time()
            h = pa.gmg_hierarchy(
                parts, Ah, (n, n, n), coarse_threshold=500
            )
            return Ah, bh, h, time.time() - t0

        A, b, x_exact, x0 = assemble_poisson(parts, (n, n, n))

        def cast(M):
            return pa.CSRMatrix(
                M.indptr, M.indices, (M.data / 16.0).astype(np.float32), M.shape
            )

        A.values = pa.map_parts(cast, A.values)
        A.invalidate_blocks()
        b = A @ pa.PVector(
            pa.map_parts(
                lambda v: np.asarray(v, np.float32), x_exact.values
            ),
            x_exact.rows,
        )
        Ah, bh = pa.decouple_dirichlet(A, b)
        t0 = time.time()
        h = pa.gmg_hierarchy(parts, Ah, (n, n, n), coarse_threshold=500)
        t_build = time.time() - t0
        return Ah, bh, h, t_build

    print("building operator + hierarchy ...", flush=True)
    Ah, bh, h, t_build = pa.prun(driver, backend, (1, 1, 1))
    print(f"hierarchy: {len(h.levels)} levels, build {t_build:.1f}s", flush=True)

    dA = device_matrix(Ah, backend)
    db = _b_on_cols_layout(bh, dA)
    x0 = pa.PVector.full(0.0, Ah.cols, dtype=np.float32)
    dx0 = DeviceVector.from_pvector(x0, backend, dA.col_layout)

    # converged iteration counts (real solves, honest residuals)
    xg, ig = pa.pcg(Ah, bh, minv=h, tol=1e-5)
    xc, ic = pa.cg(Ah, bh, tol=1e-5)
    print(
        f"iterations to 1e-5: pcg+gmg={ig['iterations']} "
        f"plain cg={ic['iterations']}", flush=True,
    )

    # marginal per-iteration costs
    def measure(make, k0, k1):
        solves = {k: make(k) for k in (k0, k1)}
        for s in solves.values():
            _ = [float(v) for v in s(db.data, dx0.data)[1:4]]

        def run_k(k):
            ts = []
            for _ in range(5):
                t0 = time.perf_counter()
                out = solves[k](db.data, dx0.data)
                _ = float(out[1])
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts))

        per = []
        for _ in range(3):
            per.append((run_k(k1) - run_k(k0)) / (k1 - k0))
        return float(np.median(per))

    dt_gmg = measure(
        lambda k: make_gmg_pcg_fn(h, backend, tol=0.0, maxiter=k), 10, 60
    )

    def mk_cg(k):
        fn = make_cg_fn(dA, tol=0.0, maxiter=k)
        return lambda b_, x_: fn(b_, x_, None)

    dt_cg = measure(mk_cg, 100, 500)
    t_gmg = ig["iterations"] * dt_gmg
    t_cg = ic["iterations"] * dt_cg
    print(
        f"per-iteration: pcg+gmg={dt_gmg * 1e3:.2f} ms, plain cg="
        f"{dt_cg * 1e3:.3f} ms"
    )
    print(
        f"derived solve time to 1e-5 at {n}^3: pcg+gmg="
        f"{t_gmg * 1e3:.1f} ms, plain cg={t_cg * 1e3:.1f} ms, "
        f"speedup={t_cg / t_gmg:.1f}x"
    )

    # artifact: per-mode record incl. which transfer path each level
    # staged (the periodic claim is empty unless the Galerkin levels
    # really took the stencil path)
    import json

    dh = _device_hierarchy(h, backend)
    rec = {
        "n": n,
        "mode": "periodic-torus" if periodic else "dirichlet",
        "levels": len(h.levels),
        "transfer_paths": [
            (
                f"stencil[{len(l['stencil'])}]"
                if "stencil" in l
                else ("structured-S" if "dS" in l else "assembled")
            )
            for l in dh["levels"]
        ],
        "iterations_pcg_gmg": ig["iterations"],
        "iterations_cg": ic["iterations"],
        "gmg_ms_per_it": round(dt_gmg * 1e3, 3),
        "cg_ms_per_it": round(dt_cg * 1e3, 4),
        "derived_speedup": round(t_cg / max(t_gmg, 1e-12), 2),
    }
    out_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "GMG_BENCH.json",
    )
    # merge per mode so the periodic and dirichlet records coexist
    from partitionedarrays_jl_tpu.telemetry import artifacts

    try:
        with open(out_path) as f:
            all_rec = json.load(f)
    except Exception:
        all_rec = {}
    all_rec[rec["mode"]] = rec
    # the envelope may predate this run (merged artifact): refresh the
    # fields that describe THIS write, keep the per-mode records
    all_rec.pop("platform", None)
    all_rec.pop("pa_env", None)
    artifacts.write(out_path, all_rec, tool="bench_gmg", echo=False)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
