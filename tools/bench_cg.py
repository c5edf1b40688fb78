"""Per-iteration cost of the compiled CG program on one real chip.

The whole Krylov loop is one `lax.while_loop` program ending in host
scalar fetches, so a K-iteration solve IS a K-step dependency chain —
exactly the shape the differenced-chain protocol of bench.py wants:
difference two iteration counts far apart, median of several rounds.

Prints one line: per-iteration microseconds and the derived effective
SpMV+vector-op throughput. Run on the default (real TPU) platform.
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

    n = int(os.environ.get("PA_BENCH_N", "192"))
    pa.enable_compilation_cache()
    backend = TPUBackend(devices=jax.devices()[:1])

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (n, n, n))
        A.values = pa.map_parts(
            lambda M: pa.CSRMatrix(
                M.indptr, M.indices, (M.data / 16.0).astype(np.float32), M.shape
            ),
            A.values,
        )
        A.invalidate_blocks()
        b.values = pa.map_parts(lambda x: np.asarray(x, np.float32), b.values)
        x0.values = pa.map_parts(lambda x: np.asarray(x, np.float32), x0.values)
        return A, b, x0

    A, b, x0 = pa.prun(driver, backend, (1, 1, 1))
    dA = device_matrix(A, backend)
    db = _b_on_cols_layout(b, dA)
    dx0 = DeviceVector.from_pvector(x0, backend, dA.col_layout)

    K0, K1 = 100, 500
    flops = dA.flops_per_spmv  # one SpMV per CG iteration

    def measure(fused: bool = False) -> float:
        # compile each K-program ONCE; only the timed executions repeat
        solves = {
            k: make_cg_fn(dA, tol=0.0, maxiter=k, fused=fused)
            for k in (K0, K1)
        }
        for s in solves.values():  # warm: the solve ends in host scalars
            _ = [float(v) for v in s(db.data, dx0.data, None)[1:4]]

        def run_k(k):
            solve = solves[k]
            ts = []
            for _i in range(5):
                t0 = time.perf_counter()
                out = solve(db.data, dx0.data, None)
                _ = float(out[1])  # host fetch closes the chain
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts))

        per_it = []
        for _round in range(3):
            t0, t1 = run_k(K0), run_k(K1)
            per_it.append((t1 - t0) / (K1 - K0))
        return float(np.median(per_it))

    rec = {"n": n, "dofs": n ** 3, "dtype": "float32",
           "flops_per_spmv": int(flops), "bodies": {}}

    dt = measure()
    rec["bodies"]["standard"] = {"s_per_it": round(dt, 9)}
    print(
        f"cg_per_iteration_us={dt * 1e6:.1f} "
        f"spmv_equiv_gflops={flops / dt / 1e9:.1f} "
        f"(n={n}^3, f32, one chip; includes 2 dots + 3 axpys + halo no-op)"
    )
    dtf = measure(fused=True)
    rec["bodies"]["fused"] = {
        "s_per_it": round(dtf, 9),
        "speedup_vs_standard": round(dt / dtf, 4),
    }
    print(
        f"fused_cg_per_iteration_us={dtf * 1e6:.1f} "
        f"spmv_equiv_gflops={flops / dtf / 1e9:.1f} "
        f"speedup_vs_standard={dt / dtf:.3f}x "
        "(fused body, PA_TPU_FUSED_CG default)"
    )

    # --rhs leg: block (multi-RHS) CG marginals — per-RHS cost at each
    # K against the K=1 block leg (the operator streams once per K)
    argv = sys.argv[1:]
    rhs_arg = os.environ.get("PA_BENCH_RHS", "")
    if "--rhs" in argv and argv.index("--rhs") + 1 < len(argv):
        rhs_arg = argv[argv.index("--rhs") + 1]
    if rhs_arg:
        from partitionedarrays_jl_tpu.parallel.tpu import (
            _block_on_cols_layout, make_cg_fn as _mk,
        )
        import statistics

        ks = [int(s) for s in rhs_arg.split(",") if s]

        def measure_block(K: int) -> float:
            db_b = _block_on_cols_layout([b] * K, dA)
            dz_b = _block_on_cols_layout([x0] * K, dA, with_ghosts=True)
            solves = {
                k: _mk(dA, tol=0.0, maxiter=k, rhs_batch=K)
                for k in (K0, K1)
            }
            for s in solves.values():
                np.asarray(s(db_b, dz_b, None)[1])

            def run_k(k):
                ts = []
                for _i in range(5):
                    t0 = time.perf_counter()
                    out = solves[k](db_b, dz_b, None)
                    np.asarray(out[1])
                    ts.append(time.perf_counter() - t0)
                return float(np.median(ts))

            per_it = []
            for _round in range(3):
                t0, t1 = run_k(K0), run_k(K1)
                per_it.append((t1 - t0) / (K1 - K0))
            return float(statistics.median(per_it))

        base = None
        rec["block"] = {}
        for K in ks:
            t_it = measure_block(K)
            per_rhs = t_it / K
            if K == 1:
                base = per_rhs
            speed = f" per_rhs_speedup_vs_k1={base / per_rhs:.3f}x" if base else ""
            rec["block"][f"K{K}"] = {
                "s_per_it": round(t_it, 9),
                "s_per_rhs_it": round(per_rhs, 9),
            }
            print(
                f"block_cg_K{K}_per_iteration_us={t_it * 1e6:.1f} "
                f"per_rhs_us={per_rhs * 1e6:.1f}{speed} "
                f"(rhs block, operator streamed once per {K} columns)"
            )

    # optional artifact: the probe numbers above as one schema-versioned
    # record through the shared writer (--out PATH or PA_BENCH_CG_OUT)
    out_path = os.environ.get("PA_BENCH_CG_OUT", "")
    if "--out" in argv and argv.index("--out") + 1 < len(argv):
        out_path = argv[argv.index("--out") + 1]
    if out_path:
        from partitionedarrays_jl_tpu.telemetry import artifacts

        artifacts.write(out_path, rec, tool="bench_cg")


if __name__ == "__main__":
    main()
