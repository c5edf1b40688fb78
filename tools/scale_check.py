"""1e8-DOF end-to-end scale check (the reference's large-assembly config):
assemble a 464^3 3-D Poisson operator on host, lower it, and compare the
compiled SpMV against the f32 host oracle. Run on a real chip with no
extra env (first compile is slow); shrink with PA_SCALE_N for smoke runs.

    python tools/scale_check.py            # 464^3 = 99.9M DOFs
    PA_SCALE_N=192 python tools/scale_check.py
"""
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
        DeviceVector,
        TPUBackend,
        device_matrix,
        make_spmv_fn,
    )

    n = int(os.environ.get("PA_SCALE_N", "464"))
    pa.enable_compilation_cache()
    backend = TPUBackend(devices=jax.devices()[:1])

    def driver(parts):
        t0 = time.perf_counter()
        A, b, xe, x0 = assemble_poisson(parts, (n, n, n))
        t1 = time.perf_counter()
        print(f"assembly {n}^3 = {n**3/1e6:.1f}M DOFs: {t1-t0:.1f}s", flush=True)
        A.values = pa.map_parts(
            lambda M: pa.CSRMatrix(
                M.indptr, M.indices, M.data.astype(np.float32), M.shape
            ),
            A.values,
        )
        A.invalidate_blocks()
        xe.values = pa.map_parts(lambda v: np.asarray(v, np.float32), xe.values)
        host = pa.gather_pvector(A @ xe)
        t2 = time.perf_counter()
        print(f"host oracle SpMV: {t2-t1:.1f}s", flush=True)
        dA = device_matrix(A, backend)
        t3 = time.perf_counter()
        print(
            f"device lowering: {t3-t2:.1f}s mode={dA.dia_mode} "
            f"padded={dA.pallas_plan is not None}",
            flush=True,
        )
        dx = DeviceVector.from_pvector(xe, backend, dA.col_layout)
        y = make_spmv_fn(dA)(dx.data)
        got = pa.gather_pvector(
            DeviceVector(y, A.rows, dA.row_layout, backend).to_pvector()
        )
        t4 = time.perf_counter()
        print(f"compiled SpMV: {t4-t3:.1f}s (incl. compile+transfer)", flush=True)
        err = np.max(np.abs(host - got)) / np.max(np.abs(host))
        print(f"rel err vs host oracle: {err:.2e}", flush=True)
        assert err < 1e-5
        return True

    pa.prun(driver, backend, (1, 1, 1))
    print("scale check OK", flush=True)


if __name__ == "__main__":
    main()
