"""`stream_spmv_hbm_roofline`: the least time one product with THIS
operator can take on this chip, over `spmv_us`. Source: device_trace.

The operator is a 7-point stencil whose entries are made of one coefficient
a face (`configs/varcoef7_192.json`: `beta` at the face's centre), so the
work counted is the operator's as its source states it, per chip, whatever
implements the product:

* the three arrays of face coefficients, one an axis, each of one value a
  cell (and one more layer of faces, a 192th, left out): 3 passes;
* x read once: 1 pass;
* y written once: 1 pass.

5 passes of (element size) x (owned DOFs per chip) bytes. It is counted
from the configuration and never from the lowering, so a lowering that
stores the seven diagonals (7 + 2 passes) can reach 5/9 of it, one that
stores the four of a symmetric operator 5/6, a matrix-free product from the
faces all of it, and none can pass 100 %.

Bound: memory (HBM bytes per second from `peaks.json`): thirteen operations
a row against twenty bytes leave no compute bound to compare with. A share
over 100 % would mean the coefficients do not come from HBM, which at 85 MB
they must.
"""
from benchmark.layer_metrics import spmv_us

FACE_ARRAYS = 3
VECTOR_PASSES = 2  # x read, y written


def stream_spmv_bytes(dofs_per_chip: int, itemsize: int) -> int:
    return (FACE_ARRAYS + VECTOR_PASSES) * itemsize * dofs_per_chip


def least_spmv_s(dofs_per_chip: int, itemsize: int, hbm_bytes_per_s: float):
    return stream_spmv_bytes(dofs_per_chip, itemsize) / hbm_bytes_per_s


def reduce(run):
    if "beta" not in run.cfg:
        return None  # the count is of an operator made of face coefficients
    measured_us = spmv_us.reduce(run)
    if measured_us is None:
        return None
    least = least_spmv_s(
        run.dofs_per_chip, run.itemsize, run.peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / (measured_us * 1e-6)
