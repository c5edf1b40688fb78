"""`stream_iter_hbm_roofline`: the least time one unpreconditioned CG
iteration with THIS operator can take on this chip, over `iter_us`. Source:
device_trace.

The work counted is the ALGORITHM's, per chip, not the lowering's:

* the 10 vector passes of a plain CG iteration, as `cg_iter_hbm_roofline`
  counts them and for its reasons (SpMV: read p, write q; after alpha: read
  x, p, r, q and write x, r; after beta: read r, p and write p, the write
  fused into the next product's read);
* the operator's own bytes, which a constant stencil does not have and this
  one does: the three arrays of face coefficients of
  `stream_spmv_hbm_roofline`, read once an iteration: 3 passes.

13 passes of (element size) x (owned DOFs per chip) bytes: the share of the
whole iteration that bounds every later claim in the cell. Bound: memory
(HBM bytes per second from `peaks.json`), as for both counts it is made of.
A share over 100 % would mean that vectors or coefficients do not come from
HBM, which at 28 MB each they must.
"""
from benchmark.layer_metrics import iter_us
from benchmark.layer_metrics.cg_iter_hbm_roofline import VECTOR_PASSES
from benchmark.layer_metrics.stream_spmv_hbm_roofline import FACE_ARRAYS


def stream_iteration_bytes(dofs_per_chip: int, itemsize: int) -> int:
    return (VECTOR_PASSES + FACE_ARRAYS) * itemsize * dofs_per_chip


def least_iteration_s(dofs_per_chip: int, itemsize: int, hbm_bytes_per_s: float):
    return stream_iteration_bytes(dofs_per_chip, itemsize) / hbm_bytes_per_s


def reduce(run):
    if "beta" not in run.cfg:
        return None  # the count is of an operator made of face coefficients
    if run.mix.get("entry") != "cg" or run.mix.get("preconditioner") is not None:
        return None  # and of plain CG only
    measured_us = iter_us.reduce(run)
    if measured_us is None:
        return None
    least = least_iteration_s(
        run.dofs_per_chip, run.itemsize, run.peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / (measured_us * 1e-6)
