"""`cg_iter_hbm_roofline`: the least time one unpreconditioned CG iteration
can take on this chip, over `iter_us`. Source: device_trace.

The work counted is the ALGORITHM's, per chip, not the lowering's, so a
kernel that packs, codes or fuses differently does the same counted work
and a PR cannot move the number by recounting:

* SpMV q = A p: read p, write q (2 passes). The operator costs ZERO bytes:
  a constant stencil needs none, whatever form the program stores it in.
* after alpha = rs / (p . q): read x, p, r, q and write x, r (6 passes;
  the two dot products ride these passes);
* after beta: read r, p and write p (3 passes), of which the write of p
  can be fused into the next SpMV's read of it: counted as 2.

10 vector passes of (element size) x (owned DOFs per chip) bytes. Bound:
memory (HBM bytes per second from `peaks.json`). No float32 vector peak is
published for this chip, so there is no compute bound to compare with and
this share says "of the HBM roofline" only. A share over 100 % would mean
the vectors do not come from HBM at all, which at 28 MB each they must.
"""
from benchmark.layer_metrics import iter_us

VECTOR_PASSES = 10


def cg_iteration_bytes(dofs_per_chip: int, itemsize: int) -> int:
    return VECTOR_PASSES * itemsize * dofs_per_chip


def least_iteration_s(dofs_per_chip: int, itemsize: int, hbm_bytes_per_s: float):
    return cg_iteration_bytes(dofs_per_chip, itemsize) / hbm_bytes_per_s


def reduce(run):
    if run.mix.get("entry") != "cg" or run.mix.get("preconditioner") is not None:
        return None  # the count above is of plain CG only
    measured_us = iter_us.reduce(run)
    if measured_us is None:
        return None
    least = least_iteration_s(
        run.dofs_per_chip, run.itemsize, run.peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / (measured_us * 1e-6)
