"""`spmv_csr_roofline`: the least time one product with THIS operator can
take on this chip, over `spmv_us`. Source: device_trace.

The work counted is the operator's, per chip, whatever implements the
product: every stored value and its column index read once (a CSR row
pointer per row is small beside them and left out), x read once and y
written once. It is counted from the configuration file (`nnz`, and the
owned DOFs per chip), never from the lowering, so a lowering that pads,
densifies or blocks the operator does the same counted work and shows its
extra bytes as a lower share:

    nnz x (element size + 4) + 2 x DOFs per chip x element size

Bound: memory (HBM bytes per second from `peaks.json`): two operations a
stored value against twelve bytes leaves no compute bound to compare with.
A share over 100 % would mean the operator's values do not come from HBM,
which at 112 MB they must.
"""
from benchmark.layer_metrics import spmv_us

INDEX_BYTES = 4


def csr_spmv_bytes(nnz: int, dofs_per_chip: int, itemsize: int) -> int:
    return nnz * (itemsize + INDEX_BYTES) + 2 * dofs_per_chip * itemsize


def least_spmv_s(nnz: int, dofs_per_chip: int, itemsize: int,
                 hbm_bytes_per_s: float) -> float:
    return csr_spmv_bytes(nnz, dofs_per_chip, itemsize) / hbm_bytes_per_s


def reduce(run):
    if "nnz" not in run.cfg:
        return None  # a stencil configuration states no stored operator
    measured_us = spmv_us.reduce(run)
    if measured_us is None:
        return None
    least = least_spmv_s(
        int(run.cfg["nnz"]) // run.chips, run.dofs_per_chip, run.itemsize,
        run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / (measured_us * 1e-6)
