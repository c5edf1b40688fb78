"""`block_iter_hbm_roofline`: the least time the traced slabs' block
iterations can take on this chip, over the device time measured inside
their spans. Source: device_trace.

The work counted is the ALGORITHM's, per chip, and the same whatever
implements the body: a block iteration of width ``k`` is ``k`` plain CG
iterations side by side, each the 10 vector passes of
`cg_iter_hbm_roofline.py` (SpMV: read p, write q; after alpha: read x, p,
r, q, write x, r; after beta: read r, p, write p, of which the write can
ride the next product's read); the operator is a constant stencil and
costs no bytes, however wide the block. So a slab of width ``k`` that made
``trips`` block iterations needs at least

    trips x k x 10 x (element size) x (owned DOFs per chip)

bytes, each slab at its own ``k``. Bound: memory (HBM bytes per second
from `peaks.json`); no float32 vector peak is published for this chip, so
there is no compute bound to compare with.
"""
from benchmark.layer_metrics._slabs import traced_slab_busy

VECTOR_PASSES = 10


def block_iterations_bytes(slabs, dofs_per_chip: int, itemsize: int) -> int:
    """Bytes of the block iterations of ``[(start, end, k, trips)]``."""
    return sum(
        trips * k * VECTOR_PASSES * itemsize * dofs_per_chip
        for _lo, _hi, k, trips in slabs
    )


def reduce(run):
    found = traced_slab_busy(run)
    if found is None:
        return None
    slabs, busy = found
    least = block_iterations_bytes(
        slabs, run.dofs_per_chip, run.itemsize
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / busy
