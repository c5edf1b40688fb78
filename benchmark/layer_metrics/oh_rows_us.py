"""`oh_rows_us`: device self time of the ops traced under the sub-scope
`oh` of `pa.spmv_local` (the boundary rows of the local product, those
that read ghost columns, in their face-slab form: per class a static slice
of the ghost segment times its coefficients, added into a slice of the
owned block) per Krylov iteration of the traced solves, mean over the
cell's devices, in microseconds. `spmv_us` counts the same ops, beside the
kernel and the embedding of its product. Source: device_trace, through the
program's named scopes.

The sub-scope carries no `pa.` of its own, so this reader parses the
profile the way `sd_gather_share` does, keeping every component of an
`op_name` from its first `pa.` one on. None where no op carries the
sub-scope: one part, a boundary block in the ELL or node-block form, or a
program that does not name it.
"""
import os
from unittest import mock

from benchmark import trace as tr
from benchmark.layer_metrics import _scoped
from benchmark.layer_metrics.sd_gather_share import components_from_pa

PHASE, PART = "pa.spmv_local", "oh"


def under_part(components):
    """True for an op whose innermost `pa.` component is PHASE and whose
    `op_name` has the component PART behind it."""
    scopes = tuple(c for c in components if c.startswith(_scoped.SCOPE_PREFIX))
    return _scoped.phase_of(scopes) == PHASE and PART in components


def part_us(device_ops: dict, lo: float, hi: float, iterations: int):
    secs = _scoped.seconds_by(device_ops, lo, hi, under_part).get(True)
    if secs is None or iterations <= 0:
        return None
    return 1e6 * secs / len(device_ops) / iterations


def reduce(run):
    found = _scoped.scoped_ops(run)
    if found is None:
        return None
    _ops, lo, hi = found
    # the file `scoped_ops` has just read, found the same way
    path = tr.find_xplane(os.path.join(os.path.dirname(tr.__file__), ".trace"))
    with mock.patch.object(_scoped, "scopes_of", components_from_pa):
        device_ops = _scoped.parse(path)
    iterations = sum(
        int(r["info"].get("iterations", 0)) for r in run.traced_records
    )
    return part_us(device_ops, lo, hi, iterations)
