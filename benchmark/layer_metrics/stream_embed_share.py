"""`stream_embed_share`: of the device self time under `pa.spmv_local`, the
share under its sub-scope `dia.embed`, in percent: the copies around the
streaming-DIA kernel (the owned region cut out of the frame and padded into
the kernel's lane-tiled operand; the product cut to its owned length and
embedded in a frame again). The rest is `dia.stream`, the kernel itself.
Source: device_trace, through the program's named scopes.

The sub-scope carries no `pa.` of its own, so this reader parses the profile
the way `sd_gather_share` does, keeping every component of an `op_name` from
its first `pa.` one on. None where no op carries the sub-scope: another
lowering, or a program that does not name it.
"""
import os
from unittest import mock

from benchmark import trace as tr
from benchmark.layer_metrics import _scoped
from benchmark.layer_metrics.sd_gather_share import components_from_pa

PHASE, PART = "pa.spmv_local", "dia.embed"


def part_of(components):
    """``PART`` for an op under PHASE/PART, ``PHASE`` for any other op whose
    innermost `pa.` component is PHASE, else None."""
    scopes = tuple(c for c in components if c.startswith(_scoped.SCOPE_PREFIX))
    if _scoped.phase_of(scopes) != PHASE:
        return None
    return PART if PART in components else PHASE


def share(device_ops: dict, lo: float, hi: float):
    by = _scoped.seconds_by(device_ops, lo, hi, part_of)
    if PART not in by:
        return None
    return 100.0 * by[PART] / (by[PART] + by.get(PHASE, 0.0))


def reduce(run):
    found = _scoped.scoped_ops(run)
    if found is None:
        return None
    _ops, lo, hi = found
    # the file `scoped_ops` has just read, found the same way
    path = tr.find_xplane(os.path.join(os.path.dirname(tr.__file__), ".trace"))
    with mock.patch.object(_scoped, "scopes_of", components_from_pa):
        device_ops = _scoped.parse(path)
    return share(device_ops, lo, hi)
