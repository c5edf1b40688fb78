"""`halo_index_share`: of the device self time under `pa.halo_exchange`,
the share under its sub-scopes `ex.pack` (a round's gather of the send
slots) and `ex.unpack` (its scatter into the receive slots), in percent:
the part of the generic exchange that is index work on the core. The rest
is the collective-permutes and whatever else XLA put under the phase.
Source: device_trace, through the program's named scopes.

The sub-scopes carry no `pa.` of their own, so this reader parses the
profile the way `sd_gather_share` does, keeping every component of an
`op_name` from its first `pa.` one on. None where no op carries either
sub-scope: a box plan, one part, or a program that does not name them.
"""
import os
from unittest import mock

from benchmark import trace as tr
from benchmark.layer_metrics import _scoped
from benchmark.layer_metrics.sd_gather_share import components_from_pa

PHASE, PARTS = "pa.halo_exchange", ("ex.pack", "ex.unpack")


def index_work(components):
    """True for an op under PHASE with one of PARTS behind it, False for
    any other op whose innermost `pa.` component is PHASE, else None."""
    scopes = tuple(c for c in components if c.startswith(_scoped.SCOPE_PREFIX))
    if _scoped.phase_of(scopes) != PHASE:
        return None
    return any(part in components for part in PARTS)


def share(device_ops: dict, lo: float, hi: float):
    by = _scoped.seconds_by(device_ops, lo, hi, index_work)
    if True not in by:
        return None
    return 100.0 * by[True] / (by[True] + by.get(False, 0.0))


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
