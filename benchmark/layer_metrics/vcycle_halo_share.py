"""`vcycle_halo_share`: of the device self time under any V-cycle level
scope (`pa.gmg.l<k>`), the share under `pa.halo_exchange` inside it, in
percent: the ghost exchanges of the smoothers' and residuals' products
and of the transfers, at every level, against all the V-cycle does. On
one part it reads 0 (no part has a neighbour). Source: device_trace,
through the program's named scopes (`_scoped.py`)."""
from benchmark.layer_metrics._scoped import level_of, scoped_ops, seconds_by

HALO = "pa.halo_exchange"


def key(scopes):
    """None outside the V-cycle; else whether the op is an exchange's."""
    if level_of(scopes) is None:
        return None
    return HALO in scopes


def share(device_ops: dict, lo: float, hi: float):
    by = seconds_by(device_ops, lo, hi, key)
    total = by.get(True, 0.0) + by.get(False, 0.0)
    if total <= 0.0:
        return None
    return 100.0 * by.get(True, 0.0) / total


def reduce(run):
    found = scoped_ops(run)
    if found is None:
        return None
    return share(*found)
