"""`vcycle_coarse_share`: of the device self time under any V-cycle level
scope (`pa.gmg.l<k>`; an op's level is its innermost one), the share under
level 1 and coarser, in percent. A geometric hierarchy of a 3-D grid gives
12.5 %; more is overhead of small kernels or a slower lowering on the
coarse levels. Source: device_trace, through the program's named scopes
(`_scoped.py`)."""
from benchmark.layer_metrics._scoped import level_of, scoped_ops, seconds_by


def reduce(run):
    found = scoped_ops(run)
    if found is None:
        return None
    by = seconds_by(*found, level_of)
    total = sum(v for k, v in by.items() if k is not None)
    if total <= 0.0:
        return None
    return 100.0 * sum(v for k, v in by.items() if k) / total
