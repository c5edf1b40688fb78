"""`scope_coverage`: of the devices' busy time inside the traced stretch,
the share whose op (the innermost one running) carries any `pa.` scope of
the program, in percent. It guards the names the other scoped metrics read
against a refactor; what stays outside is what XLA inserts with no
metadata (relayout copies, async slices). Source: device_trace, through
the program's named scopes (`_scoped.py`)."""
from benchmark.layer_metrics._scoped import scoped_ops, seconds_by


def reduce(run):
    found = scoped_ops(run)
    if found is None:
        return None
    by = seconds_by(*found, bool)
    if not by.get(True):
        return None  # a program without the scopes: nothing to read
    return 100.0 * by[True] / sum(by.values())
