"""What the trace-reading metrics share: the traced stretch, and the traced
solves, each with the seconds its devices were busy inside it (mean over the
cell's devices)."""
from benchmark import trace as tr


def traced_stretch(run):
    """``(trace, lo, hi)`` of a run whose trace holds device ops and traced
    solves, else None."""
    t = run.trace
    if t is None or not t.device_ops:
        return None
    st = tr.stretch(t)
    return None if st is None else (t, *st)


def traced_solves(run):
    """``[(wall_s, busy_s, iterations)]`` of the solves inside the trace, or
    None where there is nothing to read. Worked out once for a run."""
    if not hasattr(run, "_traced_solves"):
        run._traced_solves = _traced_solves(run)
    return run._traced_solves


def _traced_solves(run):
    if traced_stretch(run) is None:
        return None
    spans = tr.solve_spans(run.trace)
    if len(spans) != len(run.traced_records):
        return None
    out = [
        (hi - lo, tr.mean_busy(run.trace, lo, hi), int(rec["info"]["iterations"]))
        for (lo, hi), rec in zip(spans, run.traced_records)
    ]
    return out if sum(b for _, b, _ in out) > 0.0 else None
