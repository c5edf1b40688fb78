"""`host_per_solve_ms`: of one solve's wall time through the public entry,
the part in which no op ran on the device: staging of the vectors, dispatch,
the fetch of the answer and the library's host glue. Mean over the traced
solves; a solve's busy time is the mean over the cell's devices of the
union of its op intervals inside the solve's span. Source: device_trace."""
from benchmark.layer_metrics._traced import traced_solves


def reduce(run):
    solves = traced_solves(run)
    if solves is None:
        return None
    return 1e3 * sum(w - b for w, b, _ in solves) / len(solves)
