"""`fetch_ms`: wall time of the program's `pa:<solver>:fetch` span per
traced solve: the copy of the solve's outputs to the host and the lift of
the answer frame to a host `PVector`. Source: program_span."""
from benchmark.layer_metrics._host_spans import phase_ms_per_solve


def reduce(run):
    return phase_ms_per_solve(run, "fetch")
