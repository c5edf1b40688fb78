"""`stage_ms`: wall time of the program's `pa:<solver>:stage` span per
traced solve: the operator lookup, and for each vector the host frame's
pack and its put onto the device. Source: program_span."""
from benchmark.layer_metrics._host_spans import phase_ms_per_solve


def reduce(run):
    return phase_ms_per_solve(run, "stage")
