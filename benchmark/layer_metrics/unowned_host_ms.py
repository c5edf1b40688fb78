"""`unowned_host_ms`: per traced solve, the time the first device sat idle
inside the harness's `bench:solve` span under no leaf span of the program:
the innermost span over it is `bench:solve` itself or the program's
`pa:solve` root. Source: program_span."""
from benchmark.layer_metrics._host_spans import unowned_ms_per_solve


def reduce(run):
    return unowned_ms_per_solve(run)
