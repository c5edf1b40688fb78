"""`submit_ms`: wall time of the program's `pa:service:submit` span, the
whole of `SolveService.submit` on its client's thread (validation, the
paspec forecast, admission, the request's record), mean over the traced
requests: the part of a request's life that lies in front of its
submission stamp, where `queue_wait_ms` starts. Source: program_span."""
from benchmark import trace as tr
from benchmark.layer_metrics._request_path import submit_ms
from benchmark.layer_metrics._traced import traced_stretch


def reduce(run):
    if traced_stretch(run) is None:
        return None
    return submit_ms(run.trace.spans, tr.solve_spans(run.trace))
