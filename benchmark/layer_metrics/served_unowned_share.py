"""`served_unowned_share`: of the time the first device idles in the
traced stretch of a served cell, the share that no leaf span of the
program owns: the innermost span over it is none, the harness's
`bench:solve` or the program's `pa:solve` root, in percent
(`_host_spans.idle_by_span` over the whole stretch, where
`unowned_host_ms` looks inside the solves of a closed loop). Source:
device_trace."""
from benchmark.layer_metrics._request_path import unowned_share
from benchmark.layer_metrics._traced import traced_stretch


def reduce(run):
    st = traced_stretch(run)
    if st is None:
        return None
    t, lo, hi = st
    return unowned_share(t.device_ops[min(t.device_ops)], t.spans, lo, hi)
