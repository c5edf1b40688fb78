"""`worker_idle_share`: the share of the traced stretch the service's
worker thread spent with an empty queue, under its `pa:service:idle` span,
in percent. Read beside `device_idle_share`: the difference of the two is
the device idle while a request is in the host's hands. Like that share
it RISES in an open-loop cell under its knee when the service gets faster.
Source: program_span."""
from benchmark.layer_metrics._request_path import idle_share
from benchmark.layer_metrics._traced import traced_stretch


def reduce(run):
    st = traced_stretch(run)
    if st is None:
        return None
    t, lo, hi = st
    return idle_share(t.spans, lo, hi)
