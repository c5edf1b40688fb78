"""`device_idle_share`: 100 x (1 - busy / stretch), where the stretch runs
from the first traced solve's issue to the last one's completion and busy
is the union of the intervals in which any op runs on a device, mean over
the cell's devices. Source: device_trace."""
from benchmark import trace as tr
from benchmark.layer_metrics._traced import traced_stretch


def reduce(run):
    st = traced_stretch(run)
    if st is None:
        return None
    t, lo, hi = st
    busy = tr.mean_busy(t, lo, hi)
    if busy <= 0.0:
        return None
    return 100.0 * (1.0 - busy / (hi - lo))
