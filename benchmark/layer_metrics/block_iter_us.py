"""`block_iter_us`: device-busy time inside the traced slabs' spans
(`pa:service:slab`, each whole inside the trace) over the sum of their
`trips`: what one block iteration costs on the device, at whatever widths
the batcher formed, whatever it is made of. Mean over the cell's devices.
Source: device_trace."""
from benchmark.layer_metrics._slabs import traced_slab_busy


def reduce(run):
    found = traced_slab_busy(run)
    if found is None:
        return None
    slabs, busy = found
    return 1e6 * busy / sum(trips for _lo, _hi, _k, trips in slabs)
