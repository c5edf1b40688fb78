"""`collective_share`: device time inside XLA's collective ops (the halo
exchange's `collective-permute`s and the dot products' `all-reduce` /
`all-gather`, start and done halves alike) over device-busy time, both as
unions of intervals inside the traced stretch, mean over the cell's
devices. A cell on one chip has no such op and reports nothing. Source:
device_trace."""
from benchmark import trace as tr
from benchmark.layer_metrics._traced import traced_stretch


def reduce(run):
    st = traced_stretch(run)
    if st is None:
        return None
    t, lo, hi = st
    shares = []
    for ops in t.device_ops.values():
        coll = [o for o in ops if tr.is_collective(o[2])]
        total = tr.busy(ops, lo, hi)
        if not coll or total <= 0.0:
            return None
        shares.append(tr.busy(coll, lo, hi) / total)
    return 100.0 * sum(shares) / len(shares)
