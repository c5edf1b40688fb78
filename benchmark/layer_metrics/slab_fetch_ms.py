"""`slab_fetch_ms`: wall time of the block solve's `pa:block-cg:fetch`
span per traced slab (`pa:service:slab`): the copy of the `(P, W, K)`
answer slab and the scalars to the host, and the lift of each column to a
host `PVector`. Source: program_span."""
from benchmark.layer_metrics._slabs import leaf_ms_per_slab


def reduce(run):
    return leaf_ms_per_slab(run, "fetch")
