"""`slab_stage_ms`: wall time of the block solve's `pa:block-cg:stage`
span per traced slab (`pa:service:slab`): the operator lookup, the fill of
the `(P, W, K)` host slabs of right-hand sides and start vectors, and
their puts onto the device. Source: program_span."""
from benchmark.layer_metrics._slabs import leaf_ms_per_slab


def reduce(run):
    return leaf_ms_per_slab(run, "stage")
