"""`sd_fill`: of the dense entries the supernode-dense lowering made of
the operator, the share that are its stored non-zeros, in percent:
`lowering.sd.nnz / lowering.sd.dense_entries`, both counted by the program
where it stages the operator. The product streams every dense entry, so
100 / fill is by how much the lowering's bytes exceed the operator's.
Source: program_counter. None where the operator did not lower to SD, or
the program has no such counters. Like the readers beside it, it speaks
only in a run whose trace holds device ops."""
from benchmark.layer_metrics._traced import traced_stretch


def fill(counters: dict):
    dense = counters.get("lowering.sd.dense_entries", 0)
    if not dense:
        return None
    return 100.0 * counters.get("lowering.sd.nnz", 0) / dense


def reduce(run):
    if traced_stretch(run) is None:
        return None
    from partitionedarrays_jl_tpu import telemetry

    return fill(telemetry.counters("lowering.sd"))
