"""`transfer_matrix_free_share`: of the V-cycle levels whose transfer the
program staged, the share it applies matrix-free, in percent:
`(gmg.transfer.stencil + gmg.transfer.separable) / gmg.transfer.levels`,
counted by the program where it stages each level's transfer
(`tpu_gmg._device_hierarchy`). The rest went through `device_matrix`: the
interpolation stencil S as an operator (`.operator`) or the assembled R
and P (`.assembled`). It is the ledger's record of which transfer a cell
ran. Source: program_counter. None where the program staged no hierarchy
or has no such counters; like the readers beside it, it speaks only in a
run whose trace holds device ops."""
from benchmark.layer_metrics._traced import traced_stretch


def share(counters: dict):
    levels = counters.get("gmg.transfer.levels", 0)
    if not levels:
        return None
    free = counters.get("gmg.transfer.stencil", 0) + counters.get(
        "gmg.transfer.separable", 0
    )
    return 100.0 * free / levels


def reduce(run):
    if traced_stretch(run) is None:
        return None
    from partitionedarrays_jl_tpu import telemetry

    return share(telemetry.counters("gmg.transfer"))
