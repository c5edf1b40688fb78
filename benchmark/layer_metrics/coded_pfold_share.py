"""`coded_pfold_share`: of the coded operators staged on the padded frame,
the share whose CG direction fold (p = r + beta p_prev) runs inside the
coded kernel's window pass, in percent: `lowering.coded.pfold /
lowering.coded.operators`, both counted by the program where it stages the
operator. 100 % says the fused CG body runs `pa_dia_coded_spmv_pfold`; 0 %
says it runs `pa_dia_coded_spmv` and the fold as a sweep of its own in
XLA. Source: program_counter. None where no coded operator was staged on
the padded frame, or the program has no such counters. Like the readers
beside it, it speaks only in a run whose trace holds device ops."""
from benchmark.layer_metrics._traced import traced_stretch


def share(counters: dict):
    operators = counters.get("lowering.coded.operators", 0)
    if not operators:
        return None
    return 100.0 * counters.get("lowering.coded.pfold", 0) / operators


def reduce(run):
    if traced_stretch(run) is None:
        return None
    from partitionedarrays_jl_tpu import telemetry

    return share(telemetry.counters("lowering.coded"))
