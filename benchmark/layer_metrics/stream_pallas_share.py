"""`stream_pallas_share`: of the operators staged as streamed diagonals,
the share whose product runs the Mosaic kernel `pa_dia_stream_spmv`, in
percent: `lowering.stream.pallas / lowering.stream.operators`, both
counted by the program where it stages the operator. 100 % says every
streamed operator (in a GMG hierarchy the 27-point Galerkin levels) runs
the kernel; less says some take the XLA shifted-slice form
(`pa.spmv_local/dia.xla`) because no block of the kernel's plan holds
their band. Source: program_counter. None where no operator was staged
as streamed diagonals, or the program does not count its operators. Like
the readers beside it, it speaks only in a run whose trace holds device
ops."""
from benchmark.layer_metrics._traced import traced_stretch


def share(counters: dict):
    operators = counters.get("lowering.stream.operators", 0)
    if not operators:
        return None
    return 100.0 * counters.get("lowering.stream.pallas", 0) / operators


def reduce(run):
    if traced_stretch(run) is None:
        return None
    from partitionedarrays_jl_tpu import telemetry

    return share(telemetry.counters("lowering.stream"))
