"""`coded_window_reread`: the bytes of p the coded kernel fetches for each
byte of p it owns, in percent: `lowering.coded.x_window_rows /
lowering.coded.block_rows`, both counted by the program where it stages a
coded operator on the padded frame. A block's window is the block and the
operator's halo on both sides, fetched anew for every block, so 100 % is an
operand read once; by the plan the 7-point Poisson operator reads 128.5 % at
192^3 (2,632-row windows for 2,048-row blocks) and 178.5 % at 320^3
(3,656). Source: program_counter. None where no coded operator was staged
on the padded frame, or the program has no such counters. Like the readers
beside it, it speaks only in a run whose trace holds device ops."""
from benchmark.layer_metrics._traced import traced_stretch


def reread(counters: dict):
    block = counters.get("lowering.coded.block_rows", 0)
    if not block:
        return None
    return 100.0 * counters.get("lowering.coded.x_window_rows", 0) / block


def reduce(run):
    if traced_stretch(run) is None:
        return None
    from partitionedarrays_jl_tpu import telemetry

    return reread(telemetry.counters("lowering.coded"))
