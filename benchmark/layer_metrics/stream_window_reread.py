"""`stream_window_reread`: the bytes of x the streaming-DIA kernel fetches
for each byte of x it owns, in percent: `lowering.stream.x_window_rows /
lowering.stream.block_rows`, both counted by the program where it stages
the operator's diagonals. A block's window is the block and the operator's
halo on both sides, fetched anew for every block, so 100 % is an operand
read once and 214 % the plan of 512-row blocks under a 288-row halo at
192^3. Source: program_counter. None where the operator's diagonals are not
streamed through the kernel, or the program has no such counters. Like the
readers beside it, it speaks only in a run whose trace holds device ops."""
from benchmark.layer_metrics._traced import traced_stretch


def reread(counters: dict):
    block = counters.get("lowering.stream.block_rows", 0)
    if not block:
        return None
    return 100.0 * counters.get("lowering.stream.x_window_rows", 0) / block


def reduce(run):
    if traced_stretch(run) is None:
        return None
    from partitionedarrays_jl_tpu import telemetry

    return reread(telemetry.counters("lowering.stream"))
