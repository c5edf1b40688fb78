"""What the readers of the warm-up's counters share (PR 36): the
program's `lowering.*_us` (`parallel/tpu.py` `_LowerSpans`, around
`DeviceMatrix.__init__`) and `compile.*_us` (`telemetry/metrics.py`, what
JAX reports of its own compile path) count since the process began, and
are read when the reader is called, as `sd_fill` reads `lowering.sd.*`:
they are the warm-up's where nothing is lowered or compiled after it
(`run.py` says "COMPILED INSIDE THE WINDOW" where something is). Like the
readers beside them they speak only in a run whose trace holds device
ops."""
from benchmark.layer_metrics._traced import traced_stretch


def seconds(counters: dict, names):
    """Sum of the microsecond counters ``names`` in seconds, or None
    where the program has none of them."""
    found = [counters[n] for n in names if n in counters]
    return 1e-6 * sum(found) if found else None


def process_seconds(run, names):
    if traced_stretch(run) is None:
        return None
    from partitionedarrays_jl_tpu import telemetry

    return seconds(telemetry.counters(), names)
