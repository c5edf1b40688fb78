"""`staged_mb_per_solve`: bytes of the device frames the program staged its
solves' vectors into, over its device solves, both counted by the program
since the process began (`solve.staged_bytes` / `solve.calls`, warm-up
included; every solve of a cell stages the same frames), in MB of 1e6
bytes. Source: program_counter. Like the trace readers beside it, it
speaks only in a run whose trace holds device ops: the per-layer line of a
rehearsal off the chip stays what `tests/test_run_cpu.py` pins."""
from benchmark.layer_metrics._traced import traced_stretch


def reduce(run):
    if traced_stretch(run) is None:
        return None
    from partitionedarrays_jl_tpu import telemetry

    counters = telemetry.counters("solve")
    calls = counters.get("solve.calls", 0)
    if not calls or "solve.staged_bytes" not in counters:
        return None
    return counters["solve.staged_bytes"] / calls / 1e6
