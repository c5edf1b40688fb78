"""`compile_s`: what JAX reports of its own compile path, summed over the
process: tracing to jaxprs, lowering to MLIR, backend compilation and the
retrieval of executables from the persistent cache (`compile.trace_us +
.lower_us + .backend_us + .cache_load_us`; the program keeps a retrieval
out of the backend event JAX times it in, so the four add up), in
seconds. Source: program_counter."""
from benchmark.layer_metrics._setup_counters import process_seconds

PARTS = [
    "compile.trace_us", "compile.lower_us", "compile.backend_us",
    "compile.cache_load_us",
]


def reduce(run):
    return process_seconds(run, PARTS)
