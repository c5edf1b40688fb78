"""`lower_upload_s`: of `lower_s`, the time inside `_stage` of the
operator's operands (span `pa:lower:upload`), counter
`lowering.upload_us`, in seconds. Source: program_counter."""
from benchmark.layer_metrics._setup_counters import process_seconds


def reduce(run):
    return process_seconds(run, ["lowering.upload_us"])
