"""`lower_s`: wall time of every `DeviceMatrix.__init__` of the process
(span `pa:lower`: detection of the operator's form, layouts, plans and
codebooks, the upload of its operands), counter `lowering.wall_us`, in
seconds. Part of `first_solve_s`, or of `assemble_s` where a hierarchy's
levels are lowered as it is built. Source: program_counter."""
from benchmark.layer_metrics._setup_counters import process_seconds


def reduce(run):
    return process_seconds(run, ["lowering.wall_us"])
