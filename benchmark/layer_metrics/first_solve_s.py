"""`first_solve_s`: host clock around the warm-up solve, the first call of
the public entry in the process: lowering, staging of the operator, and
compilation or its load from the persistent cache, then one solve. Source:
host_clock. Part of `setup_s`."""


def reduce(run):
    return run.timings.get("first_solve_s")
