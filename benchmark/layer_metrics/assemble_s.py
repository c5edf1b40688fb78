"""`assemble_s`: host clock around the builder's assembly of the operator
through the public API (`assemble_poisson`, plus `gmg_hierarchy` where the
mix has a preconditioner). Source: host_clock. Part of `setup_s`."""


def reduce(run):
    return run.timings.get("assemble_s")
