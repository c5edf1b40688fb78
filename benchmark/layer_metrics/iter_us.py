"""`iter_us`: device-busy time of the traced solves over the sum of the
iterations the solver reports for them (`info["iterations"]`): what one
Krylov iteration costs on the device, whatever it is made of (SpMV, sweeps,
dots, halo exchange, a V-cycle). Source: device_trace."""
from benchmark.layer_metrics._traced import traced_solves


def reduce(run):
    solves = traced_solves(run)
    if solves is None:
        return None
    iterations = sum(it for _, _, it in solves)
    if iterations <= 0:
        return None
    return 1e6 * sum(b for _, b, _ in solves) / iterations
