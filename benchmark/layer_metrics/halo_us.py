"""`halo_us`: device self time of the ops traced under `pa.halo_exchange`
(the ghost region's pack, the collective-permutes' start and done halves,
the unpack) per Krylov iteration of the traced solves, mean over the
cell's devices: the cost of the exchange on the core, which
`collective_share` (collective ops only) cannot see. Source: device_trace,
through the program's named scopes (`_scoped.py`)."""
from benchmark.layer_metrics._scoped import phase_us


def reduce(run):
    return phase_us(run, "pa.halo_exchange")
