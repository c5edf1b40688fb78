"""`dots_us`: device self time of the ops traced under `pa.dot_allgather`
(the dot products: local partial, all-gather or all-reduce, fold) per
Krylov iteration of the traced solves, mean over the cell's devices. A
fusion counts under the scope of its root, so an update sweep fused with a
dot's partial sum lands here or under `body_update_us` as a whole. Source:
device_trace, through the program's named scopes (`_scoped.py`)."""
from benchmark.layer_metrics._scoped import phase_us


def reduce(run):
    return phase_us(run, "pa.dot_allgather")
