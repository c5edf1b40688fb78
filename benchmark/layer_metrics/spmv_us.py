"""`spmv_us`: device self time of the ops traced under `pa.spmv_local`
(the local operator product: the coded Mosaic kernel or its XLA forms,
the embedding of the product, the boundary rows) per Krylov iteration of
the traced solves, mean over the cell's devices. Source: device_trace,
through the program's named scopes (`_scoped.py`)."""
from benchmark.layer_metrics._scoped import phase_us


def reduce(run):
    return phase_us(run, "pa.spmv_local")
