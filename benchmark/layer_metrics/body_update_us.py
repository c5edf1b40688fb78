"""`body_update_us`: device self time of the ops traced under
`pa.axpy_sweep` (the Krylov step outside its SpMV, exchange and dots: the
vector updates, the packing and unpacking of the carry, the loop itself)
per Krylov iteration of the traced solves, mean over the cell's devices.
Source: device_trace, through the program's named scopes (`_scoped.py`)."""
from benchmark.layer_metrics._scoped import phase_us


def reduce(run):
    return phase_us(run, "pa.axpy_sweep")
