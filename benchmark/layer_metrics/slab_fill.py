"""`slab_fill`: the columns the service's slabs ran over the columns they
could have run: `service.slab_columns` over `service.slabs` times the
configuration's `kmax`, both counted by the program over the traced
stretch (`_slabs.window_counters`), in percent. 100 %
is every slab full; 25 % at `kmax` 4 is every request alone in its slab.
Source: program_counter."""
from benchmark.layer_metrics._slabs import window_counters


def reduce(run):
    c = window_counters(run)
    if c is None or not c.get("service.slabs"):
        return None
    kmax = int(run.cfg["service"]["kmax"])
    return 100.0 * c["service.slab_columns"] / (c["service.slabs"] * kmax)
