"""`queue_wait_ms`: what a request waits between its submission and the
formation of the slab it rides in, mean over the requests that joined a
slab in the traced stretch:
`service.queue_wait_us` over `service.slab_columns`, both counted by the
program over the traced stretch (`_slabs.window_counters`), in
milliseconds. Source: program_counter."""
from benchmark.layer_metrics._slabs import window_counters


def reduce(run):
    c = window_counters(run)
    if c is None or not c.get("service.slab_columns"):
        return None
    if "service.queue_wait_us" not in c:
        return None
    return 1e-3 * c["service.queue_wait_us"] / c["service.slab_columns"]
