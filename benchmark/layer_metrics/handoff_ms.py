"""`handoff_ms`: from a request's terminal stamp on the worker's thread to
its answer in the hands of the client that waits for it (the record's
finish, the event, the client's wake-up): `service.handoff_us` over
`service.answers`, both counted by the program over the traced stretch
(`_slabs.window_counters`), in milliseconds. Source: program_counter."""
from benchmark.layer_metrics._request_path import ratio_ms
from benchmark.layer_metrics._slabs import window_counters


def reduce(run):
    return ratio_ms(
        window_counters(run), "service.handoff_us", "service.answers"
    )
