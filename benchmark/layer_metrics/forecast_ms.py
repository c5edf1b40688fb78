"""`forecast_ms`: what the paspec forecast costs a request on its client's
thread inside `submit` (the operator's fingerprint, `||b - A x0||` on the
host, the prediction): `service.forecast_us` over `service.admitted`, both
counted by the program over the traced stretch
(`_slabs.window_counters`), in milliseconds. Source: program_counter."""
from benchmark.layer_metrics._request_path import ratio_ms
from benchmark.layer_metrics._slabs import window_counters


def reduce(run):
    return ratio_ms(
        window_counters(run), "service.forecast_us", "service.admitted"
    )
