"""`forecast_deferred_share`: of the requests completed in the traced
stretch, the percent whose paspec forecast was made from their slab's own
first residual when their first column reported, and so took no
`||b - A x0||` on the host inside `submit`: `service.forecasts_deferred`
over `service.completed`, both counted by the program over the traced
stretch (`_slabs.window_counters`) and both on the worker's thread as a
column reports, so a request is in both or in neither (over
`service.admitted` the backlog behind the profiler's start, admitted
before the stretch and reported inside it, read 116.7 %). A request that
brings `r0_norm`, one with a deadline under `PA_SPEC_ADMIT=1` and one of
an unmeasured operator are not deferred. None where the program has no
such counter. Source: program_counter."""
from benchmark.layer_metrics._slabs import window_counters


def reduce(run):
    counters = window_counters(run)
    if (
        counters is None
        or "service.forecasts_deferred" not in counters
        or not counters.get("service.completed")
    ):
        return None
    return (
        100.0 * counters["service.forecasts_deferred"]
        / counters["service.completed"]
    )
