"""`forecast_deferred_share` (PR 37) on hand-made counters: the share of
the stretch's completed requests forecast from their slab's report; and
None, without raising, where the program has no such counter (the
parent's side of the PR's own comparison) or nothing was completed."""
import pytest

from benchmark.layer_metrics import forecast_deferred_share
from benchmark.tests.test_request_path import counted, served_run

SPANS = [(0.0, 2.0, "bench:solve"), (0.5, 1.9, "pa:service:slab")]
OPS = {0: [(0.6, 1.8, "%while while")]}
OLD = {"service.slab_columns": 3, "service.admitted": 3, "service.completed": 3}


def run_with(at_open: dict, at_close: dict):
    return served_run(SPANS, OPS, counted(at_open, at_close))


@pytest.mark.parametrize("deferred,share", [(14, 100.0), (7, 50.0), (0, 0.0)])
def test_the_share_of_the_completed(deferred, share):
    at_open = dict(OLD, **{"service.forecasts_deferred": 11})
    at_close = {
        "service.slab_columns": 17, "service.admitted": 15,
        "service.completed": 17, "service.forecasts_deferred": 11 + deferred,
    }
    got = forecast_deferred_share.reduce(run_with(at_open, at_close))
    assert got == pytest.approx(share)


def test_a_counter_that_first_counts_inside_the_stretch():
    """The warm-up of a program may defer none: the counter is then
    missing from the first traced request's reading."""
    at_close = dict(OLD, **{"service.completed": 5, "service.forecasts_deferred": 2})
    assert forecast_deferred_share.reduce(run_with(OLD, at_close)) == 100.0


def test_nothing_where_there_is_nothing():
    parent = run_with(OLD, {k: v + 2 for k, v in OLD.items()})
    none_completed = run_with(
        OLD, dict(OLD, **{"service.slab_columns": 5, "service.forecasts_deferred": 0})
    )
    no_trace = served_run(SPANS, {}, [{"info": {}}])
    for run in (parent, none_completed, no_trace):
        assert forecast_deferred_share.reduce(run) is None
