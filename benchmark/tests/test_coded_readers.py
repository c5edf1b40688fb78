"""`coded_window_reread` and `coded_pfold_share` on hand-made counters:
the plan's numbers at 192^3 and 320^3, the share of coded operators that
fold in the kernel; and None, without raising, where the program has no
such counter (the parent's side of the PR that brought them) or the run
holds no trace."""
import importlib
import types

import pytest

from benchmark import trace as tr
from benchmark.layer_metrics import coded_pfold_share, coded_window_reread

SPANS = [(0.0, 2.0, "bench:solve")]
OPS = {0: [(0.5, 1.9, "%while while")]}


def traced_run(monkeypatch, counters: dict):
    telemetry = importlib.import_module("partitionedarrays_jl_tpu.telemetry")
    monkeypatch.setattr(
        telemetry, "counters",
        lambda prefix="": {k: v for k, v in counters.items() if k.startswith(prefix)},
    )
    return types.SimpleNamespace(
        trace=tr.Trace(OPS, SPANS), traced_records=[{"info": {"iterations": 1}}],
    )


def plan_counters(window_rows: int, pfold: int, operators: int = 1) -> dict:
    return {
        "lowering.coded.operators": operators,
        "lowering.coded.block_rows": 2048 * operators,
        "lowering.coded.x_window_rows": window_rows * operators,
        "lowering.coded.pfold": pfold,
        "lowering.stream.pallas": 0,
    }


@pytest.mark.parametrize(
    "window_rows,reread", [(2632, 128.515625), (3656, 178.515625)],
    ids=["192-cubed", "320-cubed"],
)
def test_the_window_reread_of_the_plan(monkeypatch, window_rows, reread):
    run = traced_run(monkeypatch, plan_counters(window_rows, 1))
    assert coded_window_reread.reduce(run) == pytest.approx(reread)


@pytest.mark.parametrize(
    "pfold,operators,share", [(1, 1, 100.0), (0, 1, 0.0), (1, 2, 50.0)]
)
def test_the_share_that_folds_in_the_kernel(monkeypatch, pfold, operators, share):
    run = traced_run(monkeypatch, plan_counters(2632, pfold, operators))
    assert coded_pfold_share.reduce(run) == pytest.approx(share)


def test_nothing_where_there_is_nothing(monkeypatch):
    # the parent's program counts no coded operator; a streamed operator
    # alone counts none either
    parent = traced_run(monkeypatch, {"lowering.stream.block_rows": 1024})
    for m in (coded_window_reread, coded_pfold_share):
        assert m.reduce(parent) is None, m.__name__
    no_trace = types.SimpleNamespace(trace=None, traced_records=[])
    for m in (coded_window_reread, coded_pfold_share):
        assert m.reduce(no_trace) is None, m.__name__
