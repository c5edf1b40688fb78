"""`stream_pallas_share` on hand-made counters: the share of streamed
operators whose product runs the Mosaic kernel (a GMG hierarchy's four
27-point Galerkin levels, two or four of them through the kernel); and
None, without raising, where the program does not count its streamed
operators (the parent's side of the PR that brought the counter) or the
run holds no trace."""
import importlib
import types

import pytest

from benchmark import trace as tr
from benchmark.layer_metrics import stream_pallas_share

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


@pytest.mark.parametrize(
    "pallas,operators,share", [(4, 4, 100.0), (2, 4, 50.0), (1, 1, 100.0), (0, 1, 0.0)],
    ids=["gmg-all", "gmg-two-of-four", "varcoef", "off-chip"],
)
def test_the_share_that_runs_the_kernel(monkeypatch, pallas, operators, share):
    run = traced_run(monkeypatch, {
        "lowering.stream.operators": operators,
        "lowering.stream.diagonals": 27 * operators,
        "lowering.stream.pallas": pallas,
    })
    assert stream_pallas_share.reduce(run) == pytest.approx(share)


def test_nothing_where_there_is_nothing(monkeypatch):
    # the parent's program counts diagonals and kernels but no operators;
    # a run with a coded operator alone counts no streamed one
    parent = traced_run(monkeypatch, {
        "lowering.stream.diagonals": 108, "lowering.stream.pallas": 2,
    })
    assert stream_pallas_share.reduce(parent) is None
    coded = traced_run(monkeypatch, {"lowering.coded.operators": 1})
    assert stream_pallas_share.reduce(coded) is None
    no_trace = types.SimpleNamespace(trace=None, traced_records=[])
    assert stream_pallas_share.reduce(no_trace) is None
