"""The readers of PR 36 on hand-made traces and counters: a served
request's path outside its slab (`submit_ms`, `forecast_ms`,
`worker_idle_share`, `handoff_ms`, `served_unowned_share`) and the
warm-up's counters (`lower_s`, `lower_upload_s`, `compile_s`); and None,
without raising, where the program has no such span or counter (the
parent's side of the PR's own comparison)."""
import importlib
import types

import pytest

from benchmark import trace as tr

SERVED = ("submit_ms", "forecast_ms", "worker_idle_share", "handoff_ms",
          "served_unowned_share")
SETUP = ("lower_s", "lower_upload_s", "compile_s")


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


def counted(at_open: dict, at_close: dict) -> list:
    """Two traced records: the first's submission, the last's answer."""
    late = {k: 10**9 for k in at_close}
    return [
        {"info": {"service_counters": {"at_submit": at_open, "at_answer": late}}},
        {"info": {"service_counters": {"at_submit": late, "at_answer": at_close}}},
    ]


def served_run(spans, ops, records):
    return types.SimpleNamespace(
        trace=tr.Trace(ops, sorted(spans)), traced_records=records,
        cfg={"service": {"kmax": 4}},
    )


def test_the_served_readers_on_a_made_up_trace():
    """Two requests on threads of their own, 0 to 2 s and 1 to 3.5 s, the
    second's `submit` inside the first's solve as well as its own; the
    worker idle 2.9 to 4 s (past the stretch's end at 3.5); the device
    busy 0.5 to 1.9 and 2.0 to 2.8: the numbers by hand."""
    spans = [
        (0.0, 2.0, "bench:solve"), (1.0, 3.5, "bench:solve"),
        (0.0, 0.4, "pa:service:submit"), (0.05, 0.35, "pa:submit:forecast"),
        (0.1, 0.3, "pa:forecast:norm"),
        (1.0, 1.6, "pa:service:submit"), (1.1, 1.5, "pa:submit:forecast"),
        (0.5, 1.9, "pa:service:slab"), (2.0, 2.9, "pa:service:slab"),
        (2.9, 4.0, "pa:service:idle"),
        (9.0, 9.5, "pa:service:submit"),  # the burst behind the trace
    ]
    ops = {0: [(0.5, 1.9, "%while while"), (2.0, 2.8, "%while while")]}
    at_open = {"service.slab_columns": 8, "service.admitted": 10,
               "service.forecast_us": 100, "service.answers": 10,
               "service.handoff_us": 50, "service.submit_us": 0}
    at_close = {"service.slab_columns": 10, "service.admitted": 12,
                "service.forecast_us": 700100, "service.answers": 12,
                "service.handoff_us": 3050, "service.submit_us": 10**6}
    run = served_run(spans, ops, counted(at_open, at_close))
    assert reader("submit_ms").reduce(run) == pytest.approx(1e3 * (0.4 + 0.6) / 2)
    assert reader("forecast_ms").reduce(run) == pytest.approx(700.0 / 2)
    assert reader("handoff_ms").reduce(run) == pytest.approx(3.0 / 2)
    assert reader("worker_idle_share").reduce(run) == pytest.approx(
        100.0 * 0.6 / 3.5
    )
    # idle: 0 to 0.5 (0.1 under `submit`, 0.1 under the forecast, 0.2
    # under the norm, 0.1 under `bench:solve` alone), 1.9 to 2.0 (between
    # the slabs: `bench:solve` alone), 2.8 to 2.9 (the second slab's), 2.9
    # to 3.5 (the idle worker's)
    assert reader("served_unowned_share").reduce(run) == pytest.approx(
        100.0 * (0.1 + 0.1) / 1.3
    )


def test_the_parents_trace_through_the_same_readers():
    """No `pa:service:submit`, no `pa:service:idle`, no new counter: four
    readers are silent and `served_unowned_share` says what the parent's
    trace leaves without an owner."""
    spans = [(0.0, 2.0, "bench:solve"), (0.5, 1.9, "pa:service:slab")]
    ops = {0: [(0.6, 1.8, "%while while")]}
    old = {"service.slab_columns": 3, "service.admitted": 3}
    run = served_run(spans, ops, counted(old, {k: v + 2 for k, v in old.items()}))
    for name in SERVED[:4]:
        assert reader(name).reduce(run) is None, name
    # idle 0 to 0.6 and 1.8 to 2.0: 0.5 + 0.1 under `bench:solve` alone,
    # 0.1 + 0.1 the slab's
    assert reader("served_unowned_share").reduce(run) == pytest.approx(
        100.0 * 0.6 / 0.8
    )


def test_the_served_readers_find_nothing_where_there_is_nothing():
    no_trace = types.SimpleNamespace(trace=None, traced_records=[])
    no_ops = served_run([(0.0, 1.0, "bench:solve")], {}, [{"info": {}}])
    never_idle = served_run(
        [(0.0, 1.0, "bench:solve")], {0: [(0.0, 1.0, "%while while")]},
        [{"info": {}}],
    )
    for run in (no_trace, no_ops, never_idle):
        for name in SERVED:
            assert reader(name).reduce(run) is None, name


def test_the_set_up_readers_read_the_programs_counters(monkeypatch):
    from partitionedarrays_jl_tpu import telemetry

    run = served_run(
        [(0.0, 1.0, "bench:solve")], {0: [(0.1, 0.9, "%while while")]}, []
    )
    counters = {
        "lowering.wall_us": 6_500_000, "lowering.detect_us": 4_000_000,
        "lowering.upload_us": 2_250_000, "lowering.sd.nnz": 7,
        "compile.trace_us": 400_000, "compile.lower_us": 100_000,
        "compile.backend_us": 2_000_000, "compile.cache_load_us": 500_000,
        "compile.programs": 9,
    }
    monkeypatch.setattr(
        telemetry, "counters",
        lambda prefix="": {k: v for k, v in counters.items() if k.startswith(prefix)},
    )
    assert reader("lower_s").reduce(run) == pytest.approx(6.5)
    assert reader("lower_upload_s").reduce(run) == pytest.approx(2.25)
    assert reader("compile_s").reduce(run) == pytest.approx(3.0)
    # a runtime that reports no retrieval leaves its counter out: the sum
    # of the rest
    del counters["compile.cache_load_us"]
    assert reader("compile_s").reduce(run) == pytest.approx(2.5)
    # the parent's program: other `lowering.*` counters, none of these
    counters = {"lowering.sd.nnz": 7, "lowering_cache.hit": 3}
    for name in SETUP:
        assert reader(name).reduce(run) is None, name
    # and no reader speaks in a run without a device trace
    counters = {"lowering.wall_us": 1, "lowering.upload_us": 1, "compile.trace_us": 1}
    for name in SETUP:
        assert reader(name).reduce(types.SimpleNamespace(trace=None)) is None
