"""The served cell rehearsed off the chip: the whole of a run through
`run_cell` at 16^3 cells, one part on one CPU device, the service's worker
thread and the open-loop driver as on the chip (arrivals far faster: a slab
takes milliseconds here). What has to come out as NOT correct does: two
columns of a slab swapped on return, an answer scaled by 1.001 where it is
produced, a request that is never answered, and the control (the plain
reference CG in bfloat16 in the service's place); the float32 witness
passes.
"""
import importlib
import json
import time
import types

import jax
import numpy as np
import pytest

from benchmark import run as R
from benchmark.builders import poisson7_served
from benchmark.tests.test_run_cpu import HERE, PEAKS, ROOT, SEED

#: arrivals a second, for 0.15 s: some 45 requests, fewer than the queue's
#: 64 whatever the machine sustains, and close enough for slabs of 2 to 4
RATE = 300.0


def tiny_cell(**mix_changes):
    manifest = R.read_json(ROOT, "BENCHMARK.json")
    mix = R.read_json(R.HERE, "traffic", "served_k4_open.json")
    mix["arrivals"] = dict(mix["arrivals"], rate_per_s=RATE)
    mix["answer_timeout_s"] = 20.0
    mix.update(mix_changes)
    return types.SimpleNamespace(
        name="rehearsal.served_k4", chips=1,
        cfg=R.read_json(HERE, "configs", "poisson7_16_served.json"), mix=mix,
        end_to_end=manifest["end_to_end"], per_layer=manifest["per_layer"],
    )


def drive(trace=False, seconds=0.15, seed=SEED, **mix_changes):
    cell = tiny_cell(**mix_changes)
    return R.run_cell(
        cell, jax.devices()[:1], PEAKS, seed, seconds, trace, time.perf_counter()
    )


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_a_rehearsed_run_is_correct_and_its_line_is_the_contracts(trace, capsys, monkeypatch):
    handed = []
    fill = importlib.import_module("benchmark.layer_metrics.slab_fill")
    monkeypatch.setattr(
        fill, "reduce", lambda run: handed.extend(run.traced_records) or None
    )
    result = drive(trace=trace)
    if trace:
        # every traced request's info carries the service's counters as its
        # client saw them, which only grow: what `window_counters` reads
        assert len(handed) >= 2
        seen = [r["info"]["service_counters"] for r in handed]
        for c in seen:
            assert c["at_answer"]["service.slab_columns"] > c["at_submit"].get("service.slab_columns", 0)
        assert seen[-1]["at_answer"]["service.slabs"] > seen[0]["at_submit"]["service.slabs"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 30  # the schedule's arrivals, all of them
    assert list(result)[-1] == "compared"
    for c in result["compared"].values():
        assert c["value"] is not None and c["value"] <= c["limit"]
    # every width was warmed in set-up: the window compiled nothing
    assert result["run"]["compiles_in_window"]["compile_events"] == 0
    assert result["run"]["checked"] == 6
    names = set(result["metrics"])
    if trace:
        # no device plane in a CPU trace: only the host-clock readers speak
        assert names == {"assemble_s", "first_solve_s"}
    else:
        assert {"setup_s", "solve_s", "solve_p95_s"} <= names
    json.dumps(result)
    err = capsys.readouterr().err
    assert "bench: served_k4_open: generator lateness max " in err
    stats = eval(err.split("service stats ")[1].splitlines()[0])
    assert stats["rejected"] == stats["ejected"] == stats["retried_solo"] == 0
    # the warm-up's slabs of widths 4, 3, 2, 1 and its own request, then
    # the window's: fewer slabs than requests means the batcher batched
    assert stats["completed"] == stats["admitted"] == result["attempted"] + 11
    assert stats["slabs"] - 5 < result["attempted"]


def test_the_same_schedule_in_every_run_and_other_inputs_by_seed():
    a, b = drive(seed=SEED), drive(seed=SEED + 1)
    assert a["attempted"] == b["attempted"]  # `arrival_seed`, not `--seed`
    assert a["compared"]["residual_rel"] != b["compared"]["residual_rel"]


def test_the_readers_of_the_slabs_find_nothing_without_the_program_side(monkeypatch):
    """On a parent of PR 34 the program opens no `pa:service:slab` span and
    counts no `service.slab_columns`: the new readers return None and do
    not raise, on a trace with device ops too; nor where the records carry
    no counters at all (another builder's)."""
    tr = importlib.import_module("benchmark.trace")
    slabs = importlib.import_module("benchmark.layer_metrics._slabs")
    run = types.SimpleNamespace(
        trace=tr.Trace({0: [(0.0, 1.0, "%fusion fusion")]}, [(0.0, 1.0, "bench:solve")]),
        cfg={"service": {"kmax": 4}}, peaks=PEAKS, dofs_per_chip=4096, itemsize=4,
        traced_records=[{"info": {"service_counters": {
            "at_submit": {"service.slabs": 3}, "at_answer": {"service.slabs": 4},
        }}}],
    )
    monkeypatch.setattr(slabs, "read_slab_spans", lambda path: [])
    monkeypatch.setattr(tr, "find_xplane", lambda d: "nowhere")
    bare = types.SimpleNamespace(**{**vars(run), "traced_records": [{"info": {}}]})
    for name in ("slab_fill", "queue_wait_ms", "slab_stage_ms", "slab_fetch_ms",
                 "block_iter_us", "block_iter_hbm_roofline"):
        reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
        assert reader.reduce(run) is None and reader.reduce(bare) is None, name


def test_the_readers_of_the_slabs_on_a_made_up_trace(monkeypatch):
    """Two slabs, of widths 4 and 2, 100 trips each, the device busy 0.8 s
    and 0.5 s inside them: the numbers by hand."""
    tr = importlib.import_module("benchmark.trace")
    slabs = importlib.import_module("benchmark.layer_metrics._slabs")
    spans = [
        (0.0, 3.0, "bench:solve"),
        (0.1, 1.1, "pa:service:slab"), (0.1, 0.2, "pa:block-cg:stage"),
        (1.0, 1.05, "pa:block-cg:fetch"),
        (1.2, 2.0, "pa:service:slab"), (1.2, 1.26, "pa:block-cg:stage"),
        (1.9, 1.93, "pa:block-cg:fetch"),
        (2.5, 2.9, "pa:block-cg:stage"),  # outside every whole slab
    ]
    ops = {0: [(0.2, 1.0, "%while while"), (1.3, 1.8, "%while while")]}
    at_open = {"service.slabs": 5, "service.slab_columns": 11, "service.queue_wait_us": 1000}
    at_close = {"service.slabs": 15, "service.slab_columns": 41, "service.queue_wait_us": 61000}
    late = {"service.slabs": 99, "service.slab_columns": 99, "service.queue_wait_us": 99}
    run = types.SimpleNamespace(
        trace=tr.Trace(ops, sorted(spans)), cfg={"service": {"kmax": 4}},
        peaks={"hbm_bytes_per_s": 800e9}, dofs_per_chip=10**6, itemsize=4,
        # the first traced request's submission to the last one's answer
        traced_records=[
            {"info": {"service_counters": {"at_submit": at_open, "at_answer": late}}},
            {"info": {"service_counters": {"at_submit": late, "at_answer": late}}},
            {"info": {"service_counters": {"at_submit": late, "at_answer": at_close}}},
        ],
    )
    monkeypatch.setattr(tr, "find_xplane", lambda d: "nowhere")
    monkeypatch.setattr(
        slabs, "read_slab_spans",
        lambda path: [(0.1, 1.1, 4, 100), (1.2, 2.0, 2, 100), (2.1, 2.2, 1, 0)],
    )

    def read(name):
        return importlib.import_module(f"benchmark.layer_metrics.{name}").reduce(run)

    assert read("slab_fill") == pytest.approx(100.0 * 30 / (10 * 4))
    assert read("queue_wait_ms") == pytest.approx(60.0 / 30)
    assert read("slab_stage_ms") == pytest.approx(1e3 * (0.1 + 0.06) / 2)
    assert read("slab_fetch_ms") == pytest.approx(1e3 * (0.05 + 0.03) / 2)
    assert read("block_iter_us") == pytest.approx(1e6 * 1.3 / 200)
    # (100 x 4 + 100 x 2) x 10 passes x 4 B x 1e6 DOFs = 24 GB: 0.03 s at 800 GB/s
    assert read("block_iter_hbm_roofline") == pytest.approx(100.0 * 0.03 / 1.3)


# ---------------------------------------------------------------------------
# what has to come out as NOT correct
# ---------------------------------------------------------------------------


def test_two_columns_of_a_slab_swapped_on_return_fail(monkeypatch):
    """Each of the two requests gets a converged answer, of the other's
    right-hand side."""
    service = importlib.import_module("partitionedarrays_jl_tpu.service.service")
    block_solve = service.SolveService._block_solve
    swapped = {"slabs": 0}

    def swapping(self, B, X0, tol, maxiter):
        xs, info = block_solve(self, B, X0, tol, maxiter)
        if self._worker is not None and len(xs) >= 2:  # the window's slabs
            xs[0], xs[1] = xs[1], xs[0]
            swapped["slabs"] += 1
        return xs, info

    monkeypatch.setattr(service.SolveService, "_block_solve", swapping)
    result = drive()
    assert swapped["slabs"] > 5
    assert result["failed"] == 0  # every request was answered, and converged
    assert result["correct"] is False
    c = result["compared"]["residual_rel"]
    assert c["value"] > 1000 * c["limit"]


def test_an_answer_altered_where_it_is_produced_fails(monkeypatch):
    tpu = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    lift = tpu._host_frame_to_pvector
    monkeypatch.setattr(
        tpu, "_host_frame_to_pvector",
        lambda host, rows, layout: lift(host * np.float32(1.001), rows, layout),
    )
    result = drive()
    assert result["failed"] == 0 and result["correct"] is False
    c = result["compared"]["residual_rel"]
    assert c["value"] > c["limit"]


def test_a_request_that_is_never_answered_fails_and_the_run_ends(monkeypatch):
    service = importlib.import_module("partitionedarrays_jl_tpu.service.service")
    finish = service.SolveService._finish

    def losing(self, req, x, col_info, via=None):
        if req.id == 25:  # one request of the window
            return
        finish(self, req, x, col_info, via)

    monkeypatch.setattr(service.SolveService, "_finish", losing)
    t0 = time.perf_counter()
    result = drive(answer_timeout_s=1.5)
    assert 1.5 < time.perf_counter() - t0 < 30.0
    assert result["failed"] == 1 and result["correct"] is False
    assert result["compared"]["unanswered"] == {"value": 1, "limit": 0}
    assert result["compared"]["residual_rel"]["value"] <= 1e-4  # the others' are sound


def test_the_control_fails_and_the_witness_passes(monkeypatch):
    """The reference CG in the service's place, through the same check: in
    bfloat16 it fails the limit, in the configuration's float32 it passes."""
    ctl = tiny_cell().mix["control"]
    monkeypatch.setattr(
        poisson7_served.System, "solve",
        lambda self, req: self.control_solve(req, ctl["dtype"], ctl["maxiter"]),
    )
    result = drive(arrivals={"arrival_seed": 11, "rate_per_s": 40.0})
    assert result["correct"] is False
    c = result["compared"]["residual_rel"]
    assert c["value"] > 3 * c["limit"]
    monkeypatch.setattr(
        poisson7_served.System, "solve",
        lambda self, req: self.control_solve(req, "float32", 1500),
    )
    assert drive(arrivals={"arrival_seed": 11, "rate_per_s": 40.0})["correct"] is True


def test_a_program_without_wait_ends_the_run_at_once(monkeypatch):
    """The parent of PR 34: the builder says what is missing and the
    process ends with another exit code than 0, before anything is built."""
    request = importlib.import_module("partitionedarrays_jl_tpu.service.request")
    monkeypatch.delattr(request.SolveRequest, "wait")
    with pytest.raises(SystemExit, match="no blocking wait"):
        drive()
