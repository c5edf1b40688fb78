"""The cell `varcoef7_192.cg` rehearsed off the chip at 12^3 cells: the
new builder under the mix `cg_closed` through `run_cell` on the CPU backend,
what has to come out as not correct, a run whose operator fell to another
lowering, and the four readers PR 38 added on hand-made inputs.
"""
import importlib
import time
import types

import jax
import numpy as np
import pytest

from benchmark import run as R
from benchmark import trace as tr
from benchmark.builders import varcoef7
from benchmark.layer_metrics import (
    _scoped, stream_embed_share, stream_iter_hbm_roofline,
    stream_spmv_hbm_roofline, stream_window_reread,
)

HERE = R.os.path.dirname(R.os.path.abspath(__file__))
PEAKS = {"hbm_bytes_per_s": 819e9}
SEED = 2**31 + 38038  # the driver's seeds do not fit 32 signed bits
NEW = {
    "stream_spmv_hbm_roofline", "stream_iter_hbm_roofline",
    "stream_embed_share", "stream_window_reread",
}
READERS = (
    stream_spmv_hbm_roofline, stream_iter_hbm_roofline, stream_embed_share,
    stream_window_reread,
)
DOFS = 192**3


def tiny_cell():
    manifest = R.read_json(R.ROOT, "BENCHMARK.json")
    return types.SimpleNamespace(
        name="rehearsal.varcoef", chips=1,
        cfg=R.read_json(HERE, "configs", "varcoef7_12.json"),
        mix=R.read_json(R.HERE, "traffic", "cg_closed.json"),
        end_to_end=manifest["end_to_end"], per_layer=manifest["per_layer"],
    )


def drive(trace: bool = False, seed: int = SEED):
    return R.run_cell(
        tiny_cell(), jax.devices()[:1], PEAKS, seed, 0.3, trace,
        time.perf_counter(),
    )


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_a_rehearsed_run_is_correct(trace):
    result = drive(trace=trace)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    c = result["compared"]["residual_rel"]
    assert c["value"] is not None and c["value"] <= c["limit"]
    assert result["run"]["compiles_in_window"]["compile_events"] == 0
    # images of one field under the cube's symmetries: one spectrum
    assert result["run"]["iterations_max"] - result["run"]["iterations_min"] <= 1
    if trace:
        # no device plane in a CPU trace: the new readers invent no number
        assert set(result["metrics"]) == {"assemble_s", "first_solve_s"}
    else:
        assert {"setup_s", "solve_s"} <= set(result["metrics"])


def test_the_manifest_gives_the_cell_its_metrics():
    cell = R.load_cell(R.read_json(R.ROOT, "BENCHMARK.json"), "varcoef7_192.cg")
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "solve_s"]
    names = {m["name"] for m in cell.per_layer}
    assert NEW <= names and "cg_iter_hbm_roofline" not in names
    assert {"iter_us", "spmv_us", "scope_coverage", "device_idle_share"} <= names
    for m in cell.per_layer:
        if m["name"] in NEW:
            assert m["workloads"] == ["varcoef7_192.cg"] and m["moves"] == "solve_s"
    assert cell.cfg["chips"] == cell.chips == 1
    assert cell.cfg["dofs"] == DOFS and cell.cfg["stencil_points"] == 7
    assert cell.cfg["nnz"] == 7 * DOFS - 6 * 192**2 == varcoef7.count_nnz((192,) * 3)
    assert cell.mix["name"] == "cg_closed" and cell.mix["limits"]["residual_rel"] == 1e-4


def test_the_control_fails_and_the_witness_passes(monkeypatch):
    ctl = tiny_cell().mix["control"]
    monkeypatch.setattr(
        varcoef7.System, "solve",
        lambda self, req: self.control_solve(req, ctl["dtype"], ctl["maxiter"]),
    )
    result = drive()
    assert result["correct"] is False
    c = result["compared"]["residual_rel"]
    assert c["value"] > 3 * c["limit"]
    monkeypatch.setattr(
        varcoef7.System, "solve",
        lambda self, req: self.control_solve(req, "float32", 1500),
    )
    assert drive()["correct"] is True


def test_an_answer_altered_where_it_is_produced_fails(monkeypatch):
    tpu = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    from partitionedarrays_jl_tpu import telemetry

    hand_on = tpu._as_callers_array
    monkeypatch.setattr(
        tpu, "_as_callers_array",
        lambda fetched: hand_on(fetched) * np.float32(1.001),
    )
    before = telemetry.counters("solve")
    result = drive()
    after = telemetry.counters("solve")
    # the fault sat on the path the solves took
    assert after["solve.device_lifts"] > before.get("solve.device_lifts", 0)
    assert result["correct"] is False
    c = result["compared"]["residual_rel"]
    assert c["value"] > c["limit"]


def test_a_run_that_fell_to_another_lowering_answers_nothing(monkeypatch):
    """The builder looks at `lowering.stream.*` behind its first solve: a
    program that counts no streamed operator (here: the counter taken away)
    makes every solve raise, which `run.py` counts as unanswered."""
    tpu = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    monkeypatch.setattr(tpu, "_count_stream_lowering", lambda vals, plan: None)
    result = drive()
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1


def test_a_configuration_that_miscounts_its_operator_is_refused():
    cell = tiny_cell()
    cell.cfg["nnz"] += 1
    with pytest.raises(SystemExit, match="states nnz"):
        R.run_cell(cell, jax.devices()[:1], PEAKS, SEED, 0.3, False,
                   time.perf_counter())


# -- the four readers on hand-made inputs ------------------------------------------


def test_the_byte_counts_against_a_hand_count():
    """192^3 cells, float32: a pass is 28,311,552 bytes. The product: three
    face arrays, x, y. The iteration: ten vector passes and the faces."""
    one_pass = 4 * DOFS
    assert stream_spmv_hbm_roofline.stream_spmv_bytes(DOFS, 4) == 5 * one_pass == 141557760
    assert stream_iter_hbm_roofline.stream_iteration_bytes(DOFS, 4) == 13 * one_pass
    assert stream_spmv_hbm_roofline.least_spmv_s(DOFS, 4, 819e9) == (
        pytest.approx(172.84e-6, rel=1e-4)
    )
    assert stream_iter_hbm_roofline.least_iteration_s(DOFS, 4, 819e9) == (
        pytest.approx(449.39e-6, rel=1e-4)
    )


EMBED_IN = ("pa.axpy_sweep", "pa.spmv_local", "dia.embed", "pad:")
KERNEL = ("pa.axpy_sweep", "pa.spmv_local", "dia.stream", "custom-call:")
EMBED_OUT = ("pa.axpy_sweep", "pa.spmv_local", "dia.embed", "dynamic_update_slice:")
DOTS = ("pa.axpy_sweep", "pa.dot_allgather", "reduce_sum:")


def stream_ops():
    """Two iterations on [0, 10]: the operand's copy 0.5 s, the kernel 3 s,
    the product's copy 0.5 s, a dot 1 s each; the `while` keeps nothing."""
    return [
        (0.0, 10.0, ("pa.axpy_sweep", "while:")),
        (0.0, 0.5, EMBED_IN), (0.5, 3.5, KERNEL), (3.5, 4.0, EMBED_OUT), (4.0, 5.0, DOTS),
        (5.0, 5.5, EMBED_IN), (5.5, 8.5, KERNEL), (8.5, 9.0, EMBED_OUT), (9.0, 10.0, DOTS),
    ]


def make_run(device_ops, iterations, monkeypatch, cfg=None, mix=None):
    plain = {
        d: [(s, e, "/".join(sc) or "op") for s, e, sc in ops]
        for d, ops in device_ops.items()
    }
    run = types.SimpleNamespace(
        trace=tr.Trace(plain, [(0.0, 10.0, "bench:solve")]),
        traced_records=[{"info": {"iterations": n}} for n in iterations],
        cfg={"beta": {}} if cfg is None else cfg,
        mix={"entry": "cg", "preconditioner": None} if mix is None else mix,
        chips=1, dofs_per_chip=DOFS, itemsize=4, peaks=PEAKS,
    )
    # like the parser, through whatever `scopes_of` is at the time of the call
    monkeypatch.setattr(
        _scoped, "parse",
        lambda path: {
            d: [(s, e, _scoped.scopes_of("/".join(sc))) for s, e, sc in ops]
            for d, ops in device_ops.items()
        },
    )
    monkeypatch.setattr(tr, "find_xplane", lambda log_dir: "unused")
    return run


def test_the_parts_of_the_product_by_sub_scope():
    assert stream_embed_share.part_of(EMBED_IN) == "dia.embed"
    assert stream_embed_share.part_of(EMBED_OUT) == "dia.embed"
    assert stream_embed_share.part_of(KERNEL) == "pa.spmv_local"
    assert stream_embed_share.part_of(DOTS) is None


def test_the_shares_on_a_synthetic_trace(monkeypatch):
    run = make_run({0: stream_ops()}, [2], monkeypatch)
    # 2 s of copies of 8 s under pa.spmv_local
    assert stream_embed_share.reduce(run) == pytest.approx(25.0)
    # 8 s under pa.spmv_local over 2 iterations: 4 s a product
    assert stream_spmv_hbm_roofline.reduce(run) == pytest.approx(
        100.0 * 172.84e-6 / 4.0, rel=1e-4
    )
    # the devices were busy 10 s over 2 iterations
    run.records = run.traced_records
    assert stream_iter_hbm_roofline.reduce(run) == pytest.approx(
        100.0 * 449.39e-6 / 5.0, rel=1e-4
    )


def test_absent_scopes_and_other_configurations_read_none(monkeypatch):
    # a program that does not name the sub-scope (the parent's): no share
    other = [
        (s, e, tuple(c for c in sc if c != "dia.embed")) for s, e, sc in stream_ops()
    ]
    assert stream_embed_share.reduce(make_run({0: other}, [2], monkeypatch)) is None
    # a configuration that states no face coefficients; a preconditioned mix
    run = make_run({0: stream_ops()}, [2], monkeypatch, cfg={})
    assert stream_spmv_hbm_roofline.reduce(run) is None
    assert stream_iter_hbm_roofline.reduce(run) is None
    run = make_run(
        {0: stream_ops()}, [2], monkeypatch, mix={"entry": "pcg", "preconditioner": "gmg"}
    )
    assert stream_iter_hbm_roofline.reduce(run) is None


def test_the_new_readers_read_nothing_without_a_trace():
    run = types.SimpleNamespace(
        trace=None, traced_records=[], cfg={"beta": {}},
        mix={"entry": "cg", "preconditioner": None}, chips=1, dofs_per_chip=1,
        itemsize=4, peaks=PEAKS,
    )
    for m in READERS:
        assert m.reduce(run) is None, m.__name__


def test_reread_is_the_ratio_of_the_programs_counters(monkeypatch):
    assert stream_window_reread.reread({}) is None
    assert stream_window_reread.reread(
        {"lowering.stream.block_rows": 512, "lowering.stream.x_window_rows": 1096}
    ) == pytest.approx(214.0625)
    telemetry = importlib.import_module("partitionedarrays_jl_tpu.telemetry")
    run = make_run({0: stream_ops()}, [2], monkeypatch)
    monkeypatch.setattr(
        telemetry, "counters",
        lambda prefix: {"lowering.stream.block_rows": 8, "lowering.stream.x_window_rows": 32},
    )
    assert stream_window_reread.reduce(run) == 400.0
    # the XLA form has no block: diagonals and bytes only
    monkeypatch.setattr(
        telemetry, "counters",
        lambda prefix: {"lowering.stream.diagonals": 7, "lowering.stream.pallas": 0},
    )
    assert stream_window_reread.reduce(run) is None
