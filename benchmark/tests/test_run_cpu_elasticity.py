"""The cell `elasticity_tet_64.pcg` rehearsed off the chip at 8^3 nodes: the
new builder and mix through `run_cell` on the CPU backend, what has to come
out as not correct, and the three readers PR 28 added on hand-made inputs.
"""
import importlib
import time
import types

import jax
import numpy as np
import pytest

from benchmark import run as R
from benchmark import trace as tr
from benchmark.builders import elasticity_tet
from benchmark.layer_metrics import (
    _scoped, sd_fill, sd_gather_share, spmv_csr_roofline,
)

HERE = R.os.path.dirname(R.os.path.abspath(__file__))
PEAKS = {"hbm_bytes_per_s": 819e9}
SEED = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits
NEW = {"spmv_csr_roofline", "sd_gather_share", "sd_fill"}


def tiny_cell():
    manifest = R.read_json(R.ROOT, "BENCHMARK.json")
    return types.SimpleNamespace(
        name="rehearsal.elasticity", chips=1,
        cfg=R.read_json(HERE, "configs", "elasticity_tet_8.json"),
        mix=R.read_json(R.HERE, "traffic", "jacobi_pcg_closed.json"),
        end_to_end=manifest["end_to_end"], per_layer=manifest["per_layer"],
    )


def drive(trace: bool = False, seed: int = SEED):
    cell = tiny_cell()
    return R.run_cell(
        cell, jax.devices()[:1], PEAKS, seed, 0.3, trace, time.perf_counter()
    )


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_a_rehearsed_run_is_correct(trace):
    result = drive(trace=trace)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    c = result["compared"]["residual_rel"]
    assert c["value"] is not None and c["value"] <= c["limit"]
    assert result["run"]["compiles_in_window"]["compile_events"] == 0
    # scaling a system leaves its Krylov work
    assert result["run"]["iterations_max"] - result["run"]["iterations_min"] <= 2
    if trace:
        # no device plane in a CPU trace: the new readers invent no number
        assert set(result["metrics"]) == {"assemble_s", "first_solve_s"}
    else:
        assert {"setup_s", "solve_s"} <= set(result["metrics"])


def test_the_manifest_gives_the_cell_its_metrics():
    cell = R.load_cell(R.read_json(R.ROOT, "BENCHMARK.json"), "elasticity_tet_64.pcg")
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "solve_s"]
    names = {m["name"] for m in cell.per_layer}
    assert NEW <= names and "cg_iter_hbm_roofline" not in names
    assert cell.cfg["chips"] == cell.chips == 1
    interior, boundary = 62**3, 64**3 - 62**3
    assert cell.cfg["nnz"] == interior * 3 * 39 + boundary * 3
    assert cell.cfg["dofs"] == 3 * 64**3 and cell.cfg["tets"] == 5 * 63**3


def test_the_control_fails_and_the_witness_passes(monkeypatch):
    ctl = tiny_cell().mix["control"]
    monkeypatch.setattr(
        elasticity_tet.System, "solve",
        lambda self, req: self.control_solve(req, ctl["dtype"], ctl["maxiter"]),
    )
    result = drive()
    assert result["correct"] is False
    c = result["compared"]["residual_rel"]
    assert c["value"] > 3 * c["limit"]
    monkeypatch.setattr(
        elasticity_tet.System, "solve",
        lambda self, req: self.control_solve(req, "float32", 1500),
    )
    assert drive()["correct"] is True


def test_an_answer_altered_where_it_is_produced_fails(monkeypatch):
    tpu = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    lift = tpu._host_frame_to_pvector
    monkeypatch.setattr(
        tpu, "_host_frame_to_pvector",
        lambda host, rows, layout: lift(host * np.float32(1.001), rows, layout),
    )
    result = drive()
    assert result["correct"] is False
    c = result["compared"]["residual_rel"]
    assert c["value"] > c["limit"]


def test_a_configuration_that_miscounts_its_operator_is_refused():
    cell = tiny_cell()
    cell.cfg["nnz"] += 1
    with pytest.raises(SystemExit, match="states nnz"):
        R.run_cell(cell, jax.devices()[:1], PEAKS, SEED, 0.3, False,
                   time.perf_counter())


def test_the_same_seed_gives_the_same_load_cases_and_another_seed_others():
    a, b, c = (elasticity_tet.load_factors(s, 4, [0.5, 2.0])
               for s in (SEED, SEED, SEED + 1))
    assert a == b != c and len(set(a)) == 4
    assert all(0.5 <= abs(x) < 2.0 for x in a)
    assert {x > 0 for s in range(8) for x in elasticity_tet.load_factors(s, 4, [0.5, 2.0])} == {True, False}


# -- the three readers on hand-made inputs ----------------------------------------


def test_the_csr_byte_count_against_a_hand_count_at_4_cubed():
    """4^3 nodes: 8 interior nodes, 56 on the boundary. Every interior node
    of a 4^3 grid has its 18 face-diagonal and axis neighbours only if its
    cell parity says so; the reference counts the stored entries, and the
    bytes follow by hand: values and column indices once, x and y once."""
    coords, tets, boundary = elasticity_tet.mesh((4, 4, 4), 0.2, 0)
    A = elasticity_tet.assemble_reference(coords, tets, boundary, 1.0, 1.0)
    assert A.shape == (192, 192) and int(boundary.sum()) == 56
    by_hand = A.nnz * 4 + A.nnz * 4 + 192 * 4 + 192 * 4
    assert spmv_csr_roofline.csr_spmv_bytes(A.nnz, 192, 4) == by_hand
    # the cell's own: 229.9 MB, 280.8 us at 819 GB/s
    assert spmv_csr_roofline.csr_spmv_bytes(27955824, 786432, 4) == 229938048
    assert spmv_csr_roofline.least_spmv_s(27955824, 786432, 4, 819e9) == (
        pytest.approx(280.75e-6, rel=1e-4)
    )


GATHER = ("pa.axpy_sweep", "pa.spmv_local", "sd.gather", "gather:")
EINSUM = ("pa.axpy_sweep", "pa.spmv_local", "sd.einsum", "dot_general:")
EMBED = ("pa.axpy_sweep", "pa.spmv_local", "scatter:")
DOTS = ("pa.axpy_sweep", "pa.dot_allgather", "reduce_sum:")


def sd_ops():
    """Two iterations on [0, 10]: gathers 1 s, products 3 s, the embedding
    of the product 0.5 s, a dot 0.5 s each; the `while` keeps the rest."""
    return [
        (0.0, 10.0, ("pa.axpy_sweep", "while:")),
        (0.0, 1.0, GATHER), (1.0, 4.0, EINSUM), (4.0, 4.5, EMBED), (4.5, 5.0, DOTS),
        (5.0, 6.0, GATHER), (6.0, 9.0, EINSUM), (9.0, 9.5, EMBED), (9.5, 10.0, DOTS),
    ]


def make_run(device_ops, iterations, monkeypatch, cfg=None):
    plain = {
        d: [(s, e, "/".join(sc) or "op") for s, e, sc in ops]
        for d, ops in device_ops.items()
    }
    run = types.SimpleNamespace(
        trace=tr.Trace(plain, [(0.0, 10.0, "bench:solve")]),
        traced_records=[{"info": {"iterations": n}} for n in iterations],
        cfg=cfg or {}, chips=1, dofs_per_chip=786432, itemsize=4, peaks=PEAKS,
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


def test_components_behind_the_first_pa_one_are_kept():
    f = sd_gather_share.components_from_pa
    assert f("jit(fn)/while/body/pa.axpy_sweep/pa.spmv_local/sd.gather/gather:") == GATHER
    assert f("jit(fn)/while:") == () == f("")
    assert sd_gather_share.part_of(GATHER) == "sd.gather"
    assert sd_gather_share.part_of(EINSUM) == sd_gather_share.part_of(EMBED) == "pa.spmv_local"
    assert sd_gather_share.part_of(DOTS) is None


def test_gather_share_and_roofline_on_a_synthetic_trace(monkeypatch):
    run = make_run({0: sd_ops()}, [2], monkeypatch, cfg={"nnz": 27955824})
    # 2 s of gathers of 9 s under pa.spmv_local
    assert sd_gather_share.reduce(run) == pytest.approx(100.0 * 2.0 / 9.0)
    # 9 s under pa.spmv_local over 2 iterations: 4.5 s a product
    assert spmv_csr_roofline.reduce(run) == pytest.approx(100.0 * 280.75e-6 / 4.5, rel=1e-4)
    # a stencil configuration states no stored operator; another lowering
    # has no such sub-scope
    run = make_run({0: sd_ops()}, [2], monkeypatch, cfg={})
    assert spmv_csr_roofline.reduce(run) is None
    other = [(s, e, tuple(c for c in sc if c != "sd.gather")) for s, e, sc in sd_ops()]
    assert sd_gather_share.reduce(make_run({0: other}, [2], monkeypatch)) is None


def test_the_new_readers_read_nothing_without_a_trace():
    run = types.SimpleNamespace(
        trace=None, traced_records=[], cfg={"nnz": 1}, chips=1, dofs_per_chip=1,
        itemsize=4, peaks=PEAKS,
    )
    for m in (sd_fill, sd_gather_share, spmv_csr_roofline):
        assert m.reduce(run) is None, m.__name__


def test_fill_is_the_ratio_of_the_programs_counters(monkeypatch):
    assert sd_fill.fill({}) is None
    assert sd_fill.fill({"lowering.sd.nnz": 3, "lowering.sd.dense_entries": 60}) == 5.0
    telemetry = importlib.import_module("partitionedarrays_jl_tpu.telemetry")
    run = make_run({0: sd_ops()}, [2], monkeypatch)
    monkeypatch.setattr(
        telemetry, "counters",
        lambda prefix: {"lowering.sd.nnz": 1, "lowering.sd.dense_entries": 8},
    )
    assert sd_fill.reduce(run) == 12.5
    monkeypatch.setattr(telemetry, "counters", lambda prefix: {})
    assert sd_fill.reduce(run) is None  # the operator did not lower to SD
