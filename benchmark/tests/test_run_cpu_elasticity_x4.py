"""The cell `elasticity_tet_64_x4.pcg` rehearsed off the chip at 8^3 nodes on
four parts, one a virtual CPU device: the PR 28 builder and mix through
`run_cell`, what has to come out as not correct where the four parts are
what is broken, and the two readers PR 32 added on hand-made inputs.
"""
import importlib
import time
import types

import jax
import numpy as np
import pytest

from benchmark import run as R
from benchmark import trace as tr
from benchmark.layer_metrics import (
    exchange_fill, halo_index_share, halo_us, oh_rows_us,
)
from benchmark.layer_metrics.sd_gather_share import components_from_pa

from test_oh_rows import make_run  # a synthetic trace as a run's

HERE = R.os.path.dirname(R.os.path.abspath(__file__))
PEAKS = {"hbm_bytes_per_s": 819e9}
SEED = 2**31 + 54321  # the driver's seeds do not fit 32 signed bits
CELL = "elasticity_tet_64_x4.pcg"
NEW = {"exchange_fill", "halo_index_share"}


def tiny_cell():
    """The cell as the manifest gives it, at the rehearsal's size."""
    cell = R.load_cell(R.read_json(R.ROOT, "BENCHMARK.json"), CELL)
    cell.cfg = R.read_json(HERE, "configs", "elasticity_tet_8_x4.json")
    return cell


def drive(trace: bool = False, seed: int = SEED):
    cell = tiny_cell()
    return R.run_cell(
        cell, jax.devices()[:4], PEAKS, seed, 0.3, trace, time.perf_counter()
    )


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_a_rehearsed_run_is_correct(trace):
    telemetry = importlib.import_module("partitionedarrays_jl_tpu.telemetry")
    before = telemetry.counters("solve")
    result = drive(trace=trace)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1 and result["device"]["count"] == 4
    c = result["compared"]["residual_rel"]
    assert c["value"] is not None and c["value"] <= c["limit"]
    assert result["run"]["compiles_in_window"]["compile_events"] == 0
    # scaling a system leaves its Krylov work
    assert result["run"]["iterations_max"] - result["run"]["iterations_min"] <= 2
    # every vector of every solve was packed and lifted on the devices
    after = telemetry.counters("solve")
    assert after["solve.device_lifts"] > before.get("solve.device_lifts", 0)
    for path in ("solve.host_packs", "solve.host_lifts"):
        assert after[path] == before.get(path, 0)
    if trace:
        # no device plane in a CPU trace: the readers invent no number
        assert set(result["metrics"]) == {"assemble_s", "first_solve_s"}
    else:
        assert set(result["metrics"]) == {"setup_s", "solve_s"}


def test_the_manifest_gives_the_cell_its_metrics():
    manifest = R.read_json(R.ROOT, "BENCHMARK.json")
    cell = R.load_cell(manifest, CELL)
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "solve_s"]
    names = {m["name"] for m in cell.per_layer}
    assert NEW | {
        "halo_us", "collective_share", "oh_rows_us", "spmv_us", "sd_gather_share",
        "sd_fill", "spmv_csr_roofline", "iter_us", "scope_coverage",
    } <= names
    assert not names & {"cg_iter_hbm_roofline", "vcycle_coarse_share"}
    for m in manifest["per_layer"]:
        if m["name"] in NEW:  # listed for the new cell only, after all others
            assert m["workloads"] == [CELL] and m["layer"] == "halo exchange"
            assert m["moves"] == "solve_s" and m["unit"] == "%"
    assert [m["name"] for m in manifest["per_layer"][-2:]] == sorted(NEW)
    assert manifest["workloads"][-1]["name"] == CELL
    # the one-part cell's operator, cut in four
    one = R.load_cell(manifest, "elasticity_tet_64.pcg").cfg
    cfg = cell.cfg
    assert cfg["chips"] == cell.chips == 4 and cfg["part_grid"] == [4]
    for key in ("builder", "nodes_per_dim", "dofs", "nnz", "tets", "dofs_per_node",
                "dtype", "tol", "boundary", "guarantees", "reduced"):
        assert cfg[key] == one[key], key
    assert {**cfg["assumed"], "partition": None} == {**one["assumed"], "partition": None}
    gives = cfg["partition_gives"]
    assert 4 * gives["owned_dofs_per_part"] == cfg["dofs"]
    assert sum(gives["ghost_dofs_by_owner"]) == gives["ghost_dofs_per_part"]
    assert 4 * sum(gives["stored_entries_per_part"].values()) == cfg["nnz"]
    config = next(c for c in manifest["configs"] if c["name"] == cfg["name"])
    assert config["source"] == cfg["source"] and len(cfg["source"]) <= 200


def test_the_exchange_between_chips_left_out_fails(monkeypatch):
    """The generic body patched to return its operand: every ghost stays
    what the pack put there, the start vector's."""
    tpu = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    planned = []

    def no_exchange(plan, combine, abft=False):
        planned.append(type(plan).__name__)
        return lambda xv, si, sm, ri: xv

    monkeypatch.setattr(tpu, "_shard_exchange", no_exchange)
    result = drive()
    assert "DeviceExchangePlan" in planned  # the fault sat on the path taken
    assert result["correct"] is False


def test_an_answer_altered_where_the_device_path_produces_it_fails(monkeypatch):
    tpu = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    hand_on = tpu._as_callers_array
    monkeypatch.setattr(
        tpu, "_as_callers_array",
        lambda fetched: hand_on(fetched) * np.float32(1.001),
    )
    result = drive()
    assert result["correct"] is False
    c = result["compared"]["residual_rel"]
    assert c["value"] > c["limit"]


# -- the two readers on hand-made inputs -------------------------------------------

HALO = ("pa.axpy_sweep", "pa.spmv_local", "pa.halo_exchange")
PACK = HALO + ("ex.pack", "gather:")
UNPACK = HALO + ("ex.unpack", "scatter:")
WIRE = HALO + ("ppermute:",)
OH = ("pa.axpy_sweep", "pa.spmv_local", "oh", "scatter-add:")
EINSUM = ("pa.axpy_sweep", "pa.spmv_local", "sd.einsum", "dot_general:")
# a component named like the sub-scope under another phase is no index work
OTHER = ("pa.axpy_sweep", "pa.dot_allgather", "ex.pack", "mul:")


def pcg_ops():
    """Two iterations on [0, 10]: the products 2 s, a round's gather 0.5 s,
    its permute 0.25 s, its scatter 0.75 s, the boundary rows 0.5 s and,
    under the dots, an op whose name has a sub-scope's component, 0.5 s;
    the `while` keeps the rest."""
    return [
        (0.0, 10.0, ("pa.axpy_sweep", "while:")),
        (0.0, 2.0, EINSUM), (2.0, 2.5, PACK), (2.5, 2.75, WIRE), (2.75, 3.5, UNPACK),
        (3.5, 4.0, OH), (4.0, 4.5, OTHER),
        (5.0, 7.0, EINSUM), (7.0, 7.5, PACK), (7.5, 7.75, WIRE), (7.75, 8.5, UNPACK),
        (8.5, 9.0, OH), (9.0, 9.5, OTHER),
    ]


def test_index_work_is_the_ops_behind_the_two_sub_scopes():
    assert halo_index_share.index_work(PACK) is True
    assert halo_index_share.index_work(UNPACK) is True
    assert halo_index_share.index_work(WIRE) is False
    for other in (OH, EINSUM, OTHER, ()):
        assert halo_index_share.index_work(other) is None, other
    f = components_from_pa
    assert f("jit(fn)/shard_map/while/body/" + "/".join(PACK)) == PACK


def test_index_share_on_a_synthetic_trace(monkeypatch):
    run = make_run({0: pcg_ops(), 1: pcg_ops()}, [2], monkeypatch)
    # on each device 2.5 s of gathers and scatters of 3 s under the phase
    assert halo_index_share.reduce(run) == pytest.approx(100.0 * 2.5 / 3.0)
    # `halo_us` keeps counting all three, `oh_rows_us` reads the new form
    run = make_run({0: pcg_ops(), 1: pcg_ops()}, [2], monkeypatch)
    assert halo_us.reduce(run) == pytest.approx(1.5e6)
    run = make_run({0: pcg_ops(), 1: pcg_ops()}, [2], monkeypatch)
    assert oh_rows_us.reduce(run) == pytest.approx(0.5e6)
    # the parent's program names neither sub-scope: nothing to read
    parent = [
        (s, e, tuple(c for c in sc if c not in halo_index_share.PARTS))
        for s, e, sc in pcg_ops()
    ]
    assert halo_index_share.reduce(make_run({0: parent}, [2], monkeypatch)) is None


def test_fill_is_the_ratio_of_the_programs_counters(monkeypatch):
    assert exchange_fill.fill({}) is None
    assert exchange_fill.fill(
        {"exchange.plan.slots": 47988, "exchange.plan.padded_slots": 71424}
    ) == pytest.approx(100.0 * 11997 / 17856)
    telemetry = importlib.import_module("partitionedarrays_jl_tpu.telemetry")
    run = make_run({0: pcg_ops()}, [2], monkeypatch)
    monkeypatch.setattr(
        telemetry, "counters",
        lambda prefix: {"exchange.plan.slots": 3, "exchange.plan.padded_slots": 4},
    )
    assert exchange_fill.reduce(run) == 75.0
    monkeypatch.setattr(telemetry, "counters", lambda prefix: {})
    assert exchange_fill.reduce(run) is None  # a box plan, one part, or the parent


def test_the_new_readers_read_nothing_without_a_trace():
    for trace in (None, tr.Trace({}, [])):
        run = types.SimpleNamespace(trace=trace, traced_records=[])
        for m in (exchange_fill, halo_index_share):
            assert m.reduce(run) is None, m.__name__
    # a device plane that holds no op: no scope to read
    run = types.SimpleNamespace(
        trace=tr.Trace({0: []}, [(0.0, 1.0, "bench:solve")]), traced_records=[]
    )
    assert halo_index_share.reduce(run) is None


def test_the_program_writes_the_names_the_readers_look_for():
    T = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    assert halo_index_share.PHASE == T.SCOPE_HALO
    assert halo_index_share.PARTS == (T.SCOPE_EX_PACK, T.SCOPE_EX_UNPACK)
    assert (oh_rows_us.PHASE, oh_rows_us.PART) == (T.SCOPE_SPMV, T.SCOPE_OH)
