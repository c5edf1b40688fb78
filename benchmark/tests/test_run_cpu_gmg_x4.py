"""The cell `poisson7_192_x4.gmg_pcg` rehearsed off the chip at 16^3 cells
a part on a (2,2,1) grid, one part a virtual CPU device: the `poisson7`
builder and the `gmg_pcg_closed` mix as they stand, through `run_cell`;
which transfer each level staged; and what has to come out as not correct
where the four parts are what is broken.
"""
import importlib
import time

import jax
import pytest

from benchmark import run as R
from benchmark.builders import poisson7

HERE = R.os.path.dirname(R.os.path.abspath(__file__))
PEAKS = {"hbm_bytes_per_s": 819e9}
SEED = 2**31 + 44044  # the driver's seeds do not fit 32 signed bits
CELL = "poisson7_192_x4.gmg_pcg"


def tiny_cell():
    """The cell as the manifest gives it, at the rehearsal's size."""
    cell = R.load_cell(R.read_json(R.ROOT, "BENCHMARK.json"), CELL)
    cell.cfg = R.read_json(HERE, "configs", "poisson7_16_x4.json")
    return cell


def drive(trace: bool = False, seed: int = SEED):
    cell = tiny_cell()
    return R.run_cell(
        cell, jax.devices()[:4], PEAKS, seed, 0.3, trace, time.perf_counter()
    )


def transfer_counters():
    telemetry = importlib.import_module("partitionedarrays_jl_tpu.telemetry")
    return telemetry.counters("gmg.transfer")


def test_the_cell_is_the_manifests():
    cell = R.load_cell(R.read_json(R.ROOT, "BENCHMARK.json"), CELL)
    assert cell.chips == 4 and cell.cfg["part_grid"] == [2, 2, 1]
    assert cell.cfg["name"] == "poisson7_192_x4_gmg"
    assert cell.mix["preconditioner"] == "gmg" and cell.mix["entry"] == "pcg"
    names = {m["name"] for m in cell.per_layer}
    assert {
        "transfer_matrix_free_share", "vcycle_halo_share",
        "transfer_hbm_roofline", "vcycle_coarse_share", "halo_us",
        "dots_us", "body_update_us",
    } <= names
    # the coded counters record the fused CG body's fold, which pa.pcg
    # does not run: they would read for a kernel the cell never calls
    assert not {"coded_pfold_share", "coded_window_reread"} & names
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "solve_s", "solve_p95_s"}


def test_the_configuration_is_the_x4_problem_with_its_solver_stated():
    """`poisson7_192_x4_gmg` poses the problem of `poisson7_192_x4`, key
    for key, and adds only the solver the mix runs: a change to the grid
    or the operator of one and not the other would split the two cells
    that measure it."""
    plain = R.read_json(R.ROOT, "benchmark", "configs", "poisson7_192_x4.json")
    gmg = R.read_json(R.ROOT, "benchmark", "configs", "poisson7_192_x4_gmg.json")
    told = {"name", "source", "solver", "reduced", "reduced_from"}
    assert set(gmg) - told == set(plain) - told
    for key in set(plain) - told:
        assert gmg[key] == plain[key], key
    assert gmg["reduced"] == plain["reduced"] + ["solver"]
    assert gmg["solver"]["entry"] == "pa.pcg"
    mix = R.read_json(R.ROOT, "benchmark", "traffic", "gmg_pcg_closed.json")
    assert mix["entry"] == "pcg" and mix["preconditioner"] == "gmg"


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_a_rehearsed_run_is_correct_and_its_transfers_are_matrix_free(trace):
    before = transfer_counters()
    result = drive(trace=trace)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1 and result["device"]["count"] == 4
    c = result["compared"]["residual_rel"]
    assert c["value"] is not None and c["value"] <= c["limit"]
    assert result["run"]["compiles_in_window"]["compile_events"] == 0
    staged = {
        k: v - before.get(k, 0) for k, v in transfer_counters().items()
    }
    # level 0 (the 7-point operator, faces only) separable, level 1 (a
    # Galerkin operator, the full shell) the one-pass stencil
    assert staged["gmg.transfer.levels"] == 2, staged
    assert staged["gmg.transfer.separable"] == 1, staged
    assert staged["gmg.transfer.stencil"] == 1, staged
    assert staged.get("gmg.transfer.operator", 0) == 0
    assert staged.get("gmg.transfer.assembled", 0) == 0


def test_the_control_fails(monkeypatch):
    """The reference CG in bfloat16, put in the program's place."""
    ctl = tiny_cell().mix["control"]
    monkeypatch.setattr(
        poisson7.System, "solve",
        lambda self, req: self.control_solve(req, ctl["dtype"], ctl["maxiter"]),
    )
    result = drive()
    assert result["correct"] is False
    c = result["compared"]["residual_rel"]
    assert c["value"] > 3 * c["limit"]


def faceless(monkeypatch):
    """The V-cycle's separable transfers with their face permutes left out."""
    gmg = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu_gmg")
    apply = gmg._separable_apply

    def without_faces(jax_, jnp_, u, fbs, axes, sel=None, facemask=None):
        return apply(jax_, jnp_, u, fbs, tuple((None, None) for _ in axes), sel, facemask)

    monkeypatch.setattr(gmg, "_separable_apply", without_faces)


def test_the_halo_left_out_of_the_four_part_program_fails(monkeypatch):
    """Every exchange between chips left out: the box exchange of each
    level's products (the outer CG's among them) and the transfer's face
    permutes. The answers are then another system's."""
    box = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu_box")
    monkeypatch.setattr(
        box, "shard_box_exchange", lambda plan, combine: lambda xv, si, sm, ri: xv
    )
    faceless(monkeypatch)
    result = drive()
    assert result["correct"] is False
    c = result["compared"]["residual_rel"]
    assert c["value"] > c["limit"]


def test_the_transfers_faces_left_out_cost_iterations_not_answers(monkeypatch):
    """The transfer's face permutes alone left out: R and P lose their
    terms across part boundaries alike (R is still P's transpose), so the
    V-cycle is a weaker symmetric preconditioner and `pa.pcg` still meets
    the tolerance, in more iterations. `correct` judges answers and cannot
    see this fault; `tests/test_gmg_separable.py` holds the V-cycle itself
    to the float64 reference, where it reads 9.4e-2 against 1e-5."""
    clean = drive()
    faceless(monkeypatch)
    broken = drive()
    assert clean["correct"] is True and broken["correct"] is True
    assert broken["run"]["iterations_min"] > clean["run"]["iterations_max"]
