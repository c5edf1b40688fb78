"""The runner's own functions, driven off the chip at 16^3 cells per part.

The command refuses to run without a TPU and no option changes that; these
tests steer around the look for a chip (`find_chips`) and drive the rest of
a run (`run_cell`) on the CPU backend, on one device and on four virtual
ones. They hold what decides `correct` to its word: the program passes, the
control (the plain reference CG in bfloat16, in the program's place) does
not, and neither does a run whose timed path is broken underneath.
"""
import importlib
import json
import os
import subprocess
import sys
import time
import types

import jax
import numpy as np
import pytest

from benchmark import run as R
from benchmark.builders import poisson7

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = R.ROOT
PEAKS = {"hbm_bytes_per_s": 819e9}
SEED = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits

CELLS = {
    "cg": ("poisson7_16", "cg_closed", 1),
    "gmg_pcg": ("poisson7_16", "gmg_pcg_closed", 1),
    "x4_cg": ("poisson7_16_x4", "cg_closed", 4),
}


def tiny_cell(which: str):
    config, mix, chips = CELLS[which]
    manifest = R.read_json(ROOT, "BENCHMARK.json")
    return types.SimpleNamespace(
        name=f"rehearsal.{which}", chips=chips,
        cfg=R.read_json(HERE, "configs", config + ".json"),
        mix=R.read_json(R.HERE, "traffic", mix + ".json"),
        end_to_end=manifest["end_to_end"], per_layer=manifest["per_layer"],
    )


def drive(which: str, trace: bool = False, seconds: float = 0.3, seed: int = SEED):
    cell = tiny_cell(which)
    return R.run_cell(
        cell, jax.devices()[: cell.chips], PEAKS, seed, seconds, trace,
        time.perf_counter(),
    )


def test_the_command_refuses_to_run_off_a_tpu():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "poisson7_192.cg",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, timeout=300, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert p.returncode != 0
    assert "CpuDevice" in p.stderr, p.stderr  # names the devices it found
    assert p.stdout.strip() == "", "printed a result off the chip"


def test_an_unknown_device_kind_or_too_few_chips_is_an_error(monkeypatch):
    class Fake:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    cell = tiny_cell("x4_cg")
    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()] * 4)
    with pytest.raises(SystemExit, match="no peaks for device_kind"):
        R.find_chips(cell)
    Fake.device_kind = "TPU v5 lite"
    assert len(R.find_chips(cell)[0]) == 4
    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    with pytest.raises(SystemExit, match="needs 4 chips"):
        R.find_chips(cell)


@pytest.mark.parametrize("which", sorted(CELLS))
@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_a_rehearsed_run_is_correct_and_its_line_is_the_contracts(which, trace):
    result = drive(which, trace=trace)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert list(result)[-1] == "compared"  # the compared numbers come last
    for c in result["compared"].values():
        assert c["value"] is not None and c["value"] <= c["limit"]
    assert result["run"]["compiles_in_window"]["compile_events"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    assert result["device"]["count"] == CELLS[which][2]
    names = set(result["metrics"])
    if trace:
        # no device plane in a CPU trace: only the host-clock readers speak,
        # and no reader invents a number
        assert names == {"assemble_s", "first_solve_s"}
    else:
        want = {"setup_s", "solve_s"} | ({"solve_p95_s"} if which == "gmg_pcg" else set())
        # the rehearsal cell is handed every metric; a real cell gets its own
        assert want <= names
        assert all(result["metrics"][n]["value"] > 0 for n in names)
    json.dumps(result)  # one JSON object


def test_the_traced_stretch_is_cut_by_solves_or_by_seconds(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append("stop"))
    seen = {}
    tr = importlib.import_module("benchmark.trace")
    monkeypatch.setattr(tr, "find_xplane", lambda d: "nowhere")
    monkeypatch.setattr(tr, "read", lambda path: tr.Trace({}, []))
    iter_us = importlib.import_module("benchmark.layer_metrics.iter_us")
    monkeypatch.setattr(
        iter_us, "reduce", lambda run: seen.update(n=len(run.traced_records))
    )
    cell = tiny_cell("cg")
    for spec, want in (
        ({"skip_solves": 1, "solves": 3, "seconds": 60.0}, 3),  # by solves
        ({"skip_solves": 1, "solves": 1000, "seconds": 0.0}, 1),  # by seconds
        ({"skip_solves": 10**6, "solves": 3, "seconds": 5.0}, None),  # never began
    ):
        calls.clear(), seen.clear()
        cell.mix["trace"] = spec
        result = R.run_cell(cell, jax.devices()[:1], PEAKS, SEED, 0.3, True,
                            time.perf_counter())
        assert result["correct"] is True
        if want is None:
            assert calls == [] and seen == {"n": 0} and "breakdown" not in result
            assert set(result["metrics"]) == {"assemble_s", "first_solve_s"}
        else:
            assert calls == ["start", "stop"] and seen == {"n": want}


def test_the_same_seed_gives_the_same_inputs_and_another_seed_others():
    pa = importlib.import_module("partitionedarrays_jl_tpu")
    cell = tiny_cell("cg")
    backend = pa.TPUBackend(devices=jax.devices()[:1])

    def pools(parts):
        s = poisson7.build(pa, parts, cell.cfg, cell.mix)
        return [
            [pa.gather_pvector(r.b) for r in s.make_pool(seed)]
            for seed in (SEED, SEED, SEED + 1)
        ]

    a, b, c = pa.prun(pools, backend, (1, 1, 1))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    # the pool's entries differ from each other, and all have one norm:
    # images of one field under the grid's symmetries
    assert len({x.tobytes() for x in a}) == len(a)
    assert np.allclose([np.linalg.norm(x) for x in a], np.linalg.norm(a[0]), rtol=1e-5)


def test_the_reference_operator_is_the_programs_to_rounding():
    """Two statements of one operator: the program's assembled matrix and
    the reference's stencil agree on a random vector, on one part and on a
    (2,2,1) grid (the host SpMV, float64)."""
    pa = importlib.import_module("partitionedarrays_jl_tpu")
    for grid, ns in (((1, 1, 1), (9, 8, 7)), ((2, 2, 1), (12, 10, 6))):
        def both(parts):
            A, _b, _xe, _x0 = pa.assemble_poisson(parts, ns, decoupled=True)
            u = np.random.default_rng(3).standard_normal(ns)
            v = pa.scatter_pvector_values(u.ravel(), A.cols)
            return pa.gather_pvector(A @ v).reshape(ns), poisson7.apply_reference(u)

        got, want = pa.prun(both, pa.sequential, grid)
        assert np.allclose(got, want, rtol=0, atol=1e-12)


def test_the_start_residual_is_the_right_hand_sides_interior():
    """`make_pool` takes ||b - A x0|| in closed form; hold it to the stencil."""
    u = poisson7.base_field((9, 8, 7), 5, 3, 4)
    b = poisson7.apply_reference(u)
    r0 = b - poisson7.apply_reference(poisson7.boundary_only(u))
    assert np.linalg.norm(r0) == pytest.approx(
        np.linalg.norm(poisson7.interior_of(b)), rel=1e-14
    )


def test_an_image_of_b_is_the_b_of_the_image():
    """The operator maps onto itself under every symmetry of its grid, so
    `make_pool` may take images of (b, x0) instead of applying the stencil
    to each image of u."""
    for ns, grid in (((8, 8, 8), (1, 1, 1)), ((12, 12, 6), (2, 2, 1))):
        u = poisson7.base_field(ns, 5, 3, 4)
        b = poisson7.apply_reference(u)
        syms = poisson7.symmetries(ns, grid)
        assert len(syms) == (96 if grid == (1, 1, 1) else 32)
        for sym in syms:
            ui = poisson7.image(u, sym)
            assert ui.flags["C_CONTIGUOUS"] and ui.shape == ns
            assert np.allclose(
                poisson7.image(b, sym), poisson7.apply_reference(ui),
                rtol=0, atol=1e-13,
            )
            assert np.array_equal(
                poisson7.image(poisson7.boundary_only(u), sym),
                poisson7.boundary_only(ui),
            )
    assert poisson7.image(u, syms[0]) is not u  # a copy even of the identity


def test_the_sample_is_a_reservoir_drawn_from_the_seed():
    kept = {}
    for seed in (SEED, SEED, SEED + 1):
        s = R.Sample([[] for _ in range(4)], lambda x, slot: slot.append(x), seed)
        for i in range(100):
            s.offer(i, i % 4, i)
        got = s.answers()
        assert len(got) == 4 and all(slot[-1] == i for i, _k, slot in got)
        kept.setdefault(seed, []).append([i for i, _k, _s in got])
    assert kept[SEED][0] == kept[SEED][1] != kept[SEED + 1][0]
    few = R.Sample([[] for _ in range(4)], lambda x, slot: slot.append(x), SEED)
    few.offer(0, 0, "a")
    assert [(i, k) for i, k, _ in few.answers()] == [(0, 0)]


# ---------------------------------------------------------------------------
# what has to come out as NOT correct
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("which", sorted(CELLS))
def test_the_control_fails(which, monkeypatch):
    """The reference CG in bfloat16, put in the program's place."""
    ctl = tiny_cell(which).mix["control"]
    monkeypatch.setattr(
        poisson7.System, "solve",
        lambda self, req: self.control_solve(req, ctl["dtype"], ctl["maxiter"]),
    )
    result = drive(which)
    assert result["correct"] is False
    c = result["compared"]["residual_rel"]
    assert c["value"] > 3 * c["limit"]


def test_the_witness_passes(monkeypatch):
    """The same reference CG in the configuration's own float32 passes:
    it is the precision that fails the control, not the stand-in."""
    monkeypatch.setattr(
        poisson7.System, "solve",
        lambda self, req: self.control_solve(req, "float32", 1500),
    )
    assert drive("cg")["correct"] is True


@pytest.mark.parametrize("which", sorted(CELLS))
def test_an_answer_altered_where_it_is_produced_fails(which, monkeypatch):
    tpu = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    lift = tpu._host_frame_to_pvector
    monkeypatch.setattr(
        tpu, "_host_frame_to_pvector",
        lambda host, rows, layout: lift(host * np.float32(1.001), rows, layout),
    )
    result = drive(which)
    assert result["correct"] is False
    assert result["compared"]["residual_rel"]["value"] > result["compared"]["residual_rel"]["limit"]


def test_the_exchange_between_chips_left_out_fails(monkeypatch):
    box = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu_box")
    monkeypatch.setattr(
        box, "shard_box_exchange", lambda plan, combine: lambda xv, si, sm, ri: xv
    )
    result = drive("x4_cg")
    assert result["correct"] is False
    # the same fault on one part changes nothing: there is no exchange there
    assert drive("cg")["correct"] is True


def test_a_solve_that_raises_or_does_not_converge_is_counted(monkeypatch):
    solve = poisson7.System.solve
    calls = {"n": 0}

    def flaky(self, req):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("planted")
        x, info = solve(self, req)
        return x, dict(info, converged=calls["n"] != 4 and info["converged"])

    monkeypatch.setattr(poisson7.System, "solve", flaky)
    result = drive("cg")
    assert result["failed"] == 2 and result["correct"] is False
    assert result["compared"]["unanswered"] == {"value": 2, "limit": 0}
