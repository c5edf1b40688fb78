"""The patrace observability layer (partitionedarrays_jl_tpu.telemetry).

The tentpole's hard contract, pinned here with the same discipline as
ABFT (tests/test_abft.py):

* **Telemetry OFF is free.** The compiled CG program with
  ``PA_TRACE_ITERS`` unset/0 is byte-identical StableHLO to the same
  build under ``PA_METRICS=0`` — the record layer is host-side only and
  can never reach a traced program.
* **Telemetry ON adds ZERO collectives.** The α/β trace ring is a
  replicated while-carry of scalars the dot gathers already replicated;
  per-kind collective counts are identical ON vs OFF.
* **Trajectory identity.** Under strict-bits the residual history and
  solution are BITWISE identical with the trace ring on, off, and with
  the whole record layer killed — and the recorded α/β entries obey the
  CG recurrence against the residual history itself.
* **Static-vs-measured reconciliation.** A finished solve's runtime
  comms accounting (plan model × iterations) equals what the lowered
  program statically implies, per collective kind in ops AND bytes
  (probe legs here; the full 15-case matrix runs under the slow marker
  in test_static_analysis.py and `tools/palint.py --check`).

Plus the host-side machinery: SolveRecord/InfoDict compat, event
nesting, the metrics registry, record persistence + the patrace CLI,
the PTimer trace bridge, and the shared artifact writer.
"""
import json
import os

import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import telemetry
from partitionedarrays_jl_tpu.analysis import collective_counts
from partitionedarrays_jl_tpu.models import assemble_poisson, cg
from partitionedarrays_jl_tpu.parallel.tpu import (
    TPUBackend,
    device_matrix,
    make_cg_fn,
    tpu_cg,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _backend(n=8):
    import jax

    return TPUBackend(devices=jax.devices()[:n])


def _probe(backend):
    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (6, 6, 6))
        return A, b, x0

    return pa.prun(driver, backend, (2, 2, 2))


# ---------------------------------------------------------------------------
# the hard contract: OFF is HLO-identical, ON adds zero collectives
# ---------------------------------------------------------------------------


def test_trace_off_program_hlo_identical_across_telemetry_env(monkeypatch):
    """PA_METRICS (the record layer) and PA_TRACE_ITERS=0 (explicit
    trace-off) lower the IDENTICAL program — byte-equal StableHLO. Only
    a nonzero trace depth may change the traced program (and that via
    its registered key site, covered by test_static_analysis.py)."""
    backend = _backend()
    A, b, _x0 = _probe(backend)
    dA = device_matrix(A, backend)
    from partitionedarrays_jl_tpu.parallel.tpu import _matrix_operands

    ops = _matrix_operands(dA)
    P = dA.col_plan.layout.P
    z = np.zeros((P, dA.col_plan.layout.W))

    def text():
        fn = make_cg_fn(dA, tol=1e-9, maxiter=50)
        return fn.jit_fn.lower(z, z, z, ops).as_text()

    base = text()
    monkeypatch.setenv("PA_METRICS", "0")
    off = text()
    monkeypatch.delenv("PA_METRICS")
    monkeypatch.setenv("PA_TRACE_ITERS", "0")
    explicit = text()
    assert base == off == explicit


def test_trace_ring_adds_zero_collectives(monkeypatch):
    """The α/β ring rides the while carry: per-kind collective counts
    identical with PA_TRACE_ITERS on vs off."""
    backend = _backend()
    A, b, _x0 = _probe(backend)
    dA = device_matrix(A, backend)
    from partitionedarrays_jl_tpu.parallel.tpu import _matrix_operands

    ops = _matrix_operands(dA)
    z = np.zeros((dA.col_plan.layout.P, dA.col_plan.layout.W))
    off = collective_counts(make_cg_fn(dA, tol=1e-9, maxiter=50),
                            z, z, z, ops)
    monkeypatch.setenv("PA_TRACE_ITERS", "16")
    fn_on = make_cg_fn(dA, tol=1e-9, maxiter=50)
    assert fn_on.trace_iters == 16
    on = collective_counts(fn_on, z, z, z, ops)
    assert any(off.values()), "probe program shows no collectives"
    assert on == off, (on, off)


def test_strict_bits_trajectory_bitwise_with_trace_ring(monkeypatch):
    """Under strict-bits the solve trajectory is BITWISE identical with
    the trace ring on, off, and with PA_METRICS=0 — and the recorded
    α/β obey the CG recurrence against the residual history (β_i =
    (h_{i+1}/h_i)², h = √rs, in the unpreconditioned standard body)."""
    monkeypatch.setenv("PA_TPU_STRICT_BITS", "1")
    backend = _backend()
    A, b, x0 = _probe(backend)

    def solve():
        def driver(parts):
            x, info = tpu_cg(A, b, x0=x0, tol=1e-9, maxiter=100)
            return np.asarray(pa.gather_pvector(x)), info

        return pa.prun(driver, backend, (2, 2, 2))

    x_off, inf_off = solve()
    monkeypatch.setenv("PA_TRACE_ITERS", "64")
    x_on, inf_on = solve()
    monkeypatch.delenv("PA_TRACE_ITERS")
    monkeypatch.setenv("PA_METRICS", "0")
    x_kill, inf_kill = solve()
    monkeypatch.delenv("PA_METRICS")

    assert inf_on["iterations"] == inf_off["iterations"] == \
        inf_kill["iterations"]
    np.testing.assert_array_equal(x_on, x_off)
    np.testing.assert_array_equal(x_kill, x_off)
    np.testing.assert_array_equal(
        np.asarray(inf_on["residuals"]), np.asarray(inf_off["residuals"])
    )

    # the traced ring ties back to the trajectory it rode along with
    rec = inf_on.record
    it = inf_on["iterations"]
    assert rec.trace_start == 0
    assert len(rec.alpha) == len(rec.beta) == it
    hist = np.asarray(inf_on["residuals"])
    np.testing.assert_allclose(
        np.asarray(rec.beta), (hist[1:it + 1] / hist[:it]) ** 2,
        rtol=1e-10,
    )
    assert all(a > 0 for a in rec.alpha)  # SPD operator

    # the killed layer returned an inert record: nothing retained
    assert getattr(inf_kill, "record").enabled is False
    assert inf_kill.record.events == []

    # overflowing ring (depth < iterations): a TRUE ring — the record
    # keeps the LAST `depth` committed iterations, un-rotated, with
    # trace_start marking the window; the trajectory is untouched
    depth = max(2, it - 2)
    monkeypatch.setenv("PA_TRACE_ITERS", str(depth))
    x_ring, inf_ring = solve()
    monkeypatch.delenv("PA_TRACE_ITERS")
    np.testing.assert_array_equal(x_ring, x_off)
    rr = inf_ring.record
    assert rr.trace_start == it - depth
    assert len(rr.alpha) == len(rr.beta) == depth
    a = np.arange(rr.trace_start, it)
    np.testing.assert_allclose(
        np.asarray(rr.beta), (hist[a + 1] / hist[a]) ** 2, rtol=1e-10,
    )


# ---------------------------------------------------------------------------
# static-vs-measured comms reconciliation (fast probe legs)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case_name", ["standard", "standard_abft"])
def test_comms_reconciliation_probe(case_name):
    """The runtime accounting a finished probe solve reports equals the
    lowered program's static expectation — per kind, ops AND bytes, at
    the solve's trip count (the SDC-defended leg counts while-loop
    trips, not committed iterations). Full matrix: slow marker +
    `tools/palint.py --check`."""
    from partitionedarrays_jl_tpu.analysis.program_report import analyze_text
    from partitionedarrays_jl_tpu.parallel.tpu import (
        case_probe_solve,
        case_program_text,
        lowering_matrix,
    )

    backend = _backend()
    case = {c["name"]: c for c in lowering_matrix(fast=False)}[case_name]
    rec = case_probe_solve(backend, case)
    assert rec.comms is not None and rec.comms["iterations"] > 0
    report = analyze_text(case_program_text(backend, case))
    mismatches = telemetry.reconcile(report, rec.comms)
    assert not mismatches, "\n".join(mismatches)
    obs = rec.comms["observed"]
    assert obs["collective_permute"]["ops"] > 0
    assert obs["all_gather"]["bytes"] > 0


# ---------------------------------------------------------------------------
# records, events, the info-dict compat view
# ---------------------------------------------------------------------------


def test_host_solve_returns_infodict_with_record():
    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8))
        x, info = cg(A, b, x0=x0, tol=1e-9)
        assert isinstance(info, dict)  # every legacy consumer holds
        assert dict(info)["converged"] == info["converged"]
        rec = info.record
        assert rec.solver == "cg" and rec.finished
        assert rec.status != "raised" and rec.iterations == \
            info["iterations"]
        assert rec.config["backend"] == "host"
        assert rec.config["tol"] == 1e-9
        assert rec.config["pa_env"].get("PA_TPU_CHECKS") == "1"
        assert rec.wall_s > 0
        assert len(rec.residuals) == info["iterations"] + 1
        assert telemetry.last_record("cg") is rec
        # round-trips through the persisted-JSON shape
        d = rec.as_dict()
        assert d["schema_version"] == telemetry.RECORD_SCHEMA_VERSION
        json.dumps(d)
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))


def test_event_nesting_and_kill_switch(monkeypatch):
    outer = telemetry.begin_record("outer")
    inner = telemetry.begin_record("inner")
    telemetry.emit_event("checkpoint_save", label="x", iteration=3, n=1)
    # a bare begin_record hears every event and is on no thread's stack;
    # the calling thread's record is the innermost solve_scope it opened
    assert telemetry.current_record() is None
    with telemetry.solve_scope("scoped-outer") as so:
        with telemetry.solve_scope("scoped-inner") as si:
            assert telemetry.current_record() is si
            si.finish(None)
            assert telemetry.current_record() is so
        assert telemetry.current_record() is so
    assert telemetry.current_record() is None
    inner.finish(None)
    telemetry.emit_event("restart", label="y")
    outer.finish(None)
    # the outer scope saw BOTH events; the inner only its own
    assert [e.kind for e in outer.events] == ["checkpoint_save", "restart"]
    assert [e.kind for e in inner.events] == ["checkpoint_save"]
    assert inner.events[0].iteration == 3
    assert inner.events[0].details == {"n": 1}

    monkeypatch.setenv("PA_METRICS", "0")
    ghost = telemetry.begin_record("ghost")
    telemetry.emit_event("restart")
    ghost.finish(None)
    assert ghost.enabled is False and ghost.events == []
    assert telemetry.last_record("ghost") is None


def test_metrics_registry():
    telemetry.reset_counters("t_test")
    assert telemetry.counter("t_test.a") == 0
    telemetry.bump("t_test.a")
    telemetry.bump("t_test.a", 2)
    telemetry.bump("t_test.b")
    assert telemetry.counter("t_test.a") == 3
    snap = telemetry.counters("t_test")
    assert snap == {"t_test.a": 3, "t_test.b": 1}
    telemetry.reset_counters("t_test")
    assert telemetry.counters("t_test") == {}


# ---------------------------------------------------------------------------
# persistence + the patrace CLI
# ---------------------------------------------------------------------------


def test_record_persistence_and_patrace_cli(monkeypatch, tmp_path, capsys):
    d = str(tmp_path / "recs")
    monkeypatch.setenv("PA_METRICS_DIR", d)

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8))
        cg(A, b, x0=x0, tol=1e-9)
        cg(A, b, x0=x0, tol=1e-6)
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))
    paths = telemetry.list_persisted_records(d)
    assert len(paths) == 2
    rec = telemetry.load_record(paths[-1])
    assert rec["schema_version"] == telemetry.RECORD_SCHEMA_VERSION
    assert rec["solver"] == "cg" and rec["iterations"] > 0

    # drive the CLI in-process (a subprocess would re-import jax and
    # burn ~8s of the tier-1 budget for no added coverage)
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "patrace_cli", os.path.join(REPO, "tools", "patrace.py")
    )
    patrace = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(patrace)
    out_trace = str(tmp_path / "trace.json")
    rc = patrace.main(
        ["--list", "--last", "--trace", out_trace, "--n", "2", "--dir", d]
    )
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "record:" in out and "solver=cg" in out
    assert "events [" in out
    trace = json.load(open(out_trace))
    assert trace["metadata"]["schema_version"] == \
        telemetry.TRACE_SCHEMA_VERSION
    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert len(spans) == 2  # one complete span per record
    assert all(s["dur"] > 0 for s in spans)


# ---------------------------------------------------------------------------
# the PTimer bridge + the shared artifact writer
# ---------------------------------------------------------------------------


def test_ptimer_trace_bridge(tmp_path):
    def driver(parts):
        t = pa.PTimer(parts)
        t.tic(barrier=True)
        sum(range(1000))
        t.toc("stage")
        t.tic(barrier=False)
        sum(range(10))
        t.toc("solve")
        data = t.data_json()
        assert data["schema_version"] == 1
        assert set(data["sections"]) == {"stage", "solve"}
        assert [s["name"] for s in data["spans"]] == ["stage", "solve"]
        # the barrier drain is its own recorded cost, not hidden
        assert data["spans"][0]["barrier_s"] >= 0.0
        assert data["spans"][1]["barrier_s"] == 0.0
        evs = t.trace_events()
        names = [e["name"] for e in evs]
        assert "stage" in names and "solve" in names
        if data["spans"][0]["barrier_s"] > 0:
            assert "stage:tic_barrier" in names
        # lands on the same timeline as solver records
        combined = telemetry.chrome_trace(records=[], timers=[t])
        assert any(e.get("cat") == "ptimer"
                   for e in combined["traceEvents"])
        out = str(tmp_path / "ptimer.json")
        t.print_timer(json_path=out)
        if os.path.exists(out):  # written on MAIN only
            assert json.load(open(out))["sections"]
        return True

    assert pa.prun(driver, pa.sequential, 2)


def test_artifact_writer_envelope(tmp_path, capsys):
    rec = telemetry.stamp({"x": 1, "platform": "tpu"}, tool="t")
    # setdefault discipline: a tool-recorded platform survives stamping
    assert rec["platform"] == "tpu"
    assert rec["schema_version"] == telemetry.ARTIFACT_SCHEMA_VERSION
    assert rec["generated_by"] == "t"
    path = str(tmp_path / "X_BENCH.json")
    telemetry.write(path, {"y": 2}, tool="bench_x")
    on_disk = json.load(open(path))
    assert on_disk["schema_version"] == telemetry.ARTIFACT_SCHEMA_VERSION
    assert on_disk["generated_by"] == "bench_x"
    assert on_disk["y"] == 2 and "pa_env" in on_disk
    # dry-run prints, never touches the path
    telemetry.write(str(tmp_path / "no.json"), {"z": 3}, dry_run=True)
    assert not os.path.exists(str(tmp_path / "no.json"))
    assert '"z": 3' in capsys.readouterr().out
