"""paspec — the convergence observatory
(`partitionedarrays_jl_tpu.telemetry.spectrum`).

The contracts pinned here:

* **Lanczos reconstruction** — the CG α/β recurrence reconstructs the
  exact eigenvalues of a known-spectrum operator (synthetic dense CG),
  and the κ̂ estimated from the DEVICE trace ring on the analytic
  Poisson FDM fixture lies inside the documented band of the closed-
  form value (the `tools/paspec.py --check` pin, run in-process).
* **Forecaster** — `predict_iters` is monotone non-increasing in tol,
  exact (1 iteration) on a uniform diagonal operator with known
  spectrum, and its realized error on the conformance probe stays
  inside the committed band.
* **Block-vs-solo** — under strict-bits the block ring's per-column
  spectra equal the solo solves' spectra EXACTLY (the trajectories are
  bitwise, so the tridiagonals are too).
* **Trace-ring exemption honesty** — a body that cannot carry the ring
  (BiCGStab) emits the typed ``trace_unavailable`` event naming
  itself instead of silently returning no spectrum.
* **Overhead** — the solver path never reads ``PA_SPEC*``: the block
  program lowers to byte-identical StableHLO with the observatory and
  admission fully enabled vs disabled.
* **Admission** — `DeadlineInfeasible` end-to-end over HTTP: typed 422
  refusal at the gate door with predicted_s/available_s diagnostics,
  zero solver iterations spent; the chaos-matrix row pins the
  in-process service variant with full metric deltas.

Budget note: the device legs reuse the tiny (6,6,6)/8-part fixture;
everything else is sequential-backend or pure numpy.
"""
import json
import os
import urllib.request

import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import telemetry
from partitionedarrays_jl_tpu.models import assemble_poisson, cg, pcg
from partitionedarrays_jl_tpu.parallel.health import DeadlineInfeasible
from partitionedarrays_jl_tpu.service import SolveService
from partitionedarrays_jl_tpu.telemetry import spectrum

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _backend(n=8):
    import jax

    from partitionedarrays_jl_tpu.parallel.tpu import TPUBackend

    return TPUBackend(devices=jax.devices()[:n])


# ---------------------------------------------------------------------------
# Lanczos reconstruction: exact on a synthetic known-spectrum system
# ---------------------------------------------------------------------------


def _dense_cg_ab(A, b, iters):
    """Textbook dense CG collecting the α/β recurrence — the oracle the
    reconstruction formulas are checked against."""
    x = np.zeros_like(b)
    r = b - A @ x
    p = r.copy()
    rs = float(r @ r)
    alphas, betas = [], []
    for _ in range(iters):
        q = A @ p
        alpha = rs / float(p @ q)
        x += alpha * p
        r -= alpha * q
        rs_new = float(r @ r)
        beta = rs_new / rs
        p = r + beta * p
        alphas.append(alpha)
        betas.append(beta)
        rs = rs_new
        if rs == 0.0:
            break
    return alphas, betas


def test_lanczos_reconstruction_exact_on_known_spectrum():
    """After k = #distinct-eigenvalues CG iterations the reconstructed
    T_k's Ritz values ARE the eigenvalues (CG–Lanczos equivalence,
    exact to rounding on a well-separated synthetic spectrum)."""
    eigs = np.array([1.0, 2.0, 4.0, 8.0])
    rng = np.random.default_rng(0)
    Q, _ = np.linalg.qr(rng.standard_normal((4, 4)))
    A = Q @ np.diag(eigs) @ Q.T
    b = rng.standard_normal(4)
    alphas, betas = _dense_cg_ab(A, b, 4)
    ritz = spectrum.ritz_values(alphas, betas)
    np.testing.assert_allclose(ritz, eigs, rtol=1e-8)
    est = spectrum.estimate_solve(alphas, betas, None)
    assert est["kappa"] == pytest.approx(8.0, rel=1e-8)
    # None-masked tails (the block-solve convention) truncate cleanly
    ritz2 = spectrum.ritz_values(
        list(alphas[:2]) + [None, None], list(betas[:2]) + [None, None]
    )
    assert len(ritz2) == 2
    # no usable coefficients -> no claim
    assert spectrum.ritz_values([], []) is None
    assert spectrum.estimate_solve(None, None, None) is None


def test_trailing_window_reconstruction_stays_inside_spectrum():
    """A trailing window (wrapped ring / resumed host loop,
    ``trace_start > 0``) must spend its first pair completing the next
    diagonal entry: the reconstruction IS the true principal submatrix
    ``T[j0+1:, j0+1:]`` (checked against the full T explicitly), so
    its eigenvalues interlace and stay INSIDE the spectrum — a naive
    rebuild would leak a Ritz value below λmin and inflate κ̂ into the
    admission path."""
    eigs = np.linspace(1.0, 30.0, 12)
    rng = np.random.default_rng(3)
    Q, _ = np.linalg.qr(rng.standard_normal((12, 12)))
    A = Q @ np.diag(eigs) @ Q.T
    b = rng.standard_normal(12)
    alphas, betas = _dense_cg_ab(A, b, 10)
    j0 = 3
    d_full, e_full = spectrum.lanczos_tridiagonal(alphas, betas)
    T = np.diag(d_full) + np.diag(e_full, 1) + np.diag(e_full, -1)
    want = np.linalg.eigvalsh(T[j0 + 1:, j0 + 1:])
    got = spectrum.ritz_values(
        alphas[j0:], betas[j0:], trace_start=j0
    )
    np.testing.assert_allclose(got, want, rtol=1e-10)
    assert got[0] >= eigs[0] - 1e-8 and got[-1] <= eigs[-1] + 1e-8
    # the naive (trace_start-ignorant) rebuild demonstrably leaks low
    naive = spectrum.ritz_values(alphas[j0:], betas[j0:])
    assert naive[0] < got[0]


# ---------------------------------------------------------------------------
# the forecaster
# ---------------------------------------------------------------------------


def test_predict_iters_monotone_in_tol_and_edges():
    """Tightening tol can never DECREASE the forecast (the blended rate
    is target-independent); unmeasured specs make no claim; an already-
    satisfied target predicts 0."""
    spec = {"kappa": 50.0, "rate": 0.3, "samples": 4}
    tols = [1e-2, 1e-4, 1e-6, 1e-8, 1e-10, 1e-12]
    preds = [
        spectrum.predict_iters(spec, t, r0_norm=10.0) for t in tols
    ]
    assert all(isinstance(p, int) and p >= 1 for p in preds)
    assert preds == sorted(preds), preds
    # rate-only and kappa-only specs both forecast
    assert spectrum.predict_iters(
        {"rate": 0.5, "samples": 1}, 1e-6
    ) >= 1
    assert spectrum.predict_iters({"kappa": 100.0}, 1e-6) >= 1
    # no measurement -> no claim; satisfied target -> 0; None spec
    assert spectrum.predict_iters({}, 1e-8) is None
    assert spectrum.predict_iters(None, 1e-8) is None
    assert spectrum.predict_iters(spec, 0.5, r0_norm=0.1) == 0


def _diagonal_operator(parts, N=24, diag=3.0):
    """A = diag·I over a 1-D block partition — the known-spectrum
    (single eigenvalue, κ = 1) fixture."""
    rows = pa.prange(parts, N)

    def coo(i):
        g = np.asarray(i.oid_to_gid)
        # I and J must be distinct buffers: from_coo renumbers in place
        return g.copy(), g.copy(), np.full(len(g), diag)

    c = pa.map_parts(coo, rows.partition)
    cols = pa.add_gids(rows, pa.map_parts(lambda t: t[1], c))
    return pa.PSparseMatrix.from_coo(
        pa.map_parts(lambda t: t[0], c),
        pa.map_parts(lambda t: t[1], c),
        pa.map_parts(lambda t: t[2], c),
        rows, cols, ids="global",
    )


def test_predict_iters_exact_on_uniform_diagonal():
    """A uniform diagonal operator (κ = 1, one distinct eigenvalue):
    CG converges in exactly one iteration, the ring reconstructs the
    eigenvalue exactly, and the forecaster predicts exactly 1."""

    def driver(parts):
        A = _diagonal_operator(parts, 24, 3.0)  # A = 3 I
        xe = pa.PVector.full(1.0, A.cols)
        b = A @ xe
        telemetry.reset_store()
        x, info = cg(A, b, tol=1e-10)
        assert info["iterations"] == 1
        rec = info.record
        # T_1 = [[1/alpha_0]] = [[3.0]] exactly
        ritz = spectrum.ritz_values(rec.alpha, rec.beta)
        assert ritz is not None and ritz[0] == pytest.approx(3.0)
        spec = telemetry.spectrum_store().spec(
            telemetry.spectrum_fingerprint(A), "float64", "none"
        )
        assert spec["kappa"] == pytest.approx(1.0)
        r0 = float(info["residuals"][0])
        for tol in (1e-4, 1e-8, 1e-12):
            assert spectrum.predict_iters(spec, tol, r0_norm=r0) == 1
        return True

    assert pa.prun(driver, pa.sequential, 2)


def test_spectrum_fingerprint_is_value_sensitive():
    """Two same-shaped operators must NOT share a spectrum-store key:
    κ/rate are value-bound, so the spectral fingerprint digests the
    value streams while the throughput key stays shape-only (cost IS
    shape-bound) — the cross-tenant blending guard."""
    from partitionedarrays_jl_tpu.telemetry.throughput import (
        operator_fingerprint,
    )

    def driver(parts):
        A1 = _diagonal_operator(parts, 24, 3.0)
        A2 = _diagonal_operator(parts, 24, 7.0)  # same shape, new values
        assert operator_fingerprint(A1) == operator_fingerprint(A2)
        f1 = telemetry.spectrum_fingerprint(A1)
        f2 = telemetry.spectrum_fingerprint(A2)
        assert f1 != f2
        assert f1.startswith(operator_fingerprint(A1))
        # cached: the O(nnz) digest is paid once per operator
        assert telemetry.spectrum_fingerprint(A1) is f1
        return True

    assert pa.prun(driver, pa.sequential, 2)


def test_warm_start_forecasts_remaining_work():
    """A resubmission FROM a (near-)converged iterate (the eviction-
    requeue / journal-resume shape) must forecast its REMAINING work:
    ``residual_norm(A, b, x0)`` is ~0 at the solution, the target is
    already met, and the forecast is 0 — a cold ``‖b‖`` forecast here
    could refuse a finished request as infeasible."""

    def driver(parts):
        A = _diagonal_operator(parts, 24, 3.0)
        xe = pa.PVector.full(1.0, A.cols)
        b = A @ xe
        cold = spectrum.residual_norm(A, b)
        warm = spectrum.residual_norm(A, b, xe)
        assert cold > 1.0 and warm <= 1e-12 * cold
        spec = {"kappa": 100.0, "rate": 0.9, "samples": 4}
        assert spectrum.predict_iters(spec, 1e-8, r0_norm=cold) > 10
        assert spectrum.predict_iters(spec, 1e-8, r0_norm=warm) == 0
        return True

    assert pa.prun(driver, pa.sequential, 2)


def test_paspec_check_covers_kappa_band_forecast_and_feasibility():
    """`tools/paspec.py --check` in-process: device probe with the
    trace ring, κ̂ inside the documented band of the ANALYTIC Poisson
    value, forecaster validated on three (operator, tol) pairs, and
    the PA_SPEC_ADMIT feasibility verdict demonstrated (typed refusal,
    zero iterations) — exit status is the contract."""
    import importlib.util

    path = os.path.join(REPO, "tools", "paspec.py")
    spec_ = importlib.util.spec_from_file_location("paspec_t", path)
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    assert mod.check() == 0


# ---------------------------------------------------------------------------
# block ring vs solo (strict-bits)
# ---------------------------------------------------------------------------


def test_block_per_column_spectra_match_solo_bitwise(monkeypatch):
    """Strict-bits: each block column's trajectory IS its solo
    trajectory (PR 3), so the per-column rings reconstruct IDENTICAL
    spectra — masked post-convergence trips truncate, never pollute."""
    monkeypatch.setenv("PA_TPU_STRICT_BITS", "1")
    monkeypatch.setenv("PA_TRACE_ITERS", "128")
    from partitionedarrays_jl_tpu.parallel.tpu import tpu_block_cg, tpu_cg

    backend = _backend()

    def probe(parts):
        A, b, xe, x0 = assemble_poisson(parts, (6, 6, 6))
        b2 = pa.PVector(
            pa.map_parts(lambda v: v * 1.5, b.values), b.rows
        )
        return A, b, b2, x0

    A, b, b2, x0 = pa.prun(probe, backend, (2, 2, 2))

    def driver(parts):
        xs, binfo = tpu_block_cg(
            A, [b, b2], X0=[x0, x0], tol=1e-9, maxiter=100
        )
        brec = binfo.record
        assert isinstance(brec.alpha[0], list) and len(brec.alpha) == 2
        for k, bk in enumerate((b, b2)):
            x, sinfo = tpu_cg(A, bk, x0=x0, tol=1e-9, maxiter=100)
            eb = telemetry.estimate_solve(
                brec.alpha[k], brec.beta[k],
                binfo["columns"][k]["residuals"],
            )
            es = telemetry.estimate_solve(
                sinfo.record.alpha, sinfo.record.beta,
                sinfo["residuals"],
            )
            assert eb["ritz_k"] == es["ritz_k"]
            assert eb["lam_min"] == es["lam_min"]  # bitwise-equal rings
            assert eb["lam_max"] == es["lam_max"]
            assert eb["kappa"] == es["kappa"]
        return True

    assert pa.prun(driver, backend, (2, 2, 2))


def test_trace_unavailable_event_names_the_body(monkeypatch):
    """Trace-ring exemption honesty: a BiCGStab solve under
    PA_TRACE_ITERS cannot carry the ring — it must say so typed
    (``trace_unavailable`` naming the body) instead of silently
    returning a record with no spectrum."""
    monkeypatch.setenv("PA_TRACE_ITERS", "64")
    from partitionedarrays_jl_tpu.parallel.tpu import tpu_bicgstab

    backend = _backend()

    def probe(parts):
        A, b, xe, x0 = assemble_poisson(parts, (6, 6, 6))
        return A, b, x0

    A, b, x0 = pa.prun(probe, backend, (2, 2, 2))

    def driver(parts):
        x, info = tpu_bicgstab(A, b, x0=x0, tol=1e-9, maxiter=100)
        rec = info.record
        assert rec.alpha is None  # no ring on the BiCGStab body
        evs = rec.events_of("trace_unavailable")
        assert evs and evs[0].label == "bicgstab"
        assert evs[0].details["requested"] == 64
        # the spectrum layer still measured the RATE from the history
        est = telemetry.estimate_solve(
            rec.alpha, rec.beta, info["residuals"]
        )
        assert est["lam_min"] is None and est["rate"] is not None
        return True

    assert pa.prun(driver, backend, (2, 2, 2))


# ---------------------------------------------------------------------------
# anomaly detectors
# ---------------------------------------------------------------------------


def test_anomaly_detectors_classify_trajectories():
    """Synthetic trajectories hit exactly their documented class, and a
    degraded preconditioner (κ̂ drift vs the stored baseline) is
    flagged only against a measured baseline."""
    W = spectrum.ANOMALY_WINDOW
    # clean geometric convergence: nothing fires
    clean = [10.0 * 0.5 ** i for i in range(3 * W)]
    assert spectrum.detect_anomalies(None, clean, None, True, "none") == []
    # plateau (fp floor) on an unconverged solve: stagnation
    stalled = [10.0 * 0.5 ** i for i in range(W)] + [1e-12] * (2 * W)
    assert spectrum.detect_anomalies(
        None, stalled, None, False, "none"
    ) == ["stagnation"]
    # growth far above the best-seen: divergence
    diverging = [1.0, 0.5, 0.2, 5.0, 40.0]
    assert spectrum.detect_anomalies(
        None, diverging, None, False, "none"
    ) == ["divergence"]
    # preconditioner degradation: κ̂ drifted 4x above a measured prior
    prior = {"kappa": 10.0, "rate": 0.2, "samples": 3}
    est = {"kappa": 100.0, "rate": 0.2}
    assert spectrum.detect_anomalies(
        est, clean, prior, True, "diag"
    ) == ["precond_degradation"]
    # ... but never for unpreconditioned solves or unmeasured priors
    assert spectrum.detect_anomalies(est, clean, prior, True, "none") == []
    assert spectrum.detect_anomalies(
        est, clean, {"kappa": 10.0, "rate": 0.2, "samples": 1}, True,
        "diag",
    ) == []


def test_stagnation_anomaly_emitted_through_observe_path():
    """The observe wiring end-to-end: a stalled trajectory fed through
    `observe_solve` lands a ``convergence_anomaly`` event on the ACTIVE
    record and ticks the labeled ``spec.anomalies`` counter (the
    CATALOG row); the estimate still enters the store."""

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8))
        W = spectrum.ANOMALY_WINDOW
        stalled = [10.0 * 0.5 ** i for i in range(W)] + [1e-12] * (
            2 * W
        )
        c0 = telemetry.registry().counter(
            "spec.anomalies", labels={"kind": "stagnation"}
        ).value
        with telemetry.solve_scope("cg", backend="host") as rec:
            est = telemetry.observe_solve(
                A, rec,
                info={"residuals": stalled, "converged": False},
                dtype=np.float64,
            )
            assert est is not None and est["rate"] is not None
            evs = rec.events_of("convergence_anomaly")
            assert evs and evs[0].label == "stagnation"
        assert telemetry.registry().counter(
            "spec.anomalies", labels={"kind": "stagnation"}
        ).value == c0 + 1
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))


# ---------------------------------------------------------------------------
# the overhead contract
# ---------------------------------------------------------------------------


def test_spec_env_is_invisible_to_compiled_programs(monkeypatch):
    """The solver path never reads PA_SPEC*: the block program lowers
    to byte-identical StableHLO with the observatory + admission fully
    on vs fully off (the PR 6/9/13 convention)."""
    from partitionedarrays_jl_tpu.parallel.tpu import (
        _matrix_operands,
        device_matrix,
        make_cg_fn,
    )

    backend = _backend()
    A = pa.prun(
        lambda parts: assemble_poisson(parts, (6, 6, 6))[0],
        backend, (2, 2, 2),
    )
    dA = device_matrix(A, backend)
    ops = _matrix_operands(dA)
    P, W = dA.col_plan.layout.P, dA.col_plan.layout.W
    z = np.zeros((P, W, 4))

    def text():
        fn = make_cg_fn(dA, tol=1e-9, maxiter=50, rhs_batch=4)
        return fn.jit_fn.lower(z, z, z[..., 0], ops).as_text()

    monkeypatch.setenv("PA_SPEC", "0")
    monkeypatch.setenv("PA_SPEC_ADMIT", "0")
    off = text()
    monkeypatch.setenv("PA_SPEC", "1")
    monkeypatch.setenv("PA_SPEC_ADMIT", "1")
    on = text()
    assert on == off


# ---------------------------------------------------------------------------
# DeadlineInfeasible end-to-end over HTTP
# ---------------------------------------------------------------------------


def test_deadline_infeasible_typed_422_over_http(monkeypatch):
    """The acceptance pin: an infeasible deadline is refused typed at
    the GATE door over HTTP — 422 DeadlineInfeasible with
    predicted_s/available_s diagnostics, never dispatched, zero solver
    iterations spent, event trail + metric deltas — and distinct from
    429 (shed) / 503 (queue backpressure)."""
    from partitionedarrays_jl_tpu.frontdoor import (
        Gate,
        http_solve,
        serve_gate,
    )
    from partitionedarrays_jl_tpu.models import gather_pvector

    A, b, xe, x0 = pa.prun(
        lambda parts: assemble_poisson(parts, (8, 8)),
        pa.sequential, (2, 2),
    )
    gate = Gate(start_workers=True)
    gate.register("p8", A, kmax=2)
    srv = serve_gate(gate, port=0)
    try:
        bg, x0g = gather_pvector(b), gather_pvector(x0)
        # train: one completed request measures spectrum + throughput
        out = http_solve(srv.url, "p8", bg, x0=x0g, tol=1e-9,
                         tag="train")
        assert out["state"] == "done" and out["info"]["converged"]
        svc = gate.service("p8")
        reg = telemetry.registry()
        admitted0 = reg.counter("service.admitted").value
        infeasible0 = reg.counter("spec.infeasible").value
        ev_inf0 = telemetry.counter("events.deadline_infeasible")
        ev_health0 = telemetry.counter("events.health_error")
        monkeypatch.setenv("PA_SPEC_ADMIT", "1")
        out = http_solve(srv.url, "p8", bg, x0=x0g, tol=1e-9,
                         deadline=1e-9, tag="doomed")
        assert out["http_status"] == 422
        assert out["error"] == "DeadlineInfeasible"
        d = out["diagnostics"]
        assert d["predicted_s"] > d["available_s"]
        assert d["predicted_iters"] >= 1 and d["s_per_it"] > 0
        # refused at the door: nothing reached the tenant service, the
        # typed counters and events tell exactly one story
        assert reg.counter("service.admitted").value == admitted0
        assert reg.counter("spec.infeasible").value == infeasible0 + 1
        assert telemetry.counter("events.deadline_infeasible") == (
            ev_inf0 + 1
        )
        assert telemetry.counter("events.health_error") == (
            ev_health0 + 1
        )
        assert svc.stats["slabs"] == 1  # only the training slab ran
        # a generous deadline admits and completes under the same env
        out = http_solve(srv.url, "p8", bg, x0=x0g, tol=1e-9,
                         deadline=3600.0, tag="fine")
        assert out["state"] == "done" and out["info"]["converged"]
        monkeypatch.delenv("PA_SPEC_ADMIT")
        # default-off: the same hopeless deadline is admitted and can
        # only fail later by EXPIRY (the pre-paspec behavior preserved)
        out = http_solve(srv.url, "p8", bg, x0=x0g, tol=1e-9,
                         deadline=1e-9, tag="legacy")
        assert out.get("http_status") != 422
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# the committed artifact
# ---------------------------------------------------------------------------


def test_committed_spectrum_artifact_store_roundtrip():
    """The committed SPECTRUM.json loads back into a `SpectrumStore`
    whose spec forecasts — the admission path can bootstrap from the
    committed record before any live solve measures."""
    rec = json.load(open(os.path.join(REPO, "SPECTRUM.json")))
    st = telemetry.SpectrumStore.load(rec)
    conf = rec["conformance"]
    spec = st.spec(conf["fingerprint"], conf["dtype"],
                   conf["minv_class"])
    assert spec is not None and spec["kappa"] is not None
    pred = spectrum.predict_iters(spec, 1e-8, r0_norm=100.0)
    assert isinstance(pred, int) and pred >= 1
