"""A served request is queued when it arrives (PR 37): the paspec forecast
of a request that nobody reads before it runs takes `||b - A x0||` from
its slab's own first residual, when its first column reports, and a
request whose forecast IS read at `submit` (a deadline under
`PA_SPEC_ADMIT=1`, or a caller that brings `r0_norm`) keeps the parent's
path. 8x8 Poisson on the sequential backend; one case on a `TPUBackend`
over one CPU device in float32, the precision the device reports in."""
import jax
import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import telemetry
from partitionedarrays_jl_tpu.models import assemble_poisson
from partitionedarrays_jl_tpu.parallel.faults import inject_faults
from partitionedarrays_jl_tpu.parallel.health import DeadlineInfeasible
from partitionedarrays_jl_tpu.service import SolveService
from partitionedarrays_jl_tpu.service import service as service_mod
from partitionedarrays_jl_tpu.telemetry import spectrum

TOL = 1e-9
BOTH = ("service.forecasts", "service.forecasts_deferred")
RESIDUAL_NORM = spectrum.residual_norm


def host_system():
    return pa.prun(
        lambda parts: assemble_poisson(parts, (8, 8)), pa.sequential, (2, 2)
    )


def device_system():
    return pa.prun(
        lambda parts: pa.assemble_poisson(
            parts, (12, 12, 12), dtype=np.float32, decoupled=True
        ),
        pa.TPUBackend(devices=jax.devices()[:1]), (1, 1, 1),
    )


def trained(A, b, x0, tol=TOL, **kw) -> SolveService:
    """A service whose operator a first request has measured."""
    svc = SolveService(A, **kw)
    h = svc.submit(b, x0=x0, tol=tol, tag="train")
    svc.drain()
    assert h.result()[1]["converged"]
    assert spectrum.has_spec(
        spectrum.spectrum_fingerprint(A), str(np.dtype(b.dtype)), "none"
    )
    return svc


def the_norm_is_not_taken(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("residual_norm called on the host")

    monkeypatch.setattr(spectrum, "residual_norm", refuse)


def spans_opened(monkeypatch) -> list:
    """The names `service.py` hands to `annotate`, as they are opened."""
    names, annotate = [], service_mod.annotate

    def recording(name):
        names.append(name)
        return annotate(name)

    monkeypatch.setattr(service_mod, "annotate", recording)
    return names


def grew(before: dict, *names) -> dict:
    after = telemetry.counters("")
    return {k: after.get(k, 0) - before.get(k, 0) for k in names}


def at_submit(A, b, x0, svc, tol=TOL) -> dict:
    """What the parent's `submit` would have stamped: the same formula
    over the host's float64 norm, on the store as it stands now."""
    return spectrum.admission_prediction(
        spectrum.spectrum_fingerprint(A), str(np.dtype(b.dtype)), "none",
        tol, r0_norm=RESIDUAL_NORM(A, b, x0), cost_fingerprint=svc.fingerprint,
    )


def checked(req) -> list:
    """The request's own `forecast_checked` events (an event lands on
    every record that is active when it is emitted)."""
    return [e for e in req.record.events_of("forecast_checked")
            if e.label == req.tag]


@pytest.mark.parametrize("system,tol", [(host_system, TOL), (device_system, 1e-5)],
                         ids=["host", "device-f32"])
def test_a_request_nobody_gates_is_forecast_from_its_slab(system, tol, monkeypatch):
    A, b, _xe, x0 = system()
    svc = trained(A, b, x0, tol=tol)
    names = spans_opened(monkeypatch)
    the_norm_is_not_taken(monkeypatch)
    before = telemetry.counters("")
    svc.start()
    h = svc.submit(b, x0=x0, tol=tol)
    assert "pa:forecast:fingerprint" in names
    assert "pa:forecast:norm" not in names
    _x, info = h.wait(60.0)
    svc.shutdown()
    assert info["converged"]
    assert grew(before, *BOTH, "spec.predictions") == {
        BOTH[0]: 0, BOTH[1]: 1, "spec.predictions": 1,
    }
    want = at_submit(A, b, x0, svc, tol)
    assert h.record.config["forecast"] == h.forecast
    assert abs(h.forecast["predicted_iters"] - want["predicted_iters"]) <= 1
    assert {k: h.forecast[k] for k in ("kappa", "rate", "samples")} == {
        k: want[k] for k in ("kappa", "rate", "samples")
    }
    (ev,) = checked(h)
    assert ev.details["predicted"] == h.forecast["predicted_iters"]
    assert ev.iteration == info["iterations"]


def test_the_forecast_is_made_and_checked_once_whatever_the_ride(monkeypatch):
    """A K=2 slab, a request of two chunks and more (a deadline with the
    gate off), and a column the host oracle contained before it reported
    anything, healed by a solo retry: one prediction each for the first
    three, from the norm of the ORIGINAL start, and none for the last."""
    A, b, _xe, x0 = host_system()
    svc = trained(A, b, x0, kmax=2, chunk=5, retries=1)
    r0 = RESIDUAL_NORM(A, b, x0)
    the_norm_is_not_taken(monkeypatch)
    made, predict = [], spectrum.admission_prediction

    def recording(*a, r0_norm=None, **k):
        made.append((r0_norm, predict(*a, r0_norm=r0_norm, **k)))
        return made[-1][1]

    monkeypatch.setattr(spectrum, "admission_prediction", recording)
    before, predicted = telemetry.counters(""), svc.stats["predicted"]
    pair = [svc.submit(b, x0=x0, tol=TOL, tag=f"pair-{i}") for i in range(2)]
    svc.drain()
    chunks = svc.submit(b, x0=x0, tol=TOL, deadline=1e6, tag="chunks")
    svc.drain()
    assert chunks.result()[1]["iterations"] > 2 * svc.chunk
    assert svc.stats["slabs"] == 3  # the training slab, the pair, the chunks
    assert [r for r, _ in made] == pytest.approx([r0] * 3, rel=1e-12)
    for h, (_, forecast) in zip(pair + [chunks], made):
        assert h.forecast is forecast and h._forecast_owed is None
        (ev,) = checked(h)
        assert ev.details["predicted"] == forecast["predicted_iters"]
    assert svc.stats["predicted"] == predicted + 3
    assert grew(before, *BOTH, "spec.predictions") == {
        BOTH[0]: 0, BOTH[1]: 3, "spec.predictions": 3,
    }
    with inject_faults("nan@part=1,call=5", seed=1):
        healed = svc.submit(b, x0=x0, tol=TOL, tag="healed")
        svc.drain()
    assert healed.result()[1]["resolved_via"] == "solo_retry"
    assert healed.forecast is None and healed._forecast_owed is None
    assert "forecast" not in healed.record.config and not checked(healed)
    assert svc.stats["predicted"] == predicted + 3 and len(made) == 3


def test_a_gated_deadline_still_pays_its_norm_in_submit(monkeypatch):
    A, b, _xe, x0 = host_system()
    svc = trained(A, b, x0)
    monkeypatch.setenv("PA_SPEC_ADMIT", "1")
    names = spans_opened(monkeypatch)
    before, slabs = telemetry.counters(""), svc.stats["slabs"]
    with pytest.raises(DeadlineInfeasible):
        svc.submit(b, x0=x0, tol=TOL, deadline=1e-9, tag="doomed")
    assert names.count("pa:forecast:norm") == 1
    assert svc.stats["slabs"] == slabs and svc.pending() == 0
    h = svc.submit(b, x0=x0, tol=TOL, deadline=1e6, tag="feasible")
    assert names.count("pa:forecast:norm") == 2
    assert h._forecast_owed is None
    assert h.record.config["forecast"] == h.forecast == at_submit(A, b, x0, svc)
    svc.drain()
    assert len(checked(h)) == 1
    assert grew(before, *BOTH, "spec.infeasible", "spec.predictions") == {
        BOTH[0]: 2, BOTH[1]: 0, "spec.infeasible": 1, "spec.predictions": 1,
    }


def test_a_caller_that_brings_the_norm_is_forecast_at_submit(monkeypatch):
    A, b, _xe, x0 = host_system()
    svc = trained(A, b, x0)
    r0 = RESIDUAL_NORM(A, b, x0)
    the_norm_is_not_taken(monkeypatch)
    before = telemetry.counters("")
    h = svc.submit(b, x0=x0, tol=TOL, r0_norm=r0)
    assert h._forecast_owed is None
    assert h.record.config["forecast"] == h.forecast == at_submit(A, b, x0, svc)
    svc.drain()
    assert len(checked(h)) == 1
    assert grew(before, *BOTH, "spec.predictions") == {
        BOTH[0]: 0, BOTH[1]: 0, "spec.predictions": 1,
    }


def test_a_deferred_request_is_stamped_before_any_work_on_its_vectors(monkeypatch):
    """On a clock that moves a millisecond a reading, `submit` reads it
    four times (its own stretch, the forecast's, the stamp between): a
    fifth reading in front of the stamp would be a step nobody named.
    And no product with `A`, copy or norm of a vector runs in `submit`."""
    A, b, _xe, x0 = host_system()
    ticks = iter(range(10**6))
    svc = trained(A, b, x0, clock=lambda: 1e-3 * next(ticks))
    the_norm_is_not_taken(monkeypatch)

    def no_pass(self, *a, **k):
        raise AssertionError("submit made a pass over n values")

    opened = 1e-3 * next(ticks)
    with monkeypatch.context() as m:
        m.setattr(type(A), "__matmul__", no_pass)
        for name in ("norm", "copy"):
            m.setattr(type(b), name, no_pass)
        h = svc.submit(b, x0=x0, tol=TOL)
    assert h._forecast_owed is not None
    # `submit` opens, the forecast opens and closes, then the stamp
    assert h.submitted_at == pytest.approx(opened + 4e-3)
    svc.drain()
    assert h.forecast is not None
