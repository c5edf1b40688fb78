"""The pamon observability plane (round 12): deterministic histograms,
the typed metric registry, SLO/throughput accounting, and the overhead
pin.

The tentpole's hard contracts, pinned here:

* **Determinism.** Histogram bucket edges are module constants — two
  histograms fed the same values are byte-identical JSON; merge is
  associative; quantile estimates BRACKET the true quantile;
  snapshot→delta→apply_delta round-trips exactly. No wall-clock ever
  enters a deterministic field.
* **Thread safety.** Counters, the record/event layer, and histograms
  all serialize on the ONE registry lock — the two-thread hammer
  asserts exact totals (the PR 9 satellite: the service background
  worker used to race the submitting thread on bare dict/list
  mutation).
* **Observing stays free.** With the registry fully enabled (PA_MON on,
  metrics flowing) the compiled block program is byte-identical
  StableHLO to the PA_MON=0 build, and the service slab still consumes
  the bare block body's cached program (program-cache HIT — zero extra
  collectives by construction).
* **The adaptive-K input.** Finished slabs feed the EWMA throughput
  model; its curve/suggest_k readouts are the measured per-RHS surface
  ROADMAP item 1 was blocked on.

Plus the operator surfaces: `tools/pamon.py --check` (the tier-1
smoke) and `tools/patrace.py --service` (per-slab timeline join).
"""
import importlib.util
import json
import os
import threading

import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import telemetry
from partitionedarrays_jl_tpu.models import assemble_poisson
from partitionedarrays_jl_tpu.service import SolveService
from partitionedarrays_jl_tpu.telemetry.histogram import (
    BUCKET_BOUNDS,
    LatencyHistogram,
    apply_delta,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# histogram determinism
# ---------------------------------------------------------------------------


def test_histogram_fixed_bounds_and_byte_stable_json():
    """The bucket layout is a module constant (4/decade, 1e-7..1e4 s),
    and identical observations produce byte-identical JSON — no
    wall-clock, no data-dependent layout."""
    assert len(BUCKET_BOUNDS) == 45
    assert BUCKET_BOUNDS[0] == pytest.approx(1e-7)
    assert BUCKET_BOUNDS[-1] == pytest.approx(1e4)
    assert all(
        b2 > b1 for b1, b2 in zip(BUCKET_BOUNDS, BUCKET_BOUNDS[1:])
    )
    # ratio between consecutive edges is the fixed 10^(1/4) factor
    assert BUCKET_BOUNDS[1] / BUCKET_BOUNDS[0] == pytest.approx(
        10.0 ** 0.25
    )
    values = [3e-8, 1e-4, 1e-4, 0.02, 0.5, 7.0, 1e5]
    a, b = LatencyHistogram(), LatencyHistogram()
    for v in values:
        a.observe(v)
    for v in values:
        b.observe(v)
    assert a.to_json() == b.to_json()
    snap = json.loads(a.to_json())
    assert set(snap) == {
        "histogram_schema_version", "count", "sum", "min", "max",
        "buckets",
    }
    # underflow and overflow both land (first and last bucket index)
    assert snap["buckets"]["0"] == 1
    assert snap["buckets"][str(len(BUCKET_BOUNDS))] == 1
    # round-trip through the snapshot is exact
    assert LatencyHistogram.from_snapshot(snap).to_json() == a.to_json()


def test_histogram_merge_associative_and_commutative():
    rng = np.random.default_rng(7)
    parts = [rng.lognormal(-6, 3, 50) for _ in range(3)]
    hs = []
    for p in parts:
        h = LatencyHistogram()
        for v in p:
            h.observe(float(v))
        hs.append(h)
    ab_c = hs[0].copy().merge(hs[1]).merge(hs[2])
    a_bc = hs[0].copy().merge(hs[1].copy().merge(hs[2]))
    c_ba = hs[2].copy().merge(hs[1]).merge(hs[0])
    # counts/min/max/quantiles agree exactly; sums up to fp fold order
    for other in (a_bc, c_ba):
        assert other.counts == ab_c.counts
        assert (other.total, other.min, other.max) == (
            ab_c.total, ab_c.min, ab_c.max,
        )
        assert other.sum == pytest.approx(ab_c.sum, rel=1e-12)
    # merged == histogram of the concatenation
    flat = LatencyHistogram()
    for p in parts:
        for v in p:
            flat.observe(float(v))
    assert flat.counts == ab_c.counts


def test_histogram_quantile_brackets_true_quantile():
    rng = np.random.default_rng(11)
    values = np.sort(rng.lognormal(-5, 2, 400))
    h = LatencyHistogram()
    for v in values:
        h.observe(float(v))
    for q in (0.05, 0.25, 0.5, 0.9, 0.99):
        true_q = float(values[min(len(values) - 1,
                                  max(0, int(np.ceil(q * len(values))) - 1))])
        lo, hi = h.quantile_bounds(q)
        assert lo <= true_q <= hi, (q, lo, true_q, hi)
        assert h.quantile(q) == hi  # the conservative upper edge
        # the bracket is one fixed bucket wide at most
        assert hi / max(lo, 1e-300) <= 10.0 ** 0.25 + 1e-9 or lo == hi
    assert h.quantile_bounds(0.0)[0] == h.min
    assert h.quantile(1.0) == h.max


def test_histogram_snapshot_delta_roundtrip():
    h = LatencyHistogram()
    for v in (1e-3, 2e-3, 0.5):
        h.observe(v)
    snap_a = h.snapshot()
    for v in (1e-6, 0.5, 20.0):
        h.observe(v)
    snap_b = h.snapshot()
    delta = h.delta(snap_a)
    assert delta["count"] == 3
    assert apply_delta(snap_a, delta) == snap_b
    # an empty delta round-trips too (min/max keep the earlier state)
    assert apply_delta(snap_b, h.delta(snap_b)) == snap_b
    # the round-trip is exact for ARBITRARY data, not just friendly
    # values: float sums do not invert under IEEE subtraction, so the
    # delta carries the current sum verbatim (review finding — 27/2000
    # random round-trips mismatched under the naive prev+diff scheme)
    rng = np.random.default_rng(3)
    g = LatencyHistogram()
    prev = g.snapshot()
    for _ in range(200):
        for v in rng.lognormal(0, 5, 10):
            g.observe(float(v))
        cur = g.snapshot()
        assert apply_delta(prev, g.delta(prev)) == cur
        prev = cur


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------


def test_registry_types_exporters_and_catalog_enforcement():
    reg = telemetry.registry()
    reg.reset("t_pamon")
    try:
        c = reg.counter("t_pamon.c")
        c.inc()
        c.inc(2)
        g = reg.gauge("t_pamon.g")
        g.set(4.0)
        g.inc()
        g.dec(2.0)
        h = reg.histogram("t_pamon.h")
        h.observe(0.25)
        lc = reg.counter("t_pamon.slo", labels={"tol_class": "1e-08"})
        lc.inc(5)
        snap = reg.snapshot("t_pamon")
        assert snap["counters"] == {
            "t_pamon.c": 3, "t_pamon.slo{tol_class=1e-08}": 5,
        }
        assert snap["gauges"] == {"t_pamon.g": 3.0}
        assert snap["histograms"]["t_pamon.h"]["count"] == 1
        # deterministic JSON (sorted keys, no wall-clock)
        assert reg.to_json("t_pamon") == reg.to_json("t_pamon")
        prom = reg.to_prometheus()
        assert "pa_t_pamon_c 3" in prom
        assert "pa_t_pamon_g 3" in prom
        assert '# TYPE pa_t_pamon_h histogram' in prom
        assert "pa_t_pamon_h_count 1" in prom
        assert 'pa_t_pamon_slo{tol_class="1e-08"} 5' in prom
        # cumulative le buckets end at +Inf == count
        inf_line = [ln for ln in prom.splitlines()
                    if ln.startswith('pa_t_pamon_h_bucket{le="+Inf"}')]
        assert inf_line == ['pa_t_pamon_h_bucket{le="+Inf"} 1']
        # a declared name must be touched with its declared kind
        with pytest.raises(TypeError):
            reg.gauge("lowering_cache.hit")
        with pytest.raises(TypeError):
            reg.counter("service.queue_wait_s")
        with pytest.raises(TypeError):
            reg.gauge("events.solve_aborted")
    finally:
        reg.reset("t_pamon")


def test_registry_two_thread_hammer():
    """The PR 9 thread-safety satellite, as a lean hammer: two threads
    bump ONE counter, observe ONE histogram, and emit events into the
    SAME active record; every total must be exact (the pre-registry
    code raced on bare dict/list mutation from the service worker)."""
    reg = telemetry.registry()
    reg.reset("t_hammer")
    rec = telemetry.begin_record("t-hammer")
    N_BUMP, N_OBS, N_EV = 2000, 500, 200
    errors = []

    def work():
        try:
            c = reg.counter("t_hammer.c")
            h = reg.histogram("t_hammer.h")
            for i in range(N_BUMP):
                c.inc()
            for i in range(N_OBS):
                h.observe(1e-3)
            for i in range(N_EV):
                telemetry.emit_event("t_hammer", label="x", i=i)
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=work) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        assert not errors
        assert reg.counter_value("t_hammer.c") == 2 * N_BUMP
        assert reg.histogram("t_hammer.h").count == 2 * N_OBS
        assert len(rec.events_of("t_hammer")) == 2 * N_EV
        assert telemetry.counter("events.t_hammer") >= 2 * N_EV
    finally:
        rec.finish(None)
        telemetry.clear_history()
        reg.reset("t_hammer")
        reg.reset("events.t_hammer")


# ---------------------------------------------------------------------------
# the throughput model
# ---------------------------------------------------------------------------


def test_throughput_model_ewma_suggest_k_and_kill_switch(monkeypatch):
    m = telemetry.ThroughputModel(alpha=0.5)
    m.observe_slab("op", "float32", 4, 0.010, 10)
    m.observe_slab("op", "float32", 4, 0.020, 10)  # EWMA: 0.015
    assert m.s_per_it("op", "float32", 4) == pytest.approx(0.015)
    assert m.per_rhs("op", "float32", 4) == pytest.approx(0.015 / 4)
    m.observe_slab("op", "float32", 1, 0.004, 10)
    m.observe_slab("op", "float32", 8, 0.016, 10)
    # per-RHS: K=1 -> 4.0e-3, K=4 -> 3.75e-3, K=8 -> 2.0e-3
    assert m.curve("op", "float32") == pytest.approx(
        {1: 0.004, 4: 0.00375, 8: 0.002}
    )
    assert m.suggest_k("op", "float32", queue_depth=64, kmax=8) == 8
    assert m.suggest_k("op", "float32", queue_depth=6, kmax=8) == 4
    assert m.suggest_k("op", "float32", queue_depth=1, kmax=8) == 1
    # unmeasured operator: fall back to the static min(queue, kmax)
    assert m.suggest_k("other", "float32", 3, 8) == 3
    # export/load round-trip preserves the table
    again = telemetry.ThroughputModel.load(m.export())
    assert again.export()["entries"] == m.export()["entries"]
    # degenerate observations are refused, kill switch gates updates
    m.observe_slab("op", "float32", 4, 0.0, 10)
    m.observe_slab("op", "float32", 4, 0.5, 0)
    assert m.s_per_it("op", "float32", 4) == pytest.approx(0.015)
    monkeypatch.setenv("PA_MON", "0")
    m.observe_slab("op", "float32", 4, 99.0, 10)
    assert m.s_per_it("op", "float32", 4) == pytest.approx(0.015)


# ---------------------------------------------------------------------------
# service instrumentation end-to-end
# ---------------------------------------------------------------------------


def _counters(*names):
    return {n: telemetry.counter(n) for n in names}


def test_service_lifecycle_metrics_end_to_end():
    """One drained service exercises the whole declared surface:
    lifecycle histograms with the right observation counts, gauges in
    their terminal state, SLO attainment for the deadline class, and a
    throughput-model entry under the service's fingerprint."""
    reg = telemetry.registry()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8))
        before_h = {
            n: reg.histogram(n).count
            for n in ("service.queue_wait_s", "service.slab_wait_s",
                      "service.solve_s", "service.total_s",
                      "service.deadline_slack_s")
        }
        before_c = _counters("service.admitted", "service.completed",
                             "service.slabs", "service.slabs_ragged")
        before_slo = reg.counter(
            "service.slo.requests", labels={"tol_class": "1e-09"}
        ).value
        before_hits = reg.counter(
            "service.slo.hits", labels={"tol_class": "1e-09"}
        ).value
        telemetry.reset_model()
        svc = SolveService(A, kmax=4)
        hs = [
            svc.submit(b, x0=x0, tol=1e-9, deadline=3600.0,
                       tag=f"m-{i}")
            for i in range(3)  # 3 < kmax: a ragged slab
        ]
        svc.drain()
        for h in hs:
            assert h.result()[1]["converged"]
            assert h.finished_at is not None
            assert h.finished_at >= h.submitted_at
        d_c = {
            k: telemetry.counter(k) - v for k, v in before_c.items()
        }
        assert d_c["service.admitted"] == 3
        assert d_c["service.completed"] == 3
        assert d_c["service.slabs"] == 1
        assert d_c["service.slabs_ragged"] == 1
        d_h = {
            n: reg.histogram(n).count - c for n, c in before_h.items()
        }
        assert d_h["service.queue_wait_s"] == 3
        assert d_h["service.total_s"] == 3
        assert d_h["service.deadline_slack_s"] == 3
        assert d_h["service.slab_wait_s"] == 1
        assert d_h["service.solve_s"] >= 1  # one per chunk
        # gauges: drained service, nothing queued or in flight; the
        # last slab was 3 of 4 wide and ragged
        snap = reg.snapshot("service")
        assert snap["gauges"]["service.queue_depth"] == 0
        assert snap["gauges"]["service.inflight_slabs"] == 0
        assert snap["gauges"]["service.slab_utilization"] == 0.75
        assert 0 < snap["gauges"]["service.ragged_fraction"] <= 1
        # SLO: all three deadline-carrying requests hit the 1e-09 class
        assert reg.counter(
            "service.slo.requests", labels={"tol_class": "1e-09"}
        ).value - before_slo == 3
        assert reg.counter(
            "service.slo.hits", labels={"tol_class": "1e-09"}
        ).value - before_hits == 3
        # the slab fed the throughput model under this service's key
        model = telemetry.throughput_model()
        dtype = str(np.dtype(b.dtype))
        curve = model.curve(svc.fingerprint, dtype)
        assert 3 in curve and curve[3] > 0
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))


def test_pa_mon_kill_switch_gates_instrumentation(monkeypatch):
    """PA_MON=0: counters and records keep working (their PR 6
    contracts), but histograms/gauges/throughput stay silent."""
    monkeypatch.setenv("PA_MON", "0")
    reg = telemetry.registry()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8))
        before_h = reg.histogram("service.total_s").count
        before_sl = reg.histogram("service.deadline_slack_s").count
        before_c = telemetry.counter("service.completed")
        before_slo = reg.counter(
            "service.slo.requests", labels={"tol_class": "1e-09"}
        ).value
        telemetry.reset_model()
        svc = SolveService(A, kmax=2)
        h = svc.submit(b, x0=x0, tol=1e-9, deadline=3600.0, tag="off")
        svc.drain()
        assert h.result()[1]["converged"]
        assert telemetry.counter("service.completed") == before_c + 1
        # SLO attainment is a COUNTER — always on, like every counter
        assert reg.counter(
            "service.slo.requests", labels={"tol_class": "1e-09"}
        ).value == before_slo + 1
        # ...while the histograms stay silent
        assert reg.histogram("service.total_s").count == before_h
        assert reg.histogram(
            "service.deadline_slack_s"
        ).count == before_sl
        assert telemetry.throughput_model().curve(
            svc.fingerprint, str(np.dtype(b.dtype))
        ) == {}
        # the event/record layer is untouched by PA_MON
        assert h.record.finished
        assert any(e.kind == "request_done" for e in h.record.events)
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))


# ---------------------------------------------------------------------------
# the overhead pin: observing stays free
# ---------------------------------------------------------------------------


def test_block_program_hlo_identical_with_registry_enabled(monkeypatch):
    """The acceptance pin: a registry-on build (PA_MON=1, metrics
    flowing through the registry) lowers the block body to
    byte-identical StableHLO vs the killed plane (PA_MON=0) — the
    program-cache-hit leg lives in
    test_service.py::test_service_consumes_bare_block_program, which
    runs under the default-enabled registry."""
    import jax

    from partitionedarrays_jl_tpu.parallel.tpu import (
        TPUBackend,
        _matrix_operands,
        device_matrix,
        make_cg_fn,
    )

    backend = TPUBackend(devices=jax.devices()[:8])

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (6, 6, 6))
        return A

    A = pa.prun(driver, backend, (2, 2, 2))
    dA = device_matrix(A, backend)
    ops = _matrix_operands(dA)
    P, W = dA.col_plan.layout.P, dA.col_plan.layout.W
    zb = np.zeros((P, W, 2))

    def text():
        fn = make_cg_fn(dA, tol=1e-9, maxiter=50, rhs_batch=2)
        return fn.jit_fn.lower(zb, zb, zb[..., 0], ops).as_text()

    # fully enabled AND carrying live data (a non-empty registry must
    # not leak anything into a traced program)
    telemetry.registry().histogram("service.solve_s").observe(0.01)
    on = text()
    monkeypatch.setenv("PA_MON", "0")
    off = text()
    assert on == off


# ---------------------------------------------------------------------------
# the operator surfaces: pamon --check, patrace --service
# ---------------------------------------------------------------------------


def test_pamon_check_smoke(capsys):
    """`tools/pamon.py --check` is the tier-1 smoke of the whole plane:
    demo service, invariant assertions, every render surface."""
    pamon = _load_tool("pamon")
    rc = pamon.main(["--check"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "pamon --check: OK" in out
    assert "service.total_s" in out
    assert "SLO attainment" in out
    # the model the demo service fitted online, rendered
    assert "throughput model" in out


def test_patrace_service_timeline_joins_slab(tmp_path, monkeypatch,
                                             capsys):
    """`tools/patrace.py --service`: the poisoned-column incident —
    previously smeared across K per-request records — reads as ONE
    slab story: formation, the verdict, the ejection, each request's
    outcome, with the cross-record duplicates deduped."""
    d = str(tmp_path / "svc-recs")
    monkeypatch.setenv("PA_METRICS_DIR", d)

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8))
        bad = b.copy()

        def poison(i, vals):
            if int(i.part) == 0:
                np.asarray(vals)[0] = np.nan

        pa.map_parts(poison, bad.rows.partition, bad.values)
        svc = SolveService(A, kmax=3, retries=0)
        svc.submit(b, x0=x0, tol=1e-9, tag="tl-good")
        svc.submit(bad, x0=x0, tol=1e-9, tag="tl-bad")
        svc.submit(b, x0=x0, tol=1e-9, tag="tl-good2")
        svc.drain()
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))
    patrace = _load_tool("patrace")
    rc = patrace.main(["--service", "--dir", d])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "slab 0: K=3" in out
    assert "tl-good, tl-bad, tl-good2" in out
    # the story is joined AND deduped: each lifecycle line once
    assert out.count("column_ejected") == 1
    assert out.count("request_failed:tl-bad") == 1
    assert out.count("slab_formed:K=3") == 1
    assert "outcomes:" in out
    assert "tl-bad FAILED(NonFiniteError)" in out
    assert "tl-good converged" in out


def test_patrace_service_all_ejected_slab_shows_retry_story(
    tmp_path, monkeypatch, capsys
):
    """ISSUE-14 BUGFIX pin: a slab whose EVERY request is ejected and
    retried solo must render the retry continuation — the injected
    faults, the typed health errors, the aborted attempts of the
    nested solo solves — inside the incident view, not just the bare
    formed/ejected/done skeleton. Pre-fix those events were dropped as
    unnamed (the nested records never name the request); now they join
    by their ejection-window timing, annotated ``retry_of``."""
    from partitionedarrays_jl_tpu.parallel.faults import inject_faults

    d = str(tmp_path / "svc-recs")
    monkeypatch.setenv("PA_METRICS_DIR", d)

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8))
        svc = SolveService(A, kmax=2, retries=1, retry_backoff=0.0)
        # one one-shot fault per slab column: BOTH columns eject, both
        # solo retries heal (the faults do not refire)
        with inject_faults("nan@part=1,call=5;nan@part=1,call=9",
                           seed=1):
            r0 = svc.submit(b, x0=x0, tol=1e-9, tag="ej-0")
            r1 = svc.submit(b, x0=x0, tol=1e-9, tag="ej-1")
            svc.drain()
        assert r0.state == "done" and r1.state == "done"
        assert svc.stats["ejected"] == 2
        assert svc.stats["retried_solo"] == 2
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))
    patrace = _load_tool("patrace")
    rc = patrace.main(["--service", "--dir", d])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "slab 0: K=2" in out
    # the continuation story renders inside the slab timeline
    assert "fault_injected:nan" in out
    assert "health_error:NonFiniteError" in out
    assert "solve_aborted:NonFiniteError" in out
    # ejection-window attribution: retry-window events name their
    # owner (the first fault fires in the SLAB pass, pre-ejection —
    # the in-window ones carry retry_of)
    assert out.count("column_ejected") == 2
    assert "ej-0 converged" in out and "ej-1 converged" in out


# ---------------------------------------------------------------------------
# round 13 (ISSUE 10): exporter label hygiene, labeled-histogram
# concurrency, adaptive K
# ---------------------------------------------------------------------------


def test_prometheus_label_hygiene_with_hostile_value():
    """Exposition-format escaping: a label value carrying backslash,
    double quote, and newline must render escaped (\\\\, \\", \\n), the
    scrape must stay line-structured, and a LABELED histogram must emit
    ``_bucket``/``_sum``/``_count`` all carrying the identical escaped
    label set with the +Inf bucket equal to ``_count``."""
    import re

    reg = telemetry.registry()
    reg.reset("t_esc")
    try:
        hostile = 'wei"rd\\lab\nel'
        reg.counter("t_esc.c", labels={"tag": hostile}).inc(3)
        h = reg.histogram("t_esc.h", labels={"tag": hostile})
        h.observe(0.5)
        h.observe(2.0)
        prom = reg.to_prometheus()
        esc = 'tag="wei\\"rd\\\\lab\\nel"'
        assert "pa_t_esc_c{%s} 3" % esc in prom
        # every series line still parses as one NAME{LABELS} VALUE line
        # (an unescaped newline/quote would shatter this)
        for ln in prom.splitlines():
            if ln.startswith("#") or not ln:
                continue
            assert re.fullmatch(
                r"[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? \S+", ln
            ), ln
        hist_lines = [
            ln for ln in prom.splitlines()
            if ln.startswith("pa_t_esc_h")
        ]
        buckets = [ln for ln in hist_lines if "_bucket{" in ln]
        sums = [ln for ln in hist_lines if ln.startswith("pa_t_esc_h_sum")]
        counts = [
            ln for ln in hist_lines if ln.startswith("pa_t_esc_h_count")
        ]
        assert buckets and len(sums) == 1 and len(counts) == 1
        # identical escaped label set on every series of the family
        assert all(esc in ln for ln in buckets + sums + counts)
        assert sums[0] == "pa_t_esc_h_sum{%s} 2.5" % esc
        assert counts[0] == "pa_t_esc_h_count{%s} 2" % esc
        inf = [ln for ln in buckets if 'le="+Inf"' in ln]
        assert len(inf) == 1 and inf[0].endswith(" 2")
    finally:
        reg.reset("t_esc")


def test_labeled_histogram_two_thread_observe_vs_snapshot_hammer():
    """ISSUE-10 lean concurrency pin: one thread observes a LABELED
    histogram while another snapshots it through the shared lock —
    every snapshot must be internally consistent (bucket sum == count)
    and the final total exact. Bounded work, no sleeps."""
    reg = telemetry.registry()
    reg.reset("t_lh")
    try:
        labels = {"tol_class": "1e-08"}
        h = reg.histogram("t_lh.h", labels=labels)
        N = 3000
        torn = []
        done = threading.Event()

        def observer():
            for i in range(N):
                h.observe(1e-3 if i % 2 else 1e-1)
            done.set()

        def snapshotter():
            while not done.is_set():
                snap = h.snapshot()
                if sum(snap["buckets"].values()) != snap["count"]:
                    torn.append(snap)
            # one read after the writer finished: the final state
            snap = reg.snapshot("t_lh")["histograms"][
                "t_lh.h{tol_class=1e-08}"
            ]
            torn.extend(
                [snap]
                if sum(snap["buckets"].values()) != snap["count"]
                else []
            )

        threads = [
            threading.Thread(target=observer),
            threading.Thread(target=snapshotter),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not torn, torn[:1]
        assert h.count == N
    finally:
        reg.reset("t_lh")


def test_histogram_merge_associative_across_label_sets():
    """Per-label-set histograms roll up into one view in ANY grouping
    order: (a+b)+c == a+(b+c) == (c+b)+a, bucket-exactly — the
    property that lets per-class SLO histograms aggregate."""
    feeds = {
        "1e-06": [1e-4, 2e-3, 2e-3],
        "1e-08": [5e-2, 7e-1],
        "1e-10": [3.0, 3e-5, 9e-2, 2e-2],
    }
    hists = {}
    for cls, values in feeds.items():
        h = LatencyHistogram()
        for v in values:
            h.observe(v)
        hists[cls] = h
    a, b, c = (hists[k] for k in sorted(feeds))
    left = a.copy().merge(b).merge(c)
    bc = b.copy().merge(c)
    right = a.copy().merge(bc)
    rev = c.copy().merge(b).merge(a)
    assert left.snapshot() == right.snapshot()
    assert left.counts == rev.counts
    assert left.total == rev.total == sum(len(v) for v in feeds.values())
    assert left.min == rev.min and left.max == rev.max


def test_adaptive_k_picks_measured_optimum_and_static_path_unchanged(
    monkeypatch,
):
    """ISSUE-10 satellite: PA_SERVE_ADAPTIVE_K=1 caps the slab at
    suggest_k's measured per-RHS optimum (a deep queue picks the
    measured-best width, not kmax); off (default) the static
    PA_SERVE_KMAX path coalesces exactly as before."""
    from partitionedarrays_jl_tpu.service.batcher import effective_kmax

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8))
        telemetry.reset_model()
        try:
            svc = SolveService(A, kmax=4, queue_depth=16)
            m = telemetry.throughput_model()
            dt = str(np.dtype(b.dtype))
            # measured per-RHS curve with its optimum at K=2:
            # K=1 -> 4.0e-3, K=2 -> 1.5e-3, K=4 -> 4.0e-3 per RHS
            m.observe_slab(svc.fingerprint, dt, 1, 0.004, 10)
            m.observe_slab(svc.fingerprint, dt, 2, 0.003, 10)
            m.observe_slab(svc.fingerprint, dt, 4, 0.016, 10)
            handles = [
                svc.submit(b, x0=x0, tol=1e-9, tag=f"ad-{i}")
                for i in range(6)
            ]
            # adaptive ON: a 6-deep queue forms a width-2 slab
            monkeypatch.setenv("PA_SERVE_ADAPTIVE_K", "1")
            assert effective_kmax(svc._queue, svc.kmax,
                                  svc.fingerprint) == 2
            # ...and a chunk-boundary top_up of a width-2 RUNNING slab
            # honors the same cap (anchor = the slab, base = its
            # width): no refill back toward the static kmax
            from partitionedarrays_jl_tpu.service.batcher import top_up

            queue = list(svc._queue)
            slab = [queue.pop(0), queue.pop(0)]
            cap = effective_kmax(queue, svc.kmax, svc.fingerprint,
                                 anchor=slab[0], base=len(slab))
            assert cap == 2
            assert top_up(queue, slab, cap) == []
            assert len(queue) == 4  # nothing consumed
            assert svc.step() == 2
            # OFF (the default): the static path runs kmax wide
            monkeypatch.delenv("PA_SERVE_ADAPTIVE_K")
            assert effective_kmax(svc._queue, svc.kmax,
                                  svc.fingerprint) == 4
            assert svc.step() == 4
            assert svc.pending() == 0
            for h in handles:
                x, info = h.result()
                assert info["converged"]
            # an UNMEASURED operator under adaptive K falls back to
            # the static min(depth, kmax) policy
            monkeypatch.setenv("PA_SERVE_ADAPTIVE_K", "1")
            svc2 = SolveService(A, kmax=4, queue_depth=16)
            telemetry.reset_model()
            q = [svc2.submit(b, x0=x0, tol=1e-9, tag="un-0"),
                 svc2.submit(b, x0=x0, tol=1e-9, tag="un-1")]
            assert effective_kmax(svc2._queue, svc2.kmax,
                                  svc2.fingerprint) == 2
            assert svc2.step() == 2
            for h in q:
                h.result()
        finally:
            telemetry.reset_model()
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))
