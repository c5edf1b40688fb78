"""The fault-kind x detector x recovery-path matrix, executable.

docs/resilience.md documents which layer catches each injected fault
kind and what happens next; this file IS that table as tier-1 smoke
tests — one short solve per kind, asserting the documented outcome
(typed error, self-heal to the fault-free answer, or clean
completion), so the matrix can never silently rot into prose.

| kind       | detector                     | documented outcome        |
|------------|------------------------------|---------------------------|
| nan        | free scalar guard            | NonFiniteError; recovery restarts and reproduces the clean run |
| nan + ABFT | exchange slab checksum       | SilentCorruptionError -> in-memory rollback self-heal |
| bitflip    | (none by default)            | SILENT wrong answer — the threat model (pinned in test_abft.py) |
| bitflip + ABFT | exchange slab checksum   | rollback self-heal, bitwise |
| bitflip + audit | true-residual audit     | rollback self-heal, bitwise |
| drop       | exchange deadline            | ExchangeTimeoutError, typed; survivable by restart |
| delay      | nothing to detect            | clean completion (slow host is not an error) |
| controller | runtime surface              | ControllerLostError; survivable by restart |

Round 9 (patrace): each case ALSO asserts its telemetry story — the
injected fault, the detector that fired, and the recovery path taken
all appear as structured events in the solve's `SolveRecord`
(``info.record``, or the aborted record in the history ring for the
typed-raise paths). No recovery may be silent in the event log.

Round 10 (pasolve): the solve service adds request-level rows to the
matrix — faults and overload hitting the MULTI-TENANT layer, each with
its documented outcome and event trail:

| condition               | detector            | documented outcome   |
|-------------------------|---------------------|----------------------|
| queue over depth bound  | admission control   | AdmissionRejected (typed backpressure) + admission_rejected event |
| deadline past at chunk boundary | service clock | SolveDeadlineError + deadline_expired/health_error events; co-batched requests unaffected |
| poisoned column in a shared slab | per-column verdict export | that request ejected + typed NonFiniteError; co-batched requests complete clean (column_verdict/column_ejected/request_failed events) |

Round 12 (pamon): each service row ALSO asserts its METRIC deltas —
the registry counters and histogram counts the incident must move
(rejection/expiry/ejection counters, total-latency and SLO
accounting), so the event log and the metrics plane can never
silently drift apart: an incident that narrates but does not count
(or counts but does not narrate) fails here.

Round 11 (paplan): a corrupted *plan* (mutated slot indices — not wire
data) is a fault class every runtime row above is blind to until the
wrong answer lands; with ``PA_PLAN_VERIFY=1`` it is caught STATICALLY:

| condition               | detector            | documented outcome   |
|-------------------------|---------------------|----------------------|
| corrupted exchange plan | static plan verifier at the build site | PlanSoundnessError (typed, with check + part/slot diagnostics) + plan_defect/health_error events, BEFORE any solve runs |

Round 14 (pagate): the front door adds the TENANCY/overload rows —
failures hitting the multi-OPERATOR layer, each with its documented
outcome, event trail, and metric deltas (docs/service.md Front door):

| condition               | detector            | documented outcome   |
|-------------------------|---------------------|----------------------|
| operator footprint over PA_GATE_MEM_BUDGET | registry admission | TenantBudgetError (typed; tenant never registered) + tenant_budget_rejected event + gate.budget_rejected counter |
| gate queue past the shed watermark | SLO-class shed policy | lowest class refused with typed LoadShedded (retry_after_s / HTTP 429 Retry-After) + load_shedded event + gate.shed{slo_class=…}; DISTINCT from service.rejected{reason=queue_full} |
| eviction during an in-flight chunked solve | LRU paging + PR 7 checkpoint path | request_checkpointed at the chunk boundary, tenant_evicted/tenant_requeued/tenant_paged_in events, checkpoint_restore on resume, and the request COMPLETES from its saved iterate |

Round 15 (padur): the DURABILITY rows — the gate's own death, each
with its documented outcome, event trail, and metric deltas
(docs/resilience.md Durability):

| condition               | detector            | documented outcome   |
|-------------------------|---------------------|----------------------|
| gate killed mid-solve (kill -9 semantics: state abandoned, no shutdown) | write-ahead journal replay at the next start | Gate.recover() resumes the in-flight request from its chunk-checkpointed iterate (gate.recovered{outcome=resumed}, request_recovered/gate_recovered/checkpoint_restore events) and it COMPLETES; nothing lost, nothing duplicated |
| torn journal tail (crash mid-append) | per-record CRC32 at replay | tail truncated (journal.truncated + journal_truncated event), clean prefix recovered intact; mid-file corruption raises typed JournalCorruptError instead |
| duplicate idempotency-key submit | gate key map (journal-rebuilt) | original id + bitwise result returned (gate.idempotent_hits + idempotent_replay event); service.admitted does NOT move — a single solve, across restarts included |

Round 16 (pafleet): the REPLICATION rows — faults hitting the fleet
layer, each with its documented outcome, event trail, and metric
deltas (docs/service.md Gate fleet):

| condition               | detector            | documented outcome   |
|-------------------------|---------------------|----------------------|
| replica killed (kill -9 semantics: lease goes stale) | peer lease watcher | the rendezvous-ranked survivor adopts the dead replica's journal (fleet.lease_missed + fleet_lease_missed, fleet.adopted{outcome=…} + request_adopted/fleet_adopted) and completes its live requests under their ORIGINAL rids — zero lost; the victim's journal carries the adopted marker, so a restarted victim refuses typed (AdoptedByPeer) — zero duplicated; ONE stitched trace across the hop |
| overload on one replica with peer headroom | shed-forward peer picker | HTTP 307 to the shallowest live-leased peer (fleet.forwarded + fleet_forwarded) instead of 429; `http_solve` follows with the same idempotency key + traceparent, the request solves on the peer, one stitched trace |
| torn/corrupt lease file | lease CRC at the reader | typed LeaseCorruptError from check_peers — REFUSED takeover (no adoption, no adopted marker, fleet.lease_missed does NOT move); pick_peer degrades to None (429 fallback), never a false forward |

Round 17 (paspec): the convergence observatory adds the PREDICTIVE
refusal row — overload the scheduler can see COMING instead of
discovering by burning iterations (docs/observability.md "Convergence
observatory"):

| condition               | detector            | documented outcome   |
|-------------------------|---------------------|----------------------|
| infeasible deadline on a measured operator (PA_SPEC_ADMIT=1) | spectral forecast x measured s_per_it at admission | DeadlineInfeasible (typed, predicted_s/available_s diagnostics) + deadline_infeasible/health_error events + spec.infeasible counter; NEVER dispatched — zero iterations, service.admitted/slabs do not move; distinct by type and metric from queue-full AdmissionRejected, LoadShedded, and post-hoc SolveDeadlineError expiry |

Round 19 (paelastic): part LOSS — a casualty no same-partition restart
can ever outwait (its exchange contribution is gone for good), so the
recovery ladder forks on ``PA_ELASTIC`` instead of burning budget:

| condition               | detector            | documented outcome   |
|-------------------------|---------------------|----------------------|
| part loss, PA_ELASTIC=1 | exchange choke point (part_loss clause) | elastic shrink onto the survivor grid + resume from the last chunk checkpoint: elastic_shrink/checkpoint_restore/restart events, elastic.shrink{reason=part_loss} + elastic.crosspart_restores deltas, a tenant.repartition span, info["elastic"] ledger — and the NEXT full-capacity solve emits elastic_restore (grow back) |
| part loss, PA_ELASTIC=0 | exchange choke point (part_loss clause) | typed PartLossError escalates IMMEDIATELY to the caller's checkpoint tier — zero restarts attempted (no silent same-partition retry loop), no restart events, restart budget untouched |

Round 20 (palock): the THREAD-LIFECYCLE row — the leak class the
static leaked-thread check forbids at the AST level, asserted live:

| condition               | detector            | documented outcome   |
|-------------------------|---------------------|----------------------|
| drained shutdown of every thread-spawning component (SolveService worker, FleetMember beat/watch) | palock leaked-thread check + this row | zero live threads survive: shutdown(drain=True) joins the worker after finishing the queue, FleetMember.stop() joins beat+watch; the process-wide live-thread set returns to its pre-start baseline (no non-daemon thread may outlive its owner — daemon spawns need a DAEMON_WAIVERS reason) |
"""
import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import telemetry
from partitionedarrays_jl_tpu.models import (
    assemble_poisson,
    cg,
    gather_pvector,
    solve_with_recovery,
)
from partitionedarrays_jl_tpu.parallel.faults import inject_faults
from partitionedarrays_jl_tpu.parallel.health import (
    ControllerLostError,
    ExchangeTimeoutError,
    NonFiniteError,
    SilentCorruptionError,
)


def _run(driver):
    assert pa.prun(driver, pa.sequential, (2, 2))


def _has_event(rec, kind, label=None):
    """Does the record log an event of ``kind`` (and ``label``)?"""
    return any(
        e.kind == kind and (label is None or e.label == label)
        for e in rec.events
    )


def _metric_state(*names):
    """Counter values + histogram counts before an incident (the
    service rows assert exact DELTAS against this, not absolutes — the
    registry is process-wide and other tests feed it). Labeled
    counters spell their label inline: ``name{key=value}``."""
    reg = telemetry.registry()
    out = {}
    for name in names:
        if name.endswith("_s"):
            out[name] = reg.histogram(name).count
        elif "{" in name:
            base, rest = name.split("{", 1)
            key, value = rest.rstrip("}").split("=", 1)
            out[name] = reg.counter(base, labels={key: value}).value
        else:
            out[name] = telemetry.counter(name)
    return out


def test_matrix_nan_typed_then_recovers():
    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        x_clean, _ = cg(A, b, x0=x0, tol=1e-9)
        with inject_faults("nan@part=1,call=9", seed=1):
            with pytest.raises(NonFiniteError):
                cg(A, b, x0=x0, tol=1e-9)
        # the aborted solve's record survives with the whole story:
        # the injected fault, the detector, and the abort itself
        aborted = telemetry.last_record("cg")
        assert aborted.status == "raised"
        assert _has_event(aborted, "fault_injected", "nan")
        assert _has_event(aborted, "health_error", "NonFiniteError")
        with inject_faults("nan@part=1,call=9", seed=1):
            x, info = solve_with_recovery(A, b, x0=x0, tol=1e-9)
        assert info["converged"] and info["restarts"] == 1
        # the recovery record logs the fault, the detector, AND the
        # recovery path taken (restart) — nothing healed silently
        rec = info.record
        assert _has_event(rec, "fault_injected", "nan")
        assert _has_event(rec, "health_error", "NonFiniteError")
        restarts = [e for e in rec.events if e.kind == "restart"]
        assert len(restarts) == 1
        assert restarts[0].label == "NonFiniteError"
        np.testing.assert_array_equal(
            gather_pvector(x_clean), gather_pvector(x)
        )
        return True

    _run(driver)


def test_matrix_nan_under_abft_heals_in_memory(monkeypatch):
    monkeypatch.setenv("PA_TPU_ABFT", "1")

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        x_clean, _ = cg(A, b, x0=x0, tol=1e-9)
        with inject_faults("nan@part=1,call=9", seed=1):
            x, info = cg(A, b, x0=x0, tol=1e-9)
        assert info["converged"] and info["sdc"]["rollbacks"] == 1
        # in-memory self-heal, but NOT silent: the record logs the
        # fault, the detection, and the rollback (with its iteration)
        rec = info.record
        assert _has_event(rec, "fault_injected", "nan")
        rolls = [e for e in rec.events if e.kind == "sdc_rollback"]
        assert _has_event(rec, "sdc_detection") and len(rolls) == 1
        assert rolls[0].iteration is not None
        np.testing.assert_array_equal(
            gather_pvector(x_clean), gather_pvector(x)
        )
        return True

    _run(driver)


def test_matrix_bitflip_under_abft_heals_bitwise(monkeypatch):
    monkeypatch.setenv("PA_TPU_ABFT", "1")
    monkeypatch.setenv("PA_HEALTH_AUDIT_EVERY", "6")

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        x_clean, _ = cg(A, b, x0=x0, tol=1e-9)
        with inject_faults("bitflip@part=1,call=9,bit=51", seed=7) as st:
            x, info = cg(A, b, x0=x0, tol=1e-9)
        assert any(e["kind"] == "bitflip" for e in st.events)
        assert info["converged"] and info["sdc"]["detections"] == 1
        # event completeness: fault kind + detection + rollback, with
        # the iteration the recovery rewound to
        rec = info.record
        assert _has_event(rec, "fault_injected", "bitflip")
        assert _has_event(rec, "sdc_detection", "cg")
        rolls = [e for e in rec.events if e.kind == "sdc_rollback"]
        assert len(rolls) == 1
        assert "restored_iteration" in rolls[0].details
        np.testing.assert_array_equal(
            gather_pvector(x_clean), gather_pvector(x)
        )
        return True

    _run(driver)


def test_matrix_drop_typed_timeout():
    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        with inject_faults("drop@part=2,call=5", seed=0) as st:
            with pytest.raises(ExchangeTimeoutError) as ei:
                cg(A, b, x0=x0, tol=1e-9)
        assert ei.value.diagnostics["missing_parts"] == [2]
        assert st.events[0]["kind"] == "drop"
        aborted = telemetry.last_record("cg")
        assert aborted.status == "raised"
        assert _has_event(aborted, "fault_injected", "drop")
        assert _has_event(aborted, "health_error", "ExchangeTimeoutError")
        return True

    _run(driver)


def test_matrix_delay_completes_clean():
    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        with inject_faults("delay@call=3,seconds=0.0", seed=0) as st:
            x, info = cg(A, b, x0=x0, tol=1e-9)
        assert info["converged"]  # a slow host is not an error
        assert st.events[0]["kind"] == "delay"
        # the record shows the injection AND that nothing needed to
        # recover: no detector fired, no recovery path was taken
        rec = info.record
        assert _has_event(rec, "fault_injected", "delay")
        for kind in ("health_error", "sdc_detection", "sdc_rollback",
                     "restart"):
            assert not _has_event(rec, kind), kind
        return True

    _run(driver)


def test_matrix_controller_typed_then_recovers():
    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        with inject_faults("controller@call=6", seed=0):
            with pytest.raises(ControllerLostError):
                cg(A, b, x0=x0, tol=1e-9)
        with inject_faults("controller@call=6", seed=0):
            x, info = solve_with_recovery(A, b, x0=x0, tol=1e-9)
        assert info["converged"] and info["restarts"] == 1
        assert info["recovery"]["attempts"] == 2
        rec = info.record
        assert _has_event(rec, "fault_injected", "controller")
        assert _has_event(rec, "health_error", "ControllerLostError")
        assert _has_event(rec, "restart", "ControllerLostError")
        return True

    _run(driver)


def test_matrix_service_admission_rejected():
    """Service row 1: overload hits the bounded queue — the documented
    outcome is TYPED backpressure (AdmissionRejected with machine-
    readable diagnostics), never unbounded buffering or a silent drop,
    and the rejection is an event (the counter always ticks)."""
    from partitionedarrays_jl_tpu.service import (
        AdmissionRejected,
        SolveService,
    )

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        svc = SolveService(A, queue_depth=1)
        held = svc.submit(b, x0=x0, tol=1e-9, tag="held")
        before = telemetry.counter("events.admission_rejected")
        m0 = _metric_state("service.rejected{reason=queue_full}",
                           "service.admitted",
                           "service.completed")
        with pytest.raises(AdmissionRejected) as ei:
            svc.submit(b, x0=x0, tol=1e-9, tag="over")
        assert ei.value.diagnostics["reason"] == "queue_full"
        assert telemetry.counter("events.admission_rejected") == before + 1
        # the metrics plane counted the same incident the event log
        # narrated: one rejection, zero admissions
        m1 = _metric_state("service.rejected{reason=queue_full}",
                           "service.admitted",
                           "service.completed")
        assert m1["service.rejected{reason=queue_full}"] == (
            m0["service.rejected{reason=queue_full}"] + 1
        )
        assert m1["service.admitted"] == m0["service.admitted"]
        # the queued request is untouched by the rejection
        svc.drain()
        assert held.result()[1]["converged"]
        m2 = _metric_state("service.completed")
        assert m2["service.completed"] == m0["service.completed"] + 1
        return True

    _run(driver)


def test_matrix_service_deadline_expiry():
    """Service row 2: a request's deadline passes at a chunk boundary —
    typed SolveDeadlineError (in the SolverHealthError family, so the
    health_error event fires) with the full story in the request's
    record; the co-batched deadline-free request completes."""
    from partitionedarrays_jl_tpu.parallel.health import SolveDeadlineError
    from partitionedarrays_jl_tpu.service import SolveService

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        t = {"now": 0.0}

        def clock():
            t["now"] += 1.0
            return t["now"]

        svc = SolveService(A, kmax=2, chunk=4, clock=clock)
        m0 = _metric_state(
            "service.deadline_expired", "service.failed",
            "service.completed", "service.total_s",
            "service.deadline_slack_s", "service.slo.requests{tol_class=1e-09}",
            "service.slo.hits{tol_class=1e-09}",
        )
        rd = svc.submit(b, x0=x0, tol=1e-9, deadline=0.5, tag="tight")
        rf = svc.submit(b, x0=x0, tol=1e-9, tag="free")
        svc.drain()
        with pytest.raises(SolveDeadlineError):
            rd.result()
        assert rf.result()[1]["converged"]
        rec = rd.record
        assert rec.status == "raised"
        assert _has_event(rec, "deadline_expired", "tight")
        assert _has_event(rec, "health_error", "SolveDeadlineError")
        assert _has_event(rec, "request_failed", "tight")
        # metric deltas, not just events: the expiry counted, both
        # requests' total latencies landed, and the SLO accounting for
        # the 1e-09 class saw one deadline-carrying request and NO hit
        m1 = _metric_state(
            "service.deadline_expired", "service.failed",
            "service.completed", "service.total_s",
            "service.deadline_slack_s", "service.slo.requests{tol_class=1e-09}",
            "service.slo.hits{tol_class=1e-09}",
        )
        d = {k: m1[k] - m0[k] for k in m0}
        assert d["service.deadline_expired"] == 1, d
        assert d["service.failed"] == 1 and d["service.completed"] == 1, d
        assert d["service.total_s"] == 2, d
        assert d["service.deadline_slack_s"] == 1, d
        assert d["service.slo.requests{tol_class=1e-09}"] == 1, d
        assert d["service.slo.hits{tol_class=1e-09}"] == 0, d
        return True

    _run(driver)


def test_matrix_service_poisoned_column_ejection():
    """Service row 3: a NaN-poisoned b shares a slab with clean
    requests — the poisoned request is ejected with a typed
    NonFiniteError and its event trail, the co-batched requests
    complete equal to their clean solo solves, and nothing heals
    silently."""
    from partitionedarrays_jl_tpu.service import SolveService

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        x_clean, _ = cg(A, b, x0=x0, tol=1e-9)
        bad = b.copy()

        def poison(i, vals):
            if int(i.part) == 0:
                np.asarray(vals)[0] = np.nan

        pa.map_parts(poison, bad.rows.partition, bad.values)
        svc = SolveService(A, kmax=3, retries=0)
        m0 = _metric_state(
            "service.ejected", "service.failed", "service.completed",
            "service.retried_solo", "service.slabs",
            "service.queue_wait_s", "service.total_s",
        )
        h_good = svc.submit(b, x0=x0, tol=1e-9, tag="good")
        h_bad = svc.submit(bad, x0=x0, tol=1e-9, tag="bad")
        h_good2 = svc.submit(b, x0=x0, tol=1e-9, tag="good2")
        svc.drain()
        assert svc.stats["slabs"] == 1  # one shared slab
        # metric deltas: one slab, one ejection (NO solo retry —
        # retries=0), one failure, two completions, and all three
        # requests' queue-wait + total-latency observations
        m1 = _metric_state(
            "service.ejected", "service.failed", "service.completed",
            "service.retried_solo", "service.slabs",
            "service.queue_wait_s", "service.total_s",
        )
        d = {k: m1[k] - m0[k] for k in m0}
        assert d["service.slabs"] == 1, d
        assert d["service.ejected"] == 1, d
        assert d["service.retried_solo"] == 0, d
        assert d["service.failed"] == 1 and d["service.completed"] == 2, d
        assert d["service.queue_wait_s"] == 3, d
        assert d["service.total_s"] == 3, d
        with pytest.raises(NonFiniteError):
            h_bad.result()
        for h in (h_good, h_good2):
            x, info = h.result()
            assert info["converged"]
            np.testing.assert_array_equal(
                gather_pvector(x), gather_pvector(x_clean)
            )
        rec = h_bad.record
        assert rec.status == "raised"
        assert _has_event(rec, "column_verdict")
        assert _has_event(rec, "column_ejected")
        assert _has_event(rec, "request_failed", "bad")
        # the clean requests' records show no failure of their own
        assert not _has_event(h_good.record, "request_failed", "good")
        return True

    _run(driver)


def test_matrix_corrupted_plan_caught_statically(monkeypatch):
    """paplan row: a corrupted exchange PLAN — mutated slot indices,
    the class every runtime detector above would only see as a wrong
    answer or a hang — is refused at the plan BUILD site under
    ``PA_PLAN_VERIFY=1``: typed `PlanSoundnessError` with the failing
    check and part/slot diagnostics, the ``plan_defect`` event
    emitted, and NO solve ever started."""
    from partitionedarrays_jl_tpu.parallel.health import PlanSoundnessError
    from partitionedarrays_jl_tpu.parallel.tpu import device_exchange_plan

    monkeypatch.setenv("PA_PLAN_VERIFY", "1")
    monkeypatch.setenv("PA_TPU_BOX", "0")  # the generic plan reads lids

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        rows = A.cols
        # corrupt the host plan in place: an overlapping ghost slot
        ex = rows.exchanger
        t = next(t for t in ex.lids_rcv.part_values() if len(t.data) >= 2)
        t.data[1] = t.data[0]
        before = telemetry.counter("events.plan_defect")
        health_before = telemetry.counter("events.health_error")
        last = telemetry.last_record()
        with pytest.raises(PlanSoundnessError) as ei:
            device_exchange_plan(rows)
        assert "ghost-race" in ei.value.diagnostics["checks"]
        d = ei.value.diagnostics["defects"][0]
        assert d["part"] is not None and d["check"] == "ghost-race"
        # the static catch is narrated (one plan_defect event per
        # failing check class + the health_error every typed failure
        # emits) and happened BEFORE any solve — no new SolveRecord
        assert telemetry.counter("events.plan_defect") == (
            before + len(ei.value.diagnostics["checks"])
        )
        assert telemetry.counter("events.health_error") == health_before + 1
        assert telemetry.last_record() is last
        return True

    _run(driver)


def test_matrix_never_returns_silently_wrong(monkeypatch):
    """The bottom line of the matrix: with the defense on, a PERSISTENT
    bitflip stream either heals or raises typed — across the whole
    ladder it never returns a wrong iterate labelled converged."""
    monkeypatch.setenv("PA_TPU_ABFT", "1")
    monkeypatch.setenv("PA_HEALTH_MAX_ROLLBACKS", "1")

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        with inject_faults("bitflip@part=*,after=0,bit=51,prob=0.5", seed=9):
            with pytest.raises(SilentCorruptionError):
                solve_with_recovery(
                    A, b, x0=x0, tol=1e-9, max_restarts=1
                )
        # even the give-up path is fully narrated: the aborted outer
        # record carries the detections, the exhausted rollbacks, the
        # escalation, and the abort marker
        aborted = telemetry.last_record("solve_with_recovery")
        assert aborted.status == "raised"
        assert aborted.error["type"] == "SilentCorruptionError"
        assert _has_event(aborted, "fault_injected", "bitflip")
        assert _has_event(aborted, "sdc_detection")
        assert _has_event(aborted, "sdc_escalation")
        assert _has_event(aborted, "solve_aborted", "SilentCorruptionError")
        return True

    _run(driver)


# ---------------------------------------------------------------------------
# round 14 — the front-door (pagate) rows
# ---------------------------------------------------------------------------


def test_matrix_gate_budget_exceeded_admission():
    """Gate row 1: an operator whose static footprint exceeds
    PA_GATE_MEM_BUDGET outright — the documented outcome is the typed
    TenantBudgetError at REGISTRATION (capacity planning, not
    per-request backpressure): the tenant is never admitted, the
    refusal is evented AND counted, and no service ever runs."""
    from partitionedarrays_jl_tpu.frontdoor import (
        Gate,
        TenantBudgetError,
    )

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        m0 = _metric_state("gate.budget_rejected")
        ev0 = telemetry.counter("events.tenant_budget_rejected")
        gate = Gate(mem_budget_bytes=4096)
        with pytest.raises(TenantBudgetError) as ei:
            gate.register("toolarge", A, footprint_bytes=8192)
        assert ei.value.diagnostics == {
            "tenant": "toolarge", "footprint_bytes": 8192,
            "budget_bytes": 4096,
        }
        m1 = _metric_state("gate.budget_rejected")
        assert m1["gate.budget_rejected"] == m0["gate.budget_rejected"] + 1
        assert telemetry.counter("events.tenant_budget_rejected") == ev0 + 1
        assert gate.residency() == []  # never admitted
        return True

    _run(driver)


def test_matrix_gate_load_shed_distinct_from_queue_full():
    """Gate row 2: overload past the shed watermark — the documented
    outcome for the LOWEST class is the typed LoadShedded carrying a
    retry_after_s (HTTP 429 + Retry-After on the wire), counted under
    gate.shed{slo_class=…} and narrated by the load_shedded event,
    while the queue-full AdmissionRejected reason counter does NOT
    move — the two overload behaviors stay separable in /metrics."""
    from partitionedarrays_jl_tpu.frontdoor import Gate, LoadShedded
    from partitionedarrays_jl_tpu.service import AdmissionRejected

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        gate = Gate(shed_watermark=1)
        gate.register("t", A, kmax=2)
        m0 = _metric_state(
            "gate.shed{slo_class=besteffort}",
            "service.rejected{reason=queue_full}",
        )
        ev0 = telemetry.counter("events.load_shedded")
        held = gate.submit("t", b, x0=x0, tol=1e-9,
                           slo_class="besteffort", tag="held")
        with pytest.raises(LoadShedded) as ei:
            gate.submit("t", b, x0=x0, tol=1e-9,
                        slo_class="besteffort", tag="over")
        assert not isinstance(ei.value, AdmissionRejected)
        assert ei.value.retry_after_s > 0.0
        assert ei.value.diagnostics["slo_class"] == "besteffort"
        assert ei.value.diagnostics["watermark"] == 1
        m1 = _metric_state(
            "gate.shed{slo_class=besteffort}",
            "service.rejected{reason=queue_full}",
        )
        assert m1["gate.shed{slo_class=besteffort}"] == (
            m0["gate.shed{slo_class=besteffort}"] + 1
        )
        assert m1["service.rejected{reason=queue_full}"] == (
            m0["service.rejected{reason=queue_full}"]
        ), "shedding must never masquerade as queue-full backpressure"
        assert telemetry.counter("events.load_shedded") == ev0 + 1
        # the held request is untouched: it drains to a clean result
        gate.drain()
        assert held.result()[1]["converged"]
        # patx continuity: the shed refusal is ONE one-span trace
        # (gate.shed, status=shed) — no dangling request spans — and
        # the held request's trace is a complete orphan-free tree
        from partitionedarrays_jl_tpu.telemetry import tracing

        spans = tracing.recorded_spans()
        shed_spans = [
            s for s in spans
            if s["kind"] == "gate.shed" and s["name"] == "over"
        ]
        assert len(shed_spans) == 1
        shed_tid = shed_spans[0]["trace_id"]
        assert tracing.verify_trace(spans, shed_tid) == []
        assert [
            s["kind"] for s in spans if s["trace_id"] == shed_tid
        ] == ["gate.shed"]
        assert shed_spans[0]["status"] == "shed"
        held_tid = held.trace.trace_id
        assert held_tid != shed_tid
        assert tracing.verify_trace(spans, held_tid) == []
        roots, orphans = tracing.span_tree(
            [s for s in spans if s["trace_id"] == held_tid]
        )
        assert len(roots) == 1 and not orphans
        return True

    _run(driver)


def test_matrix_gate_eviction_during_inflight_checkpoint_resume(tmp_path):
    """Gate row 3: a tenant is EVICTED while one of its chunked solves
    is in flight — the documented outcome is the PR 7 checkpoint path:
    the iterate checkpoints at the chunk boundary
    (request_checkpointed), the tenant pages out (tenant_evicted), the
    drained request re-enters the gate's EDF queue (tenant_requeued),
    and after the next page-in it RESUMES from the saved iterate
    (checkpoint_restore) and completes. Driven synchronously: the stop
    flag is raised mid-slab exactly as a live eviction's
    shutdown(drain=False) would at the next chunk boundary."""
    from partitionedarrays_jl_tpu.frontdoor import Gate

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (12, 12))
        x_direct, _ = cg(A, b, x0=x0, tol=1e-9)
        gate = Gate(checkpoint_dir=str(tmp_path))
        gate.register("t", A, kmax=2, chunk=4)
        m0 = _metric_state(
            "service.checkpointed", "gate.evictions", "gate.page_ins",
            "service.completed",
        )
        # a deadline-carrying request runs CHUNKED; dispatch it, then
        # signal stop mid-slab (what a concurrent eviction does) so
        # the first chunk boundary checkpoints the iterate
        h = gate.submit("t", b, x0=x0, tol=1e-9, deadline=3600.0,
                        slo_class="interactive", tag="inflight")
        gate.pump(dispatch_only=True)  # into the tenant's batcher
        svc = gate.service("t")
        svc._stop = True
        svc.step()  # one chunk, then checkpoint at the boundary
        assert h.request.state == "checkpointed"
        it_before = h.request.iterations
        assert it_before > 0
        rec_ck = h.request.record
        assert _has_event(rec_ck, "request_checkpointed", "inflight")
        ev_requeue0 = telemetry.counter("events.tenant_requeued")
        gate.evict("t")
        # the eviction requeued the checkpointed request with its
        # saved iterate as x0
        assert telemetry.counter("events.tenant_requeued") == (
            ev_requeue0 + 1
        )
        assert h.state == "gate-queued"
        assert h.kwargs["x0"] is not None
        res = {r["tenant"]: r for r in gate.residency()}
        assert not res["t"]["resident"]
        # drain: page back in, re-stage, resume from the checkpoint
        gate.drain()
        x, info = h.result()
        assert info["converged"]
        np.testing.assert_allclose(
            gather_pvector(x), gather_pvector(x_direct),
            rtol=0, atol=1e-6,
        )
        m1 = _metric_state(
            "service.checkpointed", "gate.evictions", "gate.page_ins",
            "service.completed",
        )
        d = {k: m1[k] - m0[k] for k in m0}
        assert d["service.checkpointed"] == 1, d
        assert d["gate.evictions"] == 1, d
        assert d["gate.page_ins"] == 1, d
        assert d["service.completed"] == 1, d
        # the resume is narrated end to end
        assert _has_event(h.request.record, "request_done", "inflight")
        assert telemetry.counter("events.checkpoint_restore") > 0
        # patx continuity: the whole eviction/requeue/resume story is
        # ONE trace — the root, BOTH gate-queue waits (the requeue
        # flagged), the checkpointed AND the resumed slab rides, the
        # re-stage page-in — with correct parentage and zero orphans
        from partitionedarrays_jl_tpu.telemetry import tracing

        gate.account()
        tid = h.trace.trace_id
        spans = tracing.recorded_spans()
        assert tracing.verify_trace(spans, tid) == []
        mine = [s for s in spans if s["trace_id"] == tid]
        roots, orphans = tracing.span_tree(mine)
        assert len(roots) == 1 and not orphans
        assert roots[0]["kind"] == "rpc.request"
        queues = [s for s in mine if s["kind"] == "gate.queue"]
        assert len(queues) == 2
        assert [bool(s["attrs"].get("requeued")) for s in queues].count(
            True
        ) == 1
        solves = [s for s in mine if s["kind"] == "slab.solve"]
        assert {s["status"] for s in solves} == {"checkpointed", "ok"}
        assert any(s["kind"] == "tenant.page_in" for s in mine), (
            "the re-stage page-in must land in the request's trace"
        )
        by_id = {s["span_id"]: s for s in mine}
        for s in queues + solves:
            assert by_id[s["parent_id"]]["kind"] == "rpc.request"
        return True

    _run(driver)


# ---------------------------------------------------------------------------
# round 15 — the durability (padur) rows
# ---------------------------------------------------------------------------


def test_matrix_gate_crash_midsolve_journal_recovery(tmp_path):
    """Durability row 1: the gate dies mid-solve (kill -9 semantics —
    the first gate's state is ABANDONED, no shutdown or eviction path
    runs). The write-ahead journal has the admitted/dispatched/chunk
    records, so a fresh gate over the same journal dir resumes the
    request from its chunk-checkpointed iterate and COMPLETES it:
    gate.recovered{outcome=resumed} counts it, request_recovered /
    gate_recovered / checkpoint_restore narrate it, and the journal
    ends with exactly one completed record for the rid (zero lost,
    zero duplicated)."""
    from partitionedarrays_jl_tpu.frontdoor import Gate, read_journal

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (12, 12))
        x_direct, _ = cg(A, b, x0=x0, tol=1e-9)
        jd = str(tmp_path / "journal")
        g1 = Gate(journal_dir=jd, checkpoint_dir=str(tmp_path / "c1"))
        g1.register("t", A, kmax=2, chunk=4)
        h = g1.submit("t", b, x0=x0, tol=1e-9, deadline=3600.0,
                      slo_class="interactive", tag="crashy",
                      idempotency_key="crash-key")
        g1.pump(dispatch_only=True)
        svc = g1.service("t")
        svc._stop = True  # freeze after one chunk: the kill window
        svc.step()
        assert h.request.iterations > 0
        # ---- crash: g1 is abandoned with its request mid-flight ----
        m0 = _metric_state(
            "gate.recovered{outcome=resumed}", "service.completed",
        )
        ev0 = telemetry.counter("events.request_recovered")
        evg0 = telemetry.counter("events.gate_recovered")
        evr0 = telemetry.counter("events.checkpoint_restore")
        g2 = Gate(journal_dir=jd, checkpoint_dir=str(tmp_path / "c2"))
        g2.register("t", A, kmax=2, chunk=4)
        summary = g2.recover()
        assert summary["resumed"] == 1, summary
        assert telemetry.counter("events.request_recovered") == ev0 + 1
        assert telemetry.counter("events.gate_recovered") == evg0 + 1
        assert telemetry.counter("events.checkpoint_restore") == evr0 + 1
        g2.drain()
        x, info = g2.handle(h.rid).result()
        assert info["converged"]
        np.testing.assert_allclose(
            gather_pvector(x), gather_pvector(x_direct),
            rtol=0, atol=1e-6,
        )
        m1 = _metric_state(
            "gate.recovered{outcome=resumed}", "service.completed",
        )
        d = {k: m1[k] - m0[k] for k in m0}
        assert d["gate.recovered{outcome=resumed}"] == 1, d
        assert d["service.completed"] == 1, d
        completed = [
            r for r in read_journal(jd)
            if r.get("kind") == "completed" and r.get("rid") == h.rid
        ]
        assert len(completed) == 1, "zero lost, zero duplicated"
        # patx continuity: the recovered request keeps its ORIGINAL
        # trace_id; the post-crash root stitches to the pre-crash root
        # (left interrupted by the abandoned gate); zero orphans — one
        # tree across the "kill"
        from partitionedarrays_jl_tpu.telemetry import tracing

        g2.account()
        h2 = g2.handle(h.rid)
        tid = h.trace.trace_id
        assert h2.trace.trace_id == tid, (
            "recovery must preserve the original trace_id"
        )
        spans = tracing.recorded_spans()
        assert tracing.verify_trace(spans, tid) == []
        mine = [s for s in spans if s["trace_id"] == tid]
        roots_list = [s for s in mine if s["kind"] == "rpc.request"]
        pre = [s for s in roots_list if not s["attrs"].get("recovered")]
        post = [s for s in roots_list if s["attrs"].get("recovered")]
        assert len(pre) == 1 and len(post) == 1
        assert pre[0]["status"] == "interrupted", (
            "the abandoned gate's root must surface as interrupted"
        )
        assert post[0]["parent_id"] == pre[0]["span_id"], (
            "the recovered root must parent to the pre-crash root"
        )
        assert post[0]["attrs"]["recovered"] == "resumed"
        _, orphans = tracing.span_tree(mine)
        assert not orphans
        return True

    _run(driver)


def test_matrix_torn_journal_tail_truncates_typed(tmp_path):
    """Durability row 2: a crash mid-append tears the LAST journal
    record — replay truncates it (journal.truncated counter +
    journal_truncated event) and the clean prefix recovers intact; a
    defective record that is NOT the tail is real corruption and
    raises the typed JournalCorruptError instead of silently dropping
    acknowledged history."""
    from partitionedarrays_jl_tpu.frontdoor import (
        Gate,
        JournalCorruptError,
        RequestJournal,
        read_journal,
    )

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        jd = str(tmp_path / "journal")
        g1 = Gate(journal_dir=jd)
        g1.register("t", A, kmax=4)
        h = g1.submit("t", b, x0=x0, tol=1e-9, tag="pre-tear")
        g1.drain()
        x1 = gather_pvector(h.result()[0])
        # tear the tail: a half-written record, as a crash mid-append
        # would leave it
        last = sorted(g1.journal.segments())[-1]
        with open(last, "ab") as f:
            f.write(b'{"kind":"completed","seq":999,"x":[0.123')
        m0 = _metric_state("journal.truncated")
        ev0 = telemetry.counter("events.journal_truncated")
        g2 = Gate(journal_dir=jd)
        g2.register("t", A, kmax=4)
        summary = g2.recover()
        m1 = _metric_state("journal.truncated")
        assert m1["journal.truncated"] == m0["journal.truncated"] + 1
        assert telemetry.counter("events.journal_truncated") == ev0 + 1
        # the clean prefix survived: the completed request still serves
        assert summary["completed"] == 1, summary
        np.testing.assert_array_equal(
            g2.handle(h.rid).result()[0], x1
        )
        # mid-file corruption is NOT a torn tail: typed refusal
        jc = str(tmp_path / "corrupt")
        jx = RequestJournal(jc, fsync=False)
        jx.append("shed", tag="aaaa", slo_class="x", depth=0)
        jx.append("shed", tag="bbbb", slo_class="x", depth=1)
        jx.close()
        seg = sorted(jx.segments())[0]
        data = bytearray(open(seg, "rb").read())
        data[data.find(b"aaaa")] = ord("z")
        open(seg, "wb").write(bytes(data))
        with pytest.raises(JournalCorruptError):
            read_journal(jc, strict=True)
        return True

    _run(driver)


def test_matrix_duplicate_idempotency_key_single_solve(tmp_path):
    """Durability row 3: a duplicate idempotency-key submit — the
    retried-timed-out-POST scenario — returns the ORIGINAL id and
    bitwise result and never starts a second solve: gate.idempotent_hits
    counts it, idempotent_replay narrates it, and service.admitted does
    not move; the key map survives a gate restart via the journal."""
    from partitionedarrays_jl_tpu.frontdoor import Gate

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        jd = str(tmp_path / "journal")
        g1 = Gate(journal_dir=jd)
        g1.register("t", A, kmax=4)
        h1 = g1.submit("t", b, x0=x0, tol=1e-9, tag="orig",
                       idempotency_key="dup-key")
        g1.drain()
        x1 = gather_pvector(h1.result()[0])
        m0 = _metric_state(
            "gate.idempotent_hits", "service.admitted",
            "service.completed",
        )
        ev0 = telemetry.counter("events.idempotent_replay")
        h2 = g1.submit("t", b, idempotency_key="dup-key")
        assert h2 is h1, "the original handle, not a second request"
        np.testing.assert_array_equal(gather_pvector(h2.result()[0]), x1)
        m1 = _metric_state(
            "gate.idempotent_hits", "service.admitted",
            "service.completed",
        )
        d = {k: m1[k] - m0[k] for k in m0}
        assert d["gate.idempotent_hits"] == 1, d
        assert d["service.admitted"] == 0, "a replay admits NOTHING"
        assert d["service.completed"] == 0, d
        assert telemetry.counter("events.idempotent_replay") == ev0 + 1
        # across a crash: the journal rebuilds the key map
        g2 = Gate(journal_dir=jd)
        g2.register("t", A, kmax=4)
        g2.recover()
        h3 = g2.submit("t", b, idempotency_key="dup-key")
        assert h3.rid == h1.rid
        np.testing.assert_array_equal(h3.result()[0], x1)
        m2 = _metric_state("gate.idempotent_hits", "service.admitted")
        assert m2["gate.idempotent_hits"] == (
            m1["gate.idempotent_hits"] + 1
        )
        assert m2["service.admitted"] == m1["service.admitted"]
        return True

    _run(driver)


# ---------------------------------------------------------------------------
# round 16 — the fleet (pafleet) rows
# ---------------------------------------------------------------------------


def test_matrix_fleet_replica_death_peer_adopts_journal(tmp_path):
    """Fleet row 1: a replica dies with kill -9 semantics (state
    abandoned, lease goes stale) while holding a queued request — the
    documented outcome is journal-backed peer failover: the
    rendezvous-ranked survivor counts the missed lease, adopts the
    victim's journal, and completes the request under its ORIGINAL rid
    bitwise-equal to the solo solve (zero lost); the adopted marker in
    the victim's journal makes a restarted victim refuse typed
    (AdoptedByPeer — zero duplicated), exactly one completed record
    exists across the journal union, and patx stitches ONE trace
    across the replica hop."""
    import os
    import time

    from partitionedarrays_jl_tpu.frontdoor import (
        Gate,
        RecoveredError,
        fleet,
        read_journal,
    )

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        x_direct, _ = cg(A, b, x0=x0, tol=1e-9)
        fd = str(tmp_path / "fleet")
        g0dir = os.path.join(fd, "g0")
        os.makedirs(g0dir)
        victim = Gate(journal_dir=g0dir, rid_namespace="g0")
        victim.register("t", A, kmax=4)
        h = victim.submit("t", b, x0=x0, tol=1e-9, tag="orphaned",
                          idempotency_key="fleet-key")
        fleet.write_lease(
            os.path.join(g0dir, fleet.LEASE_NAME), "g0", depth=1
        )
        # ---- kill -9: the victim is abandoned mid-queue ----
        survivor = Gate(
            journal_dir=os.path.join(fd, "g1"), rid_namespace="g1"
        )
        survivor.register("t", A, kmax=4)
        member = fleet.FleetMember(fd, "g1", survivor, lease_s=0.05)
        member.heartbeat()
        m0 = _metric_state(
            "fleet.lease_missed", "fleet.adopted{outcome=requeued}",
            "service.admitted",
        )
        ev0 = telemetry.counter("events.fleet_lease_missed")
        eva0 = telemetry.counter("events.request_adopted")
        time.sleep(0.2)  # > 3 x lease_s: the victim's lease is stale
        adopted = member.check_peers()
        assert set(adopted) == {"g0"}, adopted
        assert adopted["g0"]["requeued"] == 1, adopted
        m1 = _metric_state(
            "fleet.lease_missed", "fleet.adopted{outcome=requeued}",
            "service.admitted",
        )
        d = {k: m1[k] - m0[k] for k in m0}
        assert d["fleet.lease_missed"] == 1, d
        assert d["fleet.adopted{outcome=requeued}"] == 1, d
        assert telemetry.counter("events.fleet_lease_missed") == ev0 + 1
        assert telemetry.counter("events.request_adopted") == eva0 + 1
        # the sweep is once-per-death: a second pass adopts nothing
        assert member.check_peers() == {}
        # the ORIGINAL rid completes on the survivor, bitwise
        survivor.drain()
        x, info = survivor.handle(h.rid).result()
        assert info["converged"]
        np.testing.assert_array_equal(
            gather_pvector(x), gather_pvector(x_direct)
        )
        # zero lost, zero duplicated: one completed record across the
        # union, and the victim's journal carries the adopted marker
        union = read_journal(g0dir) + read_journal(
            os.path.join(fd, "g1")
        )
        completed = [
            r for r in union
            if r.get("kind") == "completed" and r.get("rid") == h.rid
        ]
        assert len(completed) == 1, "exactly one solve fleet-wide"
        assert any(
            r.get("kind") == "adopted" and r.get("rid") == h.rid
            and r.get("by") == "g1"
            for r in read_journal(g0dir)
        )
        # a RESTARTED victim folds the marker and refuses typed —
        # never a second solve (service.admitted moved exactly once)
        back = Gate(journal_dir=g0dir, rid_namespace="g0")
        back.register("t", A, kmax=4)
        s = back.recover()
        assert s["adopted_away"] == 1, s
        with pytest.raises(RecoveredError, match="adopted") as ei:
            back.handle(h.rid).result()
        assert ei.value.error_type == "AdoptedByPeer"
        m2 = _metric_state("service.admitted")
        assert m2["service.admitted"] == m0["service.admitted"] + 1
        # an idempotent resubmit against the survivor replays the
        # original rid (the key map crossed the hop with the journal)
        assert survivor.submit(
            "t", b, idempotency_key="fleet-key"
        ).rid == h.rid
        # patx continuity: ONE trace — the adopted root parents into
        # the victim's interrupted root, zero orphans
        from partitionedarrays_jl_tpu.telemetry import tracing

        survivor.account()
        tid = h.trace.trace_id
        spans = tracing.recorded_spans()
        assert tracing.verify_trace(spans, tid) == []
        mine = [s for s in spans if s["trace_id"] == tid]
        roots = [s for s in mine if s["kind"] == "rpc.request"]
        pre = [s for s in roots if not s["attrs"].get("recovered")]
        post = [s for s in roots if s["attrs"].get("recovered")]
        # the survivor's adoption AND the restarted victim's
        # adopted_away terminal each stitch a recovered root — both
        # must parent into the single interrupted pre-crash root
        assert len(pre) == 1 and len(post) >= 1
        assert all(s["parent_id"] == pre[0]["span_id"] for s in post)
        assert any(
            s["attrs"].get("adopted_from") == g0dir for s in post
        )
        _, orphans = tracing.span_tree(mine)
        assert not orphans
        return True

    _run(driver)


def test_matrix_fleet_shed_forward_redirect(tmp_path):
    """Fleet row 2: overload on one replica while a live-leased peer
    has headroom — the documented outcome is a 307 shed-forward
    (fleet.forwarded + fleet_forwarded) instead of the 429: the client
    reposts the identical body to the peer, the request SOLVES there
    (rid carries the peer's namespace), and the whole exchange — the
    shed refusal on the owner plus the solve on the peer — is ONE
    stitched trace."""
    import os

    from partitionedarrays_jl_tpu.frontdoor import (
        Gate,
        fleet,
        http_solve,
        serve_gate,
    )
    from partitionedarrays_jl_tpu.models.solvers import gather_pvector

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        fd = str(tmp_path / "fleet")
        g0 = Gate(shed_watermark=1, rid_namespace="g0")
        g0.register("t", A, kmax=4)
        g1 = Gate(rid_namespace="g1", start_workers=True)
        g1.register("t", A, kmax=4)
        srv0, srv1 = serve_gate(g0, port=0), serve_gate(g1, port=0)
        try:
            m0f = fleet.FleetMember(fd, "g0", g0, server=srv0,
                                    lease_s=30.0)
            m1f = fleet.FleetMember(fd, "g1", g1, server=srv1,
                                    lease_s=30.0)
            m0f.heartbeat()
            m1f.heartbeat()
            m0f.map.write_url("g0", srv0.url)
            m1f.map.write_url("g1", srv1.url)
            srv0.peer_picker = m0f.pick_peer
            # build g0's backlog past the watermark with dispatch held
            g0.paused = True
            held = g0.submit("t", b, x0=x0, tol=1e-9,
                             slo_class="interactive", tag="held")
            m0 = _metric_state(
                "fleet.forwarded", "gate.shed{slo_class=besteffort}",
            )
            ev0 = telemetry.counter("events.fleet_forwarded")
            bg, x0g = gather_pvector(b), gather_pvector(x0)
            out = http_solve(
                srv0.url, "t", bg, x0=x0g, tol=1e-9,
                slo_class="besteffort", tag="forwarded",
                idempotency_key="fwd-key",
            )
            assert out["state"] == "done", out
            assert out["id"].startswith("g1-"), (
                "the solve must land on the PEER's rid namespace"
            )
            m1 = _metric_state(
                "fleet.forwarded", "gate.shed{slo_class=besteffort}",
            )
            d = {k: m1[k] - m0[k] for k in m0}
            assert d["fleet.forwarded"] == 1, d
            assert d["gate.shed{slo_class=besteffort}"] == 1, (
                "the shed still counts — forwarding rides ON the "
                "refusal, it does not hide it"
            )
            assert telemetry.counter("events.fleet_forwarded") == (
                ev0 + 1
            )
            # one stitched trace: the owner's shed span AND the peer's
            # request tree share the client's trace id, zero orphans
            from partitionedarrays_jl_tpu.telemetry import tracing

            g1.account()
            tid = out["trace_id"]
            spans = tracing.recorded_spans()
            assert tracing.verify_trace(spans, tid) == []
            mine = [s for s in spans if s["trace_id"] == tid]
            kinds = {s["kind"] for s in mine}
            assert "gate.shed" in kinds, "the refusal is in-trace"
            assert "rpc.request" in kinds, "the peer solve is in-trace"
            _, orphans = tracing.span_tree(mine)
            assert not orphans
            # the held request was untouched by the forward
            g0.paused = False
            g0.drain()
            assert held.result()[1]["converged"]
        finally:
            srv0.stop(drain=False)
            srv1.stop(drain=False)
        return True

    _run(driver)


def test_matrix_fleet_torn_lease_refuses_takeover(tmp_path):
    """Fleet row 3: a peer's lease file is torn (crash or disk fault
    mid-write straight to the final name) — the documented outcome is
    the typed `LeaseCorruptError` REFUSING takeover: a corrupt lease
    is evidence of unknown state, not of death, and a false takeover
    (two replicas solving one journal) is the one unrecoverable
    outcome. No adoption happens, no adopted marker lands, the
    fleet.lease_missed/fleet.adopted counters do NOT move, and
    pick_peer degrades to None (the 429 fallback) instead of
    forwarding into the unknown."""
    import os

    from partitionedarrays_jl_tpu.frontdoor import (
        Gate,
        LeaseCorruptError,
        fleet,
        read_journal,
    )

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        fd = str(tmp_path / "fleet")
        g0dir = os.path.join(fd, "g0")
        os.makedirs(g0dir)
        victim = Gate(journal_dir=g0dir, rid_namespace="g0")
        victim.register("t", A, kmax=4)
        victim.submit("t", b, x0=x0, tol=1e-9, tag="in-limbo")
        lease_path = os.path.join(g0dir, fleet.LEASE_NAME)
        fleet.write_lease(lease_path, "g0", depth=1)
        raw = open(lease_path).read()
        open(lease_path, "w").write(raw[: len(raw) // 2])  # torn
        survivor = Gate(
            journal_dir=os.path.join(fd, "g1"), rid_namespace="g1"
        )
        survivor.register("t", A, kmax=4)
        member = fleet.FleetMember(fd, "g1", survivor, lease_s=0.05)
        member.heartbeat()
        m0 = _metric_state("fleet.lease_missed")
        a0 = sum(
            v for k, v in telemetry.registry().snapshot()[
                "counters"
            ].items() if k.startswith("fleet.adopted")
        )
        with pytest.raises(LeaseCorruptError, match="refusing"):
            member.check_peers()
        m1 = _metric_state("fleet.lease_missed")
        a1 = sum(
            v for k, v in telemetry.registry().snapshot()[
                "counters"
            ].items() if k.startswith("fleet.adopted")
        )
        assert m1["fleet.lease_missed"] == m0["fleet.lease_missed"], (
            "a corrupt lease is NOT a missed lease"
        )
        assert a1 == a0, "no adoption on a refused takeover"
        assert not any(
            r.get("kind") == "adopted" for r in read_journal(g0dir)
        ), "no adopted marker may land on a refusal"
        assert member.pick_peer() is None, (
            "forwarding degrades to the 429 fallback, never a guess"
        )
        # a fresh heartbeat heals the lease and the fleet resumes:
        # g0 is live again, so the sweep finds nothing stale
        fleet.write_lease(lease_path, "g0", depth=1)
        assert member.check_peers() == {}
        return True

    _run(driver)


# ---------------------------------------------------------------------------
# round 17 — the convergence-observatory (paspec) row
# ---------------------------------------------------------------------------


def test_matrix_infeasible_deadline_refused_at_admission(monkeypatch):
    """Paspec row: an infeasible-deadline request under PA_SPEC_ADMIT=1
    is refused typed AT ADMISSION — never dispatched, zero solver
    iterations burned — with the full event trail and metric deltas,
    and stays DISTINCT from the queue-full, shed, and expiry rows (its
    own type, its own counter, its own event kind)."""
    from partitionedarrays_jl_tpu.parallel.health import (
        DeadlineInfeasible,
        SolveDeadlineError,
    )
    from partitionedarrays_jl_tpu.service import (
        AdmissionRejected,
        SolveService,
    )

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        svc = SolveService(A, kmax=2)
        # train: one completed request measures spectrum + throughput
        h = svc.submit(b, x0=x0, tol=1e-9, tag="train")
        svc.drain()
        assert h.result()[1]["converged"]
        m0 = _metric_state(
            "spec.infeasible", "service.admitted", "service.completed",
            "service.deadline_expired",
            "service.rejected{reason=queue_full}",
            "events.deadline_infeasible", "events.health_error",
        )
        slabs0 = svc.stats["slabs"]
        monkeypatch.setenv("PA_SPEC_ADMIT", "1")
        with pytest.raises(DeadlineInfeasible) as ei:
            svc.submit(b, x0=x0, tol=1e-9, deadline=1e-9, tag="doomed")
        # typed + diagnosable: the prediction that refused it is on the
        # error, and the type is NONE of its refusal-ladder neighbors
        d = ei.value.diagnostics
        assert d["predicted_s"] > d["available_s"]
        assert d["predicted_iters"] >= 1 and d["s_per_it"] > 0
        assert not isinstance(ei.value, SolveDeadlineError)
        assert not isinstance(ei.value, AdmissionRejected)
        m1 = _metric_state(
            "spec.infeasible", "service.admitted", "service.completed",
            "service.deadline_expired",
            "service.rejected{reason=queue_full}",
            "events.deadline_infeasible", "events.health_error",
        )
        delta = {k: m1[k] - m0[k] for k in m0}
        # its own counter and events moved ...
        assert delta["spec.infeasible"] == 1, delta
        assert delta["events.deadline_infeasible"] == 1, delta
        assert delta["events.health_error"] == 1, delta
        # ... and NOTHING was admitted, dispatched, or mis-binned into
        # the neighboring refusal rows: zero iterations spent
        assert delta["service.admitted"] == 0, delta
        assert delta["service.deadline_expired"] == 0, delta
        assert delta["service.rejected{reason=queue_full}"] == 0, delta
        assert svc.stats["slabs"] == slabs0
        assert svc.stats["infeasible"] == 1
        # default-off contract: the same hopeless deadline is ADMITTED
        # with PA_SPEC_ADMIT unset (pre-paspec behavior preserved —
        # whatever happens next is the post-hoc chunk-boundary expiry
        # row's business, not admission's)
        monkeypatch.delenv("PA_SPEC_ADMIT")
        h2 = svc.submit(b, x0=x0, tol=1e-9, deadline=1e-9, tag="legacy")
        m2 = _metric_state("service.admitted")
        assert m2["service.admitted"] == m1["service.admitted"] + 1
        svc.drain()
        assert h2.done()
        return True

    _run(driver)


# ---------------------------------------------------------------------------
# round 19 — the part-loss (paelastic) rows
# ---------------------------------------------------------------------------


def test_matrix_part_loss_elastic_shrinks_and_resumes(
    tmp_path, monkeypatch
):
    """Paelastic row 1: a lost part under PA_ELASTIC=1 shrinks the
    partition over the survivors and resumes from the last chunk
    checkpoint — one stitched event trail + metric deltas + the
    tenant.repartition span, and the next full-capacity solve
    announces grow-back."""
    from partitionedarrays_jl_tpu.parallel import elastic
    from partitionedarrays_jl_tpu.models.solvers import solve_with_recovery
    from partitionedarrays_jl_tpu.telemetry.tracing import (
        clear_spans,
        recorded_spans,
    )

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        x_clean, _ = cg(A, b, x0=x0, tol=1e-9)
        elastic._DEGRADED.clear()
        m0 = _metric_state(
            "elastic.shrink{reason=part_loss}",
            "elastic.crosspart_restores",
            "events.elastic_shrink", "events.elastic_restore",
        )
        clear_spans()
        monkeypatch.setenv("PA_ELASTIC", "1")
        with inject_faults("part_loss@part=3,after=6", seed=1):
            x, info = solve_with_recovery(
                A, b, x0=x0, checkpoint_dir=str(tmp_path), every=3,
                tol=1e-9,
            )
        monkeypatch.delenv("PA_ELASTIC")
        # the elastic ledger: 4 -> 2 survivors, resumed from the last
        # chunk checkpoint, converged to the clean answer — and NO
        # restart budget burned on the casualty
        el = info["elastic"]
        assert el["from_parts"] == 4 and el["to_parts"] == 2
        assert el["dead_part"] == 3
        assert el["checkpoint_iteration"] and el["checkpoint_iteration"] > 0
        assert info["converged"] and info["restarts"] == 0
        assert (
            np.abs(gather_pvector(x) - gather_pvector(x_clean)).max()
            < 1e-7
        )
        srcs = info["recovery"]["restart_sources"]
        assert [s["from"] for s in srcs] == ["elastic_shrink_checkpoint"]
        assert info["recovery"]["checkpoint_restarts"] == 1
        # the stitched trail: every stage narrates ...
        rec = telemetry.last_record("solve_with_recovery")
        assert _has_event(rec, "fault_injected", "part_loss")
        assert _has_event(rec, "health_error", "PartLossError")
        assert _has_event(rec, "elastic_shrink", "part_loss")
        assert _has_event(rec, "checkpoint_restore")
        assert _has_event(rec, "restart", "PartLossError")
        # ... and counts (event log and metrics plane agree)
        m1 = _metric_state(
            "elastic.shrink{reason=part_loss}",
            "elastic.crosspart_restores",
            "events.elastic_shrink", "events.elastic_restore",
        )
        assert m1["elastic.shrink{reason=part_loss}"] \
            - m0["elastic.shrink{reason=part_loss}"] == 1
        assert m1["elastic.crosspart_restores"] \
            - m0["elastic.crosspart_restores"] == 1
        assert m1["events.elastic_shrink"] \
            - m0["events.elastic_shrink"] == 1
        assert m1["events.elastic_restore"] \
            - m0["events.elastic_restore"] == 0
        spans = [
            s for s in recorded_spans()
            if s["kind"] == "tenant.repartition"
        ]
        assert len(spans) == 1
        assert spans[0]["attrs"]["from_parts"] == 4
        assert spans[0]["attrs"]["to_parts"] == 2
        # grow back: capacity returned — the next full-grid solve says so
        x2, info2 = solve_with_recovery(A, b, x0=x0, tol=1e-9)
        rec2 = telemetry.last_record("solve_with_recovery")
        assert _has_event(rec2, "elastic_restore", "grow_back")
        assert not elastic.degraded_state()
        return True

    _run(driver)


def test_matrix_part_loss_without_elastic_escalates_typed(monkeypatch):
    """Paelastic row 2: with PA_ELASTIC=0 a lost part escalates as a
    typed PartLossError to the caller's checkpoint tier IMMEDIATELY —
    no same-partition retry loop, zero restarts attempted, no restart
    events — because the casualty's contribution can never arrive."""
    from partitionedarrays_jl_tpu.parallel.health import PartLossError
    from partitionedarrays_jl_tpu.models.solvers import solve_with_recovery

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        monkeypatch.delenv("PA_ELASTIC", raising=False)
        m0 = _metric_state("events.restart", "events.elastic_shrink")
        with inject_faults("part_loss@part=3,after=6", seed=1):
            with pytest.raises(PartLossError) as ei:
                solve_with_recovery(A, b, x0=x0, tol=1e-9, max_restarts=2)
        # typed + diagnosable: the dead part and the exchange call are
        # on the error, and the loss is NOT a timeout
        from partitionedarrays_jl_tpu.parallel.health import (
            ExchangeTimeoutError,
        )

        assert ei.value.diagnostics["part"] == 3
        assert ei.value.diagnostics["call"] == 6
        assert not isinstance(ei.value, ExchangeTimeoutError)
        # the aborted record carries the whole story ...
        aborted = telemetry.last_record("solve_with_recovery")
        assert aborted.status == "raised"
        assert _has_event(aborted, "fault_injected", "part_loss")
        assert _has_event(aborted, "health_error", "PartLossError")
        # ... and NO restart was attempted or narrated: the budget was
        # not burned spinning on a permanent casualty
        assert not _has_event(aborted, "restart")
        m1 = _metric_state("events.restart", "events.elastic_shrink")
        assert m1["events.restart"] - m0["events.restart"] == 0
        assert m1["events.elastic_shrink"] \
            - m0["events.elastic_shrink"] == 0
        return True

    _run(driver)


# ---------------------------------------------------------------------------
# round 20: palock — thread lifecycle
# ---------------------------------------------------------------------------


def test_matrix_drained_shutdown_leaves_zero_live_threads(tmp_path):
    """Palock row: the thread-shutdown audit, live. Every component
    that spawns threads (the service worker, the fleet member's
    beat/watch pair) must return the process to its pre-start
    live-thread baseline on a drained shutdown/stop — the dynamic twin
    of the static leaked-thread check (which proves, at the AST level,
    that every `threading.Thread` in the package has a join on some
    shutdown path; DAEMON_WAIVERS is empty because nothing needs
    waiving)."""
    import os
    import threading

    from partitionedarrays_jl_tpu.frontdoor import Gate, fleet
    from partitionedarrays_jl_tpu.service import SolveService

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        baseline = set(threading.enumerate())
        # -- the service worker: start -> submit -> drained shutdown --
        svc = SolveService(A, kmax=2).start()
        h = svc.submit(b, x0=x0, tol=1e-9)
        stats = svc.shutdown(drain=True)
        assert stats["completed"] == 1 and h.result()[1]["converged"]
        assert not svc._worker.is_alive()
        # -- the fleet member's beat/watch pair: start -> stop --------
        fd = str(tmp_path / "fleet")
        os.makedirs(os.path.join(fd, "g0"), exist_ok=True)
        gate = Gate(journal_dir=os.path.join(fd, "g0"),
                    rid_namespace="g0")
        member = fleet.FleetMember(fd, "g0", gate, lease_s=0.05).start()
        assert any(
            t.name.startswith("pafleet-") for t in threading.enumerate()
        )
        member.stop()
        assert member._threads == []
        # -- the baseline holds: nothing outlived its owner -----------
        leaked = [
            t for t in threading.enumerate()
            if t not in baseline and t.is_alive()
        ]
        assert leaked == [], f"threads outlived shutdown: {leaked}"
        # non-daemon leaks would also hang interpreter exit — assert
        # the stronger process-wide property directly
        assert [
            t for t in threading.enumerate()
            if not t.daemon and t is not threading.main_thread()
            and t not in baseline
        ] == []
        return True

    _run(driver)
