"""paprof — phase-attributed profiling and the exchange cost matrix.

The ISSUE-10 tentpole acceptance lives here:

* phase attribution on the 4-part conformance fixture sums to the
  measured per-iteration total within the pinned band
  (`telemetry.profile.PHASE_SUM_BAND`) and reconciles per collective
  kind against `telemetry.comms`'s static per-iteration inventory;
* with profiling off (and on — profiling builds standalone programs)
  the block solver program is byte-identical StableHLO;
* the comms matrix's static side reconciles against
  `comms._exchange_inventory` on BOTH plan families, and the committed
  artifacts cannot drift from a fresh derivation;
* `tools/paprof.py --check` is the tier-1 in-process smoke.

Kept lean (tier-1 sits at ~748s of the 870s budget): ONE (6, 6)
4-part fixture shared module-wide, the deterministic split-timer (the
one capture method), and tiny trip counts.
"""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import telemetry
from partitionedarrays_jl_tpu.models import assemble_poisson
from partitionedarrays_jl_tpu.parallel.tpu import (
    TPUBackend,
    _env_overrides,
    _matrix_operands,
    device_matrix,
    make_cg_fn,
)
from partitionedarrays_jl_tpu.telemetry import commsmatrix as cmx
from partitionedarrays_jl_tpu.telemetry import profile as prof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def fixture_Ab():
    """The 4-part (6, 6) conformance-scale Poisson operator on a
    (2, 2) device mesh — one staging for the whole module."""
    import jax

    backend = TPUBackend(devices=jax.devices()[:4])

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (6, 6))
        return A

    return pa.prun(driver, backend, (2, 2)), backend


# ---------------------------------------------------------------------------
# phase attribution: the tentpole acceptance
# ---------------------------------------------------------------------------


def test_phase_profile_sums_in_band_and_reconciles(fixture_Ab,
                                                   monkeypatch):
    """Split-timer attribution on the 4-part fixture: the four phases
    sum to the measured per-iteration total within PHASE_SUM_BAND, and
    the per-phase collective split reconciles per kind against
    cg_comms_profile's per-iteration inventory — both recomputed
    independently by `reconcile_phases`."""
    A, backend = fixture_Ab
    profile = prof.capture_phase_profile(A, backend, reps=3)
    # a loaded host (the full tier-1 suite around this test) can push
    # one capture round out of band on pure timer jitter — the same
    # bounded re-capture discipline as paprof's CLI entry points
    for _retry in range(2):
        if profile is None or profile["in_band"]:
            break
        profile = prof.capture_phase_profile(A, backend, reps=3)
    assert profile is not None
    assert profile["phase_schema_version"] == prof.PHASE_SCHEMA_VERSION
    assert profile["method"] == "split-timer"
    assert set(profile["phases"]) == set(prof.PHASES)
    # keyed by the palint case name + the operator fingerprint
    assert profile["case"] in ("fused", "standard")
    assert profile["fingerprint"] == "g36-p4"
    assert profile["lowering"]["plan"] in ("box", "generic")
    # every phase measured nonnegative, the sum is the attributed total
    s = sum(profile["phases"][p]["s_per_it"] for p in prof.PHASES)
    # phases and the total are rounded to 9 decimals independently
    assert s == pytest.approx(profile["attributed_s_per_it"], abs=1e-8)
    assert all(
        profile["phases"][p]["s_per_it"] >= 0.0 for p in prof.PHASES
    )
    # the pinned band: attributed vs measured
    lo, hi = prof.PHASE_SUM_BAND
    assert profile["band"] == [lo, hi]
    assert lo <= profile["ratio_attributed_over_measured"] <= hi
    assert profile["in_band"] is True
    # per-kind reconciliation, inventory recomputed from the matrix
    dA = device_matrix(A, backend)
    assert prof.reconcile_phases(profile, dA=dA) == []
    # the split itself: permutes ride the halo phase, gathers the dots
    per_it = profile["per_iteration_comms"]
    halo = profile["phases"]["halo_exchange"]["comms"]
    dots = profile["phases"]["dot_allgather"]["comms"]
    assert halo["collective_permute"] == per_it["collective_permute"]
    assert dots["all_gather"] == per_it["all_gather"]
    assert per_it["collective_permute"]["ops"] > 0
    assert per_it["all_gather"]["ops"] > 0
    assert profile["unattributed_comms"] == {}
    # a seeded defect is caught: inflate one phase's gather count
    broken = json.loads(json.dumps(profile))
    broken["phases"]["dot_allgather"]["comms"]["all_gather"]["ops"] += 1
    assert any(
        "all_gather.ops" in m for m in prof.reconcile_phases(broken)
    )


def test_phase_trace_events_merge_shape(fixture_Ab):
    """The patrace merge feed: spans for every phase, synthetic
    iterations consecutive, args carrying the attribution identity.
    The committed artifact is the schema-2 multi-case container."""
    rec = json.load(open(os.path.join(REPO, "PHASE_PROFILE.json")))
    for case in ("standard", "fused"):
        committed = rec["profiles"][case]
        events = prof.phase_trace_events(committed, iterations=2)
        spans = [e for e in events if e.get("cat") == "phase"]
        assert len(spans) == 2 * len(prof.PHASES)
        assert {e["name"] for e in spans} == set(prof.PHASES)
        ts = [e["ts"] for e in spans]
        assert ts == sorted(ts)
        assert all(
            e["args"]["case"] == committed["case"] for e in spans
        )


def test_pa_prof_off_noop_and_solver_hlo_identical(fixture_Ab,
                                                   monkeypatch):
    """PA_PROF=0 turns capture into a no-op — and the overhead
    contract: the block solver program is byte-identical StableHLO
    with profiling on, off, or unset (profiling builds standalone
    programs; the solver path never reads PA_PROF*)."""
    A, backend = fixture_Ab
    dA = device_matrix(A, backend)
    ops = _matrix_operands(dA)
    P, W = dA.col_plan.layout.P, dA.col_plan.layout.W
    zb = np.zeros((P, W, 2))

    def text():
        fn = make_cg_fn(dA, tol=1e-9, maxiter=50, rhs_batch=2)
        return fn.jit_fn.lower(zb, zb, zb[..., 0], ops).as_text()

    monkeypatch.setenv("PA_PROF", "1")
    on = text()
    monkeypatch.setenv("PA_PROF", "0")
    off = text()
    assert on == off
    assert prof.capture_phase_profile(A, backend) is None


# ---------------------------------------------------------------------------
# the comms matrix
# ---------------------------------------------------------------------------


def test_comms_matrix_static_reconciles_both_plan_families(fixture_Ab):
    """The static per-edge matrix must reconcile exactly with
    comms._exchange_inventory on the box plan AND the generic index
    plan — the two derivations of bytes-on-the-wire can never fork."""
    A, backend = fixture_Ab
    dA = device_matrix(A, backend)
    m = cmx.static_matrix(dA.col_plan, np.float64, K=1, backend=backend)
    assert cmx.reconcile_matrix(m, dA) == []
    assert m["plan"] == "box"
    assert m["rounds"] == m["static"]["ops"] > 0
    with _env_overrides({"PA_TPU_BOX": "0"}):
        A2, _ = fixture_Ab

        def driver(parts):
            a, b, xe, x0 = assemble_poisson(parts, (6, 6))
            return a

        A2 = pa.prun(driver, backend, (2, 2))
        dA2 = device_matrix(A2, backend)
        m2 = cmx.static_matrix(
            dA2.col_plan, np.float64, K=4, backend=backend
        )
        assert m2["plan"] == "generic"
        assert cmx.reconcile_matrix(m2, dA2) == []
    # K scales bytes, not ops
    assert m2["static"]["per_device_bytes"] % 4 == 0
    # every edge labeled by the fabric hook; the virtual CPU mesh is
    # one process, so non-self edges classify as ici
    assert all(e["fabric"] == "ici" for e in m2["edges"]
               if e["src"] != e["dst"])
    # a seeded defect is caught: shrink one wire slab under its payload
    broken = json.loads(json.dumps(m2))
    broken["edges"][0]["wire_slots"] = (
        broken["edges"][0]["payload_slots"] - 1
    )
    assert cmx.reconcile_matrix(broken, dA2) != []


def test_committed_comms_matrix_matches_fresh_static_derivation():
    """COMMS_MATRIX.json is committed from the generic-plan fixture;
    its static side (edges, rounds, bytes) must equal a fresh
    derivation — measured timings may drift, the plan may not."""
    import jax

    committed = json.load(open(os.path.join(REPO, "COMMS_MATRIX.json")))
    assert committed["comms_matrix_schema_version"] == (
        cmx.COMMS_MATRIX_SCHEMA_VERSION
    )
    assert committed["static_check"] == []
    assert committed["attribution"] == "measured-round"
    assert committed["generated_by"] == "paprof"
    backend = TPUBackend(devices=jax.devices()[:4])
    with _env_overrides({"PA_TPU_BOX": "0"}):

        def driver(parts):
            a, b, xe, x0 = assemble_poisson(parts, (6, 6))
            return a

        A = pa.prun(driver, backend, (2, 2))
        dA = device_matrix(A, backend)
        fresh = cmx.static_matrix(
            dA.col_plan, committed["dtype"], K=committed["K"],
            backend=backend,
        )
    static_keys = ("round", "src", "dst", "payload_slots",
                   "wire_slots", "payload_bytes", "wire_bytes")
    committed_static = [
        {k: e[k] for k in static_keys} for e in committed["edges"]
    ]
    fresh_static = [
        {k: e[k] for k in static_keys} for e in fresh["edges"]
    ]
    assert committed_static == fresh_static
    assert committed["static"] == fresh["static"]
    assert all(e["measured_s"] >= 0.0 for e in committed["edges"])


def test_committed_phase_profile_is_reconciled():
    """PHASE_PROFILE.json (the schema-2 container): every committed
    case internally reconciled and in its own recorded band, the
    envelope on the container, and the case set covering the full
    lowering matrix through `phase_case_of`."""
    rec = json.load(open(os.path.join(REPO, "PHASE_PROFILE.json")))
    assert rec["phase_schema_version"] == prof.PHASE_SCHEMA_VERSION
    profiles = rec["profiles"]
    assert set(profiles) == {
        "standard", "fused", "block_k1_fused", "block_k4_fused",
    }
    for case, p in profiles.items():
        assert p["case"] == case
        assert prof.reconcile_phases(p) == [], case
        assert p["in_band"] is True, case
        assert p["fingerprint"] == "g36-p4"
    for p in profiles.values():
        assert set(p["phases"]) == set(prof.PHASES)
    # every lowering-matrix case must map onto a committed entry —
    # paprof --check's coverage gate, pinned here against the artifact
    from partitionedarrays_jl_tpu.parallel.tpu import lowering_matrix

    for case in lowering_matrix():
        assert prof.phase_case_of(case["name"]) in profiles, case["name"]
    assert rec.get("schema_version") == telemetry.ARTIFACT_SCHEMA_VERSION
    assert rec.get("generated_by") == "paprof"
    assert rec.get("platform") and isinstance(rec.get("pa_env"), dict)


# ---------------------------------------------------------------------------
# the operator surface: paprof --check
# ---------------------------------------------------------------------------


def test_paprof_check_smoke(capsys, monkeypatch):
    """`tools/paprof.py --check` in-process: capture, reconcile, comms
    matrix, committed-artifact validation — the tier-1 smoke (reps
    trimmed: the suite sits near its wall-clock budget)."""
    monkeypatch.setenv("PA_PROF_REPS", "3")
    paprof = _load_tool("paprof")
    rc = paprof.main(["--check"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "paprof --check: OK" in out
    assert "phase profile:" in out
    assert "comms matrix:" in out
    assert "static reconciliation vs comms inventory: OK" in out
