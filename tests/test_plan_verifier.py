"""paplan — the static exchange-plan soundness verifier.

Four layers, each pinned here:

* **Negative corpus** (tests/fixtures/paplan/): one COMMITTED mutated-
  plan fixture per defect class — overlapping ghost slot, dropped
  slot, asymmetric counts, self-send round, dead slot — each caught by
  exactly its check; the unmutated base plan verifies clean. A
  verifier without negative tests is a verifier that may be checking
  nothing (the same discipline docs/static_analysis.md demands of
  contracts).
* **Device plans**: the generic index plan and the box slice plan
  verify sound as built (pure-numpy construction — no compile), and
  seeded slot/round mutations on each are caught.
* **Construction-time gate**: ``PA_PLAN_VERIFY=1`` verifies at the
  plan build sites and raises the typed `PlanSoundnessError`; clean
  builds pass through untouched.
* **Rebuild/restore equality** (the ROADMAP item 4 invariant): a plan
  rebuilt from the same partition is fingerprint-IDENTICAL; a plan
  rebuilt from a checkpoint-restored partition (the PR 1 repartition
  smoke's path, which renumbers ghost lids) verifies sound and
  exchanges the identical global columns over the identical edges
  (`canonical_exchange_fingerprint`).

Plus the tier-1 CLI gate: ``tools/palint.py --check --fast`` exit
status asserted in-process, so a contract-registry or verifier
regression fails the suite, not just the CLI.
"""
import copy
import glob
import os

import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu.analysis import plan_verifier as pv
from partitionedarrays_jl_tpu.parallel.health import PlanSoundnessError
from partitionedarrays_jl_tpu.parallel.tpu import (
    DeviceExchangePlan,
    DeviceLayout,
)
from partitionedarrays_jl_tpu.parallel.tpu_box import (
    BoxExchangePlan,
    analyze_box_structure,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXDIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "fixtures", "paplan")

DEFECT_FIXTURES = [
    ("overlapping_ghost_slot.json", "ghost-race"),
    ("dropped_slot.json", "coverage"),
    ("asymmetric_counts.json", "symmetry"),
    ("self_send_round.json", "rounds"),
    ("dead_slot.json", "dead-slot"),
]


# ---------------------------------------------------------------------------
# the committed negative corpus
# ---------------------------------------------------------------------------


def test_corpus_is_complete():
    """One committed fixture per defect class, plus the clean base —
    and no fixture class is missing from PLAN_CHECKS."""
    names = {os.path.basename(p) for p in glob.glob(
        os.path.join(FIXDIR, "*.json")
    )}
    assert names == {n for n, _ in DEFECT_FIXTURES} | {"clean.json"}
    assert {c for _, c in DEFECT_FIXTURES} == set(pv.PLAN_CHECKS)


def test_clean_fixture_verifies_sound():
    ex, parts, ref, defect = pv.load_exchanger_fixture(
        os.path.join(FIXDIR, "clean.json")
    )
    assert defect is None
    assert pv.verify_exchanger(ex, parts, referenced=ref) == []


@pytest.mark.parametrize("name,check", DEFECT_FIXTURES)
def test_defect_fixture_caught_by_its_check(name, check):
    ex, parts, ref, defect = pv.load_exchanger_fixture(
        os.path.join(FIXDIR, name)
    )
    assert defect == check, "fixture self-description drifted"
    defects = pv.verify_exchanger(ex, parts, referenced=ref)
    assert defects, f"{name}: verifier saw nothing"
    checks = {d.check for d in defects}
    assert check in checks, (name, checks)
    # the defect report carries actionable part/slot diagnostics
    hit = next(d for d in defects if d.check == check)
    assert hit.part is not None and hit.message


def test_check_plan_raises_typed_with_diagnostics():
    ex, parts, ref, _ = pv.load_exchanger_fixture(
        os.path.join(FIXDIR, "overlapping_ghost_slot.json")
    )
    with pytest.raises(PlanSoundnessError) as ei:
        pv.check_plan(ex, parts=parts, referenced=ref, context="corpus")
    diag = ei.value.diagnostics
    assert "ghost-race" in diag["checks"]
    assert diag["defects"] and diag["defects"][0]["check"]
    assert diag["context"] == "corpus"


# ---------------------------------------------------------------------------
# device plans (pure-numpy construction — no compile, host backend)
# ---------------------------------------------------------------------------


def _probe_system(parts):
    A, b, xe, x0 = pa.assemble_poisson(parts, (6, 6))
    return A


def test_device_plans_verify_sound_and_mutations_caught():
    def driver(parts):
        A = _probe_system(parts)
        rows = A.cols
        ref = pv.referenced_ghosts(A)
        # every ghost of the assembled operator is genuinely referenced
        assert all(m.all() for m in ref)

        layout = DeviceLayout(rows, padded=False)
        plan = DeviceExchangePlan(rows.exchanger, layout)
        assert pv.verify_device_plan(plan, referenced=ref) == []

        # seeded: redirect one receive slot onto another -> ghost-race
        # (and the orphaned slot becomes a coverage hole)
        bad = DeviceExchangePlan(rows.exchanger, layout)
        q, r = next(
            (q, r)
            for q in range(layout.P) for r in range(bad.R)
            if (bad.rcv_idx[q, r] != layout.trash).sum() >= 2
        )
        slots = np.nonzero(bad.rcv_idx[q, r] != layout.trash)[0]
        bad.rcv_idx = bad.rcv_idx.copy()
        bad.rcv_idx[q, r, slots[1]] = bad.rcv_idx[q, r, slots[0]]
        checks = {d.check for d in pv.verify_device_plan(bad, referenced=ref)}
        assert "ghost-race" in checks

        # seeded: a self-send edge smuggled into a round -> rounds
        bad2 = DeviceExchangePlan(rows.exchanger, layout)
        perms = [list(p) for p in bad2.perms]
        perms[0] = list(perms[0]) + [(0, 0)]
        bad2.perms = tuple(tuple(p) for p in perms)
        checks = {d.check for d in pv.verify_device_plan(bad2, referenced=ref)}
        assert "rounds" in checks

        # the box slice plan of the same partition
        info = analyze_box_structure(rows)
        assert info is not None, "probe partition lost its box structure"
        blayout = DeviceLayout(rows, padded=False, box_info=info)
        bplan = BoxExchangePlan(blayout, info)
        assert pv.verify_box_plan(bplan, referenced=ref) == []

        # seeded: collide two segment slots on one part -> ghost-race
        info2 = analyze_box_structure(rows)
        p = next(
            p for p in range(info2.P)
            if len(np.asarray(info2.ghost_rel_slots[p])) >= 2
        )
        rel = np.asarray(info2.ghost_rel_slots[p]).copy()
        rel[1] = rel[0]
        info2.ghost_rel_slots = (
            list(info2.ghost_rel_slots[:p]) + [rel]
            + list(info2.ghost_rel_slots[p + 1:])
        )
        bad3 = BoxExchangePlan(blayout, info2)
        checks = {d.check for d in pv.verify_box_plan(bad3, referenced=ref)}
        assert "ghost-race" in checks
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))


def test_construction_time_gate_catches_corrupted_plan(monkeypatch):
    """PA_PLAN_VERIFY=1: a clean build passes through; a corrupted
    host plan is refused at the DEVICE-PLAN build site with the typed
    error, before any program could lower from it."""
    monkeypatch.setenv("PA_PLAN_VERIFY", "1")

    def driver(parts):
        A = _probe_system(parts)
        rows = A.cols
        from partitionedarrays_jl_tpu.parallel.tpu import (
            device_exchange_plan,
        )

        # clean: the gate verifies and passes (both plan flavors)
        plan = device_exchange_plan(rows)
        assert plan is device_exchange_plan(rows)  # cached, not re-run

        # corrupt the HOST plan in place (an overlapping ghost slot),
        # then force the device plan to rebuild from it
        ex = rows.exchanger
        t = next(
            t for t in ex.lids_rcv.part_values() if len(t.data) >= 2
        )
        t.data[1] = t.data[0]
        monkeypatch.setenv("PA_TPU_BOX", "0")  # generic plan reads lids
        rows._device_plan = {}
        for attr in ("_device_layout", "_box_info"):
            if hasattr(rows, attr):
                delattr(rows, attr)
        with pytest.raises(PlanSoundnessError) as ei:
            device_exchange_plan(rows)
        assert "ghost-race" in ei.value.diagnostics["checks"]
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))


def test_exchanger_construction_gate_passes_clean(monkeypatch):
    monkeypatch.setenv("PA_PLAN_VERIFY", "1")

    def driver(parts):
        rows = pa.cartesian_partition(parts, (6, 6), pa.with_ghost)
        ex = rows.exchanger  # from_partition runs the gate
        assert ex is not None
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))


# ---------------------------------------------------------------------------
# rebuild / checkpoint-restore equality (the ROADMAP item 4 invariant)
# ---------------------------------------------------------------------------


def test_rebuilt_plan_fingerprint_identical_and_sound():
    def driver(parts):
        A = _probe_system(parts)
        rows = A.cols
        fp0 = pv.plan_fingerprint(rows.exchanger)
        dev0 = pv.plan_fingerprint(
            DeviceExchangePlan(rows.exchanger, DeviceLayout(rows))
        )
        rows.invalidate_exchanger()
        ex1 = rows.exchanger  # rebuilt from the same partition
        assert pv.plans_equal(ex1, ex1)
        assert pv.plan_fingerprint(ex1) == fp0
        assert pv.plan_fingerprint(
            DeviceExchangePlan(ex1, DeviceLayout(rows))
        ) == dev0
        ref = pv.referenced_ghosts(A)
        assert pv.verify_exchanger(ex1, rows.partition, referenced=ref) == []
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))


def test_checkpoint_restored_partition_plans_sound_and_canonical_equal(
    tmp_path,
):
    """The PR 1 repartition-smoke path: save the operator, restore it
    into a FRESH partition (which renumbers ghost lids in column-sorted
    order). The rebuilt plans must verify sound against the restored
    operator's sparsity and exchange the IDENTICAL global columns over
    the identical edges — the invariant ROADMAP item 4's incremental
    re-plan will rely on. (Exact slot-level fingerprints legitimately
    differ across the two lid orders; `plan_fingerprint` equality is
    pinned for the same-partition rebuild above.)"""
    p = str(tmp_path / "A.npz")
    state = {}

    def save(parts):
        A = _probe_system(parts)
        state["canonical"] = pv.canonical_exchange_fingerprint(
            A.cols.exchanger, A.cols.partition
        )
        pa.save_psparse(p, A)
        return True

    def load(parts):
        rows = pa.cartesian_partition(parts, (6, 6), pa.no_ghost)
        A2 = pa.load_psparse(p, rows)
        ref = pv.referenced_ghosts(A2)
        defects = pv.verify_exchanger(
            A2.cols.exchanger, A2.cols.partition, referenced=ref
        )
        assert defects == [], [str(d) for d in defects]
        plan = DeviceExchangePlan(A2.cols.exchanger, DeviceLayout(A2.cols))
        assert pv.verify_device_plan(plan, referenced=ref) == []
        assert pv.canonical_exchange_fingerprint(
            A2.cols.exchanger, A2.cols.partition
        ) == state["canonical"]
        return True

    assert pa.prun(save, pa.sequential, (2, 2))
    assert pa.prun(load, pa.sequential, (2, 2))


# ---------------------------------------------------------------------------
# the tier-1 CLI gate (ISSUE 8 satellite: a contract-registry or
# verifier regression fails the SUITE, not just the CLI)
# ---------------------------------------------------------------------------


def test_palint_check_fast_exits_zero():
    """`tools/palint.py --check --fast` (env lint + plan-soundness leg;
    the fast contract matrix itself is exercised in-process by
    tests/test_static_analysis.py, so the CLI leg skips re-lowering it
    to stay inside the tier-1 time budget) must exit 0."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "palint_t1", os.path.join(REPO, "tools", "palint.py")
    )
    palint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(palint)
    rc = palint.main(["--check", "--fast", "--skip-matrix"])
    assert rc == 0


def test_palint_check_exits_nonzero_on_plan_defect(monkeypatch):
    """The CLI's teeth for the new leg: a verifier that reports a
    defect must turn into exit 1."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "palint_t1b", os.path.join(REPO, "tools", "palint.py")
    )
    palint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(palint)
    monkeypatch.setattr(
        palint, "_plan_soundness_leg",
        lambda verbose=None: (1, [pv.PlanDefect(
            "ghost-race", "device-generic", 0, "seeded defect"
        )]),
    )
    rc = palint.main(["--check", "--fast", "--skip-matrix",
                      "--skip-lint"])
    assert rc == 1
