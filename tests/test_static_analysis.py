"""palint — the static program-contract analyzer and env-key lint gate.

Four layers, each pinned here:

* **Analyzer unit tests** against COMMITTED lowered-text fixtures
  (tests/fixtures/palint/ — a 4-part (6, 6) Poisson CG program in both
  dialects): exact collective/dtype/copy/while-carry inventories, and
  the migration pin — `analysis.collective_counts` reproduces the raw
  regex counts the three historical per-file helpers produced, on the
  same text.
* **Negative tests**: the dtype-closure contract catches a deliberately
  injected f64 op (the PR 3 poisoning class), the copy-budget contract
  catches copy growth (the PR 2 anomaly class), the loop-residency
  contract catches an injected infeed, and the env lint catches an
  unkeyed lowering-affecting flag in a synthetic package.
* **The env-key lint gate** (tier-1): every lowering-affecting ``PA_*``
  read in the package is key-covered and documented; the classification
  itself is pinned as a fixture so a new flag fails until classified.
* **The contract matrix**: the fast subset every CI run lowers
  (standard / fused / block K∈{1,4} / ABFT pair / f32 probe) holds all
  contracts; the full matrix (with strict-bits and both block bodies)
  is the slow leg `tools/palint.py --check` also runs.
"""
import os
import re
import subprocess
import sys

import pytest

from partitionedarrays_jl_tpu import analysis
from partitionedarrays_jl_tpu.analysis import (
    analyze_text,
    check_contracts,
    classify,
    collective_counts,
    env_lint,
    key_coverage,
    lint_env_keys,
)
from partitionedarrays_jl_tpu.analysis.contracts import COPY_BUDGETS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXDIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "fixtures", "palint")


def _fix(name):
    with open(os.path.join(FIXDIR, name), encoding="utf-8") as f:
        return f.read()


# ---------------------------------------------------------------------------
# analyzer unit tests: committed fixtures with known inventories
# ---------------------------------------------------------------------------


def test_fixture_f64_stablehlo_inventory():
    rep = analyze_text(_fix("cg_4part_f64.stablehlo.txt"))
    assert rep.dialect == "stablehlo"
    assert rep.collectives == {
        "all_gather": 3, "collective_permute": 8,
        "all_reduce": 0, "reduce_scatter": 0,
    }
    assert rep.float_dtypes == {"f64"}
    assert rep.copies == 0  # the op does not exist pre-optimization
    assert rep.host_transfer_ops == []
    # ONE compiled solve loop with the standard body's 14-slot carry
    assert len(rep.while_loops) == 1
    assert len(rep.while_loops[0].carries) == 14
    assert rep.while_loops[0].carry_bytes == 1061
    # payload accounting: 4-part gathers of f64 scalars are visible
    assert rep.collective_bytes["all_gather"] > 0
    assert rep.collective_bytes["collective_permute"] > 0


def test_fixture_f32_stablehlo_closed_over_f32():
    rep = analyze_text(_fix("cg_4part_f32.stablehlo.txt"))
    assert rep.float_dtypes == {"f32"}
    assert rep.f64_lines == []
    assert rep.collectives["collective_permute"] == 8


def test_fixture_compiled_hlo_inventory():
    rep = analyze_text(_fix("cg_4part_f64.hlo.txt"))
    assert rep.dialect == "hlo"
    # collective OP SITES survive compilation unchanged on this program
    assert rep.collectives["all_gather"] == 3
    assert rep.collectives["collective_permute"] == 8
    # the PR 2 canary number this fixture pins: XLA materializes 17
    # copy ops (while-carry copies + fusion roots) for the standard body
    assert rep.copies == 17
    # scatter-add loops + the solve loop
    assert len(rep.while_loops) == 3
    assert max(len(w.carries) for w in rep.while_loops) == 18


def test_hlo_parser_sees_tuple_and_async_collectives():
    """Compiled-HLO op-site counting must survive the two other result
    spellings XLA prints: a TUPLE result (spaces defeat a naive \\S+
    capture) and an async start/done pair (one collective, counted at
    the start op only — done consumes the handle)."""
    txt = "\n".join([
        "ENTRY %main {",
        "  %p0 = f64[9]{0} collective-permute(%x), channel_id=1",
        "  %p1 = (f64[3]{0}, f64[3]{0}) collective-permute(%a, %b)",
        "  %s = (f32[2]{0}, f32[2]{0}, u32[], u32[]) "
        "collective-permute-start(%c)",
        "  %d = f32[2]{0} collective-permute-done(%s)",
        "  %g = (f64[8,2]{1,0}) all-gather(%y), dimensions={0}",
        "  %c0 = f64[9]{0} copy(%x)",
        "  %c1 = (f64[9]{0}, u32[]) copy-start(%x)",
        "  %c2 = f64[9]{0} copy-done(%c1)",
        "}",
    ])
    rep = analyze_text(txt)
    assert rep.dialect == "hlo"
    assert rep.collectives["collective_permute"] == 3  # p0, p1, start
    assert rep.collectives["all_gather"] == 1
    assert rep.collective_bytes["collective_permute"] >= 9 * 8 + 2 * 3 * 8
    assert rep.collective_bytes["all_gather"] == 8 * 2 * 8
    assert rep.copies == 2  # c0 + the start/done pair counted once


def test_collective_counts_pins_legacy_regex_semantics():
    """The migration contract: `analysis.collective_counts` must
    reproduce EXACTLY the numbers the three deleted per-file helpers
    (`len(re.findall(kind, text))` over the lowered text) pinned before
    the refactor — including the quirk that attribute mentions count
    (``all_gather_dim`` makes each StableHLO gather count twice)."""
    for name in ("cg_4part_f64.stablehlo.txt", "cg_4part_f32.stablehlo.txt"):
        txt = _fix(name)
        legacy = {
            k: len(re.findall(k, txt))
            for k in ("collective_permute", "all_gather", "all_reduce")
        }
        assert collective_counts(txt) == legacy
        # and the quirk is real: op sites != raw hits for all_gather
        rep = analyze_text(txt)
        assert legacy["all_gather"] == 2 * rep.collectives["all_gather"]


def test_no_private_collective_counts_definitions_remain():
    """The dedup satellite's acceptance: zero private helper
    definitions in the three migrated files (they import the shared
    one)."""
    for rel in ("test_fused_cg.py", "test_block_cg.py", "test_abft.py"):
        with open(os.path.join(REPO, "tests", rel), encoding="utf-8") as f:
            src = f.read()
        assert "def _collective_counts" not in src, rel
        assert "partitionedarrays_jl_tpu.analysis" in src, rel


# ---------------------------------------------------------------------------
# negative tests: the contracts catch seeded regressions
# ---------------------------------------------------------------------------


def test_dtype_closure_catches_injected_f64():
    """Seed the PR 3 poisoning class into the f32 fixture: one f64
    convert op anywhere in the program must trip dtype-closure."""
    clean = _fix("cg_4part_f32.stablehlo.txt")
    cases = {"probe_f32": {"name": "probe_f32", "tags": {"staged": "f32"}}}
    ok = check_contracts({"probe_f32": analyze_text(clean)}, cases)
    assert not [v for v in ok if v.contract == "dtype-closure"]
    poisoned = clean.replace(
        "func.func public @main",
        '  %poison = stablehlo.convert %arg0 : (tensor<4x46xf32>) -> '
        "tensor<4x46xf64>\n  func.func public @main",
        1,
    )
    rep = analyze_text(poisoned)
    assert "f64" in rep.float_dtypes
    bad = check_contracts({"probe_f32": rep}, cases)
    hits = [v for v in bad if v.contract == "dtype-closure"]
    assert hits, "dtype-closure did not catch the injected f64 op"
    assert "PR 3" in hits[0].message


def test_copy_budget_catches_copy_growth(monkeypatch):
    """Seed the PR 2 anomaly class: a compiled report whose copy count
    exceeds its body's budget must trip copy-budget; at the budget it
    must not."""
    rep = analyze_text(_fix("cg_4part_f64.hlo.txt"))  # copies == 17
    cases = {"probe": {"name": "probe", "tags": {"body": "standard"}}}
    monkeypatch.setitem(COPY_BUDGETS, "probe", 16)
    bad = check_contracts({"probe__compiled": rep}, cases)
    assert [v for v in bad if v.contract == "copy-budget"]
    monkeypatch.setitem(COPY_BUDGETS, "probe", 17)
    ok = check_contracts({"probe__compiled": rep}, cases)
    assert not [v for v in ok if v.contract == "copy-budget"]


def test_loop_residency_catches_injected_infeed():
    """An infeed smuggled INTO the while region must trip
    no-host-transfer-in-loop; the clean fixture must not."""
    clean = _fix("cg_4part_f64.stablehlo.txt")
    cases = {"probe": {"name": "probe", "tags": {}}}
    ok = check_contracts({"probe": analyze_text(clean)}, cases)
    assert not [v for v in ok if v.contract == "no-host-transfer-in-loop"]
    m = re.search(r"^(.*stablehlo\.while.*)$", clean, re.M)
    assert m, "fixture lost its while loop"
    doctored = clean.replace(
        m.group(1),
        m.group(1) + '\n      %hx = "stablehlo.infeed"(%arg0) : '
        "(tensor<4x46xf64>) -> tensor<4x46xf64>",
        1,
    )
    bad = check_contracts({"probe": analyze_text(doctored)}, cases)
    assert [v for v in bad if v.contract == "no-host-transfer-in-loop"]


def test_sanity_contract_guards_parser_rot():
    """If the analyzer stops seeing collectives, the equality contracts
    would pass vacuously — the sanity contract must fail instead."""
    rep = analyze_text("func.func public @main() {\n}\n")
    cases = {"standard": {"name": "standard", "tags": {"body": "standard"}}}
    bad = check_contracts({"standard": rep}, cases)
    assert [v for v in bad if v.contract == "sanity"]


# ---------------------------------------------------------------------------
# env-key lint: the gate, its pinned classification, and its teeth
# ---------------------------------------------------------------------------

#: The pinned clean state (ISSUE 5 satellite): exactly these flags
#: alter tracing/lowering today. A NEW flag landing in either direction
#: fails this test until a human (a) keys it or exempts it with a
#: reason, and (b) updates this fixture + docs/api.md.
EXPECTED_LOWERING_FLAGS = {
    "PA_FAULT_DEVICE",
    "PA_HEALTH_AUDIT_EVERY",
    "PA_HEALTH_AUDIT_TOL",
    "PA_HEALTH_MAX_ROLLBACKS",
    "PA_HEALTH_ROLLBACK_DEPTH",
    "PA_TPU_ABFT",
    "PA_TPU_ABFT_TOL",
    "PA_TPU_BOX",
    "PA_TPU_BSR",
    "PA_TPU_CLASS_ACC",
    "PA_TPU_ELL_GUARD",
    "PA_TPU_ELL_MAX_GATHER",
    "PA_TPU_FUSED_CG",
    "PA_TPU_GMG_BOX",
    "PA_TPU_GMG_STENCIL",
    "PA_TPU_OH_BUCKETS",
    "PA_TPU_SD",
    "PA_TPU_STRICT_BITS",
    "PA_TRACE_ITERS",
}


def test_env_lint_green():
    """The acceptance gate: every lowering-affecting PA_* read is
    key-covered AND the docs/api.md env table agrees with the source
    inventory in both directions."""
    violations = lint_env_keys()
    assert not violations, "\n".join(str(v) for v in violations)


def test_env_lint_classification_pinned():
    cls = classify()
    lowering = {n for n, e in cls.items() if e["class"] == "lowering"}
    assert lowering == EXPECTED_LOWERING_FLAGS, (
        "lowering-affecting flag set drifted — if you added a flag, key "
        "it (or exempt it with a reason in analysis.env_lint."
        "NON_LOWERING), document it in docs/api.md, and update this "
        f"fixture. diff: +{lowering - EXPECTED_LOWERING_FLAGS} "
        f"-{EXPECTED_LOWERING_FLAGS - lowering}"
    )
    # every exemption names a real read and carries a reason
    for name, reason in env_lint.NON_LOWERING.items():
        assert name in cls, f"stale exemption {name}"
        assert len(reason) > 20, f"exemption {name} needs a real reason"


def test_key_coverage_resolves_through_helpers():
    """The coverage closure must see THROUGH the one-helper-per-mode
    indirections: strict_bits() lives in utils.helpers, abft_enabled()
    in parallel.health, the GMG resolutions in tpu_gmg — all reached
    from the three registered key sites."""
    cov = key_coverage()
    assert cov["PA_TPU_STRICT_BITS"] == "_lowering_env_key"
    assert cov["PA_TPU_ABFT"] == "_lowering_env_key"
    assert cov["PA_TPU_GMG_BOX"] == "_gmg_env_key"
    assert cov["PA_HEALTH_AUDIT_EVERY"] == "_sdc_config"
    assert cov["PA_FAULT_DEVICE"] == "_sdc_config"
    assert cov["PA_TRACE_ITERS"] == "_trace_config"
    assert EXPECTED_LOWERING_FLAGS <= set(cov)


def test_env_lint_catches_unkeyed_flag(tmp_path):
    """The lint's teeth, proven on a synthetic package: a PA_* read
    inside a staging root with NO key site covering it must be flagged;
    adding it to the key site clears it."""
    pkg = tmp_path / "fakepkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "import os\n\n"
        "def make_cg_fn():\n"
        "    return os.environ.get('PA_TPU_FAKEMODE', '0')\n\n"
        "def _lowering_env_key():\n"
        "    return ()\n"
    )
    violations = lint_env_keys(root=str(pkg), check_docs=False)
    assert any("PA_TPU_FAKEMODE" in v for v in violations), violations
    (pkg / "mod.py").write_text(
        "import os\n\n"
        "def make_cg_fn():\n"
        "    return os.environ.get('PA_TPU_FAKEMODE', '0')\n\n"
        "def _lowering_env_key():\n"
        "    return (os.environ.get('PA_TPU_FAKEMODE', '0'),)\n"
    )
    violations = lint_env_keys(root=str(pkg), check_docs=False)
    assert not any("PA_TPU_FAKEMODE" in v for v in violations), violations


def test_key_coverage_not_fooled_by_name_collision(tmp_path):
    """Coverage must be module-qualified: the key site calls its own
    local helper; an UNRELATED module defines a same-named helper that
    reads a PA_* flag consumed by a staging root. A name-only closure
    unions the two definitions, marks the flag key-covered, and the
    lint passes green on exactly the stale-cache bug class it exists to
    catch — the module-qualified closure must flag it instead."""
    pkg = tmp_path / "fakepkg"
    pkg.mkdir()
    (pkg / "keys.py").write_text(
        "import os\n\n"
        "def _resolve():\n"
        "    return ()\n\n\n"
        "def _lowering_env_key():\n"
        "    return _resolve()\n"
    )
    (pkg / "other.py").write_text(
        "import os\n\n"
        "def _resolve():\n"
        "    return os.environ.get('PA_TPU_NEWMODE', '0')\n\n\n"
        "def make_cg_fn():\n"
        "    return _resolve()\n"
    )
    assert "PA_TPU_NEWMODE" not in key_coverage(root=str(pkg))
    violations = lint_env_keys(root=str(pkg), check_docs=False)
    assert any("PA_TPU_NEWMODE" in v for v in violations), violations
    # a key site that genuinely IMPORTS a helper (no local definition)
    # still resolves it cross-module — coverage survives the tightening
    (pkg / "keys.py").write_text(
        "import os\n\n"
        "from .other import _resolve\n\n\n"
        "def _lowering_env_key():\n"
        "    return _resolve()\n"
    )
    assert key_coverage(root=str(pkg)).get("PA_TPU_NEWMODE") == (
        "_lowering_env_key"
    )
    violations = lint_env_keys(root=str(pkg), check_docs=False)
    assert not any("PA_TPU_NEWMODE" in v for v in violations), violations


def test_env_lint_sees_method_and_module_level_reads(tmp_path):
    """The two attribution blind spots a name-only scanner has, both
    closed: (a) a read inside a METHOD that a staging root reaches only
    through an attribute call (`planner.pick_mode()` — the class name
    never appears in the call chain), and (b) a MODULE-LEVEL read
    consumed by a staging root (import-time freeze: no later cache key
    can see a flip, the staleness hazard itself)."""
    pkg = tmp_path / "fakepkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "import os\n\n"
        "_MODLEVEL = os.environ.get('PA_TPU_MODLEVEL', '0')\n\n\n"
        "class Planner:\n"
        "    def pick_mode(self):\n"
        "        return os.environ.get('PA_TPU_METHODMODE', '0')\n\n\n"
        "def make_cg_fn(planner):\n"
        "    return planner.pick_mode(), _MODLEVEL\n\n\n"
        "def _lowering_env_key():\n"
        "    return ()\n"
    )
    violations = lint_env_keys(root=str(pkg), check_docs=False)
    assert any("PA_TPU_METHODMODE" in v for v in violations), violations
    assert any("PA_TPU_MODLEVEL" in v for v in violations), violations


# ---------------------------------------------------------------------------
# the ELL-guard env-key fold (the lint's first real finding) — rekey pin
# ---------------------------------------------------------------------------


def test_ell_guard_envs_rekey_the_lowering(monkeypatch):
    from partitionedarrays_jl_tpu.parallel.tpu import _lowering_env_key

    monkeypatch.delenv("PA_TPU_ELL_MAX_GATHER", raising=False)
    monkeypatch.delenv("PA_TPU_ELL_GUARD", raising=False)
    k0 = _lowering_env_key()
    # NORMALIZED resolution (one helper for guard site and key site):
    # spelling the default explicitly must NOT spuriously rekey
    monkeypatch.setenv("PA_TPU_ELL_MAX_GATHER", "25000000")
    assert _lowering_env_key() == k0
    monkeypatch.setenv("PA_TPU_ELL_MAX_GATHER", "2.5e7")
    assert _lowering_env_key() == k0
    monkeypatch.setenv("PA_TPU_ELL_MAX_GATHER", "123456")
    k1 = _lowering_env_key()
    assert k1 != k0
    monkeypatch.setenv("PA_TPU_ELL_GUARD", "0")
    assert _lowering_env_key() not in (k0, k1)


def test_ell_guard_env_inf_takes_the_graceful_path(monkeypatch):
    """``PA_TPU_ELL_MAX_GATHER=inf`` parses as a float but overflows
    ``int()`` — it must take the same raw-string path as junk (key on
    the spelling, never crash `_lowering_env_key`), and only the ACTIVE
    guard site turns it into an error."""
    from partitionedarrays_jl_tpu.parallel.tpu import (
        _ell_guard_check,
        _ell_guard_env,
        _lowering_env_key,
    )

    monkeypatch.setenv("PA_TPU_ELL_MAX_GATHER", "inf")
    monkeypatch.setenv("PA_TPU_ELL_GUARD", "0")
    assert _ell_guard_env() == ("0", "inf")
    _lowering_env_key()  # must not raise with the guard disabled
    _ell_guard_check(4, 10**9, 10**9, None)  # disabled guard: ignored
    monkeypatch.setenv("PA_TPU_ELL_GUARD", "1")
    with pytest.raises(ValueError, match="PA_TPU_ELL_MAX_GATHER"):
        _ell_guard_check(4, 10, 10, None)


def test_ell_guard_flip_reruns_staging_admission(monkeypatch):
    """The regression the fold closes: stage an ELL matrix under a
    raised footprint ceiling, then drop the ceiling — `device_matrix`
    must RE-RUN admission and refuse, not serve the cached lowering
    staged under the laxer rule."""
    import jax

    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.models import assemble_poisson
    from partitionedarrays_jl_tpu.parallel.tpu import (
        ELLFootprintError,
        TPUBackend,
        device_matrix,
    )

    # strict-bits forces the pure-ELL lowering; guard=1 enforces on the
    # host mesh too (it only warns there by default)
    monkeypatch.setenv("PA_TPU_STRICT_BITS", "1")
    monkeypatch.setenv("PA_TPU_ELL_GUARD", "1")
    monkeypatch.setenv("PA_TPU_ELL_MAX_GATHER", "1000000")
    backend = TPUBackend(devices=jax.devices()[:4])

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (6, 6))
        return A

    A = pa.prun(driver, backend, (2, 2))
    dA = device_matrix(A, backend)  # stages fine under the high ceiling
    assert dA is device_matrix(A, backend)  # cached while env unchanged
    monkeypatch.setenv("PA_TPU_ELL_MAX_GATHER", "1")
    with pytest.raises(ELLFootprintError):
        device_matrix(A, backend)
    # restoring the ceiling serves the original staged lowering again
    monkeypatch.setenv("PA_TPU_ELL_MAX_GATHER", "1000000")
    assert device_matrix(A, backend) is dA


# ---------------------------------------------------------------------------
# the contract matrix (fast subset in tier-1; full matrix is slow)
# ---------------------------------------------------------------------------


def test_matrix_base_env_pins_every_lowering_flag():
    """The matrix's base env must pin DOWN exactly the flags the lint
    classifies as lowering-affecting — otherwise an ambient shell
    export (`PA_TPU_CLASS_ACC=0`, a raised rollback depth, ...) makes
    every case lower a different program than the one the contracts and
    copy budgets were pinned against."""
    from partitionedarrays_jl_tpu.parallel.tpu import _MATRIX_BASE_ENV

    assert set(_MATRIX_BASE_ENV) == EXPECTED_LOWERING_FLAGS, (
        f"+{set(_MATRIX_BASE_ENV) - EXPECTED_LOWERING_FLAGS} "
        f"-{EXPECTED_LOWERING_FLAGS - set(_MATRIX_BASE_ENV)}"
    )


def test_lowering_matrix_enumerator_well_formed():
    from partitionedarrays_jl_tpu.parallel.tpu import lowering_matrix

    full = lowering_matrix(fast=False)
    fast = lowering_matrix(fast=True)
    names = [c["name"] for c in full]
    assert len(names) == len(set(names))
    assert {c["name"] for c in fast} <= set(names)
    by_name = {c["name"]: c for c in full}
    for c in full:
        off = c["tags"].get("abft_off")
        if off:
            assert off in by_name, (c["name"], off)
            assert "abft" not in by_name[off]["tags"]
        if c["tags"].get("body") == "block":
            assert c["tags"].get("block_of") in by_name
    # the dtype-closure probes are part of the FAST subset — the PR 3
    # class must be caught by every CI run, not just the slow leg
    assert any(c["tags"].get("staged") == "f32" for c in fast)


def _run_matrix(fast, with_runtime=False):
    import jax

    from partitionedarrays_jl_tpu.analysis import build_reports
    from partitionedarrays_jl_tpu.analysis import check_contracts as check
    from partitionedarrays_jl_tpu.parallel.tpu import TPUBackend

    backend = TPUBackend(devices=jax.devices()[:8])
    cases, reports = build_reports(
        backend, fast=fast, with_compiled=True, with_runtime=with_runtime,
        with_plans=True, with_memory=True,
    )
    violations = check(reports, cases)
    assert not violations, "\n".join(str(v) for v in violations)
    # the matrix really lowered: baseline cases present with inventories
    assert reports["standard"].collective_count_total > 0
    assert reports["standard__compiled"].copies <= COPY_BUDGETS["standard"]
    return cases, reports


def test_fast_matrix_contracts_hold():
    """Tier-1: the fast subset of the lowering matrix honors every
    contract (standard/fused/block-K1/K4, the ABFT parity pair, the f32
    dtype-closure probe, both compiled copy-budget legs, the per-case
    plan-soundness audits, and the static memory budgets)."""
    cases, reports = _run_matrix(fast=True)
    # dtype-closure's compiled leg is live, not dead code: the f32-
    # staged probe gets a compiled-HLO report too, so an f64 op XLA
    # introduces only during compilation would still trip the contract
    assert "standard_f32__compiled" in reports
    assert "f64" not in reports["standard_f32__compiled"].float_dtypes
    # the plan audits are live: default-env cases verified the BOX
    # plan, the nobox/ABFT cases the GENERIC plan, all with zero defects
    # and the host exchanger alongside
    kinds = {cases[n]["plan_audit"]["kind"] for n in cases}
    assert kinds == {"device-box", "device-generic"}
    for n in cases:
        audit = cases[n]["plan_audit"]
        assert audit["n_defects"] == 0, (n, audit)
        assert "host-exchanger" in audit["plans"]
    # the memory footprints are live, and the compiled cases' peaks
    # really came from the XLA buffer assignment
    for n in ("standard", "fused", "standard_f32"):
        assert cases[n]["memory"]["peak_source"] == "hlo-buffer-assignment"
    assert cases["standard_nobox"]["memory"]["peak_source"] == "shape-sum"
    # and the committed artifact matches what this build measured for
    # the deterministic shape-sum fields (regenerate with
    # tools/palint.py --write-memory when a lowering legitimately
    # changes its footprint)
    import json

    committed = json.load(
        open(os.path.join(REPO, "MEMORY_FOOTPRINT.json"))
    )["cases"]
    for n in cases:
        fp = cases[n]["memory"]
        assert committed[n]["carry_bytes"] == fp["carry_bytes"], n
        assert committed[n]["plan_bytes"] == fp["plan_bytes"], n
        assert committed[n]["operand_bytes"] == fp["operand_bytes"], n


@pytest.mark.slow
def test_full_matrix_contracts_hold():
    """The full matrix `tools/palint.py --check` gates on (adds both
    block bodies, the nobox/ABFT fused pairs, strict-bits, fused f32).
    ``with_runtime`` probe-solves every case so the
    static-measured-reconciliation contract (the patrace tentpole's
    acceptance criterion) is checked across ALL 15 cases — the fast
    probe legs live in tests/test_telemetry.py. Plan audits and memory
    budgets ride along over the full case set."""
    cases, reports = _run_matrix(fast=False, with_runtime=True)
    assert "strict_standard" in reports
    assert all(c["plan_audit"]["n_defects"] == 0 for c in cases.values())


# ---------------------------------------------------------------------------
# negative tests: the two paplan contracts catch seeded regressions
# (verifier-level negatives live in tests/test_plan_verifier.py)
# ---------------------------------------------------------------------------


def test_plan_soundness_contract_catches_seeded_audit_defect():
    """A case whose plan audit reports ANY defect must trip the
    plan-soundness contract; a clean audit must not."""
    clean = {"name": "probe", "tags": {}, "plan_audit": {
        "kind": "device-box",
        "plans": {"host-exchanger": [], "device-box": []},
        "n_defects": 0,
    }}
    ok = check_contracts({}, {"probe": clean})
    assert not [v for v in ok if v.contract == "plan-soundness"]
    seeded = {"name": "probe", "tags": {}, "plan_audit": {
        "kind": "device-box",
        "plans": {"host-exchanger": [], "device-box": [{
            "check": "ghost-race", "plan": "device-box", "part": 2,
            "message": "overlapping segment slot", "details": {},
        }]},
        "n_defects": 1,
    }}
    bad = check_contracts({}, {"probe": seeded})
    hits = [v for v in bad if v.contract == "plan-soundness"]
    assert hits and "ghost-race" in hits[0].message


def test_memory_budget_contract_catches_growth_and_missing_budget(
    monkeypatch,
):
    """A footprint past its pinned budget must trip memory-budget; at
    the budget it must not; and a matrix case with NO pinned budget
    fails loudly (the new-case discipline)."""
    from partitionedarrays_jl_tpu.analysis.memory_report import (
        MEMORY_BUDGETS,
    )

    fp = {"carry_bytes": 100, "plan_bytes": 10, "operand_bytes": 300,
          "peak_bytes": 500, "peak_source": "shape-sum"}
    case = {"name": "probe", "tags": {}, "memory": dict(fp)}
    monkeypatch.setitem(MEMORY_BUDGETS, "probe", 499)
    bad = check_contracts({}, {"probe": case})
    assert [v for v in bad if v.contract == "memory-budget"]
    monkeypatch.setitem(MEMORY_BUDGETS, "probe", 500)
    ok = check_contracts({}, {"probe": case})
    assert not [v for v in ok if v.contract == "memory-budget"]
    unbudgeted = {"name": "newcase", "tags": {}, "memory": dict(fp)}
    bad = check_contracts({}, {"newcase": unbudgeted})
    hits = [v for v in bad if v.contract == "memory-budget"]
    assert hits and "no pinned" in hits[0].message


# ---------------------------------------------------------------------------
# the CLI gate
# ---------------------------------------------------------------------------


def test_palint_cli_lint_only_green():
    # lint-only leg stays jax-free and fast; the plan-soundness leg's
    # CLI path is exercised in-process by tests/test_plan_verifier.py
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "palint.py"),
         "--check", "--skip-matrix", "--skip-plans"],
        capture_output=True, text=True, timeout=240,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "palint: OK" in out.stdout


def test_palint_cli_exits_nonzero_on_violation(monkeypatch):
    """--check must exit nonzero and print the human-readable diff when
    a contract/lint violation exists (seeded: a stale exemption)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "palint", os.path.join(REPO, "tools", "palint.py")
    )
    palint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(palint)
    monkeypatch.setitem(
        env_lint.NON_LOWERING, "PA_TPU_NEVER_READ",
        "a stale exemption the lint must flag as no longer read",
    )
    rc = palint.main(["--check", "--skip-matrix", "--skip-plans"])
    assert rc == 1
