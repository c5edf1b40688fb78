"""The program-contract registry: structural invariants of the lowered
solver bodies as declarative objects.

Each `Contract` inspects the `ProgramReport`s of the lowering matrix
(`parallel.tpu.lowering_matrix`) and returns `Violation`s. The
invariants here are the ones the test tree used to assert ad hoc —
PR 3's K-independence, PR 4's ABFT collective parity — plus the two
regression canaries for bug classes this repo has actually shipped
fixes for:

* **dtype closure** (the PR 3 f64-poisoning class: an empty-receiver
  Table exchange allocated f64 into an f32-staged GMG hierarchy) — an
  f32-staged program must lower with NO f64 op anywhere;
* **copy budget** (the PR 2 buffer-copy-anomaly class: XLA's while-loop
  carry copies spiked 2–3× in the 292³–300³ window until the packed
  (3, W) carry sidestepped them) — the compiled (optimized-HLO) body
  may not grow its ``copy`` op count past a pinned budget.

Contracts compare COUNTS and STRUCTURE, never timings — they are
deterministic, platform-independent, and cheap enough for CI.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from .program_report import (
    COLLECTIVE_KINDS,
    _SPMD_CUSTOM_CALLS,
    ProgramReport,
)

#: Compiled-HLO ``copy`` op budgets per matrix case (measured on the
#: fixed (6,6,6)/(2,2,2) probe; headroom ≈ 2× so routine XLA version
#: drift passes but a PR 2-class regression — copies scaling with the
#: carry count — trips loudly). Budgets exist only for the cases palint
#: compiles; lowered-only cases have no ``copy`` ops to budget.
COPY_BUDGETS: Dict[str, int] = {
    "standard": 40,
    "fused": 24,
}


@dataclass
class Violation:
    contract: str
    cases: List[str]
    message: str
    expected: object = None
    found: object = None

    def __str__(self):
        s = f"[{self.contract}] {'/'.join(self.cases)}: {self.message}"
        if self.expected is not None or self.found is not None:
            s += f"\n    expected: {self.expected}\n    found:    {self.found}"
        return s


@dataclass
class Contract:
    """One declarative invariant over the lowering matrix.

    ``check(reports, cases)`` gets every report keyed by case name
    (compiled-HLO reports under ``<name>__compiled``) plus the case
    descriptors, and returns violations. A contract must SKIP silently
    when the cases it speaks about are absent from the build (the fast
    tier-1 subset lowers fewer cases than palint's full matrix).
    """

    name: str
    description: str
    check: Callable[
        [Dict[str, ProgramReport], Dict[str, dict]], List[Violation]
    ] = field(repr=False, default=None)


def _counts(rep: ProgramReport) -> Dict[str, int]:
    return {k: rep.collectives.get(k, 0) for k in COLLECTIVE_KINDS}


def _check_sanity(reports, cases):
    """The parser-rot guard: if the analyzer stopped seeing collectives
    at all, every equality contract would pass vacuously — so the
    baseline program must show a nonzero inventory and its while loop."""
    out = []
    rep = reports.get("standard")
    if rep is None:
        return out
    if not any(_counts(rep).values()):
        out.append(Violation(
            "sanity", ["standard"],
            "baseline program shows NO collectives — analyzer rot or a "
            "broken lowering", found=_counts(rep),
        ))
    if rep.dialect == "stablehlo":
        if not rep.while_loops:
            out.append(Violation(
                "sanity", ["standard"],
                "baseline program shows no while loop — the CG body did "
                "not lower as one compiled loop",
            ))
        elif not any(
            f"stablehlo.{k}" in w.region_text
            for w in rep.while_loops for k in COLLECTIVE_KINDS
        ):
            # region capture itself can rot (printer format drift would
            # truncate the body and let the loop-residency contract pass
            # vacuously) — the solve loop's body MUST show its halo/dot
            # collectives
            out.append(Violation(
                "sanity", ["standard"],
                "no collective inside any captured while region — region "
                "capture truncated (printer drift?) or the loop lost its "
                "halo exchange",
            ))
    return out


def _check_abft_parity(reports, cases):
    """PR 4's acceptance invariant: ABFT detection rides WIDENED
    payloads (checksum lanes on the dot gather, one slot per exchange
    round) — per-kind collective counts identical ON vs OFF."""
    out = []
    for name, case in cases.items():
        off_name = case.get("tags", {}).get("abft_off")
        if not off_name or name not in reports or off_name not in reports:
            continue
        con, coff = _counts(reports[name]), _counts(reports[off_name])
        if con != coff:
            out.append(Violation(
                "abft-collective-parity", [name, off_name],
                "ABFT-on program changes per-kind collective counts — "
                "detection must ride existing collectives, never add one",
                expected=coff, found=con,
            ))
    return out


def _check_k_independence(reports, cases):
    """PR 3's acceptance invariant: the block program's per-iteration
    collective count is K-independent (dot payloads widen to (K,)/(K,2)
    stacks on the SAME gathers; halo rounds ship (…, K) slabs)."""
    out = []
    by_body: Dict[str, List[str]] = {}
    for name, case in cases.items():
        tags = case.get("tags", {})
        if tags.get("body") == "block" and name in reports and (
            "plan" not in tags and "abft" not in tags
        ):
            by_body.setdefault(tags.get("block_of", "?"), []).append(name)
    for body, names in by_body.items():
        names = sorted(names, key=lambda n: cases[n]["tags"].get("K", 0))
        if len(names) < 2:
            continue
        base = _counts(reports[names[0]])
        for other in names[1:]:
            oc = _counts(reports[other])
            if oc != base:
                out.append(Violation(
                    "k-independence", [names[0], other],
                    f"block-{body} collective counts depend on K",
                    expected=base, found=oc,
                ))
    return out


def _check_block_le_solo(reports, cases):
    """The K=1 block program must not pay MORE collectives than the
    solo program of the same body — widening payloads is free, extra
    rounds are not."""
    out = []
    for name, case in cases.items():
        tags = case.get("tags", {})
        if tags.get("body") != "block" or tags.get("K") != 1:
            continue
        solo = tags.get("block_of")
        if name not in reports or solo not in reports:
            continue
        cb, cs = _counts(reports[name]), _counts(reports[solo])
        for kind in COLLECTIVE_KINDS:
            if cb[kind] > cs[kind]:
                out.append(Violation(
                    "block-le-solo", [name, solo],
                    f"K=1 block program pays more {kind} than the solo "
                    f"{solo} body",
                    expected=f"<= {cs[kind]}", found=cb[kind],
                ))
    return out


def _check_fused_no_extra(reports, cases):
    """PR 2's acceptance invariant: the fused body restructures VECTOR
    sweeps — it must not add collectives over the standard body."""
    out = []
    if "standard" not in reports or "fused" not in reports:
        return out
    cu, cf = _counts(reports["standard"]), _counts(reports["fused"])
    for kind in COLLECTIVE_KINDS:
        if cf[kind] > cu[kind]:
            out.append(Violation(
                "fused-no-extra-collectives", ["fused", "standard"],
                f"fused body pays more {kind} than the standard body",
                expected=f"<= {cu[kind]}", found=cf[kind],
            ))
    return out


def _check_dtype_closure(reports, cases):
    """The PR 3 f64-poisoning canary: an f32-staged program must lower
    CLOSED over f32 — any f64 tensor anywhere in it is exactly the
    class of silent upcast that poisoned the f32 GMG hierarchy (an
    empty-receiver exchange allocating in the default dtype)."""
    out = []
    for name, case in cases.items():
        if case.get("tags", {}).get("staged") != "f32":
            continue
        for rname in (name, name + "__compiled"):
            rep = reports.get(rname)
            if rep is None:
                continue
            if "f64" in rep.float_dtypes:
                lines = rep.f64_lines[:8]
                out.append(Violation(
                    "dtype-closure", [rname],
                    "f32-staged program contains f64 ops (the PR 3 "
                    f"poisoning class) — first hits at lines {lines}",
                    expected="no f64 tensor in the lowering",
                    found=f"f64 on {len(rep.f64_lines)} lines",
                ))
    return out


def _check_no_host_transfer_in_loop(reports, cases):
    """The solve loop must be device-resident: no infeed/outfeed or
    non-SPMD custom-call inside any while region (a host round-trip per
    iteration is a 1000× iteration-latency regression on a real TPU)."""
    out = []
    for name, rep in reports.items():
        if rep.dialect != "stablehlo":
            continue
        for w in rep.while_loops:
            bad = []
            for marker in ("stablehlo.infeed", "stablehlo.outfeed"):
                if marker in w.region_text:
                    bad.append(marker)
            for m in re.finditer(r"custom_call\s+@(\w+)", w.region_text):
                if m.group(1) not in _SPMD_CUSTOM_CALLS:
                    bad.append(f"custom_call @{m.group(1)}")
            if bad:
                out.append(Violation(
                    "no-host-transfer-in-loop", [name],
                    f"while loop at line {w.line} contains host-transfer "
                    "ops — the solve loop must stay device-resident",
                    expected="none", found=bad,
                ))
    return out


def _check_runtime_reconciliation(reports, cases):
    """The telemetry tentpole's contract: a finished solve's runtime
    comms accounting (``setup + per_iteration x iterations``, built
    from the PLAN objects — telemetry.comms.cg_comms_profile) must
    equal, per collective kind in both ops and payload bytes, what the
    lowered program statically implies (collectives inside the solve's
    while region are per-iteration, the rest setup). Cases carry their
    measured accounting under ``runtime_comms`` when the matrix was
    built with runtime probes (`analysis.matrix.build_reports(
    with_runtime=True)`); absent probes, the contract skips silently
    like every other."""
    from ..telemetry.comms import reconcile

    out = []
    for name, case in cases.items():
        comms = case.get("runtime_comms")
        rep = reports.get(name)
        if comms is None or rep is None or rep.dialect != "stablehlo":
            continue
        for msg in reconcile(rep, comms):
            out.append(Violation(
                "static-measured-reconciliation", [name],
                "runtime comms accounting disagrees with the lowered "
                "program: " + msg,
            ))
    return out


def _check_plan_soundness(reports, cases):
    """The paplan tentpole's contract: every plan a case's program is
    lowered from — the host column `Exchanger` plus the device plan
    (box under the default env, generic under the nobox/strict/ABFT
    envs) — must verify SOUND against the probe operator's sparsity
    (analysis.plan_verifier: symmetry, ghost-race, coverage,
    dead-slot, rounds). Cases carry their verification results under
    ``plan_audit`` when the matrix was built with plan audits
    (`analysis.matrix.build_reports(with_plans=True)`); absent audits,
    the contract skips silently like every other."""
    out = []
    for name, case in cases.items():
        audit = case.get("plan_audit")
        if audit is None:
            continue
        for plan_name, defects in sorted(audit["plans"].items()):
            if defects:
                first = defects[0]
                out.append(Violation(
                    "plan-soundness", [name],
                    f"{plan_name} plan fails static soundness "
                    f"verification ({len(defects)} defect(s)); first: "
                    f"[{first['check']}] {first['message']}",
                    expected="no plan defects",
                    found=[f"[{d['check']}] part {d['part']}"
                           for d in defects[:6]],
                ))
    return out


def _check_memory_budget(reports, cases):
    """The memory tentpole's contract: each case's STATIC peak
    footprint (analysis.memory_report — compiled buffer assignment
    where a compiled leg exists, conservative shape-sum otherwise)
    stays under its pinned probe-scale budget, and every matrix case
    HAS a pinned budget (a new case without one fails loudly, the
    same discipline the env lint applies to new flags). Skips
    silently when footprints were not attached
    (`build_reports(with_memory=True)`)."""
    from .memory_report import MEMORY_BUDGETS

    out = []
    for name, case in cases.items():
        fp = case.get("memory")
        if fp is None:
            continue
        budget = MEMORY_BUDGETS.get(name)
        if budget is None:
            out.append(Violation(
                "memory-budget", [name],
                "matrix case has no pinned static-memory budget — add "
                "it to analysis.memory_report.MEMORY_BUDGETS and "
                "regenerate MEMORY_FOOTPRINT.json",
                expected="a MEMORY_BUDGETS entry", found=None,
            ))
        elif fp["peak_bytes"] > budget:
            out.append(Violation(
                "memory-budget", [name],
                "static peak footprint blew its pinned budget (source: "
                f"{fp['peak_source']})",
                expected=f"<= {budget} B", found=f"{fp['peak_bytes']} B",
            ))
    return out


def _check_copy_budget(reports, cases):
    """The PR 2 buffer-copy canary: the compiled body's ``copy`` count
    is the structural signature of XLA's while-carry copies — the
    anomaly class that cost 2–3× in the 292³–300³ window. Budgets are
    pinned per body with ~2× headroom; a body whose copies jump past
    its budget regressed structurally even if today's wall-clock looks
    fine."""
    out = []
    for name, budget in COPY_BUDGETS.items():
        rep = reports.get(name + "__compiled")
        if rep is None:
            continue
        if rep.copies > budget:
            out.append(Violation(
                "copy-budget", [name],
                "compiled program's copy-op count blew its budget (the "
                "PR 2 buffer-copy-anomaly canary)",
                expected=f"<= {budget}", found=rep.copies,
            ))
    return out


def _check_concurrency_soundness(reports, cases):
    """The palock tentpole's lock half, run over the package SOURCE
    (not the lowered reports — the threaded service stack never
    lowers): unguarded shared access, lock-order cycles, blocking
    calls under a lock, manual acquire without try/finally, and
    leaked threads, with guarded-by inference seeing through
    "callers hold self._lock" helper indirection. The lock model is
    stat-signature cached, so re-running here is cheap."""
    from .concurrency_lint import lint_concurrency

    findings = lint_concurrency(checks=[
        "unguarded-shared-access",
        "lock-order-cycle",
        "blocking-under-lock",
        "manual-acquire",
        "leaked-thread",
    ])
    return [
        Violation("concurrency-soundness", [], msg) for msg in findings
    ]


def _check_durability_ordering(reports, cases):
    """The palock tentpole's write-ahead half: every client-visible
    ack in a journal-acked transition is DOMINATED (branch-aware, on
    every path) by its fsync'd journal append (`DURABILITY_RULES`),
    and the journal-mask bypass accessor stays private to
    frontdoor/scheduler.py. A seeded ack-before-append mutant fails
    this contract (tests/fixtures/palock/ack_before_append)."""
    from .concurrency_lint import lint_concurrency

    findings = lint_concurrency(checks=["durability-ordering"])
    return [
        Violation("durability-ordering", [], msg) for msg in findings
    ]


CONTRACTS: List[Contract] = [
    Contract("sanity",
             "baseline program shows collectives and a while loop "
             "(guards the analyzer itself against parser rot)",
             _check_sanity),
    Contract("abft-collective-parity",
             "per-kind collective counts identical ABFT on vs off "
             "(detection rides widened payloads — PR 4)",
             _check_abft_parity),
    Contract("k-independence",
             "block-CG per-iteration collective counts independent of K "
             "(payloads widen, rounds don't — PR 3)",
             _check_k_independence),
    Contract("block-le-solo",
             "K=1 block program pays no more collectives than the solo "
             "body (PR 3)",
             _check_block_le_solo),
    Contract("fused-no-extra-collectives",
             "fused body adds no collectives over the standard body "
             "(PR 2)",
             _check_fused_no_extra),
    Contract("dtype-closure",
             "f32-staged programs lower with zero f64 ops (the PR 3 "
             "f64-poisoning class)",
             _check_dtype_closure),
    Contract("no-host-transfer-in-loop",
             "no infeed/outfeed/non-SPMD custom-call inside any while "
             "region",
             _check_no_host_transfer_in_loop),
    Contract("copy-budget",
             "compiled copy-op count within the pinned per-body budget "
             "(the PR 2 buffer-copy-anomaly canary)",
             _check_copy_budget),
    Contract("static-measured-reconciliation",
             "runtime comms accounting (plan-model x iterations) equals "
             "the lowered program's static per-kind collective ops and "
             "bytes (the patrace tentpole)",
             _check_runtime_reconciliation),
    Contract("plan-soundness",
             "every plan a case lowers from (host Exchanger + device "
             "box/generic plan) verifies statically sound against the "
             "probe operator's sparsity (the paplan tentpole)",
             _check_plan_soundness),
    Contract("memory-budget",
             "per-case static peak footprint (compiled buffer "
             "assignment or conservative shape-sum) within its pinned "
             "budget; every case budgeted (the paplan tentpole)",
             _check_memory_budget),
    Contract("concurrency-soundness",
             "source-level lock soundness: no unguarded shared access, "
             "no lock-order cycle, no unwaivered blocking call under a "
             "lock, no bare acquire, no leaked thread (the palock "
             "tentpole)",
             _check_concurrency_soundness),
    Contract("durability-ordering",
             "every journal-acked transition's fsync'd append dominates "
             "its client-visible ack on every path — the PR 12 "
             "write-ahead invariant, proven statically (the palock "
             "tentpole)",
             _check_durability_ordering),
]


def contract_by_name(name: str) -> Optional[Contract]:
    for c in CONTRACTS:
        if c.name == name:
            return c
    return None


def check_contracts(
    reports: Dict[str, ProgramReport],
    cases: Dict[str, dict],
    contracts: Optional[List[Contract]] = None,
) -> List[Violation]:
    """Run every contract against the built reports; returns all
    violations (empty = the lowering matrix honors its contracts)."""
    out: List[Violation] = []
    for c in contracts or CONTRACTS:
        out.extend(c.check(reports, cases))
    return out
