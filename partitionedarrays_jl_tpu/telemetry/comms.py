"""Static-vs-measured comms accounting for the compiled CG programs.

Two independent derivations of "what goes on the wire per solve", kept
honest against each other (cf. arXiv:1612.08060 — node-aware SpMV is
argued entirely in expected-vs-observed bytes-on-the-wire terms, and
the adaptive-collectives line of work assumes plans can report what
they actually moved):

* **Measured (runtime accounting)** — `cg_comms_profile` builds, from
  the host-side plan objects alone (exchange plan rounds and slab
  sizes, dot-gather lane structure, body form), the per-iteration and
  setup collective inventory of a compiled CG body; a finished solve
  then reports ``observed = setup + per_iteration x iterations``
  (`observed_comms`, stamped into the `SolveRecord`). This is the
  *model* of the program the plan thinks it lowered to.
* **Static (program truth)** — `expected_from_report` reads the SAME
  split out of the lowered StableHLO text (`analysis.program_report`):
  collectives inside the solve's ``while`` region are per-iteration,
  the rest are setup.

`reconcile` compares the two at a solve's actual iteration count —
op counts AND payload bytes, per collective kind. A mismatch means the
plan-level model and the lowered program disagree about the wire
(exactly the drift class the palint runtime contract pins across the
lowering matrix). Byte totals are PER-DEVICE result-tensor bytes, the
same accounting `ProgramReport` does.
"""
from __future__ import annotations

from typing import Dict, Optional

__all__ = [
    "COMM_KINDS",
    "cg_comms_profile",
    "observed_comms",
    "expected_from_report",
    "reconcile",
]

#: The kinds this accounting speaks about (the program-report family).
COMM_KINDS = (
    "all_gather",
    "collective_permute",
    "all_reduce",
    "reduce_scatter",
)


def _zero() -> Dict[str, Dict[str, int]]:
    return {k: {"ops": 0, "bytes": 0} for k in COMM_KINDS}


def _add(tbl, kind: str, ops: int, nbytes: int) -> None:
    tbl[kind]["ops"] += int(ops)
    tbl[kind]["bytes"] += int(nbytes)


def _exchange_inventory(dA, abft: bool, K: int, itemsize: int):
    """(ops, bytes) of ONE halo update ('set' combine) of the matrix's
    column plan: the generic index plan runs R `ppermute` rounds of the
    padded max-edge slab (ABFT: one checksum slot wider); the box plan
    runs one `ppermute` per geometric direction, each shipping that
    direction's segment slab."""
    from ..parallel.tpu_box import BoxExchangePlan

    plan = dA.col_plan
    if isinstance(plan, BoxExchangePlan):
        sizes = [d.size for d in plan.info.dirs]
    else:
        if plan.R == 0:
            return 0, 0
        slot = plan.snd_idx.shape[-1] + (1 if abft else 0)
        sizes = [slot] * plan.R
    return len(sizes), sum(s * K * itemsize for s in sizes)


def cg_comms_profile(
    dA,
    dtype,
    precond: bool = False,
    fused: bool = False,
    rhs_batch: Optional[int] = None,
    sdc: bool = False,
    abft: bool = False,
) -> dict:
    """The plan-level collective inventory of one compiled CG body:
    ``{"setup": {kind: {ops, bytes}}, "per_iteration": {...}}``.

    Derivation (mirrors the bodies in `parallel.tpu.make_cg_fn` /
    `make_block_cg_fn` — the palint runtime contract pins the mirror):

    * every SpMV runs exactly one halo update (`_exchange_inventory`);
    * each deterministic dot is ONE `all_gather` of the per-part
      partial: scalar partials gather ``(P,)`` payloads, the fused
      preconditioned pair and the block column-stacks widen the SAME
      gather to ``(P, 2)`` / ``(P, K)`` / ``(P, K, 2)``;
    * the SDC-defended bodies route the p·q dot through the extra-lane
      gather (`_pdot_extra_factory`): ABFT adds two checksum lanes to
      that one payload, never an op.
    """
    import numpy as np

    itemsize = int(np.dtype(dtype).itemsize)
    P = dA.row_layout.P
    K = int(rhs_batch) if rhs_batch else 1
    block = rhs_batch is not None

    ex_ops, ex_bytes = _exchange_inventory(dA, abft, K, itemsize)

    def ag(tbl, lanes: int) -> None:
        # one all_gather of a (lanes,)-per-column partial: result is
        # (P,) / (P, K) for one lane, (P, 2) / (P, K, 2) for two, ...
        _add(tbl, "all_gather", 1, P * K * lanes * itemsize)

    def exchange(tbl) -> None:
        _add(tbl, "collective_permute", ex_ops, ex_bytes)

    setup = _zero()
    per_it = _zero()

    # ---- setup: initial residual SpMV + rs0 (+ rz0 when precond) ----
    exchange(setup)
    ag(setup, 1)
    if precond:
        ag(setup, 1)

    # ---- one iteration ----
    exchange(per_it)  # the body's one SpMV call site
    if sdc:
        ag(per_it, 1 + (2 if abft else 0))  # p·q via the extra-lane dot
        if fused or block:
            ag(per_it, 2 if precond else 1)  # fused one-sweep dot pair
        else:
            ag(per_it, 1)  # r·r
            if precond:
                ag(per_it, 1)  # r·z
    elif fused or block:
        ag(per_it, 1)  # p·q
        ag(per_it, 2 if precond else 1)  # rs (+ rz) on one gather
    else:
        ag(per_it, 1)  # p·q
        ag(per_it, 1)  # r·r
        if precond:
            ag(per_it, 1)  # r·z
    return {"setup": setup, "per_iteration": per_it}


def observed_comms(profile: dict, iterations: int) -> dict:
    """The runtime accounting of one finished solve: the profile
    evaluated at the solve's actual iteration count."""
    it = int(iterations)
    obs = _zero()
    for k in COMM_KINDS:
        obs[k]["ops"] = (
            profile["setup"][k]["ops"]
            + profile["per_iteration"][k]["ops"] * it
        )
        obs[k]["bytes"] = (
            profile["setup"][k]["bytes"]
            + profile["per_iteration"][k]["bytes"] * it
        )
    return {
        "iterations": it,
        "setup": profile["setup"],
        "per_iteration": profile["per_iteration"],
        "observed": obs,
    }


def expected_from_report(report) -> dict:
    """The static split of a lowered program's collectives into
    per-iteration (inside the solve ``while`` region) and setup (the
    rest), ops and bytes per kind. StableHLO reports only — the
    pre-optimization dialect is where counting is stable."""
    from ..analysis.program_report import analyze_text

    loop = _zero()
    for w in report.while_loops:
        if not w.region_text:
            continue
        sub = analyze_text(w.region_text)
        for k in COMM_KINDS:
            _add(loop, k, sub.collectives.get(k, 0),
                 sub.collective_bytes.get(k, 0))
    setup = _zero()
    for k in COMM_KINDS:
        setup[k]["ops"] = report.collectives.get(k, 0) - loop[k]["ops"]
        setup[k]["bytes"] = (
            report.collective_bytes.get(k, 0) - loop[k]["bytes"]
        )
    return {"setup": setup, "per_iteration": loop}


def reconcile(report, comms: dict) -> list:
    """Cross-check a solve's runtime accounting (``comms`` — the
    `observed_comms` structure stamped into its SolveRecord) against the
    lowered program's static expectation, at the solve's iteration
    count. Returns human-readable mismatch strings (empty = agree)."""
    exp = expected_from_report(report)
    it = int(comms["iterations"])
    out = []
    for k in COMM_KINDS:
        for field in ("ops", "bytes"):
            want = (
                exp["setup"][k][field]
                + exp["per_iteration"][k][field] * it
            )
            got = comms["observed"][k][field]
            if want != got:
                out.append(
                    f"{k}.{field}: static expectation {want} "
                    f"(setup {exp['setup'][k][field]} + "
                    f"{exp['per_iteration'][k][field]}/it x {it} it) != "
                    f"measured accounting {got}"
                )
    return out
