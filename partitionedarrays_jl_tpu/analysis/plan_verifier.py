"""paplan — static soundness verification of exchange PLANS.

palint (analysis/contracts.py) proves properties of the lowered
PROGRAM; this module proves properties of the PLAN the program is
lowered from. The gap matters: a malformed exchange plan — overlapping
ghost writes, an uncovered off-part column, an asymmetric or
non-bijective ppermute round — lowers cleanly, passes every HLO
contract, and only surfaces as a wrong answer or a hang at runtime
(the host `ufunc.at` unpack even ACCUMULATES colliding writes
silently). Both exchange-plan papers this repo builds on treat the
plan as the first-class artifact whose structure must stay sound as
topology and sparsity change (Node-Aware SpMV, arXiv:1612.08060; the
adaptive space-efficient collectives work, arXiv:2607.04676) — and
ROADMAP items 3/4 (node-aware two-level plans, incremental re-plan)
are about to start mutating exactly these structures.

Five check classes over any constructed plan — the host `Exchanger`,
the generic index plan (`parallel.tpu.DeviceExchangePlan`), and the
slice plan (`parallel.tpu_box.BoxExchangePlan`):

* ``symmetry`` — part i's slots to j match part j's slots from i in
  count (and both directions exist): an asymmetric edge is a receiver
  waiting forever (deadlock) or a sender shipping into nothing.
* ``ghost-race`` — destination indices within each part's receive
  region are IN-RANGE and DISJOINT across sources: two sources
  writing one ghost slot is the write-race class the `.at[].set`
  scatter resolves arbitrarily and `ufunc.at` accumulation tolerates
  silently.
* ``coverage`` — every off-part column the operator's sparsity
  references is covered by a plan slot (a dropped slot = a stale
  ghost read every iteration).
* ``dead-slot`` — no slot delivers data nothing reads (given the
  operator's referenced-ghost set): dead slots are wasted wire bytes
  and the signature of a plan diverging from its sparsity.
* ``rounds`` — every wire round is a SELF-SEND-FREE partial
  permutation over participating parts (unique senders, unique
  receivers, no p→p edge, no edge delivered twice across rounds):
  the validity condition for one `ppermute` per round, and the
  static deadlock-freedom argument for the round schedule.

`verify_plan` returns `PlanDefect`s (empty = sound); `check_plan`
raises the typed `PlanSoundnessError` (parallel.health family) with
the failing check + part/slot diagnostics. ``PA_PLAN_VERIFY=1`` runs
`check_plan` at the three plan BUILD sites (Exchanger construction,
the generic device plan, the box plan) — off by default so the hot
path pays nothing.

Verification is pure host-side numpy over plan metadata; nothing here
touches jax or changes any plan.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

__all__ = [
    "PLAN_CHECKS",
    "PlanDefect",
    "PartSpec",
    "audit_case",
    "canonical_exchange_fingerprint",
    "check_plan",
    "exchanger_fixture",
    "load_exchanger_fixture",
    "plan_fingerprint",
    "plan_verify_enabled",
    "plans_equal",
    "referenced_ghosts",
    "verify_box_plan",
    "verify_device_plan",
    "verify_exchanger",
    "verify_plan",
]

#: The check classes, in report order. Each has a committed negative
#: fixture (tests/fixtures/paplan/) proving the verifier catches it.
PLAN_CHECKS = ("symmetry", "ghost-race", "coverage", "dead-slot", "rounds")


def plan_verify_enabled() -> bool:
    """``PA_PLAN_VERIFY=1``: verify plans AT CONSTRUCTION and raise
    `PlanSoundnessError` on any defect. Off by default — the verifier
    walks every edge of the neighbor graph, which is pure host-side
    setup cost but not free at scale."""
    return os.environ.get("PA_PLAN_VERIFY", "0") != "0"


@dataclass
class PlanDefect:
    """One soundness violation: which check, where, and the slots."""

    check: str  # one of PLAN_CHECKS
    plan: str  # which plan object ("exchanger", "device-generic", ...)
    part: Optional[int]
    message: str
    details: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "check": self.check, "plan": self.plan, "part": self.part,
            "message": self.message, "details": self.details,
        }

    def __str__(self):
        where = f"part {self.part}" if self.part is not None else "plan"
        return f"[{self.check}] {self.plan} {where}: {self.message}"


@dataclass
class PartSpec:
    """The minimal per-part layout the host verifier needs — what a
    real `AbstractIndexSet` exposes, reduced to three fields so the
    committed negative fixtures can serialize a partition without the
    full index-set machinery."""

    num_lids: int
    num_oids: int
    lid_to_ohid: np.ndarray  # signed: oid >= 0, ghost -> -(hid+1)

    @property
    def num_hids(self) -> int:
        return self.num_lids - self.num_oids


def _part_values(x) -> list:
    return x.part_values() if hasattr(x, "part_values") else list(x)


def referenced_ghosts(A) -> List[np.ndarray]:
    """Per-part boolean mask over hids: which ghost columns the
    operator's sparsity actually reads (the coverage/dead-slot
    oracle). Derived from the local CSR column lids of a
    `PSparseMatrix` through the column partition's signed
    ``lid_to_ohid`` map."""
    out = []
    for iset, csr in zip(
        _part_values(A.cols.partition), _part_values(A.values)
    ):
        ohid = np.asarray(iset.lid_to_ohid)
        mask = np.zeros(int(iset.num_hids), dtype=bool)
        lids = np.unique(np.asarray(csr.indices))
        if lids.size:
            oh = ohid[lids]
            mask[-oh[oh < 0] - 1] = True
        out.append(mask)
    return out


def _all_hids_referenced(parts) -> List[np.ndarray]:
    return [np.ones(int(i.num_hids), dtype=bool) for i in parts]


# ---------------------------------------------------------------------------
# host Exchanger
# ---------------------------------------------------------------------------


def verify_exchanger(
    exchanger,
    parts: Sequence,
    referenced: Optional[Sequence[np.ndarray]] = None,
    name: str = "exchanger",
) -> List[PlanDefect]:
    """Verify a host `Exchanger` (forward owner→ghost orientation)
    against the per-part layout ``parts`` (index sets or `PartSpec`s)
    and the operator's ``referenced`` ghost masks (default: every
    ghost is referenced — the PRange contract, since ghosts exist
    because some column asked for them)."""
    parts = _part_values(parts)
    P = len(parts)
    if referenced is None:
        referenced = _all_hids_referenced(parts)
    out: List[PlanDefect] = []
    parts_snd = [np.asarray(t) for t in _part_values(exchanger.parts_snd)]
    parts_rcv = [np.asarray(t) for t in _part_values(exchanger.parts_rcv)]
    lids_snd = _part_values(exchanger.lids_snd)
    lids_rcv = _part_values(exchanger.lids_rcv)

    def _neighbor_list_ok(arr, p, which):
        ok = True
        if arr.size and arr.dtype.kind not in "iu":
            out.append(PlanDefect(
                "symmetry", name, p,
                f"{which} neighbor list has non-integer dtype {arr.dtype}",
            ))
            ok = False
        if ((arr < 0) | (arr >= P)).any():
            out.append(PlanDefect(
                "symmetry", name, p,
                f"{which} names out-of-range part(s) "
                f"{sorted(set(arr[(arr < 0) | (arr >= P)].tolist()))} "
                f"(P={P})",
            ))
            ok = False
        if (arr == p).any():
            out.append(PlanDefect(
                "rounds", name, p,
                f"self-send: part {p} lists itself in {which} — no wire "
                "round can realize a p→p edge",
            ))
            ok = False
        if len(np.unique(arr)) != len(arr):
            out.append(PlanDefect(
                "symmetry", name, p,
                f"duplicate neighbor in {which} (edges must be unique)",
            ))
            ok = False
        return ok

    edges_ok = True
    for p in range(P):
        edges_ok &= _neighbor_list_ok(parts_snd[p], p, "parts_snd")
        edges_ok &= _neighbor_list_ok(parts_rcv[p], p, "parts_rcv")
    if not edges_ok:
        return out  # slot checks below index by neighbor — stop here

    # --- symmetry: the two directed edge maps must agree ----------------
    snd_count: Dict[tuple, int] = {}
    for p in range(P):
        for j, q in enumerate(parts_snd[p]):
            snd_count[(p, int(q))] = lids_snd[p].row_length(j)
    rcv_count: Dict[tuple, int] = {}
    for q in range(P):
        for i, p in enumerate(parts_rcv[q]):
            rcv_count[(int(p), q)] = lids_rcv[q].row_length(i)
    for (p, q), n in sorted(snd_count.items()):
        if (p, q) not in rcv_count:
            out.append(PlanDefect(
                "symmetry", name, q,
                f"part {p} sends {n} slot(s) to part {q}, but {q} has no "
                f"receive edge from {p} — the payload lands nowhere",
                details={"edge": [p, q], "snd": n, "rcv": 0},
            ))
        elif rcv_count[(p, q)] != n:
            out.append(PlanDefect(
                "symmetry", name, q,
                f"asymmetric counts on edge {p}→{q}: sender packs {n} "
                f"slot(s), receiver expects {rcv_count[(p, q)]}",
                details={"edge": [p, q], "snd": n,
                         "rcv": rcv_count[(p, q)]},
            ))
    for (p, q), n in sorted(rcv_count.items()):
        if (p, q) not in snd_count:
            out.append(PlanDefect(
                "symmetry", name, q,
                f"part {q} expects {n} slot(s) from part {p}, but {p} has "
                f"no send edge to {q} — the receiver waits forever",
                details={"edge": [p, q], "snd": 0, "rcv": n},
            ))

    # --- per-part slot checks -------------------------------------------
    for p in range(P):
        iset = parts[p]
        nl, no = int(iset.num_lids), int(iset.num_oids)
        ohid = np.asarray(iset.lid_to_ohid)
        # senders pack OWNED lids
        snd = np.asarray(lids_snd[p].data[: lids_snd[p].ptrs[-1]])
        bad = snd[(snd < 0) | (snd >= nl)]
        if bad.size:
            out.append(PlanDefect(
                "coverage", name, p,
                f"send slot lid(s) out of range: {sorted(set(bad.tolist()))[:8]} "
                f"(num_lids={nl})",
            ))
            snd = snd[(snd >= 0) & (snd < nl)]
        nonowned = snd[ohid[snd] < 0]
        if nonowned.size:
            out.append(PlanDefect(
                "coverage", name, p,
                f"plan packs NON-OWNED lid(s) {sorted(set(nonowned.tolist()))[:8]} "
                "for sending — only owners may source halo data",
            ))
        # receivers land on GHOST lids, in range, disjoint across sources
        rcv = np.asarray(lids_rcv[p].data[: lids_rcv[p].ptrs[-1]])
        bad = rcv[(rcv < 0) | (rcv >= nl)]
        if bad.size:
            out.append(PlanDefect(
                "ghost-race", name, p,
                f"receive destination lid(s) out of range: "
                f"{sorted(set(bad.tolist()))[:8]} (num_lids={nl})",
            ))
            rcv = rcv[(rcv >= 0) & (rcv < nl)]
        owned_dst = rcv[ohid[rcv] >= 0]
        if owned_dst.size:
            out.append(PlanDefect(
                "ghost-race", name, p,
                f"receive destination lid(s) {sorted(set(owned_dst.tolist()))[:8]} "
                "are OWNED — a forward halo plan may only write ghosts",
            ))
        uniq, counts = np.unique(rcv, return_counts=True)
        dup = uniq[counts > 1]
        if dup.size:
            srcs = {}
            for i, q in enumerate(parts_rcv[p]):
                row = np.asarray(lids_rcv[p][i])
                for d in dup.tolist():
                    if (row == d).any():
                        srcs.setdefault(int(d), []).append(int(q))
            out.append(PlanDefect(
                "ghost-race", name, p,
                f"overlapping ghost slot(s): lid(s) {sorted(srcs)[:8]} "
                "written by multiple sources "
                f"{ {k: v for k, v in sorted(srcs.items())[:8]} } — the "
                "unpack scatter resolves the race arbitrarily",
                details={"collisions": {str(k): v for k, v in srcs.items()}},
            ))
        # coverage / dead slots, at hid granularity
        ref = np.asarray(referenced[p], dtype=bool)
        covered = np.zeros(nl - no, dtype=bool)
        ghost_dst = rcv[ohid[rcv] < 0]
        covered[-ohid[ghost_dst] - 1] = True
        missing = np.nonzero(ref & ~covered)[0]
        if missing.size:
            out.append(PlanDefect(
                "coverage", name, p,
                f"dropped slot(s): referenced ghost hid(s) "
                f"{missing.tolist()[:8]} are covered by NO plan slot — "
                "stale reads every exchange",
                details={"missing_hids": missing.tolist()[:64]},
            ))
        dead = np.nonzero(covered & ~ref)[0]
        if dead.size:
            out.append(PlanDefect(
                "dead-slot", name, p,
                f"dead slot(s): ghost hid(s) {dead.tolist()[:8]} receive "
                "data no operator column references",
                details={"dead_hids": dead.tolist()[:64]},
            ))
    return out


# ---------------------------------------------------------------------------
# generic device index plan
# ---------------------------------------------------------------------------


def _verify_rounds(perms, P: int, name: str, out: List[PlanDefect]):
    """Shared round validity: each round a self-send-free partial
    permutation; no edge delivered twice across the schedule."""
    seen_edges = set()
    for r, perm in enumerate(perms):
        senders, receivers = set(), set()
        for src, dst in perm:
            if not (0 <= src < P and 0 <= dst < P):
                out.append(PlanDefect(
                    "rounds", name, None,
                    f"round {r} edge ({src}, {dst}) names an "
                    f"out-of-range part (P={P})",
                ))
                continue
            if src == dst:
                out.append(PlanDefect(
                    "rounds", name, src,
                    f"self-send in round {r}: edge ({src}, {dst}) — a "
                    "ppermute round must be self-send-free",
                    details={"round": r},
                ))
            if src in senders:
                out.append(PlanDefect(
                    "rounds", name, src,
                    f"round {r} is not a partial permutation: part {src} "
                    "sends twice in one round",
                    details={"round": r},
                ))
            if dst in receivers:
                out.append(PlanDefect(
                    "rounds", name, dst,
                    f"round {r} is not a partial permutation: part {dst} "
                    "receives twice in one round",
                    details={"round": r},
                ))
            senders.add(src)
            receivers.add(dst)
            if (src, dst) in seen_edges:
                out.append(PlanDefect(
                    "rounds", name, dst,
                    f"edge ({src}, {dst}) scheduled in more than one "
                    "round — double delivery",
                    details={"round": r},
                ))
            seen_edges.add((src, dst))
    return seen_edges


def verify_device_plan(
    plan,
    referenced: Optional[Sequence[np.ndarray]] = None,
    name: str = "device-generic",
) -> List[PlanDefect]:
    """Verify a generic `DeviceExchangePlan` (forward orientation):
    round validity over ``perms``, per-round count symmetry between
    the send masks and the non-trash receive slots, receive-slot
    race freedom/range inside the ghost region, and hid-slot
    coverage against the layout's ``hid_slots`` maps."""
    out: List[PlanDefect] = []
    layout = plan.layout
    P, trash, g0, o0 = layout.P, layout.trash, layout.g0, layout.o0
    if referenced is None:
        referenced = [
            np.ones(int(n), dtype=bool) for n in layout.nhids
        ]
    _verify_rounds(plan.perms, P, name, out)

    R = len(plan.perms)
    for r in range(R):
        perm = plan.perms[r]
        senders = {s: d for s, d in perm}
        receivers = {d: s for s, d in perm}
        for p in range(P):
            k_snd = int(plan.snd_mask[p, r].sum())
            k_rcv = int((plan.rcv_idx[p, r] != trash).sum())
            if k_snd and p not in senders:
                out.append(PlanDefect(
                    "rounds", name, p,
                    f"part {p} packs {k_snd} slot(s) in round {r} but is "
                    "not a sender in that round's permutation",
                    details={"round": r},
                ))
            if k_rcv and p not in receivers:
                out.append(PlanDefect(
                    "rounds", name, p,
                    f"part {p} has {k_rcv} receive slot(s) in round {r} "
                    "but is not a receiver in that round's permutation",
                    details={"round": r},
                ))
        for src, dst in perm:
            k_snd = int(plan.snd_mask[src, r].sum())
            k_rcv = int((plan.rcv_idx[dst, r] != trash).sum())
            if k_snd != k_rcv:
                out.append(PlanDefect(
                    "symmetry", name, dst,
                    f"asymmetric counts on round-{r} edge {src}→{dst}: "
                    f"{k_snd} packed vs {k_rcv} landed",
                    details={"round": r, "edge": [src, dst],
                             "snd": k_snd, "rcv": k_rcv},
                ))

    noids = layout.noids
    for p in range(P):
        # send gathers read the part's OWNED slot range
        snd = plan.snd_idx[p][plan.snd_mask[p]]
        bad = snd[(snd < o0) | (snd >= o0 + int(noids[p]))]
        if bad.size:
            out.append(PlanDefect(
                "coverage", name, p,
                f"send gather slot(s) {sorted(set(bad.tolist()))[:8]} "
                f"outside part {p}'s owned range "
                f"[{o0}, {o0 + int(noids[p])})",
            ))
        # receive scatters: ghost region, race-free
        rcv = plan.rcv_idx[p][plan.rcv_idx[p] != trash]
        bad = rcv[(rcv < g0) | (rcv >= trash)]
        if bad.size:
            out.append(PlanDefect(
                "ghost-race", name, p,
                f"receive slot(s) {sorted(set(bad.tolist()))[:8]} outside "
                f"the ghost region [{g0}, {trash})",
            ))
        uniq, counts = np.unique(rcv, return_counts=True)
        dup = uniq[counts > 1]
        if dup.size:
            out.append(PlanDefect(
                "ghost-race", name, p,
                f"overlapping ghost slot(s) {sorted(dup.tolist())[:8]} on "
                f"part {p}: written by multiple rounds/sources",
                details={"slots": dup.tolist()[:64]},
            ))
        # coverage at hid granularity through the layout's slot map
        ref = np.asarray(referenced[p], dtype=bool)
        hid_slots = np.asarray(layout.hid_slots[p])
        covered_slots = set(rcv.tolist())
        missing = [
            h for h in np.nonzero(ref)[0].tolist()
            if int(hid_slots[h]) not in covered_slots
        ]
        if missing:
            out.append(PlanDefect(
                "coverage", name, p,
                f"dropped slot(s): referenced ghost hid(s) {missing[:8]} "
                "receive no round's payload — stale reads every exchange",
                details={"missing_hids": missing[:64]},
            ))
        ref_slots = set(hid_slots[ref].tolist())
        dead = sorted(covered_slots - set(hid_slots.tolist()) | (
            covered_slots & set(hid_slots[~ref].tolist())
        ))
        if dead:
            out.append(PlanDefect(
                "dead-slot", name, p,
                f"dead slot(s) {dead[:8]} on part {p}: delivered but "
                "referenced by no operator column",
                details={"slots": dead[:64], "referenced": len(ref_slots)},
            ))
    return out


# ---------------------------------------------------------------------------
# box slice plan
# ---------------------------------------------------------------------------


def verify_box_plan(
    plan,
    referenced: Optional[Sequence[np.ndarray]] = None,
    name: str = "device-box",
) -> List[PlanDefect]:
    """Verify a `BoxExchangePlan`: per-direction round validity, pack
    slices inside their variant's box, segment-slot race freedom and
    mask agreement, and per-hid coverage (each ghost's segment slot
    must belong to a direction that actually ppermutes INTO the
    part)."""
    import math

    out: List[PlanDefect] = []
    info = plan.info
    P = info.P
    if referenced is None:
        referenced = [
            np.ones(len(np.asarray(info.ghost_rel_slots[p])), dtype=bool)
            for p in range(P)
        ]
    _verify_rounds([d.perm for d in info.dirs], P, name, out)

    for d in info.dirs:
        for v, (start, shape) in enumerate(d.geo):
            bs = info.box_shapes[v]
            if any(
                a < 0 or a + s > b for a, s, b in zip(start, shape, bs)
            ) and math.prod(bs) > 0:
                out.append(PlanDefect(
                    "coverage", name, None,
                    f"direction {d.dir} variant {v} pack slice "
                    f"start={start} shape={shape} exceeds the owned box "
                    f"{bs}",
                ))
            if math.prod(shape) > d.size:
                out.append(PlanDefect(
                    "symmetry", name, None,
                    f"direction {d.dir} variant {v} slab "
                    f"({math.prod(shape)}) larger than the direction's "
                    f"segment ({d.size}) — receiver slots overflow",
                ))

    recv_dirs = [
        {q for _, q in d.perm} for d in info.dirs
    ]
    seg_mask = np.asarray(info.seg_mask)
    for p in range(P):
        rel = np.asarray(info.ghost_rel_slots[p])
        ref = np.asarray(referenced[p], dtype=bool)
        bad = rel[(rel < 0) | (rel >= info.nh_total)]
        if bad.size:
            out.append(PlanDefect(
                "ghost-race", name, p,
                f"segment slot(s) {sorted(set(bad.tolist()))[:8]} outside "
                f"the segment frame [0, {info.nh_total})",
            ))
        uniq, counts = np.unique(rel, return_counts=True)
        dup = uniq[counts > 1]
        if dup.size:
            out.append(PlanDefect(
                "ghost-race", name, p,
                f"overlapping segment slot(s) {sorted(dup.tolist())[:8]} "
                f"on part {p}: two ghosts mapped to one slot",
                details={"slots": dup.tolist()[:64]},
            ))
        ok = (rel >= 0) & (rel < info.nh_total)
        if rel[ok].size and not seg_mask[p, rel[ok]].all():
            unmasked = rel[ok][~seg_mask[p, rel[ok]]]
            out.append(PlanDefect(
                "coverage", name, p,
                f"real ghost slot(s) {sorted(set(unmasked.tolist()))[:8]} "
                "not marked in seg_mask — the assembly path would drop "
                "their contributions",
            ))
        extra = int(seg_mask[p].sum()) - len(np.unique(rel[ok]))
        if extra > 0:
            out.append(PlanDefect(
                "dead-slot", name, p,
                f"{extra} seg_mask slot(s) on part {p} marked real but "
                "mapped by no ghost hid",
            ))
        # every REFERENCED hid's slot must lie in a direction that
        # ppermutes into p (a dropped perm edge = a never-written slot)
        for h in np.nonzero(ref & ok)[0].tolist():
            s = int(rel[h])
            hit = False
            for k, d in enumerate(info.dirs):
                if d.off <= s < d.off + d.size:
                    hit = p in recv_dirs[k]
                    break
            if not hit:
                out.append(PlanDefect(
                    "coverage", name, p,
                    f"dropped slot: ghost hid {h} (segment slot {s}) "
                    "lies in a direction with no incoming edge to part "
                    f"{p} — it never receives",
                    details={"hid": h, "slot": s},
                ))
                break  # one defect per part keeps reports readable
    return out


# ---------------------------------------------------------------------------
# dispatch / gate
# ---------------------------------------------------------------------------


def verify_plan(
    plan,
    parts: Optional[Sequence] = None,
    referenced: Optional[Sequence[np.ndarray]] = None,
    name: Optional[str] = None,
) -> List[PlanDefect]:
    """Dispatch on the plan type. ``parts`` is required for host
    `Exchanger`s (index sets or `PartSpec`s); device plans carry
    their layout."""
    from ..parallel.exchanger import Exchanger

    if isinstance(plan, Exchanger):
        if parts is None:
            raise TypeError(
                "verify_plan: a host Exchanger needs its partition "
                "(parts=...) — the plan alone has no layout"
            )
        return verify_exchanger(
            plan, parts, referenced, name=name or "exchanger"
        )
    from ..parallel.tpu_box import BoxExchangePlan

    if isinstance(plan, BoxExchangePlan):
        return verify_box_plan(plan, referenced, name=name or "device-box")
    return verify_device_plan(
        plan, referenced, name=name or "device-generic"
    )


def check_plan(plan, parts=None, referenced=None, name=None,
               context: str = "") -> None:
    """Verify and RAISE the typed `PlanSoundnessError` on any defect
    (the ``PA_PLAN_VERIFY=1`` construction-time gate). Emits a
    ``plan_defect`` telemetry event per failing check class before
    raising, so the static catch is as narrated as a runtime one."""
    defects = verify_plan(plan, parts=parts, referenced=referenced,
                          name=name)
    if not defects:
        return
    from ..parallel.health import PlanSoundnessError
    from ..telemetry import emit_event

    for c in sorted({d.check for d in defects}):
        emit_event(
            "plan_defect", label=c,
            plan=defects[0].plan, context=context,
            count=sum(1 for d in defects if d.check == c),
        )
    first = defects[0]
    raise PlanSoundnessError(
        f"unsound exchange plan ({context or first.plan}): "
        f"{len(defects)} defect(s), first: {first}",
        diagnostics={
            "context": context,
            "checks": sorted({d.check for d in defects}),
            "defects": [d.to_dict() for d in defects[:16]],
        },
    )


# ---------------------------------------------------------------------------
# structural equality (the ROADMAP item 4 invariant: a plan rebuilt
# from an equivalent partition must be THIS-equal to the original)
# ---------------------------------------------------------------------------


def plan_fingerprint(plan) -> tuple:
    """A hashable structural fingerprint: two plans exchange identical
    slots over identical rounds iff their fingerprints are equal."""
    from ..parallel.exchanger import Exchanger

    def _b(a):
        return np.ascontiguousarray(np.asarray(a)).tobytes()

    if isinstance(plan, Exchanger):
        return (
            "exchanger",
            tuple(
                (_b(pr), _b(ps), _b(lr.data), _b(lr.ptrs), _b(ls.data),
                 _b(ls.ptrs))
                for pr, ps, lr, ls in zip(
                    _part_values(plan.parts_rcv),
                    _part_values(plan.parts_snd),
                    _part_values(plan.lids_rcv),
                    _part_values(plan.lids_snd),
                )
            ),
        )
    from ..parallel.tpu_box import BoxExchangePlan

    if isinstance(plan, BoxExchangePlan):
        info = plan.info
        return (
            "box", bool(plan.reverse_mode), info.box_shapes,
            _b(info.variants), info.nh_total,
            tuple((d.dir, d.geo, d.off, d.size, d.perm)
                  for d in info.dirs),
            tuple(_b(r) for r in info.ghost_rel_slots),
            _b(info.seg_mask),
        )
    return (
        "generic", plan.R, plan.L, plan.perms,
        _b(plan.snd_idx), _b(plan.snd_mask), _b(plan.rcv_idx),
    )


def plans_equal(a, b) -> bool:
    return plan_fingerprint(a) == plan_fingerprint(b)


def canonical_exchange_fingerprint(exchanger, parts) -> tuple:
    """The LAYOUT-INDEPENDENT fingerprint of a host plan: per directed
    edge (p → q), the sorted GLOBAL ids exchanged. Two partitions of
    the same operator that number their local/ghost lids differently
    (e.g. assembly-order ghosts vs a checkpoint-restored column-sorted
    partition) still exchange the same global columns over the same
    edges — THIS is the invariant ROADMAP item 4's incremental re-plan
    must preserve, while `plan_fingerprint` additionally pins the
    slot-level layout of one partition's plan."""
    parts = _part_values(parts)
    edges = []
    for p, (nbrs, lids) in enumerate(zip(
        _part_values(exchanger.parts_snd), _part_values(exchanger.lids_snd)
    )):
        gid = np.asarray(parts[p].lid_to_gid)
        for j, q in enumerate(np.asarray(nbrs)):
            edges.append((
                int(p), int(q),
                tuple(sorted(gid[np.asarray(lids[j])].tolist())),
            ))
    return tuple(sorted(edges))


# ---------------------------------------------------------------------------
# fixture (de)serialization — the committed negative corpus
# ---------------------------------------------------------------------------


class _ListPData:
    """Minimal part container for fixture-loaded plans."""

    def __init__(self, parts):
        self._parts = list(parts)

    def part_values(self):
        return self._parts


def exchanger_fixture(exchanger, parts, referenced=None,
                      defect: Optional[str] = None,
                      note: str = "") -> dict:
    """Serialize a host plan + its partition summary (+ the referenced
    ghost masks) as a JSON-able dict — the committed negative-corpus
    format (tests/fixtures/paplan/)."""
    parts = _part_values(parts)
    return {
        "format": "paplan-exchanger-fixture",
        "version": 1,
        "defect": defect,
        "note": note,
        "parts": [
            {
                "num_lids": int(i.num_lids),
                "num_oids": int(i.num_oids),
                "lid_to_ohid": np.asarray(i.lid_to_ohid).tolist(),
            }
            for i in parts
        ],
        "referenced": (
            None if referenced is None
            else [np.asarray(m).astype(int).tolist() for m in referenced]
        ),
        "parts_rcv": [
            np.asarray(t).tolist() for t in _part_values(exchanger.parts_rcv)
        ],
        "parts_snd": [
            np.asarray(t).tolist() for t in _part_values(exchanger.parts_snd)
        ],
        "lids_rcv": [
            {"data": np.asarray(t.data).tolist(),
             "ptrs": np.asarray(t.ptrs).tolist()}
            for t in _part_values(exchanger.lids_rcv)
        ],
        "lids_snd": [
            {"data": np.asarray(t.data).tolist(),
             "ptrs": np.asarray(t.ptrs).tolist()}
            for t in _part_values(exchanger.lids_snd)
        ],
    }


def load_exchanger_fixture(path_or_dict):
    """Load a committed fixture back into ``(exchanger, parts,
    referenced, defect)`` ready for `verify_exchanger`."""
    from ..utils.table import INDEX_DTYPE, Table
    from ..parallel.exchanger import Exchanger

    if isinstance(path_or_dict, dict):
        d = path_or_dict
    else:
        with open(path_or_dict, encoding="utf-8") as f:
            d = json.load(f)
    if d.get("format") != "paplan-exchanger-fixture":
        raise ValueError(f"not a paplan fixture: {path_or_dict}")
    parts = [
        PartSpec(
            num_lids=int(p["num_lids"]), num_oids=int(p["num_oids"]),
            lid_to_ohid=np.asarray(p["lid_to_ohid"], dtype=INDEX_DTYPE),
        )
        for p in d["parts"]
    ]
    referenced = (
        None if d.get("referenced") is None
        else [np.asarray(m, dtype=bool) for m in d["referenced"]]
    )

    def _tables(rows):
        return _ListPData([
            Table(np.asarray(t["data"], dtype=INDEX_DTYPE),
                  np.asarray(t["ptrs"], dtype=INDEX_DTYPE))
            for t in rows
        ])

    ex = Exchanger(
        _ListPData([np.asarray(a, dtype=INDEX_DTYPE)
                    for a in d["parts_rcv"]]),
        _ListPData([np.asarray(a, dtype=INDEX_DTYPE)
                    for a in d["parts_snd"]]),
        _tables(d["lids_rcv"]),
        _tables(d["lids_snd"]),
    )
    return ex, parts, referenced, d.get("defect")


# ---------------------------------------------------------------------------
# the lowering-matrix hook (analysis.matrix / palint)
# ---------------------------------------------------------------------------


def audit_case(backend, case: dict) -> dict:
    """Verify every plan ``case``'s program is lowered from, under the
    case's pinned env: the host column `Exchanger` plus the device
    column plan (box under the default env, generic under
    ``PA_TPU_BOX=0`` / strict-bits / ABFT) — all against the probe
    operator's actual referenced-ghost sparsity. Returns the summary
    the ``plan-soundness`` contract checks (stashed at
    ``cases[name]["plan_audit"]`` by `analysis.matrix.build_reports`)."""
    from ..parallel.tpu import (
        _MATRIX_BASE_ENV,
        _env_overrides,
        _matrix_probe_system,
        device_matrix,
    )
    from ..parallel.tpu_box import BoxExchangePlan

    env = dict(_MATRIX_BASE_ENV)
    env.update(case.get("env", {}))
    with _env_overrides(env):
        A, _b, _x0 = _matrix_probe_system(backend, case.get("dtype", "f64"))
        dA = device_matrix(A, backend)
        ref = referenced_ghosts(A)
        plans = {
            "host-exchanger": verify_exchanger(
                A.cols.exchanger, A.cols.partition, referenced=ref
            ),
        }
        plan = dA.col_plan
        kind = (
            "device-box" if isinstance(plan, BoxExchangePlan)
            else "device-generic"
        )
        plans[kind] = verify_plan(plan, referenced=ref, name=kind)
    return {
        "kind": kind,
        "plans": {
            k: [d.to_dict() for d in v] for k, v in plans.items()
        },
        "n_defects": sum(len(v) for v in plans.values()),
    }
