"""Host time of the traced solves by the program's own spans
(`telemetry.annotate`, names that start with `pa:`), from
`run.trace.spans`: the wall time of a named span per solve, and the time
the first device sat idle inside a solve by the innermost span over it.

The reductions are pure and work on `(start, end, name)` tuples and on the
`(start, end)` of the traced solves (the harness's `bench:solve` spans).
Device 0 stands for the cell's devices, as in `trace.breakdown`: the
devices of one SPMD program idle together.
"""
from __future__ import annotations

from benchmark import trace as tr
from benchmark.layer_metrics._traced import traced_stretch

#: Spans that are not a leaf of the program: idle time whose innermost span
#: is one of these has no owner.
UNOWNED = ("bench:solve", "pa:solve")


def phase_wall_s(spans, solves, phase: str) -> float:
    """Seconds inside the ``solves`` under spans named ``pa:<solver>:<phase>``
    (``pa:cg:stage``, ``pa:pcg+gmg:stage``), summed."""
    tail = ":" + phase
    mine = [
        (s, e) for s, e, name in spans
        if name.startswith("pa:") and name.endswith(tail)
    ]
    return sum(tr.length(tr.clip(mine, lo, hi)) for lo, hi in solves)


def idle_by_span(ops, spans, solves) -> dict:
    """Seconds of the ``solves`` in which no op of ``ops`` runs, by the
    innermost span that covers them: a gap is cut where a span opens or
    closes, and each piece goes to the shortest span over its middle (a
    `bench:solve` span covers every piece, so there is always one)."""
    edges = sorted({t for s, e, _ in spans for t in (s, e)})
    out: dict = {}
    for lo, hi in solves:
        for s, e in tr.gaps(ops, lo, hi):
            cuts = [s] + [t for t in edges if s < t < e] + [e]
            for a, b in zip(cuts, cuts[1:]):
                name = tr.innermost_span(spans, 0.5 * (a + b)) or "(no span)"
                out[name] = out.get(name, 0.0) + (b - a)
    return out


def _solves(run):
    """``(trace, [(lo, hi)])`` of the traced solves, or None."""
    if traced_stretch(run) is None:
        return None
    solves = tr.solve_spans(run.trace)
    return (run.trace, solves) if solves else None


def phase_ms_per_solve(run, phase: str):
    """Wall time of the program's ``phase`` span per traced solve, in
    milliseconds; None where the trace holds no such span."""
    found = _solves(run)
    if found is None:
        return None
    t, solves = found
    total = phase_wall_s(t.spans, solves, phase)
    return 1e3 * total / len(solves) if total > 0.0 else None


def unowned_ms_per_solve(run):
    """Idle time of the first device inside the traced solves whose
    innermost span is the harness's or the program's root (`UNOWNED`),
    per solve, in milliseconds; None where the program opens no root span
    (then every gap outside `stage` and `solve` would count, which is what
    `breakdown.idle_gaps` already shows)."""
    found = _solves(run)
    if found is None:
        return None
    t, solves = found
    if not any(name == "pa:solve" for _, _, name in t.spans):
        return None
    first = t.device_ops[min(t.device_ops)]
    by = idle_by_span(first, t.spans, solves)
    return 1e3 * sum(by.get(n, 0.0) for n in UNOWNED) / len(solves)
