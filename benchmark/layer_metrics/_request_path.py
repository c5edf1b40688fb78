"""What the metrics of a served request's path OUTSIDE its slab share
(PR 36): the program's `pa:service:submit` and `pa:service:idle` spans
(`service/service.py` `submit`, `_work`) and the owners of the device's
idle time over the traced stretch.

The reductions are pure and work on `(start, end, name)` tuples, as those
of `_host_spans.py`. In the served cell the traced `bench:solve` spans
overlap (every request has a thread of its own), so a span is counted
once, where it lies inside some traced solve, and never once a solve that
covers it. Where the program opens no such span (a parent of PR 36) every
reader returns None.
"""
from __future__ import annotations

from benchmark import trace as tr
from benchmark.layer_metrics._host_spans import UNOWNED, idle_by_span

SUBMIT = "pa:service:submit"
IDLE = "pa:service:idle"
#: The owners that are no owner: none at all, the harness's span, the
#: program's root.
NO_OWNER = UNOWNED + ("(no span)",)


def spans_inside(spans, solves, name: str) -> list:
    """``[(start, end)]`` of the spans named ``name`` that lie whole
    inside one of ``solves``, each once."""
    return [
        (s, e) for s, e, n in spans
        if n == name and any(lo <= s and e <= hi for lo, hi in solves)
    ]


def submit_ms(spans, solves):
    """Mean wall time of a traced request's `submit`, in milliseconds."""
    mine = spans_inside(spans, solves, SUBMIT)
    return 1e3 * tr.length(mine) / len(mine) if mine else None


def idle_share(spans, lo: float, hi: float):
    """Percent of ``[lo, hi]`` the worker thread spent with an empty
    queue; None where the trace holds no such span at all. A wait that
    began before the profiler did is in no trace, so the stretch's first
    request's `submit` may be missing from it."""
    mine = [(s, e) for s, e, n in spans if n == IDLE]
    if not mine or hi <= lo:
        return None
    return 100.0 * tr.length(tr.union(tr.clip(mine, lo, hi))) / (hi - lo)


def unowned_share(ops, spans, lo: float, hi: float):
    """Of the time no op of ``ops`` runs in ``[lo, hi]``, the percent
    whose innermost span is none, the harness's or the program's root."""
    by = idle_by_span(ops, spans, [(lo, hi)])
    total = sum(by.values())
    if total <= 0.0:
        return None
    return 100.0 * sum(by.get(n, 0.0) for n in NO_OWNER) / total


def ratio_ms(counters, total_us: str, count: str):
    """``counters[total_us] / counters[count]`` in milliseconds, or None
    where either is missing or nothing was counted."""
    if counters is None or total_us not in counters or not counters.get(count):
        return None
    return 1e-3 * counters[total_us] / counters[count]
