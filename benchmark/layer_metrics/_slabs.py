"""What the metrics of the solve service share: the slabs of the traced
stretch, and the service's counters over the window.

A slab is one `pa:service:slab` span of the program (`service/service.py`
`_run_slab`; stats ``k``, the slab's width, and ``trips``, the block
iterations of its block solves) that opened and closed inside the trace.
`benchmark/trace.py` keeps a span's name and interval only, so the stats
are read here from the same file with `jax.profiler.ProfileData`. Where
the program opens no such span (a parent of PR 34) there are no slabs and
every reader returns None.

The counters (`service.slab_columns`, `service.slabs`,
`service.queue_wait_us`) count since the process began. The served builder
hands every request's `info` the counters as its client saw them when it
submitted and when it had its answer (`service_counters`), and
`window_counters` takes the first traced request's submission from the last
one's answer: the traced stretch, without the warm-up's slabs of set widths
and without what follows the trace (a traced run's generator waits behind
the profiler's stop, and the burst it then sends is no part of the cell).
"""
from __future__ import annotations

import os

from benchmark import trace as tr
from benchmark.layer_metrics._traced import traced_stretch

SLAB = "pa:service:slab"


def read_slab_spans(path: str) -> list:
    """``[(start, end, k, trips)]`` of the file's slab spans, sorted;
    a span without both stats is left out."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != SLAB:
                    continue
                stats = dict(ev.stats)
                if "k" in stats and "trips" in stats:
                    out.append((
                        ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9,
                        int(stats["k"]), int(stats["trips"]),
                    ))
    return sorted(out)


def traced_slabs(run):
    """``(trace, [(start, end, k, trips)])`` of a run whose trace holds
    device ops and whole slabs that made block iterations, else None.
    Read once for a run."""
    if not hasattr(run, "_traced_slabs"):
        run._traced_slabs = _traced_slabs(run)
    return run._traced_slabs


def _traced_slabs(run):
    if traced_stretch(run) is None:
        return None
    try:
        path = tr.find_xplane(
            os.path.join(os.path.dirname(tr.__file__), ".trace")
        )
    except FileNotFoundError:
        return None
    slabs = [s for s in read_slab_spans(path) if s[3] > 0]
    return (run.trace, slabs) if slabs else None


def traced_slab_busy(run):
    """``(slabs, busy_s)``: the traced slabs and the seconds in which an op
    ran on the device inside their spans, summed over the slabs, mean over
    the cell's devices; None where there is no slab or the device never
    ran inside one."""
    found = traced_slabs(run)
    if found is None:
        return None
    trace, slabs = found
    busy = sum(tr.mean_busy(trace, lo, hi) for lo, hi, _k, _trips in slabs)
    return (slabs, busy) if busy > 0.0 else None


def leaf_ms_per_slab(run, leaf: str):
    """Wall time of the block solve's ``pa:block-<solver>:<leaf>`` spans
    inside the traced slabs, per slab, in milliseconds; None where there
    is no slab or no such span."""
    found = traced_slabs(run)
    if found is None:
        return None
    trace, slabs = found
    tail = ":" + leaf
    mine = [
        (s, e) for s, e, name in trace.spans
        if name.startswith("pa:block-") and name.endswith(tail)
    ]
    total = sum(
        tr.length(tr.clip(mine, lo, hi)) for lo, hi, _k, _trips in slabs
    )
    return 1e3 * total / len(slabs) if total > 0.0 else None


def window_counters(run):
    """The program's unlabeled `service.*` counters over the traced
    stretch, or None: in a run without a device trace (as the counter
    readers beside this one), where the builder hands no counters, and
    where the program counts no slab columns."""
    if traced_stretch(run) is None or not run.traced_records:
        return None
    first = run.traced_records[0]["info"].get("service_counters")
    last = run.traced_records[-1]["info"].get("service_counters")
    if not first or not last or "service.slab_columns" not in last["at_answer"]:
        return None
    return {
        k: v - first["at_submit"].get(k, 0) for k, v in last["at_answer"].items()
    }
