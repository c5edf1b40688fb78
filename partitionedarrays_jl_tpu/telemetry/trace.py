"""Chrome-trace / Perfetto export: one timeline for solver records and
PTimer sections.

The exported file is the plain Chrome ``traceEvents`` JSON (load it at
``chrome://tracing`` or https://ui.perfetto.dev): every `SolveRecord`
becomes one complete span (``ph: "X"``) carrying its config in args,
each of its telemetry events an instant (``ph: "i"``) at the event's
offset inside the span, and every `PTimer` section a span on its own
track — including the ``barrier`` cost of ``tic(barrier=True)``, which
is a real, otherwise-invisible line item (it drains the device FIFOs).

All timestamps are absolute wall-clock microseconds (records carry
``started_at``; PTimer spans record their own epoch starts), so records
and timer sections from the same process land on one coherent timeline.

`annotate` is the one span helper of the device solve path: it opens a
``jax.profiler.TraceAnnotation`` (so the span lands in a captured
profile on the same clock as the device ops) AND adds the span's
``perf_counter`` duration to the calling thread's record
(`record.current_record`: the innermost `solve_scope` open on the
thread that opens the span, never another thread's and never a
request's), under the span's last component of ``timings``, so an
operator without a profiler reads the same split from
``info.record.timings``. The spans a device solve opens
(``pa:solve`` root from `solve_scope`; ``pa:<solver>:stage|solve|wait|
fetch|finish`` and the ``pa:stage:*`` / ``pa:fetch:*`` leaves from
``parallel/tpu.py`` `_run_krylov` / `_tpu_block_cg_impl`) are listed in
docs/observability.md, "Spans and scopes of a solve".
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager, nullcontext
from typing import Iterable, List, Optional

from .record import current_record

__all__ = [
    "TRACE_SCHEMA_VERSION",
    "annotate",
    "profiler_span",
    "chrome_trace",
    "record_trace_events",
    "write_chrome_trace",
]

TRACE_SCHEMA_VERSION = 1


def profiler_span(name: str, **stats):
    """A ``jax.profiler.TraceAnnotation`` named ``name`` (``stats`` become
    the event's keyword stats in the captured profile; the event's name
    stays bare), or a null context where jax is not importable."""
    try:
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(name, **stats)
    except Exception:
        return nullcontext()


@contextmanager
def annotate(name: str):
    """``with annotate("pa:cg:stage"): ...`` — a profiler span (see
    `profiler_span`) whose wall time is also added to the calling
    thread's record, ``timings[<last component of name>]``, when the
    thread has one and it is enabled: ``pa:cg:stage`` ->
    ``timings["stage"]``, ``pa:stage:pack`` -> ``timings["pack"]``
    (spans that repeat inside one solve add up). Outside any
    `solve_scope` of the thread it is the profiler span alone. Inactive,
    a span costs well under a microsecond."""
    rec = current_record()
    timed = rec is not None and rec.enabled
    t0 = time.perf_counter() if timed else 0.0
    with profiler_span(name):
        try:
            yield
        finally:
            if timed:
                leaf = name.rsplit(":", 1)[-1]
                rec.timings[leaf] = (
                    rec.timings.get(leaf, 0.0) + time.perf_counter() - t0
                )


def record_trace_events(rec, tid: int = 0) -> List[dict]:
    """Chrome events of one `SolveRecord`: the solve span plus one
    instant per telemetry event."""
    d = rec.as_dict() if hasattr(rec, "as_dict") else dict(rec)
    t0_us = float(d.get("started_at") or 0.0) * 1e6
    dur_us = float(d.get("wall_s") or 0.0) * 1e6
    out = [
        {
            "name": f"solve:{d.get('solver')}",
            "ph": "X",
            "ts": t0_us,
            "dur": max(dur_us, 1.0),
            "pid": 1,
            "tid": tid,
            "cat": "solve",
            "args": {
                "solver": d.get("solver"),
                "iterations": d.get("iterations"),
                "status": d.get("status"),
                "config": d.get("config"),
                "comms": d.get("comms"),
                "timings": d.get("timings"),
            },
        }
    ]
    for ev in d.get("events") or []:
        out.append(
            {
                "name": f"{ev['kind']}:{ev.get('label') or ''}".rstrip(":"),
                "ph": "i",
                "s": "t",
                "ts": t0_us + float(ev.get("t") or 0.0) * 1e6,
                "pid": 1,
                "tid": tid,
                "cat": "event",
                "args": {
                    "iteration": ev.get("iteration"),
                    **(ev.get("details") or {}),
                },
            }
        )
    return out


def chrome_trace(
    records: Optional[Iterable] = None, timers: Optional[Iterable] = None
) -> dict:
    """The full Chrome-trace object for a set of records and PTimers
    (each timer contributes `PTimer.trace_events` spans)."""
    events: List[dict] = [
        {"name": "process_name", "ph": "M", "pid": 1,
         "args": {"name": "partitionedarrays_jl_tpu solves"}},
        {"name": "process_name", "ph": "M", "pid": 2,
         "args": {"name": "partitionedarrays_jl_tpu ptimers"}},
    ]
    for tid, rec in enumerate(records or []):
        events.extend(record_trace_events(rec, tid=tid))
    for timer in timers or []:
        events.extend(timer.trace_events(pid=2))
    return {
        "displayTimeUnit": "ms",
        "metadata": {"schema_version": TRACE_SCHEMA_VERSION,
                     "generated_by": "partitionedarrays_jl_tpu.telemetry"},
        "traceEvents": events,
    }


def write_chrome_trace(path: str, records=None, timers=None,
                       extra_events=None) -> str:
    """The ONE trace serializer. ``extra_events`` appends pre-built
    Chrome events (e.g. `telemetry.profile.phase_trace_events`) onto
    the same timeline — callers never hand-roll the file format."""
    trace = chrome_trace(records=records, timers=timers)
    if extra_events:
        trace["traceEvents"].extend(extra_events)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(trace, f, indent=1)
    return path
