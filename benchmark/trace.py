"""From a profiler trace (`.xplane.pb`) to device intervals and host spans.

Everything a per-layer metric reads from the trace passes through here, so
that every PR reduces a trace in the same way. Nothing relies on the name of
an XLA op or scope, except `COLLECTIVE_OPS`, which are XLA's own names for
its collective instructions.

Times are seconds on the trace's own clock (the profiler stamps host and
device events on one timeline).
"""
from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

#: XLA's opcodes for collectives, start and done halves alike. An op of the
#: trace is named by its HLO text; `short_name` cuts that to "%name opcode".
COLLECTIVE_OPS = re.compile(
    r"(^|\s)(all-reduce|all-gather|collective-permute|reduce-scatter|all-to-all)"
    r"(-start|-done)?$"
)
_HLO = re.compile(r"^(%?[\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(hlo: str) -> str:
    """``%fusion.6 = (f32[...]...) fusion(...), kind=kLoop`` -> ``%fusion.6
    fusion``; a custom call also names its target. Anything else is cut to
    120 characters."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:120]
    target = _TARGET.search(hlo) if m.group(2) == "custom-call" else None
    return f"{m.group(1)} {m.group(2)}" + (f" {target.group(1)}" if target else "")


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE_OPS.search(name))


DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: The line of a device plane that holds one event per executed XLA op.
OPS_LINE = "XLA Ops"
#: Spans of the harness and of the program (`telemetry.annotate`).
SPAN_PREFIXES = ("bench:", "pa:")


class Trace(NamedTuple):
    """``device_ops[d]``: sorted ``(start, end, name)`` of device d's ops;
    ``spans``: sorted ``(start, end, name)`` of the host's named spans."""

    device_ops: dict
    spans: list


def find_xplane(log_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read(path: str) -> Trace:
    from jax.profiler import ProfileData

    def interval(ev, name):
        return ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, name

    device_ops, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            device_ops[int(m.group(1))] = sorted(
                interval(ev, short_name(ev.name))
                for line in plane.lines if line.name == OPS_LINE
                for ev in line.events
            )
        elif plane.name.startswith("/host:"):
            spans += [
                interval(ev, ev.name)
                for line in plane.lines for ev in line.events
                if ev.name.startswith(SPAN_PREFIXES)
            ]
    return Trace(device_ops, sorted(spans))


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals) -> list:
    """Sorted, disjoint ``(start, end)`` covering the same points."""
    out = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [
        (max(s, lo), min(e, hi)) for s, e, *_ in intervals
        if min(e, hi) > max(s, lo)
    ]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy(ops, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which some op of ``ops`` runs."""
    return length(union(clip(ops, lo, hi)))


def gaps(ops, lo: float, hi: float) -> list:
    """The parts of ``[lo, hi]`` in which no op of ``ops`` runs."""
    out, t = [], lo
    for s, e in union(clip(ops, lo, hi)):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def self_times(ops) -> dict:
    """Seconds by op name, each instant given to the innermost op that
    covers it (a `while` holds its body's ops; their time is theirs)."""
    totals: dict = {}
    stack: list = []  # (end, name, start-of-unattributed)

    def close_until(t):
        while stack and stack[-1][0] <= t:
            end, name, since = stack.pop()
            totals[name] = totals.get(name, 0.0) + max(0.0, end - since)
            if stack:
                stack[-1] = (stack[-1][0], stack[-1][1], max(end, stack[-1][2]))

    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        close_until(s)
        if stack:
            pend, pname, since = stack[-1]
            totals[pname] = totals.get(pname, 0.0) + max(0.0, min(s, pend) - since)
            stack[-1] = (pend, pname, s)
        stack.append((min(e, stack[-1][0]) if stack else e, name, s))
    close_until(float("inf"))
    return totals


def innermost_span(spans, t: float):
    """Name of the shortest span that covers ``t``, or None."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return None if best is None else best[1]


# ---------------------------------------------------------------------------
# what the harness asks of a trace
# ---------------------------------------------------------------------------


def solve_spans(trace: Trace) -> list:
    return [(s, e) for s, e, name in trace.spans if name == "bench:solve"]


def stretch(trace: Trace):
    """The traced stretch: first traced solve's issue to the last one's
    completion. The profiler's own start and stop lie outside it."""
    sp = solve_spans(trace)
    if not sp:
        return None
    return min(s for s, _ in sp), max(e for _, e in sp)


def mean_busy(trace: Trace, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which an op ran, mean over the devices."""
    per_device = [busy(ops, lo, hi) for ops in trace.device_ops.values()]
    return sum(per_device) / len(per_device)


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most (self) time, summed over the devices,
    and the longest idle time by the host span that covers it, on the
    first device (the devices of one SPMD program idle together)."""
    st = stretch(trace)
    if st is None or not trace.device_ops:
        return {}
    lo, hi = st
    by_op: dict = {}
    for ops in trace.device_ops.values():
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in ops if e > lo and s < hi]
        for name, secs in self_times(inside).items():
            by_op[name] = by_op.get(name, 0.0) + secs
    by_span: dict = {}
    first = trace.device_ops[min(trace.device_ops)]
    edges = sorted({t for s, e, _ in trace.spans for t in (s, e)})
    for s, e in gaps(first, lo, hi):
        # a gap is cut where a span opens or closes, and each piece goes
        # to the innermost span over it
        cuts = [s] + [t for t in edges if s < t < e] + [e]
        for a, b in zip(cuts, cuts[1:]):
            name = innermost_span(trace.spans, 0.5 * (a + b)) or "(no span)"
            by_span[name] = by_span.get(name, 0.0) + (b - a)

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": ranked(by_op), "idle_gaps": ranked(by_span)}
