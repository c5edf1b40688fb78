"""Device ops of the traced run with the program's `pa.` scopes, for the
metrics that give device time to a phase or to a V-cycle level.

Where the scope lives (found by looking at one trace of `poisson7_192.cg`
by hand, PR 26): not in the event. An op event of a device plane carries
three stats of its own (`device_offset_ps`, `device_duration_ps`, `Time
Scale Multiplier`), and its name is the HLO text without `metadata={...}`.
The `op_name` that `jax.named_scope` writes is the `tf_op` stat of the
event's METADATA entry (`XPlane.event_metadata[id].stats`), for example
`jit(fn)/while/body/pa.axpy_sweep/pa.dot_allgather/reduce_sum:`, and
`jax.profiler.ProfileData`, which `benchmark/trace.py` reads with, shows
an event's own stats only. So this file decodes the `.xplane.pb` itself:
the protobuf wire format, and of the messages `XSpace`, `XPlane`, `XLine`,
`XEvent`, `XEventMetadata`, `XStatMetadata`, `XStat` the fields named below.

A fusion takes the scope of its root: XLA gives a fusion instruction the
metadata of the instruction the others were fused into, so a loop fusion
that computes an axpy and the partial sum of a dot counts wholly under
whichever of the two is its root.

Parsing (`parse`) and reduction (`phase_of`, `level_of`, `seconds_by`)
are separate; the reduction is pure and works on `(start, end, scopes)`
tuples, `scopes` being the `pa.`-prefixed components of the op's
`op_name`, outermost first. Times are seconds on the clock of
`benchmark/trace.py` (`line.timestamp_ns + offset_ps / 1000`, as
`ProfileData` computes an event's `start_ns`).

    python -m benchmark.layer_metrics._scoped <file.xplane.pb>

prints seconds by phase and by level, and the unscoped ops that took most.
"""
from __future__ import annotations

import os
import re
import sys

from benchmark import trace as tr

SCOPE_PREFIX = "pa."
LEVEL = re.compile(r"^pa\.gmg\.l(\d+)$")
_INSTRUCTION = re.compile(r"^(%?[\w.\-]+)")


# ---------------------------------------------------------------------------
# the protobuf wire format, as far as an xplane needs it
# ---------------------------------------------------------------------------


def _varint(buf, i: int):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if not c & 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are
    skipped (no message read here has one that matters)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i : i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _map_entry(buf):
    """``(key, value)`` of a protobuf map entry with an integer key."""
    key = value = None
    for number, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _plane(buf):
    """``(name, lines, event_metadata, stat_names)`` of an `XPlane`:
    fields 2 (name), 3 (lines), 4 (event_metadata), 5 (stat_metadata)."""
    name, lines, events, stats = "", [], {}, {}
    for number, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            key, value = _map_entry(v)
            events[key] = value
        elif number == 5:
            key, value = _map_entry(v)
            # XStatMetadata: 1 id, 2 name
            stats[key] = next(
                (_text(x) for n, x in _fields(value) if n == 2), ""
            )
    return name, lines, events, stats


def _op_of(meta, stat_names) -> tuple:
    """``(name, op_name)`` of an `XEventMetadata`: fields 2 (name), 5
    (stats); of an `XStat` 1 (metadata_id) and 5 (str_value)."""
    name, op_name = "", ""
    for number, v in _fields(meta):
        if number == 2:
            name = _text(v)
        elif number == 5:
            sid = text = None
            for n, x in _fields(v):
                if n == 1:
                    sid = x
                elif n == 5:
                    text = x
            if text is not None and stat_names.get(sid) == "tf_op":
                op_name = _text(text)
    return name, op_name


def scopes_of(op_name: str) -> tuple:
    """The `pa.`-prefixed components of an `op_name`, outermost first."""
    return tuple(c for c in op_name.split("/") if c.startswith(SCOPE_PREFIX))


def parse(path: str, with_names: bool = False) -> dict:
    """``{device: [(start, end, scopes)]}`` of the file's device planes,
    sorted; with ``with_names`` the tuples end in the op's instruction
    name (``%fusion.6``). Of an `XLine` fields 2 (name), 3 (timestamp_ns),
    4 (events); of an `XEvent` 1 (metadata_id), 2 (offset_ps), 3
    (duration_ps)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, lines, events, stat_names = _plane(plane)
        m = tr.DEVICE_PLANE.match(name)
        if not m:
            continue
        known = {}  # metadata id -> (scopes, instruction name)
        ops = []
        for line in lines:
            line_name, t0_ns, evs = "", 0, []
            for n, v in _fields(line):
                if n == 2:
                    line_name = _text(v)
                elif n == 3:
                    t0_ns = v
                elif n == 4:
                    evs.append(v)
            if line_name != tr.OPS_LINE:
                continue
            for ev in evs:
                mid = offset_ps = duration_ps = 0
                for n, v in _fields(ev):
                    if n == 1:
                        mid = v
                    elif n == 2:
                        offset_ps = v
                    elif n == 3:
                        duration_ps = v
                if mid not in known:
                    op, op_name = _op_of(events.get(mid, b""), stat_names)
                    found = _INSTRUCTION.match(op)
                    known[mid] = (
                        scopes_of(op_name), found.group(1) if found else op[:60]
                    )
                start = (t0_ns + offset_ps / 1000.0) * 1e-9
                op = (start, start + duration_ps * 1e-12, known[mid][0])
                ops.append(op + (known[mid][1],) if with_names else op)
        out[int(m.group(1))] = sorted(ops)
    return out


# ---------------------------------------------------------------------------
# the reduction: pure, on tuples
# ---------------------------------------------------------------------------


def phase_of(scopes):
    """The innermost `pa.` component that is not a V-cycle level
    (`pa.gmg.l<k>`), or None."""
    for c in reversed(scopes):
        if not LEVEL.match(c):
            return c
    return None


def level_of(scopes):
    """k of the innermost `pa.gmg.l<k>` component, or None."""
    for c in reversed(scopes):
        m = LEVEL.match(c)
        if m:
            return int(m.group(1))
    return None


def seconds_by(device_ops: dict, lo: float, hi: float, key) -> dict:
    """Self time inside ``[lo, hi]`` by ``key(scopes)``, summed over the
    devices: each instant goes to the innermost op that covers it, as in
    `trace.self_times` (a `while` holds its body's ops; their time is
    theirs), so the values add up to the devices' busy time."""
    out: dict = {}
    for ops in device_ops.values():
        inside = [
            (max(s, lo), min(e, hi), scopes)
            for s, e, scopes, *_ in ops if e > lo and s < hi
        ]
        for scopes, secs in tr.self_times(inside).items():
            k = key(scopes)
            out[k] = out.get(k, 0.0) + secs
    return out


# ---------------------------------------------------------------------------
# what the metrics ask
# ---------------------------------------------------------------------------


def scoped_ops(run):
    """``(device_ops, lo, hi)`` of the run's newest trace, clipped by its
    traced stretch, or None where there is nothing to read. Parsed once
    for a run."""
    if not hasattr(run, "_scoped_ops"):
        run._scoped_ops = _scoped_ops(run)
    return run._scoped_ops


def _scoped_ops(run):
    if run.trace is None or not run.trace.device_ops:
        return None
    st = tr.stretch(run.trace)
    if st is None:
        return None
    try:
        path = tr.find_xplane(
            os.path.join(os.path.dirname(tr.__file__), ".trace")
        )
    except FileNotFoundError:
        return None
    device_ops = parse(path)
    if not any(device_ops.values()):
        return None
    return (device_ops, *st)


def phase_us(run, phase: str):
    """Device self time under ``phase`` per Krylov iteration of the traced
    solves, mean over the cell's devices, in microseconds; None where no
    op of the trace carries the phase (a program without the scopes)."""
    found = scoped_ops(run)
    if found is None:
        return None
    device_ops, lo, hi = found
    secs = seconds_by(device_ops, lo, hi, phase_of).get(phase)
    iterations = sum(
        int(r["info"].get("iterations", 0)) for r in run.traced_records
    )
    if secs is None or iterations <= 0:
        return None
    return 1e6 * secs / len(device_ops) / iterations


def main(path: str) -> None:
    device_ops = parse(path, with_names=True)
    lo = min(o[0] for ops in device_ops.values() for o in ops)
    hi = max(o[1] for ops in device_ops.values() for o in ops)
    for title, key in (("phase", phase_of), ("level", level_of)):
        by = seconds_by(device_ops, lo, hi, key)
        print(f"seconds by {title} (all devices, whole trace):")
        for k, v in sorted(by.items(), key=lambda kv: -kv[1]):
            print(f"  {v:12.6f}  {k}")
    unscoped: dict = {}
    for ops in device_ops.values():
        named = [(s, e, (scopes, name)) for s, e, scopes, name in ops]
        for (scopes, name), secs in tr.self_times(named).items():
            if not scopes:
                unscoped[name] = unscoped.get(name, 0.0) + secs
    print("unscoped ops that took most:")
    for k, v in sorted(unscoped.items(), key=lambda kv: -kv[1])[:12]:
        print(f"  {v:12.6f}  {k}")


if __name__ == "__main__":
    main(sys.argv[1])
