"""Look at a trace by hand: planes, lines, and the first events of each.

    python benchmark/tests/dump_trace.py <file.xplane.pb>
"""
import sys

from jax.profiler import ProfileData


def main(path: str) -> None:
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:6]:
                print(f"    {ev.name[:90]!r} start={ev.start_ns} dur={ev.duration_ns}")


if __name__ == "__main__":
    main(sys.argv[1])
