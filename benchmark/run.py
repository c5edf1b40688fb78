"""The benchmark's command: one process, one cell, one run.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of `workloads` in `BENCHMARK.json`. Everything that
belongs to one configuration, one traffic mix, one loop kind or one
per-layer metric is a file that this program finds BY NAME:

    configs/<config>.json      sizes, dtype, tolerance, and its `builder`
    builders/<builder>.py      the system through the public API + the plain reference
    traffic/<mix>.json         driver, solver entry, pool, sample, traced stretch, limits
    drivers/<driver>.py        the loop that offers the load
    layer_metrics/<name>.py    reduce(run) -> value, or None where it finds nothing
    peaks.json                 the chip's peaks by device_kind

so a later PR adds files and entries and edits nothing here. It runs only
on a TPU that has the chips the cell asks for, and says what it found
otherwise. The last line of stdout is the result; the numbers that decide
`correct` are also the last lines of stderr, each beside its limit.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse
import importlib
import json
import math
import os
import shutil
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: Fixed, inside the checkout, ignored by git. Holds the newest trace only:
#: a traced run empties it before it starts the profiler.
TRACE_DIR = os.path.join(HERE, ".trace")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def by_name(kind: str, name: str):
    """The module `benchmark/<kind>/<name>.py`."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    return importlib.import_module(f"benchmark.{kind}.{name}")


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------


def metrics_of(entries: list, cell: str) -> list:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def load_cell(manifest: dict, workload: str) -> types.SimpleNamespace:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no cell {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    config = next(c for c in manifest["configs"] if c["name"] == w["config"])
    return types.SimpleNamespace(
        name=workload, chips=int(w["chips"]),
        cfg=read_json(ROOT, config["file"]),
        mix=read_json(HERE, "traffic", w["traffic"] + ".json"),
        end_to_end=metrics_of(manifest["end_to_end"], workload),
        per_layer=metrics_of(manifest["per_layer"], workload),
    )


def find_chips(cell):
    """The devices the cell runs on and the chip's peaks, or exit non-zero
    naming what JAX found. No option makes this pass anywhere else."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, and JAX found {devices}")
    if len(devices) < cell.chips:
        raise SystemExit(
            f"bench: cell {cell.name} needs {cell.chips} chips, and JAX found "
            f"{len(devices)}: {devices}"
        )
    peaks = read_json(HERE, "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise SystemExit(
            f"bench: no peaks for device_kind {kind!r} in peaks.json "
            f"(knows {sorted(peaks)})"
        )
    return devices[: cell.chips], peaks[kind]


# ---------------------------------------------------------------------------
# compilations inside the window
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts what JAX compiles or loads: its own compile-duration events
    and the program's `persistent_cache.{hit,miss}` counters."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, telemetry):
        import jax.monitoring

        self.telemetry, self.events = telemetry, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == self.EVENT:
            self.events += 1

    def read(self) -> dict:
        cache = self.telemetry.counters("persistent_cache")
        return {
            "compile_events": self.events,
            "cache_hit": int(cache.get("persistent_cache.hit", 0)),
            "cache_miss": int(cache.get("persistent_cache.miss", 0)),
        }


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def percentile_nearest_rank(values: list, q: float) -> float:
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


class Sample:
    """A reservoir of the window's answers, drawn from the seed: what the
    reference judges once the window has closed. An answer that is drawn is
    copied into one of ``len(slots)`` places made in set-up (`keep(x,
    slot)`), so the harness holds no answer of the program alive."""

    def __init__(self, slots: list, keep, seed: int):
        import numpy as np

        self.slots, self.keep, self.which = slots, keep, []
        self.rng = np.random.default_rng([int(seed), 0x5A])

    def offer(self, i: int, k: int, x) -> None:
        if len(self.which) < len(self.slots):
            j = len(self.which)
            self.which.append((i, k))
        else:
            j = int(self.rng.integers(0, i + 1))
            if j >= len(self.slots):
                return
            self.which[j] = (i, k)
        self.keep(x, self.slots[j])

    def answers(self) -> list:
        """``[(i, k, slot)]`` in the order the solves were made."""
        return sorted(
            ((i, k, slot) for (i, k), slot in zip(self.which, self.slots)),
            key=lambda a: a[0],
        )


def run_cell(cell, devices, peaks, seed: int, seconds: float, trace: bool,
             t_process: float) -> dict:
    """Everything of a run behind the look for a chip: set-up, window,
    the judging of the answers, and the result as a dict."""
    import jax

    # where set-up goes, for the `run` record: seconds since the process began
    stamps = {"jax_and_chip": time.perf_counter() - t_process}
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu import telemetry

    stamps["import_program"] = time.perf_counter() - t_process

    cache_dir = pa.enable_compilation_cache()
    compiles = CompileCounter(telemetry)
    builder = by_name("builders", cell.cfg["builder"])
    driver = by_name("drivers", cell.mix["driver"])
    backend = pa.TPUBackend(devices=list(devices))
    grid = tuple(int(g) for g in cell.cfg["part_grid"])
    if not math.prod(grid) == len(devices) == cell.chips:
        raise SystemExit(
            f"bench: part grid {grid}, {len(devices)} devices and the cell's "
            f"{cell.chips} chips do not agree"
        )

    def body(parts):
        # -- set-up ---------------------------------------------------------
        system = builder.build(pa, parts, cell.cfg, cell.mix)
        stamps["build"] = time.perf_counter() - t_process
        pool = system.make_pool(seed)
        stamps["pool"] = time.perf_counter() - t_process

        def solve(req):
            with jax.profiler.TraceAnnotation("bench:solve"):
                try:
                    return system.solve(req)
                except Exception as e:  # a failed solve is counted, not hidden
                    log(f"solve failed: {type(e).__name__}: {e}")
                    return None, {"iterations": 0, "converged": False}

        sample = Sample(
            system.new_slots(int(cell.mix["check_sample"])), system.keep, seed
        )
        t0 = time.perf_counter()
        _x, warm_info = solve(pool[0])  # stages, lowers, compiles or loads
        del _x
        timings = {
            "assemble_s": system.assemble_s,
            "first_solve_s": time.perf_counter() - t0,
        }
        at_open = compiles.read()
        setup_s = stamps["warm_up_solve"] = time.perf_counter() - t_process
        log(f"set-up {setup_s:.3f} s {json.dumps({**timings, **at_open})}; "
            f"warm-up solve: {warm_info.get('iterations')} iterations")

        # -- the window -----------------------------------------------------
        # a traced run profiles a short stretch of the same window: from the
        # mix's `skip_solves`-th solve on, `solves` solves or the first that
        # ends past `seconds`, whichever comes first
        tspec = cell.mix["trace"]
        first = int(tspec["skip_solves"])
        tracing = {"on": False, "t0": 0.0, "last": None}

        def before(i):
            if trace and i == first:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
                tracing.update(on=True, t0=time.perf_counter())

        def after(i, k, x, info):
            if tracing["on"] and (
                i - first + 1 >= int(tspec["solves"])
                or time.perf_counter() - tracing["t0"] >= float(tspec["seconds"])
            ):
                jax.profiler.stop_trace()
                tracing.update(on=False, last=i)
            if x is not None:
                sample.offer(i, k, x)

        records = driver.run(solve, pool, seconds, before=before, after=after)
        if tracing["on"]:  # the window closed inside the traced stretch
            jax.profiler.stop_trace()
            tracing.update(on=False, last=records[-1]["i"])
        in_window = {k: v - at_open[k] for k, v in compiles.read().items()}
        if in_window["compile_events"] or in_window["cache_miss"]:
            log(f"COMPILED INSIDE THE WINDOW: {json.dumps(in_window)}")
        memory_peak = system.device_bytes_peak()

        # -- end-to-end metrics ----------------------------------------------
        times = [r["t_done"] - r["t_issue"] for r in records]
        window_s = records[-1]["t_done"] - records[0]["t_issue"]
        values = {
            "setup_s": setup_s,
            "solve_s": window_s / len(records),
            "solve_p95_s": percentile_nearest_rank(times, 0.95),
        }

        # -- what decides `correct` (the window is closed, the peak is read) --
        # a solve that raised has left a record that says "not converged"
        unanswered = sum(
            1 for r in records if not r["info"].get("converged", False)
        )
        t0 = time.perf_counter()
        checked = [
            (i, system.check(pool[k], slot)) for i, k, slot in sample.answers()
        ]
        compared = {"unanswered": unanswered}
        for _i, numbers in checked:
            for name, v in numbers.items():
                compared[name] = max(compared.get(name, 0.0), v)
        limits = cell.mix["limits"]
        missing = sorted(set(limits) - set(compared))
        correct = bool(checked) and not missing and all(
            compared[name] <= limits[name] for name in limits
        )
        check_s = time.perf_counter() - t0

        # -- per-layer metrics -------------------------------------------------
        # all that a reader under layer_metrics/ may read: later PRs add
        # readers and cannot edit this file
        run = types.SimpleNamespace(
            trace=None,
            traced_records=[
                r for r in records
                if tracing["last"] is not None and first <= r["i"] <= tracing["last"]
            ],
            timings=timings, cfg=cell.cfg, mix=cell.mix, peaks=peaks,
            chips=cell.chips, dofs_per_chip=system.dofs_per_chip,
            itemsize=system.dtype.itemsize, records=records,
        )
        device = {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak,
        }
        result = {"correct": correct, "attempted": len(records),
                  "failed": unanswered}
        if trace:
            tr = importlib.import_module("benchmark.trace")
            if tracing["last"] is not None:  # else the window closed too early
                run.trace = tr.read(tr.find_xplane(TRACE_DIR))
            layer = {}
            for m in cell.per_layer:
                v = by_name("layer_metrics", m["name"]).reduce(run)
                if v is not None:
                    layer[m["name"]] = {"value": v, "unit": m["unit"]}
            st = tr.stretch(run.trace) if run.trace is not None else None
            if st is not None and run.trace.device_ops:
                device["busy_s"] = tr.mean_busy(run.trace, *st)
                device["window_s"] = st[1] - st[0]
            result.update(metrics=layer, device=device)
            if run.trace is not None:
                result["breakdown"] = tr.breakdown(run.trace)
            result["end_to_end"] = {
                m["name"]: values[m["name"]] for m in cell.end_to_end
            }
        else:
            result.update(
                metrics={
                    m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                    for m in cell.end_to_end
                },
                device=device,
            )
        its = [int(r["info"].get("iterations", 0)) for r in records]
        result["run"] = {
            "cell": cell.name, "seed": seed, "seconds": seconds,
            "window_s": window_s, "solves": len(records),
            "iterations_min": min(its), "iterations_max": max(its),
            "solve_time_min": min(times), "solve_time_median": sorted(times)[len(times) // 2],
            "solve_time_max": max(times), "between_solves_s": window_s - sum(times),
            "checked": len(checked), "check_s": check_s,
            "compiles_in_window": in_window, "compile_cache_dir": cache_dir,
            "setup_stamps_s": stamps, **timings,
        }
        result["compared"] = {
            name: {"value": compared.get(name), "limit": limits[name]}
            for name in limits
        }
        return result

    return pa.prun(body, backend, grid)


def emit(result: dict) -> None:
    """The compared numbers as the last lines of stderr, the result as the
    last line of stdout."""
    sys.stdout.flush()
    for name, c in result["compared"].items():
        print(f"bench: compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"bench: correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(read_json(ROOT, "BENCHMARK.json"), args.workload)
    devices, peaks = find_chips(cell)
    emit(run_cell(cell, devices, peaks, args.seed, args.seconds,
                  bool(args.trace), T_PROCESS))
    return 0


if __name__ == "__main__":
    sys.exit(main())
