"""The knee of a served cell, in one process on the chip:

    python benchmark/tests/knee.py --workload <cell> --rates 0.6,0.8,1.0,1.2,1.4 --seconds 40

Builds the cell's system once and warms it as a run does, then offers the
mix's open-loop arrivals at each rate in turn (the mix's own schedule
seed; each rate lets its requests finish before the next begins) and
prints, per rate: requests offered and answered, answers a second over
the whole of the rate's run (first arrival to last answer), the time the
last answer came after the last arrival, the latency's median, 95th
percentile and its mean over the first and the second half of the
arrivals (a queue that grows shows there), and the service's slab count
and columns. The knee is the highest rate the service sustains: the
answers a second where the offered rate passes them. Not run by the
benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="requests a second, comma-separated")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=2_300_000_011)
    args = ap.parse_args()

    from benchmark import run as R

    cell = R.load_cell(R.read_json(ROOT, "BENCHMARK.json"), args.workload)
    devices, _peaks = R.find_chips(cell)
    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu import telemetry

    pa.enable_compilation_cache()
    builder = R.by_name("builders", cell.cfg["builder"])
    driver = R.by_name("drivers", cell.mix["driver"])
    backend = pa.TPUBackend(devices=list(devices))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, f"knee_{cell.name}.jsonl"), "w")

    def say(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    def body(parts):
        system = builder.build(pa, parts, cell.cfg, cell.mix)
        pool = system.make_pool(args.seed)

        def solve(req):
            try:
                return system.solve(req)
            except Exception as e:
                print(f"knee: solve failed: {type(e).__name__}: {e}",
                      file=sys.stderr, flush=True)
                return None, {"iterations": 0, "converged": False}

        _x, info = solve(pool[0])  # warms every width, starts the worker
        del _x
        say({"who": "warm-up", "iterations": info.get("iterations")})
        for rate in (float(r) for r in args.rates.split(",")):
            pool.arrivals = dict(pool.arrivals, rate_per_s=rate)
            c0 = telemetry.counters("service")
            records = driver.run(solve, pool, args.seconds)
            c1 = telemetry.counters("service")
            lat = [r["t_done"] - r["t_issue"] for r in records]
            n = len(records)
            first, last = records[0]["t_issue"], records[-1]["t_issue"]
            end = max(r["t_done"] for r in records)
            say({
                "who": "rate", "rate_per_s": rate, "offered": n,
                "answered": sum(1 for r in records if r["info"].get("converged")),
                "answers_per_s": n / (end - first),
                "drain_s": end - last,
                "latency_p50_s": R.percentile_nearest_rank(lat, 0.5),
                "latency_p95_s": R.percentile_nearest_rank(lat, 0.95),
                "latency_mean_first_half_s": sum(lat[: n // 2]) / max(1, n // 2),
                "latency_mean_second_half_s": sum(lat[n // 2:]) / max(1, n - n // 2),
                "slabs": c1.get("service.slabs", 0) - c0.get("service.slabs", 0),
                "slab_columns": c1.get("service.slab_columns", 0)
                - c0.get("service.slab_columns", 0),
                "stats": dict(system.service.stats),
            })

    pa.prun(body, backend, tuple(cell.cfg["part_grid"]))
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
