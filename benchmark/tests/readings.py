"""The readings a cell's limits are set from, in one process on the chip:

    python benchmark/tests/readings.py --workload <cell> --seeds 12 --control-seeds 3

Builds the cell's system once, then for each seed draws the pool as a run
does and solves its entries through the timed entry (`system.solve`), and
prints what `system.check` reads for every answer: the LOWER reading is
the largest of these. Then, on `--control-seeds` seeds, the control (the
plain reference CG in the precision below the configuration's, in the
program's place) through the same check: the UPPER reading is the smallest
of these. `--witness 1` adds the reference CG in the configuration's own
precision as a second witness beside the program. Not run by the benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--entries", type=int, default=0, help="pool entries per seed; 0 = all")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--witness", type=int, default=1)
    args = ap.parse_args()

    from benchmark import run as R

    cell = R.load_cell(R.read_json(ROOT, "BENCHMARK.json"), args.workload)
    devices, _peaks = R.find_chips(cell)
    import partitionedarrays_jl_tpu as pa

    pa.enable_compilation_cache()
    builder = R.by_name("builders", cell.cfg["builder"])
    backend = pa.TPUBackend(devices=list(devices))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, f"readings_{cell.name}.jsonl"), "w")

    def say(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    def body(parts):
        system = builder.build(pa, parts, cell.cfg, cell.mix)
        slot = system.new_slots(1)[0]

        def judge(req, x):
            system.keep(x, slot)
            return system.check(req, slot)

        lower, upper = [], []
        for s in range(args.seeds):
            seed = args.first_seed + 7919 * s
            pool = system.make_pool(seed)
            for k, req in enumerate(pool[: args.entries or len(pool)]):
                t0 = time.perf_counter()
                x, info = system.solve(req)
                dt = time.perf_counter() - t0
                numbers = judge(req, x)
                lower.append(numbers)
                say({"who": "program", "seed": seed, "k": k, "sym": repr(req.sym),
                     "iterations": int(info["iterations"]),
                     "converged": bool(info["converged"]), "solve_s": dt,
                     **numbers})
        ctl = cell.mix["control"]
        who = [("control", ctl["dtype"])] * args.control_seeds
        if args.witness:
            who.append(("witness", cell.cfg["dtype"]))
        for s, (name, dtype) in enumerate(who):
            seed = args.first_seed + 104729 * (s + 1)
            req = system.make_pool(seed)[0]
            t0 = time.perf_counter()
            x, info = system.control_solve(req, dtype, int(ctl["maxiter"]))
            dt = time.perf_counter() - t0
            numbers = judge(req, x)
            if name == "control":
                upper.append(numbers)
            say({"who": name, "dtype": dtype, "seed": seed, "solve_s": dt,
                 **info, **numbers})
        names = sorted(lower[0])
        say({"who": "summary", "cell": cell.name,
             "lower": {n: max(r[n] for r in lower) for n in names},
             "upper": {n: min(r[n] for r in upper) for n in names} if upper else None,
             "limits": cell.mix.get("limits")})

    pa.prun(body, backend, tuple(cell.cfg["part_grid"]))
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
