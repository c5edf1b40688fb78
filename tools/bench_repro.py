"""Reproducibility study for bench.py's metrics: run the halo and SpMV
legs K times each in ONE process and print the distribution, so a
documented range comes from a measured spread instead of round-to-round
anecdotes, and so a swing in the halo value/ratio (round 3 read
11.1 GB/s / 137x in one session and 20.3 GB/s / 65.4x in another) can
be attributed to the device numerator or the host-oracle denominator.

The committed record (``docs/repro_r5.json`` by default) goes through
the shared schema-versioned artifact writer (`telemetry.artifacts`),
the same envelope every committed bench artifact carries;
tests/test_doc_consistency.py checks it. ``--dry-run`` prints without
committing.

    python tools/bench_repro.py          # 5 reps each, ~10 min on chip
    PA_REPRO_REPS=8 python tools/bench_repro.py
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax

    import bench
    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.parallel.tpu import TPUBackend

    reps = int(os.environ.get("PA_REPRO_REPS", "5"))
    n = int(os.environ.get("PA_BENCH_N", "192"))
    pa.enable_compilation_cache()
    backend = TPUBackend(devices=jax.devices()[:1])
    out = {"n": n, "reps": reps, "halo": [], "halo_host_oracle": [],
           "spmv": [], "methodology": bench.METHODOLOGY}

    # --- halo leg, reps times (device numerator AND host denominator
    # recorded separately per rep) --------------------------------------
    for r in range(reps):
        rec = bench.bench_halo(n, backend, pa)
        out["halo"].append(rec["value"])
        out["halo_host_oracle"].append(rec["host_oracle_bytes_per_s"])
        print(f"halo rep {r}: {rec['value']/1e9:.2f} GB/s device, "
              f"{rec['host_oracle_bytes_per_s']/1e6:.1f} MB/s host",
              flush=True)

    # --- SpMV leg, reps times, via the SHIPPED chain builder -----------
    run_chain, _A, _x, _dA, flops = bench.spmv_chain(n, backend, pa)
    for r in range(reps):
        dt = bench.marginal_chain_time(run_chain, 50, 450)
        g = flops / dt / 1e9
        out["spmv"].append(round(g, 1))
        print(f"spmv rep {r}: {g:.1f} GFLOP/s", flush=True)

    for k in ("halo", "halo_host_oracle", "spmv"):
        v = out[k]
        out[k + "_stats"] = {
            "min": min(v), "max": max(v),
            "median": statistics.median(v),
            "spread_pct": round(100 * (max(v) - min(v)) / statistics.median(v), 1),
        }
    print(json.dumps(out, indent=1), flush=True)
    from partitionedarrays_jl_tpu.telemetry import artifacts

    name = os.environ.get("PA_REPRO_NAME", "repro_r5.json")
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", name)
    artifacts.write(
        path, out, tool="bench_repro", dry_run="--dry-run" in sys.argv[1:]
    )


if __name__ == "__main__":
    main()
