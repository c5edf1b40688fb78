#!/usr/bin/env python
"""s-step / overlap CG A/B bench -> SSTEP_BENCH.json.

The communication-avoiding PR's perf artifact, same discipline as the
ABFT and OBS ones: per-iteration cost of the compiled CG program in
its three single-RHS shapes on one multi-part mesh —

* ``standard``   the textbook body (the strict-bits oracle): 2 scalar
                 all_gather fold-dots per iteration;
* ``sstep2``     the s-step body at depth `SSTEP` (``PA_TPU_SSTEP``):
                 ONE block all_gather per s-iteration trip carrying
                 the (2s+1)-wide Gram payload;
* ``overlap``    the interior/boundary overlap body
                 (``PA_TPU_OVERLAP``): same collectives as standard,
                 interior SpMV scheduled against the in-flight halo.

Protocol: the differenced marginal of tools/bench_cg.py —
each body compiled ONCE per maxiter leg (tol=0 pins the trip count),
warmed, median-of-5 executions per leg, two legs differenced, median
of 3 rounds. The whole solve is one `lax.while_loop` ending in host
scalar fetches, so a K-iteration program IS a K-step dependency chain.

Bands: the device knee (`SSTEP_BANDS`) demands the s-step body win
>= 1.15x per iteration on real TPUs, where the two scalar-gather
latencies it removes dominate small-N steps (docs/performance.md);
the overlap body must at worst break even. Device-kind bands gate
only records measured on real TPUs — a cpu-platform record leaves
them unmeasured (``in_band: null``) and instead records wide
canary-kind sanity bands: XLA-CPU "collectives" are memcpys, so host
speedups carry no signal about the ICI win (the established ABFT/OBS
gating). ``tools/pareg.py`` folds the committed artifact into
PERF_LEDGER.json.

Usage:
    python tools/bench_sstep.py            # refresh SSTEP_BENCH.json
    python tools/bench_sstep.py --dry-run  # print without writing
"""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np

METHODOLOGY = "v1-sstep"

#: The s-step depth the artifact measures — the depth the committed
#: lowering-matrix case pins (tags {"body": "sstep", "s": 2}).
SSTEP = 2

#: Guard bands for the committed artifact; keys match
#: SSTEP_BENCH.json["bands"] (tests/test_doc_consistency.py asserts
#: the committed artifact and this table agree). The 1.15 floor IS the
#: acceptance knee: on device the s-step body must buy at least 15%
#: per iteration where gather latency dominates.
SSTEP_BANDS = {
    "sstep2_speedup_vs_standard": (1.15, 32.0, "device"),
    "overlap_speedup_vs_standard": (1.0, 32.0, "device"),
}

#: Wide sanity bounds for the cpu-canary rows: they pin "the variant
#: compiles, runs its fixed trips, and times within a sane ratio of
#: the textbook body", never a perf claim (XLA-CPU collectives are
#: memcpys).
CANARY_BANDS = {
    "sstep2_speedup_cpu_canary": (0.05, 50.0, "canary"),
    "overlap_speedup_cpu_canary": (0.05, 50.0, "canary"),
}

#: Probe geometry: a (2,2) box partition so every body pays real halo
#: exchange and fold-dot collectives.
PARTS = (2, 2)
DEVICE_NS, DEVICE_K = (512, 512), (40, 240)
HOST_NS, HOST_K = (32, 32), (24, 120)


def _mesh():
    """Device mesh setup: the host-device-count flag must land before
    jax initializes its backends (harmless on real TPUs — it only
    shapes the cpu platform)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        # host canary leg: f64 so the measured bodies match the
        # conformance dtype (x64 update is safe post-init)
        jax.config.update("jax_enable_x64", True)
    return jax, platform


def measure(make_cg_fn, dA, db, dx0, k0, k1, **kwargs) -> float:
    """One body's differenced per-iteration marginal (module
    docstring protocol)."""
    solves = {
        k: make_cg_fn(dA, tol=0.0, maxiter=k, **kwargs)
        for k in (k0, k1)
    }
    for s in solves.values():  # warm: the solve ends in host scalars
        _ = float(np.asarray(s(db, dx0, None)[1]).ravel()[0])

    def run_k(k):
        solve = solves[k]
        ts = []
        for _i in range(5):
            t0 = time.perf_counter()
            out = solve(db, dx0, None)
            _ = float(np.asarray(out[1]).ravel()[0])  # close the chain
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    per_it = []
    for _round in range(3):
        t0, t1 = run_k(k0), run_k(k1)
        per_it.append((t1 - t0) / (k1 - k0))
    return float(np.median(per_it))


def main():
    argv = sys.argv[1:]
    dry = "--dry-run" in argv
    jax, platform = _mesh()

    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.models import assemble_poisson
    from partitionedarrays_jl_tpu.parallel.tpu import (
        DeviceVector, TPUBackend, device_matrix, make_cg_fn,
    )
    from partitionedarrays_jl_tpu.telemetry import artifacts

    ns = DEVICE_NS if platform == "tpu" else HOST_NS
    k0, k1 = DEVICE_K if platform == "tpu" else HOST_K
    dtype = "float32" if platform == "tpu" else "float64"
    if "--n" in argv:
        n = int(argv[argv.index("--n") + 1])
        ns = (n, n)
    pa.enable_compilation_cache()
    backend = TPUBackend(devices=jax.devices()[: int(np.prod(PARTS))])

    def fixture(parts):
        A, b, _xe, x0 = assemble_poisson(parts, ns)
        if dtype == "float32":
            A.values = pa.map_parts(
                lambda M: pa.CSRMatrix(
                    M.indptr, M.indices,
                    np.asarray(M.data, np.float32), M.shape,
                ),
                A.values,
            )
            A.invalidate_blocks()
            for v in (b, x0):
                v.values = pa.map_parts(
                    lambda x: np.asarray(x, np.float32), v.values
                )
        return A, b, x0

    A, b, x0 = pa.prun(fixture, backend, PARTS)
    dA = device_matrix(A, backend)
    db = DeviceVector.from_pvector(b, backend, dA.col_layout).data
    dx0 = DeviceVector.from_pvector(x0, backend, dA.col_layout).data

    bodies = {}
    dt_std = measure(make_cg_fn, dA, db, dx0, k0, k1, fused=False)
    bodies["standard"] = {"s_per_it": round(dt_std, 9)}
    print(f"[bench_sstep] standard: {dt_std * 1e6:.1f} us/it", flush=True)
    for label, kwargs in (
        (f"sstep{SSTEP}", dict(sstep=SSTEP)),
        ("overlap", dict(fused=False, overlap=True)),
    ):
        dt = measure(make_cg_fn, dA, db, dx0, k0, k1, **kwargs)
        bodies[label] = {
            "s_per_it": round(dt, 9),
            "speedup_vs_standard": round(dt_std / dt, 4),
        }
        print(
            f"[bench_sstep] {label}: {dt * 1e6:.1f} us/it "
            f"speedup_vs_standard={dt_std / dt:.3f}x",
            flush=True,
        )

    bands = {}
    for key, (lo, hi, kind) in SSTEP_BANDS.items():
        body = key.split("_speedup", 1)[0]
        measured = (
            bodies[body]["speedup_vs_standard"]
            if platform == "tpu" else None
        )
        bands[key] = {
            "lo": lo, "hi": hi, "kind": kind, "measured": measured,
            "in_band": (
                None if measured is None else bool(lo <= measured <= hi)
            ),
        }
    if platform != "tpu":
        for key, (lo, hi, kind) in CANARY_BANDS.items():
            body = key.split("_speedup", 1)[0]
            measured = bodies[body]["speedup_vs_standard"]
            bands[key] = {
                "lo": lo, "hi": hi, "kind": kind, "measured": measured,
                "in_band": bool(lo <= measured <= hi),
            }

    # the policy tie-in: what depth the committed spectrum store would
    # suggest for its measured operator classes (telemetry.suggest_s)
    policy = None
    spec_path = os.path.join(REPO, "SPECTRUM.json")
    if os.path.exists(spec_path):
        from partitionedarrays_jl_tpu import telemetry

        policy = []
        for e in json.load(open(spec_path)).get("entries") or []:
            pol = telemetry.suggest_s(
                {"kappa": e.get("kappa"), "rate": e.get("rate"),
                 "samples": e.get("samples", 1)},
                e["dtype"], tol=1e-8,
            )
            policy.append({
                "fingerprint": e["fingerprint"],
                "dtype": e["dtype"],
                "minv_class": e["minv_class"],
                "suggested_s": pol["s"],
                "policy": pol["policy"],
                "kappa": pol["kappa"],
                "gather_factor": pol["gather_factor"],
                "forecast": pol.get("forecast"),
            })

    rec = {
        "methodology": METHODOLOGY,
        "protocol": (
            "differenced compiled-CG marginal (tools/bench_cg.py "
            "discipline): per body, two maxiter legs compiled once, "
            "warmed, median-of-5 executions, differenced, median of 3 "
            "rounds; tol=0 pins the trip count"
        ),
        "platform": platform,
        "dtype": dtype,
        "operator": (
            f"Poisson FDM on a {ns} grid, ({PARTS[0]},{PARTS[1]}) box "
            "partition — every body pays real halo cpermutes and "
            "fold-dot gathers"
        ),
        "sstep": SSTEP,
        "maxiter_legs": [k0, k1],
        "bodies": bodies,
        "suggest_s": policy,
        "bands": bands,
        "bands_ok_device": (
            all(
                b["in_band"]
                for b in bands.values()
                if b["kind"] == "device" and b["measured"] is not None
            )
            if platform == "tpu"
            else None
        ),
        "note": (
            "device-kind bands gate records measured on real TPUs; a "
            "cpu-platform record is the structural canary (the "
            "variants compile, run their pinned trips, and time "
            "within sane ratios), never the acceptance number — "
            "XLA-CPU lowers the gathers the s-step body removes to "
            "memcpys, so host speedups carry no ICI-latency signal"
        ),
    }
    artifacts.write(
        os.path.join(REPO, "SSTEP_BENCH.json"), rec, tool="bench_sstep",
        dry_run=dry,
    )


if __name__ == "__main__":
    main()
