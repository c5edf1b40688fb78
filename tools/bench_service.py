"""Solve-service throughput bench -> SERVICE_BENCH.json +
THROUGHPUT_MODEL.json.

Three legs, honestly separated:

* **measured service rows** — requests/s THROUGH the service (submit K
  compatible requests, drain: admission + coalescing + the compiled
  block slab + result plumbing) vs K sequential solo solves, at
  K ∈ {1, 4, 8, 16}, fixed trip count (tol far below the dtype floor
  keeps every column active to maxiter, the same trick as the multirhs
  protocol). These rows measure what the SERVICE adds on THIS platform
  — dispatch, batching, verdict reads — and on a CPU host they are an
  overhead canary, not a device throughput claim.
* **inherited device bands** — the per-RHS speedup the slab itself
  delivers is a property of the compiled block program, which the
  service feeds UNCHANGED (tests/test_service.py pins HLO collective
  parity against the bare block body, and the service adds zero
  per-iteration work). The acceptance number therefore inherits from
  the committed MULTIRHS_BENCH.json device record — the K=8 ≥ 1.5×
  floor IS the ROADMAP item-1 / round-7 acceptance floor — and
  `tests/test_doc_consistency.py` asserts the inherited values equal
  the MULTIRHS record's measured values (cross-artifact traceability),
  so this artifact can never silently drift from its source.
* **metrics-on/off marginal** (round 12 / pamon) — the K=8 drained leg
  re-run with the observability plane killed (``PA_MON=0``): the
  requests/s ratio on/off is the measured cost of the metric registry
  + throughput model on the service hot path, banded in
  ``metrics_on_off_ratio`` (a host-platform canary band — the
  structural claim is "metrics are host-side and cheap", the
  byte-identical-program pin lives in tests/test_pamon.py).
* **tracing-on/off marginal** (round 16 / patx) — the same K=8 leg
  with every request carrying a trace context, span capture on vs
  killed (``PA_TX=0``): the measured cost of the distributed-tracing
  plane on the hot path, banded in ``tracing_on_off_ratio`` (same
  canary convention; the byte-identical-program pin lives in
  tests/test_patx.py).

The PA_MON-on service legs also FEED the online throughput model
(`telemetry.throughput`): after the sweep this tool exports the
accumulated measured s_per_it(K) table as ``THROUGHPUT_MODEL.json``
(shared artifacts envelope) next to the MULTIRHS device reference
curve — the committed form of the adaptive-K input, cross-checked by
`tests/test_doc_consistency.py` at overlapping K.

``--dry-run`` prints without writing; ``--n`` overrides the local
measurement size (smoke).
"""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np

#: Guard bands for the committed artifact. The measured values are the
#: INHERITED MULTIRHS per-RHS speedups (see module docstring); the K=8
#: floor of 1.5 is the acceptance criterion. Bounds match
#: tools/bench_multirhs.py MULTIRHS_BANDS by construction.
SERVICE_BANDS = {
    "per_rhs_gain_k8": (1.5, 2.2, "device"),
    "per_rhs_gain_k16": (1.55, 2.4, "device"),
}

#: The metrics-on/off requests/s ratio band (on/off ≈ 1: the registry
#: is invisible on the hot path). A HOST canary, not a device claim —
#: committed records must fall inside, but the kind keeps it out of
#: `bands_ok_device`; generous bounds absorb CPU wall-clock noise on a
#: sub-second leg.
METRICS_BANDS = {
    "metrics_on_off_ratio": (0.7, 1.3, "canary"),
}

#: The tracing-on/off requests/s ratio band (round 16 / patx): the K=8
#: drained leg with every request carrying a trace context, span plane
#: on vs killed (``PA_TX=0``). Same canary convention as the metrics
#: marginal — the structural claim (byte-identical programs, host-only
#: capture) is pinned in tests/test_patx.py; this band keeps the
#: measured hot-path cost recorded and ledgered.
TRACING_BANDS = {
    "tracing_on_off_ratio": (0.7, 1.3, "canary"),
}

METHODOLOGY = "v3-service-tx"

KS = (1, 4, 8, 16)

#: Fixed trip count for the local requests/s legs.
TRIPS = 40


def _service_leg(pa, A, x0, bs, tol, maxiter, kmax, traced=False):
    """One drained service run over ``bs``; returns wall seconds.
    ``traced`` submits every request under a fresh trace context (the
    gate's propagation path) so the span plane's hot-path cost is on
    the clock — with ``PA_TX=0`` the same submits take the inert
    path, which is exactly the tracing marginal's A/B."""
    from partitionedarrays_jl_tpu.service import SolveService
    from partitionedarrays_jl_tpu.telemetry import tracing

    svc = SolveService(A, kmax=kmax)
    t0 = time.perf_counter()
    handles = [
        svc.submit(
            b, x0=x0, tol=tol, maxiter=maxiter,
            trace=(
                tracing.mint_trace()
                if traced and tracing.tracing_enabled() else None
            ),
        )
        for b in bs
    ]
    svc.drain()
    wall = time.perf_counter() - t0
    for h in handles:
        h.result()  # surface any failure loudly
    return wall


def _solo_leg(pa, A, x0, bs, tol, maxiter):
    from partitionedarrays_jl_tpu.parallel.tpu import tpu_cg

    t0 = time.perf_counter()
    for b in bs:
        tpu_cg(A, b, x0=x0, tol=tol, maxiter=maxiter)
    return time.perf_counter() - t0


def measure_rows(pa, A, x0, rhs_pool, tol, maxiter, reps=3):
    rows = []
    for K in KS:
        bs = [rhs_pool[i % len(rhs_pool)] for i in range(K)]
        # warm both legs (compile), then median of reps
        _service_leg(pa, A, x0, bs, tol, maxiter, kmax=K)
        _solo_leg(pa, A, x0, bs, tol, maxiter)
        service = sorted(
            _service_leg(pa, A, x0, bs, tol, maxiter, kmax=K)
            for _ in range(reps)
        )[reps // 2]
        solo = sorted(
            _solo_leg(pa, A, x0, bs, tol, maxiter) for _ in range(reps)
        )[reps // 2]
        rows.append(
            {
                "K": K,
                "service_wall_s": round(service, 9),
                "solo_wall_s": round(solo, 9),
                "service_requests_per_s": round(K / service, 6),
                "solo_requests_per_s": round(K / solo, 6),
                "service_vs_solo": round(solo / service, 3),
            }
        )
    return rows


def measure_metrics_marginal(pa, A, x0, rhs_pool, tol, maxiter, reps=3):
    """The K=8 drained leg, metrics plane on vs killed (PA_MON=0):
    what the registry + throughput model cost on the service hot
    path."""
    K = 8
    bs = [rhs_pool[i % len(rhs_pool)] for i in range(K)]

    def leg():
        return sorted(
            _service_leg(pa, A, x0, bs, tol, maxiter, kmax=K)
            for _ in range(reps)
        )[reps // 2]

    _service_leg(pa, A, x0, bs, tol, maxiter, kmax=K)  # warm
    on = leg()
    prev = os.environ.get("PA_MON")
    os.environ["PA_MON"] = "0"
    try:
        _service_leg(pa, A, x0, bs, tol, maxiter, kmax=K)
        off = leg()
    finally:
        if prev is None:
            os.environ.pop("PA_MON", None)
        else:
            os.environ["PA_MON"] = prev
    return {
        "K": K,
        "on_requests_per_s": round(K / on, 6),
        "off_requests_per_s": round(K / off, 6),
        "ratio_on_off": round(off / on, 3),
    }


def measure_tracing_marginal(pa, A, x0, rhs_pool, tol, maxiter, reps=3):
    """The K=8 drained leg with per-request trace contexts, span plane
    on vs killed (``PA_TX=0``): what patx span capture costs on the
    service hot path (round 16)."""
    K = 8
    bs = [rhs_pool[i % len(rhs_pool)] for i in range(K)]

    def leg():
        return sorted(
            _service_leg(pa, A, x0, bs, tol, maxiter, kmax=K,
                         traced=True)
            for _ in range(reps)
        )[reps // 2]

    _service_leg(pa, A, x0, bs, tol, maxiter, kmax=K, traced=True)
    on = leg()
    prev = os.environ.get("PA_TX")
    os.environ["PA_TX"] = "0"
    try:
        _service_leg(pa, A, x0, bs, tol, maxiter, kmax=K, traced=True)
        off = leg()
    finally:
        if prev is None:
            os.environ.pop("PA_TX", None)
        else:
            os.environ["PA_TX"] = prev
    return {
        "K": K,
        "on_requests_per_s": round(K / on, 6),
        "off_requests_per_s": round(K / off, 6),
        "ratio_on_off": round(off / on, 3),
    }


def main():
    import importlib.util

    import jax

    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.parallel.tpu import TPUBackend

    argv = sys.argv[1:]
    dry = "--dry-run" in argv
    n = int(os.environ.get("PA_BENCH_N", "48"))
    if "--n" in argv:
        n = int(argv[argv.index("--n") + 1])

    spec = importlib.util.spec_from_file_location(
        "bench_multirhs", os.path.join(REPO, "tools", "bench_multirhs.py")
    )
    bm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bm)

    pa.enable_compilation_cache()

    backend = TPUBackend(devices=jax.devices()[:1])
    A = pa.prun(
        lambda parts: bm.assemble_varcoef_poisson(
            parts, (n, n, n), pa, np.float32
        ),
        backend, (1, 1, 1),
    )

    def _rhs(seed):
        from partitionedarrays_jl_tpu.parallel.pvector import _write_owned

        v = pa.PVector.full(0.0, A.cols, dtype=np.float32)

        def fill(i, vals):
            rng = np.random.default_rng(seed + int(i.part))
            _write_owned(
                i, vals,
                rng.standard_normal(i.num_oids).astype(np.float32),
            )

        pa.map_parts(fill, v.rows.partition, v.values)
        return v

    rhs_pool = [_rhs(s) for s in range(4)]
    from partitionedarrays_jl_tpu import telemetry

    # a clean model: the PA_MON-on service legs below are exactly the
    # observations the exported THROUGHPUT_MODEL.json should hold
    telemetry.reset_model()
    # tol far below the f32 floor: every column stays active to maxiter,
    # so both legs run exactly TRIPS iterations per request
    rows = measure_rows(pa, A, None, rhs_pool, 1e-300, TRIPS)
    marginal = measure_metrics_marginal(pa, A, None, rhs_pool, 1e-300,
                                        TRIPS)
    tx_marginal = measure_tracing_marginal(pa, A, None, rhs_pool,
                                           1e-300, TRIPS)

    fingerprint = telemetry.operator_fingerprint(A)
    model = telemetry.throughput_model()
    measured_per_rhs = [
        {
            "K": K,
            "s_per_it": round(model.s_per_it(fingerprint, "float32", K),
                              9),
            "per_rhs_s_per_it": round(
                model.per_rhs(fingerprint, "float32", K), 9
            ),
        }
        for K in KS
        if model.s_per_it(fingerprint, "float32", K) is not None
    ]

    mr = json.load(open(os.path.join(REPO, "MULTIRHS_BENCH.json")))
    mr_by_k = {r["K"]: r for r in mr["curve"]}
    inherited = {
        "per_rhs_gain_k8": mr_by_k[8]["per_rhs_speedup_vs_k1"],
        "per_rhs_gain_k16": mr_by_k[16]["per_rhs_speedup_vs_k1"],
        "source": "MULTIRHS_BENCH.json",
        "note": (
            "the service feeds the identical compiled block program "
            "(make_cg_fn(rhs_batch=K)) the multirhs record measured — "
            "tests/test_service.py pins HLO collective parity against "
            "the bare block body and the service adds zero "
            "per-iteration work, so the slab's per-RHS speedup is "
            "inherited, not re-measured; the service rows above "
            "measure what the service layer itself adds on this "
            "platform"
        ),
    }

    rec = {
        "methodology": METHODOLOGY,
        "protocol": (
            "service rows: requests/s through a drained SolveService "
            f"(admission + coalescing + block slab) vs {len(KS)} x K "
            "sequential solo solves, fixed trips (tol below the dtype "
            f"floor, maxiter={TRIPS}), warmed, median-of-3; device "
            "per-RHS bands inherited from MULTIRHS_BENCH.json (see "
            "inherited.note)"
        ),
        "n": n,
        "dofs": n ** 3,
        "dtype": "float32",
        "trips": TRIPS,
        "ks": list(KS),
        "service_rows": rows,
        "inherited": inherited,
        "metrics_marginal": marginal,
        "tracing_marginal": tx_marginal,
        "measured_per_rhs": measured_per_rhs,
        "operator_fingerprint": fingerprint,
        "bands": {},
    }
    ok = True
    for key, (lo, hi, kind) in SERVICE_BANDS.items():
        v = inherited[key]
        in_band = lo <= v <= hi
        rec["bands"][key] = {
            "lo": lo, "hi": hi, "measured": v, "in_band": in_band,
            "kind": kind,
        }
        ok = ok and (in_band or kind != "device")
    for key, (lo, hi, kind) in METRICS_BANDS.items():
        v = marginal["ratio_on_off"]
        rec["bands"][key] = {
            "lo": lo, "hi": hi, "measured": v,
            "in_band": lo <= v <= hi, "kind": kind,
        }
    for key, (lo, hi, kind) in TRACING_BANDS.items():
        v = tx_marginal["ratio_on_off"]
        rec["bands"][key] = {
            "lo": lo, "hi": hi, "measured": v,
            "in_band": lo <= v <= hi, "kind": kind,
        }
    rec["bands_ok_device"] = ok

    from partitionedarrays_jl_tpu.telemetry import artifacts

    path = os.path.join(REPO, "SERVICE_BENCH.json")
    artifacts.write(path, rec, tool="bench_service", dry_run=dry)

    # -- THROUGHPUT_MODEL.json: the committed adaptive-K input --------
    model_rec = model.export()
    model_rec.update(
        {
            "methodology": "v1-throughput",
            "protocol": (
                "online EWMA of measured s_per_it(K) from the PA_MON-on "
                "drained service legs above (every warm + rep drain is "
                "one observation per slab chunk), keyed (operator "
                "fingerprint, dtype, K); reference_curve restates the "
                "committed MULTIRHS_BENCH.json device per-RHS curve "
                "the model converges to at the recorded size"
            ),
            "n": n,
            "dofs": n ** 3,
            "dtype": "float32",
            "trips": TRIPS,
            "operator_fingerprint": fingerprint,
            "reference_curve": {
                "source": "MULTIRHS_BENCH.json",
                "n": mr["n"],
                "dtype": mr["dtype"],
                "operator": mr["operator"],
                "per_rhs_s_per_it": {
                    str(r["K"]): r["per_rhs_s_per_it"]
                    for r in mr["curve"]
                },
                "per_rhs_speedup_vs_k1": {
                    str(r["K"]): r["per_rhs_speedup_vs_k1"]
                    for r in mr["curve"]
                },
            },
            "note": (
                "entries are measured ON THIS PLATFORM (see the "
                "envelope's platform field) — a cpu-host record is the "
                "structural canary of the online pipeline, not a device "
                "throughput claim; the adaptive-K policy reads the LIVE "
                "model (telemetry.throughput_model()), this artifact "
                "pins the export schema and the MULTIRHS traceability"
            ),
        }
    )
    artifacts.write(
        os.path.join(REPO, "THROUGHPUT_MODEL.json"), model_rec,
        tool="bench_service", dry_run=dry,
    )


if __name__ == "__main__":
    main()
