"""Telemetry overhead A/B -> OBS_BENCH.json.

The patrace tentpole's perf artifact, same discipline as the ABFT one
(tools/bench_abft.py): per-iteration cost of the compiled CG body with
the telemetry layer fully ON (``PA_TRACE_ITERS`` ring deep enough to
cover every trip, records + events enabled) vs OFF (the default —
trace depth 0), on the streaming-DIA variable-coefficient operator.
The acceptance criterion is a <= 5% telemetry-on overhead at 320^3 on
device: the α/β ring is a replicated (Ht, 2) while-carry of scalars
the dot gathers already replicated, so the cost is the two ring writes
per committed iteration — never extra wire.

Also recorded, at record time AND re-checked by tests:

* ``hlo_identity`` — the trace-off program is byte-identical StableHLO
  whether the host record layer is on or killed (``PA_METRICS=0``):
  telemetry off IS the pre-telemetry program.
* ``collective_parity`` — per-kind collective counts identical with
  the ring on vs off (telemetry on adds ZERO collectives).

Protocol: the fixed-trip compiled-CG marginal of bench.py
(`cg_marginal_s_per_it`): two maxiter legs, warmed, median-of-5,
differenced; tol=0 pins the trip count. ``--n`` overrides the size
list for smoke runs; ``--dry-run`` prints without committing. The
committed record names its platform — device-kind bands gate only
records measured on real TPUs.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

#: Guard bands for the committed artifact. Keys match
#: OBS_BENCH.json["bands"]; tests/test_doc_consistency.py asserts the
#: committed artifact and this table agree, and that device-kind bands
#: hold whenever the record was measured on a real TPU. The 320^3
#: ceiling of 1.05 IS the round-9 acceptance criterion.
OBS_BANDS = {
    "trace_overhead_ratio_320": (0.90, 1.05, "device"),
    "trace_overhead_ratio_192": (0.90, 1.10, "device"),
}

METHODOLOGY = "v1-obs"

#: Device sizes (the acceptance pair). A non-TPU platform records its
#: own (smaller) sizes honestly under platform="cpu" — useful as a
#: structural canary, not as the acceptance measurement.
DEVICE_SIZES = (192, 320)
HOST_SIZES = (32, 48)

#: Ring depth for the ON leg: deeper than the longest marginal leg, so
#: every committed iteration pays its ring write (the honest worst case).
TRACE_DEPTH = 1024


def _load_sibling(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           f"{name}.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "bench.py",
        ),
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def _identity_probe(pa, A, backend):
    """Lower the probe CG program three ways and pin the hard contract:
    trace-off text identical with the record layer on vs killed, and
    per-kind collective counts identical trace-on vs off."""
    from partitionedarrays_jl_tpu.analysis import collective_counts
    from partitionedarrays_jl_tpu.parallel.tpu import (
        _matrix_operands, device_matrix, make_cg_fn,
    )

    dA = device_matrix(A, backend)
    ops = _matrix_operands(dA)
    z = np.zeros((dA.col_plan.layout.P, dA.col_plan.layout.W))

    def lower():
        return make_cg_fn(dA, tol=1e-9, maxiter=50).jit_fn.lower(
            z, z, z, ops
        ).as_text()

    counts = collective_counts  # shared raw-substring semantics (PR 5)

    saved = {
        k: os.environ.pop(k, None)
        for k in ("PA_TRACE_ITERS", "PA_METRICS")
    }
    try:
        base = lower()
        os.environ["PA_METRICS"] = "0"
        killed = lower()
        del os.environ["PA_METRICS"]
        os.environ["PA_TRACE_ITERS"] = str(TRACE_DEPTH)
        traced = lower()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return {
        "hlo_identity": base == killed,
        "counts_on": counts(traced),
        "counts_off": counts(base),
        "parity": counts(traced) == counts(base),
    }


def main():
    import jax

    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.parallel.tpu import (
        TPUBackend, device_matrix,
    )
    from partitionedarrays_jl_tpu.telemetry import artifacts

    bench = _load_bench()
    bench_mr = _load_sibling("bench_multirhs")

    argv = sys.argv[1:]
    dry = "--dry-run" in argv
    platform = jax.devices()[0].platform
    sizes = list(DEVICE_SIZES if platform == "tpu" else HOST_SIZES)
    if "--n" in argv:
        sizes = [int(argv[argv.index("--n") + 1])]
    pa.enable_compilation_cache()
    backend = TPUBackend(devices=jax.devices()[:1])

    rows = []
    for n in sizes:
        A = pa.prun(
            lambda parts: bench_mr.assemble_varcoef_poisson(
                parts, (n, n, n), pa, np.float32
            ),
            backend, (1, 1, 1),
        )
        dA = device_matrix(A, backend)
        legs = {}
        for label, depth in (("off", None), ("on", str(TRACE_DEPTH))):
            if depth:
                os.environ["PA_TRACE_ITERS"] = depth
            else:
                os.environ.pop("PA_TRACE_ITERS", None)
            legs[label] = bench.cg_marginal_s_per_it(pa, dA, 40, 240)
        os.environ.pop("PA_TRACE_ITERS", None)
        rows.append(
            {
                "n": n,
                "dofs": n ** 3,
                "trace_off_s_per_it": round(legs["off"], 9),
                "trace_on_s_per_it": round(legs["on"], 9),
                "overhead_ratio": round(legs["on"] / legs["off"], 4),
            }
        )
        print(f"[bench_obs] n={n}: {rows[-1]}", flush=True)

    # the identity/parity probe on a small MULTI-part fixture (a
    # single-part mesh has no collectives to count)
    from partitionedarrays_jl_tpu.models import assemble_poisson

    ndev = min(8, len(jax.devices()))
    pbackend = TPUBackend(devices=jax.devices()[:ndev])
    pgrid = (2, 2, 2) if ndev >= 8 else (ndev, 1, 1)
    Ap = pa.prun(
        lambda parts: assemble_poisson(parts, (16, 16, 16))[0],
        pbackend, pgrid,
    )
    identity = _identity_probe(pa, Ap, pbackend)
    assert identity["hlo_identity"], (
        "telemetry-off must lower the identical program: "
        + json.dumps(identity)
    )
    assert identity["parity"], (
        "the trace ring must not add collectives: " + json.dumps(identity)
    )

    by_n = {r["n"]: r for r in rows}
    bands = {}
    for key, (lo, hi, kind) in OBS_BANDS.items():
        n = int(key.rsplit("_", 1)[-1])
        row = by_n.get(n)
        measured = row["overhead_ratio"] if row else None
        bands[key] = {
            "lo": lo,
            "hi": hi,
            "kind": kind,
            "measured": measured,
            "in_band": (
                (lo <= measured <= hi) if measured is not None else None
            ),
        }
    rec = {
        "methodology": METHODOLOGY,
        "protocol": (
            "fixed-trip compiled-CG marginal (bench.py "
            "cg_marginal_s_per_it): two maxiter legs, warmed, "
            "median-of-5, differenced; tol=0 pins the trip count; "
            f"telemetry leg = PA_TRACE_ITERS={TRACE_DEPTH} (ring "
            "deeper than every leg, so each committed iteration pays "
            "its two ring writes) with records and events enabled"
        ),
        "platform": platform,
        "dtype": "float32",
        "operator": (
            "variable-coefficient 7-point diffusion (streaming-DIA "
            "lowering — the large-N value-streaming operator whose "
            "per-iteration cost the ring writes compete with)"
        ),
        "trace_depth": TRACE_DEPTH,
        "sizes": rows,
        "identity": identity,
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
            "cpu-platform record is the structural canary (HLO "
            "identity + collective parity + protocol + artifact "
            "wiring), not the acceptance number. On XLA-CPU the "
            "sub-ms marginals are dominated by host-load noise, so "
            "cpu overhead ratios scatter on BOTH sides of 1.0 and "
            "carry no signal about the device cost of the ring writes"
        ),
    }
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "OBS_BENCH.json",
    )
    artifacts.write(path, rec, tool="bench_obs", dry_run=dry)


if __name__ == "__main__":
    main()
