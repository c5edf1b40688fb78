"""ABFT clean-path A/B -> ABFT_BENCH.json.

The round-8 SDC tentpole's perf artifact: per-iteration cost of the
compiled CG body with the full in-graph defense ON (``PA_TPU_ABFT=1``
checksum lanes + the default 32-iteration true-residual audit) vs OFF,
on the streaming-DIA variable-coefficient operator. The acceptance
criterion is a <= 5% clean-path overhead at 320^3 on device — the
detection machinery rides EXISTING collectives (checksum lanes on the
dot all_gather, one extra slot per exchange round, the audit's operand
select on the loop's one SpMV call site), so the cost is the checksum
sweeps (two extra owned-region reductions + the w·x product) and the
1/32 audit stall-trips, not extra wire.

Also recorded: the HLO per-kind collective-count parity between the two
programs (the zero-extra-collectives claim, asserted at record time AND
re-checked against the committed artifact by tests/test_abft.py /
tests/test_doc_consistency.py).

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
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

#: Guard bands for the committed artifact. Keys match
#: ABFT_BENCH.json["bands"]; tests/test_doc_consistency.py asserts the
#: committed artifact and this table agree, and that device-kind bands
#: hold whenever the record was measured on a real TPU. The 320^3
#: ceiling of 1.05 IS the round-8 acceptance criterion.
ABFT_BANDS = {
    "clean_overhead_ratio_320": (0.90, 1.05, "device"),
    "clean_overhead_ratio_192": (0.90, 1.10, "device"),
}

METHODOLOGY = "v1-abft"

#: Device sizes (the acceptance pair). A non-TPU platform records its
#: own (smaller) sizes honestly under platform="cpu" — useful as a
#: structural canary, not as the acceptance measurement.
DEVICE_SIZES = (192, 320)
HOST_SIZES = (32, 48)


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


def _collective_counts(fn, *args):
    txt = fn.jit_fn.lower(*args).as_text()
    return {
        k: len(re.findall(k, txt))
        for k in ("collective_permute", "all_gather", "all_reduce")
    }


def _parity_probe(pa, A, backend):
    """Lower the ABFT-on and -off programs for one small operator and
    record per-kind collective counts — the parity claim, measured.
    PA_TPU_BOX=0 on both sides so the A/B compares like exchange plans
    (ABFT itself pins the generic plan)."""
    from partitionedarrays_jl_tpu.parallel.tpu import (
        _matrix_operands, device_matrix, make_cg_fn,
    )

    out = {}
    old_box = os.environ.get("PA_TPU_BOX")
    os.environ["PA_TPU_BOX"] = "0"
    try:
        for label, abft in (("on", "1"), ("off", None)):
            if abft:
                os.environ["PA_TPU_ABFT"] = abft
            else:
                os.environ.pop("PA_TPU_ABFT", None)
            dA = device_matrix(A, backend)
            ops = _matrix_operands(dA)
            fn = make_cg_fn(dA, tol=1e-9, maxiter=50)
            db = np.zeros((dA.col_plan.layout.P, dA.col_plan.layout.W))
            out[label] = _collective_counts(fn, db, db, db, ops)
    finally:
        os.environ.pop("PA_TPU_ABFT", None)
        if old_box is None:
            os.environ.pop("PA_TPU_BOX", None)
        else:
            os.environ["PA_TPU_BOX"] = old_box
    return {
        "counts_on": out["on"],
        "counts_off": out["off"],
        "parity": out["on"] == out["off"],
    }


def main():
    import jax

    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.parallel.tpu import (
        TPUBackend, device_matrix,
    )

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
        legs = {}
        for label, abft in (("off", None), ("on", "1")):
            if abft:
                os.environ["PA_TPU_ABFT"] = abft
            else:
                os.environ.pop("PA_TPU_ABFT", None)
            dA = device_matrix(A, backend)
            legs[label] = bench.cg_marginal_s_per_it(pa, dA, 40, 240)
        os.environ.pop("PA_TPU_ABFT", None)
        rows.append(
            {
                "n": n,
                "dofs": n ** 3,
                "abft_off_s_per_it": round(legs["off"], 9),
                "abft_on_s_per_it": round(legs["on"], 9),
                "overhead_ratio": round(legs["on"] / legs["off"], 4),
            }
        )
        print(f"[bench_abft] n={n}: {rows[-1]}", flush=True)

    # collective parity on a small MULTI-part fixture (a single-part
    # mesh has no collectives to count); 8 virtual devices on cpu, the
    # real chips on tpu. assemble_poisson handles multi-part ghost
    # discovery (the varcoef assembler is single-chip-only).
    from partitionedarrays_jl_tpu.models import assemble_poisson

    ndev = min(8, len(jax.devices()))
    pbackend = TPUBackend(devices=jax.devices()[:ndev])
    pgrid = (2, 2, 2) if ndev >= 8 else (ndev, 1, 1)
    Ap = pa.prun(
        lambda parts: assemble_poisson(parts, (16, 16, 16))[0],
        pbackend, pgrid,
    )
    parity = _parity_probe(pa, Ap, pbackend)
    assert parity["parity"], (
        "ABFT must not add collectives: " + json.dumps(parity)
    )

    by_n = {r["n"]: r for r in rows}
    bands = {}
    for key, (lo, hi, kind) in ABFT_BANDS.items():
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
            "ABFT leg = PA_TPU_ABFT=1 with the default 32-iteration "
            "audit (its stall trips are part of the measured cost)"
        ),
        "platform": platform,
        "dtype": "float32",
        "operator": (
            "variable-coefficient 7-point diffusion (streaming-DIA "
            "lowering — the large-N value-streaming operator the "
            "checksum sweeps compete with)"
        ),
        "sizes": rows,
        "collective_parity": parity,
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
            "cpu-platform record is the structural canary (parity + "
            "protocol + artifact wiring), not the acceptance number. "
            "XLA-CPU copies while-loop carries (incl. the R*3*W "
            "rollback ring) every trip instead of aliasing them, so "
            "cpu overhead ratios run far above the device target and "
            "vary with host load"
        ),
    }
    from partitionedarrays_jl_tpu.telemetry import artifacts

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "ABFT_BENCH.json",
    )
    artifacts.write(path, rec, tool="bench_abft", dry_run=dry)


if __name__ == "__main__":
    main()
