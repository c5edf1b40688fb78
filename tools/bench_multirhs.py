"""Multi-RHS block-CG flagship curve -> MULTIRHS_BENCH.json.

The round-7 tentpole's acceptance artifact: per-RHS per-iteration cost
of the block-CG program (`make_cg_fn(rhs_batch=K)`) at K ∈ {1,2,4,8,16}
on the >=320³ single-chip problem, f32, fused body. Two operators:

* **streaming-DIA headline** — a variable-coefficient 7-point diffusion
  operator (harmonic-mean arm weights over a smooth k-field declines
  the coded detector), so every iteration streams 7 f32 value diagonals
  (28 B/row). That stream — plus the halo slabs and the while-loop's
  K-invariant overheads — is paid ONCE per K columns (JITSPMM, arxiv
  2312.05639), which is where the per-RHS speedup comes from; the
  per-column vector sweeps (x/r/p/q updates + dots) scale with K and
  bound the asymptote at roughly (operator+vectors)/vectors.
* **coded A/B** — the constant-coefficient Poisson whose coded lowering
  streams ~1 BYTE per row: its operator stream is already almost free,
  so the multi-RHS win shrinks to the K-invariant loop overheads. The
  A/B is recorded so the docs can say WHERE batching pays, not just
  that it does.

Protocol: the fixed-trip block-CG marginal of bench.py
(`block_cg_marginal_s_per_it`) — two maxiter legs, warmed,
median-of-5, differenced; tol=0 keeps every column active so the trip
count is exact. Run on the default (real TPU) platform; ``--dry-run``
prints the record without touching the committed artifact, ``--n``
overrides the size for smoke runs.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

#: Guard bands for the committed flagship artifact (320^3 f32, one
#: chip, streaming-DIA headline operator). Keys match
#: MULTIRHS_BENCH.json["bands"]; tests/test_doc_consistency.py asserts
#: the committed artifact and this table agree. The K=8 floor of 1.5 IS
#: the round-7 acceptance criterion.
MULTIRHS_BANDS = {
    "per_rhs_speedup_k8": (1.5, 2.2, "device"),
    "per_rhs_speedup_k16": (1.55, 2.4, "device"),
}

METHODOLOGY = "v1-multirhs"

KS = (1, 2, 4, 8, 16)


def assemble_varcoef_poisson(parts, ns, pa, dtype=np.float32):
    """Variable-coefficient 7-point (3-D) / 5-point (2-D) diffusion
    operator with harmonic-mean arm weights over a smooth k-field and
    Dirichlet identity boundary rows. Every diagonal carries many
    distinct values, so the device lowering takes the STREAMING-DIA
    path — the operator whose value stream multi-RHS amortizes."""
    ns = tuple(int(n) for n in ns)
    dim = len(ns)
    rows = pa.cartesian_partition(parts, ns, pa.no_ghost)
    cis = pa.p_cartesian_indices(parts, ns, pa.no_ghost)

    def k_field(*cs):
        f = 1.0
        for d, c in enumerate(cs):
            f = f * (1.0 + 0.4 * np.sin(0.37 * (d + 1) * np.asarray(c)))
        return 1.0 + 0.8 * f

    def coo(ci):
        grid = ci.grid()
        cs = [g.ravel() for g in grid]
        gid = np.ravel_multi_index(tuple(cs), ns)
        interior = np.ones(len(gid), dtype=bool)
        for d in range(dim):
            interior &= (cs[d] > 0) & (cs[d] < ns[d] - 1)
        I = [gid[~interior]]
        J = [gid[~interior]]
        V = [np.ones(int((~interior).sum()))]
        gi = gid[interior]
        ics = [c[interior] for c in cs]
        diag = np.zeros(len(gi))
        for d in range(dim):
            for s in (-1, 1):
                nb = list(ics)
                nb[d] = ics[d] + s
                kn = 2.0 / (
                    1.0 / k_field(*ics) + 1.0 / k_field(*nb)
                )
                I.append(gi)
                J.append(np.ravel_multi_index(tuple(nb), ns))
                V.append(-kn)
                diag += kn
        I.append(gi)
        J.append(gi)
        V.append(diag + 1e-3)  # shifted: safely SPD with identity rows
        return (
            np.concatenate(I),
            np.concatenate(J),
            np.concatenate(V).astype(dtype) / 16.0,  # bounded chains
        )

    trip = pa.map_parts(coo, cis)
    I = pa.map_parts(lambda t: t[0], trip)
    J = pa.map_parts(lambda t: t[1], trip)
    V = pa.map_parts(lambda t: t[2], trip)
    # cols = rows + the stencil's ghost layer, so the same assembler
    # serves a multi-part grid (one part: no off-part gids, cols == rows)
    cols = pa.add_gids(rows, J)
    return pa.PSparseMatrix.from_coo(I, J, V, rows, cols, ids="global")


def _curve(pa, dA, ks, bench):
    rows = []
    base = None
    for K in ks:
        t_it = bench.block_cg_marginal_s_per_it(pa, dA, K, 40, 240)
        per_rhs = t_it / K
        if K == 1:
            base = per_rhs
        rows.append(
            {
                "K": K,
                "block_s_per_it": round(t_it, 9),
                "per_rhs_s_per_it": round(per_rhs, 9),
                "per_rhs_speedup_vs_k1": (
                    round(base / per_rhs, 3) if base else None
                ),
            }
        )
    return rows


def main():
    import importlib.util

    import jax

    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.parallel.tpu import (
        TPUBackend, device_matrix,
    )

    spec = importlib.util.spec_from_file_location(
        "bench",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "bench.py",
        ),
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    argv = sys.argv[1:]
    dry = "--dry-run" in argv
    n = int(os.environ.get("PA_BENCH_N", "320"))
    if "--n" in argv:
        n = int(argv[argv.index("--n") + 1])
    ks = [k for k in KS if k <= max(KS)]
    pa.enable_compilation_cache()
    backend = TPUBackend(devices=jax.devices()[:1])

    # headline: streaming-DIA variable-coefficient operator
    A = pa.prun(
        lambda parts: assemble_varcoef_poisson(
            parts, (n, n, n), pa, np.float32
        ),
        backend, (1, 1, 1),
    )
    dA = device_matrix(A, backend)
    assert dA.dia_mode == "stream", (
        f"headline operator must take the streaming-DIA path, got "
        f"{dA.dia_mode!r}"
    )
    curve = _curve(pa, dA, ks, bench)

    # coded A/B: the constant-coefficient Poisson (coded lowering)
    from partitionedarrays_jl_tpu.models import assemble_poisson

    Ac, *_ = pa.prun(
        lambda parts: bench.assemble_poisson_scaled(
            parts, (n, n, n), pa, np.float32
        ),
        backend, (1, 1, 1),
    )
    dAc = device_matrix(Ac, backend)
    t1 = bench.block_cg_marginal_s_per_it(pa, dAc, 1, 40, 240)
    t8 = bench.block_cg_marginal_s_per_it(pa, dAc, 8, 40, 240)

    by_k = {r["K"]: r for r in curve}
    rec = {
        "methodology": METHODOLOGY,
        "protocol": (
            "fixed-trip block-CG marginal (bench.py "
            "block_cg_marginal_s_per_it): two maxiter legs, warmed, "
            "median-of-5, differenced; tol=0 keeps every column active; "
            "per-RHS = block_s_per_it / K"
        ),
        "n": n,
        "dofs": n ** 3,
        "dtype": "float32",
        "cg_body": "fused",
        "operator": (
            "variable-coefficient 7-point diffusion, harmonic-mean arm "
            "weights (streaming-DIA lowering: 7 f32 value diagonals = "
            "28 B/row streamed once per K columns)"
        ),
        "ks": list(ks),
        "curve": curve,
        "coded_ab": {
            "note": (
                "constant-coefficient Poisson (coded-DIA lowering, ~1 "
                "B/row operator stream): the multi-RHS win here is only "
                "the K-invariant loop overheads — recorded so the docs "
                "can say WHERE batching pays"
            ),
            "K1_s_per_it": round(t1, 9),
            "K8_s_per_it": round(t8, 9),
            "per_rhs_speedup_at_k8": round(t1 / (t8 / 8), 3),
        },
        "bands": {},
    }
    measured = {
        "per_rhs_speedup_k8": by_k[8]["per_rhs_speedup_vs_k1"],
        "per_rhs_speedup_k16": by_k[16]["per_rhs_speedup_vs_k1"],
    }
    ok = True
    for key, (lo, hi, kind) in MULTIRHS_BANDS.items():
        v = measured[key]
        in_band = lo <= v <= hi
        rec["bands"][key] = {
            "lo": lo, "hi": hi, "measured": v, "in_band": in_band,
            "kind": kind,
        }
        ok = ok and (in_band or kind != "device")
    rec["bands_ok_device"] = ok

    from partitionedarrays_jl_tpu.telemetry import artifacts

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "MULTIRHS_BENCH.json",
    )
    rec = artifacts.write(path, rec, tool="bench_multirhs", dry_run=dry)
    if not dry:
        print(json.dumps(rec, indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
