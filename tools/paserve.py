#!/usr/bin/env python
"""paserve — run the solve service against a demo operator.

The CLI harness of `partitionedarrays_jl_tpu.service.SolveService`: it
assembles a Poisson system, starts a service, submits a batch of
requests (optionally poisoning one with a NaN right-hand side to watch
the blast-radius containment work, optionally with per-request
deadlines), drains, and prints one outcome line per request plus the
service stats — the smallest end-to-end path through admission,
coalescing, the compiled block slab, ejection, and typed failure.

Usage:
    python tools/paserve.py --grid 8 8 --requests 6 --kmax 4
    python tools/paserve.py --grid 8 8 8 --requests 8 --poison 3
    python tools/paserve.py --backend tpu --requests 8 --deadline 30
    python tools/paserve.py ... --summary-json out.json
    python tools/paserve.py ... --metrics-json m.json   # pamon --snapshot

Exit status: 0 when every request ends in a documented terminal state
(done, or failed-with-typed-error for poisoned requests), 1 otherwise.
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _build_requests(pa, A, b, x0, n_requests, poison, seed=0):
    """The demo request mix: the assembled (b, x0) plus scaled variants
    — the system is linear, so scaling BOTH keeps the Dirichlet
    boundary rows consistent — with request ``poison`` (if any)
    NaN-poisoned in one owned entry of its b."""
    import numpy as np

    out = []
    for i in range(n_requests):
        bi, x0i = b.copy(), x0.copy()
        if i:
            scale = 1.0 + 0.25 * i

            # scale all local values in place (owned and ghost scale
            # together, so no exchange is needed)
            def _scale(iset, vals, s=scale):
                np.asarray(vals)[...] *= s

            pa.map_parts(_scale, bi.rows.partition, bi.values)
            pa.map_parts(_scale, x0i.rows.partition, x0i.values)
        if poison is not None and i == poison:
            def _poison(iset, vals):
                if int(iset.part) == 0 and len(np.asarray(vals)):
                    np.asarray(vals)[0] = np.nan

            pa.map_parts(_poison, bi.rows.partition, bi.values)
        out.append((bi, x0i))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--grid", type=int, nargs="+", default=[8, 8],
                    help="Poisson grid (2-D or 3-D), default 8 8")
    ap.add_argument("--parts", type=int, nargs="+", default=None,
                    help="part grid (default 2 2 [2])")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--kmax", type=int, default=None,
                    help="slab width bound (default PA_SERVE_KMAX)")
    ap.add_argument("--queue-depth", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--tol", type=float, default=1e-9)
    ap.add_argument("--maxiter", type=int, default=None)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline seconds (slabs chunk)")
    ap.add_argument("--poison", type=int, default=None,
                    help="NaN-poison request #N (containment demo)")
    ap.add_argument("--retries", type=int, default=None)
    ap.add_argument("--backend", choices=("seq", "tpu"), default="seq")
    ap.add_argument("--summary-json", default=None,
                    help="write the outcome summary as JSON")
    ap.add_argument("--metrics-json", default=None,
                    help="export the metric-registry snapshot as JSON "
                         "(render/watch it with tools/pamon.py "
                         "--snapshot)")
    args = ap.parse_args(argv)

    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.models import assemble_poisson
    from partitionedarrays_jl_tpu.service import SolveService

    grid = tuple(args.grid)
    parts_grid = (
        tuple(args.parts) if args.parts else (2,) * len(grid)
    )
    if args.backend == "tpu":
        # one part per device, on the devices JAX gives this process: a
        # chip where there is one, the virtual CPU mesh where the caller
        # asked for it (tier-1's conftest; by hand:
        # JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8).
        # Nothing here picks a platform.
        import jax

        need = 1
        for p in parts_grid:
            need *= p
        devices = jax.devices()
        if need > len(devices):
            ap.error(
                f"--backend tpu: part grid {parts_grid} needs {need} "
                f"devices and JAX found {len(devices)} ({devices[0].platform})"
                "; pass a smaller --parts, or ask for the virtual CPU mesh "
                "with JAX_PLATFORMS=cpu "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8"
            )
        pa.enable_compilation_cache()
        backend = pa.TPUBackend(devices=devices[:need])
    else:
        backend = pa.sequential

    rows = []

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, grid)
        svc = SolveService(
            A, kmax=args.kmax, queue_depth=args.queue_depth,
            chunk=args.chunk, retries=args.retries,
        )
        bs = _build_requests(pa, A, b, x0, args.requests, args.poison)
        handles = []
        for i, (bi, x0i) in enumerate(bs):
            handles.append(
                svc.submit(
                    bi, x0=x0i, tol=args.tol, maxiter=args.maxiter,
                    deadline=args.deadline, tag=f"req-{i}",
                )
            )
        svc.drain()
        stats = svc.shutdown()
        for i, h in enumerate(handles):
            row = {"request": h.tag, "state": h.state,
                   "iterations": h.iterations}
            if h.state == "done":
                _x, info = h.result()
                row["converged"] = bool(info["converged"])
                row["status"] = str(info["status"])
            elif h.state == "failed":
                row["error"] = type(h.error).__name__
            rows.append(row)
        return stats

    stats = pa.prun(driver, backend, parts_grid)

    for row in rows:
        line = (
            f"  {row['request']:>8s}  {row['state']:>6s}  "
            f"it={row['iterations']:>4d}"
        )
        if "converged" in row:
            line += f"  converged={row['converged']}  {row['status']}"
        if "error" in row:
            line += f"  {row['error']}"
        print(line)
    print(f"stats: {json.dumps(stats, sort_keys=True)}")

    ok = True
    for i, row in enumerate(rows):
        if args.poison is not None and i == args.poison:
            ok = ok and row["state"] == "failed"
        else:
            ok = ok and row["state"] == "done" and row.get("converged")
    if args.summary_json:
        with open(args.summary_json, "w", encoding="utf-8") as f:
            json.dump(
                {"requests": rows, "stats": stats, "ok": ok},
                f, indent=1, sort_keys=True,
            )
        print(f"wrote {args.summary_json}")
    if args.metrics_json:
        from partitionedarrays_jl_tpu import telemetry

        with open(args.metrics_json, "w", encoding="utf-8") as f:
            f.write(telemetry.registry().to_json())
        print(f"wrote {args.metrics_json}")
    print("paserve:", "OK" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
