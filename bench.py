"""Benchmark harness: PSparseMatrix SpMV GFLOPS/chip (3-D Poisson FDM)
plus the `exchange!` halo microbench (BASELINE.json configs[1]).

Prints TWO JSON lines, each
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
The halo line comes first; the LAST line is the primary SpMV metric (the
position the round-1 driver parsed).

SpMV metric: the compiled SpMV throughput of the 7-point 3-D Poisson
operator on one chip. The reference publishes no absolute numbers
(BASELINE.md: "published": {}), so `vs_baseline` reports the speedup
over this repo's own sequential (NumPy CSR) oracle on the same problem —
the honest stand-in for the reference's CPU execution model.

Halo metric: per-chip payload bandwidth of the compiled halo exchange
(pack gather -> `ppermute` -> unpack scatter) for part 0 of the 8-part
2x2x2 partition of the same grid — the workload of reference
test/test_fdm.jl:8-120 over the Exchanger of src/Interfaces.jl:846-889.
This leg runs on ONE chip, so its `ppermute`s are self-loops: the wire
hop is a device-local copy and the measured cost is the per-chip
pack/unpack kernel path (the plan itself is the real 8-part plan). With
more than one chip the ICI legs (`bench_ici`) run true neighbor
`ppermute`s as well. `vs_baseline` is the speedup over the sequential backend's
eager 8-part exchange on the same PRange.

Runs on a TPU only: it exits non-zero, naming the devices JAX found,
anywhere else — a timing from XLA's CPU backend is not a device metric.
A leg that raises fails the run. Every record names the device it was
measured on (`platform`, `device_kind`, `device_count`).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


# Methodology version: bump when a metric's measurement protocol changes
# so artifact JSONs from different rounds are comparable only when the
# version matches.
METHODOLOGY = "v4"


def stamp(rec: dict) -> dict:
    """Name the measuring device and the protocol in a metric record."""
    import jax

    d = jax.devices()
    rec.update(
        methodology=METHODOLOGY, platform=d[0].platform,
        device_kind=d[0].device_kind, device_count=len(d),
    )
    return rec


def marginal_chain_time(run_chain, k1: int, k2: int, nreps: int = 5) -> float:
    """Shared marginal-cost timing protocol (docs/performance.md): per
    chain length, warm twice then take the median of `nreps` timed runs;
    difference two well-separated lengths so the fixed per-dispatch cost
    (launch + scalar fetch) cancels; double the long chain until the
    marginal cost comes out positive (host jitter can invert short
    differences); report the median of three full measurements. `run_chain(k)` must execute one
    compiled k-step dependency chain ending in a host scalar fetch."""
    import statistics

    def chain_time(k: int) -> float:
        run_chain(k)
        run_chain(k)
        ts = []
        for _ in range(nreps):
            t0 = time.perf_counter()
            v = run_chain(k)
            ts.append(time.perf_counter() - t0)
        assert v == v, "chain produced NaN — operator scaling broken"
        return statistics.median(ts)

    def measure_once() -> float:
        t1 = chain_time(k1)
        kk2 = k2
        for _ in range(4):
            t2 = chain_time(kk2)
            dt = (t2 - t1) / (kk2 - k1)
            if dt > 0:
                return dt
            kk2 = 2 * kk2
        # still inverted: conservative whole-chain cost of the LAST
        # measured chain (t2 was taken before the final doubling)
        return t2 / (kk2 // 2)

    dts = sorted(measure_once() for _ in range(3))
    return dts[1]


def bench_halo(n: int, backend, pa) -> dict:
    """Per-chip halo-exchange payload bandwidth (see module docstring).

    Uses whatever plan `device_exchange_plan` selects for the 8-part
    Cartesian PRange — the slice-based box plan (tpu_box.py) on the fast
    path, or the generic gather plan if detection declines — so the
    metric always measures the shipping halo path. Part 0's program runs
    with self-loop `ppermute`s on the single reachable chip; for the box
    plan each send-direction's packed slab lands in the opposite
    direction's ghost segment (equal boxes make the shapes match), which
    is exactly one part's per-exchange pack+unpack work."""
    import statistics
    from functools import partial

    import jax
    import jax.numpy as jnp

    from partitionedarrays_jl_tpu.parallel.sequential import SequentialBackend
    from partitionedarrays_jl_tpu.parallel.tpu import (
        _stage, device_exchange_plan,
    )
    from partitionedarrays_jl_tpu.parallel.tpu_box import BoxExchangePlan

    dtype = np.float32
    # the real 8-part plan, built host-side exactly as a 2x2x2 run would.
    # PA_BENCH_HALO_PERIODIC=1 benches the TORUS halo instead: wrapped
    # ghosts ride the same slice-based box plan (tpu_box.py handles the
    # wrap), so the periodic fast path's bandwidth is measurable on the
    # same protocol (round-4 directive 6)
    periodic = os.environ.get("PA_BENCH_HALO_PERIODIC", "0") == "1"
    seq = SequentialBackend()
    rows = pa.prun(
        lambda parts: pa.prange(
            parts, (n, n, n), pa.with_ghost,
            periodic=(True, True, True) if periodic else None,
        ),
        seq, (2, 2, 2),
    )
    plan = device_exchange_plan(rows, False)
    layout = plan.layout
    p0 = 0
    # payload: each ghost entry of part 0 lands once per exchange
    hids = rows.partition.part_values()[p0].num_hids
    payload_bytes = hids * np.dtype(dtype).itemsize
    mesh = backend.mesh(1)
    spec = backend.parts_spec()
    x0 = np.zeros((1, layout.W), dtype=dtype)
    x0[0, layout.o0 : layout.o0 + layout.no_max] = 1.0
    x = jax.device_put(x0, jax.sharding.NamedSharding(mesh, spec))

    if isinstance(plan, BoxExchangePlan):
        info = plan.info
        if len(info.box_shapes) > 1:
            # the manual-slab leg below reads single-variant geometry
            # (info.box_shape, d.start/d.shape); an n not divisible by
            # the 2x2x2 split yields a multi-variant plan that this
            # protocol cannot replay part-0-only — fail loudly instead
            # of asserting deep in BoxInfo.box_shape (advisor r4)
            raise NotImplementedError(
                "bench_halo's manual-slab protocol needs equal per-part "
                f"boxes; n={n} is not divisible by the 2x2x2 split"
            )
        o0, g0 = layout.o0, layout.g0
        no = int(np.prod(info.box_shape))
        bs = info.box_shape
        by_dir = {d.dir: d for d in info.dirs}
        # part 0's send directions, each paired with the segment it
        # would fill on the receiving side (the opposite direction)
        legs = []
        for d in info.dirs:
            if any(p == p0 for p, _ in d.perm):
                opp = by_dir[tuple(-c for c in d.dir)]
                assert opp.size == d.size, "asymmetric halo shapes"
                legs.append((d, opp))

        def step_body(xv):
            own = jax.lax.slice(xv, (o0,), (o0 + no,)).reshape(bs)
            for d, opp in legs:
                sl = tuple(
                    slice(a, a + s) for a, s in zip(d.start, d.shape)
                )
                buf = own[sl].reshape(-1)
                buf = jax.lax.ppermute(buf, "parts", perm=((0, 0),))
                xv = jax.lax.dynamic_update_slice(
                    xv, buf, (g0 + opp.off,)
                )
            # one-element ghost->owned feedback per corner: the owned
            # region must EVOLVE across iterations (as it does in a real
            # solver), or the compiler may hoist the loop-invariant packs
            # and the chain would measure permute+unpack only. The HI
            # corner (o0+no-1) lies in every positive-direction slab
            # (all part 0 sends on the non-periodic 2x2x2 split); the LO
            # corner covers the negative-direction slabs the PERIODIC
            # torus adds.
            eps = jnp.asarray(1e-30, xv.dtype)
            xv = xv.at[o0 + no - 1].add(xv[g0] * eps)
            return xv.at[o0].add(xv[g0 + 1] * eps)

        @partial(jax.jit, static_argnums=1)
        def chain(x, k):
            def shard_fn(xs):
                return jax.lax.fori_loop(
                    0, k, lambda _, xv: step_body(xv), xs[0]
                )[None]

            return jax.shard_map(
                shard_fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
                check_vma=False,
            )(x).sum()

        run_chain = lambda k: float(chain(x, k))
    else:
        si = _stage(backend, plan.snd_idx[p0][None], 1)
        sm = _stage(backend, plan.snd_mask[p0][None], 1)
        ri = _stage(backend, plan.rcv_idx[p0][None], 1)
        R, trash = plan.R, layout.trash

        @partial(jax.jit, static_argnums=4)
        def chain(x, si, sm, ri, k):
            def shard_fn(xs, sis, sms, ris):
                xv, siv, smv, riv = xs[0], sis[0], sms[0], ris[0]

                def step(_, xv):
                    for r in range(R):
                        buf = jnp.where(smv[r], xv[siv[r]], 0)
                        buf = jax.lax.ppermute(
                            buf, "parts", perm=((0, 0),)
                        )
                        xv = xv.at[riv[r]].set(buf)
                        xv = xv.at[trash].set(0)
                    return xv

                return jax.lax.fori_loop(0, k, step, xv)[None]

            return jax.shard_map(
                shard_fn, mesh=mesh, in_specs=(spec,) * 4, out_specs=spec,
                check_vma=False,
            )(x, si, sm, ri).sum()

        run_chain = lambda k: float(chain(x, si, sm, ri, k))

    # chain lengths sized so the marginal cost is tens of ms of signal
    # against per-dispatch jitter
    dt = marginal_chain_time(run_chain, 100, 3300)
    bw = payload_bytes / dt

    # sequential-oracle comparand: the eager 8-part exchange (numpy
    # pack/copy/unpack through the same Exchanger) on the same PRange,
    # per-part marginal = total / 8
    v = pa.prun(
        lambda parts: pa.PVector.full(np.float32(1.0), rows, dtype=dtype),
        seq, (2, 2, 2),
    )
    v.exchange()  # warm
    host_ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        v.exchange()
        host_ts.append(time.perf_counter() - t0)
    host_dt = statistics.median(host_ts) / 8
    host_bw = payload_bytes / host_dt
    kind = "torus" if periodic else "poisson3d"
    rec = {
        "metric": f"halo_exchange_bytes_per_s_per_chip_{kind}_{n}cube_f32",
        "value": round(bw, 1),
        "unit": "B/s",
        "vs_baseline": round(bw / host_bw, 3),
        "host_oracle_bytes_per_s": round(host_bw, 1),
        "plan": type(plan).__name__,
    }
    return stamp(rec)


def bench_cg_vs_cpu(n: int, backend, pa, dA) -> dict:
    """Whole-solver comparand: compiled-CG iteration throughput on one
    chip vs the sequential backend's eager host CG on the SAME operator
    (1/16-scaled 3-D Poisson at n^3 ~ 1e7 DOFs). Device timing is the
    marginal cost between two fixed-trip programs (tol=0, different
    maxiter) so dispatch and compile cancel; host timing is a plain
    median over short runs of the same recurrence."""
    import statistics

    dtype = np.float32

    # host leg: K iterations of the sequential backend's eager CG on an
    # identically-built operator (the TPU-backend A would dispatch to the
    # compiled path — the comparand must be the host execution model)
    from partitionedarrays_jl_tpu.parallel.sequential import SequentialBackend

    def host_driver(parts):
        Ah, _, _, _ = assemble_poisson_scaled(parts, (n, n, n), pa, dtype)
        bh = pa.PVector.full(np.float32(1.0), Ah.cols, dtype=dtype)
        x0h = pa.PVector.full(np.float32(0.0), Ah.cols, dtype=dtype)
        K = 25
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            pa.cg(Ah, bh, x0=x0h, tol=0.0, maxiter=K)
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts) / K

    host_it_s = pa.prun(host_driver, SequentialBackend(), (1, 1, 1))

    # device leg: two fixed-trip compiled solves, marginal cost per it
    dev_it_s = cg_marginal_s_per_it(pa, dA, 60, 1000)
    speedup = host_it_s / dev_it_s
    rec = {
            "metric": f"cg_iteration_speedup_vs_cpu_poisson3d_{n}cube_f32",
            "value": round(speedup, 2),
            # advisor r3: the comparand is this repo's own sequential
            # single-core proxy of the reference's per-rank execution
            # model (eager NumPy, no inter-rank comm), NOT a measured
            # MPIBackend run — say so in the record
            "unit": "x (chip CG it/s over sequential-backend CPU CG it/s)",
            "comparand": "sequential single-core proxy (eager NumPy, no "
            "inter-rank comm) — not a measured reference MPI run",
            "vs_baseline": round(speedup / 5.0, 3),  # >=1 passes the 5x gate
            "baseline_cpu": {
                "cg_s_per_iteration": round(host_it_s, 5),
                "dofs": n**3,
                "host": "sequential backend, 1 core",
            },
            "device_cg_s_per_iteration": round(dev_it_s, 6),
    }
    return stamp(rec)


def cg_marginal_s_per_it(pa, dA, k1: int, k2: int, fused=None) -> float:
    """Fixed-trip compiled-CG marginal cost per iteration: two solves at
    maxiter k1/k2 (tol=0), each warmed then median-of-5 timed, so
    dispatch and compile cancel in the difference. Shared by the
    single-chip CG comparand, the ICI leg, and the scale curve's fused
    A/B (one protocol, one place). ``fused=None`` measures the shipped
    default body; True/False pin a body for A/B legs."""
    import statistics

    from partitionedarrays_jl_tpu.parallel.tpu import DeviceVector, make_cg_fn

    dtype = np.float32
    b = pa.PVector.full(np.float32(1.0), dA.cols, dtype=dtype)
    z = pa.PVector.full(np.float32(0.0), dA.cols, dtype=dtype)
    db = DeviceVector.from_pvector(b, dA.backend, dA.col_layout)
    dz = DeviceVector.from_pvector(z, dA.backend, dA.col_layout)

    def run_k(k):
        fn = make_cg_fn(dA, tol=0.0, maxiter=k, fused=fused)
        fn(db.data, dz.data, None)

        def once():
            t0 = time.perf_counter()
            out = fn(db.data, dz.data, None)
            float(out[1])
            return time.perf_counter() - t0

        once()
        return statistics.median(once() for _ in range(5))

    t1, t2 = run_k(k1), run_k(k2)
    return max((t2 - t1) / (k2 - k1), 1e-9)


def block_cg_marginal_s_per_it(pa, dA, K: int, k1: int, k2: int, fused=None):
    """`cg_marginal_s_per_it` widened to a K-column RHS block: the
    fixed-trip marginal per iteration of the (P, W, K) block-CG program
    (tol=0 keeps every column active, so the trip count is exact).
    Divide by K for the per-RHS figure — the multi-RHS story is that
    this ratio DROPS as K grows while the operator stream is paid once
    per K columns."""
    import statistics

    from partitionedarrays_jl_tpu.parallel.tpu import (
        _block_on_cols_layout, make_cg_fn,
    )

    dtype = np.float32
    b = pa.PVector.full(np.float32(1.0), dA.cols, dtype=dtype)
    z = pa.PVector.full(np.float32(0.0), dA.cols, dtype=dtype)
    db = _block_on_cols_layout([b] * K, dA)
    dz = _block_on_cols_layout([z] * K, dA, with_ghosts=True)

    def run_k(k):
        fn = make_cg_fn(dA, tol=0.0, maxiter=k, fused=fused, rhs_batch=K)
        fn(db, dz, None)

        def once():
            t0 = time.perf_counter()
            out = fn(db, dz, None)
            np.asarray(out[1])  # host fetch closes the chain
            return time.perf_counter() - t0

        once()
        return statistics.median(once() for _ in range(5))

    t1, t2 = run_k(k1), run_k(k2)
    return max((t2 - t1) / (k2 - k1), 1e-9)


def bench_multirhs(n: int, pa, dA, ks) -> list:
    """The --rhs leg: block-CG marginals at each K, reported per RHS
    with the K=1 leg as the denominator. The full banded flagship curve
    lives in tools/bench_multirhs.py / MULTIRHS_BENCH.json; this leg is
    the quick per-size probe."""
    recs = []
    base = None
    for K in ks:
        t_it = block_cg_marginal_s_per_it(pa, dA, K, 40, 240)
        per_rhs = t_it / K
        if base is None:
            base = per_rhs if K == 1 else None
        recs.append(
            stamp({
                "metric": f"multirhs_cg_s_per_it_per_rhs_{n}cube_K{K}_f32",
                "value": round(per_rhs, 9),
                "unit": "s/iteration/rhs",
                "vs_baseline": 0.0,
                "block_s_per_iteration": round(t_it, 9),
                "rhs_batch": K,
                "per_rhs_speedup_vs_k1": (
                    round(base / per_rhs, 3) if base else None
                ),
            })
        )
    return recs


def bench_ici(n: int, devices, pa, fabric: str):
    """Multi-device halo + CG legs with TRUE neighbor `ppermute`s
    (round-4 directive 8). `main` runs them whenever more than one chip
    is present; the same code runs on the virtual CPU mesh via
    `tools/bench_ici.py` with the records labeled
    ``fabric='virtual-cpu'`` (kernel-correctness only — virtual-mesh
    bandwidth says nothing about ICI wires). Reference anchor: the
    multi-node exchange these legs will measure,
    /root/reference/src/MPIBackend.jl:213-309."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from partitionedarrays_jl_tpu.parallel.sequential import SequentialBackend
    from partitionedarrays_jl_tpu.parallel.tpu import (
        TPUBackend, device_matrix, make_exchange_fn, _stage,
    )

    shapes = {8: (2, 2, 2), 4: (2, 2, 1), 2: (2, 1, 1)}
    P = max(k for k in shapes if k <= len(devices))
    pshape = shapes[P]
    backend = TPUBackend(devices=devices[:P])
    dtype = np.float32

    # --- halo leg: the compiled multi-shard exchange, loop-carried ----
    seq = SequentialBackend()
    rows = pa.prun(
        lambda parts: pa.prange(parts, (n, n, n), pa.with_ghost),
        seq, pshape,
    )
    exch = make_exchange_fn(rows, backend)
    from partitionedarrays_jl_tpu.parallel.tpu import (
        _padded_for, device_exchange_plan,
    )

    # the SAME layout the exchange program was compiled against — on a
    # real TPU _padded_for selects the padded frame with different
    # o0/g0/W (review r4: a device_layout(rows, False) input here would
    # shape-mismatch the compiled chain on the ici fabric)
    layout = device_exchange_plan(rows, _padded_for(backend)).layout
    payload = sum(
        i.num_hids for i in rows.partition.part_values()
    ) * np.dtype(dtype).itemsize
    x0 = np.ones((P, layout.W), dtype=dtype)
    x = _stage(backend, x0, P)
    o_last = layout.o0 + layout.no_max - 1

    @partial(jax.jit, static_argnums=1)
    def chain(xv, k):
        def step(_, v):
            v = exch(v)
            # loop-carried feedback: owned values must evolve or XLA
            # hoists the packs (docs/performance.md methodology)
            return v.at[:, o_last].add(
                v[:, layout.g0] * jnp.asarray(1e-30, v.dtype)
            )

        return jax.lax.fori_loop(0, k, step, xv).sum()

    run_chain = lambda k: float(chain(x, k))
    dt = marginal_chain_time(run_chain, 50, 650)
    halo_rec = stamp({
        "metric": f"ici_halo_bytes_per_s_aggregate_{n}cube_{P}dev_f32",
        "value": round(payload / dt, 1),
        "unit": "B/s",
        "vs_baseline": 0.0,
        "fabric": fabric,
        "devices": P,
        "payload_bytes_per_exchange": payload,
    })

    # --- CG leg: fixed-trip marginal per iteration over the mesh ------
    def driver(parts):
        A, b, xe, x0v = assemble_poisson_scaled(parts, (n, n, n), pa, dtype)
        return A

    A = pa.prun(driver, backend, pshape)
    dA = device_matrix(A, backend)
    cg_rec = stamp({
        "metric": f"ici_cg_s_per_iteration_{n}cube_{P}dev_f32",
        "value": round(cg_marginal_s_per_it(pa, dA, 40, 440), 6),
        "unit": "s/iteration",
        "vs_baseline": 0.0,
        "fabric": fabric,
        "devices": P,
    })
    return [halo_rec, cg_rec]


def assemble_poisson_scaled(parts, ns, pa, dtype):
    """The bench operator: 1/16-scaled Poisson in `dtype` (bounded under
    repeated application), shared by the single-chip and ICI legs."""
    from partitionedarrays_jl_tpu.models import assemble_poisson

    A, b, xe, x0 = assemble_poisson(parts, ns)
    A.values = pa.map_parts(
        lambda M: pa.CSRMatrix(
            M.indptr, M.indices, (M.data / 16).astype(dtype), M.shape
        ),
        A.values,
    )
    A.invalidate_blocks()
    xe.values = pa.map_parts(lambda v: np.asarray(v, dtype=dtype), xe.values)
    return A, b, xe, x0


def spmv_chain(n: int, backend, pa):
    """Build the SHIPPED SpMV timing chain: the 1/16-scaled n^3 Poisson
    operator lowered to the device, a jitted k-step `fori_loop` of
    dependent SpMVs ending in a scalar fetch. Returns
    ``(run_chain, A, dA, flops)``. One builder shared by `main` and
    `tools/bench_repro.py` so the band-calibration study can never
    desynchronize from the guard it calibrates."""
    import jax
    from functools import partial

    from partitionedarrays_jl_tpu.parallel.tpu import (
        DeviceVector, device_matrix, make_spmv_fn,
    )

    dtype = np.float32

    def driver(parts):
        # 1/16-scaled so the timing chain (repeated application) stays
        # bounded: the raw 7-point operator amplifies ~12x per step
        A, b, x_exact, x0 = assemble_poisson_scaled(parts, (n, n, n), pa, dtype)
        return A, x_exact

    A, x = pa.prun(driver, backend, (1, 1, 1))
    dA = device_matrix(A, backend)
    dx = DeviceVector.from_pvector(x, backend, dA.col_layout)
    spmv = make_spmv_fn(dA)
    assert dx.data.shape == spmv(dx.data).shape, "square chain layout expected"

    @partial(jax.jit, static_argnums=1)
    def chain(xv, k):
        return jax.lax.fori_loop(0, k, lambda i, y: spmv(y), xv).sum()

    return (
        lambda k: float(chain(dx.data, k)),
        A,
        x,
        dA,
        dA.flops_per_spmv,
    )


def main():
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            f"bench.py measures a TPU, and JAX found {devices}: a timing "
            "from any other backend is not a device metric."
        )

    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.ops.sparse import csr_spmv
    from partitionedarrays_jl_tpu.parallel.tpu import TPUBackend

    pa.enable_compilation_cache()
    n = int(os.environ.get("PA_BENCH_N", "192"))  # n^3 cells, 7-pt stencil
    reps = int(os.environ.get("PA_BENCH_REPS", "50"))
    dtype = np.float32

    backend = TPUBackend(devices=devices[:1])

    # Device timing by *marginal* chain cost: chain K dependent SpMVs in
    # ONE compiled program, force completion with a host scalar fetch,
    # and difference two well-separated chain lengths (medians over
    # reps) so the fixed per-dispatch cost cancels. The operator is
    # pre-scaled (see spmv_chain) so repeated application stays bounded
    # instead of overflowing, which would poison the timing.
    import statistics

    run_chain, A, x, dA, flops = spmv_chain(n, backend, pa)
    dt = marginal_chain_time(run_chain, 50, 50 + 8 * max(50, reps))
    gflops = flops / dt / 1e9

    # sequential-oracle timing on the same local problem (NumPy CSR).
    # Median of per-run times, not a mean: host contention produces slow
    # outliers that made the reported ratio swing 3x between otherwise
    # identical runs.
    M = A.values.part_values()[0]
    xv = np.asarray(x.values.part_values()[0], dtype=dtype)
    host_reps = max(3, min(7, reps // 7))
    csr_spmv(M, xv)  # warm
    host_ts = []
    for _ in range(host_reps):
        t0 = time.perf_counter()
        csr_spmv(M, xv)
        host_ts.append(time.perf_counter() - t0)
    host_dt = statistics.median(host_ts)
    host_gflops = flops / host_dt / 1e9

    # halo microbench first; the primary SpMV metric stays the LAST line.
    # A leg that raises ends the run with its traceback and a non-zero
    # exit: a partial result is not a result.
    print(json.dumps(bench_halo(n, backend, pa)), flush=True)

    # full-CG CPU comparand at matched DOFs/core (BASELINE.json north-star
    # gate: ">=5x MPIBackend ... at 1e7 DOFs/core" — 192^3 is 7.1M DOFs on
    # one part/one chip). The host number is a REAL measurement of this
    # repo's sequential backend (the reference's one-core execution
    # model: eager per-part NumPy, same CG recurrence), not a self-ratio.
    print(json.dumps(bench_cg_vs_cpu(n, backend, pa, dA)), flush=True)

    # multi-RHS leg: `--rhs 1,2,4,8` (or PA_BENCH_RHS) runs block-CG
    # marginals at each K and reports per-RHS cost vs the K=1 leg
    rhs_arg = os.environ.get("PA_BENCH_RHS", "")
    argv = sys.argv[1:]
    if "--rhs" in argv and argv.index("--rhs") + 1 < len(argv):
        rhs_arg = argv[argv.index("--rhs") + 1]
    if rhs_arg:
        ks = [int(s) for s in rhs_arg.split(",") if s]
        for r in bench_multirhs(n, pa, dA, ks):
            print(json.dumps(r), flush=True)

    # ICI legs: only when more than one chip is present — true neighbor
    # ppermutes (the virtual-mesh form runs via tools/bench_ici.py)
    if len(devices) > 1:
        for r in bench_ici(n, devices, pa, "ici"):
            print(json.dumps(r), flush=True)

    print(json.dumps(stamp({
        "metric": f"spmv_gflops_per_chip_poisson3d_{n}cube_f32",
        "value": round(gflops, 3),
        "unit": "GFLOP/s",
        "vs_baseline": round(gflops / host_gflops, 3),
    })))


if __name__ == "__main__":
    main()
