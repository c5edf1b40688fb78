"""Compiled LOBPCG: the whole block eigensolve as ONE shard_map program.

The host loop in models/solvers.py issues eager ops per block vector; here
the entire iteration — m overlapped SpMVs, the (3m, n) basis Gram products
(MXU matmuls riding one all_gather each), the whitened Rayleigh–Ritz
eigenproblem (`jnp.linalg.eigh` on the replicated 3m×3m pencil), and the
convergence test — lives inside a single `lax.while_loop`.

Fixed-shape stabilization: the host path DROPS near-dependent basis
directions (a data-dependent rank, impossible under jit); here the
whitening keeps all 3m directions but clamps tiny Gram eigenvalues and
adds a large diagonal penalty to the masked directions in the reduced
pencil, pushing the spurious Ritz values to the far end of the sought
spectrum, where the top-m selection never picks them. Same span, jit-able
shapes; trajectories therefore differ from the host oracle in late
iterations, so the cross-backend gate is eigenpair accuracy, not
iteration parity.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ..utils.helpers import check
from .pvector import PVector
from .tpu import (
    _shard_ops,
    DeviceVector,
    TPUBackend,
    _matrix_operands,
    _spmv_body,
    _stage,
    device_matrix,
)


def make_lobpcg_fn(
    dA, nev: int, tol: float, maxiter: int, largest: bool, precond: bool,
    gmg_h=None,
):
    """``gmg_h`` (a models.gmg.GMGHierarchy) inlines the ENTIRE multigrid
    V-cycle as the preconditioner applied to each residual block row —
    multigrid-preconditioned modal analysis as ONE compiled program."""
    import jax
    import jax.numpy as jnp
    shard_map = jax.shard_map

    m = int(nev)
    mesh = dA.backend.mesh(dA.row_layout.P)
    spec = dA.backend.parts_spec()
    none_spec = jax.sharding.PartitionSpec()
    body_spmv = _spmv_body(dA)
    L = dA.col_plan.layout
    Lr = dA.row_layout
    no = L.no_max
    sl = slice(L.o0, L.o0 + no)
    ops = _matrix_operands(dA)
    specs = jax.tree.map(lambda _: spec, ops)
    sgn = -1.0 if largest else 1.0
    # the closures below must reference only this BOOL, never gmg_h
    # itself: the returned fn lives in a cache evicted by a weakref
    # finalizer on the hierarchy, which can only fire if the fn does not
    # hold the hierarchy alive (its staged operands ride `dh`/`vcycle`)
    has_gmg = gmg_h is not None
    if has_gmg:
        from .tpu_gmg import (
            _device_hierarchy, _gmg_operands, _vcycle_shard_body,
        )

        dh = _device_hierarchy(gmg_h, dA.backend)
        vcycle = _vcycle_shard_body(gmg_h, dh)
        gops = _gmg_operands(dh)
        gspecs = jax.tree.map(lambda _: spec, gops)
        cinv_host = dh["cinv"]

    @jax.jit
    def fn(X0, mv, mats_in, *g):
        def shard_fn(X0s, mvs, ms, *gs):
            X = X0s[0]  # (m, no) owned block
            mats = _shard_ops(jax, ms)
            mvv = mvs[0]
            dt = X.dtype
            if has_gmg:
                gmat = _shard_ops(jax, gs[0])
                cinv_r = gs[1]

            def gsum(partial_):
                return jnp.sum(jax.lax.all_gather(partial_, "parts"), axis=0)

            def spmv_rows(B):  # (k, no) -> (k, no), row-wise A @ b
                def one(b_owned):
                    z = jnp.zeros(L.W, dtype=dt).at[sl].set(b_owned)
                    y, _ = body_spmv(z, mats)
                    return y[Lr.o0 : Lr.o0 + no]

                return jnp.stack([one(B[i]) for i in range(B.shape[0])])

            def gram(U, V):  # (a, no), (b, no) -> (a, b) cross-part
                return gsum(U @ V.T)

            def rownorms(B):
                return jnp.sqrt(gsum(jnp.sum(B * B, axis=1)))

            def unit_rows(B):
                nrm = rownorms(B)
                safe = jnp.where(nrm > 0, nrm, 1.0)
                return B / safe[:, None]

            # orthonormalize the start block (whitened, no dropping)
            def whiten(G):
                w, Q = jnp.linalg.eigh(G)
                wmax = jnp.maximum(w[-1], jnp.asarray(1e-300, dt))
                bad = w <= wmax * 1e-10
                ws = jnp.where(bad, wmax, w)
                return Q / jnp.sqrt(ws)[None, :], bad

            B0, _ = whiten(gram(X, X))
            X = B0.T @ X
            AX = spmv_rows(X)
            P = jnp.zeros_like(X)
            AP = jnp.zeros_like(X)
            lam0 = gsum(jnp.sum(X * AX, axis=1))
            # full-length history: parity with the host info contract
            # (rows beyond the reached iteration stay NaN and are
            # compacted away on the way out)
            hist = jnp.full((int(maxiter), m), jnp.nan, dtype=dt)

            def cond(st):
                _X, _AX, _P, _AP, _lam, res, it, _h = st
                lam = _lam
                good = res <= tol * jnp.maximum(1.0, jnp.abs(lam))
                return (~jnp.all(good)) & (it < maxiter)

            def step(st):
                X, AX, P, AP, lam, _res, it, hist = st
                R = AX - lam[:, None] * X
                if has_gmg:
                    # one full V-cycle per residual block row, inlined
                    def prec_one(r_owned):
                        rv = jnp.zeros(L.W, dtype=dt).at[sl].set(r_owned)
                        return vcycle(rv, gmat, cinv_r)[sl]

                    W = jnp.stack([prec_one(R[i]) for i in range(m)])
                elif precond:
                    W = R * mvv[None, sl]
                else:
                    W = R
                W = unit_rows(W)
                Pn = unit_rows(P)
                S = jnp.concatenate([X, W, Pn], axis=0)  # (3m, no)
                AW = spmv_rows(W)
                # A @ Pn: P rows were unit-scaled; scale AP identically
                pnrm = rownorms(P)
                psafe = jnp.where(pnrm > 0, pnrm, 1.0)
                APn = AP / psafe[:, None]
                AS = jnp.concatenate([AX, AW, APn], axis=0)
                G_a = gram(S, AS)
                G_m = gram(S, S)
                Bw, bad = whiten(G_m)
                red = Bw.T @ (sgn * G_a) @ Bw
                # masked (near-dependent) directions: huge diagonal
                # penalty pushes their Ritz values past the sought end
                big = jnp.asarray(1e12, dt) * (
                    1.0 + jnp.max(jnp.abs(red))
                )
                red = red + jnp.diag(big * bad.astype(dt))
                red = 0.5 * (red + red.T)
                _w_r, Q_r = jnp.linalg.eigh(red)
                C = Bw @ Q_r[:, :m]  # (3m, m)
                X_new = C.T @ S
                AX_new = C.T @ AS
                Cp = C.at[:m, :].set(0.0)
                P_new = Cp.T @ S
                AP_new = Cp.T @ AS
                lam_new = gsum(jnp.sum(X_new * AX_new, axis=1)) / gsum(
                    jnp.sum(X_new * X_new, axis=1)
                )
                Rn = AX_new - lam_new[:, None] * X_new
                res_new = rownorms(Rn)
                hist = hist.at[jnp.minimum(it, hist.shape[0] - 1)].set(
                    res_new
                )
                return (
                    X_new, AX_new, P_new, AP_new, lam_new, res_new,
                    it + 1, hist,
                )

            R0 = AX - lam0[:, None] * X
            res0 = rownorms(R0)
            X, AX, P, AP, lam, res, it, hist = jax.lax.while_loop(
                cond, step, (X, AX, P, AP, lam0, res0, jnp.int32(0), hist)
            )
            # sort by the sought direction
            order = jnp.argsort(sgn * lam)
            return X[order][None], lam[order], res[order], it, hist

        in_specs = (spec, spec, specs)
        if has_gmg:
            in_specs = in_specs + (gspecs, none_spec)
        return shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(spec, none_spec, none_spec, none_spec, none_spec),
            check_vma=False,
        )(X0, mv, mats_in, *g)

    def run(X0, mv):
        if has_gmg:
            return fn(X0, X0 if mv is None else mv, ops, gops, cinv_host)
        return fn(X0, X0 if mv is None else mv, ops)

    return run


def tpu_lobpcg(
    A,
    nev: int = 1,
    X0=None,
    minv: Optional[PVector] = None,
    tol: float = 1e-6,
    maxiter: int = 200,
    largest: bool = False,
    seed: int = 0,
    verbose: bool = False,
):
    """Device LOBPCG (see make_lobpcg_fn): X0/minv are staged into the
    matrix's column layout; eigenvectors come back as PVectors."""
    from ..models.gmg import GMGHierarchy

    backend = A.values.backend if hasattr(A.values, "backend") else None
    check(isinstance(backend, TPUBackend), "tpu_lobpcg needs the TPU backend")
    gmg_h = minv if isinstance(minv, GMGHierarchy) else None
    check(
        minv is None or gmg_h is not None or isinstance(minv, PVector),
        "tpu_lobpcg takes a diagonal PVector or GMGHierarchy "
        "preconditioner — for other callables use models.solvers.lobpcg "
        "(host loop)",
    )
    m = int(nev)
    dA = device_matrix(A, backend)
    L = dA.col_plan.layout
    if gmg_h is not None:
        # the hierarchy's level-0 operator must share A's device frame
        dA0 = device_matrix(gmg_h.levels[0].A, backend)
        check(
            dA0.col_plan.layout.W == L.W and dA0.col_plan.layout.o0 == L.o0,
            "tpu_lobpcg: the hierarchy's level-0 frame differs from A's — "
            "build the hierarchy from the operator being solved",
        )
        import weakref

        from .tpu_gmg import _gmg_env_key

        # cached ON the matrix's device lowering (the tpu.py rule: a
        # fn's lifetime is tied to the operator whose staged operands
        # its closure holds), keyed by the hierarchy's id plus the env
        # modes. Of those, only PA_TPU_GMG_BOX does real keying work
        # here — the DeviceMatrix lowering modes are already baked into
        # dA's identity via device_matrix's own key, and ride along as
        # defense-in-depth against future cache restructuring. The id is
        # safe (no strong ref -> no pinning) because a finalizer evicts
        # the entry when the hierarchy dies — before its id can be
        # reused — which also frees the fn's staged level operands for
        # callers that rebuild hierarchies in a loop; the fn itself
        # references only `dh`/`vcycle`, never gmg_h (see
        # make_lobpcg_fn's has_gmg note).
        key = (
            "lobpcg-gmg", id(gmg_h), m, float(tol), int(maxiter),
            bool(largest),
        ) + _gmg_env_key(backend)
        if key not in dA._cg_cache:
            dA._cg_cache[key] = make_lobpcg_fn(
                dA, m, tol, maxiter, largest, False, gmg_h=gmg_h
            )
            weakref.finalize(gmg_h, dA._cg_cache.pop, key, None)
        solve = dA._cg_cache[key]
    else:
        key = (
            "lobpcg", m, float(tol), int(maxiter), bool(largest),
            minv is not None,
        )
        if key not in dA._cg_cache:
            dA._cg_cache[key] = make_lobpcg_fn(
                dA, m, tol, maxiter, largest, minv is not None
            )
        solve = dA._cg_cache[key]

    dt = A.dtype
    P = L.P
    Xs = np.zeros((P, m, L.no_max), dtype=dt)
    if X0 is not None:
        check(len(X0) == m, "tpu_lobpcg: X0 must hold nev vectors")
        for k, v in enumerate(X0):
            dv = DeviceVector.from_pvector(v, backend, L)
            Xs[:, k, :] = np.asarray(dv.data)[:, L.o0 : L.o0 + L.no_max]
    else:
        for p, iset in enumerate(A.cols.partition.part_values()):
            for k in range(m):
                rng = np.random.default_rng(seed + 7919 * k + int(iset.part))
                Xs[p, k, : iset.num_oids] = rng.standard_normal(iset.num_oids)
    X0d = _stage(backend, Xs, P)
    if minv is not None and gmg_h is None:
        mv = DeviceVector.from_pvector(minv, backend, L).data
    else:
        mv = None
    Xd, lam, res, it, hist = solve(X0d, mv)
    lam = np.asarray(lam)
    res = np.asarray(res)
    it = int(it)
    Xh = np.asarray(Xd)  # (P, m, no)
    vecs = []
    for k in range(m):
        full = np.zeros((P, L.W), dtype=dt)
        full[:, L.o0 : L.o0 + L.no_max] = Xh[:, k, :]
        data = _stage(backend, full, P)
        vecs.append(DeviceVector(data, A.cols, L, backend).to_pvector())
    hist = np.asarray(hist)
    hist = hist[~np.isnan(hist[:, 0])]
    if verbose:
        for i, row in enumerate(hist):
            print(f"lobpcg it={i + 1} max|r|={row.max():.3e}")
    return lam, vecs, {
        "iterations": it,
        "residual_norms": hist,
        "converged": bool(np.all(res <= tol * np.maximum(1.0, np.abs(lam)))),
    }
