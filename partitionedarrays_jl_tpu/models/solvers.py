"""Distributed solvers over the PData algebra.

The reference delegates Krylov solves to the *unmodified*
IterativeSolvers.jl CG, which works because PVector/PSparseMatrix provide
`mul!`, `dot`, `norm`, `similar`, broadcast (reference shim:
src/Interfaces.jl:2752-2757). This framework ships its own CG written
against the same primitive set, so the whole loop runs distributed on any
backend — and compiles to a single XLA program on the TPU backend.

Also here: the gather-to-main direct-solve debug path
(reference: src/Interfaces.jl:2626-2748 — `\\`, `lu`/`ldiv!`, `gather`,
`scatter!`).
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ..ops.sparse import CSRMatrix, compresscoo
from ..utils.helpers import check, krylov_info, warn_tol_below_floor
from ..parallel.backends import map_parts
from ..parallel.prange import PRange
from ..parallel.psparse import PSparseMatrix, psparse_global_triplets
from ..parallel.pvector import PVector, _assign_full, _owned, _write_owned


def _final_true_rel(A, x, b, rel_est, rs0_norm, tol, force=False):
    """TRUE final relative residual for status classification: the
    solver's own value when it already passes (converged runs pay no
    extra work), else recomputed from b - A@x — recurrence estimates
    (CG's rs, the Lanczos residual) drift below the true residual on
    ill-conditioned problems and would misreport a genuine failure as a
    benign floor-stall. ``force`` recomputes even on apparent success
    (set when tol sits below the dtype floor, where the recurrence can
    underflow past a test the true residual never meets)."""
    if rel_est <= tol and not force:
        return rel_est
    r = b.copy()
    q = A @ x
    _owned_update(r, lambda rv, qv: rv - qv, q)
    return float(r.norm()) / max(1.0, rs0_norm)


def _host_block_solve(solve_one, B, X0, column_errors="raise"):
    """Host multi-RHS driver: each column runs the SOLO loop — by
    definition the per-column oracle semantics the device block program
    (`tpu.tpu_block_cg`) reproduces. Returns the same ``(xs, info)``
    contract: per-column infos under ``columns``, worst-column
    aggregates at top level.

    ``column_errors="report"`` is the oracle of the device verdict
    export: a column whose solo loop raises a `SolverHealthError` is
    CONTAINED — its slot gets a failed-column info (and the error under
    ``column_health``) while every later column still runs. The default
    ``"raise"`` propagates the first column failure unchanged (the
    pre-service contract)."""
    from ..parallel.health import SolverHealthError

    K = len(B)
    check(K >= 1, "block solve: B must hold at least one right-hand side")
    X0 = list(X0) if X0 is not None else [None] * K
    check(len(X0) == K, "block solve: X0 must hold one start per RHS")
    xs, columns, health = [], [], []
    for k, (bk, x0k) in enumerate(zip(B, X0)):
        try:
            x, inf = solve_one(bk, x0k)
        except SolverHealthError as e:
            if column_errors != "report":
                raise
            from .. import telemetry

            telemetry.emit_event(
                "column_verdict", label="block-host", columns=[k],
                error=type(e).__name__,
            )
            xs.append(x0k.copy() if x0k is not None else None)
            columns.append(
                {
                    "iterations": 0,
                    "residuals": [],
                    "converged": False,
                    "status": type(e).__name__,
                }
            )
            health.append(
                {
                    "status": type(e).__name__,
                    "converged": False,
                    "iterations": 0,
                    "error": e,
                }
            )
            continue
        xs.append(x)
        columns.append(inf)
        health.append(
            {
                "status": "ok",
                "converged": bool(inf["converged"]),
                "iterations": int(inf["iterations"]),
            }
        )
    # unconverged columns dominate the aggregate (see tpu_block_cg: the
    # top-level status must never read 'converged' when converged=False)
    bad_cols = [k for k in range(K) if not columns[k]["converged"]]
    worst = (
        max(bad_cols, key=lambda k: columns[k]["iterations"])
        if bad_cols
        else max(range(K), key=lambda k: columns[k]["iterations"])
    )
    info = {
        "iterations": max(c["iterations"] for c in columns),
        "iterations_per_column": [c["iterations"] for c in columns],
        "residuals": columns[worst]["residuals"],
        "converged": not bad_cols,
        "status": columns[worst]["status"],
        "columns": columns,
        "column_health": health,
        "rhs_batch": K,
        "cg_body": "host",
    }
    return xs, info


def _check_block_args(name, b, x0, B, checkpoint, _resume_state,
                      column_errors="raise"):
    """Validate the multi-RHS call shape; returns B as a list (so an
    empty or generator B fails HERE with the friendly message, not at a
    downstream ``B[0]``)."""
    check(
        column_errors in ("raise", "report"),
        f"{name}: column_errors is 'raise' or 'report'",
    )
    check(
        b is None and x0 is None,
        f"{name}: pass b/x0 OR the multi-RHS block B/X0, not both",
    )
    B = list(B)
    check(
        len(B) >= 1,
        f"{name}: B must hold at least one right-hand side",
    )
    if checkpoint is not None or _resume_state is not None:
        raise ValueError(
            f"{name}: checkpoint/resume is a single-RHS feature — solve "
            "columns individually to checkpoint them"
        )
    return B


class _SDCGuard:
    """Host-loop silent-corruption defense shared by `cg` and `pcg`: the
    periodic true-residual audit plus the bounded in-memory rollback
    ring (`parallel.health.RollbackRing`) — the same audit/rollback
    logic the compiled device loops run in-graph, making the host loop
    the oracle for the SDC recovery ladder:

    1. a detection (`SilentCorruptionError` from an ABFT exchange
       checksum, or a failed audit here) rewinds the recurrence to the
       newest audited ring state — at most ``audit_every`` iterations
       back, NO disk I/O;
    2. consecutive failed replays walk to older ring entries;
    3. after ``PA_HEALTH_MAX_ROLLBACKS`` rollbacks the next detection
       escalates: `SilentCorruptionError` (carrying the counters under
       ``diagnostics["sdc"]``) propagates to `solve_with_recovery`,
       whose checkpoint restart is the disk tier of the ladder.

    Inactive (every call a cheap no-op) unless ``PA_TPU_ABFT=1`` or
    ``PA_HEALTH_AUDIT_EVERY > 0``. The audit's extra ``A @ x`` runs one
    exchange, so the chaos harness's call counter advances faster when
    audits are on (the counter is wire-level, and replayed iterations
    are NEW wire calls — a one-shot ``call=k`` clause never refires on
    replay, which is exactly why a clean replay self-heals)."""

    def __init__(self, name: str, A, b, rs0, health: bool):
        from ..parallel.health import (
            RollbackRing,
            abft_enabled,
            audit_every,
            audit_tolerance,
            max_rollbacks,
        )

        self.name = name
        self.A, self.b = A, b
        self.rs0 = float(rs0)
        self.every = audit_every()
        self.active = bool(health) and (abft_enabled() or self.every > 0)
        self.ring = RollbackRing() if self.active else None
        self.max_rb = max_rollbacks()
        self.tol = audit_tolerance(b.dtype) if self.active else 0.0
        self.strike = 0
        self.counters = {
            "detections": 0,
            "rollbacks": 0,
            "escalations": 0,
            "audit_iterations": 0,
        }

    def push(self, vectors: dict, meta: dict, history) -> None:
        """Record an audited-good state (the initial state counts: it is
        consistent by construction)."""
        if not self.active:
            return
        m = dict(meta)
        m["history"] = [np.float64(h) for h in history]
        self.ring.push(vectors, m)
        self.strike = 0

    def audit(self, x, r, it: int, meta: dict, extra_vectors: dict, history):
        """Every ``audit_every`` iterations: drift = ||(b - A x) - r||
        must sit inside the recurrence's rounding envelope; a pass
        pushes the state onto the ring, a failure raises
        `SilentCorruptionError` (caught by the loop's rollback arm)."""
        if not self.active or self.every <= 0 or it == 0 or it % self.every:
            return
        from ..parallel.health import SilentCorruptionError

        self.counters["audit_iterations"] += 1
        rt = self.b.copy()
        qx = self.A @ x
        _owned_update(rt, lambda tv, qv: tv - qv, qx)
        _owned_update(rt, lambda tv, rv: tv - rv, r)
        drift = float(rt.norm())
        thresh = self.tol * max(1.0, float(np.sqrt(self.rs0)))
        if not (drift <= thresh):  # NaN-safe
            raise SilentCorruptionError(
                f"{self.name}: true-residual audit failed at iteration "
                f"{it} — ||(b - A x) - r|| = {drift:.3e} exceeds "
                f"{thresh:.3e}: the recurrence has silently diverged "
                "from the true residual (finite corruption)",
                diagnostics={
                    "detector": "true_residual_audit",
                    "iteration": int(it),
                    "drift": drift,
                    "threshold": thresh,
                },
            )
        self.push({"x": x, "r": r, **extra_vectors}, meta, history)

    def rollback(self, e, it: int):
        """Handle a detection: restore the ring state ``strike`` slots
        back, or escalate once the budget is spent. Returns
        ``(vectors, meta, history)`` for the loop to reinstate."""
        from .. import telemetry
        from ..parallel.health import SilentCorruptionError

        self.counters["detections"] += 1
        telemetry.emit_event(
            "sdc_detection", label=self.name, iteration=int(it),
            detector=getattr(e, "diagnostics", {}).get("detector"),
        )
        exhausted = self.counters["rollbacks"] >= self.max_rb
        st = (
            self.ring.restore(self.strike)
            if self.active and not exhausted
            else None
        )
        if st is None:
            self.counters["escalations"] += 1
            telemetry.emit_event(
                "sdc_escalation", label=self.name, iteration=int(it),
                rollbacks=self.counters["rollbacks"],
            )
            diag = dict(getattr(e, "diagnostics", {}))
            diag["sdc"] = dict(self.counters)
            diag["iteration"] = int(it)
            raise SilentCorruptionError(
                f"{self.name}: {e} — in-memory rollback budget "
                f"({self.max_rb}) exhausted at iteration {it}; "
                "escalating to the checkpoint-restart tier "
                "(solve_with_recovery)",
                diagnostics=diag,
            ) from e
        self.counters["rollbacks"] += 1
        self.strike += 1
        vecs, meta = st
        telemetry.emit_event(
            "sdc_rollback", label=self.name, iteration=int(it),
            restored_iteration=int(meta.get("it", 0)),
            strike=self.strike,
        )
        return vecs, meta, list(meta["history"])

    def info_extra(self) -> dict:
        return {"sdc": dict(self.counters)} if self.active else {}


def cg(
    A: PSparseMatrix,
    b: Optional[PVector] = None,
    x0: Optional[PVector] = None,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    verbose: bool = False,
    fused: Optional[bool] = None,
    checkpoint=None,
    _resume_state: Optional[dict] = None,
    B=None,
    X0=None,
    column_errors: str = "raise",
) -> Tuple[PVector, dict]:
    """Conjugate gradients for SPD `A`. The start vector lives on
    ``A.cols`` — the PRange carrying the column ghost layer — mirroring the
    reference's `zerox` axes shim (src/Interfaces.jl:2752-2757), so every
    SpMV can halo-update it in place.

    ``B`` (a sequence of K right-hand-side PVectors, with optional
    matching starts ``X0``) selects the MULTI-RHS block solve instead of
    ``b``/``x0``: on the TPU backend the whole block runs as one
    compiled program whose SpMV streams the operator once per K columns
    (tpu.make_block_cg_fn — SpMV becomes SpMM, halo rounds ship K-column
    slabs, all K dot partials ride the existing collectives); each
    column still follows the textbook single-vector recurrence exactly,
    freezing when it converges, so per-column trajectories match solo
    solves (bitwise under strict-bits). On the host backend the columns
    simply run the solo loop in sequence — the semantics oracle. Returns
    ``(xs, info)`` with a list of K solutions and per-column infos under
    ``info["columns"]``. ``column_errors="report"`` (block solves only)
    contains column-local failures instead of raising: per-column
    verdicts land under ``info["column_health"]`` — the blast-radius
    contract the solve service (`pa.service.SolveService`) builds on.

    Deterministic: all reductions are fixed-order part folds; the residual
    history is reproducible bit-for-bit for a given backend, and on the TPU
    backend it matches the sequential oracle to FMA rounding with identical
    iteration counts (exchanges are bit-identical — the BASELINE.md gate).

    ``fused`` selects the TPU backend's fused streaming body (default:
    resolved from ``PA_TPU_FUSED_CG`` — ON outside strict-bits): one
    update+dot sweep, direction fold riding the SpMV pass — same
    trajectory, fewer large-N HBM sweeps per iteration
    (tpu.py:make_cg_fn). This host loop IS the fused body's value
    sequence already (eager NumPy), so the flag is a host no-op; the
    device info dict records the body under ``cg_body``.

    Resilience hooks: ``checkpoint`` takes a
    `parallel.checkpoint.SolverCheckpointer`; every ``checkpoint.every``
    iterations the FULL recurrence state (x, r, p + scalars) is saved in
    partition-independent form, and `resume_solve` /
    `solve_with_recovery` continue the exact recurrence from it (same
    trajectory, bit-identical final iterate on the same partition).
    Health guards (parallel/health.py) cost one scalar test per
    iteration on the already-reduced r·r — no extra collectives — and
    raise typed `SolverHealthError`s instead of silently diverging.
    """
    from ..parallel.tpu import TPUBackend, tpu_block_cg, tpu_cg

    if B is not None:
        B = _check_block_args(
            "cg", b, x0, B, checkpoint, _resume_state, column_errors
        )
        if isinstance(B[0].values.backend, TPUBackend):
            return tpu_block_cg(
                A, B, X0=X0, tol=tol, maxiter=maxiter, verbose=verbose,
                fused=fused, column_errors=column_errors,
            )
        return _host_block_solve(
            lambda bk, x0k: cg(
                A, bk, x0=x0k, tol=tol, maxiter=maxiter, verbose=verbose
            ),
            B, X0, column_errors=column_errors,
        )
    check(b is not None, "cg: a right-hand side b (or a block B) is required")
    if isinstance(b.values.backend, TPUBackend):
        if checkpoint is not None or _resume_state is not None:
            raise ValueError(
                "cg: per-iteration checkpointing is a host-loop feature — "
                "the compiled device solve cannot stop mid-program; use "
                "models.solvers.solve_with_recovery, which chunks the "
                "compiled solve at checkpoint boundaries"
            )
        # Device path: the whole loop is one compiled shard_map program.
        return tpu_cg(
            A, b, x0=x0, tol=tol, maxiter=maxiter, verbose=verbose,
            fused=fused,
        )
    from .. import telemetry

    maxiter = maxiter if maxiter is not None else 4 * A.rows.ngids
    with telemetry.solve_scope(
        "cg", backend="host", tol=float(tol), maxiter=int(maxiter),
        resumed=_resume_state is not None,
    ) as rec:
        x, info = _cg_host_loop(
            A, b, x0, tol, maxiter, verbose, checkpoint, _resume_state
        )
        # paspec: spectral estimate + anomaly detection, host-side on
        # the recorded recurrence, BEFORE finish (events land on rec)
        telemetry.observe_solve(A, rec, info=info, dtype=b.dtype)
        return x, rec.finish(info)


def _cg_host_loop(A, b, x0, tol, maxiter, verbose, checkpoint, _resume_state):
    """The host (sequential-backend) CG recurrence — the semantics
    oracle the compiled bodies are pinned against. Factored out of `cg`
    so the telemetry solve scope wraps it without touching the loop."""
    from ..parallel.health import (
        SilentCorruptionError,
        SolverBreakdownError,
        StagnationDetector,
        check_finite_scalar,
        health_enabled,
        stagnation_raises,
    )

    floor_warned = warn_tol_below_floor(tol, b.dtype, name="cg")

    if _resume_state is not None:
        x, r, p = _resume_state["x"], _resume_state["r"], _resume_state["p"]
        meta = _resume_state["meta"]
        rs, rs0, it = meta["rs"], meta["rs0"], int(meta["it"])
        history = [np.float64(h) for h in meta["history"]]
    else:
        x = x0.copy() if x0 is not None else PVector.full(0.0, A.cols, dtype=b.dtype)
        r = b.copy()  # rows-range residual
        q = A @ x
        _owned_update(r, lambda rv, qv: rv - qv, q)
        p = PVector.full(0.0, A.cols, dtype=b.dtype)
        _owned_assign(p, r)
        rs = r.dot(r)
        rs0 = rs
        history = [np.sqrt(rs)]
        it = 0
    health = health_enabled()
    if health and _resume_state is None:
        # a NaN in b/x0 (or in the initial residual's halo exchange)
        # makes the while test silently False — guard BEFORE the loop so
        # a poisoned start raises instead of returning converged=False
        check_finite_scalar(rs, "cg", it=0, vectors=(("r", r), ("x", x)))
    # host α/β recording (the device ring's oracle twin): the spectrum
    # layer reconstructs the Lanczos tridiagonal from these — two float
    # appends per iteration, rewound with the SDC rollback
    it0 = it
    ab_alpha: list = []
    ab_beta: list = []
    stag = StagnationDetector("cg") if health and stagnation_raises() else None
    sdc = _SDCGuard("cg", A, b, rs0, health)
    sdc.push({"x": x, "r": r, "p": p}, {"rs": rs, "it": it}, history)
    while np.sqrt(rs) > tol * max(1.0, np.sqrt(rs0)) and it < maxiter:
        try:
            q = A @ p
            pq = p.dot(q)  # owned dot across owned-compatible PRanges
            if pq == 0.0:
                raise SolverBreakdownError(
                    "cg: breakdown, p'Ap == 0",
                    diagnostics={"iteration": it, "rs": float(rs)},
                )
            alpha = rs / pq
            _owned_update(x, lambda xv, pv: xv + alpha * pv, p)
            _owned_update(r, lambda rv, qv: rv - alpha * qv, q)
            rs_new = r.dot(r)
            if health:
                # free: rs_new was reduced anyway; the per-part sweep only
                # runs after the scalar trips
                check_finite_scalar(
                    rs_new, "cg", it=it + 1,
                    vectors=(("r", r), ("q", q), ("x", x)),
                )
            beta = rs_new / rs
            _owned_update(p, lambda pv, rv: rv + beta * pv, r)
            rs = rs_new
            history.append(np.sqrt(rs))
            it += 1
            ab_alpha.append(float(alpha))
            ab_beta.append(float(beta))
            # periodic true-residual audit: recompute b - A x and cross-
            # check the recurrence residual (catches the drift a FINITE
            # corruption leaves behind); the passing state is pushed onto
            # the in-memory rollback ring
            sdc.audit(x, r, it, {"rs": rs, "it": it}, {"p": p}, history)
        except SilentCorruptionError as e:
            # in-memory rollback: rewind to the newest audited ring state
            # (<= audit_every iterations back), no disk I/O; escalate to
            # the caller (solve_with_recovery's checkpoint restart) once
            # the rollback budget is spent
            vecs, meta_r, history = sdc.rollback(e, it)
            x, r, p = vecs["x"], vecs["r"], vecs["p"]
            rs, it = meta_r["rs"], meta_r["it"]
            del ab_alpha[max(0, it - it0):]
            del ab_beta[max(0, it - it0):]
            continue
        if stag is not None:
            stag.update(float(np.sqrt(rs)), it)
        if checkpoint is not None and checkpoint.due(it):
            checkpoint.save_state(
                {"x": x, "r": r, "p": p},
                {
                    "method": "cg", "it": it, "rs": rs, "rs0": rs0,
                    "tol": tol, "maxiter": maxiter, "history": history,
                },
            )
        if verbose:
            print(f"cg it={it} residual={np.sqrt(rs):.3e}")
    if checkpoint is not None:
        checkpoint.wait()  # the last write must land before we return
    _attach_host_ab(ab_alpha, ab_beta, it0)
    return x, krylov_info(
        it, history, np.sqrt(rs) <= tol * max(1.0, np.sqrt(rs0)),
        tol, b.dtype, floor_warned,
        final_rel=_final_true_rel(
            A, x, b, np.sqrt(rs) / max(1.0, np.sqrt(rs0)), np.sqrt(rs0),
            tol, force=floor_warned,
        ),
        **sdc.info_extra(),
    )


def _attach_host_ab(ab_alpha, ab_beta, it0: int) -> None:
    """Stamp a host loop's recorded α/β recurrence onto the active
    `SolveRecord` (the device trace ring's oracle twin — the spectrum
    layer reads either identically). No-op on inert records or
    zero-iteration solves."""
    from .. import telemetry

    rec = telemetry.current_record()
    if rec is None or not rec.enabled or not ab_alpha:
        return
    rec.alpha = list(ab_alpha)
    rec.beta = list(ab_beta)
    rec.trace_start = int(it0)


def gershgorin_bounds(A: PSparseMatrix) -> Tuple[float, float]:
    """Gershgorin spectral interval: every eigenvalue lies in
    [min_i (a_ii - R_i), max_i (a_ii + R_i)] with R_i the off-diagonal
    absolute row sum. Owned rows only + cross-part reduce. Note the lower
    bound is typically <= 0 for Laplacian-like operators (diagonally
    semi-dominant rows), so it is an `lmax` source for `chebyshev_solve`,
    not an `lmin` source."""
    from ..parallel.backends import map_parts
    from ..parallel.collectives import preduce

    def _bounds(ri, ci, M):
        lo, hi = np.inf, -np.inf
        val = M.data
        diag = np.zeros(M.shape[0], dtype=val.dtype)
        radius = np.zeros(M.shape[0], dtype=val.dtype)
        r = M.row_of_nz()
        row_gid = np.asarray(ri.lid_to_gid)[r] if len(r) else r
        col_gid = np.asarray(ci.lid_to_gid)[M.indices] if M.nnz else r
        on_diag = row_gid == col_gid
        np.add.at(diag, r[on_diag], val[on_diag])
        np.add.at(radius, r[~on_diag], np.abs(val[~on_diag]))
        own = np.asarray(ri.lid_to_part) == ri.part
        if own.any():
            lo = float((diag - radius)[own].min())
            hi = float((diag + radius)[own].max())
        return lo, hi

    per = map_parts(_bounds, A.rows.partition, A.cols.partition, A.values)
    lo = preduce(min, map_parts(lambda t: t[0], per), init=np.inf)
    hi = preduce(max, map_parts(lambda t: t[1], per), init=-np.inf)
    return float(lo), float(hi)


def lanczos_bounds(
    A: PSparseMatrix,
    iters: int = 30,
    seed: int = 0,
    safety: Tuple[float, float] = (0.5, 1.05),
) -> Tuple[float, float]:
    """Extremal-eigenvalue estimates for symmetric ``A`` by a k-step
    Lanczos recurrence (the practical companion to `gershgorin_bounds`,
    whose lower bound is useless for Laplacians): returns
    ``(ritz_min * safety[0], ritz_max * safety[1])``.

    Semantics to respect: the largest Ritz value converges to λmax from
    BELOW and the smallest to λmin from ABOVE, so the margins widen the
    interval outward on BOTH ends, sign-aware: for an SPD spectrum the
    defaults reproduce the classic (0.5·ritz_min, 1.05·ritz_max); for
    indefinite or negative spectra the margins still push lo down and hi
    up (a naive multiplicative scale would invert direction on negative
    Ritz values). The start vector is seeded per part (deterministic
    across runs and backends)."""
    check(iters >= 2, "lanczos_bounds needs at least 2 iterations")

    def _rand(iset):
        rng = np.random.default_rng(seed + int(iset.part))
        vals = np.zeros(iset.num_lids)
        out = rng.standard_normal(iset.num_oids)
        return _write_owned(iset, vals, out)

    v = PVector(map_parts(_rand, A.cols.partition), A.cols)
    nrm = v.norm()
    check(nrm > 0, "lanczos_bounds: zero start vector")
    v = v / nrm
    v_old = PVector.full(0.0, A.cols, dtype=v.dtype)
    beta = 0.0
    alphas, betas = [], []
    for _ in range(int(iters)):
        av = A @ v
        alpha = float(v.dot(av))
        alphas.append(alpha)
        bk = beta
        vo = v_old
        lan = PVector.full(0.0, A.cols, dtype=v.dtype)
        _owned_zip(
            lan, lambda _l, qv, vv, ov: qv - alpha * vv - bk * ov, av, v, vo
        )
        beta = float(lan.norm())
        if beta <= 1e-14 * max(abs(a) for a in alphas):
            break  # invariant subspace: the Ritz values are exact
        betas.append(beta)
        v_old, v = v, lan / beta
    k = len(alphas)
    T = np.diag(np.array(alphas))
    if k > 1:
        off = np.array(betas[: k - 1])
        T += np.diag(off, 1) + np.diag(off, -1)
    ritz = np.linalg.eigvalsh(T)
    spread = max(float(ritz[-1] - ritz[0]), 1e-30)
    r0, r1 = float(ritz[0]), float(ritz[-1])
    # Lanczos converges fast at the dominant (large-|λ|) end and slowly
    # at the near-zero end, so the strong margin (safety[0], a toward-
    # zero halving that can never cross zero) goes to whichever extreme
    # is near zero, and the mild outward inflation (safety[1]) to the
    # dominant end(s). Indefinite spectra have two dominant ends.
    s0, s1 = float(safety[0]), float(safety[1])
    if r0 > 0.0:  # positive spectrum: min is the near-zero end
        lo, hi = r0 * s0, r1 * s1
    elif r1 < 0.0:  # negative spectrum: max is the near-zero end
        lo, hi = r0 * s1, r1 * s0
    else:  # indefinite (or an exactly-zero extreme): inflate both ends
        lo = r0 * s1 if r0 != 0.0 else -(s1 - 1.0) * spread
        hi = r1 * s1 if r1 != 0.0 else (s1 - 1.0) * spread
    return float(lo), float(hi)


def lobpcg(
    A: PSparseMatrix,
    nev: int = 1,
    X0=None,
    minv=None,
    tol: float = 1e-6,
    maxiter: int = 200,
    largest: bool = False,
    seed: int = 0,
    verbose: bool = False,
):
    """Locally-optimal block preconditioned conjugate gradients: the
    ``nev`` smallest (or largest) eigenpairs of symmetric ``A`` — the
    distributed eigensolver the reference inherits from
    IterativeSolvers.jl's `lobpcg` (src/Interfaces.jl:2752-2757 makes it
    run on a PSparseMatrix). All tall-skinny algebra is PVector blocks
    (owned dots + cross-part reduce); the 3·nev-dimensional
    Rayleigh–Ritz eigenproblem is solved replicated on the host.
    ``minv`` is an optional preconditioner: an inverse-diagonal PVector
    or any callable ``minv(r) -> z`` (a `GMGHierarchy`,
    `additive_schwarz(mode='asm')`, ...).

    Returns ``(eigenvalues (nev,), eigenvectors: list of PVector,
    info)``. On the TPU backend (diagonal or no preconditioner) the
    WHOLE eigensolve — block SpMVs, Gram matmuls, and the Rayleigh–Ritz
    `eigh` — runs as one compiled program (parallel/tpu_lobpcg.py);
    callable preconditioners run the host loop on any backend. The two
    paths stabilize the basis differently (dropping vs masked penalty),
    so they agree on eigenpairs, not on iteration counts."""
    check(nev >= 1, "lobpcg: nev must be >= 1")
    m = int(nev)
    from ..parallel.tpu import TPUBackend
    from .gmg import GMGHierarchy

    if isinstance(A.values.backend, TPUBackend) and (
        not callable(minv) or isinstance(minv, GMGHierarchy)
    ):
        # diagonal OR multigrid preconditioners compile to one program
        # (the V-cycle inlines per residual block row); other callables
        # run the host loop below
        from ..parallel.tpu_lobpcg import tpu_lobpcg

        return tpu_lobpcg(
            A, nev=m, X0=X0, minv=minv, tol=tol, maxiter=maxiter,
            largest=largest, seed=seed, verbose=verbose,
        )

    def _rand_block():
        out = []
        for k in range(m):
            def _rand(iset, k=k):
                rng = np.random.default_rng(seed + 7919 * k + int(iset.part))
                vals = np.zeros(iset.num_lids)
                return _write_owned(iset, vals, rng.standard_normal(iset.num_oids))

            out.append(PVector(map_parts(_rand, A.cols.partition), A.cols))
        return out

    X = [v.copy() for v in X0] if X0 is not None else _rand_block()
    check(len(X) == m, "lobpcg: X0 must hold nev vectors")

    def _apply_m(r):
        if minv is None:
            return r.copy()
        if callable(minv):
            return minv(r)
        z = PVector.full(0.0, A.cols, dtype=r.dtype)
        _owned_zip(z, lambda _z, mv, rv: mv * rv, minv, r)
        return z

    def _gram(U, V):
        # ONE distributed reduce per Gram product, not one per entry:
        # each part forms its whole owned-block partial U_p V_pᵀ in a
        # single matmul, and the small |U|×|V| partials fold in part
        # order — the eager analog of the device path's one all_gather
        # per Gram matmul. The old per-entry u.dot(v) issued (3m)²
        # sequential cross-part reductions per iteration.
        ku, kv = len(U), len(V)
        if ku == 0 or kv == 0:
            return np.zeros((ku, kv))
        # each vector rides with its OWN partition (blocks like AS live
        # on A.rows, not A.cols — owned-compatible but not lid-identical)
        args = []
        for w in (*U, *V):
            args.append(w.rows.partition)
            args.append(w.values)

        def _partial(*vals):
            Uo = np.stack(
                [
                    _owned(vals[2 * i], np.asarray(vals[2 * i + 1]))
                    for i in range(ku)
                ]
            )
            Vo = np.stack(
                [
                    _owned(
                        vals[2 * (ku + i)], np.asarray(vals[2 * (ku + i) + 1])
                    )
                    for i in range(kv)
                ]
            )
            return Uo @ Vo.T

        partials = map_parts(_partial, *args)
        from ..parallel.collectives import preduce
        import operator

        return preduce(operator.add, partials, np.zeros((ku, kv)))

    def _combine(blocks, C):
        """rows of C weight the concatenated blocks into new vectors."""
        out = []
        for j in range(C.shape[1]):
            w = PVector.full(0.0, A.cols, dtype=X[0].dtype)
            for c, v in zip(C[:, j], blocks):
                if c != 0.0:
                    cc = float(c)
                    _owned_update(w, lambda wv, vv: wv + cc * vv, v)
            out.append(w)
        return out

    def _orthonormalize(U):
        """Gram-based orthonormalization (replicated small eigh)."""
        G = _gram(U, U)
        w, Q = np.linalg.eigh(G)
        keep = w > w[-1] * 1e-12
        C = Q[:, keep] / np.sqrt(w[keep])
        return _combine(U, C)

    X = _orthonormalize(X)
    P: list = []
    sgn = -1.0 if largest else 1.0
    history = []
    it = 0
    lam = np.zeros(m)
    converged = False
    AX = None
    while it < maxiter:
        if AX is None:
            AX = [A @ x for x in X]
        lam = np.array([float(x.dot(ax)) for x, ax in zip(X, AX)])
        R = []
        for x, ax, l in zip(X, AX, lam):
            r = PVector.full(0.0, A.cols, dtype=x.dtype)
            ll = float(l)
            _owned_zip(r, lambda _r, av, xv: av - ll * xv, ax, x)
            R.append(r)
        rnorms = np.array([float(r.norm()) for r in R])
        history.append(rnorms.copy())
        if verbose:
            print(f"lobpcg it={it} max|r|={rnorms.max():.3e}")
        if np.all(rnorms <= tol * np.maximum(1.0, np.abs(lam))):
            converged = True
            break
        # normalize the search directions: near convergence W (and P)
        # have tiny norms, and unscaled they fall below the whitening
        # drop threshold — the span is scale-invariant, so unit-norm them
        def _unit(vs):
            out = []
            for v in vs:
                n = float(v.norm())
                if n > 0:
                    out.append(v / n)
            return out

        W = _unit([_apply_m(r) for r in R])
        P = _unit(P)
        S = X + W + P
        # Rayleigh–Ritz on span(S): solve the (dense, replicated) pencil
        AS = AX + [A @ v for v in S[m:]]
        G_a = _gram(S, AS)
        G_m = _gram(S, S)
        # drop near-dependent directions for a stable generalized eigh
        w_m, Q_m = np.linalg.eigh(G_m)
        keep = w_m > w_m[-1] * 1e-10
        B = Q_m[:, keep] / np.sqrt(w_m[keep])
        w_r, Q_r = np.linalg.eigh(sgn * (B.T @ G_a @ B))
        C = B @ Q_r[:, :m]  # coefficients of the new X in S
        X_new = _combine(S, C)
        # implicit P: the part of the new X not coming from the old X
        C_p = C.copy()
        C_p[:m, :] = 0.0
        P = _combine(S, C_p)
        X = X_new
        # A-images combine with the SAME coefficients — saves m SpMVs
        # (and their halo rounds) per iteration
        AX = _combine(AS, C)
        it += 1
    if not converged:
        # maxiter exit happens AFTER X was replaced: recompute the
        # Rayleigh quotients so the returned (lam, X) pairs agree
        AX = [A @ x for x in X]
        lam = np.array([float(x.dot(ax)) for x, ax in zip(X, AX)])
    order = np.argsort(sgn * lam)
    lam = lam[order]
    X = [X[int(k)] for k in order]
    return (
        lam,
        X,
        {
            "iterations": it,
            "residual_norms": np.array(history),
            "converged": converged,
        },
    )


def chebyshev_solve(
    A: PSparseMatrix,
    b: PVector,
    lmin: float,
    lmax: float,
    x0: Optional[PVector] = None,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    verbose: bool = False,
) -> Tuple[PVector, dict]:
    """Chebyshev iteration for SPD `A` with spectrum inside [lmin, lmax]
    (``lmax`` e.g. from ``gershgorin_bounds(A)[1]``; ``lmin`` must be a
    positive lower bound on the smallest eigenvalue — Gershgorin's lower
    bound is typically <= 0 for Laplacians, so use a problem-specific
    estimate or ``lmax / condition_estimate``). The TPU-relevant
    property: the iteration has NO inner products, so on the compiled
    path the only per-iteration communication is the SpMV halo exchange;
    one residual all-gather happens per 16-iteration leg. The host path
    is the semantics oracle and checks the residual every iteration.
    """
    check(lmax > lmin > 0.0, "chebyshev_solve needs 0 < lmin < lmax")
    from ..parallel.tpu import TPUBackend, tpu_chebyshev

    if isinstance(b.values.backend, TPUBackend):
        return tpu_chebyshev(
            A, b, lmin, lmax, x0=x0, tol=tol, maxiter=maxiter, verbose=verbose
        )

    x = x0.copy() if x0 is not None else PVector.full(0.0, A.cols, dtype=b.dtype)
    maxiter = maxiter if maxiter is not None else 10 * A.rows.ngids
    floor_warned = warn_tol_below_floor(tol, b.dtype, name="chebyshev")
    theta = (lmax + lmin) / 2.0
    delta = (lmax - lmin) / 2.0
    sigma1 = theta / delta
    rho = 1.0 / sigma1
    r = b.copy()
    q = A @ x
    _owned_update(r, lambda rv, qv: rv - qv, q)
    rs0 = r.dot(r)
    d = PVector.full(0.0, A.cols, dtype=b.dtype)
    _owned_zip(d, lambda _d, rv: rv / theta, r)
    history = [np.sqrt(rs0)]
    it, rs = 0, rs0
    while np.sqrt(rs) > tol * max(1.0, np.sqrt(rs0)) and it < maxiter:
        _owned_update(x, lambda xv, dv: xv + dv, d)
        q = A @ d
        _owned_update(r, lambda rv, qv: rv - qv, q)
        rho_new = 1.0 / (2.0 * sigma1 - rho)
        _owned_zip(
            d,
            lambda dv, rv: rho_new * rho * dv + (2.0 * rho_new / delta) * rv,
            r,
        )
        rho = rho_new
        rs = r.dot(r)
        history.append(np.sqrt(rs))
        it += 1
        if verbose:
            print(f"chebyshev it={it} residual={np.sqrt(rs):.3e}")
    return x, krylov_info(
        it, history, np.sqrt(rs) <= tol * max(1.0, np.sqrt(rs0)),
        tol, b.dtype, floor_warned,
        final_rel=_final_true_rel(
            A, x, b, np.sqrt(rs) / max(1.0, np.sqrt(rs0)), np.sqrt(rs0),
            tol, force=floor_warned,
        ),
    )


def _owned_update(dest: PVector, f, src: PVector):
    """dest.owned = f(dest.owned, src.owned), in place; dest and src may
    live on different (owned-compatible) PRanges. The one-source special
    case of `_owned_zip`."""
    _owned_zip(dest, f, src)


def _owned_assign(dest: PVector, src: PVector):
    _owned_update(dest, lambda _d, s: s, src)


# ---------------------------------------------------------------------------
# gather-to-main direct solve (debug path)
# ---------------------------------------------------------------------------


def gather_psparse(A: PSparseMatrix) -> Optional[CSRMatrix]:
    """Collect the owned-row triplets of every part and compress the global
    matrix on MAIN; other parts get None
    (reference gather(A): src/Interfaces.jl:2664-2704). Ghost rows are
    ignored: run `A.assemble()` first for unassembled matrices."""
    trip = psparse_global_triplets(A)
    gi_all, gj_all, v_all = [], [], []
    for (gi, gj, v), iset in zip(trip.part_values(), A.rows.partition.part_values()):
        owned = iset.lid_to_ohid[iset.gids_to_lids(gi)] >= 0
        gi_all.append(gi[owned])
        gj_all.append(gj[owned])
        v_all.append(v[owned])
    m, n = A.rows.ngids, A.cols.ngids
    return compresscoo(
        np.concatenate(gi_all), np.concatenate(gj_all), np.concatenate(v_all), m, n
    )


def gather_pvector(b: PVector) -> np.ndarray:
    """Owned values of every part placed at their gids (on MAIN)
    (reference gather(b): src/Interfaces.jl:2706-2732)."""
    out = np.zeros(b.rows.ngids, dtype=b.dtype)
    for iset, vals in zip(b.rows.partition.part_values(), b.values.part_values()):
        out[iset.oid_to_gid] = _owned(iset, np.asarray(vals))
    return out


def scatter_pvector_values(c_main: np.ndarray, rows: PRange) -> PVector:
    """Distribute a MAIN-resident global vector back over a PRange
    (reference scatter!: src/Interfaces.jl:2734-2748). Ghost entries are
    filled too (the data is available on main)."""
    vals = map_parts(lambda i: np.asarray(c_main)[i.lid_to_gid], rows.partition)
    return PVector(vals, rows)


class PLU:
    """Centralize-on-main LU factorization, reusable across solves
    (reference PLU/lu/ldiv!: src/Interfaces.jl:2641-2662)."""

    def __init__(self, A: PSparseMatrix):
        from scipy.linalg import lu_factor

        self.cols = A.cols
        self._factors = lu_factor(gather_psparse(A).toarray())

    def refactorize(self, A: PSparseMatrix) -> "PLU":
        from scipy.linalg import lu_factor

        self._factors = lu_factor(gather_psparse(A).toarray())
        return self

    def solve(self, b: PVector) -> PVector:
        from scipy.linalg import lu_solve

        x_main = lu_solve(self._factors, gather_pvector(b))
        return scatter_pvector_values(x_main, self.cols)


def lu(A: PSparseMatrix) -> PLU:
    return PLU(A)


def direct_solve(A: PSparseMatrix, b: PVector) -> PVector:
    """The `\\` analog: gather A and b to MAIN, dense solve, scatter back
    (reference: src/Interfaces.jl:2626-2638). Debug-scale only."""
    x_main = np.linalg.solve(gather_psparse(A).toarray(), gather_pvector(b))
    return scatter_pvector_values(x_main, A.cols)


def _owned_zip(dest: PVector, f, *srcs: PVector):
    """dest.owned = f(dest.owned, *src.owned), in place, across
    owned-compatible PRanges."""
    args = [dest.rows.partition, dest.values]
    for s in srcs:
        args += [s.rows.partition, s.values]

    def kernel(di, dv, *rest):
        owned_srcs = [
            _owned(rest[2 * k], rest[2 * k + 1]) for k in range(len(srcs))
        ]
        _write_owned(di, dv, f(_owned(di, dv), *owned_srcs))

    map_parts(kernel, *args)


def jacobi_preconditioner(A: PSparseMatrix) -> PVector:
    """The inverse diagonal of A as a PVector over ``A.cols`` — the
    classic point-Jacobi preconditioner. Owned entries are 1/diag (zero
    diagonals pass through as 1); ghost entries are zero (the
    preconditioner application is owned-local)."""
    minv = PVector.full(0.0, A.cols, dtype=A.dtype)

    def per_part(iset, M, mv):
        from .. import native

        d = native.csr_diag(M.indptr, M.indices, M.data, iset.num_oids)
        if d is None:
            d = np.zeros(iset.num_oids, dtype=M.data.dtype)
            r = M.row_of_nz()
            # defensive only: both dispatch arms below pass matrices
            # whose rows are all < num_oids (the full CSR is only read
            # when it has no ghost rows; A_oo has owned rows by
            # construction) — the bound guards d against future callers
            hits = np.nonzero(
                (M.indices == r) & (r < iset.num_oids)
            )[0]
            d[r[hits]] = M.data[hits]
        d = np.where(d == 0, 1.0, d)
        _write_owned(iset, mv, 1.0 / d)

    # diagonal entries live at col == row < num_oids, so the FULL local
    # CSR answers directly whenever it has no ghost rows — reading it
    # avoids forcing the owned/ghost block split (a second full copy of
    # the operator in fresh pages at 1e8 DOFs); pre-assembly matrices
    # with ghost rows keep the block path
    no_ghost_rows = all(
        m.shape[0] == i.num_oids
        for m, i in zip(
            A.values.part_values(), A.rows.partition.part_values()
        )
    )
    map_parts(
        per_part,
        A.cols.partition,
        A.values if no_ghost_rows else A.owned_owned_values,
        minv.values,
    )
    return minv


def _spilu_factor(M: CSRMatrix, drop_tol, fill_factor):
    """Threshold-ILU factorization of one local CSR block (None for an
    empty block) — shared by the Schwarz-family preconditioners."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.linalg import spilu

    if M.shape[0] == 0:
        return None
    check(
        M.nnz > 0,
        "spilu: a part's block is structurally zero — the preconditioner "
        "would silently map its residual to zero",
    )
    sp = csr_matrix((M.data, M.indices, M.indptr), shape=M.shape).tocsc()
    kw = {"fill_factor": fill_factor}
    if drop_tol is not None:
        kw["drop_tol"] = drop_tol
    return spilu(sp, **kw)


def block_jacobi_ilu(A: PSparseMatrix, drop_tol=None, fill_factor=10):
    """Additive-Schwarz (non-overlapping block-Jacobi) preconditioner
    with a threshold incomplete-LU (ILUT, scipy ``spilu``) factorization
    of each part's owned-owned block: z = M⁻¹ r applies the ILU solves
    part-locally, with NO communication — the classic domain-
    decomposition preconditioner for unstructured operators where a grid
    hierarchy (gmg) does not apply.

    Returns a callable for ``pcg(A, b, minv=...)``. Each application is
    embarrassingly parallel across parts; effectiveness degrades with
    part count (block-Jacobi's usual trade), which Krylov acceleration
    absorbs. Factorizations happen once, on the host.

    Caveat: an LU-based M⁻¹ is only *approximately* symmetric even for
    SPD blocks, so CG's conjugacy holds approximately — standard
    practice, fine in the well-conditioned regime, but on severely
    ill-conditioned systems expect extra iterations (an exact-symmetry
    alternative is an incomplete Cholesky, which scipy does not ship)."""
    from ..parallel.backends import get_part_ids

    factors = [
        _spilu_factor(M, drop_tol, fill_factor)
        for M in A.owned_owned_values.part_values()
    ]
    parts = get_part_ids(A.values)

    def apply(r: PVector) -> PVector:
        z = PVector.full(0.0, A.cols, dtype=r.dtype)

        def per_part(p, zi, zv, ri_, rv):
            ilu = factors[int(p)]
            if ilu is not None:
                _write_owned(zi, zv, ilu.solve(_owned(ri_, np.asarray(rv))))

        map_parts(
            per_part,
            parts, z.rows.partition, z.values, r.rows.partition, r.values,
        )
        return z

    return apply


def _ic0_factor(M: CSRMatrix, shift: float = 0.0, auto_shift: bool = True):
    """IC(0) of one local SPD CSR block: returns a solver object with a
    ``solve(r)`` applying (L Lᵀ)⁻¹, or None for an empty block.

    IC(0) is breakdown-free only for M-matrices (e.g. the Poisson
    stencil); general SPD blocks (elasticity) can hit a non-positive
    pivot. With ``auto_shift`` (Manteuffel's remedy) the diagonal is
    scaled by (1+α) with escalating α until the factorization exists —
    a weaker but valid symmetric preconditioner. Raises only when even
    α = 1 fails (the block is not SPD at all)."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.linalg import spsolve_triangular

    from .. import native

    n = M.shape[0]
    if n == 0:
        return None
    check(
        M.nnz > 0,
        "ic0: a part's block is structurally zero — the preconditioner "
        "would silently map its residual to zero",
    )
    # IC(0) reads only the lower triangle — on a nonsymmetric block that
    # would SILENTLY factor the wrong operator (observed: the
    # row-replacement-BC elasticity fixture is nonsymmetric and PCG with
    # the symmetrized factor diverges). Refuse instead.
    sp = csr_matrix((M.data, M.indices, M.indptr), shape=M.shape)
    asym = abs(sp - sp.T).max() if M.nnz else 0.0
    if asym > 1e-12 * max(abs(sp).max(), 1.0):
        raise ValueError(
            f"ic0: block is not symmetric (max |A - A'| = {asym:.2e}) — "
            "incomplete Cholesky requires an SPD block; use "
            "block_jacobi_ilu / additive_schwarz(factor='ilu') for "
            "nonsymmetric operators"
        )
    # lower triangle (diagonal last per row; rows are column-sorted)
    r = M.row_of_nz()
    keep = M.indices <= r
    li, lj, lv0 = r[keep], M.indices[keep], M.data[keep].astype(np.float64)
    # a structurally missing diagonal fails identically at every shift —
    # diagnose it up front instead of reporting a misleading pivot error
    L0 = compresscoo(li, lj, lv0, n, n)
    last = L0.indices[np.maximum(L0.indptr[1:], 1) - 1]
    row_has = (L0.indptr[1:] > L0.indptr[:-1]) & (last == np.arange(n))
    if not row_has.all():
        raise ValueError(
            f"ic0: local row {int(np.nonzero(~row_has)[0][0])} has no "
            "stored diagonal entry — IC(0) needs a full diagonal"
        )
    shifts = [shift]
    if auto_shift:
        shifts += [a for a in (1e-3, 1e-2, 1e-1, 1.0) if a > shift]
    lvals = fail = None
    for a in shifts:
        lv = np.where(li == lj, lv0 * (1.0 + a), lv0) if a else lv0
        L = compresscoo(li, lj, lv, n, n)
        lvals, fail = native.ic0(L.indptr, L.indices, L.data, n)
        if lvals is not None:
            break
    if lvals is None:
        raise np.linalg.LinAlgError(
            f"ic0: non-positive pivot at local row {fail} even with the "
            "maximum diagonal shift — the block is not SPD; use "
            "block_jacobi_ilu"
        )
    Lm = csr_matrix((lvals, L.indices, L.indptr), shape=(n, n))
    Lt = Lm.T.tocsr()

    class _IC0:
        def solve(self, rv):
            y = spsolve_triangular(Lm, rv, lower=True)
            return spsolve_triangular(Lt, y, lower=False)

    return _IC0()


def block_jacobi_ic0(A: PSparseMatrix, shift: float = 0.0):
    """Block-Jacobi preconditioner with a zero-fill incomplete CHOLESKY
    factorization of each part's owned-owned block — the exactly
    symmetric companion to `block_jacobi_ilu` for SPD operators (an LU
    keeps CG's conjugacy only approximately; L Lᵀ keeps it exactly).
    scipy ships no incomplete Cholesky, so the factorization is this
    framework's own kernel (native/planning.cpp:pa_ic0_f64, with a NumPy
    fallback). Returns a callable for ``pcg(A, b, minv=...)``."""
    from ..parallel.backends import get_part_ids

    factors = [
        _ic0_factor(M, shift) for M in A.owned_owned_values.part_values()
    ]
    parts = get_part_ids(A.values)

    def apply(r: PVector) -> PVector:
        z = PVector.full(0.0, A.cols, dtype=r.dtype)

        def per_part(p, zi, zv, ri_, rv):
            f = factors[int(p)]
            if f is not None:
                _write_owned(zi, zv, f.solve(_owned(ri_, np.asarray(rv))))

        map_parts(
            per_part,
            parts, z.rows.partition, z.values, r.rows.partition, r.values,
        )
        return z

    return apply


def additive_schwarz(
    A: PSparseMatrix, mode: str = "asm", drop_tol=None, fill_factor=10,
    factor: str = "ilu", shift: float = 0.0,
):
    """Overlapping-Schwarz preconditioner (one layer of overlap): each
    part factors the extended block over its owned rows PLUS the rows of
    its column-ghost layer — obtained by replicating owner rows along
    the ghost graph (`exchange_coo`, the reference's
    async_exchange!(I,J,V,rows) — src/Interfaces.jl:2494-2592). An
    application fills the overlap with ONE halo exchange, solves each
    extended block locally, and combines:

    * ``mode='asm'`` (default): z = Σ_p Rᵀ_p B⁻¹_p R_p r — overlap
      corrections are ASSEMBLED back (ghost→owner add). Symmetric for
      symmetric blocks, the right companion for `pcg`.
    * ``mode='ras'``: each part keeps only the owned slice of its
      correction (restricted AS) — fewer iterations in practice but a
      strongly NONsymmetric operator: use with `gmres` or `bicgstab`
      (both take ``minv``), NOT with CG (conjugacy collapses and PCG
      stalls).

    Returns a callable for ``minv=``. The overlap typically cuts
    iterations vs `block_jacobi_ilu` at the cost of factoring slightly
    larger blocks. ``factor='ic0'`` swaps the block ILUT for the exactly
    symmetric incomplete Cholesky (SPD extended blocks; see
    `block_jacobi_ic0`) — with ``mode='asm'`` that makes the whole
    preconditioner symmetric, the right companion for `pcg`."""
    check(mode in ("asm", "ras"), "additive_schwarz: mode is 'asm' or 'ras'")
    check(factor in ("ilu", "ic0"), "additive_schwarz: factor is 'ilu' or 'ic0'")
    check(
        factor == "ilu" or drop_tol is None,
        "additive_schwarz: drop_tol tunes the ILUT blocks — IC(0) is "
        "zero-fill by definition (use shift= for its Manteuffel knob)",
    )
    check(
        factor == "ic0" or shift == 0.0,
        "additive_schwarz: shift is the IC(0) Manteuffel knob — the ILUT "
        "blocks take drop_tol/fill_factor instead",
    )
    from ..parallel.backends import get_part_ids
    from ..parallel.prange import add_gids
    from ..parallel.psparse import exchange_coo, psparse_owned_triplets

    # extended row range: owned rows + the column-ghost gids (overlap 1)
    ghost_gids = map_parts(
        lambda ci: np.asarray(ci.lid_to_gid)[
            np.asarray(ci.lid_to_ohid) < 0
        ],
        A.cols.partition,
    )
    rows_ext = add_gids(A.rows, ghost_gids)
    trip = psparse_owned_triplets(A)
    I = map_parts(lambda t: t[0], trip)
    J = map_parts(lambda t: t[1], trip)
    V = map_parts(lambda t: t[2], trip)
    I2, J2, V2 = exchange_coo(I, J, V, rows_ext)

    # per part: square local block over the extended row set (couplings
    # leaving the overlap region are dropped — standard RAS truncation)
    factors = []
    for iset, gi, gj, v in zip(
        rows_ext.partition.part_values(),
        I2.part_values(), J2.part_values(), V2.part_values(),
    ):
        nl = iset.num_lids
        li = iset.gids_to_lids(np.asarray(gi, dtype=np.int64))
        lj = iset.gids_to_lids(np.asarray(gj, dtype=np.int64))
        keep = (li >= 0) & (lj >= 0)
        if nl == 0 or not np.any(keep):
            factors.append(None)
            continue
        B = compresscoo(li[keep], lj[keep], np.asarray(v)[keep], nl, nl)
        factors.append(
            _ic0_factor(B, shift)
            if factor == "ic0"
            else _spilu_factor(B, drop_tol, fill_factor)
        )

    parts = get_part_ids(A.values)

    def apply(r: PVector) -> PVector:
        # residual on the extended range, overlap filled by ONE exchange
        re = PVector.full(0.0, rows_ext, dtype=r.dtype)
        _owned_zip(re, lambda _e, rv: rv, r)
        re.exchange()
        ze = PVector.full(0.0, rows_ext, dtype=r.dtype)

        def per_part(p, ei, ev, zev):
            ilu = factors[int(p)]
            if ilu is not None:
                _assign_full(zev, ilu.solve(np.asarray(ev)))

        map_parts(per_part, parts, re.rows.partition, re.values, ze.values)
        if mode == "asm":
            # ghost corrections flow back to their owners and add
            ze.assemble()
        # else RAS: overlap corrections are simply dropped
        z = PVector.full(0.0, A.cols, dtype=r.dtype)
        _owned_zip(z, lambda _z, zev: zev, ze)
        return z

    return apply


def decouple_dirichlet(
    A: PSparseMatrix, b: Optional[PVector] = None
):
    """Symmetrize a Dirichlet-identity system without changing its
    solution. The FDM/FEM driver pattern imposes boundary conditions as
    diagonal-only rows (reference: test/test_fdm.jl:52-81), which leaves
    interior→boundary couplings in place — the full matrix is NOT
    symmetric, which breaks MINRES off the boundary-consistent subspace,
    V-cycle-preconditioned CG, and exact adjoints through
    `make_diff_solve_fn` (its docstring warns about this exact shape).

    This routine performs the classic lifting: every coupling A[i, j]
    into a diagonal-only row j is zeroed (values only — the sparsity
    pattern is preserved, so device lowerings and exchangers stay
    valid), and, when ``b`` is given, the known boundary values
    g_j = b_j / A_jj are folded into the right-hand side:
    b̂_i = b_i − Σ_j A[i, j]·g_j. The returned (Â, b̂) system is
    symmetric whenever the interior block of A is, and has the SAME
    solution as (A, b). Diagonal-only rows with a zero diagonal
    (structurally singular) are left untouched."""
    if b is not None:
        from ..parallel.prange import oids_are_equal

        check(
            oids_are_equal(b.rows, A.rows),
            "decouple_dirichlet: b must live on A's row range",
        )

    # pass 1 over the nonzeros: flag = 1 at owned diagonal-only rows
    # (nonzero diag, no off-diag values) and g = b/diag there; both
    # exchanged so each part sees the values for its ghost columns too
    flag = PVector.full(0.0, A.cols, dtype=A.dtype)
    g = PVector.full(0.0, A.cols, dtype=A.dtype)

    def _classify(ci, M, fv, gv, *b_args):
        r = M.row_of_nz()
        diag = np.zeros(M.shape[0], dtype=M.data.dtype)
        offsum = np.zeros(M.shape[0], dtype=M.data.dtype)
        on = M.indices == r
        np.add.at(diag, r[on], M.data[on])
        np.add.at(offsum, r[~on], np.abs(M.data[~on]))
        no = ci.num_oids
        only = ((offsum == 0) & (diag != 0))[:no]
        _write_owned(ci, fv, only.astype(M.data.dtype))
        if b_args:
            bi, bvals = b_args
            safe = np.where(diag[:no] == 0, 1.0, diag[:no])
            bo = _owned(bi, np.asarray(bvals))
            _write_owned(ci, gv, np.where(only, bo / safe, 0.0))

    if b is not None:
        map_parts(
            _classify, A.cols.partition, A.values, flag.values, g.values,
            b.rows.partition, b.values,
        )
        g.exchange()
    else:
        map_parts(_classify, A.cols.partition, A.values, flag.values, g.values)
    flag.exchange()

    # pass 2: one shared kill mask per part drives both the value strip
    # and the rhs lift
    b_hat = None if b is None else PVector.full(0.0, b.rows, dtype=b.dtype)

    def _strip_and_lift(M, fv, *b_args):
        r = M.row_of_nz()
        kill = (np.asarray(fv)[M.indices] != 0) & (M.indices != r)
        if b_args:
            gv, bi, bvals, bhv = b_args
            corr = np.zeros(M.shape[0], dtype=M.data.dtype)
            np.add.at(
                corr, r[kill], M.data[kill] * np.asarray(gv)[M.indices[kill]]
            )
            _write_owned(
                bi, bhv, _owned(bi, np.asarray(bvals)) - corr[: bi.num_oids]
            )
        data = np.where(kill, 0.0, M.data)
        return CSRMatrix(M.indptr, M.indices, data, M.shape)

    if b is None:
        values = map_parts(_strip_and_lift, A.values, flag.values)
        return PSparseMatrix(values, A.rows, A.cols)
    values = map_parts(
        _strip_and_lift, A.values, flag.values, g.values,
        b.rows.partition, b.values, b_hat.values,
    )
    return PSparseMatrix(values, A.rows, A.cols), b_hat


def pcg(
    A: PSparseMatrix,
    b: Optional[PVector] = None,
    x0: Optional[PVector] = None,
    minv: Optional[PVector] = None,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    verbose: bool = False,
    fused: Optional[bool] = None,
    checkpoint=None,
    _resume_state: Optional[dict] = None,
    B=None,
    X0=None,
    column_errors: str = "raise",
) -> Tuple[PVector, dict]:
    """Preconditioned CG. ``minv`` is either an inverse-diagonal PVector
    over A.cols (defaults to `jacobi_preconditioner(A)`) or a *callable*
    ``minv(r) -> z`` applying any symmetric positive preconditioner — a
    multigrid V-cycle (`GMGHierarchy` is callable), a polynomial smoother,
    etc. The diagonal form dispatches to the single compiled device
    program on the TPU backend; the host loop below runs the identical
    update sequence, so iteration counts and residual histories agree
    across backends. A `GMGHierarchy` preconditioner on the TPU backend
    compiles INTO the CG loop (one program for the whole multigrid-
    preconditioned solve — parallel/tpu_gmg.py; the hierarchy must be
    built on this exact `A`); any other callable runs the host loop on
    any backend (each application is whatever the callable compiles
    to).

    ``fused`` selects the device loop's body exactly as in `cg` (the
    fused PCG body additionally rides its r·z / r·r reductions on one
    shared all_gather) on the diagonal-``minv`` compiled path; a host
    no-op. The GMG-preconditioned device program compiles its own PCG
    body with no fused variant, so an explicit ``fused`` there raises
    rather than silently measuring the same body twice.

    ``B``/``X0`` select the multi-RHS block solve exactly as in `cg`:
    the ONE shared preconditioner applies per column. The diagonal form
    compiles to the block device program (its r·z / r·r reduction pairs
    ride one all_gather as a (K, 2) payload); callable preconditioners
    (including a `GMGHierarchy`) solve the columns in sequence, each
    through its usual solo path."""
    from ..parallel.tpu import TPUBackend, tpu_block_cg, tpu_cg

    if minv is None:
        minv = jacobi_preconditioner(A)
    apply_minv = callable(minv)
    if B is not None:
        B = _check_block_args(
            "pcg", b, x0, B, checkpoint, _resume_state, column_errors
        )
        if (
            isinstance(B[0].values.backend, TPUBackend)
            and not apply_minv
        ):
            return tpu_block_cg(
                A, B, X0=X0, tol=tol, maxiter=maxiter, verbose=verbose,
                minv=minv, fused=fused, column_errors=column_errors,
            )
        # forward `fused` so the solo path's contracts hold per column —
        # in particular a GMG hierarchy with an explicit fused flag must
        # RAISE (its compiled PCG body has no fused variant), not
        # silently run the same body under both A/B labels
        return _host_block_solve(
            lambda bk, x0k: pcg(
                A, bk, x0=x0k, minv=minv, tol=tol, maxiter=maxiter,
                verbose=verbose, fused=fused,
            ),
            B, X0, column_errors=column_errors,
        )
    check(b is not None, "pcg: a right-hand side b (or a block B) is required")
    if isinstance(b.values.backend, TPUBackend):
        if checkpoint is not None or _resume_state is not None:
            raise ValueError(
                "pcg: per-iteration checkpointing is a host-loop feature — "
                "use models.solvers.solve_with_recovery on the compiled path"
            )
        from .gmg import GMGHierarchy

        if isinstance(minv, GMGHierarchy):
            # the V-cycle preconditioner compiles INTO the CG loop: one
            # program for the whole multigrid-preconditioned solve
            from ..parallel.tpu_gmg import tpu_gmg_pcg

            if fused is not None:
                # unconditional (not check()): silently dropping the flag
                # would hand an A/B user two identical runs
                raise ValueError(
                    "pcg: the GMG-preconditioned device program has its "
                    "own compiled PCG body with no fused variant — drop "
                    "the fused argument for GMG preconditioning"
                )
            check(
                minv.levels[0].A is A,
                "pcg: the hierarchy's fine operator must be A itself",
            )
            return tpu_gmg_pcg(
                minv, b, x0=x0, tol=tol, maxiter=maxiter, verbose=verbose
            )
        if not apply_minv:
            return tpu_cg(
                A, b, x0=x0, tol=tol, maxiter=maxiter, verbose=verbose,
                minv=minv, fused=fused,
            )

    from .. import telemetry

    maxiter = maxiter if maxiter is not None else 4 * A.rows.ngids
    with telemetry.solve_scope(
        "pcg", backend="host", tol=float(tol), maxiter=int(maxiter),
        resumed=_resume_state is not None,
        preconditioner="callable" if apply_minv else "diagonal",
    ) as rec:
        x, info = _pcg_host_loop(
            A, b, x0, minv, apply_minv, tol, maxiter, verbose,
            checkpoint, _resume_state,
        )
        telemetry.observe_solve(A, rec, info=info, dtype=b.dtype,
                                minv=minv)
        return x, rec.finish(info)


def _pcg_host_loop(
    A, b, x0, minv, apply_minv, tol, maxiter, verbose, checkpoint,
    _resume_state,
):
    """The host PCG recurrence (see `_cg_host_loop`)."""
    from ..parallel.health import (
        SilentCorruptionError,
        SolverBreakdownError,
        StagnationDetector,
        check_finite_scalar,
        health_enabled,
        stagnation_raises,
    )

    floor_warned = warn_tol_below_floor(tol, b.dtype, name="pcg")

    z = PVector.full(0.0, A.cols, dtype=b.dtype)

    def _apply_precond():
        if apply_minv:
            _owned_assign(z, minv(r))
        else:
            _owned_zip(z, lambda _z, mv, rv: mv * rv, minv, r)

    if _resume_state is not None:
        x, r, p = _resume_state["x"], _resume_state["r"], _resume_state["p"]
        meta = _resume_state["meta"]
        rs, rz, rs0 = meta["rs"], meta["rz"], meta["rs0"]
        it = int(meta["it"])
        history = [np.float64(h) for h in meta["history"]]
    else:
        x = x0.copy() if x0 is not None else PVector.full(0.0, A.cols, dtype=b.dtype)
        r = b.copy()
        q = A @ x
        _owned_update(r, lambda rv, qv: rv - qv, q)
        _apply_precond()
        p = PVector.full(0.0, A.cols, dtype=b.dtype)
        _owned_assign(p, z)
        rs = r.dot(r)
        rz = r.dot(z)
        rs0 = rs
        history = [np.sqrt(rs)]
        it = 0
    health = health_enabled()
    if health and _resume_state is None:
        # see cg: a poisoned start must raise, not silently skip the loop
        check_finite_scalar(rs, "pcg", it=0, vectors=(("r", r), ("x", x)))
    # host α/β recording (see _cg_host_loop) — for PCG the reconstructed
    # tridiagonal estimates the spectrum of M⁻¹A, which is the κ that
    # governs PCG convergence (keyed by minv class in the store)
    it0 = it
    ab_alpha: list = []
    ab_beta: list = []
    stag = StagnationDetector("pcg") if health and stagnation_raises() else None
    sdc = _SDCGuard("pcg", A, b, rs0, health)
    sdc.push({"x": x, "r": r, "p": p}, {"rs": rs, "rz": rz, "it": it}, history)
    while np.sqrt(rs) > tol * max(1.0, np.sqrt(rs0)) and it < maxiter:
        try:
            q = A @ p
            pq = p.dot(q)
            if pq == 0.0:
                raise SolverBreakdownError(
                    "pcg: breakdown, p'Ap == 0",
                    diagnostics={"iteration": it, "rs": float(rs)},
                )
            alpha = rz / pq
            _owned_update(x, lambda xv, pv: xv + alpha * pv, p)
            _owned_update(r, lambda rv, qv: rv - alpha * qv, q)
            _apply_precond()
            rz_new = r.dot(z)
            rs = r.dot(r)
            if health:
                check_finite_scalar(
                    rs, "pcg", it=it + 1,
                    vectors=(("r", r), ("z", z), ("x", x)),
                )
            beta = rz_new / rz
            _owned_update(p, lambda pv, zv: zv + beta * pv, z)
            rz = rz_new
            history.append(np.sqrt(rs))
            it += 1
            ab_alpha.append(float(alpha))
            ab_beta.append(float(beta))
            sdc.audit(
                x, r, it, {"rs": rs, "rz": rz, "it": it}, {"p": p}, history
            )
        except SilentCorruptionError as e:
            # same in-memory rollback ladder as cg (see _SDCGuard)
            vecs, meta_r, history = sdc.rollback(e, it)
            x, r, p = vecs["x"], vecs["r"], vecs["p"]
            rs, rz, it = meta_r["rs"], meta_r["rz"], meta_r["it"]
            del ab_alpha[max(0, it - it0):]
            del ab_beta[max(0, it - it0):]
            continue
        if stag is not None:
            stag.update(float(np.sqrt(rs)), it)
        if checkpoint is not None and checkpoint.due(it):
            checkpoint.save_state(
                {"x": x, "r": r, "p": p},
                {
                    "method": "pcg", "it": it, "rs": rs, "rz": rz,
                    "rs0": rs0, "tol": tol, "maxiter": maxiter,
                    "history": history,
                },
            )
        if verbose:
            print(f"pcg it={it} residual={np.sqrt(rs):.3e}")
    if checkpoint is not None:
        checkpoint.wait()
    _attach_host_ab(ab_alpha, ab_beta, it0)
    return x, krylov_info(
        it, history, np.sqrt(rs) <= tol * max(1.0, np.sqrt(rs0)),
        tol, b.dtype, floor_warned,
        final_rel=_final_true_rel(
            A, x, b, np.sqrt(rs) / max(1.0, np.sqrt(rs0)), np.sqrt(rs0),
            tol, force=floor_warned,
        ),
        **sdc.info_extra(),
    )


def gmres(
    A: PSparseMatrix,
    b: PVector,
    x0: Optional[PVector] = None,
    restart: int = 30,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    minv: Optional[PVector] = None,
    verbose: bool = False,
) -> Tuple[PVector, dict]:
    """Restarted GMRES(m) for general (nonsymmetric, possibly indefinite)
    operators — the workhorse the reference borrows from
    IterativeSolvers.jl (src/Interfaces.jl:2752-2757 makes `gmres!` run
    distributed on a PSparseMatrix). Arnoldi with modified Gram-Schmidt
    on the host; the m+1 basis vectors live on ``A.cols`` so every SpMV
    halo-updates in place. With ``minv`` (an inverse-diagonal PVector over
    ``A.cols``) the iteration is left-preconditioned: it solves
    ``M^{-1} A x = M^{-1} b`` and the reported residuals are in the
    preconditioned norm. Dispatches to the single compiled shard_map
    program on the TPU backend (classical Gram-Schmidt with
    reorthogonalization there — two MXU matmuls instead of a sequential
    dot chain; host and device agree to rounding, not bit-exactly).
    ``minv`` may also be a *callable* ``minv(r) -> z`` (e.g. a
    `GMGHierarchy` or `block_jacobi_ilu`); callable preconditioners run
    the host loop on any backend."""
    from ..parallel.tpu import TPUBackend, tpu_gmres

    check(restart >= 1, "gmres: restart dimension must be >= 1")
    apply_minv = callable(minv)
    if isinstance(b.values.backend, TPUBackend) and not apply_minv:
        return tpu_gmres(
            A, b, x0=x0, restart=restart, tol=tol, maxiter=maxiter,
            minv=minv, verbose=verbose,
        )

    x = x0.copy() if x0 is not None else PVector.full(0.0, A.cols, dtype=b.dtype)
    maxiter = maxiter if maxiter is not None else 4 * A.rows.ngids
    floor_warned = warn_tol_below_floor(tol, b.dtype, name="gmres")
    m = restart

    def precond(v):
        """owned-region M^{-1} v, in place (identity when minv is None)."""
        if minv is None:
            return v
        if apply_minv:
            _owned_assign(v, minv(v))
        else:
            _owned_update(v, lambda vv, mv: mv * vv, minv)
        return v

    def residual_vec():
        r = PVector.full(0.0, A.cols, dtype=b.dtype)
        q = A @ x
        _owned_zip(r, lambda _r, bv, qv: bv - qv, b, q)
        return precond(r)

    from ..parallel.health import check_finite_scalar, health_enabled

    health = health_enabled()
    r = residual_vec()
    beta = r.norm()
    if health:
        # see cg: a poisoned b/x0 must raise, not silently "converge"
        check_finite_scalar(beta, "gmres", it=0, vectors=(("r", r),))
    rs0 = beta
    history = [beta]
    it = 0
    converged = beta <= tol * max(1.0, rs0)
    while not converged and it < maxiter:
        # --- one restart cycle: Arnoldi + incremental Givens LSQ ---
        V = [r / beta if beta > 0 else r.copy()]
        H = np.zeros((m + 1, m), dtype=np.float64)
        cs = np.zeros(m)
        sn = np.zeros(m)
        g = np.zeros(m + 1)
        g[0] = beta
        j_used = 0
        for j in range(m):
            if it >= maxiter:
                break
            w = precond(A @ V[j])
            for i in range(j + 1):  # modified Gram-Schmidt, fixed order
                hij = w.dot(V[i])
                H[i, j] = hij
                _owned_update(w, lambda wv, vv: wv - hij * vv, V[i])
            hj1 = w.norm()
            if health:
                # free: the norm was reduced anyway; a NaN anywhere in
                # the Arnoldi step (corrupted halo, overflow) poisons it
                check_finite_scalar(hj1, "gmres", it=it + 1, vectors=(("w", w),))
            H[j + 1, j] = hj1
            # apply the accumulated rotations to the new column
            for i in range(j):
                t = cs[i] * H[i, j] + sn[i] * H[i + 1, j]
                H[i + 1, j] = -sn[i] * H[i, j] + cs[i] * H[i + 1, j]
                H[i, j] = t
            # new rotation zeroing H[j+1, j]
            rho = np.hypot(H[j, j], H[j + 1, j])
            if rho == 0.0:
                cs[j], sn[j] = 1.0, 0.0
            else:
                cs[j], sn[j] = H[j, j] / rho, H[j + 1, j] / rho
            H[j, j] = rho
            H[j + 1, j] = 0.0
            g[j + 1] = -sn[j] * g[j]
            g[j] = cs[j] * g[j]
            it += 1
            j_used = j + 1
            res = abs(g[j + 1])
            history.append(res)
            if verbose:
                print(f"gmres it={it} residual={res:.3e}")
            if res <= tol * max(1.0, rs0) or hj1 == 0.0:
                # the Givens estimate drifts from the true residual under
                # roundoff — convergence is only declared from the honest
                # recomputation after the x update (as the device path does)
                break
            # the next basis vector lives on A.cols (w came out of the
            # SpMV on A.rows) so the following SpMV can halo-update it
            vn = PVector.full(0.0, A.cols, dtype=b.dtype)
            _owned_zip(vn, lambda _v, wv: wv / hj1, w)
            V.append(vn)
        # --- solve the j_used x j_used triangular system, update x ---
        if j_used:
            y = np.zeros(j_used)
            for i in range(j_used - 1, -1, -1):
                y[i] = (g[i] - H[i, i + 1 : j_used] @ y[i + 1 : j_used]) / H[i, i]
            for i in range(j_used):
                yi = y[i]
                _owned_update(x, lambda xv, vv: xv + yi * vv, V[i])
        r = residual_vec()
        beta = r.norm()
        converged = beta <= tol * max(1.0, rs0)
    return x, krylov_info(
        it, history, converged, tol, b.dtype, floor_warned,
        final_rel=beta / max(1.0, rs0),
    )


def fgmres(
    A: PSparseMatrix,
    b: PVector,
    x0: Optional[PVector] = None,
    restart: int = 30,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    minv=None,
    verbose: bool = False,
) -> Tuple[PVector, dict]:
    """FLEXIBLE restarted GMRES (Saad '93): right-preconditioned Arnoldi
    that stores the preconditioned basis Z alongside V, so ``minv`` may
    change from one application to the next — the outer Krylov method
    for *inner iterative* preconditioners (a coarse `cg` run, a V-cycle
    with its own tolerance, a Schwarz sweep whose blocks adapt), which
    plain left-preconditioned `gmres` cannot tolerate. Costs one extra
    stored basis block (Z) per restart cycle over `gmres`.

    ``minv`` is a callable ``minv(r) -> z`` (possibly stateful /
    iteration-varying), an inverse-diagonal PVector over ``A.cols``
    (e.g. `jacobi_preconditioner`), or None (then this is
    right-preconditioned GMRES with M = I and its residual history is in
    the TRUE residual norm — unlike `gmres`, whose history with minv is
    in the preconditioned norm)."""
    check(restart >= 1, "fgmres: restart dimension must be >= 1")
    apply_minv = callable(minv)

    x = x0.copy() if x0 is not None else PVector.full(0.0, A.cols, dtype=b.dtype)
    maxiter = maxiter if maxiter is not None else 4 * A.rows.ngids
    floor_warned = warn_tol_below_floor(tol, b.dtype, name="fgmres")
    m = restart

    def precond(v):
        """z = M^{-1} v as a FRESH vector on A.cols (v is kept — it stays
        in the V basis)."""
        if minv is None:
            z = PVector.full(0.0, A.cols, dtype=b.dtype)
            _owned_assign(z, v)
            return z
        if apply_minv:
            z = minv(v)
            zz = PVector.full(0.0, A.cols, dtype=b.dtype)
            _owned_assign(zz, z)
            return zz
        z = PVector.full(0.0, A.cols, dtype=b.dtype)
        _owned_zip(z, lambda _z, vv, mv: mv * vv, v, minv)
        return z

    def residual_vec():
        # TRUE residual: right preconditioning never touches the norm
        r = PVector.full(0.0, A.cols, dtype=b.dtype)
        q = A @ x
        _owned_zip(r, lambda _r, bv, qv: bv - qv, b, q)
        return r

    r = residual_vec()
    beta = r.norm()
    rs0 = beta
    history = [beta]
    it = 0
    converged = beta <= tol * max(1.0, rs0)
    while not converged and it < maxiter:
        V = [r / beta if beta > 0 else r.copy()]
        Z = []
        H = np.zeros((m + 1, m), dtype=np.float64)
        cs = np.zeros(m)
        sn = np.zeros(m)
        g = np.zeros(m + 1)
        g[0] = beta
        j_used = 0
        for j in range(m):
            if it >= maxiter:
                break
            Z.append(precond(V[j]))
            w = A @ Z[j]
            for i in range(j + 1):  # modified Gram-Schmidt, fixed order
                hij = w.dot(V[i])
                H[i, j] = hij
                _owned_update(w, lambda wv, vv: wv - hij * vv, V[i])
            hj1 = w.norm()
            H[j + 1, j] = hj1
            for i in range(j):
                t = cs[i] * H[i, j] + sn[i] * H[i + 1, j]
                H[i + 1, j] = -sn[i] * H[i, j] + cs[i] * H[i + 1, j]
                H[i, j] = t
            rho = np.hypot(H[j, j], H[j + 1, j])
            if rho == 0.0:
                cs[j], sn[j] = 1.0, 0.0
            else:
                cs[j], sn[j] = H[j, j] / rho, H[j + 1, j] / rho
            H[j, j] = rho
            H[j + 1, j] = 0.0
            g[j + 1] = -sn[j] * g[j]
            g[j] = cs[j] * g[j]
            it += 1
            j_used = j + 1
            res = abs(g[j + 1])
            history.append(res)
            if verbose:
                print(f"fgmres it={it} residual={res:.3e}")
            if res <= tol * max(1.0, rs0) or hj1 == 0.0:
                break
            vn = PVector.full(0.0, A.cols, dtype=b.dtype)
            _owned_zip(vn, lambda _v, wv: wv / hj1, w)
            V.append(vn)
        if j_used:
            y = np.zeros(j_used)
            for i in range(j_used - 1, -1, -1):
                y[i] = (g[i] - H[i, i + 1 : j_used] @ y[i + 1 : j_used]) / H[i, i]
            for i in range(j_used):
                yi = y[i]
                # the update rides the PRECONDITIONED basis Z — the one
                # line that makes the method flexible
                _owned_update(x, lambda xv, zv: xv + yi * zv, Z[i])
        r = residual_vec()
        beta = r.norm()
        converged = beta <= tol * max(1.0, rs0)
    return x, krylov_info(
        it, history, converged, tol, b.dtype, floor_warned,
        final_rel=beta / max(1.0, rs0),
    )


def minres(
    A: PSparseMatrix,
    b: PVector,
    x0: Optional[PVector] = None,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    verbose: bool = False,
) -> Tuple[PVector, dict]:
    """MINRES (Paige–Saunders) for symmetric — possibly *indefinite* —
    operators: the gap between CG (needs definiteness) and GMRES (needs
    O(m) stored vectors). Three-term Lanczos recurrence + one Givens
    rotation per step; constant memory. Another member of the
    IterativeSolvers.jl breadth the reference inherits
    (src/Interfaces.jl:2752-2757). Dispatches to the single compiled
    shard_map program on the TPU backend; the host loop below runs the
    identical update sequence."""
    from ..parallel.tpu import TPUBackend, tpu_minres

    if isinstance(b.values.backend, TPUBackend):
        return tpu_minres(A, b, x0=x0, tol=tol, maxiter=maxiter, verbose=verbose)

    x = x0.copy() if x0 is not None else PVector.full(0.0, A.cols, dtype=b.dtype)
    maxiter = maxiter if maxiter is not None else 4 * A.rows.ngids
    floor_warned = warn_tol_below_floor(tol, b.dtype, name="minres")

    r = PVector.full(0.0, A.cols, dtype=b.dtype)
    q0 = A @ x
    _owned_zip(r, lambda _r, bv, qv: bv - qv, b, q0)
    beta = r.norm()
    rs0 = beta
    history = [beta]
    if beta == 0.0:
        return x, krylov_info(
            0, history, True, tol, b.dtype, floor_warned, final_rel=0.0
        )

    v = r / beta  # Lanczos vector v_1
    v_old = PVector.full(0.0, A.cols, dtype=b.dtype)
    w = PVector.full(0.0, A.cols, dtype=b.dtype)
    w_old = PVector.full(0.0, A.cols, dtype=b.dtype)
    # Givens state: rotations G_{k-1}, G_k applied to the tridiagonal
    c_old, s_old = 1.0, 0.0
    c, s = 1.0, 0.0
    eta = beta
    # beta_k is the tridiagonal sub/superdiagonal entry of the CURRENT
    # column — zero at k=1 (the initial norm beta is not a matrix entry)
    beta_k = 0.0
    it = 0
    res = beta
    while res > tol * max(1.0, rs0) and it < maxiter:
        # Lanczos: alpha = v'Av, next = Av - alpha v - beta v_old
        av = A @ v
        alpha = v.dot(av)
        _owned_zip(av, lambda qv, vv, ov: qv - alpha * vv - beta_k * ov, v, v_old)
        beta_new = av.norm()
        # two old rotations applied to the new tridiagonal column
        delta = c * alpha - c_old * s * beta_k
        gamma2 = s * alpha + c_old * c * beta_k
        gamma3 = s_old * beta_k
        # new rotation
        rho = np.hypot(delta, beta_new)
        if rho == 0.0:
            # hard breakdown: no rotation can advance this step. Exit
            # with converged=False — the same no-op contract as the
            # compiled path (tpu.py make_minres_fn), so host and device
            # behave identically (a check() here would also divide by
            # zero under PA_TPU_CHECKS=0 and NaN-poison the iterate).
            break
        c_old, s_old = c, s
        c, s = delta / rho, beta_new / rho
        # update the solution direction: w_new = (v - γ2 w - γ3 w_old)/ρ.
        # Rotate buffers first so the 2-ago direction's storage is the one
        # overwritten (its stale content is the zip dest's own first arg)
        g2, g3, rr = gamma2, gamma3, rho
        w, w_old = w_old, w
        _owned_zip(
            w,
            lambda w2ago, vv, wprev: (vv - g2 * wprev - g3 * w2ago) / rr,
            v, w_old,
        )
        step = c * eta
        _owned_update(x, lambda xv, wv: xv + step * wv, w)
        eta = -s * eta
        # advance Lanczos buffers; the next v lives on A.cols (av came out
        # of the SpMV on A.rows) so the following SpMV can halo-update it
        vn = PVector.full(0.0, A.cols, dtype=b.dtype)
        s_beta = beta_new if beta_new > 0 else 1.0
        _owned_zip(vn, lambda _v, qv: qv / s_beta, av)
        v_old, v = v, vn
        beta_k = beta_new
        res = abs(eta)
        history.append(res)
        it += 1
        if verbose:
            print(f"minres it={it} residual={res:.3e}")
        if beta_new == 0.0:  # invariant subspace: exact solve reached
            break
    return x, krylov_info(
        it, history, res <= tol * max(1.0, rs0), tol, b.dtype, floor_warned,
        final_rel=_final_true_rel(
            A, x, b, res / max(1.0, rs0), rs0, tol, force=floor_warned
        ),
    )


def bicgstab(
    A: PSparseMatrix,
    b: PVector,
    x0: Optional[PVector] = None,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    minv=None,
    verbose: bool = False,
) -> Tuple[PVector, dict]:
    """BiCGStab for general (nonsymmetric) operators — the companion
    Krylov method the reference gets for free from IterativeSolvers.jl
    (src/Interfaces.jl:2752-2757 makes any of its solvers run
    distributed). Two SpMVs per iteration. Breakdown exits with
    ``converged=False``. Compiled to one program on the TPU backend.

    ``minv`` enables RIGHT preconditioning (solve A·M⁻¹ y = b, x = M⁻¹y —
    residuals stay the TRUE residuals, unlike left preconditioning):
    either an inverse-diagonal PVector over A.cols, or any callable
    ``minv(v) -> z`` (`additive_schwarz(mode='ras')` is the natural
    companion for nonsymmetric systems). The diagonal form compiles into
    the device program; callables run the host loop on any backend."""
    from ..parallel.tpu import TPUBackend, tpu_bicgstab

    apply_minv = callable(minv)
    if isinstance(b.values.backend, TPUBackend) and not apply_minv:
        return tpu_bicgstab(
            A, b, x0=x0, tol=tol, maxiter=maxiter, minv=minv, verbose=verbose
        )

    def precond(v):
        """K⁻¹ v as a fresh vector on A.cols; the identity returns v
        itself (aliasing is safe — the unpreconditioned loop used the
        direction vectors directly)."""
        if minv is None:
            return v
        z = PVector.full(0.0, A.cols, dtype=b.dtype)
        if apply_minv:
            _owned_assign(z, minv(v))
        else:
            _owned_zip(z, lambda _z, mv, vv: mv * vv, minv, v)
        return z

    x = x0.copy() if x0 is not None else PVector.full(0.0, A.cols, dtype=b.dtype)
    maxiter = maxiter if maxiter is not None else 4 * A.rows.ngids
    floor_warned = warn_tol_below_floor(tol, b.dtype, name="bicgstab")

    r = b.copy()
    q = A @ x
    _owned_update(r, lambda rv, qv: rv - qv, q)
    rhat = PVector.full(0.0, A.cols, dtype=b.dtype)
    _owned_assign(rhat, r)
    rcol = PVector.full(0.0, A.cols, dtype=b.dtype)
    _owned_assign(rcol, r)
    r = rcol  # residual kept on A.cols so every vector shares one range
    v = PVector.full(0.0, A.cols, dtype=b.dtype)
    p = PVector.full(0.0, A.cols, dtype=b.dtype)
    s = PVector.full(0.0, A.cols, dtype=b.dtype)
    rho = alpha = omega = 1.0
    rs = r.dot(r)
    rs0 = rs
    history = [np.sqrt(rs)]
    it = 0
    ok = True
    while ok and np.sqrt(rs) > tol * max(1.0, np.sqrt(rs0)) and it < maxiter:
        rho_new = rhat.dot(r)
        if rho_new == 0.0 or omega == 0.0:
            ok = False
            break
        beta = (rho_new / rho) * (alpha / omega)
        ww = omega
        _owned_zip(p, lambda pv, rv, vv: rv + beta * (pv - ww * vv), r, v)
        phat = precond(p)  # right preconditioning: v = A K^-1 p
        v = A @ phat
        rv_ = rhat.dot(v)
        if rv_ == 0.0:
            ok = False
            break
        alpha = rho_new / rv_
        _owned_zip(s, lambda _s, rv, vv: rv - alpha * vv, r, v)
        shat = precond(s)
        t = A @ shat
        tt = t.dot(t)
        omega = 0.0 if tt == 0.0 else t.dot(s) / tt
        aa, oo_ = alpha, omega
        # the solution update uses the PRECONDITIONED directions
        _owned_zip(x, lambda xv, pv, sv: xv + aa * pv + oo_ * sv, phat, shat)
        _owned_zip(r, lambda _r, sv, tv: sv - oo_ * tv, s, t)
        rho = rho_new
        rs = r.dot(r)
        history.append(np.sqrt(rs))
        it += 1
        if verbose:
            print(f"bicgstab it={it} residual={np.sqrt(rs):.3e}")
    return x, krylov_info(
        it, history, np.sqrt(rs) <= tol * max(1.0, np.sqrt(rs0)),
        tol, b.dtype, floor_warned,
        final_rel=_final_true_rel(
            A, x, b, np.sqrt(rs) / max(1.0, np.sqrt(rs0)), np.sqrt(rs0),
            tol, force=floor_warned,
        ),
    )


# ---------------------------------------------------------------------------
# checkpoint-based recovery (the restart half of the resilience layer;
# detection lives in parallel/health.py, injection in parallel/faults.py)
# ---------------------------------------------------------------------------


def _solver_state_ranges(A: PSparseMatrix, b: PVector) -> dict:
    """The target PRanges of a cg/pcg full-state checkpoint: x and p ride
    A.cols (the ghosted column range every SpMV halo-updates), r rides
    b's row range."""
    return {"x": A.cols, "r": b.rows, "p": A.cols}


def resume_solve(
    directory: str,
    A: PSparseMatrix,
    b: PVector,
    method: Optional[str] = None,
    minv=None,
    tol: Optional[float] = None,
    maxiter: Optional[int] = None,
    verbose: bool = False,
    checkpoint=None,
) -> Tuple[PVector, dict]:
    """Continue a checkpointed Krylov run from its last saved state.

    ``directory`` holds a full-state checkpoint written by a
    ``SolverCheckpointer`` (the solvers' ``checkpoint=`` hook). The
    state restores onto WHATEVER partition ``A``/``b`` live on —
    including a different part count or backend than the run that wrote
    it (the checkpoint format is partition-independent). On the same
    host partition the recurrence continues exactly: the resumed run's
    final iterate is bit-identical to an uninterrupted one. Resuming on
    the TPU backend (whose compiled loop cannot ingest mid-recurrence
    state) restarts Krylov from the checkpointed iterate — same answer
    to solver tolerance, not bitwise.

    ``method``, ``tol``, and ``maxiter`` default to whatever the
    checkpoint recorded, so a bare ``resume_solve(dir, A, b)`` continues
    the run the original caller configured; pass ``checkpoint=``
    (another `SolverCheckpointer`, typically on the same directory) to
    keep checkpointing the resumed run.
    """
    from ..parallel.checkpoint import load_solver_state
    from ..parallel.tpu import TPUBackend

    state = load_solver_state(directory, _solver_state_ranges(A, b))
    if state is None:
        raise ValueError(
            f"resume_solve: {directory!r} holds no complete solver "
            "checkpoint (no manifest.json)"
        )
    meta = state["meta"]
    method = method or meta.get("method", "cg")
    check(method in ("cg", "pcg"), "resume_solve: method is 'cg' or 'pcg'")
    tol = tol if tol is not None else float(meta.get("tol", 1e-8))
    if maxiter is None and meta.get("maxiter") is not None:
        maxiter = int(meta["maxiter"])
    kw = dict(tol=tol, maxiter=maxiter, verbose=verbose)
    # exact-recurrence resume needs the full (x, r, p)+scalars state AND
    # a method match — a cg checkpoint has no rz for pcg (and vice versa
    # the recurrences differ), so a method switch restarts from the
    # iterate instead of crashing on the missing scalar
    full_state = (
        all(k in state for k in ("x", "r", "p"))
        and "rs" in meta
        and meta.get("method") == method
    )
    on_device = isinstance(b.values.backend, TPUBackend)
    if on_device or not full_state:
        if on_device and checkpoint is not None:
            raise ValueError(
                "resume_solve: per-iteration checkpointing is a host-loop "
                "feature — on the device backend use "
                "models.solvers.solve_with_recovery to keep checkpointing"
            )
        # device loop (cannot ingest mid-recurrence state), an
        # iterate-only checkpoint (written by the chunked device path),
        # or a method switch: restart Krylov from the checkpointed
        # iterate; `checkpoint` keeps checkpointing the restarted run
        if method == "pcg":
            x, info = pcg(
                A, b, x0=state["x"], minv=minv,
                checkpoint=None if on_device else checkpoint, **kw,
            )
        else:
            x, info = cg(
                A, b, x0=state["x"],
                checkpoint=None if on_device else checkpoint, **kw,
            )
    elif method == "pcg":
        x, info = pcg(
            A, b, minv=minv, checkpoint=checkpoint, _resume_state=state, **kw
        )
    else:
        x, info = cg(A, b, checkpoint=checkpoint, _resume_state=state, **kw)
    info["resumed_from_iteration"] = int(meta["it"])
    return x, info


def _new_recovery_ledger() -> dict:
    """The cumulative `info["recovery"]` schema shared by the host and
    chunked-device recovery drivers (ONE definition, so the two paths
    cannot drift)."""
    return {
        "attempts": 0,
        "detections": 0,
        "rollbacks": 0,
        "checkpoint_restarts": 0,
        "restart_sources": [],
    }


def _ledger_fold_sdc(ledger: dict, counters) -> None:
    """Fold one attempt's in-memory-tier counters (an `info["sdc"]`
    dict, or the same carried on an escalated error's diagnostics) into
    the cumulative ledger."""
    if counters:
        ledger["detections"] += int(counters.get("detections", 0))
        ledger["rollbacks"] += int(counters.get("rollbacks", 0))


def solve_with_recovery(
    A: PSparseMatrix,
    b: PVector,
    method: str = "cg",
    checkpoint_dir: Optional[str] = None,
    every: int = 25,
    max_restarts: int = 2,
    minv=None,
    x0: Optional[PVector] = None,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    verbose: bool = False,
) -> Tuple[PVector, dict]:
    """Run a Krylov solve under the full resilience layer: periodic
    checkpoints every ``every`` iterations plus automatic
    restart-from-last-checkpoint when any `SolverHealthError` fires —
    a NaN-poisoned halo exchange caught by the health guards, an
    exchange timeout from a dropped part, a lost controller, a Krylov
    breakdown — or a `SilentCorruptionError` escalated by the in-memory
    rollback tier (the SDC defense ladder's disk tier). Up to
    ``max_restarts`` restarts; the final info dict carries
    ``info["restarts"]`` (and the per-failure record under
    ``info["failures"]``) plus a CUMULATIVE ``info["recovery"]`` ledger:
    ``attempts`` (solver invocations, including the successful one),
    ``rollbacks``/``detections`` consumed by the in-memory tier across
    all attempts, and ``restart_sources`` recording, per restart, the
    failure type and the state restarted from (exact-recurrence
    checkpoint, checkpointed iterate, or scratch — with the checkpoint
    iteration used), so callers and tests can assert the recovery path
    taken instead of parsing logs.

    Host backends checkpoint the FULL recurrence state in-loop, so a
    restart replays the exact trajectory (the fault-free and
    faulted-then-recovered runs agree bitwise on the same partition).
    On the TPU backend the whole solve is one compiled program that
    cannot stop mid-loop, so the solve runs in ``every``-iteration
    chunks with the iterate checkpointed between chunks; a restart
    re-enters Krylov from the checkpointed iterate (same answer to
    solver tolerance, not bitwise — conjugacy restarts at the chunk
    boundary).

    Without ``checkpoint_dir`` nothing is written and a restart begins
    from ``x0`` — detection and bounded retry, no persistence.
    """
    import sys

    from ..parallel.checkpoint import SolverCheckpointer, load_solver_state
    from ..parallel.health import SolverHealthError
    from ..parallel.tpu import TPUBackend

    from .. import telemetry

    check(
        method in ("cg", "pcg"), "solve_with_recovery: method is 'cg' or 'pcg'"
    )
    ckpt = (
        SolverCheckpointer(checkpoint_dir, every=every)
        if checkpoint_dir is not None
        else None
    )
    with telemetry.solve_scope(
        "solve_with_recovery", method=method, tol=float(tol),
        max_restarts=int(max_restarts),
        checkpointing=checkpoint_dir is not None,
    ) as rec:
        if isinstance(b.values.backend, TPUBackend):
            x, info = _solve_with_recovery_chunked(
                A, b, method, ckpt, every, max_restarts, minv, x0, tol,
                maxiter, verbose,
            )
        else:
            x, info = _solve_with_recovery_host(
                A, b, method, ckpt, max_restarts, minv, x0, tol,
                maxiter, verbose,
            )
        # grow-back: a clean full-capacity solve after an elastic
        # shrink (this one, if it did not itself run degraded) emits
        # elastic_restore and clears the degraded marker
        from ..parallel import elastic

        elastic.note_recovered(int(A.rows.partition.num_parts), info)
        return x, rec.finish(info)


def _solve_with_recovery_host(
    A, b, method, ckpt, max_restarts, minv, x0, tol, maxiter, verbose
):
    """The host-backend recovery loop (exact-recurrence checkpoint
    restarts) — see `solve_with_recovery` for the contract."""
    import sys

    from .. import telemetry
    from ..parallel import elastic
    from ..parallel.checkpoint import load_solver_state
    from ..parallel.health import PartLossError, SolverHealthError

    restarts = 0
    failures = []
    state = None
    ledger = _new_recovery_ledger()

    def _fold_sdc(counters):
        _ledger_fold_sdc(ledger, counters)

    while True:
        try:
            ledger["attempts"] += 1
            kwargs = dict(
                tol=tol, maxiter=maxiter, verbose=verbose,
                checkpoint=ckpt, _resume_state=state,
            )
            if method == "pcg":
                x, info = pcg(A, b, x0=x0, minv=minv, **kwargs)
            else:
                x, info = cg(A, b, x0=x0, **kwargs)
            info["restarts"] = restarts
            if failures:
                info["failures"] = failures
            _fold_sdc(info.get("sdc"))
            info["recovery"] = ledger
            return x, info
        except PartLossError as e:
            # a dead part is PERSISTENT: same-partition restarts can
            # never see its contribution again, so no restart budget is
            # burned here — either the elastic tier reshapes onto the
            # survivors (PA_ELASTIC=1) or the loss escalates typed to
            # the caller's checkpoint tier
            failures.append(
                {"type": type(e).__name__, "message": str(e),
                 "diagnostics": e.diagnostics}
            )
            _fold_sdc(e.diagnostics.get("sdc"))
            if not elastic.elastic_enabled():
                raise
            return elastic.shrink_and_resume(
                A, b, method, minv, ckpt, x0, tol, maxiter, verbose,
                e, ledger, failures, restarts,
            )
        except SolverHealthError as e:
            failures.append(
                {"type": type(e).__name__, "message": str(e),
                 "diagnostics": e.diagnostics}
            )
            # an escalated SilentCorruptionError carries the failed
            # attempt's in-memory-tier counters — fold them so the
            # ledger is cumulative across attempts
            _fold_sdc(e.diagnostics.get("sdc"))
            if restarts >= max_restarts:
                raise
            restarts += 1
            state = None
            how = "scratch"
            source = {"failure": type(e).__name__, "from": "scratch"}
            if ckpt is not None:
                try:
                    ckpt.wait()  # let an in-flight write land first
                except Exception:
                    pass
                if ckpt.has_state():
                    from ..parallel.checkpoint import CheckpointCorruptError

                    try:
                        st = load_solver_state(
                            ckpt.directory, _solver_state_ranges(A, b)
                        )
                    except CheckpointCorruptError as ce:
                        # a rotted checkpoint must degrade the restart to
                        # scratch, not crash the recovery itself
                        st = None
                        source["checkpoint_corrupt"] = str(ce)
                    # same contract as resume_solve: the exact-recurrence
                    # resume needs the full (x, r, p)+scalars state AND a
                    # method match — an iterate-only checkpoint (e.g.
                    # written into this directory by the chunked device
                    # path of the same job) restarts from the iterate
                    # instead of crashing the recovery on a missing key
                    if st is not None:
                        meta_ = st.get("meta", {})
                        if (
                            all(k in st for k in ("x", "r", "p"))
                            and "rs" in meta_
                            and meta_.get("method") == method
                        ):
                            state = st
                            how = "last checkpoint (exact recurrence)"
                            source["from"] = "checkpoint_state"
                        else:
                            x0 = st["x"]
                            how = "checkpointed iterate (Krylov restart)"
                            source["from"] = "checkpoint_iterate"
                        source["checkpoint_iteration"] = int(
                            meta_.get("it", 0)
                        )
                        ledger["checkpoint_restarts"] += 1
            ledger["restart_sources"].append(source)
            telemetry.emit_event(
                "restart", label=type(e).__name__, attempt=restarts,
                **source,
            )
            print(
                f"[partitionedarrays_jl_tpu] {method}: "
                f"{type(e).__name__}: {e} — restart {restarts}/"
                f"{max_restarts} from " + how,
                file=sys.stderr,
                flush=True,
            )


def _solve_with_recovery_chunked(
    A, b, method, ckpt, every, max_restarts, minv, x0, tol, maxiter, verbose
):
    """Device-backend recovery: the compiled one-program solve runs in
    ``every``-iteration chunks, checkpointing the iterate between chunks
    (x only — the compiled loop's internals never leave the device).
    Convergence is judged against the FIRST chunk's initial residual so
    the chunked run answers the same relative-tolerance question as an
    unchunked one."""
    import sys

    from ..parallel import elastic
    from ..parallel.checkpoint import load_solver_state
    from ..parallel.health import PartLossError, SolverHealthError

    maxiter = maxiter if maxiter is not None else 4 * A.rows.ngids
    chunk = max(1, int(every)) if ckpt is not None else maxiter
    x = x0.copy() if x0 is not None else PVector.full(0.0, A.cols, dtype=b.dtype)
    solver = pcg if method == "pcg" else cg
    kw = {"minv": minv} if method == "pcg" else {}
    done = 0
    restarts = 0
    failures = []
    residuals = []
    rs0 = None
    info = None
    ledger = _new_recovery_ledger()

    def _fold_sdc(counters):
        _ledger_fold_sdc(ledger, counters)

    while done < maxiter:
        try:
            ledger["attempts"] += 1
            x_new, info = solver(
                A, b, x0=x, tol=tol, maxiter=min(chunk, maxiter - done),
                verbose=verbose, **kw,
            )
            _fold_sdc(info.get("sdc"))
        except PartLossError as e:
            # persistent loss — see the host path: no restart budget,
            # shrink-and-resume (PA_ELASTIC=1) or typed escalation;
            # the elastic resume continues from the retained iterate
            # (the last checkpointed one wins inside shrink_and_resume)
            failures.append(
                {"type": type(e).__name__, "message": str(e),
                 "diagnostics": e.diagnostics}
            )
            _fold_sdc(e.diagnostics.get("sdc"))
            if not elastic.elastic_enabled():
                raise
            return elastic.shrink_and_resume(
                A, b, method, minv, ckpt, x, tol,
                max(1, maxiter - done), verbose,
                e, ledger, failures, restarts,
            )
        except SolverHealthError as e:
            failures.append(
                {"type": type(e).__name__, "message": str(e),
                 "diagnostics": e.diagnostics}
            )
            _fold_sdc(e.diagnostics.get("sdc"))
            if restarts >= max_restarts:
                raise
            restarts += 1
            # the chunked path keeps running from the last completed
            # chunk's in-memory iterate when no (clean) checkpoint
            # exists — say so, a test asserting the recovery path must
            # not read "scratch" for a retained-iterate continue
            source = {"failure": type(e).__name__, "from": "retained_iterate"}
            if ckpt is not None and ckpt.has_state():
                from ..parallel.checkpoint import CheckpointCorruptError

                # full ranges: the directory may hold a FULL-state (x,r,p)
                # checkpoint written by a host run of the same job —
                # load_checkpoint needs a target range for every object
                # present (extra entries for absent objects are ignored)
                try:
                    st = load_solver_state(
                        ckpt.directory, _solver_state_ranges(A, b)
                    )
                except CheckpointCorruptError as ce:
                    st = None
                    source["checkpoint_corrupt"] = str(ce)
                if st is not None:
                    x = st["x"]
                    done = int(st["meta"].get("it", done))
                    source["from"] = "checkpoint_iterate"
                    source["checkpoint_iteration"] = done
                    ledger["checkpoint_restarts"] += 1
            ledger["restart_sources"].append(source)
            from .. import telemetry as _telemetry

            _telemetry.emit_event(
                "restart", label=type(e).__name__, attempt=restarts,
                **source,
            )
            print(
                f"[partitionedarrays_jl_tpu] {method} (chunked): "
                f"{type(e).__name__}: {e} — restart {restarts}/{max_restarts}",
                file=sys.stderr,
                flush=True,
            )
            continue
        x = x_new
        if rs0 is None:
            rs0 = float(info["residuals"][0]) if len(info["residuals"]) else 0.0
        done += int(info["iterations"])
        residuals.extend(float(v) for v in info["residuals"][1:])
        final = float(info["residuals"][-1]) if len(info["residuals"]) else 0.0
        if final <= tol * max(1.0, rs0):
            break
        if int(info["iterations"]) == 0:
            break  # the chunk made no progress; avoid spinning forever
        if ckpt is not None:
            ckpt.save_state(
                {"x": x}, {"method": method, "it": done, "tol": tol}
            )
    if ckpt is not None:
        ckpt.wait()
    final = residuals[-1] if residuals else (rs0 or 0.0)
    from ..utils.helpers import krylov_info

    out = krylov_info(
        done, [rs0 or 0.0] + residuals,
        final <= tol * max(1.0, rs0 or 0.0), tol, b.dtype, False,
        final_rel=_final_true_rel(
            A, x, b, final / max(1.0, rs0 or 1.0), rs0 or 0.0, tol
        ),
    )
    out["restarts"] = restarts
    if failures:
        out["failures"] = failures
    out["recovery"] = ledger
    return x, out
