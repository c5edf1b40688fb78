"""Block multi-RHS CG (`make_cg_fn(rhs_batch=K)` / `cg(B=...)` /
`pcg(B=...)`): the operator streams once per K right-hand sides.

The block program's three contracts, each pinned here:

* **Per-column trajectory identity.** Every column follows the textbook
  single-vector recurrence with per-column α/β — column k's iterate
  sequence IS the K=1 program's sequence for (b_k, x0_k), bit-for-bit
  under strict-bits arithmetic (pinned on the asymmetric 4-part
  conformance partition, like the fused-body tests). Converged columns
  freeze (α=0 / state re-select) rather than exiting, so ragged blocks
  keep every column's solo trajectory.
* **Collective parity, K-independent.** The dot payloads widen from
  scalars to (K,) / (K, 2) stacks riding the SAME all_gathers
  (`_pdot_owned_factory`), and the halo ppermutes ship (…, K) slabs —
  the per-iteration collective count in the lowered HLO must not depend
  on K, for both the standard and the fused body.
* **Lowering-independent SpMM.** Every SpMV lowering (coded-DIA,
  XLA-DIA, SD, BSR, ELL) accepts the (P, W, K) block operand and agrees
  with K separate SpMVs (bitwise under strict-bits, where the ELL path
  is the oracle).
* **One recurrence, two layouts.** Where the operator's A_oo block runs
  the coded Mosaic kernel on the padded frame, the fused block body
  holds its K columns lane-major and sends each through the solo
  solve's kernel (`_block_lane_major`; PR 35): the same three contracts,
  `info["block_layout"] == "lanes"`; every other operator, body and
  mode keeps the (W, K) body and says ``"columns"``.
"""
import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu.models import (
    assemble_poisson,
    gather_pvector,
    jacobi_preconditioner,
)
from partitionedarrays_jl_tpu.models.solvers import cg, pcg
from partitionedarrays_jl_tpu.parallel.pvector import _write_owned
from partitionedarrays_jl_tpu.parallel.tpu import (
    DeviceVector,
    TPUBackend,
    _block_on_cols_layout,
    _matrix_operands,
    device_matrix,
    make_cg_fn,
    make_spmv_fn,
    tpu_block_cg,
    tpu_cg,
)

from test_fused_cg import _fixture_spd_system, _padded_frame


def _backend(n=8):
    import jax

    return TPUBackend(devices=jax.devices()[:n])


def _rand_rhs(A, seed, dtype=np.float64):
    v = pa.PVector.full(0.0, A.cols, dtype=dtype)

    def fill(i, vals):
        rng = np.random.default_rng(seed + int(i.part))
        _write_owned(i, vals, rng.standard_normal(i.num_oids))

    pa.map_parts(fill, v.rows.partition, v.values)
    return v


def _ragged_block(A, b):
    """Three RHS of very different difficulty: the assembled b, a random
    vector, and a tiny constant forcing — their solo iteration counts
    differ, which is the point (ragged convergence)."""
    w = pa.PVector.full(0.0, A.cols)

    def fill(i, vals):
        _write_owned(i, vals, np.full(i.num_oids, 1e-3))

    pa.map_parts(fill, w.rows.partition, w.values)
    return [b, _rand_rhs(A, 11), w]


# ---------------------------------------------------------------------------
# block SpMM parity across lowerings
# ---------------------------------------------------------------------------


def test_block_spmv_matches_columns_coded_dia():
    backend = _backend()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8, 8))
        return A

    A = pa.prun(driver, backend, (2, 2, 2))
    dA = device_matrix(A, backend)
    assert dA.dia_mode == "coded"  # the stencil fast path engaged
    spmv = make_spmv_fn(dA)
    Bs = [_rand_rhs(A, 7 * k) for k in range(4)]
    yblk = np.asarray(spmv(_block_on_cols_layout(Bs, dA)))
    assert yblk.shape[-1] == 4
    for k, bk in enumerate(Bs):
        dx = DeviceVector.from_pvector(bk, backend, dA.col_layout)
        np.testing.assert_allclose(
            yblk[..., k], np.asarray(spmv(dx.data)), rtol=0, atol=1e-12
        )


def test_block_spmv_strict_bits_ell_bitwise(monkeypatch):
    """Strict-bits forces the pure-ELL lowering and the generic exchange
    plan; the block product must equal the column products BITWISE."""
    monkeypatch.setenv("PA_TPU_STRICT_BITS", "1")
    backend = _backend()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8, 8))
        return A

    A = pa.prun(driver, backend, (2, 2, 2))
    dA = device_matrix(A, backend)
    assert dA.oo_vals is not None  # ELL path
    spmv = make_spmv_fn(dA)
    Bs = [_rand_rhs(A, 3 * k) for k in range(3)]
    yblk = np.asarray(spmv(_block_on_cols_layout(Bs, dA)))
    for k, bk in enumerate(Bs):
        dx = DeviceVector.from_pvector(bk, backend, dA.col_layout)
        np.testing.assert_array_equal(yblk[..., k], np.asarray(spmv(dx.data)))


def test_block_spmv_matches_columns_sd_and_bsr():
    """The irregular-graph lowerings (SD einsum buckets, node-block BSR,
    and the bucketed node-block A_oh boundary path) take the block
    operand: one (G·bs, U·bs) @ (U·bs, K) einsum per bucket."""
    import os

    from partitionedarrays_jl_tpu.models.elasticity_tet import (
        assemble_elasticity_tet,
    )
    from partitionedarrays_jl_tpu.parallel.tpu import DeviceMatrix

    def driver(parts):
        A, b, xh, x0 = assemble_elasticity_tet(parts, (4, 4, 4))
        backend = parts.backend
        dA = device_matrix(A, backend)
        assert dA.sd_bs == 3 and dA.ohb_bs == 3, (dA.sd_bs, dA.ohb_bs)
        Bs = [_rand_rhs(A, 13 * k) for k in range(3)]
        xblk = _block_on_cols_layout(Bs, dA)
        y_sd = np.asarray(make_spmv_fn(dA)(xblk))
        os.environ["PA_TPU_SD"] = "0"
        try:
            dA_bsr = DeviceMatrix(A, backend)
            assert dA_bsr.bsr_bs == 3
            y_bsr = np.asarray(
                make_spmv_fn(dA_bsr)(_block_on_cols_layout(Bs, dA_bsr))
            )
        finally:
            del os.environ["PA_TPU_SD"]
        np.testing.assert_allclose(y_sd, y_bsr, rtol=1e-10, atol=1e-10)
        for k, bk in enumerate(Bs):
            dx = DeviceVector.from_pvector(bk, backend, dA.col_layout)
            yk = np.asarray(make_spmv_fn(dA)(dx.data))
            np.testing.assert_allclose(
                y_sd[..., k], yk, rtol=1e-12, atol=1e-12
            )
        return True

    assert pa.prun(driver, pa.tpu, 4)


# ---------------------------------------------------------------------------
# ragged convergence: every column matches its solo trajectory
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fused", [False, True])
def test_block_cg_ragged_columns_match_solo(fused):
    backend = _backend()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8, 8))
        return A, _ragged_block(A, b)

    A, B = pa.prun(driver, backend, (2, 2, 2))
    xs, info = cg(A, B=B, tol=1e-8, maxiter=400, fused=fused)
    assert info["cg_body"] == ("fused" if fused else "standard")
    assert info["rhs_batch"] == 3
    its = info["iterations_per_column"]
    assert len(set(its)) > 1, f"block is not ragged: {its}"
    assert info["iterations"] == max(its)
    for k, bk in enumerate(B):
        xk, ik = tpu_cg(A, bk, tol=1e-8, maxiter=400, fused=fused)
        assert ik["iterations"] == its[k], (k, ik["iterations"], its)
        np.testing.assert_allclose(
            gather_pvector(xs[k]), gather_pvector(xk), rtol=0, atol=1e-10
        )
        n = ik["iterations"] + 1
        np.testing.assert_allclose(
            np.asarray(info["columns"][k]["residuals"])[:n],
            np.asarray(ik["residuals"])[:n],
            rtol=1e-12,
        )
        # frozen tail: nothing is logged past a column's freeze point
        hist_k = np.asarray(info["columns"][k]["residuals"])
        assert len(hist_k) == n


def test_block_pcg_matches_solo_and_host():
    backend = _backend()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8, 8))
        return A, _ragged_block(A, b)

    A, B = pa.prun(driver, backend, (2, 2, 2))
    mv = jacobi_preconditioner(A)
    xs, info = pcg(A, B=B, minv=mv, tol=1e-8, maxiter=400)
    for k, bk in enumerate(B):
        xk, ik = pcg(A, bk, minv=mv, tol=1e-8, maxiter=400)
        assert ik["iterations"] == info["iterations_per_column"][k]
        np.testing.assert_allclose(
            gather_pvector(xs[k]), gather_pvector(xk), rtol=0, atol=1e-9
        )


def test_host_backend_block_runs_solo_loops():
    """On the host backend `cg(B=...)` solves the columns with the solo
    loop — the oracle semantics — and reports the same info shape."""

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (6, 6, 6))
        B = [b, _rand_rhs(A, 5)]
        xs, info = cg(A, B=B, tol=1e-9, maxiter=300)
        assert info["cg_body"] == "host" and info["rhs_batch"] == 2
        for k, bk in enumerate(B):
            xk, ik = cg(A, bk, tol=1e-9, maxiter=300)
            assert ik["iterations"] == info["iterations_per_column"][k]
            np.testing.assert_array_equal(
                gather_pvector(xs[k]), gather_pvector(xk)
            )
        return True

    assert pa.prun(driver, pa.sequential, (2, 2, 2))


# ---------------------------------------------------------------------------
# K=1 degenerate batch == the unbatched program
# ---------------------------------------------------------------------------


def test_k1_degenerate_batch_equals_unbatched(monkeypatch):
    """Under strict-bits the K=1 block program must reproduce the
    unbatched program bit-for-bit: same iterations, identical residual
    bits, identical solution bits."""
    monkeypatch.setenv("PA_TPU_STRICT_BITS", "1")
    backend = _backend(4)

    def driver(parts):
        A, b = _fixture_spd_system(parts)
        return A, b

    A, b = pa.prun(driver, backend, 4)
    xs, binfo = tpu_block_cg(A, [b], tol=1e-12, maxiter=200)
    xk, sinfo = tpu_cg(A, b, tol=1e-12, maxiter=200)
    assert binfo["columns"][0]["iterations"] == sinfo["iterations"]
    assert sinfo["iterations"] > 3
    np.testing.assert_array_equal(
        gather_pvector(xs[0]), gather_pvector(xk)
    )
    n = sinfo["iterations"] + 1
    np.testing.assert_array_equal(
        np.asarray(binfo["columns"][0]["residuals"])[:n],
        np.asarray(sinfo["residuals"])[:n],
    )


@pytest.mark.parametrize("fused", [False, True])
def test_strict_bits_block_per_column_identity(fused, monkeypatch):
    """The tentpole pin: per-column BITWISE identity against the K=1
    oracle under strict-bits on the asymmetric 4-part conformance
    fixture, for a RAGGED block (different per-column freeze points),
    with both the standard and the fused body."""
    monkeypatch.setenv("PA_TPU_STRICT_BITS", "1")
    backend = _backend(4)

    def driver(parts):
        A, b = _fixture_spd_system(parts)
        # second column: a different, rougher RHS (solo counts differ)
        b2 = pa.PVector(
            pa.map_parts(
                lambda i: np.where(
                    np.asarray(i.lid_to_part) == i.part,
                    np.cos(2.0 + 3.0 * np.asarray(i.lid_to_gid, dtype=np.float64)),
                    0.0,
                ),
                A.rows.partition,
            ),
            A.rows,
        )
        return A, [b, b2]

    A, B = pa.prun(driver, backend, 4)
    xs, binfo = tpu_block_cg(A, B, tol=1e-10, maxiter=200, fused=fused)
    assert binfo["cg_body"] == ("fused" if fused else "standard")
    for k, bk in enumerate(B):
        xk, sinfo = tpu_cg(A, bk, tol=1e-10, maxiter=200, fused=fused)
        assert (
            binfo["columns"][k]["iterations"] == sinfo["iterations"]
        ), (k, binfo["iterations_per_column"], sinfo["iterations"])
        np.testing.assert_array_equal(
            gather_pvector(xs[k]), gather_pvector(xk)
        )
        n = sinfo["iterations"] + 1
        np.testing.assert_array_equal(
            np.asarray(binfo["columns"][k]["residuals"])[:n],
            np.asarray(sinfo["residuals"])[:n],
        )


@pytest.mark.parametrize("K", [3, 5])
def test_strict_bits_ragged_odd_widths_k3_k5(K, monkeypatch):
    """Ragged parity at ODD/PRIME slab widths — the shapes the solve
    service's re-batching actually produces (a K=8 slab that lost
    ejected/converged columns re-runs at K=3, 5, ...). Same contract as
    the K=2 pin above: per-column BITWISE identity against the K=1
    oracle under strict-bits on the 4-part conformance fixture, with
    per-column freeze points (no residuals logged past a column's
    freeze)."""
    monkeypatch.setenv("PA_TPU_STRICT_BITS", "1")
    backend = _backend(4)

    def driver(parts):
        A, b = _fixture_spd_system(parts)
        B = [b]
        for j in range(1, K):
            # distinct roughness per column: solo counts differ (the
            # ragged point), deterministically
            B.append(
                pa.PVector(
                    pa.map_parts(
                        lambda i, j=j: np.where(
                            np.asarray(i.lid_to_part) == i.part,
                            np.cos(
                                2.0 + (j + 2.0)
                                * np.asarray(i.lid_to_gid, dtype=np.float64)
                            ),
                            0.0,
                        ),
                        A.rows.partition,
                    ),
                    A.rows,
                )
            )
        return A, B

    A, B = pa.prun(driver, backend, 4)
    xs, binfo = tpu_block_cg(A, B, tol=1e-10, maxiter=200)
    assert binfo["rhs_batch"] == K
    its = binfo["iterations_per_column"]
    assert len(set(its)) > 1, f"block is not ragged: {its}"
    for k, bk in enumerate(B):
        xk, sinfo = tpu_cg(A, bk, tol=1e-10, maxiter=200)
        assert its[k] == sinfo["iterations"], (k, its, sinfo["iterations"])
        np.testing.assert_array_equal(
            gather_pvector(xs[k]), gather_pvector(xk)
        )
        n = sinfo["iterations"] + 1
        np.testing.assert_array_equal(
            np.asarray(binfo["columns"][k]["residuals"])[:n],
            np.asarray(sinfo["residuals"])[:n],
        )
        # freeze-on-convergence: nothing logged past the freeze point
        assert len(np.asarray(binfo["columns"][k]["residuals"])) == n


# ---------------------------------------------------------------------------
# fused × batched interaction under the env default
# ---------------------------------------------------------------------------


def test_fused_env_default_applies_to_block(monkeypatch):
    """PA_TPU_FUSED_CG governs the block body exactly like the solo
    body: default ON, =0 reverts to standard — and both bodies agree on
    trajectories."""
    backend = _backend()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8, 8))
        return A, _ragged_block(A, b)

    A, B = pa.prun(driver, backend, (2, 2, 2))
    xs_f, inf_f = cg(A, B=B, tol=1e-8, maxiter=400)
    assert inf_f["cg_body"] == "fused"
    monkeypatch.setenv("PA_TPU_FUSED_CG", "0")
    xs_u, inf_u = cg(A, B=B, tol=1e-8, maxiter=400)
    assert inf_u["cg_body"] == "standard"
    assert (
        inf_f["iterations_per_column"] == inf_u["iterations_per_column"]
    )
    for xf, xu in zip(xs_f, xs_u):
        np.testing.assert_allclose(
            gather_pvector(xf), gather_pvector(xu), rtol=0, atol=1e-10
        )


# ---------------------------------------------------------------------------
# HLO A/B: collective count per iteration is K-independent
# ---------------------------------------------------------------------------


# the shared analyzer (one definition for the whole test tree — this
# file used to carry a private regex copy; analysis.collective_counts
# keeps the identical raw-substring semantics, pinned by
# tests/test_static_analysis.py against a committed fixture)
from partitionedarrays_jl_tpu.analysis import collective_counts  # noqa: E402


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("precond", [False, True])
@pytest.mark.parametrize("frame", ["compact", "padded"])
def test_block_collective_count_k_independent(fused, precond, frame, monkeypatch):
    """``frame='padded'`` is the real-TPU frame with the interpret-mode
    kernel: its fused body is the lane-major one, whose columns ride ONE
    ppermute a direction as a stacked payload and one all_gather a dot."""
    dtype = np.float64
    if frame == "padded":
        _padded_frame(monkeypatch)
        dtype = np.float32
    backend = _backend()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (6, 6, 6), dtype=dtype)
        return A, b

    A, b = pa.prun(driver, backend, (2, 2, 2))
    dA = device_matrix(A, backend)
    ops = _matrix_operands(dA)
    mv = None
    if precond:
        dmv = DeviceVector.from_pvector(
            jacobi_preconditioner(A), backend, dA.col_layout
        )
        mv = dmv.data
    counts = {}
    for K in (1, 4, 8):
        Bs = [b] * K
        db = _block_on_cols_layout(Bs, dA)
        dx0 = _block_on_cols_layout(
            [pa.PVector.full(0.0, A.cols, dtype=dtype) for _ in range(K)],
            dA, with_ghosts=True,
        )
        fn = make_cg_fn(
            dA, tol=1e-9, maxiter=50, fused=fused, precond=precond,
            rhs_batch=K,
        )
        assert fn.block_layout == (
            "lanes" if fused and frame == "padded" else "columns"
        )
        counts[K] = collective_counts(
            fn, db, dx0, db[..., 0] if mv is None else mv, ops
        )
    assert any(counts[1].values()), "no collectives found at all"
    assert counts[1] == counts[4] == counts[8], counts


@pytest.mark.parametrize("frame", ["compact", "padded"])
def test_block_matches_solo_collective_counts(frame, monkeypatch):
    """The K=1 block program must not pay MORE collectives than the solo
    program of the same body — widening payloads is free, extra rounds
    are not. On the padded frame the fused K=1 block program is the
    lane-major body: the solo body plus selects."""
    dtype = np.float64
    if frame == "padded":
        _padded_frame(monkeypatch)
        dtype = np.float32
    backend = _backend()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (6, 6, 6), dtype=dtype)
        return A, b

    A, b = pa.prun(driver, backend, (2, 2, 2))
    dA = device_matrix(A, backend)
    ops = _matrix_operands(dA)
    zero = pa.PVector.full(0.0, A.cols, dtype=dtype)
    db1 = _block_on_cols_layout([b], dA)
    dx01 = _block_on_cols_layout([zero], dA, with_ghosts=True)
    db = DeviceVector.from_pvector(b, backend, dA.col_layout)
    dx0 = DeviceVector.from_pvector(zero, backend, dA.col_layout)
    for fused in (False, True):
        blk = make_cg_fn(dA, tol=1e-9, maxiter=50, fused=fused, rhs_batch=1)
        solo = make_cg_fn(dA, tol=1e-9, maxiter=50, fused=fused)
        cb = collective_counts(blk, db1, dx01, db1[..., 0], ops)
        cs = collective_counts(solo, db.data, dx0.data, db.data, ops)
        for kind in cs:
            assert cb[kind] <= cs[kind], (fused, kind, cb, cs)


# ---------------------------------------------------------------------------
# the lane-major body (PR 35): the columns ride the lanes, each through
# the solo solve's coded kernel
# ---------------------------------------------------------------------------


def _lanes_grew():
    from partitionedarrays_jl_tpu import telemetry

    before = telemetry.counters("solve").get("solve.block_lane_major", 0)
    return lambda: (
        telemetry.counters("solve").get("solve.block_lane_major", 0) - before
    )


_LANES_SYSTEMS = {}


def _lanes_system(grid, precond):
    """float32 Poisson on the padded frame, its four columns (the
    assembled system from its own start, and three random right-hand
    sides from zero) and every column's solo `tpu_cg` solve: built once
    a ``(grid, precond)`` and shared by the widths."""
    key = (grid, precond)
    if key not in _LANES_SYSTEMS:
        backend = _backend(int(np.prod(grid)))

        def driver(parts):
            A, b, xe, x0 = assemble_poisson(parts, (8, 8, 8), dtype=np.float32)
            return A, b, x0

        A, b, x0 = pa.prun(driver, backend, grid)
        dA = device_matrix(A, backend)
        assert dA.padded and dA.dia_mode == "coded"
        assert dA.pallas_plan is not None
        B = [b] + [_rand_rhs(A, 7 * k, np.float32) for k in (1, 2, 3)]
        X0 = [x0] + [pa.PVector.full(0.0, A.cols, dtype=np.float32)] * 3
        mv = jacobi_preconditioner(A) if precond else None
        solo = [
            tpu_cg(A, bk, x0=xk, tol=1e-5, maxiter=80, minv=mv)
            for bk, xk in zip(B, X0)
        ]
        _LANES_SYSTEMS[key] = (A, B, X0, mv, solo)
    return _LANES_SYSTEMS[key]


@pytest.mark.parametrize("precond", [False, True], ids=["cg", "jacobi"])
@pytest.mark.parametrize("K", [1, 2, 3, 4])
@pytest.mark.parametrize("grid", [(1, 1, 1), (2, 2, 1)], ids=["1part", "4parts"])
def test_lane_major_block_matches_solo(grid, K, precond, monkeypatch):
    """On the padded frame (interpret-mode kernel) the fused block body
    is lane-major: every column takes its solo `tpu_cg` iteration count
    and agrees with its solo answer to float32 rounding, with and without
    the Jacobi row (under it the fold is the jnp one and the product the
    plain coded kernel), on one part and on four; the record and the
    counter say which body ran."""
    _padded_frame(monkeypatch)
    A, B, X0, mv, solo = _lanes_system(grid, precond)
    grew = _lanes_grew()
    xs, info = tpu_block_cg(
        A, B[:K], X0=X0[:K], tol=1e-5, maxiter=80, minv=mv
    )
    assert info["cg_body"] == "fused" and info["rhs_batch"] == K
    assert info["block_layout"] == "lanes"
    assert info.record.config["block_layout"] == "lanes"
    assert grew() == 1
    its = info["iterations_per_column"]
    assert its == [ik["iterations"] for _x, ik in solo[:K]], its
    if K > 1:
        assert len(set(its)) > 1, f"block is not ragged: {its}"
    for k in range(K):
        xk, ik = solo[k]
        ref = gather_pvector(xk)
        np.testing.assert_allclose(
            gather_pvector(xs[k]), ref, rtol=0,
            atol=2e-5 * max(1.0, float(np.abs(ref).max())),
        )
        n = ik["iterations"] + 1
        hist_k = np.asarray(info["columns"][k]["residuals"])
        assert len(hist_k) == n  # nothing logged past the freeze point
        np.testing.assert_allclose(
            hist_k, np.asarray(ik["residuals"])[:n], rtol=2e-3
        )


def test_lane_major_frozen_columns_stay_bitwise_still(monkeypatch):
    """The per-column freeze of the lane-major body: a column that
    converges tens of trips before the others and a column given a zero
    right-hand side never move a bit of x or r once frozen. The early
    column's answer and residual after the whole block has finished are
    bitwise what they were when the loop was cut at its own last trip;
    the zero column stays exactly zero and counts no iteration."""
    _padded_frame(monkeypatch)
    A, B, X0, _mv, solo = _lanes_system((2, 2, 1), False)
    zero = pa.PVector.full(0.0, A.cols, dtype=np.float32)
    counts = [ik["iterations"] for _x, ik in solo]
    slow, early = int(np.argmax(counts)), int(np.argmin(counts))
    B3, X3 = [B[slow], B[early], zero], [X0[slow], X0[early], zero]
    xs, info = tpu_block_cg(A, B3, X0=X3, tol=1e-5, maxiter=80)
    assert info["block_layout"] == "lanes"
    its = info["iterations_per_column"]
    assert its == [counts[slow], counts[early], 0], (its, counts)
    assert its[0] - its[1] >= 20, its
    # cut the same block at the early column's last trip
    xs_cut, info_cut = tpu_block_cg(A, B3, X0=X3, tol=1e-5, maxiter=its[1])
    assert info_cut["iterations_per_column"] == [its[1], its[1], 0]
    np.testing.assert_array_equal(
        gather_pvector(xs[1]), gather_pvector(xs_cut[1])
    )
    np.testing.assert_array_equal(
        np.asarray(info["columns"][1]["residuals"]),
        np.asarray(info_cut["columns"][1]["residuals"]),
    )
    np.testing.assert_array_equal(
        gather_pvector(xs[2]), np.zeros_like(gather_pvector(xs[2]))
    )
    assert info["columns"][2]["converged"]
    assert np.all(np.isfinite(gather_pvector(xs[0])))


def test_served_slabs_run_the_lane_major_body(monkeypatch):
    """A `SolveService` over the padded-frame coded operator: every slab
    is one block solve of the lane-major body, so `solve.block_lane_major`
    grows with `service.slabs`, every block solve's record says
    ``block_layout: "lanes"``, and every request gets the answer of its
    own right-hand side whatever slab and row it rode in."""
    from partitionedarrays_jl_tpu import telemetry
    from partitionedarrays_jl_tpu.service import SolveService

    _padded_frame(monkeypatch)
    A, B, X0, _mv, solo = _lanes_system((2, 2, 1), False)
    before = telemetry.counters("service").get("service.slabs", 0)
    grew = _lanes_grew()
    seq0 = max((r.seq for r in telemetry.record_history()), default=0)
    svc = SolveService(A, kmax=4)
    order = [2, 0, 3, 1, 0]  # a slab of four, then one alone
    hs = [svc.submit(B[k], x0=X0[k], tol=1e-5, maxiter=80) for k in order]
    svc.drain()
    slabs = telemetry.counters("service").get("service.slabs", 0) - before
    assert slabs == 2 and grew() == slabs
    for h, k in zip(hs, order):
        x, info = h.wait(0.0)
        assert info["converged"]
        assert info["iterations"] == solo[k][1]["iterations"]
        ref = gather_pvector(solo[k][0])
        np.testing.assert_allclose(
            gather_pvector(x), ref, rtol=0,
            atol=2e-5 * max(1.0, float(np.abs(ref).max())),
        )
    blocks = [
        r for r in telemetry.record_history()
        if r.seq > seq0 and r.solver == "block-cg"
    ]
    assert [r.config["rhs_batch"] for r in blocks] == [4, 1]
    assert all(r.config["block_layout"] == "lanes" for r in blocks)


def _columns_case(case, monkeypatch):
    """A block system whose program must keep the (W, K) body, and why."""
    fused = True
    if case in ("sd", "ell"):
        from partitionedarrays_jl_tpu.models.elasticity_tet import (
            assemble_elasticity_tet,
        )

        if case == "ell":
            monkeypatch.setenv("PA_TPU_SD", "0")
            monkeypatch.setenv("PA_TPU_BSR", "0")
        backend = _backend(4)
        A, b = pa.prun(
            lambda parts: assemble_elasticity_tet(parts, (4, 4, 4))[:2],
            backend, 4,
        )
        dA = device_matrix(A, backend)
        assert (dA.sd_bs == 3) if case == "sd" else (dA.oo_vals is not None)
    elif case == "coded-compact":
        # the coded operator off the padded frame: no kernel, the XLA form
        backend = _backend(4)
        A, b = pa.prun(_fixture_spd_system, backend, 4)
        dA = device_matrix(A, backend)
        assert dA.dia_mode == "coded" and dA.pallas_plan is None
    else:
        # the padded-frame coded operator, which alone would run lane-major
        _padded_frame(monkeypatch)
        if case == "strict-bits":
            monkeypatch.setenv("PA_TPU_STRICT_BITS", "1")
        elif case == "sdc-audit":
            monkeypatch.setenv("PA_HEALTH_AUDIT_EVERY", "5")
        elif case == "sdc-abft":
            monkeypatch.setenv("PA_TPU_ABFT", "1")
        elif case == "standard-body":
            fused = False
        backend = _backend(4)

        def driver(parts):
            A, b, xe, x0 = assemble_poisson(parts, (8, 8, 8), dtype=np.float32)
            return A, b

        A, b = pa.prun(driver, backend, (2, 2, 1))
        dA = device_matrix(A, backend)
        if case == "strict-bits":
            assert dA.oo_vals is not None  # pure ELL, off the padded frame
        else:
            assert dA.padded and dA.dia_mode == "coded"
            assert dA.pallas_plan is not None
    return A, b, fused


@pytest.mark.parametrize(
    "case",
    ["sd", "ell", "coded-compact", "strict-bits", "sdc-audit", "sdc-abft",
     "standard-body"],
)
def test_other_lowerings_bodies_and_modes_keep_the_columns_layout(
    case, monkeypatch
):
    """The layout follows from the operator's lowering, the body and the
    SDC mode alone. An SD operator (its ``grc,gck->grk`` products stream
    the dense blocks once for K columns and want K minor), an ELL
    operator, the coded operator off the padded frame, strict-bits,
    either SDC mode and the standard body keep the (W, K) program: the record says ``"columns"``, the counter stands,
    and the lowered program is the (W, K) one (its loop carries frames of
    ``W x K`` and none of ``K x W/128 x 128``)."""
    from partitionedarrays_jl_tpu.ops.pallas_dia import LANES

    A, b, fused = _columns_case(case, monkeypatch)
    B = [b, _rand_rhs(A, 5, b.dtype)]
    grew = _lanes_grew()
    xs, info = tpu_block_cg(A, B, tol=1e-5, maxiter=40, fused=fused)
    assert info["block_layout"] == "columns"
    assert info.record.config["block_layout"] == "columns"
    assert grew() == 0
    dA = device_matrix(A, xs[0].values.backend)
    fn = make_cg_fn(dA, tol=1e-5, maxiter=40, fused=fused, rhs_batch=2)
    assert fn.block_layout == "columns"
    W = dA.col_layout.W
    dt = np.asarray(_block_on_cols_layout(B, dA)).dtype
    z = np.zeros((dA.col_layout.P, W, 2), dtype=dt)
    text = fn.jit_fn.lower(z, z, z[..., 0], fn.operands).as_text()
    assert f"tensor<{W}x2x" in text
    assert f"tensor<2x{W // LANES}x{LANES}x" not in text


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------


def test_block_rejects_checkpoint_and_bad_block_args():
    backend = _backend()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (6, 6))
        return A, b

    A, b = pa.prun(driver, backend, (2, 2))
    with pytest.raises(ValueError, match="single-RHS"):
        cg(A, B=[b], checkpoint=object())
    with pytest.raises(Exception):
        cg(A, b, B=[b])  # both b and B
    with pytest.raises(Exception, match="at least one"):
        cg(A, B=[])  # empty block fails with the friendly message
    with pytest.raises(Exception, match="at least one"):
        pcg(A, B=iter(()))  # generator B is normalized before the check
