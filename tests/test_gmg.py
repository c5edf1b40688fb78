"""Distributed geometric multigrid: transfers, Galerkin product,
V-cycle convergence, and the Dirichlet decoupling transform.

Beyond-reference capability (the reference's solver story stops at
Krylov loops); everything here is built from the framework's own COO
assembly/migration machinery, so these tests double as integration
coverage of rectangular PSparseMatrix operators."""
import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa


def _poisson(parts, ns):
    A, b, x_exact, x0 = pa.assemble_poisson(parts, ns)
    return A, b, x_exact, x0


def test_decouple_dirichlet_symmetric_same_solution():
    def driver(parts):
        ns = (8, 8, 8)
        A, b, x_exact, _ = _poisson(parts, ns)
        Ah, bh = pa.decouple_dirichlet(A, b)
        M = pa.gather_psparse(Ah).toarray()
        assert np.abs(M - M.T).max() == 0.0
        xs = np.linalg.solve(M, pa.gather_pvector(bh))
        assert np.abs(xs - pa.gather_pvector(x_exact)).max() < 1e-10
        # sparsity pattern untouched: same indptr/indices per part
        def same_pattern(M0, M1):
            np.testing.assert_array_equal(M0.indptr, M1.indptr)
            np.testing.assert_array_equal(M0.indices, M1.indices)
            return True

        pa.map_parts(same_pattern, A.values, Ah.values)
        return True

    assert pa.prun(driver, pa.sequential, (2, 2, 2))


def test_decouple_matrix_only_variant():
    def driver(parts):
        A, b, _, _ = _poisson(parts, (6, 6))
        Ah = pa.decouple_dirichlet(A)  # no rhs: returns just the operator
        M = pa.gather_psparse(Ah).toarray()
        assert np.abs(M - M.T).max() == 0.0
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))


def test_interpolation_and_restriction_are_transposes():
    def driver(parts):
        nfs, ncs = (9, 9), (5, 5)
        fine_rows = pa.cartesian_partition(parts, nfs, pa.no_ghost)
        coarse_rows = pa.cartesian_partition(parts, ncs, pa.no_ghost)
        P = pa.interpolation_cartesian(nfs, ncs, fine_rows, coarse_rows)
        R = pa.restriction_from(P, coarse_rows)
        Pm = pa.gather_psparse(P).toarray()
        Rm = pa.gather_psparse(R).toarray()
        np.testing.assert_allclose(Rm, Pm.T, atol=0)
        # every fine row interpolates with unit weight sum
        np.testing.assert_allclose(Pm.sum(axis=1), 1.0, atol=1e-14)
        # coarse points map from their coincident fine point with weight 1
        assert Pm[0, 0] == 1.0 and Pm[2, 1] == 1.0
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))


def test_galerkin_product_matches_dense_triple_product():
    def driver(parts):
        ns = (9, 9)
        A, b, _, _ = _poisson(parts, ns)
        Ah = pa.decouple_dirichlet(A)
        ncs = (5, 5)
        coarse_rows = pa.cartesian_partition(parts, ncs, pa.no_ghost)
        P = pa.interpolation_cartesian(ns, ncs, Ah.rows, coarse_rows)
        Ac = pa.galerkin_cartesian(Ah, ns, ncs, coarse_rows)
        Pm = pa.gather_psparse(P).toarray()
        Am = pa.gather_psparse(Ah).toarray()
        Acm = pa.gather_psparse(Ac).toarray()
        np.testing.assert_allclose(Acm, Pm.T @ Am @ Pm, atol=1e-12)
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))


def test_gmg_solve_converges_and_pcg_preconditioned():
    def driver(parts):
        ns = (20, 20, 20)
        A, b, x_exact, _ = _poisson(parts, ns)
        Ah, bh = pa.decouple_dirichlet(A, b)
        h = pa.gmg_hierarchy(parts, Ah, ns, coarse_threshold=200, pre=2, post=2)
        assert len(h.levels) >= 2
        x, info = pa.gmg_solve(h, bh, tol=1e-9)
        assert info["converged"], info
        err = np.abs(pa.gather_pvector(x) - pa.gather_pvector(x_exact)).max()
        assert err < 1e-6, err
        # V-cycle-preconditioned CG: the hierarchy is callable minv
        xp, ip = pa.pcg(Ah, bh, minv=h, tol=1e-9)
        assert ip["converged"] and ip["iterations"] <= 20, ip["iterations"]
        errp = np.abs(pa.gather_pvector(xp) - pa.gather_pvector(x_exact)).max()
        assert errp < 1e-6, errp
        return True

    assert pa.prun(driver, pa.sequential, (2, 2, 2))


def test_gmg_near_grid_independent_iterations():
    """The multigrid property: iteration counts stay O(10) while the DOF
    count grows 8x — no Krylov method on its own can do that."""

    def run(ns):
        def driver(parts):
            A, b, _, _ = _poisson(parts, ns)
            Ah, bh = pa.decouple_dirichlet(A, b)
            h = pa.gmg_hierarchy(
                parts, Ah, ns, coarse_threshold=500, pre=2, post=2
            )
            _, ip = pa.pcg(Ah, bh, minv=h, tol=1e-9)
            return ip["iterations"]

        return pa.prun(driver, pa.sequential, (2, 2, 2))

    it_small = run((12, 12, 12))
    it_big = run((24, 24, 24))
    assert it_small <= 15 and it_big <= 15, (it_small, it_big)
    assert it_big <= it_small + 4, (it_small, it_big)


def test_gmg_runs_on_tpu_backend():
    """The V-cycle is backend-generic PData algebra: same driver on the
    (virtual-mesh) TPU backend, eager per-op execution."""

    def driver(parts):
        ns = (12, 12, 12)
        A, b, x_exact, _ = _poisson(parts, ns)
        Ah, bh = pa.decouple_dirichlet(A, b)
        h = pa.gmg_hierarchy(parts, Ah, ns, coarse_threshold=300)
        x, info = pa.gmg_solve(h, bh, tol=1e-8)
        assert info["converged"]
        err = np.abs(pa.gather_pvector(x) - pa.gather_pvector(x_exact)).max()
        return float(err)

    err_s = pa.prun(driver, pa.sequential, (2, 2, 2))
    err_t = pa.prun(driver, pa.tpu, (2, 2, 2))
    assert err_s < 1e-6 and err_t < 1e-6
    np.testing.assert_allclose(err_t, err_s, rtol=1e-6)


def test_gmg_hierarchy_rejects_mismatched_dims():
    def driver(parts):
        A, b, _, _ = _poisson(parts, (6, 6))
        with pytest.raises(AssertionError):
            pa.gmg_hierarchy(parts, A, (7, 6))
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))


def test_compiled_vcycle_iteration_parity():
    """On the TPU backend the whole V-cycle (and the V-cycle-preconditioned
    CG) runs as ONE compiled program (parallel/tpu_gmg.py); iteration
    counts must match the host oracle exactly, and solutions to rounding."""

    def driver(parts):
        ns = (16, 16, 16)
        A, b, x_exact, _ = _poisson(parts, ns)
        Ah, bh = pa.decouple_dirichlet(A, b)
        h = pa.gmg_hierarchy(parts, Ah, ns, coarse_threshold=100, pre=2, post=2)
        x1, i1 = pa.gmg_solve(h, bh, tol=1e-9)
        x2, i2 = pa.pcg(Ah, bh, minv=h, tol=1e-9)
        e1 = np.abs(pa.gather_pvector(x1) - pa.gather_pvector(x_exact)).max()
        e2 = np.abs(pa.gather_pvector(x2) - pa.gather_pvector(x_exact)).max()
        assert i1["converged"] and i2["converged"]
        return i1["iterations"], i2["iterations"], e1, e2

    s1, s2, es1, es2 = pa.prun(driver, pa.sequential, (2, 2, 2))
    t1, t2, et1, et2 = pa.prun(driver, pa.tpu, (2, 2, 2))
    assert (s1, s2) == (t1, t2), ((s1, s2), (t1, t2))
    assert max(es1, es2, et1, et2) < 1e-6
    np.testing.assert_allclose(et1, es1, rtol=1e-5)
    np.testing.assert_allclose(et2, es2, rtol=1e-5)


def test_compiled_vcycle_mixed_padded_compact_frames(monkeypatch):
    """The real-TPU frame configuration: the square coded level operator
    takes the PADDED kernel frame (o0 = one pad block) while the
    rectangular transfers stay compact (o0 = 0). Forcing `_padded_for`
    on the CPU mesh reproduces it with the Pallas kernel interpreted —
    this is the layout mix the compiled V-cycle's cross-frame slices
    must survive (a plain-CPU run cannot catch it: every frame is
    compact there)."""
    import importlib

    tpu_mod = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    monkeypatch.setattr(tpu_mod, "_padded_for", lambda backend: True)

    def driver(parts):
        ns = (12, 12, 12)
        A, b, x_exact, _ = _poisson(parts, ns)
        Ah, bh = pa.decouple_dirichlet(A, b)
        h = pa.gmg_hierarchy(parts, Ah, ns, coarse_threshold=100)
        x, info = pa.gmg_solve(h, bh, tol=1e-8)
        assert info["converged"], info
        err = np.abs(pa.gather_pvector(x) - pa.gather_pvector(x_exact)).max()
        assert err < 1e-6, err
        # the level operator really must have taken the padded frame for
        # this test to mean anything
        from partitionedarrays_jl_tpu.parallel.tpu import device_matrix

        dA0 = device_matrix(h.levels[0].A, parts.backend)
        dP0 = device_matrix(h.levels[0].P, parts.backend)
        assert dA0.padded and not dP0.padded
        return True

    assert pa.prun(driver, pa.tpu, (2, 2, 2))


def test_gmg_deep_coarsening_empty_coarse_parts():
    """Aggressive coarsening can leave coarse grids with fewer cells
    than parts (empty parts on coarse levels); the hierarchy, the host
    V-cycle, and the compiled program must all survive it with
    iteration parity."""

    def driver(parts):
        ns = (17, 17)
        A, b, x_exact, _ = _poisson(parts, ns)
        Ah, bh = pa.decouple_dirichlet(A, b)
        h = pa.gmg_hierarchy(parts, Ah, ns, coarse_threshold=8)
        # the (3, 3) coarse grid split over a (2, 4) part grid leaves
        # genuinely empty parts in one dimension
        assert any(
            i.num_oids == 0
            for i in h.coarse_A.rows.partition.part_values()
        )
        x, info = pa.gmg_solve(h, bh, tol=1e-9)
        assert info["converged"]
        err = np.abs(pa.gather_pvector(x) - pa.gather_pvector(x_exact)).max()
        assert err < 1e-6, err
        return info["iterations"]

    it_s = pa.prun(driver, pa.sequential, (2, 4))
    it_t = pa.prun(driver, pa.tpu, (2, 4))
    assert it_s == it_t, (it_s, it_t)


def test_w_cycle_host_and_compiled():
    """W-cycle (γ = 2): fewer stationary iterations than the V-cycle on
    the same hierarchy settings, identical host/compiled iteration
    counts."""

    def run(backend, cycle):
        def driver(parts):
            ns = (20, 20, 20)
            A, b, x_exact, _ = _poisson(parts, ns)
            Ah, bh = pa.decouple_dirichlet(A, b)
            h = pa.gmg_hierarchy(
                parts, Ah, ns, coarse_threshold=30, cycle=cycle
            )
            assert len(h.levels) >= 3  # a W-cycle needs depth to differ
            x, info = pa.gmg_solve(h, bh, tol=1e-9)
            assert info["converged"]
            err = np.abs(
                pa.gather_pvector(x) - pa.gather_pvector(x_exact)
            ).max()
            assert err < 1e-6, err
            return info["iterations"]

        return pa.prun(driver, backend, (2, 2, 2))

    it_v = run(pa.sequential, "v")
    it_w = run(pa.sequential, "w")
    assert it_w <= it_v, (it_w, it_v)
    it_w_t = run(pa.tpu, "w")
    assert it_w_t == it_w, (it_w_t, it_w)

    # plumbing guard that cannot pass by convergence coincidence: one
    # W-cycle at depth 3 visits the coarse solver 2^(L-1) = 4 times
    def count_coarse(parts):
        ns = (20, 20, 20)
        A, b, _, _ = _poisson(parts, ns)
        Ah, bh = pa.decouple_dirichlet(A, b)
        h = pa.gmg_hierarchy(parts, Ah, ns, coarse_threshold=30, cycle="w")
        assert len(h.levels) == 3
        calls = []
        orig = h.coarse_solver.solve
        h.coarse_solver.solve = lambda v: (calls.append(1), orig(v))[1]
        h.vcycle(bh)
        return len(calls)

    assert pa.prun(count_coarse, pa.sequential, (2, 2, 2)) == 4


def test_gmg_variable_coefficient_operator():
    """GMG beyond the constant stencil: a 2-D diffusion operator with a
    smoothly varying coefficient k(x, y) (5-point FDM, harmonic-mean
    arm weights). Every diagonal carries many distinct values, so the
    device lowering takes the streaming-DIA path rather than the coded
    one, and the exact Galerkin product must handle arbitrary values.
    The V-cycle-preconditioned CG must still converge fast."""
    ns = (33, 33)

    def assemble_var(parts):
        rows = pa.cartesian_partition(parts, ns, pa.no_ghost)
        cis = pa.p_cartesian_indices(parts, ns, pa.no_ghost)

        def k_field(cx, cy):
            return 1.0 + 0.8 * np.sin(0.37 * cx) * np.cos(0.23 * cy)

        def coo(ci):
            grid = ci.grid()
            cx, cy = [g.ravel() for g in grid]
            gid = np.ravel_multi_index((cx, cy), ns)
            interior = (cx > 0) & (cx < ns[0] - 1) & (cy > 0) & (cy < ns[1] - 1)
            I, J, V = [gid[~interior]], [gid[~interior]], [np.ones((~interior).sum())]
            gi = gid[interior]
            icx, icy = cx[interior], cy[interior]
            diag = np.zeros(len(gi))
            for dx, dy in ((-1, 0), (1, 0), (0, -1), (0, 1)):
                kn = 2.0 / (
                    1.0 / k_field(icx, icy)
                    + 1.0 / k_field(icx + dx, icy + dy)
                )
                I.append(gi)
                J.append(np.ravel_multi_index((icx + dx, icy + dy), ns))
                V.append(-kn)
                diag += kn
            I.append(gi)
            J.append(gi)
            V.append(diag)
            return np.concatenate(I), np.concatenate(J), np.concatenate(V)

        c = pa.map_parts(coo, cis)
        I = pa.map_parts(lambda t: t[0], c)
        J = pa.map_parts(lambda t: t[1], c)
        V = pa.map_parts(lambda t: t[2], c)
        cols = pa.add_gids(rows, J)
        return pa.PSparseMatrix.from_coo(I, J, V, rows, cols, ids="global")

    def driver(parts):
        A = assemble_var(parts)
        Ah = pa.decouple_dirichlet(A)
        M = pa.gather_psparse(Ah).toarray()
        assert np.abs(M - M.T).max() < 1e-13  # harmonic means: symmetric
        xs = pa.PVector.full(1.0, Ah.cols)
        bs = Ah @ xs
        h = pa.gmg_hierarchy(parts, Ah, ns, coarse_threshold=50, pre=2, post=2)
        x, info = pa.pcg(Ah, bs, minv=h, tol=1e-10)
        assert info["converged"] and info["iterations"] <= 25, info["iterations"]
        err = np.abs(pa.gather_pvector(x) - pa.gather_pvector(xs)).max()
        assert err < 1e-7, err
        return info["iterations"]

    it_s = pa.prun(driver, pa.sequential, (2, 2))
    it_t = pa.prun(driver, pa.tpu, (2, 2))
    assert it_s == it_t, (it_s, it_t)


def test_fgmres_gmg_compiled_matches_host():
    """Compiled flexible GMRES with the inlined V-cycle preconditioner
    (parallel/tpu_gmg.py:make_fgmres_gmg_fn) vs the host
    fgmres(minv=hierarchy): same Arnoldi/Givens/restart algorithm, so
    the gate is iteration parity (+-1 for FP reassociation in the basis
    updates) and solution accuracy."""

    def driver(parts):
        ns = (12, 12, 12)
        A, b, x_exact, _ = _poisson(parts, ns)
        Ah, bh = pa.decouple_dirichlet(A, b)
        h = pa.gmg_hierarchy(parts, Ah, ns, coarse_threshold=100)
        xh, ih = pa.fgmres(Ah, bh, minv=h, tol=1e-9, restart=10)
        assert ih["converged"], ih
        xt, it_ = pa.tpu_fgmres_gmg(h, bh, tol=1e-9, restart=10)
        assert it_["converged"], it_
        errh = np.abs(pa.gather_pvector(xh) - pa.gather_pvector(x_exact)).max()
        errt = np.abs(pa.gather_pvector(xt) - pa.gather_pvector(x_exact)).max()
        assert errh < 1e-7 and errt < 1e-7, (errh, errt)
        assert abs(ih["iterations"] - it_["iterations"]) <= 1, (
            ih["iterations"], it_["iterations"],
        )
        return True

    assert pa.prun(driver, pa.tpu, (2, 2, 2))


def test_fgmres_gmg_restart_cycles():
    """A restart smaller than the iteration count forces multiple outer
    cycles through the compiled while_loop; convergence must survive."""

    def driver(parts):
        ns = (12, 12)
        A, b, x_exact, _ = _poisson(parts, ns)
        Ah, bh = pa.decouple_dirichlet(A, b)
        h = pa.gmg_hierarchy(parts, Ah, ns, coarse_threshold=30)
        xt, info = pa.tpu_fgmres_gmg(h, bh, tol=1e-10, restart=3)
        assert info["converged"], info
        err = np.abs(pa.gather_pvector(xt) - pa.gather_pvector(x_exact)).max()
        assert err < 1e-7, err
        return True

    assert pa.prun(driver, pa.tpu, (2, 2))


def test_gmg_coarse_agglomeration_iteration_parity():
    """agg_threshold moves coarse levels onto a 2x-strided sub-grid of
    parts (empty boxes elsewhere). Placement must not change the math:
    same iteration counts and solution as the full-mesh hierarchy, on
    the host loop AND the compiled program."""

    def driver(parts, agg):
        ns = (24, 24, 24)
        A, b, x_exact, _ = _poisson(parts, ns)
        Ah, bh = pa.decouple_dirichlet(A, b)
        h = pa.gmg_hierarchy(
            parts, Ah, ns, coarse_threshold=100,
            agg_threshold=agg,
        )
        if agg:
            # some level must actually be agglomerated: a coarse
            # partition with empty parts while cells >= parts
            assert any(
                min(
                    i.num_oids
                    for i in lvl.A.rows.partition.part_values()
                ) == 0
                and lvl.A.rows.ngids >= lvl.A.rows.num_parts
                for lvl in h.levels[1:]
            ) or min(
                i.num_oids
                for i in h.coarse_A.rows.partition.part_values()
            ) == 0
        x, info = pa.gmg_solve(h, bh, tol=1e-9)
        assert info["converged"]
        err = np.abs(pa.gather_pvector(x) - pa.gather_pvector(x_exact)).max()
        assert err < 1e-6, err
        xp, infop = pa.tpu_gmg_pcg(h, bh, tol=1e-9)
        assert infop["converged"]
        errp = np.abs(
            pa.gather_pvector(xp) - pa.gather_pvector(x_exact)
        ).max()
        assert errp < 1e-6, errp
        return info["iterations"], infop["iterations"]

    it_full = pa.prun(driver, pa.tpu, (2, 2, 2), 0)
    it_agg = pa.prun(driver, pa.tpu, (2, 2, 2), 2000)
    assert it_full == it_agg, (it_full, it_agg)


def test_fgmres_gmg_tight_tolerance_f64():
    """Round-3 postscript: an apparent FGMRES convergence-flag stall at
    this config came from probes that ran the DEVICE in f32 (no x64)
    while comparing against the f64 host loop — the Arnoldi residual
    estimate simply floors near f32 epsilon, as any f32 Krylov does.
    Under the suite's f64 config, host and device both converge."""

    def driver(parts):
        ns = (16, 16, 16)
        A, b, x_exact, _ = _poisson(parts, ns)
        Ah, bh = pa.decouple_dirichlet(A, b)
        h = pa.gmg_hierarchy(parts, Ah, ns, coarse_threshold=100)
        xt, info = pa.tpu_fgmres_gmg(h, bh, tol=1e-8, restart=12, maxiter=40)
        err = np.abs(pa.gather_pvector(xt) - pa.gather_pvector(x_exact)).max()
        assert err < 1e-5, err
        xh, ih = pa.fgmres(Ah, bh, minv=h, tol=1e-8, restart=12, maxiter=40)
        assert ih["converged"]
        return info["converged"], info["iterations"], ih["iterations"]

    conv, it_d, it_h = pa.prun(driver, pa.tpu, (2, 2, 2))
    assert conv
    assert abs(it_d - it_h) <= 1, (it_d, it_h)


def test_galerkin_fused_asymmetric_dense_parity():
    """Round-4 fused Galerkin (COO-free shell-exchange + native CSR
    emission, models/gmg.py:_galerkin_fused): dense triple-product
    parity on an ASYMMETRIC 3-D grid with uneven per-part boxes, plus
    the CSR structural contract the emission kernel promises (column-
    sorted rows in local ids, owned columns before ghosts)."""

    def driver(parts):
        ns = (7, 6, 9)
        A, b, _, _ = _poisson(parts, ns)
        Ah = pa.decouple_dirichlet(A)
        ncs = tuple((n + 1) // 2 for n in ns)
        coarse_rows = pa.cartesian_partition(parts, ncs, pa.no_ghost)
        P = pa.interpolation_cartesian(ns, ncs, Ah.rows, coarse_rows)
        Ac = pa.galerkin_cartesian(Ah, ns, ncs, coarse_rows)
        Pm = pa.gather_psparse(P).toarray()
        Am = pa.gather_psparse(Ah).toarray()
        Acm = pa.gather_psparse(Ac).toarray()
        np.testing.assert_allclose(Acm, Pm.T @ Am @ Pm, atol=1e-12)

        # structural contract of the fused emission
        def _check_struct(M):
            for r in range(M.shape[0]):
                c = M.indices[M.indptr[r] : M.indptr[r + 1]]
                assert (np.diff(c) > 0).all(), (r, c)  # strictly sorted
            return True

        assert all(
            pa.map_parts(_check_struct, Ac.values).part_values()
        )
        return True

    assert pa.prun(driver, pa.sequential, (2, 2, 2))
    assert pa.prun(driver, pa.sequential, (3, 1, 2))


@pytest.mark.parametrize(
    "ns,pshape",
    [
        ((40, 38, 36), (1, 1, 1)),
        ((37, 41, 39), (2, 2, 1)),
        ((48, 50), (2, 2)),
    ],
)
def test_classed_collapse_bit_identical(ns, pshape):
    """Round-4 directive 1: the classed Galerkin collapse (rep-box +
    broadcast expansion, default-on) must produce BIT-identical coarse
    operators to the full native collapse — same kernel arithmetic, same
    fine-row order per coarse row. Pins _zone_reps margins,
    galerkin_classify_dim, and the sub_coords kernel path."""
    import os

    from partitionedarrays_jl_tpu.models import assemble_poisson
    from partitionedarrays_jl_tpu.models.gmg import galerkin_cartesian
    from partitionedarrays_jl_tpu.parallel.prange import (
        cartesian_partition, no_ghost,
    )
    from partitionedarrays_jl_tpu.parallel.psparse import (
        psparse_global_triplets,
    )

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(
            parts, ns, dtype=np.float32, decoupled=True
        )
        ncs = tuple((n + 1) // 2 for n in ns)
        Ac1 = galerkin_cartesian(
            A, ns, ncs, cartesian_partition(parts, ncs, no_ghost)
        )
        os.environ["PA_TPU_GMG_CLASSED"] = "0"
        try:
            Ac2 = galerkin_cartesian(
                A, ns, ncs, cartesian_partition(parts, ncs, no_ghost)
            )
        finally:
            del os.environ["PA_TPU_GMG_CLASSED"]
        for (i1, j1, v1), (i2, j2, v2) in zip(
            psparse_global_triplets(Ac1).part_values(),
            psparse_global_triplets(Ac2).part_values(),
        ):
            o1, o2 = np.lexsort((j1, i1)), np.lexsort((j2, i2))
            assert np.array_equal(i1[o1], i2[o2])
            assert np.array_equal(j1[o1], j2[o2])
            assert np.array_equal(v1[o1], v2[o2]), "values drifted"
        return True

    assert pa.prun(driver, pa.sequential, pshape)


def test_classed_collapse_declines_variable_coefficients():
    """The zone-uniformity proof must reject operators whose values are
    not a function of boundary distance — the classed path silently
    producing wrong coarse operators for variable coefficients would be
    the worst possible failure mode."""
    from partitionedarrays_jl_tpu.models.gmg import _classed_collapse

    def driver(parts):
        ns = (24, 22, 20)
        A, b, xe, x0 = pa.assemble_poisson(parts, ns)
        # perturb one interior value: no zone function can explain it
        M = A.values.part_values()[0]
        k = len(M.data) // 2
        M.data[k] *= 1.5
        ri = A.rows.partition.part_values()[0]
        ci = A.cols.partition.part_values()[0]
        ncs = tuple((n + 1) // 2 for n in ns)
        dim = len(ns)
        flo, fhi = ri.box_lo, ri.box_hi
        elo = [max(0, (flo[d] - 1) // 2) for d in range(dim)]
        ehi = [min(ncs[d], fhi[d] // 2 + 1) for d in range(dim)]
        out = _classed_collapse(ri, ci, M, ns, ncs, flo, fhi, elo, ehi)
        assert out is None, "classed collapse accepted a non-classed operator"
        return True

    assert pa.prun(driver, pa.sequential, (1, 1, 1))


def _stencil_level_info(h, backend):
    """(descs_or_False, has_shmask, form) per level of the staged
    hierarchy; form is one of `tpu_gmg.TRANSFER_FORMS`."""
    from partitionedarrays_jl_tpu.parallel.tpu_gmg import _device_hierarchy

    dh = _device_hierarchy(h, backend)
    return [
        (
            len(l["stencil"]) if "stencil" in l else False,
            "shmask" in l,
            l["form"],
        )
        for l in dh["levels"]
    ]


def test_stencil_transfer_unequal_boxes():
    """Round-5 directive 4: unequal Cartesian splits take the matrix-free
    stencil transfer via per-descriptor `lax.switch` branches — compiled
    GMG and GMG-PCG must match the sequential oracle exactly on
    iteration counts (and to rounding on the solution)."""
    ns = (17, 14, 10)  # (9,8)/(7,7)/(5,5) boxes: multi-variant plans

    def driver(parts):
        A0, b0, xe, _ = pa.assemble_poisson(parts, ns)
        A, b = pa.decouple_dirichlet(A0, b0)
        h = pa.gmg_hierarchy(parts, A, ns, coarse_threshold=50)
        x1, i1 = pa.gmg_solve(h, b, tol=1e-9)
        x2, i2 = pa.pcg(A, b, minv=h, tol=1e-9)
        err = np.abs(pa.gather_pvector(x1) - pa.gather_pvector(xe)).max()
        assert i1["converged"] and i2["converged"]
        info = (
            _stencil_level_info(h, parts.backend)
            if parts.backend is pa.tpu
            else None
        )
        return i1["iterations"], i2["iterations"], float(err), info

    s = pa.prun(driver, pa.sequential, (2, 2, 2))
    t = pa.prun(driver, pa.tpu, (2, 2, 2))
    assert (s[0], s[1]) == (t[0], t[1]), (s, t)
    assert max(s[2], t[2]) < 1e-6
    # the run must actually have exercised the one-pass stencil's
    # multi-variant switch (the separable level 0 has unequal boxes too)
    assert any(
        isinstance(d, int) and d > 1 and f == "stencil"
        for d, _m, f in t[3]
    ), t[3]


def test_stencil_transfer_periodic():
    """Round-5 directive 4: periodic (torus) levels take the stencil
    transfer with the wrapped segments masked to zero — matching the
    truncating assembled-S oracle — instead of falling back to the
    assembled-matrix path."""
    ns = (12, 12, 12)

    def driver(parts):
        A, b, xe, x0 = pa.assemble_poisson_periodic(parts, ns, shift=1.0)
        h = pa.gmg_hierarchy(parts, A, ns, coarse_threshold=100)
        x1, i1 = pa.gmg_solve(h, b, tol=1e-9)
        x2, i2 = pa.pcg(A, b, minv=h, tol=1e-9)
        err = np.abs(pa.gather_pvector(x1) - pa.gather_pvector(xe)).max()
        assert i1["converged"] and i2["converged"]
        info = (
            _stencil_level_info(h, parts.backend)
            if parts.backend is pa.tpu
            else None
        )
        return i1["iterations"], i2["iterations"], float(err), info

    s = pa.prun(driver, pa.sequential, (2, 2, 2))
    t = pa.prun(driver, pa.tpu, (2, 2, 2))
    assert (s[0], s[1]) == (t[0], t[1]), (s, t)
    assert max(s[2], t[2]) < 1e-7
    # level 0 (7-point halo: no corner slabs) takes the face-only
    # separable form with its wrapped faces masked; the Galerkin level
    # must ENGAGE the one-pass stencil with the wrapped-segment mask staged
    assert t[3][0][1:] == (True, "separable"), t[3]
    assert any(d and m and f == "stencil" for d, m, f in t[3]), t[3]


def test_aligned_coarse_split_engages_stencil_on_odd_extents():
    """The hierarchy's coarse cuts are ceil(fine_cut/2)-aligned, so odd
    coarse extents (58 -> 29 -> 15, the flagship's deep levels) keep
    st in {0, 1} and the stencil fast path engages — the default
    remainder-last split put a coarse point's even fine position in the
    neighbor part (st = -1) and silently fell back to assembled
    transfers."""
    ns = (58, 58, 58)

    def driver(parts):
        A0, b0, xe, _ = pa.assemble_poisson(parts, ns)
        A, b = pa.decouple_dirichlet(A0, b0)
        h = pa.gmg_hierarchy(parts, A, ns, coarse_threshold=100)
        x, info = pa.gmg_solve(h, b, tol=1e-8)
        assert info["converged"]
        err = np.abs(pa.gather_pvector(x) - pa.gather_pvector(xe)).max()
        assert err < 1e-6, err
        return _stencil_level_info(h, parts.backend), [
            lvl.ncs for lvl in h.levels
        ]

    info, ncs = pa.prun(driver, pa.tpu, (2, 2, 2))
    # every Galerkin level (full 27-point shell) must take the stencil
    # path — including the odd-extent 29->15 transition
    assert all(d and f == "stencil" for d, _m, f in info[1:]), (info, ncs)


def test_cartesian_partition_dim_firsts():
    """Explicit per-dim cuts override the balanced split (zero-size
    blocks allowed); invalid cuts are rejected."""
    parts = pa.sequential.get_part_ids((2, 2))

    def driver(parts):
        r = pa.cartesian_partition(
            parts, (6, 6), pa.no_ghost, dim_firsts=[[0, 2], [0, 5]]
        )
        boxes = [
            (tuple(i.box_lo), tuple(i.box_hi))
            for i in r.partition.part_values()
        ]
        assert boxes == [
            ((0, 0), (2, 5)),
            ((0, 5), (2, 6)),
            ((2, 0), (6, 5)),
            ((2, 5), (6, 6)),
        ], boxes
        assert r.ngids == 36
        # gid->part honors the custom cuts
        g2p = r.gid_to_part
        assert int(g2p(np.array([0]))[0]) == 0
        assert int(g2p(np.array([5]))[0]) == 1  # col 5 -> second block
        assert int(g2p(np.array([2 * 6]))[0]) == 2  # row 2 -> third
        with pytest.raises(AssertionError):
            pa.cartesian_partition(
                parts, (6, 6), pa.no_ghost, dim_firsts=[[1, 2], [0, 5]]
            )
        return True

    assert pa.prun(driver, pa.sequential, (2, 2))


def test_matrix_s_fallback_gets_box_plan_on_agglomerated_levels():
    """docs/roadmap.md §4 (round-7 satellite): the matrix-S fallback's
    cols exchanger must take the slice-based box plan whenever its ghost
    set is slab-shaped — including AGGLOMERATED coarse levels, whose
    inactive parts own empty boxes (the case that used to fail the slab
    detector outright and silently lower to the generic gather plan).
    Also pins solve parity: the box-plan program reproduces the
    full-mesh hierarchy's iteration count."""
    import os

    from partitionedarrays_jl_tpu.parallel.tpu_box import BoxExchangePlan
    from partitionedarrays_jl_tpu.parallel.tpu_gmg import _device_hierarchy

    os.environ["PA_TPU_GMG_STENCIL"] = "0"  # force the matrix-S path
    try:

        def driver(parts):
            ns = (16, 16, 16)
            A, b, x_exact, _ = _poisson(parts, ns)
            Ah, bh = pa.decouple_dirichlet(A, b)
            h = pa.gmg_hierarchy(
                parts, Ah, ns, coarse_threshold=30, agg_threshold=200,
            )
            # the hierarchy must actually agglomerate somewhere
            assert any(
                min(
                    i.num_oids
                    for i in lvl.A.rows.partition.part_values()
                ) == 0
                for lvl in h.levels[1:]
            ) or min(
                i.num_oids
                for i in h.coarse_A.rows.partition.part_values()
            ) == 0
            dh = _device_hierarchy(h, parts.backend)
            s_levels = [l for l in dh["levels"] if "dS" in l]
            assert s_levels, "no level took the matrix-S fallback"
            for l in s_levels:
                assert isinstance(l["dS"].col_plan, BoxExchangePlan), (
                    "matrix-S cols exchanger lowered to the generic "
                    "gather plan on a slab-shaped ghost set"
                )
            x, info = pa.tpu_gmg_pcg(h, bh, tol=1e-9)
            assert info["converged"]
            err = np.abs(
                pa.gather_pvector(x) - pa.gather_pvector(x_exact)
            ).max()
            assert err < 1e-6, err
            return info["iterations"]

        it_agg = pa.prun(driver, pa.tpu, (2, 2, 2))
        assert it_agg > 0
    finally:
        del os.environ["PA_TPU_GMG_STENCIL"]


def test_f32_hierarchy_stages_f32_end_to_end():
    """docs/roadmap.md §4 (round-7 satellite): an f32 hierarchy must
    stage f32 everywhere — transfers (P/R/S), coarse inverse, smoother
    diagonals — with no f64 detour on host. The interpolation weights
    are exact powers of 1/2, so the f32 transfers lose nothing."""
    from partitionedarrays_jl_tpu.parallel.tpu_gmg import _device_hierarchy

    def driver(parts):
        ns = (16, 16, 16)
        A, b, x_exact, x0 = pa.assemble_poisson(
            parts, ns, dtype=np.float32
        )
        h = pa.gmg_hierarchy(parts, A, ns, coarse_threshold=30)
        for lvl in h.levels:
            assert lvl.A.dtype == np.float32
            assert lvl.dinv.dtype == np.float32
            # lazily-built assembled transfers inherit the level dtype
            assert lvl.P.dtype == np.float32, lvl.P.dtype
            assert lvl.R.dtype == np.float32, lvl.R.dtype
        assert h.coarse_A.dtype == np.float32
        dh = _device_hierarchy(h, parts.backend)
        assert dh["cinv"].dtype == np.float32, dh["cinv"].dtype
        for l in dh["levels"]:
            assert np.dtype(l["dinv"].dtype) == np.float32
            if "dS" in l:
                dS = l["dS"]
                staged = next(
                    a
                    for a in (dS.dia_cb, dS.dia_vals, dS.oo_vals)
                    if a is not None
                )
                assert np.dtype(staged.dtype) == np.float32, staged.dtype
        # and the preconditioner still works at f32
        x, info = pa.pcg(A, b, x0=x0, minv=h, tol=1e-4, maxiter=200)
        assert info["converged"]
        return True

    assert pa.prun(driver, pa.tpu, (2, 2, 2))
