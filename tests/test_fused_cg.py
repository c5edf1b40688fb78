"""The fused streaming CG body (`make_cg_fn(fused=True)`, the
``PA_TPU_FUSED_CG`` default outside strict-bits).

The fusion's three contracts, each pinned here:

* **Trajectory identity.** Every scalar follows the textbook recurrence
  on the same dots in the same order, so the iterate sequence matches
  the standard (unfused) body — bit-for-bit under strict-bits
  arithmetic, where the unfused body is the oracle. Pinned on the
  asymmetric 4-part conformance partition (the 10-gid fixture of
  test_conformance.py / reference test_interfaces.jl:177-207), whose
  ghost graph exercises the generic exchange plan.
* **Collective parity.** The fused body restructures the VECTOR sweeps;
  it must not add collectives (the preconditioned pair of reductions
  actually shares one all_gather). Asserted on the lowered HLO of the
  compiled programs — the same A/B discipline the round-1 in-graph
  health guard was verified with.
* **Kernel fold parity.** On the padded coded frame the direction
  update rides the Pallas kernel's window pass (`_padded_kernel`
  has_pfold); validated on CPU through the Pallas interpreter exactly
  like the other padded-frame tests.
"""
import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu.models import (
    assemble_poisson,
    gather_pvector,
    jacobi_preconditioner,
)
from partitionedarrays_jl_tpu.parallel.tpu import (
    DeviceVector,
    TPUBackend,
    _b_on_cols_layout,
    device_matrix,
    make_cg_fn,
    tpu_cg,
)


def _backend(n=8):
    import jax

    return TPUBackend(devices=jax.devices()[:n])


def _padded_frame(monkeypatch):
    """Force the real-TPU padded frame (kernels in interpret mode)."""
    import importlib

    tpu_mod = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    monkeypatch.setattr(tpu_mod, "_padded_for", lambda backend: True)


def test_fused_cg_matches_standard_device_loop():
    """Default mode, f64: identical iteration counts, residual history to
    tight rounding, solutions to rounding; the info dict records which
    body ran."""

    def run(fused):
        def driver(parts):
            A, b, xe, x0 = assemble_poisson(parts, (8, 8, 8))
            x, info = tpu_cg(A, b, x0=x0, tol=1e-9, maxiter=500, fused=fused)
            return gather_pvector(x), info

        return pa.prun(driver, _backend(), (2, 2, 2))

    xf, inf_f = run(True)
    xu, inf_u = run(False)
    assert inf_f["cg_body"] == "fused" and inf_u["cg_body"] == "standard"
    assert inf_f["converged"] and inf_u["converged"]
    assert inf_f["iterations"] == inf_u["iterations"]
    n = inf_u["iterations"] + 1
    np.testing.assert_allclose(
        np.asarray(inf_f["residuals"])[:n],
        np.asarray(inf_u["residuals"])[:n],
        rtol=1e-12,
    )
    np.testing.assert_allclose(np.asarray(xf), np.asarray(xu), atol=1e-10)


def test_fused_pcg_matches_standard_and_shares_gather():
    """Preconditioned fused loop: same trajectory as the standard PCG
    body (its r·z / r·r reductions ride ONE all_gather — collective
    count covered by the HLO test below)."""

    def run(fused):
        def driver(parts):
            A, b, xe, x0 = assemble_poisson(parts, (8, 8, 8))
            mv = jacobi_preconditioner(A)
            x, info = tpu_cg(
                A, b, x0=x0, tol=1e-9, maxiter=500, minv=mv, fused=fused
            )
            return gather_pvector(x), info

        return pa.prun(driver, _backend(), (2, 2, 2))

    xf, inf_f = run(True)
    xu, inf_u = run(False)
    assert inf_f["converged"] and inf_u["converged"]
    assert inf_f["iterations"] == inf_u["iterations"]
    np.testing.assert_allclose(np.asarray(xf), np.asarray(xu), atol=1e-8)


# ---------------------------------------------------------------------------
# strict-bits trajectory identity on the 4-part conformance fixture
# ---------------------------------------------------------------------------

# the 10-gid 4-part fixture (reference: test_interfaces.jl:177-207), each
# part's lids reordered owned-first (same ownership, same ghost sets, same
# neighbor graph — the block split requires owned-first local layouts)
LID_TO_GID = [
    [0, 1, 2, 4, 6, 7],
    [3, 4, 1, 9],
    [5, 6, 7, 4, 3, 9],
    [8, 9, 0, 2, 6],
]
LID_TO_PART = [
    [0, 0, 0, 1, 2, 2],
    [1, 1, 0, 3],
    [2, 2, 2, 1, 1, 3],
    [3, 3, 0, 0, 2],
]


def _fixture_spd_system(parts):
    """A symmetric positive-definite operator over the conformance
    partition: couplings only between MUTUALLY visible gid pairs (each
    owner holds the other's gid), so both triangle entries exist and the
    assembled matrix is exactly symmetric; a dominant diagonal makes it
    SPD."""
    owner = {}
    for p, (gids, ps) in enumerate(zip(LID_TO_GID, LID_TO_PART)):
        for g, q in zip(gids, ps):
            if q == p:
                owner[g] = p
    visible = [set(g) for g in LID_TO_GID]
    pairs = {
        (a, b)
        for a in range(10)
        for b in range(10)
        if a != b and b in visible[owner[a]] and a in visible[owner[b]]
    }

    def triplets(p):
        I, J, V = [], [], []
        for g, q in zip(LID_TO_GID[p], LID_TO_PART[p]):
            if q != p:
                continue
            I.append(g)
            J.append(g)
            V.append(40.0 + g)
            for b in sorted(visible[p]):
                if (g, b) in pairs:
                    I.append(g)
                    J.append(b)
                    V.append(-(1.0 + (g + b) % 3))
        return np.array(I), np.array(J), np.array(V, dtype=np.float64)

    partition = pa.map_parts(
        lambda p: pa.IndexSet(p, LID_TO_GID[p], LID_TO_PART[p]), parts
    )
    rows = pa.PRange(10, partition)
    I = pa.map_parts(lambda p: triplets(p)[0], parts)
    J = pa.map_parts(lambda p: triplets(p)[1], parts)
    V = pa.map_parts(lambda p: triplets(p)[2], parts)
    A = pa.PSparseMatrix.from_coo(I, J, V, rows, rows.copy(), ids="global")
    b = pa.PVector(
        pa.map_parts(
            lambda i: np.where(
                np.asarray(i.lid_to_part) == i.part,
                np.sin(1.0 + np.asarray(i.lid_to_gid, dtype=np.float64)),
                0.0,
            ),
            A.rows.partition,
        ),
        A.rows,
    )
    return A, b


@pytest.mark.parametrize("precond", [False, True], ids=["cg", "pcg"])
def test_strict_bits_fused_trajectory_identity(monkeypatch, precond):
    """Under strict-bits arithmetic the fused body must reproduce the
    unfused oracle's ITERATE SEQUENCE: same iteration count, identical
    residual-history bits, identical solution bits — on the asymmetric
    4-part conformance partition. With a preconditioner the two bodies
    were never bit-identical (the packed-carry body read the same: the
    fold computes ``mvv * r`` next to the add, the standard body
    materializes z first, and the product contracts differently), so
    that case pins them to a few ulps."""
    monkeypatch.setenv("PA_TPU_STRICT_BITS", "1")
    backend = _backend(4)

    def run(fused):
        def driver(parts):
            A, b = _fixture_spd_system(parts)
            mv = jacobi_preconditioner(A) if precond else None
            x, info = tpu_cg(
                A, b, tol=1e-12, maxiter=200, minv=mv, fused=fused
            )
            return gather_pvector(x), info

        return pa.prun(driver, backend, 4)

    xf, inf_f = run(True)
    xu, inf_u = run(False)
    assert inf_f["cg_body"] == "fused" and inf_u["cg_body"] == "standard"
    assert inf_f["converged"] and inf_u["converged"]
    assert inf_f["iterations"] == inf_u["iterations"]
    assert inf_f["iterations"] > 3  # a real trajectory, not a 1-step solve
    n = inf_u["iterations"] + 1
    same = (
        (lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-12))
        if precond
        else np.testing.assert_array_equal
    )
    same(np.asarray(inf_f["residuals"])[:n], np.asarray(inf_u["residuals"])[:n])
    same(np.asarray(xf), np.asarray(xu))


def test_strict_bits_default_resolves_to_standard_body(monkeypatch):
    """Strict-bits keeps the unfused body as the oracle by DEFAULT: the
    env resolution must not hand strict mode the fused form."""
    monkeypatch.setenv("PA_TPU_STRICT_BITS", "1")
    from partitionedarrays_jl_tpu.parallel.tpu import _fused_cg_enabled

    assert not _fused_cg_enabled()
    monkeypatch.delenv("PA_TPU_STRICT_BITS")
    assert _fused_cg_enabled()
    monkeypatch.setenv("PA_TPU_FUSED_CG", "0")
    assert not _fused_cg_enabled()


# ---------------------------------------------------------------------------
# HLO A/B: the fused body must not add collectives
# ---------------------------------------------------------------------------


# the shared analyzer (one definition for the whole test tree — this
# file used to carry a private regex copy; analysis.collective_counts
# keeps the identical raw-substring semantics, pinned by
# tests/test_static_analysis.py against a committed fixture)
from partitionedarrays_jl_tpu.analysis import collective_counts  # noqa: E402


def test_fused_body_no_extra_collectives():
    """Lower the fused and unfused compiled CG programs and count the
    collectives in the HLO: the fusion restructures vector sweeps only —
    per-kind collective counts must not grow (the same A/B that verified
    the in-graph health guard costs zero extra collectives)."""
    backend = _backend()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (6, 6, 6))
        return A, b

    A, b = pa.prun(driver, backend, (2, 2, 2))
    dA = device_matrix(A, backend)
    db = _b_on_cols_layout(b, dA)
    dx0 = DeviceVector.from_pvector(
        pa.PVector.full(0.0, A.cols), backend, dA.col_layout
    )
    from partitionedarrays_jl_tpu.parallel.tpu import _matrix_operands

    ops = _matrix_operands(dA)
    fused = make_cg_fn(dA, tol=1e-9, maxiter=100, fused=True)
    unfused = make_cg_fn(dA, tol=1e-9, maxiter=100, fused=False)
    cf = collective_counts(fused, db.data, dx0.data, db.data, ops)
    cu = collective_counts(unfused, db.data, dx0.data, db.data, ops)
    assert any(cu.values()), "unfused program shows no collectives at all"
    for kind in cu:
        assert cf[kind] <= cu[kind], (kind, cf, cu)


def test_fused_pcg_fewer_gathers_than_standard():
    """The preconditioned fused body's paired r·z / r·r reduction rides
    ONE all_gather where the standard body pays two — the fused PCG
    program must show strictly fewer gathers."""
    backend = _backend()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (6, 6, 6))
        return A, b

    A, b = pa.prun(driver, backend, (2, 2, 2))
    dA = device_matrix(A, backend)
    db = _b_on_cols_layout(b, dA)
    dx0 = DeviceVector.from_pvector(
        pa.PVector.full(0.0, A.cols), backend, dA.col_layout
    )
    from partitionedarrays_jl_tpu.parallel.tpu import _matrix_operands

    ops = _matrix_operands(dA)
    fused = make_cg_fn(dA, tol=1e-9, maxiter=100, precond=True, fused=True)
    unfused = make_cg_fn(dA, tol=1e-9, maxiter=100, precond=True, fused=False)
    cf = collective_counts(fused, db.data, dx0.data, db.data, ops)
    cu = collective_counts(unfused, db.data, dx0.data, db.data, ops)
    assert cf["all_gather"] < cu["all_gather"], (cf, cu)


# ---------------------------------------------------------------------------
# padded coded frame: the in-kernel direction fold (Pallas interpret)
# ---------------------------------------------------------------------------


def test_fused_padded_frame_kernel_fold_parity(monkeypatch):
    """Force the real-TPU padded frame on the CPU mesh: the fused CG
    then routes the direction fold through the Pallas kernel's pfold
    variant (interpret mode), and must agree with the standard body —
    same iterations, same solution to rounding."""
    _padded_frame(monkeypatch)
    backend = _backend()

    def run(fused):
        def driver(parts):
            # f32 like the real padded flagship frame: the f64 plan
            # legitimately fails the pfold VMEM gate (doubled windows) and
            # would silently fall back to the jnp fold
            A, b, xe, x0 = assemble_poisson(
                parts, (8, 8, 8), dtype=np.float32
            )
            dA = device_matrix(A, parts.backend)
            assert dA.padded and dA.dia_mode == "coded"
            assert dA.pallas_plan is not None
            from partitionedarrays_jl_tpu.ops.pallas_dia import pfold_vmem_ok

            # the kernel fold must actually be reachable for this plan —
            # otherwise this test silently degrades to the jnp fold
            assert pfold_vmem_ok(dA.pallas_plan)
            x, info = tpu_cg(A, b, x0=x0, tol=1e-5, maxiter=500, fused=fused)
            return gather_pvector(x), info

        return pa.prun(driver, backend, (2, 2, 2))

    xf, inf_f = run(True)
    xu, inf_u = run(False)
    assert inf_f["converged"] and inf_u["converged"]
    assert inf_f["iterations"] == inf_u["iterations"]
    np.testing.assert_allclose(
        np.asarray(xf), np.asarray(xu), atol=5e-4, rtol=1e-4
    )


def test_pcg_gmg_branch_rejects_explicit_fused():
    """The GMG-preconditioned device program compiles its own PCG body
    with no fused variant — an explicit fused flag there must raise, not
    silently run the same body twice under an A/B label."""
    backend = _backend()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8, 8))
        h = pa.gmg_hierarchy(parts, A, (8, 8, 8), coarse_threshold=30)
        from partitionedarrays_jl_tpu.models import pcg

        with pytest.raises(ValueError, match="no fused variant"):
            pcg(A, b, x0=x0, minv=h, tol=1e-8, fused=True)
        return True

    assert pa.prun(driver, backend, (2, 2, 2))


# ---------------------------------------------------------------------------
# the form of the fused carry: three (W,) frames updated where they lie
# ---------------------------------------------------------------------------


def _poisson_dA(backend, ns=(6, 6, 6), dtype=np.float64):
    def driver(parts):
        A, _b, _xe, _x0 = assemble_poisson(parts, ns, dtype=dtype)
        return A

    A = pa.prun(driver, backend, (2, 2, 2))
    return A, device_matrix(A, backend)


@pytest.mark.parametrize("form", ["cg", "pcg", "padded-kernel-fold"])
def test_fused_carry_is_three_frames(monkeypatch, form):
    """The lowered fused program's `while` carries x, r and the previous
    direction as three rank-1 (W,) float frames: no stacked (3, W) tensor
    among the carries, and no float `dynamic_update_slice` inside the loop
    whose operand is larger than one frame (the whole-buffer rewrites of
    a packed carry, which cost 85 % of an iteration on the chip)."""
    from partitionedarrays_jl_tpu.analysis import program_report

    dtype = np.float64
    if form == "padded-kernel-fold":
        _padded_frame(monkeypatch)
        dtype = np.float32
    backend = _backend()
    ns = (8, 8, 8) if form == "padded-kernel-fold" else (6, 6, 6)
    _A, dA = _poisson_dA(backend, ns, dtype)
    fn = make_cg_fn(
        dA, tol=1e-9, maxiter=100, precond=form == "pcg", fused=True
    )
    L = dA.col_layout
    z = np.zeros((L.P, L.W), dtype=dtype)
    rep = program_report.analyze(fn, z, z, z, fn.operands)
    # the Krylov loop is the one that holds the dots' all_gather (the
    # interpreted kernel brings grid loops of its own)
    loops = [
        w for w in rep.while_loops if "stablehlo.all_gather" in w.region_text
    ]
    assert len(loops) == 1, rep.summary()
    (loop,) = loops
    floats = [(d, dt) for d, dt in loop.carries if dt.startswith("f")]
    frame = L.W * np.dtype(dtype).itemsize
    nbytes = program_report._mlir_tensor_bytes
    # x, r, p_prev; the lowering also carries what the body closes over,
    # so the preconditioner's frame rides along as a fourth
    assert [d for d, _ in floats].count(str(L.W)) == 3 + (form == "pcg"), (
        loop.carries
    )
    assert f"3x{L.W}" not in [d for d, _ in floats], loop.carries
    for line in loop.region_text.splitlines():
        if "dynamic_update_slice" not in line:
            continue
        dims, dt = program_report._MLIR_TENSOR.findall(
            line.split(":", 1)[-1]
        )[0]
        if dt.startswith("f"):  # the interpreted kernel buffers its i8 codes
            assert nbytes(dims, dt) <= frame, line.strip()[:200]


def _pfold_frames(dA, r, pv, beta, mv=None):
    """Run `_spmv_body(pfold=True)` once over the mesh and return the
    host (P, W) frames of ``A p`` and ``p``."""
    import jax

    from partitionedarrays_jl_tpu.parallel.tpu import (
        _matrix_operands,
        _shard_ops,
        _spmv_body,
    )

    body = _spmv_body(dA, pfold=True)
    ops = _matrix_operands(dA)
    mesh = dA.backend.mesh(dA.row_layout.P)
    spec = dA.backend.parts_spec()
    specs = jax.tree.map(lambda _: spec, ops)
    precond = mv is not None

    @jax.jit
    def fn(r, pv, mv, m):
        def shard_fn(rs, ps, mvs, ms):
            q, p = body(
                rs[0], ps[0], beta, _shard_ops(jax, ms),
                mvs[0] if precond else None,
            )
            return q[None], p[None]

        return jax.shard_map(
            shard_fn, mesh=mesh, in_specs=(spec, spec, spec, specs),
            out_specs=(spec, spec), check_vma=False,
        )(r, pv, mv, m)

    q, p = fn(r, pv, r if mv is None else mv, ops)
    return np.asarray(q), np.asarray(p)


def _owned_random(L, dtype, seed):
    """A (P, W) frame that is random on each part's owned band and zero
    in every other slot (pads, ghost, trash): what the loop's r and
    p_prev are."""
    rng = np.random.default_rng(seed)
    f = np.zeros((L.P, L.W), dtype=dtype)
    for p, no in enumerate(L.noids):
        f[p, L.o0 : L.o0 + no] = rng.standard_normal(int(no))
    return f


@pytest.mark.parametrize("fold", ["jnp", "jnp-precond", "pallas"])
def test_body_pfold_frames_zero_off_owned_band(monkeypatch, fold):
    """`body_pfold`'s contract: ``p`` and ``A p`` are whole frames,
    exactly zero off each part's owned band. The fused body carries p to
    the next trip as it is and updates x and r on the owned slice only,
    which is legal because of it. The jnp fold on the asymmetric 4-part
    conformance fixture (generic exchange, parts of 3, 2, 3 and 2 owned
    rows); the Pallas fold in interpret mode on an uneven padded
    Poisson frame."""
    if fold == "pallas":
        _padded_frame(monkeypatch)
        backend = _backend()
        # 9 cells on the first axis: parts own 5x4x4 and 4x4x4 rows, so
        # the kernel's `e < no` mask has pads to clear
        _A, dA = _poisson_dA(backend, (9, 8, 8), np.float32)
        from partitionedarrays_jl_tpu.ops.pallas_dia import pfold_vmem_ok

        assert dA.padded and dA.dia_mode == "coded"
        assert dA.pallas_plan is not None and pfold_vmem_ok(dA.pallas_plan)
        dtype = np.float32
    else:
        backend = _backend(4)
        A, _b = pa.prun(_fixture_spd_system, backend, 4)
        dA = device_matrix(A, backend)
        dtype = np.float64
    L = dA.col_layout
    assert len(set(int(n) for n in L.noids)) > 1  # uneven parts
    r = _owned_random(L, dtype, 1)
    pv = _owned_random(L, dtype, 2)
    mv = _owned_random(L, dtype, 3) if fold == "jnp-precond" else None
    q, p = _pfold_frames(dA, r, pv, dtype(0.75), mv)
    assert q.shape == p.shape == (L.P, L.W)
    for part, no in enumerate(L.noids):
        off = np.ones(L.W, dtype=bool)
        off[L.o0 : L.o0 + int(no)] = False
        assert not p[part, off].any(), (fold, part)
        assert not q[part, off].any(), (fold, part)
        z = r[part] if mv is None else mv[part] * r[part]
        np.testing.assert_allclose(
            p[part, ~off], (z + dtype(0.75) * pv[part])[~off],
            rtol=1e-5, atol=1e-6,
        )
    assert q.any()


# ---------------------------------------------------------------------------
# the fold at 320^3's halo, and the counters that say which fold a solve ran
# ---------------------------------------------------------------------------


def _decoupled_dA(backend, ns, dtype, grid=(1, 1, 1)):
    """The benchmark's operator: Dirichlet rows decoupled, so its 28 row
    classes give four nibble code streams, as at 192^3 and 320^3."""

    def driver(parts):
        A, _b, _xe, _x0 = assemble_poisson(
            parts, ns, dtype=dtype, decoupled=True
        )
        return A

    A = pa.prun(driver, backend, grid)
    return device_matrix(A, backend)


def test_pfold_kernel_at_the_320_cubed_halo_matches_the_jnp_fold(monkeypatch):
    """A slab of 6 x 320 x 320 cells on the forced padded frame: its
    slowest offset is 320^3's 102,400, so the plan has 320^3's 800-row
    halo, four code streams and plan VMEM, and the fold gate's verdict
    there. One `_spmv_body(pfold=True)` application folds in the kernel
    (interpret mode), and its frames agree with the jnp fold's to
    rounding."""
    from partitionedarrays_jl_tpu.ops import pallas_dia
    from partitionedarrays_jl_tpu.parallel.tpu import _pfold_fits

    _padded_frame(monkeypatch)
    dA = _decoupled_dA(_backend(1), (6, 320, 320), np.float32)
    plan = dA.pallas_plan
    assert dA.dia_offsets[-1] == 320**2
    assert plan["halo_rows"] == 800 and plan["vmem"] == 7_938_048
    assert _pfold_fits(dA)  # the branch 320^3 takes on the chip
    L = dA.col_layout
    r = _owned_random(L, np.float32, 1)
    pv = _owned_random(L, np.float32, 2)
    q_kernel, p_kernel = _pfold_frames(dA, r, pv, np.float32(0.75))
    monkeypatch.setattr(
        pallas_dia, "pfold_vmem_ok", lambda plan, itemsize=4: False
    )
    assert not _pfold_fits(dA)
    q_jnp, p_jnp = _pfold_frames(dA, r, pv, np.float32(0.75))
    np.testing.assert_allclose(p_kernel, p_jnp, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(q_kernel, q_jnp, rtol=1e-5, atol=1e-5)
    assert np.abs(q_kernel).max() > 1.0


@pytest.mark.parametrize(
    "ns,grid", [((6, 320, 320), (1, 1, 1)), ((13, 200, 200), (2, 1, 1))],
    ids=["320-cubed-halo", "uneven-multi-block"],
)
def test_pfold_kernel_in_place_gives_the_bits_of_its_own_buffer(
    monkeypatch, ns, grid
):
    """The fold kernel, which writes p over p_prev, against the fold in
    XLA, whose p is a buffer of its own that the plain kernel then reads:
    ``(A p, p)`` bit for bit through `_spmv_body`. On 320^3's slab (an
    800-row halo, three blocks, a ragged tail) and on two parts of 7 and
    6 planes of 200 x 200 (a 320-row halo, two blocks, parts of uneven
    length). Interpret mode runs a DMA at its start and cannot show a
    race: the store ordering itself is checked on the chip."""
    from partitionedarrays_jl_tpu.ops import pallas_dia
    from partitionedarrays_jl_tpu.parallel.tpu import _pfold_fits

    _padded_frame(monkeypatch)
    dA = _decoupled_dA(_backend(int(np.prod(grid))), ns, np.float32, grid)
    plan = dA.pallas_plan
    assert plan["n_blocks"] >= 2 and _pfold_fits(dA)
    L = dA.col_layout
    r = _owned_random(L, np.float32, 1)
    pv = _owned_random(L, np.float32, 2)
    q_in, p_in = _pfold_frames(dA, r, pv, np.float32(0.75))
    monkeypatch.setattr(
        pallas_dia, "pfold_vmem_ok", lambda plan, itemsize=4: False
    )
    assert not _pfold_fits(dA)
    q_own, p_own = _pfold_frames(dA, r, pv, np.float32(0.75))
    np.testing.assert_array_equal(p_in, p_own)
    np.testing.assert_array_equal(q_in, q_own)
    assert np.abs(q_in).max() > 1.0


@pytest.mark.parametrize(
    "dtype,pfold,fused",
    [(np.float32, 1, True), (np.float64, 0, True), (np.float32, 1, False)],
    ids=["float32", "float64", "float32-standard-body"],
)
def test_coded_lowering_counters(monkeypatch, dtype, pfold, fused):
    """Staging a coded operator on the padded frame counts it once, with
    its plan; ``.pfold`` is the fold gate's verdict: 1 in float32, 0 in
    float64, whose doubled buffers fail the gate. ``.x_window_rows`` over
    ``.block_rows`` reads 1 where the fused body folds in the kernel,
    which fetches every block of its operands once, and the plain
    kernel's window (the block and the halo on both sides) where it does
    not: a plan the gate refuses, or the standard body
    (``PA_TPU_FUSED_CG=0``). Differences of the process's counters, which
    the other tests of the process bump too."""
    from partitionedarrays_jl_tpu import telemetry
    from partitionedarrays_jl_tpu.ops.pallas_dia import _win_rows

    _padded_frame(monkeypatch)
    if not fused:
        monkeypatch.setenv("PA_TPU_FUSED_CG", "0")
    before = telemetry.counters("lowering.coded")
    dA = _decoupled_dA(_backend(), (8, 8, 8), dtype, (2, 2, 2))
    after = telemetry.counters("lowering.coded")
    got = {k: after[k] - before.get(k, 0) for k in after}
    plan = dA.pallas_plan
    window = _win_rows(plan["block_rows"], plan["halo_rows"])
    assert window > plan["block_rows"]
    assert got == {
        "lowering.coded.operators": 1,
        "lowering.coded.block_rows": plan["block_rows"],
        "lowering.coded.halo_rows": plan["halo_rows"],
        "lowering.coded.x_window_rows": (
            plan["block_rows"] if pfold and fused else window
        ),
        "lowering.coded.plan_vmem_bytes": plan["vmem"],
        "lowering.coded.pfold": pfold,
    }
