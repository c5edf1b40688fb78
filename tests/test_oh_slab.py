"""The boundary (A_oh) rows of the compiled SpMV in their face-slab form
(`DeviceMatrix._detect_oh_slabs`, `_spmv_body._oh_slabs`).

On a box layout a direction's ghosts sit in the sender's slab scan and
the owned block is the box scan, so the boundary rows of a stencil apply
as static slices: no gather, scatter or sort, no index operand. Pinned
here:

* **Values.** ``A @ x`` through the compiled body against the host
  oracle on part grids whose faces are normal to the slow, the middle
  and the fast axis, Dirichlet and periodic, constant and variable
  coefficient, one vector and a block of four, 7 and 27 points; every
  case asserts through the ``lowering.oh.*`` counters that the slab
  form engaged.
* **Declines.** No box layout (tet elasticity), more than one box-shape
  variant, and strict-bits keep the form they had, strict-bits bit for
  bit with the oracle.
* **Structure.** The compiled fused CG program of a (2,2,1) grid holds
  no scatter or sort under `pa.spmv_local` and nothing indexed under
  its `oh` sub-scope; a one-part operator stages no boundary block.
"""
import itertools
import math
import re

import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import telemetry
from partitionedarrays_jl_tpu.models import assemble_poisson, gather_pvector
from partitionedarrays_jl_tpu.models.poisson_fdm import assemble_poisson_periodic
from partitionedarrays_jl_tpu.parallel.prange import (
    add_gids,
    cartesian_partition,
    no_ghost,
    p_cartesian_indices,
)
from partitionedarrays_jl_tpu.parallel.tpu import (
    DeviceMatrix,
    DeviceVector,
    TPUBackend,
    _matrix_operands,
    make_cg_fn,
    make_spmv_fn,
)


def _backend(grid):
    import jax

    return TPUBackend(devices=jax.devices()[: math.prod(grid)])


def _offsets(points, dim):
    if points == 27:
        return [o for o in itertools.product((-1, 0, 1), repeat=dim) if any(o)]
    return [
        tuple(s if d == a else 0 for d in range(dim))
        for a in range(dim) for s in (-1, 1)
    ]


def _stencil(parts, ns, points, periodic, varcoef, dtype):
    """A 7- or 27-point operator over a Cartesian grid, by COO. Dirichlet:
    boundary cells are identity rows and interior rows reach every
    neighbour; periodic: every row wraps. ``varcoef`` makes each entry a
    function of its (row, column) pair, so a slab coefficient that lands
    one position off is a wrong value, not the same one."""
    dim = len(ns)
    rows = cartesian_partition(parts, ns, no_ghost)
    cis = p_cartesian_indices(parts, ns, no_ghost)
    offsets = _offsets(points, dim)

    def coo(ci):
        coords = [g.ravel() for g in ci.grid()]
        gid = np.ravel_multi_index(coords, ns)
        inner = np.ones(len(gid), dtype=bool)
        if not periodic:
            for d in range(dim):
                inner &= (coords[d] > 0) & (coords[d] < ns[d] - 1)
        I, J, V = [gid], [gid], [np.where(inner, len(offsets) + 1.0, 1.0)]
        for off in offsets:
            nb = [(c[inner] + o) % n for c, o, n in zip(coords, off, ns)]
            j = np.ravel_multi_index(nb, ns)
            i = gid[inner]
            v = -1.0 - (0.5 * np.sin(0.7 * i + 1.3 * j) if varcoef else 0.0)
            I.append(i), J.append(j), V.append(v + 0.0 * i)
        return (np.concatenate(I), np.concatenate(J),
                np.concatenate(V).astype(dtype))

    trip = pa.map_parts(coo, cis)
    I, J, V = (pa.map_parts(lambda t, k=k: t[k], trip) for k in range(3))
    cols = add_gids(rows, J)
    return pa.PSparseMatrix.from_coo(I, J, V, rows, cols, ids="global")


def _vector(A, k, dtype):
    vals = pa.map_parts(
        lambda i: np.cos(
            0.37 * (k + 1) * np.asarray(i.lid_to_gid, dtype=np.float64) + k
        ).astype(dtype),
        A.cols.partition,
    )
    return pa.PVector(vals, A.cols)


def _lower(A, backend):
    """A fresh lowering of ``A`` and the ``lowering.oh.*`` counters it
    bumped."""
    telemetry.reset_counters("lowering.oh")
    dA = DeviceMatrix(A, backend)
    return dA, telemetry.counters("lowering.oh")


def _device_product(dA, A, xs, backend):
    """``A @ x`` for each of ``xs`` through ONE call of the compiled body:
    a ``(P, W)`` operand for one vector, ``(P, W, K)`` for several;
    gathered to global order like the oracle's."""
    import jax.numpy as jnp

    frames = [
        DeviceVector.from_pvector(x, backend, dA.col_layout).data for x in xs
    ]
    data = frames[0] if len(xs) == 1 else jnp.stack(frames, axis=-1)
    y = np.asarray(make_spmv_fn(dA)(data))
    y = y.reshape(y.shape[:2] + (len(xs),))
    out = []
    for k in range(len(xs)):
        yk = DeviceVector(
            jnp.asarray(y[..., k]), A.rows, dA.row_layout, backend
        ).to_pvector()
        out.append(gather_pvector(yk))
    return out


GRIDS = {  # part grid -> cells: unequal extents, so an axis mix-up shows
    (2, 2, 1): (8, 10, 6),
    (2, 2, 2): (8, 10, 12),
    (4, 1, 1): (16, 5, 6),
    (1, 2, 2): (4, 10, 12),
}
CASES = [
    (grid, 7, periodic, varcoef, K)
    for grid in GRIDS
    for periodic in (False, True)
    for varcoef in (False, True)
    for K in (1, 4)
] + [
    ((2, 2, 1), 27, periodic, True, K)
    for periodic in (False, True) for K in (1, 4)
]


def _case_id(c):
    grid, points, periodic, varcoef, K = c
    return "{}-{}pt-{}-{}-K{}".format(
        "x".join(map(str, grid)), points,
        "periodic" if periodic else "dirichlet",
        "varcoef" if varcoef else "const", K,
    )


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_slab_product_matches_the_host_oracle(case):
    grid, points, periodic, varcoef, K = case
    # float32 where the coefficients are constant, float64 where they vary
    dtype = np.float64 if varcoef else np.float32
    backend = _backend(grid)
    ns = GRIDS[grid]

    def build(parts):
        A = _stencil(parts, ns, points, periodic, varcoef, dtype)
        return A, [_vector(A, k, dtype) for k in range(K)]

    A, xs = pa.prun(build, backend, grid)
    dA, counted = _lower(A, backend)
    split = sum(1 for g in grid if g > 1)
    if points == 7:
        classes = 2 * split  # one a face
    elif not periodic:
        classes = 4 * 9 + 4 * 3  # nine shifts a face, three an edge
    else:
        # the unsplit fast axis wraps inside a part: each shift along it
        # splits into the run and the one wrapped plane
        classes = 4 * (9 + 6) + 4 * (3 + 2)
    assert dA.ohs_geo is not None and len(dA.ohs_geo) == classes
    assert dA.oh_vals is None and dA.ohb_bs is None
    assert counted == {
        "lowering.oh.nnz": dA.oh_nnz,
        "lowering.oh.slab_classes": classes,
        "lowering.oh.slab_entries": dA.ohs_vals.shape[1],
    }
    assert dA.oh_nnz <= dA.ohs_vals.shape[1] * len(dA.ohs_vals)
    assert [k for k in _matrix_operands(dA) if k.startswith("oh")] == ["ohs_v"]
    got = _device_product(dA, A, xs, backend)
    eps = np.finfo(dtype).eps
    for x, y in zip(xs, got):
        want = gather_pvector(A @ x)
        # a row sums 7 or 27 products of magnitude up to 1.5
        np.testing.assert_allclose(y, want, rtol=0, atol=64 * eps)


def test_lane_aligned_planes_take_the_same_values():
    """16^3 a part: a plane of the owned box is a whole number of 128-lane
    rows, which is the size class of the padded frame on the chip."""
    grid, ns = (2, 2, 1), (32, 32, 16)
    backend = _backend(grid)

    def build(parts):
        A = assemble_poisson(parts, ns, decoupled=True)[0]
        return A, _vector(A, 0, np.float64)

    A, x = pa.prun(build, backend, grid)
    dA, counted = _lower(A, backend)
    assert counted["lowering.oh.slab_classes"] == 4
    # Dirichlet rows ask for no ghosts: the faces are trimmed slabs
    assert {s.shape for s in dA.ohs_geo} == {(1, 16, 14), (16, 1, 14)}
    (y,) = _device_product(dA, A, [x], backend)
    np.testing.assert_allclose(y, gather_pvector(A @ x), rtol=0, atol=1e-13)


# -- the cases that decline keep the form they had -------------------------


def test_irregular_ghost_graph_keeps_the_node_block_form():
    from partitionedarrays_jl_tpu.models import assemble_elasticity_tet

    backend = _backend((4,))

    def build(parts):
        A, b, xh, x0 = assemble_elasticity_tet(parts, (4, 4, 4))
        return A, xh

    A, xh = pa.prun(build, backend, 4)
    dA, counted = _lower(A, backend)
    assert dA.col_layout.box_info is None and dA.oh_nnz > 0
    assert dA.ohs_geo is None and dA.ohb_bs == 3 and dA.oh_vals is None
    # the node-block form counts its stored entries and its padded blocks
    assert counted == {
        "lowering.oh.nnz": dA.oh_nnz,
        "lowering.oh.block_entries": sum(int(v.size) for v in dA.ohb_vals),
    }
    (y,) = _device_product(dA, A, [xh], backend)
    np.testing.assert_allclose(
        y, gather_pvector(A @ xh), rtol=1e-10, atol=1e-10
    )


def test_unequal_boxes_keep_the_ell_form():
    grid, ns = (2, 2, 2), (9, 7, 8)
    backend = _backend(grid)

    def build(parts):
        A, b, xe, x0 = assemble_poisson(parts, ns)
        return A, xe

    A, xe = pa.prun(build, backend, grid)
    dA, counted = _lower(A, backend)
    assert len(dA.col_layout.box_info.box_shapes) > 1
    assert dA.ohs_geo is None and dA.oh_vals is not None
    assert counted == {
        "lowering.oh.nnz": dA.oh_nnz,
        "lowering.oh.ell_entries": dA.oh_vals.size,
    }
    assert sorted(k for k in _matrix_operands(dA) if k.startswith("oh")) == [
        "oh_c", "oh_r", "oh_v",
    ]
    (y,) = _device_product(dA, A, [xe], backend)
    np.testing.assert_allclose(y, gather_pvector(A @ xe), rtol=0, atol=1e-13)


def test_strict_bits_keeps_the_ell_fold_bit_for_bit(monkeypatch):
    """The ELL fold's left-to-right order is the host CSR kernel's: the
    slab form adds a row's ghost terms one class after the other, equal
    only to rounding, so strict-bits must not take it."""
    monkeypatch.setenv("PA_TPU_STRICT_BITS", "1")
    grid, ns = (2, 2, 2), (6, 6, 6)
    backend = _backend(grid)

    def build(parts):
        A, b, xe, x0 = assemble_poisson(parts, ns)
        return A, xe

    A, xe = pa.prun(build, backend, grid)
    dA, counted = _lower(A, backend)
    assert dA.ohs_geo is None and dA.oh_vals is not None
    assert "lowering.oh.slab_classes" not in counted
    (y,) = _device_product(dA, A, [xe], backend)
    np.testing.assert_array_equal(y, gather_pvector(A @ xe))


# -- structure -------------------------------------------------------------

_INDEXED = ("gather", "scatter", "sort")


def _indexed_ops_under_spmv(text):
    """``(opcode, op_name from pa.spmv_local on)`` of every gather,
    scatter and sort of a compiled program's text whose `op_name` lies
    under `pa.spmv_local`."""
    found = []
    for line in text.splitlines():
        m = re.search(
            r"= \S+ ([a-z\-]+)\(.*op_name=\"[^\"]*(pa\.spmv_local[^\"]*)\"", line
        )
        if m and m.group(1) in _INDEXED:
            found.append((m.group(1), m.group(2)))
    return found


def _compiled_cg_text(A, backend):
    dA = DeviceMatrix(A, backend)
    fn = make_cg_fn(dA, 1e-8, 50)
    assert fn.fused
    L = dA.col_plan.layout
    z = np.zeros((L.P, L.W), dtype=A.dtype)
    return dA, fn.jit_fn.lower(z, z, z, fn.operands).compile().as_text()


@pytest.mark.parametrize("periodic", [False, True], ids=["dirichlet", "periodic"])
def test_fused_cg_program_indexes_nothing_for_the_boundary_rows(periodic):
    """16^3 a part on (2,2,1). What the ELL form compiled to here was a
    gather a column, a scatter-add and its sort. Static slices compile to
    slices and dynamic-update-slices with constant offsets. (The gathers
    that stay belong to the XLA form of the coded A_oo product, which
    decodes its codebook with `jnp.take`; the chip runs the Mosaic kernel
    in its place.)"""
    grid, ns = (2, 2, 1), (32, 32, 16)
    backend = _backend(grid)

    def build(parts):
        if periodic:
            return assemble_poisson_periodic(parts, ns)[0]
        return assemble_poisson(parts, ns, decoupled=True)[0]

    A = pa.prun(build, backend, grid)
    dA, text = _compiled_cg_text(A, backend)
    assert dA.ohs_geo is not None and "pa.spmv_local/oh/" in text
    found = _indexed_ops_under_spmv(text)
    assert [f for f in found if f[0] != "gather"] == []
    assert [f for f in found if "/oh" in f[1] or "take" not in f[1]] == []


def test_the_ell_form_is_what_the_structure_test_would_catch(monkeypatch):
    """The same program with the slab form declined: the indexed ops the
    test above looks for are there, so it looks in the right place."""
    monkeypatch.setattr(DeviceMatrix, "OH_SLAB_MAX_CLASSES", 0)
    grid, ns = (2, 2, 1), (32, 32, 16)
    backend = _backend(grid)
    A = pa.prun(
        lambda parts: assemble_poisson(parts, ns, decoupled=True)[0],
        backend, grid,
    )
    dA, text = _compiled_cg_text(A, backend)
    assert dA.ohs_geo is None and dA.oh_vals is not None
    found = _indexed_ops_under_spmv(text)
    assert any(op == "scatter" for op, _ in found)
    assert any(op == "gather" and "take" not in name for op, name in found)


def test_one_part_stages_no_boundary_block():
    backend = _backend((1,))
    A = pa.prun(
        lambda parts: assemble_poisson(parts, (8, 8, 8), decoupled=True)[0],
        backend, (1, 1, 1),
    )
    dA, counted = _lower(A, backend)
    assert dA.oh_nnz == 0 and counted == {}
    assert dA.oh_vals is None and dA.ohs_geo is None and dA.ohb_bs is None
    assert [k for k in _matrix_operands(dA) if k.startswith("oh")] == []
