"""Extended-box halo exchange (parallel/tpu_box.py): slice-based
pack/unpack for Cartesian partitions.

Reference anchor: the Exchanger data path these plans lower
(/root/reference/src/Interfaces.jl:846-889) and the FDM ghost layout
(/root/reference/test/test_fdm.jl:82-100). The box plan must be value-
equivalent to both the generic gather plan and the host oracle on every
Cartesian workload, and must DECLINE (fall back) on anything without the
uniform-box structure."""
import os

import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu.parallel.tpu import (
    DeviceVector,
    TPUBackend,
    device_exchange_plan,
    make_exchange_fn,
)
from partitionedarrays_jl_tpu.parallel.tpu_box import (
    BoxExchangePlan,
    analyze_box_structure,
)


def _ramp(rows):
    """Deterministic per-part values: gid-derived, so any slot shuffle
    that misroutes a single element changes some compared value."""
    vals = pa.map_parts(
        lambda i: np.asarray(i.lid_to_gid, dtype=np.float64) * 2.0
        + 1.0
        + 0.001 * i.part,
        rows.partition,
    )
    return pa.PVector(vals, rows)


def _exchange_device(parts, rows, combine="set"):
    v = _ramp(rows)
    vh = v.copy()
    if combine == "set":
        vh.exchange()
    else:
        vh.assemble()
    dv = DeviceVector.from_pvector(v, parts.backend)
    fn = make_exchange_fn(rows, parts.backend, combine=combine)
    out = DeviceVector(
        fn(dv.data), rows, dv.layout, parts.backend
    ).to_pvector()
    for a, b in zip(out.values.part_values(), vh.values.part_values()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-14)
    return True


@pytest.mark.parametrize(
    "ns,grid",
    [
        ((8, 8, 8), (2, 2, 2)),
        ((9, 7, 8), (2, 2, 2)),  # uneven cells, equal part boxes not req'd
        ((12, 12), (2, 4)),
        ((16,), (4,)),
    ],
)
def test_with_ghost_detection_and_parity(ns, grid):
    def driver(parts):
        rows = pa.prange(parts, ns, pa.with_ghost)
        info = analyze_box_structure(rows)
        # round-4: equal AND unequal Cartesian splits take the fast path
        # (unequal boxes become pack-slice variants switched per shard)
        sets = rows.partition.part_values()
        shapes = {i.box_shape for i in sets}
        assert info is not None, (ns, grid)
        assert len(info.box_shapes) == len(shapes)
        plan = device_exchange_plan(rows, False)
        assert isinstance(plan, BoxExchangePlan)
        assert _exchange_device(parts, rows)
        assert _exchange_device(parts, rows, combine="add")
        return True

    assert pa.prun(driver, pa.tpu, grid)


def test_periodic_detection_and_parity():
    def driver(parts):
        rows = pa.prange(
            parts, (8, 8), pa.with_ghost, periodic=(True, True)
        )
        assert analyze_box_structure(rows) is not None
        assert _exchange_device(parts, rows)
        assert _exchange_device(parts, rows, combine="add")
        return True

    assert pa.prun(driver, pa.tpu, (2, 2))


def test_stencil_discovery_cols_detection():
    """The assemble_poisson cols PRange (add_gids ghost discovery with
    Dirichlet-trimmed boundary faces) must still detect: the slab design
    packs bounding slabs and masks orphan slots."""

    def driver(parts):
        A, b, xe, x0 = pa.assemble_poisson(parts, (8, 8, 8))
        info = analyze_box_structure(A.cols)
        assert info is not None
        # trimmed faces -> orphan slots exist, and the mask knows them
        assert not info.seg_mask.all()
        assert _exchange_device(parts, A.cols)
        assert _exchange_device(parts, A.cols, combine="add")
        return True

    assert pa.prun(driver, pa.tpu, (2, 2, 2))


def test_unequal_boxes_take_variant_fast_path():
    """(7, 8) cells over (2, 2) parts -> box shapes (3, 4) and (4, 4):
    round-4 directive 6 — unequal splits no longer fall back; the plan
    carries per-shard pack-slice VARIANTS (lax.switch in the body) and
    must match the host oracle in both directions."""

    def driver(parts):
        rows = pa.prange(parts, (7, 8), pa.with_ghost)
        info = analyze_box_structure(rows)
        assert info is not None and len(info.box_shapes) == 2, info
        plan = device_exchange_plan(rows, False)
        assert isinstance(plan, BoxExchangePlan)
        assert _exchange_device(parts, rows)
        assert _exchange_device(parts, rows, combine="add")
        return True

    assert pa.prun(driver, pa.tpu, (2, 2))


@pytest.mark.parametrize(
    "ns,grid",
    [
        ((7, 9, 11), (2, 2, 2)),  # all dims unequal: 8 shape variants
        ((31,), (4,)),
        ((13, 8), (3, 2)),
    ],
)
def test_unequal_boxes_variant_parity(ns, grid):
    """Unequal-split parity sweep: forward and reverse exchanges through
    the variant fast path must match the host oracle exactly."""

    def driver(parts):
        rows = pa.prange(parts, ns, pa.with_ghost)
        assert isinstance(
            device_exchange_plan(rows, False), BoxExchangePlan
        )
        assert _exchange_device(parts, rows)
        assert _exchange_device(parts, rows, combine="add")
        return True

    assert pa.prun(driver, pa.tpu, grid)


def test_irregular_partition_falls_back():
    """Non-Cartesian index sets have no box metadata at all."""

    def driver(parts):
        rows = pa.uniform_partition(parts, 64)
        gids = pa.map_parts(
            lambda i: (np.asarray(i.oid_to_gid[:1]) + 17) % 64,
            rows.partition,
        )
        rows = pa.add_gids(rows, gids)
        assert analyze_box_structure(rows) is None
        assert _exchange_device(parts, rows)
        return True

    assert pa.prun(driver, pa.tpu, 4)


def test_cg_and_spmv_parity_through_box_plan():
    """End-to-end: the compiled CG (whose SpMV body embeds the box
    exchange) matches the sequential oracle's iterations and solution."""

    def driver(parts):
        A, b, xe, x0 = pa.assemble_poisson(parts, (8, 8, 8))
        plan = device_exchange_plan(A.cols, False)
        assert isinstance(plan, BoxExchangePlan)
        x, info = pa.cg(A, b, x0=x0, tol=1e-10, maxiter=400)
        err = np.abs(pa.gather_pvector(x) - pa.gather_pvector(xe)).max()
        assert info["converged"]
        return float(err), info["iterations"]

    err_t, it_t = pa.prun(driver, pa.tpu, (2, 2, 2))

    def seq_driver(parts):
        A, b, xe, x0 = pa.assemble_poisson(parts, (8, 8, 8))
        x, info = pa.cg(A, b, x0=x0, tol=1e-10, maxiter=400)
        return info["iterations"]

    it_s = pa.prun(seq_driver, pa.sequential, (2, 2, 2))
    assert err_t < 1e-6
    assert it_t == it_s


def test_env_flag_disables_box_plan():
    def driver(parts):
        rows = pa.prange(parts, (8, 8), pa.with_ghost)
        os.environ["PA_TPU_BOX"] = "0"
        try:
            plan = device_exchange_plan(rows, False)
            assert not isinstance(plan, BoxExchangePlan)
            assert _exchange_device(parts, rows)
        finally:
            del os.environ["PA_TPU_BOX"]
        plan = device_exchange_plan(rows, False)
        assert isinstance(plan, BoxExchangePlan)
        return True

    assert pa.prun(driver, pa.tpu, (2, 2))


def _assert_box_and_generic_agree(rows, backend, combine, K, padded=False):
    """Run the box body and the generic body of one exchange over the
    SAME layout (the compact frame, or the chip's padded frame) and
    compare the device arrays slot for slot."""
    import jax

    from partitionedarrays_jl_tpu.parallel.tpu import (
        DeviceExchangePlan, _box_dummy_operands, _shard_exchange, _stage,
    )

    plan_box = device_exchange_plan(rows, padded)
    assert isinstance(plan_box, BoxExchangePlan)
    layout = plan_box.layout
    assert layout.padded == padded
    P = layout.P
    exchanger = rows.exchanger
    if combine == "add":
        plan_box = plan_box.reverse()
        exchanger = exchanger.reverse()
    plan_gen = DeviceExchangePlan(exchanger, layout)
    # integer-valued columns: the two 'add' bodies accumulate in
    # different orders, which only exact sums make comparable bitwise
    cols = []
    for k in range(K):
        vals = pa.map_parts(
            lambda i, k=k: (
                np.asarray(i.lid_to_gid, dtype=np.float64) * 2.0
                + 1.0 + i.part
            ) * (k + 1),
            rows.partition,
        )
        cols.append(np.asarray(DeviceVector.from_pvector(
            pa.PVector(vals, rows), backend, layout
        ).data))
    x = _stage(backend, cols[0] if K == 1 else np.stack(cols, -1), P)
    mesh = backend.mesh(P)
    spec = backend.parts_spec()

    def run(plan, si, sm, ri):
        body = _shard_exchange(plan, combine)

        @jax.jit
        def fn(x, a, b, c):
            return jax.shard_map(
                lambda xs, as_, bs, cs: body(
                    xs[0], as_[0], bs[0], cs[0]
                )[None],
                mesh=mesh,
                in_specs=(spec,) * 4,
                out_specs=spec,
                check_vma=False,
            )(x, a, b, c)

        return np.asarray(fn(x, si, sm, ri))

    out_box = run(
        plan_box,
        *_box_dummy_operands(
            backend, P,
            plan_box.info.seg_mask if combine == "add" else None,
            variants=plan_box.info.variants,
        ),
    )
    out_gen = run(
        plan_gen,
        _stage(backend, plan_gen.snd_idx, P),
        _stage(backend, plan_gen.snd_mask, P),
        _stage(backend, plan_gen.rcv_idx, P),
    )
    # orphan slots may differ (box ships whole slabs); every REAL
    # slot — owned + mapped ghosts — must agree exactly
    o0 = layout.o0
    x_in = np.asarray(x)
    changed = False
    for p, iset in enumerate(rows.partition.part_values()):
        own = slice(o0, o0 + iset.num_oids)
        np.testing.assert_array_equal(out_box[p, own], out_gen[p, own])
        hs = layout.hid_slots[p]
        np.testing.assert_array_equal(out_box[p, hs], out_gen[p, hs])
        changed |= not np.array_equal(out_gen[p, own], x_in[p, own])
        changed |= not np.array_equal(out_gen[p, hs], x_in[p, hs])
    assert changed  # the exchange really moved something


@pytest.mark.parametrize("K", [1, 4])
@pytest.mark.parametrize("combine", ["set", "add"])
@pytest.mark.parametrize(
    "grid", [(2, 2, 1), (4, 1, 1), (1, 2, 2), (1, 1, 4), (2, 2, 2)]
)
def test_box_and_generic_plans_agree_slotwise(grid, combine, K):
    """The two plans over the SAME layout must produce identical device
    arrays (not just identical PVectors): exchange is used inside
    compiled solvers that read raw slots. Both directions ('set'
    owner->ghost, 'add' ghost->owner), a single vector and a K-column
    block, on part grids that cut each axis alone and together."""

    def driver(parts):
        rows = pa.prange(parts, (8, 8, 8), pa.with_ghost)
        _assert_box_and_generic_agree(rows, parts.backend, combine, K)
        return True

    assert pa.prun(driver, pa.tpu, grid)


# -- the forward pack's addressing forms (`tpu_box.face_form`) -------------
#
# case -> (part grid, cells, stencil points, periodic,
#          expected exchange.box.{dirs, flat_dirs, lane_dirs, boxview_dirs})
# at sizes where each form ENGAGES: 16^3 a part makes a plane two whole
# 128-lane rows, the size class of the chip's 192^3.
FORM_CASES = {
    # Dirichlet-trimmed faces (1,16,14) and (16,1,14): the shape class of
    # poisson7_192_x4
    "lane-aligned-2x2x1": ((2, 2, 1), (32, 32, 16), 7, False, (4, 2, 2, 0)),
    # a grid that cuts the fastest axis alone: faces normal to it
    "fast-axis-1x1x4": ((1, 1, 4), (16, 16, 64), 7, False, (2, 0, 0, 2)),
    # every axis cut: one pair of faces in each form
    "all-axes-2x2x2": ((2, 2, 2), (32, 32, 32), 7, False, (6, 2, 2, 2)),
    # 12^3 a part: a plane (144) is no whole lane rows, so the middle
    # axis falls back by itself (the coarse GMG levels on the chip)
    "unaligned-2x2x1": ((2, 2, 1), (24, 24, 12), 7, False, (4, 2, 0, 2)),
    # edges along the fastest axis are lane rows of the plane they lie in
    "27pt-2x2x1": ((2, 2, 1), (32, 32, 16), 27, False, (8, 2, 6, 0)),
    # the other edges and the corners pass the fill: box view
    "27pt-2x2x2": ((2, 2, 2), (32, 32, 32), 27, False, (26, 2, 6, 18)),
    # both faces of an axis go to the one neighbour, untrimmed
    "periodic-2x2x1": ((2, 2, 1), (32, 32, 16), 7, True, (4, 2, 2, 0)),
    # V = 2 box shapes, (16,16,16) and (17,16,16): a form a direction AND
    # variant (a variant that never sends in a direction packs a
    # one-element box view)
    "unequal-2x2x1": ((2, 2, 1), (33, 32, 16), 7, False, (4, 2, 4, 2)),
}


@pytest.mark.parametrize("padded", [False, True], ids=["compact", "padded"])
@pytest.mark.parametrize("K", [1, 4])
@pytest.mark.parametrize("case", list(FORM_CASES))
def test_each_pack_form_agrees_with_the_generic_plan_slotwise(
    case, K, padded
):
    """The forward body packs each face as a run of the flat frame, as a
    block of lane rows or from the box's own view (`face_form`); the
    operator's `exchange.box.*` counters say which engaged, and the
    frame after the exchange is the generic body's, slot for slot, on
    the compact frame and on the chip's padded one."""
    from test_oh_slab import _backend, _stencil

    from partitionedarrays_jl_tpu import telemetry
    from partitionedarrays_jl_tpu.parallel.tpu import DeviceMatrix

    grid, ns, points, periodic, want = FORM_CASES[case]
    backend = _backend(grid)
    A = pa.prun(
        lambda parts: _stencil(parts, ns, points, periodic, False, np.float64),
        backend, grid,
    )
    telemetry.reset_counters("exchange")
    dA = DeviceMatrix(A, backend)
    assert isinstance(dA.col_plan, BoxExchangePlan)
    assert telemetry.counters("exchange") == dict(zip(
        ("exchange.box.dirs", "exchange.box.flat_dirs",
         "exchange.box.lane_dirs", "exchange.box.boxview_dirs"), want,
    ))
    assert len(dA.col_plan.info.dirs) == want[0]
    _assert_box_and_generic_agree(A.cols, backend, "set", K, padded)


# -- structure of the compiled forward exchange ----------------------------


def _ops_under_halo(text):
    """``(opcode, result elements, op_name from pa.halo_exchange on,
    in the loop body)`` of every op of a compiled program's text whose
    `op_name` lies under `pa.halo_exchange` (a fusion counts as its
    root's result and name)."""
    import math
    import re

    found = []
    for line in text.splitlines():
        m = re.search(
            r"= \w+\[([\d,]*)\]\S* ([a-z\-]+)\(.*"
            r"op_name=\"([^\"]*)(pa\.halo_exchange[^\"]*)\"",
            line,
        )
        if m:
            dims = [int(n) for n in m.group(1).split(",") if n]
            found.append((
                m.group(2), math.prod(dims), m.group(4),
                "while/body" in m.group(3),
            ))
    return found


def test_fused_cg_program_packs_no_whole_block_for_the_exchange():
    """16^3 a part on (2,2,1), the shape class of `poisson7_192_x4`. The
    pack used to slice the owned block out of the frame and view it in
    the box's shape once a direction (four `slice` and four `reshape` of
    ``no`` elements an exchange, on the chip a copy and a relayout of the
    whole block each). The flat and lane-row forms read the faces where
    they lie: nothing under `pa.halo_exchange` but the stores, which
    update the frame in place, is as large as the owned block; one
    `collective-permute` a direction; nothing indexed."""
    from test_oh_slab import _backend, _compiled_cg_text

    from partitionedarrays_jl_tpu.models import assemble_poisson

    grid, ns = (2, 2, 1), (32, 32, 16)
    backend = _backend(grid)
    A = pa.prun(
        lambda parts: assemble_poisson(parts, ns, decoupled=True)[0],
        backend, grid,
    )
    dA, text = _compiled_cg_text(A, backend)
    info = dA.col_plan.info
    assert dA.col_plan.pack_forms() == ["flat", "lane", "lane", "flat"]
    no = dA.col_layout.no_max
    ops = _ops_under_halo(text)
    assert {o[2].split("/")[1] for o in ops} >= {"ex.pack", "ex.unpack"}
    assert [o for o in ops if o[0] in ("gather", "scatter", "sort")] == []
    stores = [o for o in ops if "/ex.unpack" in o[2]]
    assert {o[0] for o in stores} <= {"dynamic-update-slice", "fusion"}
    assert [o for o in ops if o not in stores and o[1] >= no] == []
    # the loop body's exchange, and the one of the initial residual
    for in_loop in (True, False):
        assert sum(
            o[0].startswith("collective-permute") and o[3] == in_loop
            for o in ops
        ) == len(info.dirs) == 4
