"""The stored-coefficient stencil as a supported deployment (PR 38):
`pa.assemble_diffusion_fv` against the benchmark's plain reference
(`benchmark/builders/varcoef7.py`, which imports nothing of the program)
entry by entry, the float32 path, the operator's symmetries, the lowering it
takes on a device (streamed diagonals, where a constant coefficient is
coded), `pa.cg` through that lowering against the reference CG, the
comparison that decides `correct`, and the new scopes and counters with the
Mosaic kernel interpreted. Small sizes, CPU devices.
"""
import functools
import importlib
import itertools
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import telemetry
from partitionedarrays_jl_tpu.ops.pallas_dia import LANES

T = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
ref = importlib.import_module("benchmark.builders.varcoef7")

MIX = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "cg_closed.json")))
CFG12 = json.load(
    open(os.path.join(ROOT, "benchmark", "tests", "configs", "varcoef7_12.json"))
)
N = 12
NS = (N, N, N)
BETA = ref.beta_of(CFG12["beta"])
GRIDS = {"1part": (1, 1, 1), "4parts": (2, 2, 1)}


def assemble(grid, beta=BETA, **kw):
    return pa.prun(
        lambda p: pa.assemble_diffusion_fv(p, NS, beta, **kw), pa.sequential, grid
    )


def as_dense(A) -> np.ndarray:
    """A partitioned matrix gathered by global id; an entry stored twice
    would show as a sum, one missing as a zero."""
    n = A.rows.ngids
    out = np.zeros((n, n), dtype=np.float64)
    for ri, ci, m in zip(
        A.rows.partition.part_values(), A.cols.partition.part_values(),
        A.values.part_values(),
    ):
        np.add.at(
            out,
            (np.asarray(ri.lid_to_gid)[m.row_of_nz()], np.asarray(ci.lid_to_gid)[m.indices]),
            m.data,
        )
    return out


@functools.lru_cache(maxsize=None)
def reference_dense() -> np.ndarray:
    """The reference operator as a matrix: its action on every unit vector."""
    faces = ref.face_coefficients(NS, BETA)
    cols = []
    for g in range(N**3):
        e = np.zeros(N**3)
        e[g] = 1.0
        cols.append(ref.apply_reference(faces, e.reshape(NS)).ravel())
    return np.array(cols).T


# -- (a) two statements of one operator ---------------------------------------


@pytest.mark.parametrize("dtype", [np.float64, np.float32], ids=["f64", "f32"])
@pytest.mark.parametrize("grid", GRIDS.values(), ids=GRIDS.keys())
def test_the_assembled_matrix_is_the_reference_entry_by_entry(grid, dtype):
    """float64: 1e-12 of the largest entry (both sum six face coefficients
    in float64, in other orders). float32: the float64 entries rounded once,
    bit for bit, so against the reference half an ulp of float32."""
    A = assemble(grid, dtype=dtype)
    assert A.dtype == dtype
    got, want = as_dense(A), reference_dense()
    nnz = sum(m.nnz for m in A.values.part_values())
    assert nnz == ref.count_nnz(NS) == CFG12["nnz"] == np.count_nonzero(want)
    assert np.array_equal(got != 0, want != 0)
    scale = np.abs(want).max()
    assert scale > N * N  # n^2 beta, beta up to 10
    if dtype is np.float64:
        assert np.abs(got - want).max() <= 1e-12 * scale
    else:
        A64 = assemble(grid)
        for m32, m64 in zip(A.values.part_values(), A64.values.part_values()):
            assert m32.data.dtype == np.float32
            assert np.array_equal(m32.indices, m64.indices)
            assert np.array_equal(m32.data, m64.data.astype(np.float32))
        assert np.abs(got - want).max() <= 2.0**-24 * scale


@pytest.mark.parametrize("grid", GRIDS.values(), ids=GRIDS.keys())
def test_symmetric_with_wall_faces_counted_twice(grid):
    """``A`` is symmetric to the bit (both cells of a face read one float64
    coefficient), a row sums to twice its wall faces' coefficients (the
    ghost value -u_c), and the corner cell has three of them."""
    got = as_dense(assemble(grid))
    assert np.array_equal(got, got.T)
    centres = [(np.arange(N) + 0.5) / N] * 3
    wall = np.zeros(NS)
    for axis in range(3):
        for face, cell in ((0.0, 0), (1.0, N - 1)):
            at = [c.reshape([-1 if k == d else 1 for k in range(3)])
                  for d, c in enumerate(centres)]
            at[axis] = np.full([1, 1, 1], face)
            sl = [slice(None)] * 3
            sl[axis] = slice(cell, cell + 1)
            wall[tuple(sl)] += 2.0 * N * N * BETA(*at)
    assert np.abs(got.sum(axis=1) - wall.ravel()).max() <= 1e-10 * got.max()
    h = 0.5 / N
    corner = 2.0 * N * N * (BETA(0.0, h, h) + BETA(h, 0.0, h) + BETA(h, h, 0.0))
    assert got[0].sum() == pytest.approx(corner, rel=1e-13)
    assert (np.diag(got) > 0).all() and np.linalg.eigvalsh(got).min() > 0


@pytest.mark.parametrize(
    "perm", list(itertools.permutations(range(3))), ids=lambda p: "".join(map(str, p))
)
def test_the_operator_maps_onto_itself_under_the_cubes_symmetries(perm):
    """`beta` depends on the distance from the centre alone: the 8
    reflections of each of the 6 axis permutations, 48 images, and the sign.
    To the rounding of the face centres' coordinates."""
    faces = ref.face_coefficients(NS, BETA)
    u = ref.base_field(NS, 7, 3, 4)
    y = ref.apply_reference(faces, u)
    syms = [s for s in ref.symmetries(NS, (1, 1, 1)) if s[0] == perm]
    assert len(syms) == 16 and len(ref.symmetries(NS, (1, 1, 1))) == 96
    for sym in syms:
        got = ref.apply_reference(faces, ref.image(u, sym))
        assert np.abs(got - ref.image(y, sym)).max() <= 1e-11 * np.abs(y).max()
    # and under none of them where the coefficient has a direction
    skew = lambda x, y_, z: 1.0 + x + 0.0 * (y_ + z)
    f2 = ref.face_coefficients(NS, skew)
    flip = ((0, 1, 2), (True, False, False), 1.0)
    assert np.abs(
        ref.apply_reference(f2, ref.image(u, flip))
        - ref.image(ref.apply_reference(f2, u), flip)
    ).max() > 1e-3 * np.abs(y).max()


# -- (b) the lowering ------------------------------------------------------------


@pytest.mark.parametrize(
    "beta,mode",
    [(BETA, "stream"), (lambda x, y, z: 2.5 + 0.0 * (x + y + z), "coded")],
    ids=["tanh", "constant"],
)
def test_a_varying_coefficient_streams_and_a_constant_one_is_coded(beta, mode):
    backend = pa.TPUBackend(devices=jax.devices()[:1])
    A = pa.prun(
        lambda p: pa.assemble_diffusion_fv(p, NS, beta, dtype=np.float32),
        backend, (1, 1, 1),
    )
    dA = T.device_matrix(A, backend)
    assert dA.dia_mode == mode
    assert dA.dia_offsets == (-N * N, -N, -1, 0, 1, N, N * N)
    distinct = max(
        len(np.unique(d[d != 0])) for d in as_diagonals(as_dense(A), dA.dia_offsets)
    )
    assert (distinct > T.DeviceMatrix.CODE_MAX_VALUES) == (mode == "stream")


def as_diagonals(dense, offsets):
    return [np.diagonal(dense, o) for o in offsets]


# -- (c), (d): the cell's path at 12^3 on CPU devices -------------------------------


@functools.lru_cache(maxsize=None)
def built(which: str, kernel: bool = False) -> dict:
    """The rehearsal configuration on a part grid, one part a CPU device:
    built, its pool solved, its operator staged, the counters read. With
    ``kernel`` the streamed diagonals go through the Mosaic kernel,
    interpreted, as they do on a chip."""
    grid = GRIDS[which]
    P = int(np.prod(grid))
    cfg = dict(CFG12, part_grid=list(grid))
    backend = pa.TPUBackend(devices=jax.devices()[:P])
    out = {"grid": grid, "P": P}
    with pytest.MonkeyPatch.context() as mp:
        if kernel:
            mp.setattr(T, "_stream_kernel_for", lambda backend: True)
        telemetry.reset_counters("lowering.stream")

        def body(p):
            s = ref.build(pa, p, cfg, MIX)
            pool = s.make_pool(2**31 + 38)
            out.update(
                system=s, pool=pool,
                answers=[s.solve(req) for req in pool],
                dA=T.device_matrix(s.A, backend),
            )
            return True

        assert pa.prun(body, backend, grid)
        out["counters"] = telemetry.counters("lowering.stream")
        out["names"] = op_names(out["dA"])
    return out


def op_names(dA) -> set:
    """The locations (scopes, then the primitive) in the lowered text of the
    CG program of ``dA``: what becomes an op's `op_name` once it is compiled."""
    fn = T.make_cg_fn(dA, 1e-5, 50)
    L = dA.col_plan.layout
    z = np.zeros((L.P, L.W), dtype=np.float32)
    low = fn.jit_fn.lower(z, z, z, T._matrix_operands(dA))
    return set(re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True)))


@pytest.fixture(scope="module", params=list(GRIDS), ids=list(GRIDS))
def system(request):
    return built(request.param)


def judged(s, req, x) -> float:
    slot = s.new_slots(1)[0]
    s.keep(x, slot)
    return s.check(req, slot)["residual_rel"]


def test_cg_through_the_streamed_lowering_agrees_with_the_reference_cg(system):
    """Answers: 1e-5 of the largest entry. Both run the same recurrence in
    float32 from the zero vector and stop on 1e-5 ||b||; they sum a row in
    other orders (seven stored diagonals there, three flux differences
    here), so they part by rounding, amplified over 38 iterations, and the
    stopping tolerance itself is the ceiling. Iterations within 2."""
    s = system["system"]
    assert system["dA"].dia_mode == "stream"
    limit = MIX["limits"]["residual_rel"]
    for req, (x, info) in zip(system["pool"], system["answers"]):
        assert info["converged"]
        b = pa.gather_pvector(req.b).reshape(NS)
        xr, rinfo = ref.reference_cg(s.faces, b, np.zeros_like(b), s.tol, 1500, "float32")
        assert rinfo["converged"]
        assert abs(rinfo["iterations"] - info["iterations"]) <= 2
        xp = pa.gather_pvector(x)
        assert np.abs(xp - xr.ravel()).max() <= 1e-5 * np.abs(xr).max()
        got = judged(s, req, x)
        assert got <= 0.5 * limit
        witness = judged(s, req, pa.scatter_pvector_values(xr.ravel(), s.A.cols))
        assert abs(got - witness) <= 0.1 * witness
    its = [info["iterations"] for _x, info in system["answers"]]
    assert max(its) - min(its) <= 1  # images of one field: one spectrum


def test_the_pool_is_images_of_one_right_hand_side(system):
    s, pool = system["system"], system["pool"]
    assert len({req.sym for req in pool}) == len(pool) == MIX["pool"]
    u = ref.base_field(NS, 7, 3, 4)
    b = s.apply_reference(u).astype(np.float32)
    for req in pool:
        assert np.array_equal(pa.gather_pvector(req.b).reshape(NS), ref.image(b, req.sym))
        assert not pa.gather_pvector(req.x0).any()
        # x0 is zero and every cell an unknown: the start residual is b
        assert req.r0_norm == pytest.approx(np.linalg.norm(req.b_ref), rel=1e-12)
    other = s.make_pool(2**31 + 39)
    assert [r.sym for r in other] != [r.sym for r in pool]


def test_the_control_and_a_scaled_answer_fail_the_check(system):
    s, req = system["system"], system["pool"][0]
    limit = MIX["limits"]["residual_rel"]
    ctl = MIX["control"]
    x, _info = s.control_solve(req, ctl["dtype"], ctl["maxiter"])
    assert judged(s, req, x) > 2 * limit
    witness, winfo = s.control_solve(req, "float32", 1500)
    assert winfo["converged"] and judged(s, req, witness) <= 0.5 * limit
    good, _ = system["answers"][0]
    scaled = pa.scatter_pvector_values(
        pa.gather_pvector(good) * np.float32(1.001), s.A.cols
    )
    assert judged(s, req, scaled) > limit


def test_off_the_chip_the_program_names_the_xla_form(system):
    """No Mosaic kernel on a CPU mesh: the streamed sum is the shifted-slice
    form under `dia.xla`, and the counters say the operator, its diagonals
    and bytes only."""
    sub = f"{T.SCOPE_SPMV}/{T.SCOPE_DIA_XLA}/"
    assert any(sub in n for n in system["names"])
    for other in (T.SCOPE_DIA_STREAM, T.SCOPE_DIA_EMBED):
        assert not any(f"/{other}/" in n for n in system["names"])
    dA, P = system["dA"], system["P"]
    assert dA.pallas_plan is None
    assert system["counters"] == {
        "lowering.stream.operators": 1,
        "lowering.stream.diagonals": 7,
        "lowering.stream.value_bytes": P * 7 * (N**3 // P) * 4,
        "lowering.stream.pallas": 0,
    }
    assert system["system"].lowering_fault == ""


# -- (e) the kernel's scopes and counters, the kernel interpreted ------------------------


@pytest.fixture(scope="module", params=list(GRIDS), ids=list(GRIDS))
def kernel_system(request):
    return built(request.param, kernel=True)


def test_the_kernel_path_names_its_parts_and_counts_its_plan(kernel_system):
    names, dA, P = kernel_system["names"], kernel_system["dA"], kernel_system["P"]
    for sub, op in (
        (T.SCOPE_DIA_EMBED, "_pad"),  # the operand cut out and padded
        (T.SCOPE_DIA_STREAM, "pallas_call"),
        (T.SCOPE_DIA_EMBED, "scatter"),  # the product embedded in a frame
    ):
        assert any(
            f"{T.SCOPE_SPMV}/{sub}/" in n and op in n.rsplit("/", 1)[-1] for n in names
        ), (sub, op)
    assert not any(f"/{T.SCOPE_DIA_XLA}/" in n for n in names)
    plan = dA.pallas_plan
    no_max = N**3 // P
    # the block is the data's own tiled rows, rounded up to 8 sublanes; the
    # halo is the slowest axis's stride in lane rows; the window is block +
    # two halos + the rotation's spill row, rounded up to 8
    rows = -(-(-(-no_max // LANES)) // 8) * 8
    halo = -(-max(dA.dia_offsets) // LANES)
    assert (plan["block_rows"], plan["n_rows"], plan["halo_rows"]) == (rows, rows, halo)
    window = -(-(rows + 2 * halo + 1) // 8) * 8
    assert kernel_system["counters"] == {
        "lowering.stream.operators": 1,
        "lowering.stream.diagonals": 7,
        "lowering.stream.value_bytes": P * 7 * rows * LANES * 4,
        "lowering.stream.pallas": 1,
        "lowering.stream.block_rows": rows,
        "lowering.stream.x_window_rows": window,
        "lowering.stream.blocks": 1,
        "lowering.stream.window_slots": 2,
    }
    reread = importlib.import_module("benchmark.layer_metrics.stream_window_reread")
    assert reread.reread(kernel_system["counters"]) == pytest.approx(100.0 * window / rows)


def test_the_kernel_is_the_xla_form_at_an_offset_over_one_lane_row(kernel_system):
    """Offsets of +-144 on one part reach over a 128-lane row (a row shift
    and a lane rotation in the kernel). The interpreted kernel and
    `_dia_rowsum` fold the same products in the same ascending order: one
    product, and whole solves, to the last bit or nearly (FMA contraction)."""
    which = "1part" if kernel_system["P"] == 1 else "4parts"
    plain = built(which)
    assert (max(kernel_system["dA"].dia_offsets) > LANES) == (kernel_system["P"] == 1)
    dA_k, dA_x = kernel_system["dA"], plain["dA"]
    L = dA_x.col_plan.layout
    rng = np.random.default_rng(38)
    x = np.zeros((L.P, L.W), dtype=np.float32)
    x[:, L.o0 : L.o0 + L.no_max] = rng.standard_normal((L.P, L.no_max))
    y_k = np.asarray(T.make_spmv_fn(dA_k)(x))
    y_x = np.asarray(T.make_spmv_fn(dA_x)(x))
    assert np.abs(y_k).max() > 1.0
    assert np.abs(y_k - y_x).max() <= 4e-7 * np.abs(y_x).max()
    for (xa, ia), (xb, ib) in zip(kernel_system["answers"], plain["answers"]):
        assert ia["iterations"] == ib["iterations"] and ia["converged"]
        a, b = pa.gather_pvector(xa), pa.gather_pvector(xb)
        assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max()


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "xla"])
def test_a_kernel_plan_counts_the_two_window_slots(kernel):
    """PR 39: the Mosaic kernel fetches block i+1's x window into the second
    of two VMEM slots while block i computes. Staging an operator with a
    kernel plan bumps `lowering.stream.window_slots` by 2; the XLA form
    fetches no window and bumps it by nothing."""
    key = "lowering.stream.window_slots"
    backend = pa.TPUBackend(devices=jax.devices()[:1])
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(T, "_stream_kernel_for", lambda backend: kernel)

        def body(p):
            A = pa.assemble_diffusion_fv(p, (6, 6, 6), BETA, dtype=np.float32)
            before = telemetry.counters("lowering.stream").get(key, 0)
            out["dA"] = T.device_matrix(A, backend)
            out["slots"] = telemetry.counters("lowering.stream").get(key, 0) - before
            return True

        assert pa.prun(body, backend, (1, 1, 1))
    assert out["dA"].dia_mode == "stream"
    assert (out["dA"].pallas_plan is not None) is kernel
    assert out["slots"] == (2 if kernel else 0)
