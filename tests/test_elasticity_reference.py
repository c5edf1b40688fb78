"""Tet elasticity as a supported deployment (PR 28): the program's assembly
against the benchmark's plain reference (`benchmark/builders/
elasticity_tet.py`, which imports nothing of the program), the float32
path, the faster node-pair assembly against the raw triplet path it
replaced, Jacobi-`pa.pcg` through the SD lowering against the reference
PCG, the comparison that decides `correct`, and the new scopes and
counters. Since PR 32 the cell's path on one part and on four (the
partitioned deployment: an irregular ghost graph, the generic exchange
plan, node-block boundary rows). Small sizes, CPU devices.
"""
import functools
import importlib
import json
import os
import re
import sys

import jax
import numpy as np
import pytest
import scipy.sparse as sp

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import telemetry
from partitionedarrays_jl_tpu.models import elasticity_tet as M
from partitionedarrays_jl_tpu.parallel.psparse import assemble_matrix_from_coo

T = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
ref = importlib.import_module("benchmark.builders.elasticity_tet")

MIX = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "jacobi_pcg_closed.json")))
CFG8 = json.load(
    open(os.path.join(ROOT, "benchmark", "tests", "configs", "elasticity_tet_8.json"))
)
CFG8_X4 = json.load(
    open(os.path.join(ROOT, "benchmark", "tests", "configs", "elasticity_tet_8_x4.json"))
)


def as_scipy(A) -> sp.csr_matrix:
    """A partitioned matrix gathered by global id, stored zeros kept."""
    r, c, v = [], [], []
    for ri, ci, m in zip(
        A.rows.partition.part_values(), A.cols.partition.part_values(),
        A.values.part_values(),
    ):
        r.append(np.asarray(ri.lid_to_gid)[m.row_of_nz()])
        c.append(np.asarray(ci.lid_to_gid)[m.indices])
        v.append(m.data)
    n = A.rows.ngids
    out = sp.coo_matrix(
        (np.concatenate(v), (np.concatenate(r), np.concatenate(c))), shape=(n, n)
    ).tocsr()
    out.sort_indices()
    return out


def reference_operator(n: int):
    coords, tets, boundary = ref.mesh((n,) * 3, 0.2, 0)
    return ref.assemble_reference(coords, tets, boundary, 1.0, 1.0), coords, boundary


def assemble(n: int, parts: int, **kw):
    return pa.prun(
        lambda p: pa.assemble_elasticity_tet(p, (n,) * 3, **kw), pa.sequential, parts
    )


# -- (a) two statements of one operator ---------------------------------------


@pytest.mark.parametrize("parts", [1, 4])
def test_the_reference_operator_is_the_programs_entry_by_entry(parts):
    """Tolerance 1e-12 absolute on entries of size up to 12: both sum the
    same products in float64, in other orders and by other formulas (stress
    tensors there, the closed form here), a few ulp of the largest entry."""
    A_ref, _coords, _b = reference_operator(6)
    A, *_ = assemble(6, parts)
    got = as_scipy(A)
    assert got.nnz == A_ref.nnz == sum(m.nnz for m in A.values.part_values())
    assert np.array_equal(got.indptr, A_ref.indptr)
    assert np.array_equal(got.indices, A_ref.indices)
    assert np.abs(got.data - A_ref.data).max() < 1e-12
    assert np.abs(A_ref.data).max() > 1.0


def test_the_reference_mesh_is_the_programs():
    coords0, tets0, boundary0 = M.tet_mesh((5, 6, 4), jitter=0.2, seed=3)
    perm = M.morton_permutation(coords0)
    coords, tets, boundary = ref.mesh((5, 6, 4), 0.2, 3)
    assert np.array_equal(coords[perm], coords0)
    assert np.array_equal(boundary[perm], boundary0)
    assert np.array_equal(tets, perm[tets0])


def test_the_closed_form_blocks_are_the_strain_matrix_product():
    """`_node_pair_blocks` forms ``vol (lam g_a g_b^T + mu g_b g_a^T + mu
    (g_a . g_b) I)``; `p1_elasticity_ke` is B^T C B. One tet each way."""
    coords, tets, _ = M.tet_mesh((3, 3, 3), jitter=0.2, seed=1)
    ke = M.p1_elasticity_ke(coords, tets, lam=1.3, mu=0.7)
    g, vol = M.p1_gradients(coords, tets)
    for e in (0, 7, len(tets) - 1):
        rn, cn, blocks = M._node_pair_blocks(
            tets[e : e + 1], g[e : e + 1], vol[e : e + 1], len(coords),
            lam=1.3, mu=0.7,
        )
        local = {int(n): a for a, n in enumerate(tets[e])}
        for r, c, blk in zip(rn, cn, blocks):
            a, b = local[int(r)], local[int(c)]
            want = ke[e, 3 * a : 3 * a + 3, 3 * b : 3 * b + 3]
            assert np.abs(blk - want).max() < 1e-13


# -- (b) dtype ------------------------------------------------------------------


def test_float32_is_the_float64_assembly_cast():
    A64, b64, xe64, x064 = assemble(6, 2)
    A32, b32, xe32, x032 = assemble(6, 2, dtype=np.float32)
    assert A32.dtype == np.float32 and A64.dtype == np.float64
    for m32, m64 in zip(A32.values.part_values(), A64.values.part_values()):
        assert m32.data.dtype == np.float32
        assert np.array_equal(m32.indices, m64.indices)
        assert np.array_equal(m32.data, m64.data.astype(np.float32))
    for v32, v64 in ((b32, b64), (xe32, xe64), (x032, x064)):
        for a32, a64 in zip(v32.values.part_values(), v64.values.part_values()):
            assert a32.dtype == np.float32
            assert np.array_equal(a32, np.asarray(a64).astype(np.float32))
    # what `chip_smoke.py` made of the float64 result before this PR (its
    # `_as_float32`, gone with its one caller): the values cast in place
    A64.values = pa.map_parts(
        lambda m: pa.CSRMatrix(m.indptr, m.indices, m.data.astype(np.float32), m.shape),
        A64.values,
    )
    A64.invalidate_blocks()
    for a, b in zip(
        A32.owned_owned_values.part_values(), A64.owned_owned_values.part_values()
    ):
        assert a.data.dtype == b.data.dtype == np.float32
        assert np.array_equal(a.data, b.data) and np.array_equal(a.indices, b.indices)


# -- (f) the faster assembly against the raw triplet path ------------------------


def triplet_path(parts, n):
    """The assembly as it was before PR 28, from public pieces: every tet's
    144 scalar triplets through `assemble_matrix_from_coo`."""
    coords0, tets0, boundary0 = M.tet_mesh((n,) * 3)
    perm = M.morton_permutation(coords0)
    N = len(coords0)
    coords = np.empty_like(coords0)
    coords[perm] = coords0
    boundary = np.zeros(N, dtype=bool)
    boundary[perm] = boundary0
    tets = perm[tets0]
    P = parts.num_parts
    first = np.array([(N * p) // P for p in range(P + 1)], dtype=np.int64)
    rows0 = pa.variable_partition(
        parts, pa.map_parts(lambda p: 3 * int(first[p + 1] - first[p]), parts),
        ngids=3 * N, part_to_firstgid=3 * first[:-1],
    )
    owner = np.searchsorted(first, tets[:, 0], side="right") - 1
    ke = M.p1_elasticity_ke(coords, tets)

    def local(p, iset):
        mine = owner == p
        gd = (3 * tets[mine][:, :, None] + np.arange(3)).reshape(-1, 12)
        I = np.repeat(gd, 12, axis=1).reshape(-1)
        J = np.tile(gd, (1, 12)).reshape(-1)
        V = ke[mine].reshape(-1)
        keep = ~boundary[I // 3]
        g = np.asarray(iset.oid_to_gid)
        gb = g[boundary[g // 3]]
        return (
            np.concatenate([I[keep], gb]), np.concatenate([J[keep], gb]),
            np.concatenate([V[keep], np.ones(len(gb))]),
        )

    coo = pa.map_parts(local, parts, rows0.partition)
    return assemble_matrix_from_coo(
        *(pa.map_parts(lambda c, k=k: c[k], coo) for k in range(3)), rows0
    )


@pytest.mark.parametrize("parts", [1, 2, 4])
def test_the_node_pair_assembly_is_the_triplet_assembly(parts):
    """Same pattern by global id, stored zeros included, and the same ghost
    set on every part (the ghosts' first-touch order follows the triplets'
    order, which changed: sorted by node pair now); values to 1e-12 absolute
    (entries up to 12): the sums run in another order and the element
    blocks come from the closed form."""
    old, (new, *_) = pa.prun(
        lambda p: (triplet_path(p, 6), pa.assemble_elasticity_tet(p, (6,) * 3)),
        pa.sequential, parts,
    )
    for a, b in zip(old.values.part_values(), new.values.part_values()):
        assert a.shape == b.shape and np.array_equal(a.indptr, b.indptr)
    for a, b in zip(
        old.cols.partition.part_values(), new.cols.partition.part_values()
    ):
        assert a.num_oids == b.num_oids
        assert np.array_equal(np.sort(a.lid_to_gid), np.sort(b.lid_to_gid))
    a, b = as_scipy(old), as_scipy(new)
    assert np.array_equal(a.indptr, b.indptr) and np.array_equal(a.indices, b.indices)
    assert np.abs(a.data - b.data).max() < 1e-12


# -- (c), (d), (e): the cell's path at 8^3 nodes on CPU devices -------------------


@functools.lru_cache(maxsize=None)
def built(parts: int) -> dict:
    """The rehearsal configuration on ``parts`` parts, one a CPU device:
    built, its pool solved, its operator staged, the counters read."""
    cfg = {1: CFG8, 4: CFG8_X4}[parts]
    backend = pa.TPUBackend(devices=jax.devices()[:parts])
    telemetry.reset_counters()
    out = {"parts": parts}

    def body(p):
        s = ref.build(pa, p, cfg, MIX)
        pool = s.make_pool(2**31 + 77)
        out.update(
            system=s, pool=pool,
            answers=[s.solve(req) for req in pool],
            dA=T.device_matrix(s.A, backend),
        )
        return True

    assert pa.prun(body, backend, tuple(cfg["part_grid"]))
    out["counters"] = telemetry.counters("lowering.sd")
    out["oh_counters"] = telemetry.counters("lowering.oh")
    out["plan_counters"] = telemetry.counters("exchange.plan")
    return out


@pytest.fixture(scope="module", params=[1, 4], ids=["1part", "4parts"])
def system(request):
    return built(request.param)


@pytest.fixture(scope="module")
def system_x4():
    return built(4)


def judged(s, req, x) -> float:
    slot = s.new_slots(1)[0]
    s.keep(x, slot)
    return s.check(req, slot)["residual_rel"]


def test_pcg_through_the_sd_lowering_agrees_with_the_reference_pcg(system):
    """Answers: 1e-5 of the largest entry. Both run the same recurrence in
    float32 from the same start and stop on 1e-5 ||r0||; they sum in other
    orders (dense group blocks there, CSR rows here), so they part by
    rounding, amplified over 21 iterations: 6e-7 measured at this size over
    three seeds, and the stopping tolerance itself is the ceiling.
    Iterations within 2."""
    s = system["system"]
    assert system["dA"].sd_bs == 3
    limit = MIX["limits"]["residual_rel"]
    for req, (x, info) in zip(system["pool"], system["answers"]):
        assert info["converged"]
        b = pa.gather_pvector(req.b)
        x0 = pa.gather_pvector(req.x0)
        xr, rinfo = ref.reference_pcg(s.A_ref, b, x0, s.tol, 1500, "float32")
        assert rinfo["converged"]
        assert abs(rinfo["iterations"] - info["iterations"]) <= 2
        xp = pa.gather_pvector(x)
        assert np.abs(xp - xr).max() <= 1e-5 * np.abs(xr).max()
        got = judged(s, req, x)
        assert got <= limit
        # beside the witness's: both stop on the same recurrence
        witness = judged(s, req, pa.scatter_pvector_values(xr, s.A.cols))
        assert abs(got - witness) <= 0.1 * witness
    its = [info["iterations"] for _x, info in system["answers"]]
    assert max(its) - min(its) <= 2  # scaling a system leaves its Krylov work


def test_load_cases_are_scalings_drawn_from_the_seed(system):
    s = system["system"]
    factors = [req.factor for req in system["pool"]]
    assert len(set(factors)) == len(factors) == MIX["pool"]
    assert all(0.5 <= abs(c) < 2.0 for c in factors)
    assert factors == ref.load_factors(2**31 + 77, MIX["pool"], [0.5, 2.0])
    assert factors != ref.load_factors(2**31 + 78, MIX["pool"], [0.5, 2.0])
    b0 = pa.gather_pvector(system["pool"][0].b) / np.float32(factors[0])
    for req in system["pool"]:
        assert req.r0_norm > 0
        assert np.allclose(pa.gather_pvector(req.b) / req.factor, b0, rtol=1e-6)
        assert req.r0_norm == pytest.approx(
            abs(req.factor) * system["pool"][0].r0_norm / abs(factors[0]), rel=1e-6
        )


def test_the_control_and_a_scaled_answer_fail_the_check(system):
    s, req = system["system"], system["pool"][0]
    limit = MIX["limits"]["residual_rel"]
    ctl = MIX["control"]
    x, _info = s.control_solve(req, ctl["dtype"], ctl["maxiter"])
    assert judged(s, req, x) > 3 * limit
    witness, winfo = s.control_solve(req, "float32", 1500)
    assert winfo["converged"] and judged(s, req, witness) <= limit
    good, _ = system["answers"][0]
    scaled = pa.scatter_pvector_values(
        pa.gather_pvector(good) * np.float32(1.001), s.A.cols
    )
    assert judged(s, req, scaled) > limit


def op_names(dA) -> set:
    """The `op_name`s of the compiled preconditioned CG program of ``dA``."""
    fn = T.make_cg_fn(dA, 1e-5, 50, precond=True)
    L = dA.col_plan.layout
    z = np.zeros((L.P, L.W), dtype=np.float32)
    text = fn.jit_fn.lower(z, z, z, T._matrix_operands(dA)).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', text))


def test_the_program_text_holds_the_sd_scopes(system):
    names = op_names(system["dA"])
    for sub in (T.SCOPE_SD_GATHER, T.SCOPE_SD_EINSUM):
        assert any(f"{T.SCOPE_SPMV}/{sub}" in n for n in names), sub
    # the Jacobi scaling inside the loop stays an update, not an SpMV part
    assert any(
        n.split("/")[-2:-1] == [T.SCOPE_AXPY] and "mul" in n.split("/")[-1]
        for n in names
    )


def test_the_lowering_counters_are_what_detect_sd_returned(system):
    s, dA = system["system"], system["dA"]
    oo = s.A.owned_owned_values.part_values()
    noids = np.array([m.shape[0] for m in oo])
    sd = T.DeviceMatrix._detect_sd(oo, len(oo), noids, int(noids.max()), np.float32)
    vals = [c["vals"] for c in sd["chunks"]]
    assert system["counters"] == {
        "lowering.sd.nnz": sum(m.nnz for m in oo),
        "lowering.sd.dense_entries": sum(v.size for v in vals),
        "lowering.sd.bytes": sum(v.nbytes for v in vals),
        "lowering.sd.groups": sum(v.shape[0] * v.shape[1] for v in vals),
        "lowering.sd.gather_slots": sum(c["idx"].size for c in sd["chunks"]),
    }
    assert [tuple(v.shape[1:]) for v in dA.sd_vals] == [
        tuple(v.shape[1:]) for v in vals
    ]
    oh_nnz = sum(m.nnz for m in s.A.owned_ghost_values.part_values())
    assert (oh_nnz > 0) == (system["parts"] > 1)
    assert system["counters"]["lowering.sd.nnz"] + oh_nnz == CFG8["nnz"]


# -- PR 32: what four parts add (the ghost graph, the plan, the boundary rows) ------


def test_four_parts_are_all_neighbours_over_edges_of_unequal_size(system_x4):
    """Morton runs of a cube: every part touches every other, two across a
    face and one across an edge, so the edges differ in size by a factor."""
    A = system_x4["system"].A
    for p, iset in enumerate(A.cols.partition.part_values()):
        owners = np.asarray(iset.lid_to_part)[iset.num_oids :]
        by_owner = np.bincount(owners, minlength=4)
        assert by_owner[p] == 0 and (np.delete(by_owner, p) > 0).all()
        assert by_owner.max() >= 4 * np.delete(by_owner, p).min()
        assert iset.num_hids % 3 == 0  # a ghost node brings its three DOFs


def test_the_plan_counters_are_what_the_plan_holds(system_x4):
    plan = system_x4["dA"].col_plan
    assert type(plan) is T.DeviceExchangePlan
    isets = system_x4["system"].A.cols.partition.part_values()
    ghosts = sum(i.num_hids for i in isets)
    edges = [e for perm in plan.perms for e in perm]
    assert len(set(edges)) == len(edges) == 12  # K4, both directions
    sizes = plan.snd_mask.sum(axis=-1)
    assert system_x4["plan_counters"] == {
        "exchange.plan.rounds": plan.R,
        "exchange.plan.edges": 12,
        "exchange.plan.slots": ghosts,
        "exchange.plan.padded_slots": 4 * plan.R * plan.L,
        "exchange.plan.max_edge": plan.L,
        "exchange.plan.min_edge": int(sizes[sizes > 0].min()),
    }
    assert plan.R >= 3 and int(plan.snd_mask.sum()) == ghosts
    assert plan.L > system_x4["plan_counters"]["exchange.plan.min_edge"]
    exchange_fill = importlib.import_module("benchmark.layer_metrics.exchange_fill")
    assert exchange_fill.fill(system_x4["plan_counters"]) == pytest.approx(
        100.0 * ghosts / (4 * plan.R * plan.L)
    )
    # one part has a plan with no edge, and counts nothing
    assert built(1)["plan_counters"] == {} and built(1)["oh_counters"] == {}


def test_the_boundary_rows_lower_to_node_blocks(system_x4):
    s, dA = system_x4["system"], system_x4["dA"]
    assert dA.sd_bs == 3 and dA.ohb_bs == 3
    assert dA.ohs_geo is None and dA.oh_vals is None
    oh = s.A.owned_ghost_values.part_values()
    assert system_x4["oh_counters"] == {
        "lowering.oh.nnz": sum(m.nnz for m in oh),
        "lowering.oh.block_entries": sum(int(np.prod(v.shape)) for v in dA.ohb_vals),
    }
    assert system_x4["oh_counters"]["lowering.oh.nnz"] == dA.oh_nnz > 0


def test_the_program_text_holds_the_boundary_and_exchange_scopes(system_x4):
    names = op_names(system_x4["dA"])
    halo = f"{T.SCOPE_SPMV}/{T.SCOPE_HALO}"
    for scope, op in (
        (f"{T.SCOPE_SPMV}/{T.SCOPE_OH}", "gather"),
        (f"{T.SCOPE_SPMV}/{T.SCOPE_OH}", "scatter-add"),
        (f"{halo}/{T.SCOPE_EX_PACK}", "gather"),
        (f"{halo}/{T.SCOPE_EX_UNPACK}", "scatter"),
    ):
        assert any(f"{scope}/{op}" in n for n in names), (scope, op)
    # the permutes stay the phase's own; no sub-scope opens a phase
    assert any(n.endswith(f"{halo}/ppermute") for n in names)
    assert not any(
        c.startswith("pa.") for c in (T.SCOPE_OH, T.SCOPE_EX_PACK, T.SCOPE_EX_UNPACK)
    )
    # one part exchanges nothing and has no boundary rows
    alone = op_names(built(1)["dA"])
    assert not any(T.SCOPE_EX_PACK in n or f"/{T.SCOPE_OH}/" in n for n in alone)
