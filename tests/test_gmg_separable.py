"""The face-only (separable) matrix-free transfer of the compiled V-cycle.

Level 0 of a hierarchy over the assembled 7-point operator has a halo of
faces only, so the one-pass 3^d stencil cannot read its edges and corners
there; on several parts `tpu_gmg` then applies S = S_z S_y S_x as one
pass of (0.5, 1, 0.5) an axis, each behind an exchange of that axis's
faces. Held here, on the CPU mesh, to the plain float64 statement of the
V-cycle in `_gmg_reference.py` (which imports nothing of the package):

* S alone, on the owned boxes of (2,2,1), (2,2,2), an unequal split and a
  periodic grid, against the assembled S product;
* one compiled V-cycle on four parts against the reference V-cycle;
* `pa.pcg(A, b, minv=h)` against the sequential backend (iterations and
  solution), with the `gmg.transfer.*` counters saying which form ran.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import telemetry
from partitionedarrays_jl_tpu.parallel import tpu_gmg
from partitionedarrays_jl_tpu.parallel.tpu_gmg import _device_hierarchy

import _gmg_reference as ref

EPS32 = float(np.finfo(np.float32).eps)

#: (part grid, cells, operator): each case's level 0 is a faces-only plan
GRIDS = {
    "221": ((2, 2, 1), (16, 16, 12), "decoupled"),
    "222": ((2, 2, 2), (12, 12, 12), "decoupled"),
    "unequal": ((2, 2, 2), (17, 14, 10), "decoupled"),
    "periodic": ((2, 2, 2), (12, 12, 12), "periodic"),
}


def _assemble(parts, ns, kind, dtype):
    if kind == "periodic":
        return pa.assemble_poisson_periodic(parts, ns, shift=1.0, dtype=dtype)
    return pa.assemble_poisson(parts, ns, dtype=dtype, decoupled=True)


def _transfer_counts():
    c = telemetry.counters("gmg.transfer")
    return {f: c.get(f"gmg.transfer.{f}", 0) for f in ("levels",) + tpu_gmg.TRANSFER_FORMS}


def _compiled_S(parts, h, u):
    """S u through `_separable_apply` on level 0's staged descriptor, each
    part's owned box in, the global vector out (float32 throughout)."""
    backend = parts.backend
    lv = _device_hierarchy(h, backend)["levels"][0]
    assert lv["form"] == "separable", lv["form"]
    descs = lv["stencil"]
    fsets = h.levels[0].A.cols.partition.part_values()
    P, no = len(fsets), max(i.num_oids for i in fsets)
    own = np.zeros((P, no), np.float32)
    for p, i in enumerate(fsets):
        own[p, : i.num_oids] = u[np.asarray(i.oid_to_gid)]
    ops = {"u": own, "sel": np.asarray(lv.get("dsel", np.zeros((P, 1), np.int32)))}
    if "shmask" in lv:
        ops["mask"] = np.asarray(lv["shmask"], np.float32)
    mesh, spec = backend.mesh(P), backend.parts_spec()

    def shard(m):
        m = {k: v[0] for k, v in m.items()}
        return tpu_gmg._separable_apply(
            jax, jnp, m["u"], [d[0] for d in descs], lv["axes"],
            m["sel"][0] if len(descs) > 1 else None, m.get("mask"),
        )[None]

    fn = jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=(jax.tree.map(lambda _: spec, ops),),
        out_specs=spec, check_vma=False,
    ))
    out = np.asarray(fn(ops))
    got = np.zeros_like(u, dtype=np.float64)
    for p, i in enumerate(fsets):
        got[np.asarray(i.oid_to_gid)] = out[p, : i.num_oids]
    boxes = [(i.box_lo, i.box_hi) for i in fsets]
    return got, boxes, len(descs), "mask" in ops


@pytest.mark.parametrize("case", sorted(GRIDS))
def test_separable_S_is_the_assembled_S(case):
    """S of a seeded random vector, float32 on the device, against the
    assembled S of the same (rounded) vector in float64.

    Tolerance 64 eps32 max|u|: an entry of S u is at most 8 max|u| (the
    weights of a row sum to 8 at most) and is formed by three passes,
    each rounding twice (the half-sum and the add), so float32 rounding
    is bounded by about 3 x 2 x 8 eps32 max|u| = 48 eps32 max|u| (the
    readings are 1.1 to 1.4 eps32 max|u|). The same vector with its
    neighbours' faces left out is off by order max|u| at every part
    boundary, far outside it."""
    grid, ns, kind = GRIDS[case]
    u = np.random.default_rng(44).standard_normal(ns).ravel().astype(np.float32)

    def driver(parts):
        A = _assemble(parts, ns, kind, np.float32)[0]
        h = pa.gmg_hierarchy(parts, A, ns, coarse_threshold=100)
        return _compiled_S(parts, h, u)

    got, boxes, ndescs, masked = pa.prun(driver, pa.tpu, grid)
    S = ref.stencil_S(ns)
    want = S @ u.astype(np.float64)
    tol = 64 * EPS32 * np.abs(u).max()
    assert np.abs(got - want).max() <= tol, (case, np.abs(got - want).max(), tol)
    # the check has teeth: S restricted to each part's own box (no faces
    # exchanged) is far from it
    blocks = np.zeros(u.shape, np.int64)
    for p, (lo, hi) in enumerate(boxes):
        sl = tuple(slice(a, b) for a, b in zip(lo, hi))
        blocks.reshape(ns)[sl] = p
    local = S.multiply(blocks[:, None] == blocks[None, :]) @ u.astype(np.float64)
    assert np.abs(local - want).max() > 1000 * tol
    assert (ndescs > 1) == (case == "unequal")
    assert masked == (case == "periodic")


def test_one_compiled_vcycle_on_four_parts_is_the_reference_vcycle(monkeypatch):
    """One V-cycle of the compiled program (`pa.gmg_solve` for one step
    from zero: x1 = V(b), the body `pa.pcg` inlines as its preconditioner)
    on a (2,2,1) grid at 16^3 a part, float32, against the float64
    reference V-cycle of the same b.

    Tolerance 1e-5 of max|V b|: some twenty float32 passes over a vector
    (smoothing, residuals, transfers, the float32 copy of the dense coarse
    inverse) each round at 6e-8 relative; the reading here is 5.7e-8,
    and a V-cycle whose level-0 transfer drops its faces reads 9.4e-2."""
    ns = (32, 32, 16)
    b = np.random.default_rng(7).standard_normal(ns).ravel().astype(np.float32)

    def driver(parts):
        A = _assemble(parts, ns, "decoupled", np.float32)[0]
        h = pa.gmg_hierarchy(parts, A, ns)
        bv = pa.scatter_pvector_values(b, A.cols)
        x0 = pa.scatter_pvector_values(np.zeros_like(b), A.cols)
        x, info = pa.gmg_solve(h, bv, x0=x0, tol=0.0, maxiter=1)
        assert info["iterations"] == 1
        forms = [l["form"] for l in _device_hierarchy(h, parts.backend)["levels"]]
        return pa.gather_pvector(x), forms

    got, forms = pa.prun(driver, pa.tpu, (2, 2, 1))
    assert forms == ["separable", "stencil"], forms
    H = ref.Hierarchy(ref.poisson7_decoupled(ns), ns)
    assert len(H.levels) == len(forms)
    want = H.vcycle(b.astype(np.float64))
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-5, err
    # and the tolerance has teeth: the same cycle with level 0's faces
    # left out of its transfer
    apply = tpu_gmg._separable_apply

    def faceless(jax_, jnp_, u, fbs, axes, sel=None, facemask=None):
        return apply(jax_, jnp_, u, fbs, tuple((None, None) for _ in axes), sel, facemask)

    monkeypatch.setattr(tpu_gmg, "_separable_apply", faceless)
    bad, _ = pa.prun(driver, pa.TPUBackend(), (2, 2, 1))
    assert np.abs(bad - want).max() / np.abs(want).max() > 1e-2


@pytest.mark.parametrize("case", sorted(GRIDS))
def test_pcg_with_the_separable_transfer_matches_the_sequential_backend(case):
    """`pa.pcg(A, b, minv=h)` on the device mesh against the host oracle:
    the same iteration count, the solution to float64 rounding, and the
    counters say level 0 ran the separable form and nothing was staged as
    an operator."""
    grid, ns, kind = GRIDS[case]

    def driver(parts):
        A, b, xe, x0 = _assemble(parts, ns, kind, np.float64)
        h = pa.gmg_hierarchy(parts, A, ns, coarse_threshold=100)
        x, info = pa.pcg(A, b, x0=x0, minv=h, tol=1e-9)
        assert info["converged"], info
        return info["iterations"], pa.gather_pvector(x)

    it_s, x_s = pa.prun(driver, pa.sequential, grid)
    before = _transfer_counts()
    it_t, x_t = pa.prun(driver, pa.tpu, grid)
    counted = {k: v - before[k] for k, v in _transfer_counts().items()}
    assert it_s == it_t, (case, it_s, it_t)
    assert np.abs(x_t - x_s).max() <= 1e-9 * max(1.0, np.abs(x_s).max())
    assert counted["separable"] >= 1, counted
    assert counted["operator"] == counted["assembled"] == 0, counted
    assert counted["levels"] == counted["separable"] + counted["stencil"]
