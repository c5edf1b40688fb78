"""Builder `varcoef7`: the variable-coefficient 7-point operator of a
configuration file, HPGMG-FV's second-order finite-volume operator.

Two halves that share nothing but the grid's sizes and the constants of
`beta` in the configuration file:

* the SYSTEM, through the library's public API: `pa.assemble_diffusion_fv(
  parts, ns, beta, dtype=float32)` and `pa.cg(A, b, x0=x0, tol=tol)`, host
  vectors in and host vectors out. Behind its first solve it looks at the
  program's `lowering.stream.*` counters and refuses a run whose operator
  did not lower to streamed diagonals (through the Mosaic kernel on a TPU);
* the plain REFERENCE, which imports nothing of the program: the same
  operator stated in its own words on a 3-D NumPy float64 array from three
  arrays of face coefficients, the right-hand sides, the number that decides
  `correct`, and a plain CG that stands in the program's place as the
  low-precision control.

The operator, in the reference's words. ``n0 x n1 x n2`` cells tile the
unit cube; cell ``(i, j, k)`` has its centre at ``((i + 1/2) / n0,
(j + 1/2) / n1, (k + 1/2) / n2)``. Each of the three axes has an array of
face coefficients, ``n + 1`` faces along the axis by the cells of the other
two: `beta` at the face's centre. With ``u`` continued across each wall by
the ghost value ``-u`` (the wall value is zero at the face, linear
closure), the flux through a face is its coefficient times the difference
of ``u`` across it, and ``A u`` is, axis by axis, ``n^2`` times the flux
out of the lower face minus the flux in through the upper one:
``-div(beta grad u)``. Every cell is an unknown; symmetric positive
definite. The coefficient is HPGMG-FV's `evaluateBeta`: ``c1 + c2 tanh(c3
(r - radius))`` with ``r`` the distance from the cube's centre, ``c1 =
(bmax + bmin) / 2`` and ``c2 = (bmax - bmin) / 2``.

`base_field`, `symmetries` and `image` are the Poisson builder's, imported:
`beta` depends on ``r`` alone, so the operator maps onto itself under every
reflection and axis permutation of the cube, and the pool is again images
of one smooth field. Everything that states the operator is this file's.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark.builders import poisson7
from benchmark.builders.poisson7 import Request, base_field, image, symmetries


# ---------------------------------------------------------------------------
# the plain reference (NumPy float64; no import of the program)
# ---------------------------------------------------------------------------


def beta_of(spec: dict):
    """``beta(x, y, z)`` from the configuration's five constants."""
    bmin, bmax = float(spec["bmin"]), float(spec["bmax"])
    c1, c2 = (bmax + bmin) / 2.0, (bmax - bmin) / 2.0
    c3, radius = float(spec["c3"]), float(spec["radius"])
    cx, cy, cz = (float(c) for c in spec["centre"])

    def beta(x, y, z):
        r = np.sqrt((x - cx) ** 2 + (y - cy) ** 2 + (z - cz) ** 2)
        return c1 + c2 * np.tanh(c3 * (r - radius))

    return beta


def face_coefficients(ns, beta) -> list:
    """Per axis, ``n^2 beta`` at the centres of the faces normal to it: an
    array with ``n + 1`` entries along the axis and the cells' count along
    the other two, float64."""
    centres = [(np.arange(n) + 0.5) / n for n in ns]
    out = []
    for axis, n in enumerate(ns):
        at = list(centres)
        at[axis] = np.arange(n + 1) / n
        x, y, z = np.meshgrid(*at, indexing="ij", sparse=True)
        out.append(float(n) ** 2 * beta(x, y, z))
    return out


def apply_reference(faces: list, u: np.ndarray) -> np.ndarray:
    """``A u`` for the operator described in the module docstring."""
    y = np.zeros_like(u)
    for axis, w in enumerate(faces):
        first = np.take(u, [0], axis=axis)
        last = np.take(u, [-1], axis=axis)
        flux = w * np.diff(np.concatenate([-first, u, -last], axis=axis), axis=axis)
        y -= np.diff(flux, axis=axis)
    return y


def count_nnz(ns) -> int:
    """The entries a matrix of the operator stores: seven a cell, less one
    for each face a cell has on a wall."""
    cells = int(np.prod(ns))
    return 7 * cells - sum(2 * cells // n for n in ns)


def make_reference_cg(tol: float, maxiter: int, dtype: str):
    """Plain CG on the reference operator as one jitted `jax.numpy` program
    ``solve(faces, b, x0) -> (x, rs, rs0, iterations)``: the face
    coefficients, the vectors and the stencil's arithmetic in ``dtype``, dot
    products accumulated in float32 (the most a lower-precision path could
    keep). The three face arrays are operands, not constants of the
    program. Stops on ``||r|| <= tol ||r0||`` by its own recurrence."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    def apply(faces, u):
        y = jnp.zeros_like(u)
        for axis, w in enumerate(faces):
            first = jax.lax.slice_in_dim(u, 0, 1, axis=axis)
            last = jax.lax.slice_in_dim(u, u.shape[axis] - 1, u.shape[axis], axis=axis)
            ext = jnp.concatenate([-first, u, -last], axis=axis)
            flux = (w * jnp.diff(ext, axis=axis)).astype(dt)
            y = (y - jnp.diff(flux, axis=axis)).astype(dt)
        return y

    def dot(a, c):
        return jnp.sum(a.astype(jnp.float32) * c.astype(jnp.float32))

    def solve(faces, b, x0):
        faces = [w.astype(dt) for w in faces]
        b, x0 = b.astype(dt), x0.astype(dt)
        r = (b - apply(faces, x0)).astype(dt)
        rs0 = dot(r, r)

        def cond(s):
            _x, _r, _p, rs, it = s
            return (it < maxiter) & (rs > (tol * tol) * rs0)

        def body(s):
            x, r, p, rs, it = s
            q = apply(faces, p)
            alpha = rs / dot(p, q)
            x = (x + alpha.astype(dt) * p).astype(dt)
            r = (r - alpha.astype(dt) * q).astype(dt)
            rs_new = dot(r, r)
            p = (r + (rs_new / rs).astype(dt) * p).astype(dt)
            return x, r, p, rs_new, it + 1

        x, _r, _p, rs, it = jax.lax.while_loop(
            cond, body, (x0, r, r, rs0, jnp.int32(0))
        )
        return x.astype(jnp.float32), rs, rs0, it

    return jax.jit(solve)


def reference_cg(faces: list, b: np.ndarray, x0: np.ndarray, tol: float,
                 maxiter: int, dtype: str):
    """`make_reference_cg` run on the default device: what stands in the
    program's place as the control (``bfloat16``), and a second witness
    beside the program (``float32``)."""
    x, rs, rs0, it = make_reference_cg(tol, maxiter, dtype)(
        [w.astype(np.float32) for w in faces], b, x0
    )
    rs, rs0 = float(rs), float(rs0)
    return np.asarray(x), {
        "iterations": int(it),
        "converged": bool(np.sqrt(rs) <= tol * np.sqrt(rs0)),
        "status": f"reference_cg[{dtype}]",
    }


# ---------------------------------------------------------------------------
# the system (the library's public API) and its requests
# ---------------------------------------------------------------------------


class System:
    def __init__(self, pa, parts, cfg: dict, mix: dict):
        self.pa, self.mix = pa, mix
        self.ns = tuple(int(n) for n in cfg["cells"])
        self.grid = tuple(int(g) for g in cfg["part_grid"])
        self.tol = float(cfg["tol"])
        self.dtype = np.dtype(cfg["dtype"])
        self.dofs = int(np.prod(self.ns))
        self.dofs_per_chip = self.dofs // int(np.prod(self.grid))
        if mix["entry"] != "cg" or mix.get("preconditioner") is not None:
            raise ValueError("varcoef7: the one entry it knows is plain cg")
        beta = beta_of(cfg["beta"])
        # the program first: a tree without this assembler fails here, at
        # once, and not after the reference has made its face arrays
        t0 = time.perf_counter()
        self.A = pa.assemble_diffusion_fv(
            parts, self.ns, beta, dtype=self.dtype.type
        )
        self.assemble_s = time.perf_counter() - t0
        self.faces = face_coefficients(self.ns, beta)
        for key, have in (("dofs", self.dofs), ("nnz", count_nnz(self.ns))):
            if key in cfg and int(cfg[key]) != have:
                raise SystemExit(
                    f"bench: configuration {cfg['name']} states {key} "
                    f"{cfg[key]}, and the reference counts {have}"
                )
        # what the program had counted of streamed operators before this
        # one is lowered (inside the first solve)
        self.stream_before = pa.telemetry.counters("lowering.stream")
        self.lowering_fault = None  # "" once the first solve was looked at

    def apply_reference(self, u: np.ndarray) -> np.ndarray:
        return apply_reference(self.faces, u)

    # -- requests ----------------------------------------------------------

    def make_pool(self, seed: int) -> list:
        """The mix's ONE base field u (from the mix's own `field_seed`) and
        its right-hand side b = A_ref u rounded to the configuration's
        dtype; the start vector is zero. For each of the pool's entries the
        image of b under a symmetry of the grid drawn from ``seed``, without
        repeats: the operator maps onto itself under these symmetries (to
        the rounding of the face centres' coordinates), so every seed gives
        other inputs, bit for bit, of the same spectrum and the same Krylov
        work. x0 is zero and every cell is an unknown, so r0 = b, and its
        norm is every image's."""
        f = self.mix["fields"]
        u = base_field(
            self.ns, int(f["field_seed"]), int(f["modes"]),
            int(f["max_wavenumber"]),
        )
        b = self.apply_reference(u).astype(self.dtype)
        r0_norm = float(np.linalg.norm(b.astype(np.float64)))
        syms = symmetries(self.ns, self.grid)
        rng = np.random.default_rng(int(seed))
        picks = rng.choice(len(syms), size=int(self.mix["pool"]), replace=False)
        return [self.request(b, r0_norm, syms[int(i)]) for i in picks]

    def request(self, b, r0_norm: float, sym) -> Request:
        pa, cols = self.pa, self.A.cols
        bk = image(b, sym)
        return Request(
            pa.scatter_pvector_values(bk.ravel(), cols),
            pa.scatter_pvector_values(np.zeros(self.dofs, self.dtype), cols),
            bk.astype(np.float64), r0_norm, sym,
        )

    # -- the timed entry ---------------------------------------------------

    def solve(self, req: Request):
        """One call of the public entry, as a user of the library makes it;
        behind the first, a look at what the operator lowered to."""
        out = self.pa.cg(self.A, req.b, x0=req.x0, tol=self.tol)
        if self.lowering_fault is None:
            self.lowering_fault = self.stream_lowering_fault()
        if self.lowering_fault:
            raise RuntimeError(f"varcoef7: {self.lowering_fault}")
        return out

    def stream_lowering_fault(self) -> str:
        """Empty where the program's counters say that the one operator
        lowered since set-up streams 7 diagonals, through the Mosaic kernel
        on a TPU; else what they say instead."""
        now = self.pa.telemetry.counters("lowering.stream")
        got = {
            k.rsplit(".", 1)[1]: v - self.stream_before.get(k, 0)
            for k, v in now.items()
        }
        platform = self.A.values.backend.devices()[0].platform
        want = {"diagonals": 7}
        if platform == "tpu":
            want["pallas"] = 1
        if all(got.get(k) == v for k, v in want.items()):
            return ""
        return (
            f"the operator did not lower to streamed diagonals on {platform}: "
            f"lowering.stream.* counted {got or 'nothing'}, wanted {want}"
        )

    # -- what decides `correct` ---------------------------------------------

    # places to keep answers in, made in set-up, and the copy into one:
    # the Poisson builder's, word for word (both read `self.A`, `self.dtype`)
    new_slots = poisson7.System.new_slots
    keep = poisson7.System.keep

    def check(self, req: Request, slot: list) -> dict:
        """``||b - A_ref x|| / ||b||`` in float64 (x0 is zero, so the
        start residual is b): by how much the answer, as the user reads it
        from the host vector (every part's owned values at their global
        ids), reduced the residual of the benchmark's own float64 operator.
        The float32 rounding of the program's matrix entries is part of
        what is judged."""
        xg = np.full(self.dofs, np.nan, dtype=np.float64)
        for iset, buf in zip(self.A.cols.partition.part_values(), slot):
            xg[np.asarray(iset.oid_to_gid)] = buf[np.asarray(iset.oid_to_lid)]
        if not np.isfinite(xg).all():
            return {"residual_rel": float("inf")}
        r = req.b_ref - self.apply_reference(xg.reshape(self.ns))
        return {"residual_rel": float(np.linalg.norm(r)) / req.r0_norm}

    # -- the control: the reference in the program's place -------------------

    def control_solve(self, req: Request, dtype: str, maxiter: int):
        b = self.pa.gather_pvector(req.b).reshape(self.ns)
        x0 = self.pa.gather_pvector(req.x0).reshape(self.ns)
        x, info = reference_cg(self.faces, b, x0, self.tol, maxiter, dtype)
        return (
            self.pa.scatter_pvector_values(
                x.astype(self.dtype).ravel(), self.A.cols
            ),
            info,
        )

    device_bytes_peak = poisson7.System.device_bytes_peak  # reads `self.A`, `self.grid`


def build(pa, parts, cfg: dict, mix: dict) -> System:
    return System(pa, parts, cfg, mix)
