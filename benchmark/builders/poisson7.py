"""Builder `poisson7`: the 7-point Poisson problem of a configuration file.

Two halves that share nothing but the grid's sizes:

* the SYSTEM, through the library's public API exactly as `chip_smoke.py`
  builds it: `pa.assemble_poisson(parts, ns, dtype=float32,
  decoupled=True)`, `pa.gmg_hierarchy` where the mix asks for it, and the
  solve entry the mix names (`pa.cg` / `pa.pcg`), host vectors in and host
  vectors out;
* the plain REFERENCE, which imports nothing of the program: the same
  operator stated in its own words on a 3-D NumPy float64 array, the
  right-hand sides, the number that decides `correct`, and a plain CG that
  stands in the program's place as the low-precision control.

The operator, in the reference's words. Cells of an n0 x n1 x n2 grid;
a cell with any coordinate 0 or n-1 is a boundary cell. A boundary row is
the identity. An interior row is 6 u(c) minus the sum of u over those of
the six face neighbours that are interior themselves: the Dirichlet
values are eliminated, so the operator is blockdiag(I, L) with L the
7-point Laplacian of the interior cells under a homogeneous Dirichlet
closure. Symmetric positive definite.
"""
from __future__ import annotations

import itertools
import time

import numpy as np


# ---------------------------------------------------------------------------
# the plain reference (NumPy float64; no import of the program)
# ---------------------------------------------------------------------------


def interior_of(u: np.ndarray) -> np.ndarray:
    return u[1:-1, 1:-1, 1:-1]


def apply_reference(u: np.ndarray) -> np.ndarray:
    """``A u`` for the operator described in the module docstring."""
    z = np.zeros_like(u)
    interior_of(z)[...] = interior_of(u)  # boundary values eliminated
    y = u.copy()  # identity rows
    core = interior_of(y)
    core *= 6.0
    for axis in range(3):
        for shift in (0, 2):
            sl = [slice(1, -1)] * 3
            sl[axis] = slice(shift, u.shape[axis] - 2 + shift)
            core -= z[tuple(sl)]
    return y


def boundary_only(u: np.ndarray) -> np.ndarray:
    """``u`` on the boundary cells, 0 inside: a start vector that carries
    the Dirichlet data exactly, as the upstream test builds its own."""
    x0 = u.copy()
    interior_of(x0)[...] = 0.0
    return x0


def base_field(ns, field_seed: int, modes: int, max_wavenumber: int):
    """One smooth field: ``modes`` products of one low-wavenumber sine per
    axis, wavenumbers, amplitudes and phases drawn from ``field_seed``."""
    rng = np.random.default_rng(field_seed)
    u = np.zeros(ns, dtype=np.float64)
    for _ in range(modes):
        amp = rng.uniform(0.5, 1.0)
        f = []
        for n in ns:
            kappa = int(rng.integers(1, max_wavenumber + 1))
            phase = rng.uniform(0.0, 2.0 * np.pi)
            f.append(np.sin(np.pi * kappa * (np.arange(n) + 0.5) / n + phase))
        u += amp * f[0][:, None, None] * f[1][None, :, None] * f[2][None, None, :]
    return u


def symmetries(ns, grid):
    """Every image of the grid under which the operator AND its partition
    map onto themselves: a permutation of axes that have the same number
    of cells and of parts, a reflection of any axis, and the sign."""
    perms = [
        p for p in itertools.permutations(range(3))
        if all(ns[p[d]] == ns[d] and grid[p[d]] == grid[d] for d in range(3))
    ]
    flips = list(itertools.product((False, True), repeat=3))
    return [(p, f, s) for p in perms for f in flips for s in (1.0, -1.0)]


def image(u: np.ndarray, sym) -> np.ndarray:
    """A fresh C-ordered copy of ``u`` under the symmetry ``sym``."""
    perm, flips, sign = sym
    v = np.transpose(u, perm)
    for axis, flip in enumerate(flips):
        if flip:
            v = np.flip(v, axis)
    out = np.array(v, order="C", copy=True)
    if sign != 1.0:
        np.negative(out, out=out)
    return out


def make_reference_cg(tol: float, maxiter: int, dtype: str):
    """Plain CG on the reference operator as one jitted `jax.numpy`
    program ``solve(b, x0) -> (x, rs, rs0, iterations)``: vectors and the
    stencil's arithmetic in ``dtype``, dot products accumulated in float32
    (the most a lower-precision path could keep). Stops on
    ``||r|| <= tol ||r0||`` by its own recurrence."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    def apply(u):
        z = jnp.zeros_like(u).at[1:-1, 1:-1, 1:-1].set(u[1:-1, 1:-1, 1:-1])
        core = 6.0 * u[1:-1, 1:-1, 1:-1]
        for axis in range(3):
            for shift in (0, 2):
                sl = [slice(1, -1)] * 3
                sl[axis] = slice(shift, u.shape[axis] - 2 + shift)
                core = core - z[tuple(sl)]
        return u.at[1:-1, 1:-1, 1:-1].set(core.astype(dt))

    def dot(a, c):
        return jnp.sum(a.astype(jnp.float32) * c.astype(jnp.float32))

    def solve(b, x0):
        b, x0 = b.astype(dt), x0.astype(dt)
        r = (b - apply(x0)).astype(dt)
        rs0 = dot(r, r)

        def cond(s):
            _x, _r, _p, rs, it = s
            return (it < maxiter) & (rs > (tol * tol) * rs0)

        def body(s):
            x, r, p, rs, it = s
            q = apply(p)
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


def reference_cg(b: np.ndarray, x0: np.ndarray, tol: float, maxiter: int,
                 dtype: str):
    """`make_reference_cg` run on the default device: what stands in the
    program's place as the control (``bfloat16``), and a second witness
    beside the program (``float32``)."""
    x, rs, rs0, it = make_reference_cg(tol, maxiter, dtype)(b, x0)
    rs, rs0 = float(rs), float(rs0)
    return np.asarray(x), {
        "iterations": int(it),
        "converged": bool(np.sqrt(rs) <= tol * np.sqrt(rs0)),
        "status": f"reference_cg[{dtype}]",
    }


# ---------------------------------------------------------------------------
# the system (the library's public API) and its requests
# ---------------------------------------------------------------------------


class Request:
    """One right-hand side of the pool: what the program is given (host
    `PVector`s) and what the reference keeps to judge the answer."""

    def __init__(self, b, x0, b_ref, r0_norm, sym):
        self.b, self.x0 = b, x0
        self.b_ref, self.r0_norm, self.sym = b_ref, r0_norm, sym


class System:
    def __init__(self, pa, parts, cfg: dict, mix: dict):
        self.pa, self.mix = pa, mix
        self.ns = tuple(int(n) for n in cfg["cells"])
        self.grid = tuple(int(g) for g in cfg["part_grid"])
        self.tol = float(cfg["tol"])
        self.dtype = np.dtype(cfg["dtype"])
        self.dofs = int(np.prod(self.ns))
        self.dofs_per_chip = self.dofs // int(np.prod(self.grid))
        t0 = time.perf_counter()
        self.A, _b, _xe, _x0 = pa.assemble_poisson(
            parts, self.ns, dtype=self.dtype.type, decoupled=True
        )
        self.hierarchy = None
        if mix.get("preconditioner") == "gmg":
            self.hierarchy = pa.gmg_hierarchy(parts, self.A, self.ns)
        elif mix.get("preconditioner") is not None:
            raise ValueError(
                f"poisson7: unknown preconditioner {mix['preconditioner']!r}"
            )
        self.assemble_s = time.perf_counter() - t0
        if mix["entry"] not in ("cg", "pcg"):
            raise ValueError(f"poisson7: unknown entry {mix['entry']!r}")
        if (mix["entry"] == "pcg") != (self.hierarchy is not None):
            raise ValueError("poisson7: pcg goes with a preconditioner, cg without")

    # -- requests ----------------------------------------------------------

    def make_pool(self, seed: int) -> list:
        """The mix's ONE base field u (from the mix's own `field_seed`),
        its right-hand side b = A_ref u and its start vector (u on the
        boundary, zero inside), both rounded to the configuration's dtype;
        and for each of the pool's entries their image under a symmetry of
        the grid drawn from ``seed``, without repeats. The operator maps
        onto itself under these symmetries, so an image of (b, x0) is the
        (b, x0) of the image of u. Every seed so gives other inputs, bit
        for bit, of the same spectrum: the same Krylov work in every run,
        which is what lets runs of different seeds be compared at all."""
        f = self.mix["fields"]
        u = base_field(
            self.ns, int(f["field_seed"]), int(f["modes"]),
            int(f["max_wavenumber"]),
        )
        # the program is given float32; the system judged is the one with
        # THAT right-hand side, so the reference keeps the rounded b
        b = apply_reference(u).astype(self.dtype)
        x0 = boundary_only(u).astype(self.dtype)
        # r0 = b - A x0. x0 is zero inside and no interior row sees a
        # boundary column, so A x0 = x0, which is b on the boundary: r0 is
        # b's interior, and its norm is every image's
        # (test_run_cpu.py holds this against apply_reference)
        r0_norm = float(np.linalg.norm(interior_of(b).astype(np.float64)))
        syms = symmetries(self.ns, self.grid)
        rng = np.random.default_rng(int(seed))
        picks = rng.choice(len(syms), size=int(self.mix["pool"]), replace=False)
        return [self.request(b, x0, r0_norm, syms[int(i)]) for i in picks]

    def request(self, b, x0, r0_norm: float, sym) -> Request:
        pa, cols = self.pa, self.A.cols
        bk, x0k = image(b, sym), image(x0, sym)
        return Request(
            pa.scatter_pvector_values(bk.ravel(), cols),
            pa.scatter_pvector_values(x0k.ravel(), cols),
            bk.astype(np.float64), r0_norm, sym,
        )

    # -- the timed entry ---------------------------------------------------

    def solve(self, req: Request):
        """One call of the public entry, as a user of the library makes it."""
        if self.hierarchy is not None:
            return self.pa.pcg(
                self.A, req.b, x0=req.x0, minv=self.hierarchy, tol=self.tol
            )
        return self.pa.cg(self.A, req.b, x0=req.x0, tol=self.tol)

    # -- what decides `correct` ---------------------------------------------

    def new_slots(self, n: int) -> list:
        """``n`` places to keep an answer in, made and touched in set-up:
        keeping an answer inside the window is then a copy into memory the
        process already holds, and the answer itself is dropped at once, as
        a caller's loop would drop it."""
        isets = self.A.cols.partition.part_values()
        return [
            [np.full(i.num_lids, np.nan, dtype=self.dtype) for i in isets]
            for _ in range(n)
        ]

    def keep(self, x, slot: list) -> None:
        for buf, vals in zip(slot, x.values.part_values()):
            np.copyto(buf, np.asarray(vals))

    def check(self, req: Request, slot: list) -> dict:
        """``||b - A_ref x|| / ||b - A_ref x0||`` in float64: by how much
        the answer, as the user reads it from the host vector (every part's
        owned values at their global ids), reduced the residual of the
        benchmark's own operator."""
        xg = np.full(self.dofs, np.nan, dtype=np.float64)
        for iset, buf in zip(self.A.cols.partition.part_values(), slot):
            xg[np.asarray(iset.oid_to_gid)] = buf[np.asarray(iset.oid_to_lid)]
        if not np.isfinite(xg).all():
            return {"residual_rel": float("inf")}
        r = req.b_ref - apply_reference(xg.reshape(self.ns))
        return {"residual_rel": float(np.linalg.norm(r)) / req.r0_norm}

    # -- the control: the reference in the program's place -------------------

    def control_solve(self, req: Request, dtype: str, maxiter: int):
        b = self.pa.gather_pvector(req.b).reshape(self.ns)
        x0 = self.pa.gather_pvector(req.x0).reshape(self.ns)
        x, info = reference_cg(b, x0, self.tol, maxiter, dtype)
        return (
            self.pa.scatter_pvector_values(
                x.astype(self.dtype).ravel(), self.A.cols
            ),
            info,
        )

    def device_bytes_peak(self) -> int:
        peaks = []
        for d in self.A.values.backend.devices()[: int(np.prod(self.grid))]:
            stats = d.memory_stats()
            if stats is not None:  # the CPU client reports none
                peaks.append(int(stats["peak_bytes_in_use"]))
        return max(peaks) if peaks else 0


def build(pa, parts, cfg: dict, mix: dict) -> System:
    return System(pa, parts, cfg, mix)
