"""Builder `elasticity_tet`: P1 linear elasticity on a jittered tet mesh.

Two halves that share nothing but the configuration's numbers:

* the SYSTEM, through the library's public API as a user calls it:
  `pa.assemble_elasticity_tet(parts, nodes, dtype=float32)`,
  `pa.jacobi_preconditioner(A)` once, and `pa.pcg(A, b, x0=x0, minv=minv,
  tol=tol)` per solve, host vectors in and host vectors out;
* the plain REFERENCE, which imports nothing of the program: the same mesh
  and the same operator stated in its own words, assembled with
  `scipy.sparse` in float64 into one CSR `A_ref`; the load cases; the number
  that decides `correct`; and a plain Jacobi-PCG on `A_ref` that stands in
  the program's place as the low-precision control.

The deployment, in the reference's words. Nodes of an n0 x n1 x n2 grid,
numbered in C order; a node with any index 0 or n-1 is a boundary node and
stays on the grid, every other node is moved by `jitter` x a uniform draw in
[-1, 1) per axis (`np.random.default_rng(mesh_seed)`, one (nodes, 3) draw in
node order, the boundary nodes' draws unused). Each grid cell is cut into
five tets, cells of even and odd index sum mirrored so that faces conform;
a tet the jitter turned inside out has two nodes swapped. Nodes are then
renumbered along a Morton curve of their coordinates (10 bits an axis, ties
kept in grid order). Three displacement DOFs a node, DOF 3 node + component.
The operator is the P1 stiffness of an isotropic Hooke solid (`lam`, `mu`)
on the rows of interior nodes, boundary columns kept, and the identity on
the rows of boundary nodes: Dirichlet data enters through the start vector.
"""
from __future__ import annotations

import time

import numpy as np
import scipy.sparse as sp

from benchmark.builders import poisson7

# The five tets of a cell by corner number, corner = 4 z + 2 y + x with x
# the first grid axis: an even cell keeps corner 0's diagonals, an odd cell
# is its mirror image in x.
EVEN_CELL = ((0, 1, 3, 5), (0, 2, 3, 6), (0, 4, 5, 6), (3, 5, 6, 7), (0, 3, 5, 6))
ODD_CELL = ((1, 0, 2, 4), (1, 3, 2, 7), (1, 5, 4, 7), (2, 4, 6, 7), (1, 2, 4, 7))

#: Tets and row nodes handled at a time (see `assemble_reference`): small
#: enough that a chunk's temporaries are memory the process already holds.
ELEMENT_CHUNK = 8192
NODE_CHUNK = 1024


# ---------------------------------------------------------------------------
# the plain reference (NumPy / SciPy float64; no import of the program)
# ---------------------------------------------------------------------------


def morton_rank(coords: np.ndarray, bits: int = 10) -> np.ndarray:
    """``rank[node]``: the node's place along the Z-order curve of the
    coordinates quantised to ``bits`` bits an axis over their bounding box;
    bit k of axis d lands at bit 3 k + d; equal codes keep node order."""
    lo, hi = coords.min(axis=0), coords.max(axis=0)
    span = np.where(hi > lo, hi - lo, 1.0)
    q = ((coords - lo) / span * ((1 << bits) - 1)).astype(np.uint64)
    code = np.zeros(len(coords), dtype=np.uint64)
    for k in range(bits):
        for d in range(3):
            bit = (q[:, d] >> np.uint64(k)) & np.uint64(1)
            code |= bit << np.uint64(3 * k + d)
    rank = np.empty(len(coords), dtype=np.int64)
    rank[np.argsort(code, kind="stable")] = np.arange(len(coords))
    return rank


def mesh(ns, jitter: float, mesh_seed: int):
    """``(coords, tets, boundary)`` in Morton numbering: (N, 3) float64,
    (E, 4) node numbers, (N,) bool."""
    ns = tuple(int(n) for n in ns)
    index = np.indices(ns).reshape(3, -1).T  # node -> its three grid indices
    boundary = ((index == 0) | (index == np.array(ns) - 1)).any(axis=1)
    draw = np.random.default_rng(mesh_seed).random(index.shape)
    coords = index + np.where(boundary[:, None], 0.0, (draw - 0.5) * 2 * jitter)
    cell = np.indices(tuple(n - 1 for n in ns)).reshape(3, -1).T
    corners = np.stack(
        [
            np.ravel_multi_index(
                (cell[:, 0] + x, cell[:, 1] + y, cell[:, 2] + z), ns
            )
            for z in (0, 1) for y in (0, 1) for x in (0, 1)
        ],
        axis=1,
    )
    odd = cell.sum(axis=1) % 2 == 1
    tets = np.concatenate(
        [
            corners[~odd][:, np.array(EVEN_CELL)].reshape(-1, 4),
            corners[odd][:, np.array(ODD_CELL)].reshape(-1, 4),
        ]
    )
    edges = coords[tets[:, 1:]] - coords[tets[:, :1]]
    flipped = np.linalg.det(edges) < 0
    tets[flipped] = tets[flipped][:, [0, 2, 1, 3]]
    rank = morton_rank(coords)
    out = np.empty_like(coords)
    out[rank] = coords
    bnd = np.zeros(len(coords), dtype=bool)
    bnd[rank] = boundary
    return out, rank[tets], bnd


def barycentric_gradients(coords, tets):
    """``(g, vol)``: (E, 4, 3) constant gradients of the four hat functions
    of each tet and its (E,) volume. With edge matrix M (rows x_a - x_0),
    hat function a >= 1 is row a-1 of inv(M)^T applied to x - x_0, and the
    four sum to one."""
    M = coords[tets[:, 1:]] - coords[tets[:, :1]]
    g = np.empty((len(tets), 4, 3))
    g[:, 1:] = np.swapaxes(np.linalg.inv(M), 1, 2)
    g[:, 0] = -g[:, 1:].sum(axis=1)
    return g, np.abs(np.linalg.det(M)) / 6.0


def element_blocks(g, vol, lam: float, mu: float):
    """For tets with gradients ``g`` (S, 4, 3) and volumes ``vol`` (S,),
    the 3x3 blocks between their nodes, (S, 4, 4, 3, 3), by way of the
    stress: trial function (node b, component j) has displacement gradient
    e_j g_b^T, strain its symmetric part, stress ``lam tr(strain) I + 2 mu
    strain``; tested with (node a, component i), whose gradient is e_i
    g_a^T, the entry [a, b, i, j] is vol x (stress g_a)[i]."""
    eye = np.eye(3)
    grad = eye[None, None, :, :, None] * g[:, :, None, None, :]  # [s, b, j] = e_j g_b^T
    strain = 0.5 * (grad + np.swapaxes(grad, 3, 4))
    trace = g  # tr(e_j g_b^T) = g_b[j]
    stress = lam * trace[..., None, None] * eye + 2.0 * mu * strain
    out = np.einsum("sbjiq,saq->sabij", stress, g)
    out *= vol[:, None, None, None, None]
    return out


def assemble_reference(coords, tets, boundary, lam: float, mu: float):
    """The operator of the module docstring as one float64 CSR matrix.

    Element matrices first, `ELEMENT_CHUNK` tets at a time. Then node by
    node: every (node, tet around it) incidence gives the four blocks of
    that node's rows. The incidences of `NODE_CHUNK` row nodes at a time
    become one `scipy.sparse.bsr_matrix` whose block rows list every
    contribution; SciPy sorts each block row by column, which puts the
    contributions to one block side by side, and they are summed; the
    chunks are stacked. Rows of boundary nodes get no contribution and
    the identity."""
    N, E = len(coords), len(tets)
    g, vol = barycentric_gradients(coords, tets)
    K = np.empty((E, 4, 4, 3, 3))
    for e0 in range(0, E, ELEMENT_CHUNK):
        e1 = min(e0 + ELEMENT_CHUNK, E)
        K[e0:e1] = element_blocks(g[e0:e1], vol[e0:e1], lam, mu)
    K = K.reshape(4 * E, 4, 3, 3)  # [incidence 4 e + a] = node a's rows
    flat = tets.reshape(-1)
    by_node = np.argsort(flat, kind="stable")  # incidences, grouped by node
    first = np.concatenate([[0], np.cumsum(np.bincount(flat, minlength=N))])
    pieces = []
    for n0 in range(0, N, NODE_CHUNK):
        n1 = min(n0 + NODE_CHUNK, N)
        inc = by_node[first[n0] : first[n1]]
        inc = inc[~boundary[flat[inc]]]
        per_row = 4 * np.bincount(flat[inc] - n0, minlength=n1 - n0)
        piece = sp.bsr_matrix(
            (
                K[inc].reshape(-1, 3, 3), tets[inc // 4].reshape(-1),
                np.concatenate([[0], np.cumsum(per_row)]),
            ),
            shape=(3 * (n1 - n0), 3 * N),
        )
        piece.sort_indices()
        row = np.repeat(np.arange(n1 - n0), np.diff(piece.indptr))
        head = np.ones(len(row), dtype=bool)
        head[1:] = (row[1:] != row[:-1]) | (piece.indices[1:] != piece.indices[:-1])
        starts = np.flatnonzero(head)
        summed = sp.bsr_matrix(
            (
                np.add.reduceat(piece.data, starts, axis=0), piece.indices[starts],
                np.concatenate(
                    [[0], np.cumsum(np.bincount(row[starts], minlength=n1 - n0))]
                ),
            ),
            shape=piece.shape,
        )
        pieces.append(summed.tocsr())
    A = sp.vstack(pieces, format="csr")
    # the identity on the (so far empty) rows of boundary DOFs, put into the
    # CSR arrays directly: a sum of matrices would drop the stored zeros
    dirichlet = np.repeat(boundary, 3)
    at = A.indptr[:-1][dirichlet]
    return sp.csr_matrix(
        (
            np.insert(A.data, at, 1.0),
            np.insert(A.indices, at, np.flatnonzero(dirichlet)),
            A.indptr + np.concatenate([[0], np.cumsum(dirichlet)]),
        ),
        shape=A.shape,
    )


def base_field(coords, ns, field_seed: int, modes: int, max_wavenumber: int):
    """One smooth displacement field, (N, 3): each component the sum of
    ``modes`` products of one low-wavenumber sine per axis of the node
    coordinates; wavenumbers, amplitudes and phases from ``field_seed``."""
    rng = np.random.default_rng(field_seed)
    u = np.zeros((len(coords), 3))
    for d in range(3):
        for _ in range(modes):
            term = np.full(len(coords), rng.uniform(0.5, 1.0))
            for axis, n in enumerate(ns):
                kappa = int(rng.integers(1, max_wavenumber + 1))
                phase = rng.uniform(0.0, 2.0 * np.pi)
                term *= np.sin(np.pi * kappa * coords[:, axis] / (n - 1) + phase)
            u[:, d] += term
    return u


def load_factors(seed: int, count: int, scale_range):
    """``count`` factors of either sign, no two equal, magnitudes
    log-uniform in ``scale_range``, drawn from ``seed``."""
    rng = np.random.default_rng(int(seed))
    lo, hi = (float(s) for s in scale_range)
    out = []
    while len(out) < count:
        c = float(np.exp(rng.uniform(np.log(lo), np.log(hi))))
        c = c if rng.integers(0, 2) else -c
        if c not in out:
            out.append(c)
    return out


def make_reference_pcg(A_ref, tol: float, maxiter: int, dtype: str):
    """Plain Jacobi-preconditioned CG on ``A_ref`` as one jitted `jax.numpy`
    program ``solve(b, x0) -> (x, rs, rs0, iterations)``: the operator's
    values, the vectors, the products and the row sums (a gather of x at
    the CSR column indices and a `segment_sum` over the rows) in ``dtype``,
    dot products accumulated in float32 (the most a lower-precision path
    could keep). No matrix product appears, so there is no matmul precision
    to set. Stops on ``||r|| <= tol ||r0||`` by its own recurrence."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    n = A_ref.shape[0]
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(A_ref.indptr))
    operator = (
        jnp.asarray(A_ref.data, dtype=dt),
        jnp.asarray(A_ref.indices, dtype=jnp.int32),
        jnp.asarray(rows),
        jnp.asarray(1.0 / A_ref.diagonal(), dtype=dt),
    )

    def dot(a, c):
        return jnp.sum(a.astype(jnp.float32) * c.astype(jnp.float32))

    def program(vals, cols, rows, dinv, b, x0):
        def apply(x):
            return jax.ops.segment_sum(
                vals * x[cols], rows, num_segments=n, indices_are_sorted=True
            ).astype(dt)

        b, x0 = b.astype(dt), x0.astype(dt)
        r = (b - apply(x0)).astype(dt)
        z = (dinv * r).astype(dt)
        rs0 = dot(r, r)

        def cond(s):
            _x, _r, _p, _rz, rs, it = s
            return (it < maxiter) & (rs > (tol * tol) * rs0)

        def body(s):
            x, r, p, rz, _rs, it = s
            q = apply(p)
            alpha = rz / dot(p, q)
            x = (x + alpha.astype(dt) * p).astype(dt)
            r = (r - alpha.astype(dt) * q).astype(dt)
            z = (dinv * r).astype(dt)
            rz_new = dot(r, z)
            p = (z + (rz_new / rz).astype(dt) * p).astype(dt)
            return x, r, p, rz_new, dot(r, r), it + 1

        x, _r, _p, _rz, rs, it = jax.lax.while_loop(
            cond, body, (x0, r, z, dot(r, z), rs0, jnp.int32(0))
        )
        return x.astype(jnp.float32), rs, rs0, it

    jitted = jax.jit(program)
    return lambda b, x0: jitted(*operator, b, x0)


def reference_pcg(A_ref, b, x0, tol: float, maxiter: int, dtype: str):
    """`make_reference_pcg` run on the default device: what stands in the
    program's place as the control (``bfloat16``), and a second witness
    beside the program (``float32``)."""
    x, rs, rs0, it = make_reference_pcg(A_ref, tol, maxiter, dtype)(b, x0)
    rs, rs0 = float(rs), float(rs0)
    return np.asarray(x), {
        "iterations": int(it),
        "converged": bool(np.sqrt(rs) <= tol * np.sqrt(rs0)),
        "status": f"reference_pcg[{dtype}]",
    }


# ---------------------------------------------------------------------------
# the system (the library's public API) and its requests
# ---------------------------------------------------------------------------


class Request:
    """One load case of the pool: what the program is given (host
    `PVector`s) and what the reference keeps to judge the answer."""

    def __init__(self, b, x0, b_ref, r0_norm, factor):
        self.b, self.x0 = b, x0
        self.b_ref, self.r0_norm, self.factor = b_ref, r0_norm, factor


class System:
    def __init__(self, pa, parts, cfg: dict, mix: dict):
        self.pa, self.mix = pa, mix
        self.ns = tuple(int(n) for n in cfg["nodes_per_dim"])
        self.grid = tuple(int(g) for g in cfg["part_grid"])
        self.tol = float(cfg["tol"])
        self.dtype = np.dtype(cfg["dtype"])
        assumed = cfg["assumed"]
        if mix["entry"] != "pcg" or mix.get("preconditioner") != "jacobi":
            raise ValueError("elasticity_tet: the one mix it knows is jacobi pcg")
        # the program first: a tree without this configuration fails here,
        # at once, and not after the reference has been assembled
        t0 = time.perf_counter()
        self.A, _b, _xe, _x0 = pa.assemble_elasticity_tet(
            parts, self.ns, jitter=float(assumed["jitter"]),
            seed=int(assumed["mesh_seed"]), dtype=self.dtype.type,
        )
        self.minv = pa.jacobi_preconditioner(self.A)
        self.assemble_s = time.perf_counter() - t0
        self.dofs = int(self.A.rows.ngids)
        self.dofs_per_chip = self.dofs // int(np.prod(self.grid))

        t0 = time.perf_counter()
        self.coords, tets, self.boundary = mesh(
            self.ns, float(assumed["jitter"]), int(assumed["mesh_seed"])
        )
        self.A_ref = assemble_reference(
            self.coords, tets, self.boundary,
            float(assumed["lam"]), float(assumed["mu"]),
        )
        self.reference_s = time.perf_counter() - t0
        for key, have in (
            ("dofs", self.A_ref.shape[0]), ("nnz", self.A_ref.nnz),
            ("tets", len(tets)),
        ):
            if int(cfg[key]) != have:
                raise SystemExit(
                    f"bench: configuration {cfg['name']} states {key} "
                    f"{cfg[key]}, and the reference counts {have}"
                )

    def apply_reference(self, x: np.ndarray) -> np.ndarray:
        return self.A_ref @ x

    # -- requests ----------------------------------------------------------

    def make_pool(self, seed: int) -> list:
        """The mix's ONE base field u (from the mix's own `field_seed`),
        its right-hand side b = A_ref u and its start vector (u on the
        boundary DOFs, zero inside); and for each of the pool's entries a
        factor c drawn from ``seed`` (`load_factors`): the load case is
        (c b, c x0) rounded to the configuration's dtype. The mesh has no
        symmetry to draw images from; a relative tolerance makes the Krylov
        work of a scaled system the same to rounding, so every seed gives
        other inputs, bit for bit, and the same iterations to within the
        rounding's reach."""
        f = self.mix["fields"]
        u = base_field(
            self.coords, self.ns, int(f["field_seed"]), int(f["modes"]),
            int(f["max_wavenumber"]),
        ).reshape(-1)
        b = self.apply_reference(u)
        x0 = np.where(np.repeat(self.boundary, 3), u, 0.0)
        factors = load_factors(seed, int(self.mix["pool"]), f["scale_range"])
        return [self.request(c * b, c * x0, c) for c in factors]

    def request(self, b, x0, factor: float) -> Request:
        pa, cols = self.pa, self.A.cols
        # the program is given float32; the system judged is the one with
        # THAT right-hand side and start vector, so the reference keeps
        # the rounded ones and computes the start residual from them
        bk, x0k = b.astype(self.dtype), x0.astype(self.dtype)
        b_ref = bk.astype(np.float64)
        r0 = b_ref - self.apply_reference(x0k.astype(np.float64))
        return Request(
            pa.scatter_pvector_values(bk, cols),
            pa.scatter_pvector_values(x0k, cols),
            b_ref, float(np.linalg.norm(r0)), factor,
        )

    # -- the timed entry ---------------------------------------------------

    def solve(self, req: Request):
        """One call of the public entry, as a user of the library makes it."""
        return self.pa.pcg(
            self.A, req.b, x0=req.x0, minv=self.minv, tol=self.tol
        )

    # -- what decides `correct` ---------------------------------------------

    # places to keep answers in, made in set-up, and the copy into one:
    # the Poisson builder's, word for word (both read `self.A`, `self.dtype`)
    new_slots = poisson7.System.new_slots
    keep = poisson7.System.keep

    def check(self, req: Request, slot: list) -> dict:
        """``||b - A_ref x|| / ||b - A_ref x0||`` in float64: by how much
        the answer, as the user reads it from the host vector (every part's
        owned values at their global ids), reduced the residual of the
        benchmark's own float64 operator. The float32 rounding of the
        program's matrix entries is part of what is judged."""
        xg = np.full(self.dofs, np.nan, dtype=np.float64)
        for iset, buf in zip(self.A.cols.partition.part_values(), slot):
            xg[np.asarray(iset.oid_to_gid)] = buf[np.asarray(iset.oid_to_lid)]
        if not np.isfinite(xg).all():
            return {"residual_rel": float("inf")}
        r = req.b_ref - self.apply_reference(xg)
        return {"residual_rel": float(np.linalg.norm(r)) / req.r0_norm}

    # -- the control: the reference in the program's place -------------------

    def control_solve(self, req: Request, dtype: str, maxiter: int):
        b = self.pa.gather_pvector(req.b)
        x0 = self.pa.gather_pvector(req.x0)
        x, info = reference_pcg(self.A_ref, b, x0, self.tol, maxiter, dtype)
        return (
            self.pa.scatter_pvector_values(x.astype(self.dtype), self.A.cols),
            info,
        )

    device_bytes_peak = poisson7.System.device_bytes_peak  # reads `self.A`, `self.grid`


def build(pa, parts, cfg: dict, mix: dict) -> System:
    return System(pa, parts, cfg, mix)
