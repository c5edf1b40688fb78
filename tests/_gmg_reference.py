"""A plain float64 statement of one geometric-multigrid V-cycle, to hold
the compiled one to. NumPy and SciPy only: nothing of the package is
imported, so a fault the package shares with itself cannot hide here.

The hierarchy, in its own words. Grid ``nf`` (cells per axis, C order).
Coarse point k of an axis sits on fine point 2k, so ``nc = ceil(nf / 2)``.
The interpolation stencil S weighs the fine neighbour at offset δ
(each component -1, 0 or 1) by ``0.5 ** (number of non-zero components)``
and drops a neighbour outside the grid. E puts coarse values on the even
fine points. The prolongation is ``P = S E`` and the restriction
``R = P^T``; the coarse operator is Galerkin's ``R A P``. Coarsening stops
once a grid has at most ``coarse_threshold`` points or an axis cannot
halve any more (``nc == nf`` or ``min(nc) < 3``), and the last grid is
solved dense. Smoothing is weighted Jacobi with ``omega`` 0.8, ``pre`` and
``post`` sweeps of 1, from zero.
"""
from __future__ import annotations

import itertools

import numpy as np
import scipy.sparse as sp


def stencil_S(nf) -> sp.csr_matrix:
    """S assembled offset by offset from ``0.5 ** |δ|_0``, truncated at
    the grid's boundary."""
    nf = tuple(int(n) for n in nf)
    coords = np.indices(nf).reshape(len(nf), -1)
    rows, cols, vals = [], [], []
    gid = np.arange(int(np.prod(nf)))
    for delta in itertools.product((-1, 0, 1), repeat=len(nf)):
        nb = coords + np.asarray(delta)[:, None]
        ok = np.all((nb >= 0) & (nb < np.asarray(nf)[:, None]), axis=0)
        rows.append(gid[ok])
        cols.append(np.ravel_multi_index(tuple(nb[:, ok]), nf))
        vals.append(np.full(int(ok.sum()), 0.5 ** sum(c != 0 for c in delta)))
    n = int(np.prod(nf))
    return sp.csr_matrix(
        (np.concatenate(vals), (np.concatenate(rows), np.concatenate(cols))),
        shape=(n, n),
    )


def embedding_E(nf, nc) -> sp.csr_matrix:
    """Coarse point k onto fine point 2k, per axis."""
    cc = np.indices(tuple(nc)).reshape(len(nc), -1)
    fine = np.ravel_multi_index(tuple(2 * cc), tuple(nf))
    n_c = int(np.prod(nc))
    return sp.csr_matrix(
        (np.ones(n_c), (fine, np.arange(n_c))), shape=(int(np.prod(nf)), n_c)
    )


def poisson7_decoupled(ns) -> sp.csr_matrix:
    """The 7-point Poisson operator with its Dirichlet values eliminated:
    a cell with any coordinate 0 or n-1 is a boundary cell and its row is
    the identity; an interior row is 6 on the diagonal and -1 for each of
    its six face neighbours that is interior itself."""
    ns = tuple(int(n) for n in ns)
    coords = np.indices(ns).reshape(len(ns), -1)
    hi = np.asarray(ns)[:, None] - 1
    interior = np.all((coords > 0) & (coords < hi), axis=0)
    gid = np.arange(int(np.prod(ns)))
    rows = [gid]
    cols = [gid]
    vals = [np.where(interior, 6.0, 1.0)]
    for axis in range(len(ns)):
        for step in (-1, 1):
            nb = coords.copy()
            nb[axis] += step
            inside = np.all((nb > 0) & (nb < hi), axis=0)
            ok = interior & inside
            rows.append(gid[ok])
            cols.append(np.ravel_multi_index(tuple(nb[:, ok]), ns))
            vals.append(np.full(int(ok.sum()), -1.0))
    n = int(np.prod(ns))
    return sp.csr_matrix(
        (np.concatenate(vals), (np.concatenate(rows), np.concatenate(cols))),
        shape=(n, n),
    )


class Level:
    def __init__(self, A, nf, nc):
        self.A, self.nf, self.nc = A.tocsr(), tuple(nf), tuple(nc)
        self.P = (stencil_S(nf) @ embedding_E(nf, nc)).tocsr()
        self.R = self.P.T.tocsr()
        self.dinv = 1.0 / self.A.diagonal()


class Hierarchy:
    def __init__(self, A, dims, coarse_threshold=1000, omega=0.8, pre=1, post=1):
        self.levels = []
        self.omega, self.pre, self.post = float(omega), int(pre), int(post)
        A, nf = sp.csr_matrix(A, dtype=np.float64), tuple(int(n) for n in dims)
        while int(np.prod(nf)) > coarse_threshold:
            nc = tuple((n + 1) // 2 for n in nf)
            if nc == nf or min(nc) < 3:
                break
            lvl = Level(A, nf, nc)
            self.levels.append(lvl)
            A, nf = (lvl.R @ lvl.A @ lvl.P).tocsr(), nc
        self.coarse_A = A.toarray()

    def vcycle(self, b, level=0):
        """One zero-start V-cycle for ``A_level x = b``."""
        if level == len(self.levels):
            return np.linalg.solve(self.coarse_A, b)
        lvl, om = self.levels[level], self.omega
        x = np.zeros_like(b)
        for _ in range(self.pre):
            x = x + om * lvl.dinv * (b - lvl.A @ x)
        ec = self.vcycle(lvl.R @ (b - lvl.A @ x), level + 1)
        x = x + lvl.P @ ec
        for _ in range(self.post):
            x = x + om * lvl.dinv * (b - lvl.A @ x)
        return x
