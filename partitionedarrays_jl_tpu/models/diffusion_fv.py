"""Cell-centred finite-volume diffusion with a variable coefficient: the
stored-coefficient stencil.

The second-order operator of HPGMG-FV (Adams, Brown, Shalf, Van Straalen,
Strohmaier, Williams; hpgmg.org; `finite-volume/source/operators.fv2.c`,
formerly `operators.7pt.c`) with ``a = 0``, ``b = 1``: the discretisation
of

    -div(beta grad u) = f    on the unit cube, u = 0 on the walls

on ``n_0 x ... x n_{D-1}`` cells of width ``h_d = 1 / n_d``, the
coefficient stored on the faces:

    (A u)_c = sum over the 2 D faces F of cell c of
              h_d(F)^-2 * beta_F * (u_c - u_nb(F))

``beta_F`` is ``beta`` at the face's centre and ``u_nb(F)`` the neighbour
across ``F``. Across a wall face the neighbour is the ghost value ``-u_c``
(the wall value is 0 at the face, linear closure), so a wall face adds
``2 beta_F u_c`` to the diagonal. Every cell is an unknown: there are no
identity rows. ``A`` is symmetric positive definite, and every one of its
2 D + 1 diagonals varies from row to row, so on a device it takes the
streaming-DIA lowering where the constant stencil of `assemble_poisson`
takes the coded one (`parallel/tpu.py` ``dia_mode``).

Vectorised NumPy per part, as the drivers beside it; any Cartesian part
grid, with the ghost layer discovered from the triplets' columns.
"""
from __future__ import annotations

import math
from typing import Callable, Sequence, Tuple

import numpy as np

from ..parallel.backends import AbstractPData, map_parts
from ..parallel.prange import (
    add_gids,
    cartesian_partition,
    no_ghost,
    p_cartesian_indices,
)
from ..parallel.psparse import PSparseMatrix
from ..utils.helpers import check


def diffusion_fv_coo(
    ranges: Sequence[np.ndarray],
    ns: Sequence[int],
    beta: Callable[..., np.ndarray],
    dtype=np.float64,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Triplets ``(I, J, V)``, by global cell id, of the rows of the box of
    cells ``ranges[0] x ... x ranges[D-1]`` (each a run of consecutive
    global coordinates) of the operator in the module docstring.

    ``beta(x_0, ..., x_{D-1})`` is called once per axis with coordinate
    arrays that broadcast to that axis's faces of the box (the face
    coordinate along the axis, cell centres along the others), and is
    evaluated from global coordinates alone, so both cells of a face get
    the same float64 value whichever part owns them. A row's entries are
    formed in float64 and rounded once to ``dtype``."""
    ns = tuple(int(n) for n in ns)
    dim = len(ns)
    ranges = [np.asarray(r, dtype=np.int64) for r in ranges]
    shape = tuple(len(r) for r in ranges)

    def along(d, a):
        """``a`` (one value per step of axis d) shaped to broadcast."""
        return np.asarray(a).reshape(
            tuple(len(a) if k == d else 1 for k in range(dim))
        )

    centres = [(r + 0.5) / n for r, n in zip(ranges, ns)]
    # int32 triplets wherever the grid fits, as `_assemble_stencil_coo`
    idt = np.int32 if math.prod(ns) < 2**31 else np.int64
    gid = np.ravel_multi_index(
        np.meshgrid(*ranges, indexing="ij"), ns
    ).astype(idt)
    strides = [math.prod(ns[d + 1 :]) for d in range(dim)]
    diag = np.zeros(shape, dtype=np.float64)
    I, J, V = [], [], []
    for d in range(dim):
        # the faces normal to axis d: cell i lies between faces i and i + 1
        at = [along(k, centres[k]) for k in range(dim)]
        at[d] = along(d, np.append(ranges[d], ranges[d][-1] + 1) / ns[d])
        fshape = tuple(s + (k == d) for k, s in enumerate(shape))
        w = np.broadcast_to(
            np.asarray(beta(*at), dtype=np.float64), fshape
        ) * float(ns[d]) ** 2
        for side, off in ((0, -1), (1, 1)):
            wf = np.take(w, np.arange(side, side + shape[d]), axis=d)
            inside = np.broadcast_to(
                along(d, (ranges[d] + off >= 0) & (ranges[d] + off < ns[d])),
                shape,
            )
            diag += np.where(inside, wf, 2.0 * wf)  # ghost -u_c across a wall
            rows = gid[inside]
            I.append(rows)
            J.append(rows + idt(off * strides[d]))
            V.append(-wf[inside])
    I.append(gid.ravel())
    J.append(gid.ravel())
    V.append(diag.ravel())
    return (
        np.concatenate(I), np.concatenate(J),
        np.concatenate(V).astype(dtype),
    )


def assemble_diffusion_fv(
    parts: AbstractPData,
    ns: Sequence[int],
    beta: Callable[..., np.ndarray],
    dtype=np.float64,
) -> PSparseMatrix:
    """The operator of the module docstring as a `PSparseMatrix`: rows a
    Cartesian partition of the cells, columns the rows plus the one ghost
    layer the stencil reaches. ``beta`` as in `diffusion_fv_coo`; HPGMG-FV's
    own coefficient is ``c1 + c2 tanh(c3 (r - 0.25))`` with ``r`` the
    distance from the cube's centre. ``dtype`` assembles in the target
    precision: float64 sums rounded once."""
    ns = tuple(int(n) for n in ns)
    check(min(ns) >= 2, "assemble_diffusion_fv needs >= 2 cells per dimension")
    rows = cartesian_partition(parts, ns, no_ghost)
    coo = map_parts(
        lambda ci: diffusion_fv_coo(ci.ranges, ns, beta, dtype),
        p_cartesian_indices(parts, ns, no_ghost),
    )
    I, J, V = (map_parts(lambda c, k=k: c[k], coo) for k in range(3))
    cols = add_gids(rows, J)
    return PSparseMatrix.from_coo(I, J, V, rows, cols, ids="global")
