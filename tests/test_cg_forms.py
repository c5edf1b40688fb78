"""Every combination of `make_cg_fn`'s mode arguments builds and agrees.

`make_cg_fn(dA, tol, maxiter, precond, fused, rhs_batch)` refuses no
combination of its arguments: the standard and fused bodies, each with
and without the diagonal preconditioner, as a single-vector program and
as a block program. Each is one recurrence, so on the asymmetric 4-part
conformance fixture every column of every form takes the iteration
count of the standard single-vector body on that column and lands on
its solution.
"""
import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu.models import jacobi_preconditioner
from partitionedarrays_jl_tpu.parallel.tpu import (
    DeviceVector,
    TPUBackend,
    _b_on_cols_layout,
    _block_on_cols_layout,
    device_matrix,
    make_cg_fn,
)

from test_fused_cg import _fixture_spd_system

TOL, MAXITER = 1e-10, 200


def _columns(A, b):
    """Four right-hand sides of different difficulty: the fixture's own,
    two rougher ones and a tiny constant forcing."""

    def col(f):
        return pa.PVector(
            pa.map_parts(
                lambda i: np.where(
                    np.asarray(i.lid_to_part) == i.part,
                    f(np.asarray(i.lid_to_gid, dtype=np.float64)),
                    0.0,
                ),
                A.rows.partition,
            ),
            A.rows,
        )

    return [
        b,
        col(lambda g: np.cos(2.0 + 3.0 * g)),
        col(lambda g: (-1.0) ** g * (1.0 + g)),
        col(lambda g: np.full_like(g, 1e-3)),
    ]


@pytest.fixture(scope="module")
def system():
    import jax

    backend = TPUBackend(devices=jax.devices()[:4])

    def driver(parts):
        A, b = _fixture_spd_system(parts)
        return A, _columns(A, b), jacobi_preconditioner(A)

    A, B, mv = pa.prun(driver, backend, 4)
    dA = device_matrix(A, backend)
    zero = pa.PVector.full(0.0, A.cols)
    return {
        "dA": dA,
        "B": B,
        "x0": DeviceVector.from_pvector(zero, backend, dA.col_layout).data,
        "zero": zero,
        "mv": DeviceVector.from_pvector(mv, backend, dA.col_layout).data,
        "oracle": {},
    }


def _oracle(system, precond):
    """The standard single-vector body on every column: iteration count
    and solution frame."""
    if precond not in system["oracle"]:
        dA = system["dA"]
        solve = make_cg_fn(dA, TOL, MAXITER, precond=precond, fused=False)
        out = []
        for b in system["B"]:
            x, rs, rs0, it, _ = solve(
                _b_on_cols_layout(b, dA).data, system["x0"],
                system["mv"] if precond else None,
            )
            assert np.sqrt(float(rs)) <= TOL * max(1.0, np.sqrt(float(rs0)))
            assert int(it) > 3  # a real trajectory
            out.append((int(it), np.asarray(x)))
        system["oracle"][precond] = out
    return system["oracle"][precond]


@pytest.mark.parametrize("rhs_batch", [None, 1, 4], ids=["solo", "k1", "k4"])
@pytest.mark.parametrize("fused", [False, True], ids=["standard", "fused"])
@pytest.mark.parametrize("precond", [False, True], ids=["cg", "pcg"])
def test_every_combination_of_modes_builds_and_agrees(
    system, precond, fused, rhs_batch
):
    dA = system["dA"]
    oracle = _oracle(system, precond)
    solve = make_cg_fn(
        dA, TOL, MAXITER, precond=precond, fused=fused, rhs_batch=rhs_batch
    )
    assert solve.fused is fused
    mv = system["mv"] if precond else None
    if rhs_batch is None:
        x, rs, rs0, it, _ = solve(
            _b_on_cols_layout(system["B"][0], dA).data, system["x0"], mv
        )
        xs, its = np.asarray(x)[..., None], [int(it)]
        rs, rs0 = np.reshape(rs, (1,)), np.reshape(rs0, (1,))
    else:
        K = rhs_batch
        x, rs, rs0, its, _ = solve(
            _block_on_cols_layout(system["B"][:K], dA),
            _block_on_cols_layout([system["zero"]] * K, dA, with_ghosts=True),
            mv,
        )
        xs, its = np.asarray(x), [int(i) for i in np.asarray(its)]
    for k, it in enumerate(its):
        want_it, want_x = oracle[k]
        assert it == want_it, (k, its, [o[0] for o in oracle])
        assert np.sqrt(float(rs[k])) <= TOL * max(
            1.0, np.sqrt(float(rs0[k]))
        )
        np.testing.assert_allclose(xs[..., k], want_x, rtol=0, atol=1e-9)
