"""The padded-ELL device-fault footprint guard (tpu.py:_ell_guard_check).

At the 64^3-node tet-elasticity operator (786432 dofs, 27955824 nnz) the
generic padded-ELL lowering's gather kernels FAULTED a real TPU worker
outright, while SD and BSR on the same operator ran fine. The guard
used to live only in tools/bench_irregular.py's leg selection; this file
pins its library form: the lowering itself refuses (real TPU) or warns
(host mesh) BEFORE staging an over-ceiling ELL program, whether ELL was
auto-selected (every fast path declined) or forced by strict-bits mode —
so no documented env-flag combination can reach the device-fault path.

The 64^3 strict-bits case itself is covered two ways: the ceiling
arithmetic against the RECORDED 64^3 operator shape (no assembly — the
mean-width lower bound already exceeds the ceiling), and the end-to-end
refusal exercised at test scale with the ceiling shrunk via env.
"""
import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu.models import assemble_poisson, gather_pvector
from partitionedarrays_jl_tpu.parallel.tpu import (
    ELL_MAX_GATHER,
    DeviceMatrix,
    ELLFootprintError,
    TPUBackend,
)


def _backend():
    import jax

    return TPUBackend(devices=jax.devices()[:8])


def test_recorded_64cube_footprint_exceeds_default_ceiling():
    """The operator that faulted the worker must be refused by the
    DEFAULT ceiling: at the 64^3 shape (786432 dofs, 27955824 nnz —
    the operator chip_smoke.py's irregular leg assembles, where the
    refusal itself is checked on the chip) even the MEAN row width — a
    lower bound on the padded ELL width — puts the footprint past the
    ceiling."""
    dofs, nnz = 786432, 27955824
    mean_width_floor = -(-nnz // dofs)  # ceil; true padded L is >= this
    assert dofs * mean_width_floor > ELL_MAX_GATHER
    # ...while the largest ELL program ever measured healthy (32^3,
    # 98304 dofs x width<=64) stays well inside it
    assert 98304 * 64 < ELL_MAX_GATHER


def test_strict_bits_refuses_cleanly_past_ceiling(monkeypatch):
    """Strict-bits forces the pure-ELL lowering; past the ceiling the
    build must raise the typed error (enforced mode stands in for the
    real-TPU platform check) instead of staging the faulting program."""
    monkeypatch.setenv("PA_TPU_STRICT_BITS", "1")
    monkeypatch.setenv("PA_TPU_ELL_GUARD", "1")
    monkeypatch.setenv("PA_TPU_ELL_MAX_GATHER", "1000")
    backend = _backend()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (16, 16, 16))
        with pytest.raises(ELLFootprintError) as ei:
            DeviceMatrix(A, parts.backend)
        assert "strict-bits" in str(ei.value)
        assert "PA_TPU_ELL_MAX_GATHER" in str(ei.value)
        return True

    assert pa.prun(driver, backend, (2, 2, 2))


def test_auto_selected_ell_refuses_cleanly_past_ceiling(monkeypatch):
    """Same refusal when ELL is AUTO-selected: a scattered (non-banded)
    operator declines DIA, SD/BSR are off, so ELL is the fallback — and
    past the ceiling the guard must refuse with the auto-select wording,
    not the strict-bits one."""
    monkeypatch.setenv("PA_TPU_ELL_GUARD", "1")
    monkeypatch.setenv("PA_TPU_ELL_MAX_GATHER", "100")
    monkeypatch.setenv("PA_TPU_SD", "0")
    monkeypatch.setenv("PA_TPU_BSR", "0")
    backend = _backend()
    n, per = 800, 100  # 8 parts x 100 owned rows

    def driver(parts):
        def trip(p, k):
            rows_ = np.arange(p * per, (p + 1) * per, dtype=np.int64)
            loc = rows_ - p * per
            # pseudo-random couplings INSIDE the part (they must land in
            # the A_oo block): per-row offsets scatter, so the union
            # blows the DIA_MAX_OFFSETS cap and DIA detection declines
            I = np.concatenate([rows_, rows_, rows_])
            J = np.concatenate(
                [
                    rows_,
                    p * per + (loc * 7 + 13) % per,
                    p * per + (loc * 11 + 5) % per,
                ]
            )
            V = np.concatenate(
                [np.full(per, 10.0), np.full(per, 1.0), np.full(per, 1.0)]
            )
            return (I, J, V)[k]

        I = pa.map_parts(lambda p: trip(p, 0), parts)
        J = pa.map_parts(lambda p: trip(p, 1), parts)
        V = pa.map_parts(lambda p: trip(p, 2), parts)
        A = pa.PSparseMatrix.from_coo(I, J, V, n, n, ids="global")
        with pytest.raises(ELLFootprintError) as ei:
            DeviceMatrix(A, parts.backend)
        assert "declined" in str(ei.value)
        return True

    assert pa.prun(driver, backend, 8)


def test_below_ceiling_strict_bits_runs_cleanly(monkeypatch):
    """The other half of the regression contract: UNDER the ceiling the
    strict-bits ELL program runs end-to-end — device CG bit-identical to
    the sequential oracle, exactly as tests/test_strict_bits.py pins."""
    monkeypatch.setenv("PA_TPU_STRICT_BITS", "1")
    monkeypatch.setenv("PA_TPU_ELL_GUARD", "1")
    backend = _backend()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8, 8))
        x, info = pa.cg(A, b, x0=x0, tol=1e-9, maxiter=400)
        assert info["converged"]
        return gather_pvector(x), info["iterations"]

    xt, it_t = pa.prun(driver, backend, (2, 2, 2))
    xs, it_s = pa.prun(driver, pa.sequential, (2, 2, 2))
    assert it_t == it_s
    np.testing.assert_array_equal(np.asarray(xt), np.asarray(xs))


def test_host_mesh_warns_instead_of_refusing(monkeypatch):
    """Default (auto) mode on a CPU mesh: over-ceiling ELL is slow, not
    unsafe — the lowering warns and proceeds, and the staged program
    still computes the right product."""
    monkeypatch.setenv("PA_TPU_STRICT_BITS", "1")
    monkeypatch.setenv("PA_TPU_ELL_MAX_GATHER", "1000")
    monkeypatch.delenv("PA_TPU_ELL_GUARD", raising=False)
    backend = _backend()

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (12, 12, 12))
        with pytest.warns(UserWarning, match="padded-ELL"):
            dA = DeviceMatrix(A, parts.backend)
        from partitionedarrays_jl_tpu.parallel.tpu import (
            DeviceVector, make_spmv_fn,
        )

        dx = DeviceVector.from_pvector(xe, parts.backend, dA.col_layout)
        y = make_spmv_fn(dA)(dx.data)
        host = gather_pvector(b)
        dev = np.asarray(y)
        got = np.zeros_like(host)
        for p, iset in enumerate(A.rows.partition.part_values()):
            got[iset.oid_to_gid] = dev[p, : iset.num_oids]
        np.testing.assert_array_equal(got, host)  # strict: bit-exact
        return True

    assert pa.prun(driver, backend, (2, 2, 2))
