"""TPU backend tests on a virtual 8-device CPU mesh.

The `mpiexec -n 8` analog of the reference's MPI suite (SURVEY.md §4): the
same driver bodies run under the TPU backend, and the results are compared
against the sequential oracle — the determinism gate of BASELINE.md.
"""
import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu.models import assemble_poisson, cg, gather_pvector, poisson_fdm_driver
from partitionedarrays_jl_tpu.parallel.tpu import (
    DeviceVector,
    device_matrix,
    make_exchange_fn,
    make_spmv_fn,
)


def test_backend_protocol():
    import jax

    assert len(jax.devices()) == 8, "conftest must provide 8 virtual CPU devices"
    parts = pa.tpu.get_part_ids((2, 2))
    assert parts.shape == (2, 2) and list(parts) == [0, 1, 2, 3]
    assert parts.backend is pa.tpu
    # map_parts preserves the backend identity through planning code
    doubled = pa.map_parts(lambda p: p * 2, parts)
    assert doubled.backend is pa.tpu
    g = pa.gather(doubled)
    assert g.backend is pa.tpu
    assert pa.i_am_main(parts)


def test_too_many_parts_rejected():
    with pytest.raises(AssertionError):
        pa.tpu.get_part_ids(64)


def test_device_vector_roundtrip():
    def driver(parts):
        r = pa.prange(parts, (6, 6), pa.with_ghost)
        v = pa.PVector(
            pa.map_parts(lambda i: i.lid_to_gid.astype(np.float64), r.partition), r
        )
        dv = DeviceVector.from_pvector(v, parts.backend)
        v2 = dv.to_pvector()
        for a, b in zip(v.values, v2.values):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        return True

    assert pa.prun(driver, pa.tpu, (2, 2))


def test_compiled_exchange_matches_host():
    def driver(parts):
        r = pa.prange(parts, (6, 6), pa.with_ghost)
        mk = lambda: pa.PVector(
            pa.map_parts(
                lambda i: np.where(
                    i.lid_to_part == i.part, i.lid_to_gid.astype(np.float64), -1.0
                ),
                r.partition,
            ),
            r,
        )
        # host path
        vh = mk()
        pa.exchange_values(vh.values, vh.values, r.exchanger)
        # device path
        vd = mk()
        dv = DeviceVector.from_pvector(vd, parts.backend)
        dv.data = make_exchange_fn(r, parts.backend)(dv.data)
        v2 = dv.to_pvector()
        for a, b in zip(vh.values, v2.values):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        return True

    assert pa.prun(driver, pa.tpu, (2, 2))


def test_compiled_exchange_periodic_3d():
    def driver(parts):
        r = pa.prange(parts, (4, 4, 4), pa.with_ghost, (True, True, True))
        v = pa.PVector(
            pa.map_parts(
                lambda i: np.where(
                    i.lid_to_part == i.part, i.lid_to_gid.astype(np.float64), -1.0
                ),
                r.partition,
            ),
            r,
        )
        dv = DeviceVector.from_pvector(v, parts.backend)
        dv.data = make_exchange_fn(r, parts.backend)(dv.data)
        v2 = dv.to_pvector()
        for i, vals in zip(r.partition, v2.values):
            assert np.array_equal(np.asarray(vals), i.lid_to_gid.astype(np.float64))
        return True

    assert pa.prun(driver, pa.tpu, (2, 2, 2))


def test_compiled_assembly_matches_host():
    def driver(parts):
        r = pa.prange(parts, (6, 6), pa.with_ghost)
        vh = pa.PVector.full(1.0, r)
        vh.assemble()
        vd = pa.PVector.full(1.0, r)
        dv = DeviceVector.from_pvector(vd, parts.backend)
        dv.data = make_exchange_fn(r, parts.backend, combine="add")(dv.data)
        v2 = dv.to_pvector()
        # device add-combine accumulates into owners; host then zeroes
        # ghosts — compare owned regions only
        for i, a, b in zip(r.partition, vh.values, v2.values):
            assert np.array_equal(
                np.asarray(a)[: i.num_oids], np.asarray(b)[: i.num_oids]
            )
        return True

    assert pa.prun(driver, pa.tpu, (2, 2))


def test_compiled_spmv_matches_host():
    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        dA = device_matrix(A, parts.backend)
        dx = DeviceVector.from_pvector(x_exact, parts.backend, dA.col_layout)
        y = make_spmv_fn(dA)(dx.data)
        host = gather_pvector(b)
        dev = np.asarray(y)
        got = np.zeros_like(host)
        for p, iset in enumerate(A.rows.partition.part_values()):
            got[iset.oid_to_gid] = dev[p, : iset.num_oids]
        # XLA emits fused multiply-adds in the ELL row fold; NumPy cannot,
        # so individual entries may differ by the FMA rounding (<= ~2 ulp)
        # even though the accumulation order is identical.
        np.testing.assert_allclose(got, host, rtol=1e-14, atol=1e-14)
        return True

    assert pa.prun(driver, pa.tpu, (2, 2))


def test_fdm_on_tpu_backend_matches_sequential():
    """The BASELINE.md determinism gate: the same driver, same grid, on the
    sequential oracle and the TPU backend. Iteration counts must be equal
    and the solutions equal to machine precision."""
    err_s, info_s = pa.prun(poisson_fdm_driver, pa.sequential, (2, 2, 2), (10, 10, 10))
    err_t, info_t = pa.prun(poisson_fdm_driver, pa.tpu, (2, 2, 2), (10, 10, 10))
    assert err_s < 1e-5 and err_t < 1e-5
    assert info_t["converged"]
    assert info_s["iterations"] == info_t["iterations"]
    assert abs(err_s - err_t) < 1e-12


def test_fdm_on_tpu_single_part():
    err, info = pa.prun(poisson_fdm_driver, pa.tpu, (1, 1), (8, 8))
    assert err < 1e-5 and info["converged"]


def test_cg_dispatches_to_device():
    """pa.models.cg on TPU-backend data must route to the compiled path and
    agree with the host solve."""

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (8, 8))
        x, info = cg(A, b, x0=x0, tol=1e-12)
        return float((x - x_exact).norm()), info["iterations"]

    err_t, it_t = pa.prun(driver, pa.tpu, (2, 2))
    err_s, it_s = pa.prun(driver, pa.sequential, (2, 2))
    assert err_t < 1e-9
    assert it_t == it_s


def test_coded_dia_mode_spmv_matches_host():
    """Coded-diagonal SpMV path: stencil operators draw each diagonal from
    a tiny value set, so `dia_mode == 'coded'`; the device product must
    still match the host kernel to FMA precision."""

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (48, 48, 48))
        dA = device_matrix(A, parts.backend)
        assert dA.dia_mode == "coded", dA.dia_mode
        assert all(k <= dA.CODE_MAX_VALUES for k in dA.dia_kk)
        dx = DeviceVector.from_pvector(x_exact, parts.backend, dA.col_layout)
        y = make_spmv_fn(dA)(dx.data)
        host = gather_pvector(b)
        dev = np.asarray(y)
        got = np.zeros_like(host)
        for p, iset in enumerate(A.rows.partition.part_values()):
            got[iset.oid_to_gid] = dev[p, : iset.num_oids]
        np.testing.assert_allclose(got, host, rtol=1e-14, atol=1e-14)
        return True

    assert pa.prun(driver, pa.tpu, (2, 2, 2))


def test_coded_dia_mode_cg_matches_sequential():
    """CG through the coded-DIA path converges identically to the
    sequential oracle: same iteration count, values to FMA rounding."""
    err_s, info_s = pa.prun(
        poisson_fdm_driver, pa.sequential, (2, 2, 2), (48, 48, 48), tol=1e-8
    )
    err_t, info_t = pa.prun(
        poisson_fdm_driver, pa.tpu, (2, 2, 2), (48, 48, 48), tol=1e-8
    )
    assert info_s["iterations"] == info_t["iterations"]
    np.testing.assert_allclose(err_t, err_s, rtol=1e-12, atol=1e-12)


def test_padded_layout_spmv_matches_host():
    """The real-TPU vector frame (padded block layout + in-frame coded
    kernel) validated on CPU through the Pallas interpreter: same driver,
    forced `padded=True`, must reproduce the host SpMV."""
    from partitionedarrays_jl_tpu.parallel.tpu import DeviceMatrix, make_spmv_fn as mk

    def driver(parts):
        A, b, x_exact, x0 = assemble_poisson(parts, (12, 12, 12))
        dA = DeviceMatrix(A, parts.backend, padded=True)
        assert dA.dia_mode == "coded" and dA.pallas_plan is not None
        lay = dA.row_layout
        assert lay.padded and lay.o0 > 0 and lay.W % lay.o0 == 0
        dx = DeviceVector.from_pvector(x_exact, parts.backend, dA.col_layout)
        y = make_spmv_fn(dA)(dx.data)
        host = gather_pvector(b)
        dev = np.asarray(y)
        got = np.zeros_like(host)
        for p, iset in enumerate(A.rows.partition.part_values()):
            got[iset.oid_to_gid] = dev[p, lay.o0 : lay.o0 + iset.num_oids]
        np.testing.assert_allclose(got, host, rtol=1e-13, atol=1e-13)
        # every non-owned slot of the result must be exactly zero
        for p, iset in enumerate(A.rows.partition.part_values()):
            row = dev[p].copy()
            row[lay.o0 : lay.o0 + iset.num_oids] = 0
            assert not row.any()
        return True

    assert pa.prun(driver, pa.tpu, (2, 2, 2))


def test_compiled_exchange_irregular_graph():
    """BASELINE config 5's structural core: a fully general (non-Cartesian,
    asymmetric) ghost graph from an explicit IndexSet partition, lowered to
    edge-colored ppermute rounds. Halo update and reverse assembly on the
    compiled path must match the host Exchanger bit-for-bit."""
    # the 10-gid 4-part fixture (reference: test_interfaces.jl:177-207)
    LID_TO_GID = [
        [0, 1, 2, 4, 6, 7],
        [1, 3, 4, 9],
        [5, 6, 7, 4, 3, 9],
        [0, 2, 6, 8, 9],
    ]
    LID_TO_PART = [
        [0, 0, 0, 1, 2, 2],
        [0, 1, 1, 3],
        [2, 2, 2, 1, 1, 3],
        [0, 0, 2, 3, 3],
    ]

    def driver(parts):
        partition = pa.map_parts(
            lambda p: pa.IndexSet(p, LID_TO_GID[p], LID_TO_PART[p]), parts
        )
        rows = pa.PRange(10, partition)

        def mk():
            return pa.PVector(
                pa.map_parts(
                    lambda i: np.where(
                        np.asarray(i.lid_to_part) == i.part,
                        100.0 + np.asarray(i.lid_to_gid),
                        -1.0,
                    ),
                    rows.partition,
                ),
                rows,
            )

        # owner -> ghost halo update
        host = pa.exchange_pvector(mk())
        dv = DeviceVector.from_pvector(mk(), parts.backend)
        out = make_exchange_fn(rows, parts.backend)(dv.data)
        got = DeviceVector(out, rows, dv.layout, parts.backend).to_pvector()
        for a, b in zip(host.values, got.values):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # ghost -> owner assembly (reverse plan, additive combine)
        vh = mk()
        pa.assemble(vh)
        dv2 = DeviceVector.from_pvector(mk(), parts.backend)
        out2 = make_exchange_fn(rows, parts.backend, combine="add")(dv2.data)
        got2 = DeviceVector(out2, rows, dv2.layout, parts.backend).to_pvector()
        for a, b in zip(vh.values, got2.values):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return True

    assert pa.prun(driver, pa.tpu, 4)


def test_multihost_helpers_single_host():
    """Single-host behavior of the multi-controller helpers: init is a
    no-op, process 0 is MAIN, and fetch_global round-trips a sharded
    array (the multi-host escape hatch degrades to device->host copy)."""
    pa.multihost_init()  # must not raise in a single-process run
    assert pa.is_main_process()

    def driver(parts):
        rows = pa.prange(parts, 64)
        v = pa.PVector(
            pa.map_parts(
                lambda i: np.asarray(i.lid_to_gid, dtype=np.float64),
                rows.partition,
            ),
            rows,
        )
        dv = DeviceVector.from_pvector(v, parts.backend)
        host = pa.fetch_global(dv.data)
        assert host.shape == (4, dv.layout.W)
        back = dv.to_pvector()
        for a, b in zip(v.values, back.values):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return True

    assert pa.prun(driver, pa.tpu, 4)


def test_float64_staged_without_x64_says_so_once(monkeypatch):
    """A chip has no float64: without x64, staging narrows float64 host
    data to float32 — once per process that is said out loud, and the
    solver holds the tolerance to the float32 floor it really runs at
    (a float64 `tol=1e-10` solve reports stalled, not converged)."""
    import importlib
    import warnings

    import jax

    tpu_mod = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    monkeypatch.setattr(tpu_mod, "_narrowing_noted", False)
    backend = tpu_mod.TPUBackend(devices=jax.devices()[:4])
    with jax.enable_x64(False):
        with pytest.warns(RuntimeWarning, match="float32 on the device"):
            a = tpu_mod._stage(backend, np.ones((4, 3)), 4)
        assert a.dtype == np.float32
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            err, info = pa.prun(
                poisson_fdm_driver, backend, (2, 2), (8, 8), tol=1e-10
            )
        said = [str(w.message) for w in caught]
        assert not any("staging float64" in m for m in said), "said twice"
        assert any("below the float32 resolution floor" in m for m in said)
        assert info["tol_below_dtype_floor"] and not info["converged"]
        assert err < 1e-4
    # with x64 on, float64 stays float64 and nothing is said
    monkeypatch.setattr(tpu_mod, "_narrowing_noted", False)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert tpu_mod._stage(backend, np.ones((4, 3)), 4).dtype == np.float64


def test_topology_order_fallback_is_announced_not_swallowed(monkeypatch):
    """When `mesh_utils` declines a part grid for the slice's physical
    topology, list order is used WITH a warning naming the order (the
    chip smoke fails on it); any other error is a defect and surfaces."""
    from types import SimpleNamespace

    from jax.experimental import mesh_utils

    devs = [SimpleNamespace(platform="tpu", id=i) for i in range(4)]
    backend = pa.TPUBackend(devices=devs)

    def declines(grid, devices):
        raise NotImplementedError("no assignment for this topology")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", declines)
    with pytest.warns(UserWarning, match=r"list order \[0, 1, 2, 3\]"):
        assert backend._topology_order(4, devs, (2, 2, 1)) == devs

    def broken(grid, devices):
        raise RuntimeError("a defect, not a declined grid")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", broken)
    with pytest.raises(RuntimeError, match="a defect"):
        backend._topology_order(4, devs, (2, 2, 1))


def test_padded_frame_solver_parity(monkeypatch):
    """Force the real-TPU padded kernel frame on the CPU mesh (Pallas
    interpret mode): the compiled CG and SpMV must agree with the host
    oracle exactly as the compact frame does. Without this, padded-frame
    bugs are only observable on real hardware."""
    import importlib

    tpu_mod = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    monkeypatch.setattr(tpu_mod, "_padded_for", lambda backend: True)

    from partitionedarrays_jl_tpu.parallel.tpu import TPUBackend, device_matrix

    def driver(parts):
        A, b, x_exact, x0 = pa.assemble_poisson(parts, (8, 8, 8))
        x, info = pa.cg(A, b, x0=x0, tol=1e-9)
        assert info["converged"]
        err = np.abs(pa.gather_pvector(x) - pa.gather_pvector(x_exact)).max()
        padded = (
            device_matrix(A, parts.backend).padded
            if isinstance(parts.backend, TPUBackend)
            else None
        )
        return float(err), info["iterations"], padded

    err_t, it_t, padded = pa.prun(driver, pa.tpu, (2, 2, 2))
    # the padded DeviceMatrix must actually have been selected
    assert padded
    err_s, it_s, _ = pa.prun(driver, pa.sequential, (2, 2, 2))
    assert it_s == it_t, (it_s, it_t)
    # both solve errors are ~1e-9 magnitudes; compare to rounding noise
    np.testing.assert_allclose(err_t, err_s, rtol=1e-5, atol=1e-12)
    assert err_s < 1e-6 and err_t < 1e-6


def test_stream_staging_after_fused_analysis_padded():
    """Regression (r4 review): an explicit padded=True lowering of a
    banded operator whose offsets exceed the padded plan's reserve takes
    the STREAMING staging branch; when the fused (dense-DIA-free) band
    analysis supplied the det dict, the dense diagonals must be rebuilt
    there — not staged from None as NaN."""
    import jax

    from partitionedarrays_jl_tpu.parallel.tpu import DeviceMatrix, TPUBackend

    backend = TPUBackend(devices=jax.devices()[:1])

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (3, 300000))
        dA = DeviceMatrix(A, backend, padded=True)
        assert dA.dia_mode == "stream"
        vals = np.asarray(dA.dia_vals)
        assert not np.isnan(vals).any()
        return True

    pa.prun(driver, backend, (1, 1))


def test_stencil_fast_declines_unsupported_dtype():
    """Regression (r4 review): dtypes outside the native f32/f64
    envelope must fall back to the generic COO path, not crash the
    fused emitter's post-eligibility check."""

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (8, 8), dtype=np.float16)
        assert A.dtype == np.float16
        return True

    pa.prun(driver, pa.sequential, (2, 1))
