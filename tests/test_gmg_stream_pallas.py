"""The V-cycle's 27-point Galerkin levels through the streamed Mosaic
kernel (`ops/pallas_dia.py:dia_spmv_pallas`, interpreted on the CPU).

On the chip every level below the 7-point one is a 27-diagonal operator
staged as streamed diagonals; where the default blocks do not fit the
kernel's VMEM gate its plan shrinks the block. Here the seam
`tpu._stream_kernel_for` gives those levels the kernel, on a box whose
level 1 (48 x 48 x 24, 432 tiled rows) is too wide for its capped block
and takes 216 rows, and one V-cycle is held to the XLA shifted-slice form
of the same levels and to the float64 reference V-cycle of
`_gmg_reference.py`."""
import importlib

import numpy as np

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import telemetry
from partitionedarrays_jl_tpu.parallel.tpu_gmg import _device_hierarchy

import _gmg_reference as ref

T = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")

NS = (96, 96, 48)


def _stream_counts():
    c = telemetry.counters("lowering.stream")
    return {k.rsplit(".", 1)[1]: v for k, v in c.items()}


def _one_vcycle(b, backend):
    """x1 = V(b) from zero (`pa.gmg_solve` for one step, the body `pa.pcg`
    inlines as its preconditioner) on one part, float32; with each level's
    stream mode and kernel block."""

    def driver(parts):
        A = pa.assemble_poisson(parts, NS, dtype=np.float32, decoupled=True)[0]
        h = pa.gmg_hierarchy(parts, A, NS)
        bv = pa.scatter_pvector_values(b, A.cols)
        x0 = pa.scatter_pvector_values(np.zeros_like(b), A.cols)
        x, info = pa.gmg_solve(h, bv, x0=x0, tol=0.0, maxiter=1)
        assert info["iterations"] == 1
        plans = [
            (l["dA"].dia_mode, (l["dA"].pallas_plan or {}).get("block_rows"))
            for l in _device_hierarchy(h, parts.backend)["levels"]
        ]
        return pa.gather_pvector(x), plans

    return pa.prun(driver, backend, (1, 1, 1))


def test_galerkin_levels_through_the_kernel_match_the_xla_form(monkeypatch):
    """Tolerance 1e-5 of max|V b|, as the four-part V-cycle's test: the
    reading here is some 1.3e-7 on either form, and the kernel sums the
    same float32 products in the same ascending-offset order as the XLA
    form, so the two agree to the bit."""
    b = np.random.default_rng(7).standard_normal(NS).ravel().astype(np.float32)
    before = _stream_counts()
    with monkeypatch.context() as mp:
        mp.setattr(T, "_stream_kernel_for", lambda backend: True)
        got, plans = _one_vcycle(b, pa.TPUBackend())
    counted = {k: v - before.get(k, 0) for k, v in _stream_counts().items()}
    xla, xla_plans = _one_vcycle(b, pa.TPUBackend())

    # level 0 is the 7-point operator (coded); levels 1 and 2 stream,
    # level 1 at a block shrunk from its capped 432 rows
    assert plans == [("coded", None), ("stream", 216), ("stream", 56)], plans
    assert xla_plans == [("coded", None), ("stream", None), ("stream", None)]
    assert counted["operators"] == counted["pallas"] == 2, counted
    assert counted["diagonals"] == 2 * 27

    H = ref.Hierarchy(ref.poisson7_decoupled(NS), NS)
    assert len(H.levels) == len(plans)
    want = H.vcycle(b.astype(np.float64))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() / scale < 1e-5
    assert np.abs(xla - want).max() / scale < 1e-5
    np.testing.assert_array_equal(got, xla)
