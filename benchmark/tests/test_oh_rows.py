"""The reader of `oh_rows_us` (PR 29): on hand-made tuples whose answer can
be worked out on paper, on traces that hold nothing for it, and against the
program itself: the sub-scope the reader looks for is the one the compiled
CG program of a four-part operator writes, and a one-part program writes
none. Counts of a comparison; no device number.
"""
import importlib
import re
import types

import jax
import numpy as np
import pytest

from benchmark import trace as tr
from benchmark.layer_metrics import _scoped, oh_rows_us, spmv_us
from benchmark.layer_metrics.sd_gather_share import components_from_pa

KERNEL = ("pa.axpy_sweep", "pa.spmv_local", "pa_dia_coded_spmv_pfold", "pallas_call:")
OH = ("pa.axpy_sweep", "pa.spmv_local", "oh", "dynamic_update_slice:")
HALO = ("pa.axpy_sweep", "pa.spmv_local", "pa.halo_exchange", "ppermute:")
DOTS = ("pa.axpy_sweep", "pa.dot_allgather", "reduce_sum:")
# a level named like the sub-scope is no boundary row
OTHER = ("pa.axpy_sweep", "pa.dot_allgather", "oh", "mul:")


def cg_ops():
    """Two iterations on [0, 10]: the kernel 2 s, the exchange 1 s, the
    boundary rows 0.5 s, a dot 1 s and, under the dots, an op whose name
    has the component too, 0.25 s; the `while` keeps the rest."""
    return [
        (0.0, 10.0, ("pa.axpy_sweep", "while:")),
        (0.0, 2.0, KERNEL), (2.0, 3.0, HALO), (3.0, 3.5, OH), (3.5, 4.5, DOTS),
        (4.5, 4.75, OTHER),
        (5.0, 7.0, KERNEL), (7.0, 8.0, HALO), (8.0, 8.5, OH), (8.5, 9.5, DOTS),
        (9.5, 9.75, OTHER),
    ]


def make_run(device_ops, iterations, monkeypatch):
    plain = {
        d: [(s, e, "/".join(sc) or "op") for s, e, sc in ops]
        for d, ops in device_ops.items()
    }
    run = types.SimpleNamespace(
        trace=tr.Trace(plain, [(0.0, 10.0, "bench:solve")]),
        traced_records=[{"info": {"iterations": n}} for n in iterations],
    )
    # like the parser, through whatever `scopes_of` is at the time of the call
    monkeypatch.setattr(
        _scoped, "parse",
        lambda path: {
            d: [(s, e, _scoped.scopes_of("/".join(sc))) for s, e, sc in ops]
            for d, ops in device_ops.items()
        },
    )
    monkeypatch.setattr(tr, "find_xplane", lambda log_dir: "unused")
    return run


def test_the_boundary_rows_are_the_ops_behind_the_sub_scope():
    assert oh_rows_us.under_part(OH)
    for other in (KERNEL, HALO, DOTS, OTHER, ()):
        assert not oh_rows_us.under_part(other), other


def test_self_time_per_iteration_on_a_synthetic_trace(monkeypatch):
    run = make_run({0: cg_ops(), 1: cg_ops()}, [2], monkeypatch)
    # 1 s of boundary rows on each of two devices over 2 iterations
    assert oh_rows_us.reduce(run) == pytest.approx(0.5e6)
    # and `spmv_us` keeps counting them: kernel 4 s + boundary rows 1 s
    run = make_run({0: cg_ops(), 1: cg_ops()}, [2], monkeypatch)
    assert spmv_us.reduce(run) == pytest.approx(2.5e6)


def test_a_program_without_the_sub_scope_reads_nothing(monkeypatch):
    parent = [(s, e, tuple(c for c in sc if c != "oh")) for s, e, sc in cg_ops()]
    assert oh_rows_us.reduce(make_run({0: parent}, [2], monkeypatch)) is None
    assert oh_rows_us.reduce(make_run({0: cg_ops()}, [0], monkeypatch)) is None
    for trace in (None, tr.Trace({}, []), tr.Trace({0: []}, [(0.0, 1.0, "bench:solve")])):
        run = types.SimpleNamespace(trace=trace, traced_records=[])
        assert oh_rows_us.reduce(run) is None


def op_names(grid, ns):
    """The `op_name` of every instruction of the compiled CG program of the
    7-point operator on ``grid``, on the CPU devices."""
    pa = importlib.import_module("partitionedarrays_jl_tpu")
    T = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    backend = T.TPUBackend(devices=jax.devices()[: int(np.prod(grid))])
    A = pa.prun(
        lambda parts: pa.assemble_poisson(parts, ns, dtype=np.float32, decoupled=True)[0],
        backend, grid,
    )
    dA = T.device_matrix(A, backend)
    fn = T.make_cg_fn(dA, 1e-5, 50)
    L = dA.col_plan.layout
    z = np.zeros((L.P, L.W), dtype=np.float32)
    text = fn.jit_fn.lower(z, z, z, fn.operands).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def test_the_program_writes_the_name_the_reader_looks_for():
    T = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    assert (oh_rows_us.PHASE, oh_rows_us.PART) == (T.SCOPE_SPMV, T.SCOPE_OH)
    four = [components_from_pa(n) for n in op_names((2, 2, 1), (32, 32, 16))]
    assert sum(1 for c in four if oh_rows_us.under_part(c)) > 0
    one = [components_from_pa(n) for n in op_names((1, 1, 1), (16, 16, 16))]
    assert any(c for c in one)  # it names its scopes
    assert sum(1 for c in one if oh_rows_us.under_part(c)) == 0
