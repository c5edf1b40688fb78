"""`transfer_matrix_free_share`, `vcycle_halo_share` and
`transfer_hbm_roofline` on hand-made counters and scoped ops whose answers
can be worked out on paper; and None, without raising, where the program
has no such counter (the parent's side of the PR that brought them), the
trace holds no V-cycle, or the run holds no trace."""
import importlib
import types

import pytest

from benchmark import trace as tr
from benchmark.layer_metrics import (
    _scoped, transfer_hbm_roofline, transfer_matrix_free_share,
    vcycle_halo_share,
)

SPANS = [(0.0, 10.0, "bench:solve")]
PEAKS = {"hbm_bytes_per_s": 819e9}
CFG_X4 = {"cells": [384, 384, 192], "part_grid": [2, 2, 1]}
GMG = {"entry": "pcg", "preconditioner": "gmg"}

L0, L1 = ("pa.axpy_sweep", "pa.gmg.l0"), ("pa.axpy_sweep", "pa.gmg.l0", "pa.gmg.l1")


def vcycle_ops(halo: bool):
    """One V-cycle of two levels on [0, 10] (seconds): level 0's smoother
    product 2 (its exchange 1 inside it where ``halo``), the residual's
    product inside the restriction 1, the restriction's own work 1 (its
    face exchange 0.5 inside it where ``halo``), the prolongation 0.5;
    level 1 (smoother, coarse) 1.5; the outer dot 1 and an unscoped copy
    0.5 outside any level."""
    sm = L0 + ("pa.gmg.smooth", "pa.spmv_local")
    rs = L0 + ("pa.gmg.restrict",)
    ops = [
        (0.0, 2.0, sm),
        (2.0, 3.0, rs + ("pa.spmv_local",)),
        (3.0, 4.0, rs),
        (5.0, 5.5, L0 + ("pa.gmg.prolong",)),
        (6.0, 7.0, L1 + ("pa.gmg.smooth", "pa.spmv_local")),
        (7.0, 7.5, L1 + ("pa.gmg.coarse",)),
        (8.0, 9.0, ("pa.axpy_sweep", "pa.dot_allgather")),
        (9.0, 9.5, ()),
    ]
    if halo:
        ops += [
            (1.0, 2.0, sm + ("pa.halo_exchange",)),
            (3.5, 4.0, rs + ("pa.halo_exchange",)),
        ]
    return sorted(ops)


def scoped_run(monkeypatch, device_ops, iterations, cfg=CFG_X4, mix=GMG):
    plain = {
        d: [(s, e, "/".join(sc) or "op") for s, e, sc, *_ in ops]
        for d, ops in device_ops.items()
    }
    monkeypatch.setattr(_scoped, "parse", lambda path: device_ops)
    monkeypatch.setattr(tr, "find_xplane", lambda log_dir: "unused")
    return types.SimpleNamespace(
        trace=tr.Trace(plain, SPANS),
        traced_records=[{"info": {"iterations": n}} for n in iterations],
        cfg=cfg, mix=mix, peaks=PEAKS, itemsize=4,
    )


def counter_run(monkeypatch, counters: dict):
    telemetry = importlib.import_module("partitionedarrays_jl_tpu.telemetry")
    monkeypatch.setattr(
        telemetry, "counters",
        lambda prefix="": {k: v for k, v in counters.items() if k.startswith(prefix)},
    )
    return types.SimpleNamespace(
        trace=tr.Trace({0: [(0.5, 9.5, "%while while")]}, SPANS),
        traced_records=[{"info": {"iterations": 1}}],
    )


def transfer_counters(stencil=0, separable=0, operator=0, assembled=0):
    return {
        "gmg.transfer.levels": stencil + separable + operator + assembled,
        "gmg.transfer.stencil": stencil,
        "gmg.transfer.separable": separable,
        "gmg.transfer.operator": operator,
        "gmg.transfer.assembled": assembled,
    }


@pytest.mark.parametrize(
    "forms,share",
    [
        ({"separable": 1, "stencil": 4}, 100.0),  # four chips, this program
        ({"operator": 1, "stencil": 4}, 80.0),  # four chips, level 0 as S
        ({"stencil": 5}, 100.0),  # one chip
        ({"assembled": 2, "stencil": 2}, 50.0),
    ],
    ids=["separable", "operator", "one-part", "assembled"],
)
def test_the_share_of_levels_staged_matrix_free(monkeypatch, forms, share):
    run = counter_run(monkeypatch, transfer_counters(**forms))
    assert transfer_matrix_free_share.reduce(run) == pytest.approx(share)


def test_the_halo_share_of_the_vcycle(monkeypatch):
    # inside the levels: 2 + 1 + 1 + 0.5 + 1 + 0.5 = 6 s, of which the
    # two exchanges 1 + 0.5; the dot and the copy are outside any level
    run = scoped_run(monkeypatch, {0: vcycle_ops(True), 1: vcycle_ops(True)}, [1])
    assert vcycle_halo_share.reduce(run) == pytest.approx(25.0)
    # one part: no exchange in the cycle reads 0, not nothing
    run = scoped_run(monkeypatch, {0: vcycle_ops(False)}, [1])
    assert vcycle_halo_share.reduce(run) == pytest.approx(0.0)


def test_the_transfer_roofline(monkeypatch):
    # the transfers: the restriction's own 1 s (its exchange within it) and
    # the prolongation's 0.5, the residual's product left out: 1.5 s a
    # device over 3 V-cycles
    run = scoped_run(monkeypatch, {0: vcycle_ops(True), 1: vcycle_ops(True)}, [2, 1])
    least = transfer_hbm_roofline.transfer_bytes(CFG_X4["cells"], CFG_X4["part_grid"], 4) / 819e9
    assert transfer_hbm_roofline.reduce(run) == pytest.approx(100 * least / 0.5)


def test_the_transfer_bytes_of_the_hierarchy():
    """Five levels with a transfer at 384x384x192 on (2,2,1), as at 192^3
    on one chip: 192^3 + 96^3 + 48^3 + 24^3 + 12^3 points a chip, 3.25
    passes of 4 bytes each: 105.15 MB, 128.4 us at 819 GB/s."""
    x4 = transfer_hbm_roofline.level_cells(CFG_X4["cells"])
    assert x4 == [(384, 384, 192), (192, 192, 96), (96, 96, 48), (48, 48, 24), (24, 24, 12)]
    assert transfer_hbm_roofline.level_cells([192] * 3)[-1] == (12, 12, 12)
    for cells, grid in ((CFG_X4["cells"], CFG_X4["part_grid"]), ([192] * 3, [1] * 3)):
        b = transfer_hbm_roofline.transfer_bytes(cells, grid, 4)
        assert b == pytest.approx(3.25 * 4 * (192**3 + 96**3 + 48**3 + 24**3 + 12**3))
        assert 1e6 * b / 819e9 == pytest.approx(128.39, abs=0.01)


def test_nothing_where_there_is_nothing(monkeypatch):
    # the parent's program counts no transfer form
    parent = counter_run(monkeypatch, {"lowering.coded.operators": 1})
    assert transfer_matrix_free_share.reduce(parent) is None
    # a CG trace has no V-cycle; a CG cell has no transfers to count
    cg = [(0.0, 2.0, ("pa.axpy_sweep", "pa.spmv_local")),
          (2.0, 3.0, ("pa.axpy_sweep", "pa.spmv_local", "pa.halo_exchange"))]
    run = scoped_run(monkeypatch, {0: cg}, [4])
    assert vcycle_halo_share.reduce(run) is None
    assert transfer_hbm_roofline.reduce(run) is None
    run = scoped_run(monkeypatch, {0: vcycle_ops(True)}, [1], mix={"entry": "cg"})
    assert transfer_hbm_roofline.reduce(run) is None
    no_trace = types.SimpleNamespace(trace=None, traced_records=[], mix=GMG)
    for m in (transfer_matrix_free_share, vcycle_halo_share, transfer_hbm_roofline):
        assert m.reduce(no_trace) is None, m.__name__
