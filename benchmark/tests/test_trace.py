"""The interval arithmetic, the work count and the trace-reading metrics,
on hand-made events whose answers can be worked out on paper."""
import types

import pytest

from benchmark import trace as tr
from benchmark.layer_metrics import (
    cg_iter_hbm_roofline, collective_share, device_idle_share,
    host_per_solve_ms, iter_us,
)


def test_union_clip_busy_gaps():
    ops = [(0.0, 2.0, "a"), (1.0, 3.0, "b"), (5.0, 6.0, "c"), (5.5, 5.6, "d")]
    assert tr.union(ops) == [(0.0, 3.0), (5.0, 6.0)]
    assert tr.clip(ops, 2.5, 5.5) == [(2.5, 3.0), (5.0, 5.5)]
    assert tr.busy(ops, 0.0, 10.0) == pytest.approx(4.0)
    assert tr.busy(ops, 2.5, 5.5) == pytest.approx(1.0)
    assert tr.gaps(ops, -1.0, 7.0) == [(-1.0, 0.0), (3.0, 5.0), (6.0, 7.0)]
    assert tr.union([]) == [] and tr.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_self_time_goes_to_the_innermost_op():
    # a while that holds two kernels and a collective; then a lone copy
    ops = [
        (0.0, 10.0, "while"), (1.0, 4.0, "kernel"), (4.0, 5.0, "%all-reduce.1 all-reduce"),
        (6.0, 9.0, "kernel"), (12.0, 13.0, "copy"),
    ]
    got = tr.self_times(ops)
    assert got == pytest.approx(
        {"while": 3.0, "kernel": 6.0, "%all-reduce.1 all-reduce": 1.0, "copy": 1.0}
    )
    assert sum(got.values()) == pytest.approx(tr.busy(ops, 0.0, 20.0))


def test_op_names_are_cut_short_and_collectives_are_xlas_own():
    hlo = ("%fusion.6 = (f32[1,7864320]{1,0:T(1,128)}, f32[1,7864320]{1,0:T(1,128)S(1)}) "
           "fusion(f32[3,7864320]{1,0:T(4,128)} %get-tuple-element.330), kind=kLoop")
    assert tr.short_name(hlo) == "%fusion.6 fusion"
    call = ('%body.7 = (f32[61440,128]{1,0:T(8,128)}) custom-call(f32[7,2]{1,0} %x), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert tr.short_name(call) == "%body.7 custom-call tpu_custom_call"
    assert tr.short_name("bench:solve") == "bench:solve"
    yes = ["%all-reduce.3 all-reduce", "%ar all-reduce-start", "%cp.1 collective-permute-done",
           "%all-gather.2 all-gather", "reduce-scatter", "%x all-to-all"]
    no = ["%fusion.12 fusion", "%all-reduce-like fusion", "%while.17 while",
          "%copy-start.1 copy-start", "%reduce.4 reduce", "%body.7 custom-call tpu_custom_call"]
    assert all(tr.is_collective(n) for n in yes)
    assert not any(tr.is_collective(n) for n in no)
    start = ("%collective-permute-start.1 = (f32[36864]{0}, f32[36864]{0}) "
             "collective-permute-start(f32[36864]{0} %slice.3), source_target_pairs={{0,1}}")
    assert tr.is_collective(tr.short_name(start))


def make_run(device_ops, spans, iterations, **kw):
    t = tr.Trace(device_ops, sorted(spans))
    base = dict(
        trace=t, traced_records=[{"info": {"iterations": n}} for n in iterations],
        mix={"entry": "cg", "preconditioner": None},
        peaks={"hbm_bytes_per_s": 800e9}, dofs_per_chip=8_000_000, itemsize=4,
        timings={},
    )
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_trace_metrics_on_a_hand_made_trace():
    # two solves of 1 s wall each; the device is busy 0.8 s in each, 100
    # iterations each; 0.5 s lie between them
    ops = [(0.1, 0.9, "while"), (0.2, 0.4, "%all-reduce.1 all-reduce"), (1.6, 2.4, "while")]
    spans = [(0.0, 1.0, "bench:solve"), (1.5, 2.5, "bench:solve"),
             (0.0, 0.1, "pa:cg:stage")]
    run = make_run({0: ops, 1: ops}, spans, [100, 100])
    assert host_per_solve_ms.reduce(run) == pytest.approx(200.0)
    assert iter_us.reduce(run) == pytest.approx(8000.0)
    assert device_idle_share.reduce(run) == pytest.approx(100 * (1 - 1.6 / 2.5))
    assert collective_share.reduce(run) == pytest.approx(100 * 0.2 / 1.6)
    # 10 passes x 4 B x 8e6 = 320 MB at 800 GB/s = 400 us, against 8000 us
    assert cg_iter_hbm_roofline.cg_iteration_bytes(8_000_000, 4) == 320_000_000
    assert cg_iter_hbm_roofline.reduce(run) == pytest.approx(5.0)
    bd = tr.breakdown(run.trace)
    assert bd["device_ops"][0][0] == "while"
    assert dict(bd["idle_gaps"]) == pytest.approx(
        {"(no span)": 0.5, "bench:solve": 0.3, "pa:cg:stage": 0.1}
    )


def test_readers_return_nothing_where_there_is_nothing_to_read():
    empty = make_run({}, [(0.0, 1.0, "bench:solve")], [10])
    no_trace = make_run({}, [], [], trace=None)
    idle = make_run({0: []}, [(0.0, 1.0, "bench:solve")], [10])
    for run in (empty, no_trace, idle):
        for metric in (host_per_solve_ms, iter_us, device_idle_share,
                       collective_share, cg_iter_hbm_roofline):
            assert metric.reduce(run) is None
    # a preconditioned mix has no plain-CG count; one chip has no collective
    ops = [(0.1, 0.9, "while")]
    pcg = make_run({0: ops}, [(0.0, 1.0, "bench:solve")], [7],
                   mix={"entry": "pcg", "preconditioner": "gmg"})
    assert cg_iter_hbm_roofline.reduce(pcg) is None
    assert collective_share.reduce(pcg) is None
    assert iter_us.reduce(pcg) is not None
