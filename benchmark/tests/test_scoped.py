"""The readers PR 26 added: the scoped device ops (`_scoped.py`), the
program's host spans (`_host_spans.py`) and the metrics over them, on
hand-made tuples whose answers can be worked out on paper, and the xplane
decoder on a file encoded here field by field."""
import types

import pytest

from benchmark import trace as tr
from benchmark.layer_metrics import (
    _host_spans, _scoped, body_update_us, dots_us, fetch_ms, halo_us,
    scope_coverage, spmv_us, stage_ms, staged_mb_per_solve, unowned_host_ms,
    vcycle_coarse_share,
)

SCOPED = (
    spmv_us, body_update_us, dots_us, halo_us, vcycle_coarse_share,
    scope_coverage,
)
SPANS = (stage_ms, fetch_ms, unowned_host_ms)

AXPY, SPMV, DOTS, HALO = (
    ("pa.axpy_sweep",), ("pa.axpy_sweep", "pa.spmv_local"),
    ("pa.axpy_sweep", "pa.dot_allgather"),
    ("pa.axpy_sweep", "pa.spmv_local", "pa.halo_exchange"),
)


def test_phase_and_level_are_the_innermost_of_their_kind():
    assert _scoped.phase_of(()) is None and _scoped.level_of(()) is None
    assert _scoped.phase_of(HALO) == "pa.halo_exchange"
    assert _scoped.level_of(HALO) is None
    nested = ("pa.axpy_sweep", "pa.gmg.l0", "pa.gmg.restrict", "pa.gmg.l1",
              "pa.gmg.smooth", "pa.spmv_local")
    assert _scoped.phase_of(nested) == "pa.spmv_local"
    assert _scoped.level_of(nested) == 1
    # a level scope alone names no phase but the one around it
    assert _scoped.phase_of(("pa.axpy_sweep", "pa.gmg.l2")) == "pa.axpy_sweep"
    assert _scoped.level_of(("pa.gmg.l12",)) == 12
    assert _scoped.scopes_of(
        "jit(fn)/while/body/pa.axpy_sweep/pa.spmv_local/pa_dia_coded_spmv/"
        "pallas_call:"
    ) == SPMV
    assert _scoped.scopes_of("") == () == _scoped.scopes_of("jit(fn)/while:")


def cg_ops():
    """Two iterations of a `while` on [0, 10]: each a kernel (2 s), the
    exchange inside it (1 s), a dot (1 s) and an unscoped copy (0.5 s);
    the while itself (scoped as the body) keeps what is left."""
    return [
        (0.0, 10.0, AXPY),
        (0.5, 2.5, SPMV), (2.5, 3.5, HALO), (3.5, 4.5, DOTS), (4.5, 5.0, ()),
        (5.0, 7.0, SPMV), (7.0, 8.0, HALO), (8.0, 9.0, DOTS), (9.0, 9.5, ()),
    ]


def test_seconds_by_phase_add_up_to_busy_time():
    by = _scoped.seconds_by({0: cg_ops()}, 0.0, 10.0, _scoped.phase_of)
    assert by == pytest.approx({
        "pa.axpy_sweep": 1.0, "pa.spmv_local": 4.0, "pa.halo_exchange": 2.0,
        "pa.dot_allgather": 2.0, None: 1.0,
    })
    assert sum(by.values()) == pytest.approx(10.0)
    # clipped to a stretch; two devices add up; a 4th element is let through
    named = [o + ("%x",) for o in cg_ops()]
    by = _scoped.seconds_by({0: cg_ops(), 1: named}, 5.0, 10.0, _scoped.phase_of)
    assert by["pa.spmv_local"] == pytest.approx(4.0)
    assert sum(by.values()) == pytest.approx(10.0)


def make_run(device_ops, spans, iterations, monkeypatch):
    """A run whose scoped ops are ``device_ops`` (the parser is bypassed)
    and whose `Trace` holds the same intervals under op names."""
    plain = {
        d: [(s, e, "/".join(sc) or "op") for s, e, sc, *_ in ops]
        for d, ops in device_ops.items()
    }
    run = types.SimpleNamespace(
        trace=tr.Trace(plain, sorted(spans)),
        traced_records=[{"info": {"iterations": n}} for n in iterations],
    )
    monkeypatch.setattr(_scoped, "parse", lambda path: device_ops)
    monkeypatch.setattr(tr, "find_xplane", lambda log_dir: "unused")
    return run


def test_phase_metrics_per_iteration(monkeypatch):
    spans = [(0.0, 10.0, "bench:solve")]
    run = make_run({0: cg_ops(), 1: cg_ops()}, spans, [2], monkeypatch)
    # 4 s of kernels on each of two devices over 2 iterations: 2 s each
    assert spmv_us.reduce(run) == pytest.approx(2e6)
    assert halo_us.reduce(run) == pytest.approx(1e6)
    assert dots_us.reduce(run) == pytest.approx(1e6)
    assert body_update_us.reduce(run) == pytest.approx(0.5e6)
    assert scope_coverage.reduce(run) == pytest.approx(90.0)
    assert vcycle_coarse_share.reduce(run) is None  # no level in a CG trace


def test_coarse_share_of_two_levels(monkeypatch):
    l0 = ("pa.axpy_sweep", "pa.gmg.l0", "pa.gmg.smooth", "pa.spmv_local")
    l1 = ("pa.axpy_sweep", "pa.gmg.l0", "pa.gmg.l1", "pa.gmg.smooth")
    l2 = l1[:3] + ("pa.gmg.l2", "pa.gmg.coarse")
    ops = [(0.0, 6.0, l0), (6.0, 7.5, l1), (7.5, 8.0, l2), (8.0, 10.0, DOTS)]
    run = make_run({0: ops}, [(0.0, 10.0, "bench:solve")], [1], monkeypatch)
    assert vcycle_coarse_share.reduce(run) == pytest.approx(25.0)
    assert scope_coverage.reduce(run) == pytest.approx(100.0)


def test_a_program_without_scopes_reads_nothing(monkeypatch):
    ops = [(0.0, 4.0, ()), (5.0, 6.0, ())]
    run = make_run({0: ops}, [(0.0, 10.0, "bench:solve")], [3], monkeypatch)
    for m in SCOPED:
        assert m.reduce(run) is None, m.__name__


def host_trace():
    """One solve on [0, 10]. The device works on [3, 6]. Staging [0.5, 2.5]
    holds two pack/put pairs; the idle time after the device is cut by the
    fetch's two leaves and the finish; 0.5 s at each end has no owner."""
    spans = [
        (0.0, 10.0, "bench:solve"), (0.2, 9.8, "pa:solve"),
        (0.5, 2.5, "pa:cg:stage"), (0.5, 0.6, "pa:stage:operator"),
        (0.6, 1.2, "pa:stage:pack"), (1.2, 1.5, "pa:stage:put"),
        (1.5, 2.2, "pa:stage:pack"), (2.2, 2.5, "pa:stage:put"),
        (2.5, 2.9, "pa:cg:solve"), (2.9, 6.0, "pa:cg:wait"),
        (6.0, 9.0, "pa:cg:fetch"), (6.0, 8.0, "pa:fetch:d2h"),
        (8.0, 9.0, "pa:fetch:lift"), (9.0, 9.5, "pa:cg:finish"),
    ]
    return [(3.0, 6.0, "while")], spans


def test_idle_time_goes_to_the_innermost_span():
    ops, spans = host_trace()
    by = _host_spans.idle_by_span(ops, spans, [(0.0, 10.0)])
    assert by == pytest.approx({
        "bench:solve": 0.4, "pa:solve": 0.6, "pa:stage:operator": 0.1,
        "pa:stage:pack": 1.3, "pa:stage:put": 0.6, "pa:cg:solve": 0.4,
        "pa:cg:wait": 0.1, "pa:fetch:d2h": 2.0, "pa:fetch:lift": 1.0,
        "pa:cg:finish": 0.5,
    })
    assert sum(by.values()) == pytest.approx(7.0)
    assert _host_spans.phase_wall_s(spans, [(0.0, 10.0)], "stage") == pytest.approx(2.0)
    # a leaf named like a phase is not one: `pa:stage:pack` does not end in `:stage`
    assert _host_spans.phase_wall_s(spans, [(0.0, 10.0)], "fetch") == pytest.approx(3.0)
    # only the part inside a solve counts
    assert _host_spans.phase_wall_s(spans, [(1.0, 7.0)], "stage") == pytest.approx(1.5)


def test_span_metrics_per_solve():
    ops, spans = host_trace()
    later = [(s + 20.0, e + 20.0, n) for s, e, n in spans]
    run = types.SimpleNamespace(
        trace=tr.Trace({0: ops + [(23.0, 26.0, "while")]}, sorted(spans + later)),
        traced_records=[{"info": {"iterations": 1}}] * 2,
    )
    assert stage_ms.reduce(run) == pytest.approx(2000.0)
    assert fetch_ms.reduce(run) == pytest.approx(3000.0)
    assert unowned_host_ms.reduce(run) == pytest.approx(1000.0)
    # the parent's program: stage and solve spans only, no root
    old = [s for s in spans if s[2] in ("bench:solve", "pa:cg:stage", "pa:cg:solve")]
    run = types.SimpleNamespace(trace=tr.Trace({0: ops}, sorted(old)), traced_records=[{}])
    assert stage_ms.reduce(run) == pytest.approx(2000.0)
    assert fetch_ms.reduce(run) is None
    assert unowned_host_ms.reduce(run) is None


@pytest.mark.parametrize("metric", SCOPED + SPANS, ids=lambda m: m.__name__.split(".")[-1])
def test_readers_return_none_on_an_empty_trace(metric):
    for trace in (None, tr.Trace({}, []), tr.Trace({0: []}, [(0.0, 1.0, "bench:solve")]),
                  tr.Trace({0: [(0.0, 1.0, "op")]}, [])):
        run = types.SimpleNamespace(trace=trace, traced_records=[])
        assert metric.reduce(run) is None


def test_counter_metric_reads_the_programs_counters():
    from partitionedarrays_jl_tpu import telemetry

    telemetry.reset_counters("solve")
    ops, spans = host_trace()
    run = types.SimpleNamespace(trace=tr.Trace({0: ops}, sorted(spans)))
    assert staged_mb_per_solve.reduce(run) is None  # the parent counts nothing
    telemetry.bump("solve.calls", 4)
    telemetry.bump("solve.staged_bytes", 4 * 62_914_560)
    assert staged_mb_per_solve.reduce(run) == pytest.approx(62.91456)
    # off the chip (no device op in the trace) the per-layer line stays silent
    assert staged_mb_per_solve.reduce(types.SimpleNamespace(trace=None)) is None
    telemetry.reset_counters("solve")


# -- the decoder, on an xplane encoded here ---------------------------------


def varint(x):
    out = bytearray()
    while True:
        out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
        x >>= 7
        if not x:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def entry(key, message):
    return field(1, key) + field(2, message)


def test_parse_reads_scopes_from_the_event_metadata(tmp_path):
    tf_op, other = 7, 8
    stat_meta = (
        field(5, entry(tf_op, field(1, tf_op) + field(2, "tf_op")))
        + field(5, entry(other, field(1, other) + field(2, "hlo_category")))
    )
    metas = {
        1: ("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
            "jit(fn)/pa.axpy_sweep/while/body/pa.dot_allgather/reduce_sum:"),
        2: ("%while.17 = (f32[8]{0}) while((f32[8]{0}) %t)", ""),
    }
    event_meta = b"".join(
        field(4, entry(i, field(1, i) + field(2, name)
                       + field(5, field(1, other) + field(5, "loop fusion"))
                       + (field(5, field(1, tf_op) + field(5, op)) if op else b"")))
        for i, (name, op) in metas.items()
    )
    # a double stat (fixed 64-bit) on the event itself has to be skipped
    double_stat = field(4, field(1, other) + varint(2 << 3 | 1) + b"\0" * 8)
    ops_line = (
        field(2, "XLA Ops") + field(3, 1_000_000_000)
        + field(4, field(1, 2) + field(2, 0) + field(3, 10_000_000))
        + field(4, field(1, 1) + field(2, 2_000_000) + field(3, 3_000_000) + double_stat)
    )
    other_line = field(2, "XLA Modules") + field(3, 1_000_000_000) + field(
        4, field(1, 2) + field(2, 0) + field(3, 10_000_000))
    device = field(2, "/device:TPU:3") + field(3, ops_line) + field(3, other_line) \
        + event_meta + stat_meta
    host = field(2, "/host:CPU") + field(3, ops_line) + event_meta + stat_meta
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, host) + field(1, device))
    got = _scoped.parse(str(path), with_names=True)
    assert list(got) == [3]
    (s0, e0, sc0, n0), (s1, e1, sc1, n1) = got[3]
    assert (sc0, n0) == ((), "%while.17")
    assert (sc1, n1) == (("pa.axpy_sweep", "pa.dot_allgather"), "%fusion.6")
    assert (s0, e0) == pytest.approx((1.0, 1.00001))
    assert (s1, e1) == pytest.approx((1.000002, 1.000005))
    assert [len(o) for o in _scoped.parse(str(path))[3]] == [3, 3]
