"""Host time gets an owner on the thread it runs on (PR 36): `annotate`
writes to the calling thread's record, a served request's `submit`,
forecast, idle worker and hand-off are spans and counters of the program,
and the warm-up's lowering and compile are counted where they happen.
12^3 cells, one part on one CPU device of a `TPUBackend`, float32."""
import importlib
import threading

import jax
import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import telemetry
from partitionedarrays_jl_tpu.service import AdmissionRejected, SolveService

T = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")

NS, TOL = (12, 12, 12), 1e-5
PHASES = ("stage", "solve", "wait", "fetch", "finish")
LEAVES = ("operator", "pack", "put", "d2h", "lift")
REQUEST_PATH = (
    "service.submit_us", "service.forecast_us", "service.idle_us",
    "service.handoff_us", "service.answers",
)


def assemble():
    backend = pa.TPUBackend(devices=jax.devices()[:1])
    return pa.prun(
        lambda parts: pa.assemble_poisson(
            parts, NS, dtype=np.float32, decoupled=True
        ),
        backend, (1, 1, 1),
    )


@pytest.fixture(scope="module")
def system():
    """The operator, staged and with its K=1 and K=4 block programs made,
    so that no test below waits for a compile inside a slab."""
    A, b, _xe, x0 = assemble()
    for k in (1, 4):
        pa.cg(A, B=[b] * k, X0=[x0] * k, tol=TOL)
    return A, b, x0


def test_a_block_solve_keeps_its_leaves_while_clients_submit(system, monkeypatch):
    """Four clients submit WHILE the worker's thread is inside a block
    solve (held in its pack until all four have): every leaf of that
    solve lands in its own record and none in a request's."""
    A, b, x0 = system
    in_solve, submitted = threading.Event(), threading.Event()
    pack = T._block_on_cols_layout

    def held_pack(*a, **k):
        if not in_solve.is_set():
            in_solve.set()
            assert submitted.wait(30.0)
        return pack(*a, **k)

    monkeypatch.setattr(T, "_block_on_cols_layout", held_pack)
    telemetry.clear_history()
    svc = SolveService(A, kmax=4).start()
    handles, errors = [svc.submit(b, x0=x0, tol=TOL)], []
    arrived = threading.Barrier(4 + 1)

    def client():
        try:
            assert in_solve.wait(30.0)
            h = svc.submit(b, x0=x0, tol=TOL)
            handles.append(h)
            arrived.wait(30.0)
            h.wait(60.0)
        except BaseException as e:  # surfaced on the test's thread
            errors.append(e)

    clients = [threading.Thread(target=client, daemon=True) for _ in range(4)]
    for t in clients:
        t.start()
    arrived.wait(30.0)
    submitted.set()
    for t in clients:
        t.join(90.0)
    svc.shutdown()
    assert not errors and not any(t.is_alive() for t in clients)
    assert len(handles) == 5
    assert all(h.wait(0.0)[1]["converged"] for h in handles)
    records = telemetry.record_history()
    block = [r for r in records if r.solver == "block-cg"]
    requests = [r for r in records if r.solver == "service-request"]
    assert len(block) >= 2 and len(requests) == 5
    for r in block:
        assert all(r.timings.get(k, -1.0) >= 0.0 for k in PHASES + LEAVES), (
            r.timings
        )
    assert [r.timings for r in requests] == [{}] * 5


def test_the_request_path_counters_grow_as_one_request_passes(system):
    A, b, x0 = system
    before = telemetry.counters("service")
    svc = SolveService(A, kmax=4).start()
    _x, info = svc.submit(b, x0=x0, tol=TOL).wait(60.0)
    svc.shutdown()
    assert info["converged"]
    after = telemetry.counters("service")
    grew = {k: after[k] - before.get(k, 0) for k in REQUEST_PATH}
    assert all(isinstance(after[k], int) for k in REQUEST_PATH)
    assert grew["service.answers"] == 1
    assert 0 < grew["service.forecast_us"] <= grew["service.submit_us"]
    # the worker found its queue empty when it started, or when it came
    # back from the slab to the shutdown: one of them at the least
    assert grew["service.idle_us"] > 0
    assert grew["service.handoff_us"] >= 0


def test_a_rejected_submit_still_counts_its_time(system):
    """On a clock that moves a millisecond a reading: the refused call's
    stretch is counted, in whole microseconds, and no answer is."""
    A, b, x0 = system
    ticks = iter(range(10**6))
    svc = SolveService(
        A, kmax=4, queue_depth=1, clock=lambda: 1e-3 * next(ticks)
    )
    svc.submit(b, x0=x0, tol=TOL)
    before = telemetry.counters("service")
    with pytest.raises(AdmissionRejected):
        svc.submit(b, x0=x0, tol=TOL)
    after = telemetry.counters("service")
    spent = after["service.submit_us"] - before["service.submit_us"]
    assert spent >= 1000 and spent % 1000 == 0
    inside = after["service.forecast_us"] - before["service.forecast_us"]
    assert 1000 <= inside <= spent
    assert after["service.admitted"] == before["service.admitted"]
    assert after.get("service.answers", 0) == before.get("service.answers", 0)
    svc.drain()


def host_spans(path: str) -> list:
    """``[(name, stats)]`` of a profile's `pa:` host events."""
    from jax.profiler import ProfileData

    return [
        (ev.name, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("pa:")
    ]


def test_a_profile_of_one_served_request_holds_its_path(system, tmp_path):
    import glob

    A, b, x0 = system
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        svc = SolveService(A, kmax=4).start()
        handle = svc.submit(b, x0=x0, tol=TOL)
        handle.wait(60.0)
        svc.shutdown()
        # and a slab of two: both ids survive the profile's encoding
        pair = SolveService(A, kmax=4)
        two = [pair.submit(b, x0=x0, tol=TOL) for _ in range(2)]
        pair.drain()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    spans = host_spans(path)
    names = {name for name, _ in spans}
    assert {
        "pa:service:submit", "pa:submit:forecast", "pa:forecast:fingerprint",
        "pa:service:idle", "pa:service:wait", "pa:service:slab",
    } <= names
    submit, _, _ = [s for n, s in spans if n == "pa:service:submit"]
    (wait,) = [s for n, s in spans if n == "pa:service:wait"]
    # the two slabs ran on two threads: told apart by width, not by order
    slab, wide = sorted(
        (s for n, s in spans if n == "pa:service:slab"),
        key=lambda s: int(s["k"]),
    )
    assert int(submit["request"]) == int(wait["request"]) == handle.id
    assert str(slab["requests"]).split("+") == [str(handle.id)]
    assert int(slab["k"]) == 1 and int(slab["trips"]) > 0
    assert str(wide["requests"]).split("+") == [str(h.id) for h in two]
    assert int(wide["k"]) == 2


@pytest.fixture(scope="module")
def first_and_second():
    """A first and a second `pa.cg` on a FRESH operator: each solve's
    record, and the `lowering.*` and `compile.*` counters before, between
    and after."""
    telemetry.install_jax_cache_listeners()
    A, b, _xe, x0 = assemble()

    def read():
        return {**telemetry.counters("lowering"), **telemetry.counters("compile")}

    counts, records = [read()], []
    for _ in range(2):
        _x, info = pa.cg(A, b, x0=x0, tol=TOL)
        assert info["converged"]
        records.append(info.record)
        counts.append(read())
    return records, counts


def test_the_first_solve_owns_the_lowering(first_and_second):
    (first, second), (c0, c1, c2) = first_and_second
    t = first.timings
    assert t["lower"] > 0.0
    assert all(t[k] >= 0.0 for k in ("detect", "layout", "upload"))
    assert t["detect"] + t["layout"] + t["upload"] <= t["lower"] <= first.wall_s
    assert not {"lower", "detect", "layout", "upload"} & set(second.timings)
    wall = c1["lowering.wall_us"] - c0.get("lowering.wall_us", 0)
    assert wall > 0 and abs(1e-6 * wall - t["lower"]) < 0.05
    for k in ("detect_us", "upload_us"):
        grew = c1[f"lowering.{k}"] - c0.get(f"lowering.{k}", 0)
        assert 0 < grew <= wall
    assert c1["lowering.upload_bytes"] > c0.get("lowering.upload_bytes", 0)
    for k in ("wall_us", "detect_us", "upload_us", "upload_bytes"):
        assert c2[f"lowering.{k}"] == c1[f"lowering.{k}"]
        assert isinstance(c2[f"lowering.{k}"], int)


def test_the_first_solve_owns_the_compiles(first_and_second):
    _records, (c0, c1, c2) = first_and_second
    assert c1["compile.programs"] > c0.get("compile.programs", 0)
    for k in ("trace_us", "lower_us", "backend_us"):
        assert c1[f"compile.{k}"] > c0.get(f"compile.{k}", 0)
    for k in ("programs", "trace_us", "lower_us", "backend_us"):
        assert c2[f"compile.{k}"] == c1[f"compile.{k}"]


def test_the_compile_counters_take_each_span_s_self_time():
    """JAX's spans nest: a jit traced inside a jit reports inside the
    outer trace, and a persistent-cache retrieval is timed inside the
    backend compile event that made it. Each counter takes a span's self
    time, so the four `compile.*_us` add up to the time spent. On a
    thread of its own, as a compile's events are its thread's."""
    import jax.monitoring as jm

    telemetry.install_jax_cache_listeners()
    trace = "/jax/core/compile/jaxpr_trace_duration"
    backend = "/jax/core/compile/backend_compile_duration"

    def events():
        # two inner traces end inside an outer one of 1 s; its lowering
        jm.record_event_time_span(trace, 100.25, 100.5)
        jm.record_event_time_span(trace, 100.5, 100.625)
        jm.record_event_time_span(trace, 100.0, 101.0)
        jm.record_event_time_span(
            "/jax/core/compile/jaxpr_to_mlir_module_duration", 101.0, 101.5
        )
        # a load of 0.25 s inside a backend event of 0.75 s; a compile
        jm.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.25
        )
        jm.record_event_time_span(backend, 101.5, 102.25)
        jm.record_event_time_span(backend, 103.0, 103.5)

    before = telemetry.counters("compile")
    t = threading.Thread(target=events)
    t.start()
    t.join(30.0)
    assert not t.is_alive()
    after = telemetry.counters("compile")
    grew = {k: after[k] - before.get(k, 0) for k in after}
    assert grew == {
        "compile.trace_us": 1_000_000, "compile.lower_us": 500_000,
        "compile.cache_load_us": 250_000, "compile.backend_us": 1_000_000,
        "compile.programs": 2,
    }
