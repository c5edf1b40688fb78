"""The served deployment (PR 34): a `SolveService` with its worker thread,
concurrent submitters that `wait()`, and the benchmark's plain reference
(`benchmark/builders/poisson7.py`, which imports nothing of the program).

The semantics held: every admitted request is answered exactly once, with
the answer of ITS OWN right-hand side and start vector, whatever slab it
rode in and whichever columns rode beside it. Every answer is compared
with the reference CG in float32 and with the solo `pa.cg` of the same
request, over slab widths 1 to 4, with and without start vectors. Then
`wait` on a failed, a suspended and a timed-out request, the new counters
and span, and the pin that the compiled block program is the same with and
without them. 12^3 cells, one part on one CPU device, float32.
"""
import importlib
import os
import sys
import threading
from contextlib import nullcontext

import jax
import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import telemetry
from partitionedarrays_jl_tpu.parallel.health import NonFiniteError
from partitionedarrays_jl_tpu.service import SolveService

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
ref = importlib.import_module("benchmark.builders.poisson7")
service_mod = importlib.import_module("partitionedarrays_jl_tpu.service.service")

NS = (12, 12, 12)
TOL = 1e-5
MAXITER = 500


class World:
    """The operator on a one-device `TPUBackend`, and four requests: images
    of one seeded field under symmetries of the grid, so each has its own
    right-hand side and start vector and all take the same Krylov work."""

    def __init__(self):
        backend = pa.TPUBackend(devices=jax.devices()[:1])
        self.A = pa.prun(
            lambda parts: pa.assemble_poisson(
                parts, NS, dtype=np.float32, decoupled=True
            )[0],
            backend, (1, 1, 1),
        )
        u = ref.base_field(NS, 7, 3, 4)
        b = ref.apply_reference(u).astype(np.float32)
        x0 = ref.boundary_only(u).astype(np.float32)
        syms = ref.symmetries(NS, (1, 1, 1))
        picks = np.random.default_rng(20261003).choice(len(syms), 4, replace=False)
        self.b = [ref.image(b, syms[int(i)]) for i in picks]
        self.x0 = [ref.image(x0, syms[int(i)]) for i in picks]
        cols = self.A.cols
        self.pb = [pa.scatter_pvector_values(v.ravel(), cols) for v in self.b]
        self.px0 = [pa.scatter_pvector_values(v.ravel(), cols) for v in self.x0]
        self._reference, self._solo = {}, {}

    def start(self, i: int, with_x0: bool):
        return self.x0[i] if with_x0 else np.zeros(NS, dtype=np.float32)

    def reference(self, i: int, with_x0: bool):
        """The plain reference CG in float32 on request ``i``."""
        key = (i, with_x0)
        if key not in self._reference:
            x, info = ref.reference_cg(
                self.b[i], self.start(i, with_x0), TOL, MAXITER, "float32"
            )
            assert info["converged"]
            self._reference[key] = (x.astype(np.float64), info["iterations"])
        return self._reference[key]

    def solo(self, i: int, with_x0: bool):
        """The solo `pa.cg` of request ``i``, gathered."""
        key = (i, with_x0)
        if key not in self._solo:
            x, info = pa.cg(
                self.A, self.pb[i], x0=self.px0[i] if with_x0 else None, tol=TOL
            )
            assert info["converged"]
            self._solo[key] = (
                pa.gather_pvector(x).reshape(NS).astype(np.float64),
                info["iterations"],
            )
        return self._solo[key]

    def residual_rel(self, i: int, with_x0: bool, x: np.ndarray) -> float:
        """``||b - A_ref x|| / ||b - A_ref x0||`` in float64: the number the
        cell's `correct` is decided by."""
        b = self.b[i].astype(np.float64)
        r0 = b - ref.apply_reference(self.start(i, with_x0).astype(np.float64))
        r = b - ref.apply_reference(x)
        return float(np.linalg.norm(r) / np.linalg.norm(r0))


@pytest.fixture(scope="module")
def world():
    return World()


def serve(world, width: int, with_x0: bool, kmax: int = 4):
    """``width`` clients on threads of their own, each submitting its own
    request and waiting for its own answer; the worker thread is started
    once all have submitted, so they ride ONE slab of that width."""
    svc = SolveService(world.A, kmax=kmax)
    answers, errors = {}, []

    def client(i):
        try:
            h = svc.submit(
                world.pb[i], x0=world.px0[i] if with_x0 else None, tol=TOL
            )
            answers[i] = h.wait(60.0)
        except BaseException as e:  # surfaced by the test's thread
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(width)]
    for t in threads:
        t.start()
    while svc.pending() < width and not errors:
        pass
    svc.start()
    for t in threads:
        t.join(120.0)
    stats = svc.shutdown(drain=True)
    assert not errors, errors
    return answers, stats


@pytest.mark.parametrize("with_x0", [True, False], ids=["x0", "no_x0"])
@pytest.mark.parametrize("width", [1, 2, 3, 4])
def test_every_served_answer_is_its_own_requests(world, width, with_x0):
    answers, stats = serve(world, width, with_x0)
    assert sorted(answers) == list(range(width))
    assert stats["slabs"] == 1 and stats["completed"] == width
    assert stats["rejected"] == stats["ejected"] == stats["retried_solo"] == 0
    for i, (x, info) in answers.items():
        assert info["converged"] and "resolved_via" not in info
        got = pa.gather_pvector(x).reshape(NS).astype(np.float64)
        x_ref, it_ref = world.reference(i, with_x0)
        x_solo, it_solo = world.solo(i, with_x0)
        # the cell's own limit; served answers and the reference CG's both
        # read 0.86e-5 to 0.95e-5 here
        assert world.residual_rel(i, with_x0, got) <= 1e-4
        # two float32 CGs of one recurrence, stopped by the same relative
        # test after the same 28 iterations: the answers differ by their
        # rounding, 1.0e-7 to 1.6e-7 of the answer's norm here; an answer
        # scaled by 1.001 is 1e-3 away and a neighbour's (another image of
        # the field) 1.3
        scale = np.linalg.norm(x_ref)
        assert np.linalg.norm(got - x_ref) <= 1e-5 * scale
        assert info["iterations"] == it_ref
        # a column of a block solve walks its solo trajectory: equal, or
        # 8e-8 apart where a reduction was ordered otherwise
        assert info["iterations"] == it_solo
        assert np.linalg.norm(got - x_solo) <= 1e-6 * scale
        # and it is not a neighbour's
        for j in range(4):
            if j != i:
                other = world.reference(j, with_x0)[0]
                assert np.linalg.norm(got - other) > 0.1 * scale


def test_wait_returns_what_result_returns_and_holds_no_lock(world):
    svc = SolveService(world.A, kmax=2).start()
    h = svc.submit(world.pb[0], x0=world.px0[0], tol=TOL)
    x, info = h.wait(60.0)
    assert h.done() and (x, info) == h.result()
    assert h.wait(0.0)[0] is x  # terminal: returns at once, again
    # a waiter blocks on the request's own event, never on the service's
    # lock: submit and pending go on while another thread waits
    h2 = svc.submit(world.pb[1], x0=world.px0[1], tol=TOL)
    t = threading.Thread(target=h2.wait, args=(60.0,))
    t.start()
    assert svc.pending() >= 0
    t.join(120.0)
    assert not t.is_alive() and h2.done()
    svc.shutdown(drain=True)


def sequential_system():
    return pa.prun(
        lambda parts: pa.assemble_poisson(parts, (8, 8)), pa.sequential, (2, 2)
    )


def test_wait_on_a_timed_out_a_failed_and_a_suspended_request():
    A, b, _xe, x0 = sequential_system()
    # timed out: nobody drives the service; the request stays what it was
    svc = SolveService(A, retries=0)
    h = svc.submit(b, x0=x0, tol=1e-9)
    with pytest.raises(TimeoutError, match="still queued"):
        h.wait(0.01)
    assert h.state == "queued" and not h.done()
    # ... and is answered once somebody does
    svc.drain()
    assert h.wait(0.0)[1]["converged"]
    # failed: a poisoned right-hand side, no retries; the typed error of a
    # solo solve comes out of wait as it comes out of result
    bad = b.copy()

    def poison(i, vals):
        if int(i.part) == 0:
            np.asarray(vals)[0] = np.nan

    pa.map_parts(poison, bad.rows.partition, bad.values)
    svc.start()
    hb = svc.submit(bad, x0=x0, tol=1e-9)
    ok = svc.submit(b, x0=x0, tol=1e-9)
    with pytest.raises(NonFiniteError):
        hb.wait(60.0)
    assert hb.state == "failed" and ok.wait(60.0)[1]["converged"]
    svc.shutdown(drain=True)
    # suspended: a shutdown that does not drain, before the request ran
    svc2 = SolveService(A)
    hs = svc2.submit(b, x0=x0, tol=1e-9)
    waited = []
    t = threading.Thread(
        target=lambda: waited.append(pytest.raises(RuntimeError, hs.wait, 60.0))
    )
    t.start()
    svc2.shutdown(drain=False)
    t.join(60.0)
    assert hs.state == "suspended" and "resubmit" in str(waited[0].value)


def test_the_counters_add_up_under_load():
    A, b, _xe, x0 = sequential_system()
    before = telemetry.counters("service")
    ticks = iter(range(10**6))
    svc = SolveService(A, kmax=4, clock=lambda: 0.5 * next(ticks))
    hs = [svc.submit(b, x0=x0, tol=1e-9) for _ in range(6)]  # slabs of 4 and 2
    svc.start()
    infos = [h.wait(60.0)[1] for h in hs]
    stats = svc.shutdown(drain=True)
    after = telemetry.counters("service")

    def grew(name):
        return after.get(name, 0) - before.get(name, 0)

    assert stats["slabs"] == grew("service.slabs") == 2
    assert grew("service.slab_columns") == stats["completed"] == 6
    # every column of a slab makes the slab's trips on this operator
    assert grew("service.slab_trips") == infos[0]["iterations"] + infos[4]["iterations"]
    # the fake clock ticks half a second a reading: each request waited
    # whole ticks between its submission and its slab's formation
    waited = grew("service.queue_wait_us")
    assert waited >= 6 * 500_000 and waited % 500_000 == 0


def test_one_slab_span_a_slab_with_its_width_and_trips(monkeypatch):
    A, b, _xe, x0 = sequential_system()
    opened = []

    class Span:
        def __init__(self, name, **stats):
            self.name, self.stats = name, dict(stats)
            opened.append(self)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            self.closed = True

        def set_metadata(self, **stats):
            assert not hasattr(self, "closed")  # joined while still open
            self.stats.update(stats)

    monkeypatch.setattr(service_mod, "profiler_span", Span)
    svc = SolveService(A, kmax=4)
    hs = [svc.submit(b, x0=x0, tol=1e-9) for _ in range(5)]
    svc.drain()
    # five submits each opened their own span, with the id they were given
    assert [
        s.stats["request"] for s in opened if s.name == "pa:service:submit"
    ] == [h.id for h in hs]
    slabs = [s for s in opened if s.name == "pa:service:slab"]
    assert len(slabs) == 2 and len(opened) == 5 + 2
    assert [s.stats["k"] for s in slabs] == [4, 1]
    assert [s.stats["requests"] for s in slabs] == [
        "+".join(str(h.id) for h in hs[:4]), str(hs[4].id)
    ]
    assert [s.stats["trips"] for s in slabs] == [
        hs[0].result()[1]["iterations"], hs[4].result()[1]["iterations"]
    ]
    assert all(s.closed for s in opened)


def test_the_block_program_is_the_same_with_and_without_the_spans(world, monkeypatch):
    """The span and the counters are host-side: the block body lowered
    where the service runs it (inside the open `pa:service:slab` span, the
    counters bumped) is, text for text, the one lowered with the span a
    null context and the counters left alone."""
    from partitionedarrays_jl_tpu.parallel.tpu import (
        _matrix_operands,
        device_matrix,
        make_cg_fn,
    )

    texts = []
    block_solve = SolveService._block_solve

    def lowering_block_solve(self, B, X0, tol, maxiter):
        dA = device_matrix(self.A, B[0].values.backend)
        layout = dA.col_plan.layout
        z = np.zeros((layout.P, layout.W, len(B)), dtype=np.float32)
        fn = make_cg_fn(dA, tol=tol, maxiter=maxiter, rhs_batch=len(B))
        texts.append(
            fn.jit_fn.lower(z, z, z[..., 0], _matrix_operands(dA)).as_text()
        )
        return block_solve(self, B, X0, tol, maxiter)

    monkeypatch.setattr(SolveService, "_block_solve", lowering_block_solve)

    def one_slab():
        svc = SolveService(world.A, kmax=2)
        hs = [svc.submit(world.pb[i], x0=world.px0[i], tol=TOL) for i in range(2)]
        svc.drain()
        assert all(h.result()[1]["converged"] for h in hs)

    one_slab()
    monkeypatch.setattr(service_mod, "profiler_span", lambda *a, **k: nullcontext())
    monkeypatch.setattr(SolveService, "_count_columns", lambda *a: None)
    one_slab()
    assert len(texts) == 2 and texts[0] == texts[1]
    assert "stablehlo.while" in texts[0]
