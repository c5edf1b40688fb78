"""The queued unit of the solve service: one request, its lifecycle,
and its future-style result surface.

A request moves through::

    queued -> running -> done
                      -> failed        (typed error retained)
                      -> checkpointed  (non-drain shutdown: iterate saved)
    queued ----------> suspended       (non-drain shutdown before it ran)

`SolveService.submit` returns the `SolveRequest` itself — it doubles as
the handle: ``req.result()`` returns ``(x, info)`` for a finished
request and re-raises the retained TYPED error for a failed one (the
same `SolverHealthError` subclass a solo solve would have raised, so
callers keep one error vocabulary whether they batched or not);
``req.wait(timeout)`` blocks until the request is terminal and then
behaves as ``result()`` — what a client of a service with its worker
thread running (`SolveService.start`) calls. Every
request carries its own `SolveRecord` (``req.record``): the queue /
admission / slab / ejection events of its life, plus everything the
slab solves emitted while it was active — the PR 6 observability
contract extended to the request level.
"""
from __future__ import annotations

import threading
from typing import Optional

from ..telemetry.metrics import whole_us
from ..telemetry.registry import registry
from ..telemetry.trace import profiler_span

__all__ = ["SolveRequest"]

#: Lifecycle states (strings, not an enum: they serialize into events
#: and records as-is).
_STATES = (
    "queued", "running", "done", "failed", "checkpointed", "suspended",
)


class SolveRequest:
    """One admitted solve request. Constructed by `SolveService.submit`
    only — the service assigns the id, opens the record, and stamps the
    submission clock reading (deadlines are measured from it)."""

    def __init__(
        self,
        rid: int,
        b,
        x0=None,
        tol: float = 1e-8,
        maxiter: Optional[int] = None,
        deadline: Optional[float] = None,
        retries: int = 1,
        tag: str = "",
    ):
        self.id = int(rid)
        self.b = b
        self.x0 = x0
        self.tol = float(tol)
        self.maxiter = None if maxiter is None else int(maxiter)
        #: Relative wall-clock budget in seconds (service clock units),
        #: measured from submission; None = no deadline.
        self.deadline = None if deadline is None else float(deadline)
        self.retries = int(retries)
        self.tag = tag or f"req-{rid}"
        self.state = "queued"
        self.submitted_at: float = 0.0  # stamped by the service
        #: Service-clock reading at the terminal transition (None while
        #: queued/running) — submitted_at..finished_at is the request's
        #: total latency, the `service.total_s` histogram's unit of
        #: account and the span `tools/patrace.py --service` renders.
        self.finished_at: Optional[float] = None
        #: The service's clock, handed over at admission: what `wait`
        #: reads ``finished_at`` against (``service.handoff_us``).
        self._clock = None
        self.iterations = 0  # committed across chunks
        self.record = None  # SolveRecord, opened by the service
        #: The paspec prediction of the request's cost, or None: set at
        #: `submit` where the norm it needs was at hand there, else
        #: OWED (the arguments `_forecast_from_report` keeps for it)
        #: until the request's first column reports.
        self.forecast: Optional[dict] = None
        self._forecast_owed: Optional[tuple] = None
        #: Distributed-tracing context (`telemetry.tracing.TraceContext`)
        #: propagated by the submitter (the gate stamps its root span's
        #: context here); None = untraced request. The service opens
        #: its ``slab.solve``/``chunk`` spans under it.
        self.trace = None
        self._span_solve = None  # live slab.solve Span while running
        self.checkpoint_path: Optional[str] = None
        self._x = None
        self._info = None
        self._error: Optional[BaseException] = None
        #: Set at every terminal transition (`_set_state`), after the
        #: result or the error is in place: what `wait` blocks on.
        self._terminal = threading.Event()

    # -- state transitions (service-internal) ----------------------------
    def _set_state(self, state: str) -> None:
        assert state in _STATES, state
        self.state = state
        if state not in ("queued", "running"):
            self._terminal.set()

    def _resolve(self, x, info) -> None:
        self._x, self._info = x, info
        self._set_state("done")

    def _fail(self, error: BaseException) -> None:
        self._error = error
        self._set_state("failed")

    # -- the handle surface ----------------------------------------------
    def done(self) -> bool:
        """Terminal in any way: a result, a failure, or a shutdown."""
        return self.state not in ("queued", "running")

    @property
    def error(self) -> Optional[BaseException]:
        return self._error

    def result(self):
        """``(x, info)`` of a finished request; re-raises the retained
        typed error for a failed one. Raises `RuntimeError` while the
        request is still queued/running (the service is pull-driven:
        call `SolveService.drain` / `step`, or run the worker thread
        and `wait`)
        and for shutdown-terminated requests (checkpointed/suspended —
        resubmit from the checkpointed iterate instead)."""
        if self.state == "done":
            return self._x, self._info
        if self.state == "failed":
            raise self._error
        if self.state == "checkpointed":
            raise RuntimeError(
                f"request {self.id}: service shut down mid-solve; the "
                f"iterate was checkpointed at {self.checkpoint_path!r} "
                f"(iteration {self.iterations}) — load it and resubmit"
            )
        if self.state == "suspended":
            raise RuntimeError(
                f"request {self.id}: service shut down before the "
                "request ran — resubmit to a live service"
            )
        raise RuntimeError(
            f"request {self.id} is still {self.state} — drive the "
            "service (drain()/step()) before asking for the result"
        )

    def wait(self, timeout: Optional[float] = None):
        """Block until the request is terminal (done, failed,
        checkpointed or suspended), then behave as `result`: ``(x,
        info)``, or the retained typed error, or the shutdown
        `RuntimeError`. Somebody else has to drive the service
        meanwhile (the worker thread of `SolveService.start`, or
        another thread's ``drain()``). ``timeout`` is in seconds of
        wall clock; past it `TimeoutError` is raised and the request
        stays what it was. No lock is held while waiting.

        Entry to return is the span ``pa:service:wait`` on the caller's
        thread (stat ``request``, the id). A wait that returns an answer
        counts one ``service.answers`` and adds to
        ``service.handoff_us`` the service clock now minus
        ``finished_at``, in whole microseconds: what lies between the
        request's terminal stamp on the thread that drove its slab and
        the answer in this caller's hands (for a wait begun after the
        request had ended, the time the answer lay unclaimed)."""
        with profiler_span("pa:service:wait", request=self.id):
            if not self._terminal.wait(timeout):
                raise TimeoutError(
                    f"request {self.id} is still {self.state} after "
                    f"{timeout} s"
                )
            answer = self.result()
            if self._clock is not None and self.finished_at is not None:
                reg = registry()
                reg.counter("service.handoff_us").inc(
                    whole_us(self._clock() - self.finished_at)
                )
                reg.counter("service.answers").inc()
            return answer

    def __repr__(self):
        return (
            f"SolveRequest(id={self.id}, tag={self.tag!r}, "
            f"state={self.state!r}, it={self.iterations})"
        )
