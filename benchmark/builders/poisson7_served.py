"""Builder `poisson7_served`: the 7-point Poisson operator of a
configuration file held resident by ONE solve service, and clients that
each send a right-hand side and wait for their own answer.

The operator, its assembly, the requests and everything that decides
`correct` are `poisson7`'s (the plain reference there imports nothing of
the program and is used as it stands). What differs is the timed entry:
not one synchronous library call but the service's public surface as
`docs/service.md` documents it,

    svc = pa.SolveService(A, kmax=..., queue_depth=...)   # the configuration's `service`
    ...one slab of every width through submit / step...     # the deployment's warm-up
    svc.start()                                             # the worker thread
    req = svc.submit(b, x0=x0, tol=tol); x, info = req.wait()

so which slab a request rides in, and which columns ride beside it, is
the batcher's choice and no part of the request. The semantics held to:
every admitted request is answered exactly once with the answer of ITS
OWN right-hand side and start vector; none is rejected (`submit` raises),
ejected (`wait` raises) or retried solo (raised here): each of those
leaves a record that says "not converged", which `unanswered` counts.
"""
from __future__ import annotations

import sys

from benchmark.builders import poisson7


class Pool(list):
    """The pool of requests, and the mix's arrival parameters for the
    open-loop driver (which is handed the pool and nothing of the mix)."""

    arrivals: dict = {}


class System(poisson7.System):
    def __init__(self, pa, parts, cfg: dict, mix: dict):
        if mix["entry"] != "served" or mix.get("preconditioner") is not None:
            raise ValueError(
                "poisson7_served: the entry is 'served', with no preconditioner"
            )
        if not hasattr(pa.service.SolveRequest, "wait"):
            # the served deployment cannot run on this program: say so at
            # once and with another exit code than 0
            raise SystemExit(
                "bench: poisson7_served: this program's SolveRequest has no "
                "blocking wait(): a client of a service whose worker thread "
                "runs cannot wait for its answer"
            )
        # assembly, sizes and the reference are poisson7's, under its own
        # plain-CG entry; the mix this system keeps is the served one
        super().__init__(pa, parts, cfg, dict(mix, entry="cg"))
        self.mix = mix
        spec = cfg["service"]
        if spec["deadline"] is not None or spec["worker_thread"] is not True:
            raise ValueError(
                "poisson7_served: no deadline, and the worker thread runs"
            )
        self.kmax = int(spec["kmax"])
        self.answer_timeout = float(mix["answer_timeout_s"])
        self.service = pa.SolveService(
            self.A, kmax=self.kmax, queue_depth=int(spec["queue_depth"])
        )
        self.warm = False

    def make_pool(self, seed: int) -> Pool:
        pool = Pool(super().make_pool(seed))
        pool.arrivals = dict(self.mix["arrivals"], mix=self.mix["name"])
        return pool

    # -- the timed entry ---------------------------------------------------

    def warm_up(self, req) -> None:
        """What a deployment does before it opens: one slab of every width
        the batcher can form, widest first, through the public API
        (`submit` times the width, then `step`, before the worker thread
        exists, so that the slab has exactly that width), each answer
        waited for; then the worker thread. A width is a program of its
        own, so none is left to compile inside a window."""
        svc = self.service
        for k in range(self.kmax, 0, -1):
            reqs = [
                svc.submit(req.b, x0=req.x0, tol=self.tol) for _ in range(k)
            ]
            svc.step()
            for r in reqs:
                _x, info = r.wait(0.0)
                if not info["converged"]:
                    raise RuntimeError(f"a column of the width-{k} slab did not converge")
        svc.start()
        self.warm = True

    def solve(self, req):
        """One client: send the request, wait for its own answer. The
        first call (the harness's warm-up solve, alone on its thread)
        warms the service first; a service that cannot be warmed ends the
        run."""
        if not self.warm:
            try:
                self.warm_up(req)
            except Exception as e:
                raise SystemExit(
                    f"bench: poisson7_served: the warm-up failed: "
                    f"{type(e).__name__}: {e}"
                )
        counted = {"at_submit": self.pa.telemetry.counters("service")}
        handle = self.service.submit(req.b, x0=req.x0, tol=self.tol)
        x, info = handle.wait(self.answer_timeout)
        if info.get("resolved_via") is not None:
            raise RuntimeError(
                f"request {handle.tag} left its slab: resolved via "
                f"{info['resolved_via']}"
            )
        # the program's `service.*` counters as this client saw them, for
        # the readers of `layer_metrics/_slabs.py`: between one traced
        # request's submission and another's answer they count the traced
        # stretch and nothing of the warm-up or of what follows the trace
        counted["at_answer"] = self.pa.telemetry.counters("service")
        return x, dict(info, service_counters=counted)

    # -- after the window ----------------------------------------------------

    def device_bytes_peak(self) -> int:
        """The harness asks this once, when the window has closed: the
        moment to say what the service counted (none rejected, ejected or
        retried solo is part of what the configuration guarantees)."""
        print(
            f"bench: served: service stats {dict(self.service.stats)}",
            file=sys.stderr, flush=True,
        )
        return super().device_bytes_peak()


def build(pa, parts, cfg: dict, mix: dict) -> System:
    return System(pa, parts, cfg, mix)
