"""Admission control: the bounded front door of the solve service.

A production request layer must push back, not buffer without bound —
an unbounded queue turns overload into latency collapse and OOM. The
service therefore admits a request only when the queue holds fewer than
``PA_SERVE_QUEUE_DEPTH`` requests and the service is not draining;
everything else raises the typed `AdmissionRejected` (machine-readable
``diagnostics``, mirrored as an ``admission_rejected`` telemetry
event), so callers can shed load or retry with backoff
(`parallel.health.retry_with_backoff` + ``PA_RETRY_JITTER`` is the
intended client-side pairing).

Env knobs (host-side — none can change a compiled program; the lint
records them in ``analysis.env_lint.NON_LOWERING``):

* ``PA_SERVE_QUEUE_DEPTH`` (default 64) — admission bound: queued
  requests allowed before `AdmissionRejected` backpressure.
* ``PA_SERVE_KMAX`` (default 8) — widest slab the batcher coalesces
  (K=8–16 was the per-RHS sweet spot of an early multi-RHS sweep).
* ``PA_SERVE_CHUNK`` (default 25) — chunk length in solver iterations
  for deadline-carrying slabs: the compiled program cannot stop
  mid-loop, so deadlines are enforced at chunk boundaries. Slabs with
  no deadline run unchunked (one compiled solve — which is what keeps
  co-batched trajectories bitwise equal to solo solves).
* ``PA_SERVE_RETRIES`` (default 1) — solo retry attempts for a column
  ejected from a shared slab (0 = fail immediately).
"""
from __future__ import annotations

import os
from typing import Optional

__all__ = [
    "AdmissionRejected",
    "AdmissionController",
    "DEFAULT_TOL",
    "queue_depth",
    "slab_kmax",
    "chunk_iters",
    "default_retries",
]

#: The service-wide default convergence tolerance — THE one definition
#: (`SolveService.submit` and the gate's paspec feasibility check both
#: resolve through it, so the two admission forecasts can never
#: desynchronize on a default change).
DEFAULT_TOL = 1e-8


class AdmissionRejected(RuntimeError):
    """The service refused to queue a request — bounded-queue
    backpressure (``reason="queue_full"``) or a draining/shut-down
    service (``reason="draining"``). ``diagnostics`` carries the
    reason, the queue depth and bound, and the request tag. NOT a
    `SolverHealthError`: nothing about the solve is unhealthy — the
    caller is being told to slow down, and recovery drivers must not
    burn restart budget on it."""

    def __init__(self, message: str, diagnostics: Optional[dict] = None):
        super().__init__(message)
        self.diagnostics = dict(diagnostics or {})
        from ..telemetry import emit_event
        from ..telemetry.registry import registry

        # the rejection counter is always-on (pamon's overload signal:
        # rejected/admitted is the shed-load rate) and labeled by
        # reason, so queue-full backpressure and a draining service
        # stay separable from each other AND from gate.shed (SLO-class
        # load shedding) in /metrics — the event below additionally
        # ticks events.admission_rejected
        registry().counter(
            "service.rejected",
            labels={"reason": str(self.diagnostics.get("reason", ""))},
        ).inc()
        emit_event(
            "admission_rejected",
            label=str(self.diagnostics.get("reason", "")),
            tag=self.diagnostics.get("tag"),
            queued=self.diagnostics.get("queued"),
            depth=self.diagnostics.get("depth"),
        )


def queue_depth() -> int:
    return max(1, int(os.environ.get("PA_SERVE_QUEUE_DEPTH", "64")))


def slab_kmax() -> int:
    return max(1, int(os.environ.get("PA_SERVE_KMAX", "8")))


def chunk_iters() -> int:
    return max(1, int(os.environ.get("PA_SERVE_CHUNK", "25")))


def default_retries() -> int:
    return max(0, int(os.environ.get("PA_SERVE_RETRIES", "1")))


class AdmissionController:
    """The admit/refuse decision, factored out of the service so its
    policy is testable without a live queue. Stateless between calls
    except for the bound (resolved once per service unless overridden
    per instance)."""

    def __init__(self, depth: Optional[int] = None):
        self.depth = queue_depth() if depth is None else max(1, int(depth))

    def admit(self, queued: int, draining: bool, tag: str = "") -> None:
        """Raise `AdmissionRejected` unless a request may join a queue
        currently holding ``queued`` entries."""
        if draining:
            raise AdmissionRejected(
                f"admission rejected ({tag or 'request'}): the service "
                "is draining/shut down and accepts no new requests",
                diagnostics={
                    "reason": "draining", "tag": tag,
                    "queued": int(queued), "depth": self.depth,
                },
            )
        if queued >= self.depth:
            raise AdmissionRejected(
                f"admission rejected ({tag or 'request'}): queue holds "
                f"{queued} requests (bound PA_SERVE_QUEUE_DEPTH="
                f"{self.depth}) — shed load or retry with backoff",
                diagnostics={
                    "reason": "queue_full", "tag": tag,
                    "queued": int(queued), "depth": self.depth,
                },
            )
