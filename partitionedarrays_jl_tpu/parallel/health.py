"""Solver / communication health guards (the detection half of the
resilience layer; the injection half is `parallel/faults.py`).

The reference assumes every rank and every exchange succeeds; at the
production scale the ROADMAP targets (multi-slice meshes over ICI+DCN)
that assumption breaks. This module supplies the *typed* failure
vocabulary — `SolverHealthError` and its subclasses, each carrying a
machine-readable ``diagnostics`` dict — plus the cheap checks that raise
them:

* **Non-finite detection** piggybacks on reductions the solvers already
  perform: a NaN/Inf anywhere in a part's owned values poisons the r·r
  dot, so testing the already-reduced *scalar* costs nothing and adds NO
  collectives. Only after the scalar trips does the (expensive,
  off-hot-path) per-part localization pass run to fill in diagnostics.
  The compiled device loops get the same property in-graph: their
  `while_loop` condition folds a `jnp.isfinite` of the carried residual
  into the existing convergence test (parallel/tpu.py:make_cg_fn).
* **Stagnation / breakdown detection** for the Krylov loops
  (models/solvers.py): p'Ap == 0 raises `SolverBreakdownError` instead
  of a strippable assert; an optional stagnation window
  (``PA_HEALTH_STAGNATION=1``) raises `SolverStagnationError` when the
  best residual stops improving.
* **`retry_with_backoff`** — the shared transient-failure wrapper used
  by `multihost_init` (coordinator not yet up) and the compile-cache /
  checkpoint I/O paths (shared-filesystem races).

Env knobs (all read dynamically so tests can toggle them):

* ``PA_HEALTH_CHECKS=0`` — disable every health guard (default: on;
  the guards are scalar tests on already-computed reductions).
* ``PA_HEALTH_EXCHANGE=1`` — additionally validate *received* halo
  payloads for finiteness after each host-path exchange (default: off;
  this one does touch every received entry).
* ``PA_HEALTH_STAGNATION=1`` — raise on residual stagnation instead of
  returning ``converged=False`` (default: off — classification via
  ``info["status"]`` stays the default contract).
* ``PA_HEALTH_STAGNATION_WINDOW`` (default 32) / ``_FACTOR`` (default
  0.99) — the stagnation test: over the last WINDOW iterations the best
  residual must improve below FACTOR x the previous best.
* ``PA_RETRY_ATTEMPTS`` (default 3) / ``PA_RETRY_BACKOFF`` (default
  0.5, seconds, doubling, capped at 30) — `retry_with_backoff` defaults.
  ``PA_RETRY_BACKOFF=0`` (or ``backoff=0``) is honored as a true
  zero-sleep policy.
* ``PA_RETRY_JITTER`` (default off) — nonzero integer seed enables
  seeded decorrelated retry jitter (delay ~ U[backoff, 3·previous],
  capped), so co-failing ranks/requests don't retry in lockstep.

Silent-corruption (SDC) defense knobs — the layer that catches what the
finiteness guards cannot (a FINITE bitflip sails straight through
``jnp.isfinite``):

* ``PA_TPU_ABFT=1`` — algorithm-based fault tolerance: checksummed halo
  exchanges (sender-side per-slab sums verified on receipt) and, on the
  device backend, the in-graph ``c·(A x)`` vs ``(c·A)·x`` SpMV checksum
  whose scalars ride the existing dot all_gather (default: off).
* ``PA_HEALTH_AUDIT_EVERY`` — recompute the TRUE residual ``b - A x``
  every N solver iterations and cross-check it against the recurrence
  residual (catches drift the per-op checksums miss). Default: 32 when
  ABFT is on, 0 (off) otherwise.
* ``PA_HEALTH_MAX_ROLLBACKS`` (default 3) — in-memory rollbacks allowed
  per solve before the detection escalates (raises
  `SilentCorruptionError`, which `solve_with_recovery` treats as
  survivable-by-checkpoint-restart).
* ``PA_HEALTH_ROLLBACK_DEPTH`` (default 2) — ring depth R of retained
  audited recurrence states (R·3 vectors).
* ``PA_TPU_ABFT_TOL`` / ``PA_HEALTH_AUDIT_TOL`` — relative detection
  thresholds; default dtype-scaled (see `abft_tolerance` /
  `audit_tolerance`).
"""
from __future__ import annotations

import os
import sys
import time
from typing import Callable, Optional, Sequence, Tuple, Type

import numpy as np

__all__ = [
    "SolverHealthError",
    "NonFiniteError",
    "SolverBreakdownError",
    "SolverStagnationError",
    "ExchangeTimeoutError",
    "SolveDeadlineError",
    "DeadlineInfeasible",
    "ControllerLostError",
    "PartLossError",
    "SilentCorruptionError",
    "PlanSoundnessError",
    "health_enabled",
    "exchange_validation_enabled",
    "stagnation_raises",
    "abft_enabled",
    "audit_every",
    "max_rollbacks",
    "rollback_depth",
    "abft_tolerance",
    "audit_tolerance",
    "RollbackRing",
    "StagnationDetector",
    "check_finite_scalar",
    "check_finite_pvector",
    "nonfinite_part_diagnostics",
    "retry_with_backoff",
]


# ---------------------------------------------------------------------------
# typed failures
# ---------------------------------------------------------------------------


class SolverHealthError(RuntimeError):
    """Base of every detected-unhealthy condition in the parallel stack.

    ``diagnostics`` is a plain dict safe to log/serialize: per-part
    findings, the iteration the guard tripped at, the residual history
    tail, ... — whatever the raising guard knows. Recovery drivers
    (`models.solvers.solve_with_recovery`) catch THIS type: anything
    that subclasses it is considered survivable-by-restart.
    """

    def __init__(self, message: str, diagnostics: Optional[dict] = None):
        super().__init__(message)
        self.diagnostics = dict(diagnostics or {})
        # telemetry: every typed health failure is an event in the
        # active SolveRecord(s) — construction is the one choke point
        # all guards funnel through (emit_event never raises)
        from ..telemetry import emit_event

        emit_event(
            "health_error", label=type(self).__name__,
            iteration=self.diagnostics.get("iteration"),
            context=self.diagnostics.get("context"),
            message=str(message)[:500],
        )


class NonFiniteError(SolverHealthError):
    """NaN/Inf detected in solver state or an exchanged payload."""


class SolverBreakdownError(SolverHealthError):
    """A Krylov recurrence hit an exact breakdown (p'Ap == 0, ...)."""


class SolverStagnationError(SolverHealthError):
    """The residual stopped improving (only raised when
    ``PA_HEALTH_STAGNATION=1``; the default contract is
    ``info["status"] == "stalled"``)."""


class ExchangeTimeoutError(SolverHealthError):
    """A neighbor's contribution never arrived within the exchange
    deadline (real runs: a slow/failed host; chaos runs: a `drop`
    fault clause). ``diagnostics["missing_parts"]`` names the senders."""


class SolveDeadlineError(SolverHealthError):
    """A solve request's wall-clock deadline expired. Raised by the
    solve service (`service.SolveService`) at a chunk boundary — the
    compiled program cannot stop mid-loop, so deadlines are enforced
    between ``PA_SERVE_CHUNK``-iteration chunks; ``diagnostics``
    carries the request id, the deadline, and the iterations completed
    when it expired. In the `SolverHealthError` family so recovery
    drivers and the event log treat it like every other typed
    failure — but `solve_with_recovery` restarts would be pointless
    (the clock, not the solver, failed), so the service fails the
    request instead of retrying it."""


class DeadlineInfeasible(SolverHealthError):
    """A deadline-carrying request was refused AT ADMISSION because the
    convergence observatory's forecast says it cannot be met: predicted
    cost (`telemetry.spectrum.predict_iters` x the throughput model's
    measured ``s_per_it``) exceeds the deadline budget. Raised only
    under ``PA_SPEC_ADMIT=1`` and only for spectrally-measured
    operators — unmeasured operators are always admitted. DISTINCT
    from its neighbors in the refusal ladder: `SolveDeadlineError` is
    the deadline EXPIRING after iterations burned, `AdmissionRejected`
    is queue backpressure, and `LoadShedded` is SLO-class policy — this
    one is a PREDICTION, made before any solver work, with
    ``diagnostics`` carrying ``predicted_s`` / ``available_s`` /
    ``predicted_iters`` / ``s_per_it`` and the spectral inputs
    (κ̂, measured rate) behind it."""


class ControllerLostError(SolverHealthError):
    """A controller process died mid-run (chaos runs: a `controller`
    fault clause; multi-host runs: surfaced by the runtime)."""


class PartLossError(SolverHealthError):
    """A PART (one TPU core / mesh shard) died mid-run — its exchange
    contribution will never arrive again (chaos runs: a `part_loss`
    fault clause; real runs: surfaced by the runtime when a device
    drops out of the mesh). DISTINCT from `ExchangeTimeoutError`,
    which is ONE missed deadline and survivable by a restart on the
    same partition: a lost part is PERSISTENT, so every restart on the
    original partition fails the same way. `solve_with_recovery`
    therefore never burns restart budget on it — under ``PA_ELASTIC=1``
    the elastic tier (`parallel/elastic.py`) rebuilds the partition
    over the survivors and resumes from the last checkpointed iterate;
    otherwise it escalates immediately (typed) to the caller's
    checkpoint tier. ``diagnostics["part"]`` names the dead part and
    ``diagnostics["call"]`` the exchange call it died at."""


class SilentCorruptionError(SolverHealthError):
    """FINITE data corruption detected by the SDC defense layer — an
    ABFT checksum mismatch (exchange slab or SpMV ``c·(A x)`` vs
    ``(c·A)·x``) or a true-residual audit failure. The finiteness guards
    cannot see this class of fault: a mantissa bitflip stays finite and
    the recurrence "converges" to a wrong answer. Raised either at the
    detection site (exchange verification) or after the in-memory
    rollback budget (``PA_HEALTH_MAX_ROLLBACKS``) is exhausted, in which
    case ``diagnostics["sdc"]`` carries the detection/rollback counters.
    Subclasses `SolverHealthError`, so `solve_with_recovery` escalates
    it to a checkpoint restart."""


class PlanSoundnessError(SolverHealthError):
    """A constructed exchange plan failed static soundness
    verification (``PA_PLAN_VERIFY=1`` — analysis.plan_verifier): an
    overlapping ghost slot, a dropped/uncovered slot, asymmetric edge
    counts, a self-send round, or a dead slot. Raised at the plan
    BUILD site, before any program is lowered from the plan — the
    static complement of the runtime ABFT/health detectors, which
    would only see the wrong answer or the hang the malformed plan
    produces. ``diagnostics["defects"]`` carries the failing check
    names with part/slot detail; ``diagnostics["checks"]`` the check
    classes that fired."""


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------


def health_enabled() -> bool:
    return os.environ.get("PA_HEALTH_CHECKS", "1") != "0"


def exchange_validation_enabled() -> bool:
    return os.environ.get("PA_HEALTH_EXCHANGE", "0") == "1"


def stagnation_raises() -> bool:
    return os.environ.get("PA_HEALTH_STAGNATION", "0") == "1"


def _stagnation_window() -> int:
    return max(2, int(os.environ.get("PA_HEALTH_STAGNATION_WINDOW", "32")))


def _stagnation_factor() -> float:
    return float(os.environ.get("PA_HEALTH_STAGNATION_FACTOR", "0.99"))


def abft_enabled() -> bool:
    """Algorithm-based fault tolerance: checksummed exchanges + in-graph
    SpMV checksums (``PA_TPU_ABFT=1``, default off — it is the opt-in
    defense against FINITE corruption the isfinite guards cannot see)."""
    return os.environ.get("PA_TPU_ABFT", "0") == "1"


def audit_every() -> int:
    """True-residual audit period in solver iterations; 0 disables.
    Defaults to 32 under ABFT (the audit is the drift detector the
    per-op checksums need as a backstop), 0 otherwise."""
    v = os.environ.get("PA_HEALTH_AUDIT_EVERY")
    if v is None or v == "":
        return 32 if abft_enabled() else 0
    return max(0, int(v))


def max_rollbacks() -> int:
    """In-memory rollbacks allowed per solve before escalating."""
    return max(0, int(os.environ.get("PA_HEALTH_MAX_ROLLBACKS", "3")))


def rollback_depth() -> int:
    """Ring depth R of retained audited recurrence states."""
    return max(1, int(os.environ.get("PA_HEALTH_ROLLBACK_DEPTH", "2")))


def abft_tolerance(dtype) -> float:
    """Relative ABFT checksum threshold: |Δ| > tol·scale is corruption.
    The checksum sums accumulate rounding ~ O(n)·eps·Σ|terms|, so the
    default leaves headroom above the dtype's eps; corruption below it
    is by construction within the solve's own rounding noise."""
    v = os.environ.get("PA_TPU_ABFT_TOL")
    if v:
        return float(v)
    return 1e-3 if np.dtype(dtype).itemsize <= 4 else 1e-10


def audit_tolerance(dtype) -> float:
    """Relative true-residual drift threshold: ||(b - A x) - r|| >
    tol·max(1, ||r0||) fails the audit."""
    v = os.environ.get("PA_HEALTH_AUDIT_TOL")
    if v:
        return float(v)
    return 1e-3 if np.dtype(dtype).itemsize <= 4 else 1e-8


# ---------------------------------------------------------------------------
# finite checks
# ---------------------------------------------------------------------------


def nonfinite_part_diagnostics(*vectors) -> dict:
    """Per-part non-finite census over PVectors: for each part with any
    NaN/Inf, the counts and the first offending local id. This is the
    *localization* pass — only called after a cheap scalar guard already
    tripped, so its full sweep is off the hot path."""
    parts = {}
    for name, v in vectors:
        for p, vals in enumerate(v.values.part_values()):
            a = np.asarray(vals)
            if a.dtype.kind != "f":
                continue
            bad = ~np.isfinite(a)
            if bad.any():
                d = parts.setdefault(int(p), {})
                d[name] = {
                    "nan": int(np.isnan(a).sum()),
                    "inf": int(np.isinf(a).sum()),
                    "first_lid": int(np.nonzero(bad)[0][0]),
                }
    return {"parts": parts}


def check_finite_scalar(
    value, context: str, it: Optional[int] = None, vectors: Sequence = ()
) -> None:
    """Raise `NonFiniteError` when an already-reduced scalar (a dot, a
    norm) is NaN/Inf. The scalar test is free — the reduction happened
    anyway; ``vectors`` (pairs of (name, PVector)) are only swept for
    per-part diagnostics after the guard trips."""
    if np.isfinite(value):
        return
    diag = {"context": context, "value": float(value)}
    if it is not None:
        diag["iteration"] = int(it)
    try:
        diag.update(nonfinite_part_diagnostics(*vectors))
    except Exception:  # diagnostics must never mask the primary failure
        pass
    raise NonFiniteError(
        f"{context}: non-finite reduction value {value!r}"
        + (f" at iteration {it}" if it is not None else "")
        + " — a NaN/Inf entered the solver state (see .diagnostics)",
        diagnostics=diag,
    )


def check_finite_pvector(v, context: str) -> None:
    """Full finiteness sweep of a PVector (used by the opt-in exchange
    validation, ``PA_HEALTH_EXCHANGE=1``)."""
    diag = nonfinite_part_diagnostics(("values", v))
    if diag["parts"]:
        diag["context"] = context
        raise NonFiniteError(
            f"{context}: non-finite values on parts "
            f"{sorted(diag['parts'])}", diagnostics=diag
        )


class StagnationDetector:
    """Windowed best-residual tracker for Krylov loops. ``update(res)``
    raises `SolverStagnationError` when over the last WINDOW updates the
    best residual failed to improve below FACTOR x the previous best —
    but only when stagnation raising is enabled; constructing the
    detector is free and `update` is two floats and a counter."""

    def __init__(self, context: str):
        self.context = context
        self.window = _stagnation_window()
        self.factor = _stagnation_factor()
        self.best = np.inf
        self.since_improvement = 0

    def update(self, res: float, it: int) -> None:
        if res < self.factor * self.best:
            self.best = res
            self.since_improvement = 0
            return
        self.since_improvement += 1
        if self.since_improvement >= self.window:
            raise SolverStagnationError(
                f"{self.context}: best residual {self.best:.3e} has not "
                f"improved by {1.0 - self.factor:.1%} over the last "
                f"{self.window} iterations (it={it})",
                diagnostics={
                    "context": self.context,
                    "iteration": int(it),
                    "best_residual": float(self.best),
                    "window": self.window,
                },
            )


class RollbackRing:
    """Bounded in-memory ring of the last R AUDITED solver recurrence
    states — the no-disk recovery tier of the SDC defense: a detected
    corruption rewinds at most ``audit_every`` iterations by restoring
    the newest ring entry, escalating to `solve_with_recovery`'s
    checkpoint restart only after ``PA_HEALTH_MAX_ROLLBACKS`` strikes.

    Entries are ``(vectors, meta)``: deep copies of the recurrence
    vectors (host PVectors here; the compiled device loops carry the
    same ring as an (R, 3, W) array in their while-loop state) plus the
    scalar recurrence state. ``push`` is called ONLY on states that just
    passed a true-residual audit (plus the initial state, audited by
    construction), so every ring entry is known-good.

    ``restore(strike)`` returns the entry ``strike`` slots back
    (clamped): consecutive failed replays walk to older states, bounding
    a corruption that survives the newest snapshot."""

    def __init__(self, depth: Optional[int] = None):
        self.depth = depth if depth is not None else rollback_depth()
        self._ring: list = []  # newest first

    def push(self, vectors: dict, meta: dict) -> None:
        entry = ({k: v.copy() for k, v in vectors.items()}, dict(meta))
        self._ring.insert(0, entry)
        del self._ring[self.depth:]

    def restore(self, strike: int = 0):
        """The entry ``strike`` slots back (clamped to the oldest), as
        ``(vectors, meta)`` fresh copies — or None when the ring is
        empty (the caller then restarts from scratch/escalates)."""
        if not self._ring:
            return None
        vecs, meta = self._ring[min(max(0, strike), len(self._ring) - 1)]
        return {k: v.copy() for k, v in vecs.items()}, dict(meta)

    def __len__(self):
        return len(self._ring)


# ---------------------------------------------------------------------------
# transient-failure retry
# ---------------------------------------------------------------------------


def _default_attempts() -> int:
    return max(1, int(os.environ.get("PA_RETRY_ATTEMPTS", "3")))


def _default_backoff() -> float:
    return float(os.environ.get("PA_RETRY_BACKOFF", "0.5"))


def _default_jitter_seed() -> Optional[int]:
    """``PA_RETRY_JITTER``: unset/empty/``0`` = no jitter (the classic
    deterministic doubling); any other integer = decorrelated jitter
    seeded by that value. Seeded, not wall-clock-random: tests and
    reproducibility-minded operators get the same delay sequence per
    (seed, failure count), while distinct seeds (one per rank/request)
    decorrelate the retry storms."""
    v = os.environ.get("PA_RETRY_JITTER", "")
    if not v or v == "0":
        return None
    return int(v)


def retry_with_backoff(
    fn: Callable,
    *,
    attempts: Optional[int] = None,
    backoff: Optional[float] = None,
    max_backoff: float = 30.0,
    exceptions: Tuple[Type[BaseException], ...] = (OSError,),
    describe: str = "operation",
    sleep: Callable[[float], None] = time.sleep,
    jitter_seed: Optional[int] = None,
    give_up: Optional[Callable[[], bool]] = None,
):
    """Call ``fn()`` up to ``attempts`` times, sleeping ``backoff`` then
    doubling (capped at ``max_backoff``) between tries; only the listed
    ``exceptions`` are treated as transient. The last failure re-raises
    unchanged. Each retry prints one stderr line (operators watching a
    cluster come up need to see the wait, not a silent hang).

    ``backoff=0`` is a true zero-sleep policy: every delay stays 0.0
    (callers asking for no backoff — tests, in-process service retries
    with their own pacing — must not inherit a hidden 0.1 s floor).

    ``jitter_seed`` (default: resolved from ``PA_RETRY_JITTER``)
    switches the schedule to seeded DECORRELATED jitter — each delay
    drawn uniformly from [backoff, 3·previous] (capped) — so co-failing
    ranks/requests sharing a flaky dependency spread their retries
    instead of hammering it in lockstep.

    ``give_up`` — optional predicate checked after each failure: when
    it returns True the remaining attempts are abandoned and the
    failure re-raises immediately (the solve service passes its
    deadline test here, so a deterministically-failing request cannot
    keep retrying past its deadline)."""
    attempts = attempts if attempts is not None else _default_attempts()
    backoff = backoff if backoff is not None else _default_backoff()
    if jitter_seed is None:
        jitter_seed = _default_jitter_seed()
    rng = (
        np.random.default_rng(jitter_seed)
        if jitter_seed is not None
        else None
    )
    base = max(0.0, float(backoff))
    delay = base
    for attempt in range(1, attempts + 1):
        try:
            return fn()
        except exceptions as e:
            if attempt >= attempts or (give_up is not None and give_up()):
                raise
            print(
                f"[partitionedarrays_jl_tpu] {describe} failed "
                f"(attempt {attempt}/{attempts}: {type(e).__name__}: {e}); "
                f"retrying in {delay:.1f}s",
                file=sys.stderr,
                flush=True,
            )
            sleep(delay)
            if rng is not None:
                delay = min(
                    max_backoff, float(rng.uniform(base, max(base, delay * 3)))
                )
            else:
                delay = min(max_backoff, delay * 2)
