"""Contract-enforcement helpers.

TPU-native analog of the reference's error macros (reference:
src/Helpers.jl:6-61 — `@abstractmethod`, `@notimplemented`, `@check`).
Python has no compile-time boundscheck elision, so `check` is gated by an
environment flag instead: set ``PA_TPU_CHECKS=0`` to strip contract checks in
production runs (mirrors Julia's ``--boundscheck=no``).
"""
from __future__ import annotations

import os

_CHECKS_ENABLED = os.environ.get("PA_TPU_CHECKS", "1") != "0"


class AbstractMethodError(NotImplementedError):
    pass


def abstractmethod(obj=None, name: str = "") -> None:
    """Raise: a subtype forgot to implement part of its interface contract."""
    raise AbstractMethodError(
        f"abstract method {name or ''} called on {type(obj).__name__}: "
        "this method is part of an interface definition and concrete "
        "implementations must override it"
    )


def notimplemented(msg: str = "this case is not yet implemented") -> None:
    raise NotImplementedError(msg)


def notimplementedif(condition: bool, msg: str = "this case is not yet implemented") -> None:
    if condition:
        notimplemented(msg)


def unreachable(msg: str = "this line of code cannot be reached") -> None:
    raise AssertionError(msg)


def checks_enabled() -> bool:
    return _CHECKS_ENABLED


def strict_bits() -> bool:
    """Opt-in bit-exactness mode (``PA_TPU_STRICT_BITS=1``), the literal
    form of the BASELINE.md "bit-exact vs SequentialBackend" gate: the
    device lowering blocks FMA contraction (products round separately,
    as NumPy's do), takes the fold-order-matching ELL SpMV path, and both
    host and device dots use the same fixed-tree pairwise sum. Costs
    throughput; the default mode agrees with the oracle to FMA rounding
    instead. Read dynamically (not at import) so tests can toggle it."""
    return os.environ.get("PA_TPU_STRICT_BITS", "0") == "1"


def pairwise_sum(v):
    """Fixed-tree pairwise sum: pad to the next power of two with exact
    zeros, then halve until one element. The identical tree runs in the
    compiled dot (parallel/tpu.py:_pdot_factory, strict path), making the
    per-part partials bit-identical on host and device. Zero tail slots
    are rounding-neutral, so trees padded to different power-of-two
    lengths agree bit-for-bit as long as the real data is a prefix."""
    import numpy as np

    v = np.asarray(v)
    if v.size == 0:
        return v.dtype.type(0.0) if v.dtype.kind == "f" else 0.0
    n = 1 << int(v.size - 1).bit_length() if v.size > 1 else 1
    if v.size < n:
        v = np.concatenate([v, np.zeros(n - v.size, dtype=v.dtype)])
    while v.size > 1:
        v = v[0::2] + v[1::2]
    return v[0]


#: the resolution floor multiplier for relative-residual tolerances: a
#: Krylov residual estimate in dtype d cannot reliably resolve below
#: ~TOL_FLOOR_EPS_MULTIPLE x eps(d) x problem scale (round-3 finding:
#: an f32 FGMRES with tol=1e-8 oscillates at the floor with an accurate
#: solution and converged=False)
TOL_FLOOR_EPS_MULTIPLE = 50.0


def tolerance_floor(dtype) -> float:
    """The smallest relative-residual tolerance `dtype` can resolve."""
    import numpy as np

    return TOL_FLOOR_EPS_MULTIPLE * float(np.finfo(np.dtype(dtype)).eps)


def warn_tol_below_floor(tol: float, dtype, name: str = "solver") -> bool:
    """Warn (RuntimeWarning) when a relative tolerance sits below the
    dtype's resolution floor — the round-3 f32 footgun made
    self-describing: the solver may then report converged=False with an
    accurate solution because its residual estimate flatlines near
    eps-scale. Returns whether the warning fired (recorded in info)."""
    import warnings

    import numpy as np

    if not (tol > 0):  # tol=0 fixed-trip benchmark runs are deliberate
        return False
    dt = np.dtype(dtype)
    if dt.kind != "f":
        return False
    floor = tolerance_floor(dt)
    if tol >= floor:
        return False
    warnings.warn(
        f"{name}: tol={tol:g} is below the {dt.name} resolution floor "
        f"(~{TOL_FLOOR_EPS_MULTIPLE:g}x eps = {floor:g}). A relative "
        "residual this small is generally unreachable in this dtype; the "
        "run may stall at the dtype floor with converged=False despite an "
        "accurate solution. Solve in float64 or loosen tol.",
        RuntimeWarning,
        stacklevel=3,
    )
    return True


def krylov_status(
    residuals, converged: bool, tol: float, dtype, final_rel=None
) -> str:
    """Classify a finished Krylov run for the info dict:

    * ``"converged"`` — the residual test passed.
    * ``"stalled"`` — no convergence, but the TRUE relative residual sits
      at the dtype resolution floor (tol is unreachable in this dtype —
      the r3 f32 symptom: restart cycles oscillate, the within-cycle
      Givens estimate keeps shrinking spuriously, the solution is
      accurate), or the best residual stopped improving over the tail
      of the history (a genuine stagnation above the floor).
    * ``"diverged"`` — the final residual grew well past the initial one.
    * ``"maxiter"`` — still improving when the iteration budget ran out.

    ``final_rel`` is the final TRUE relative residual when the solver has
    one (restarted methods recompute it at cycle boundaries; estimate
    histories alone cannot witness a floor-stall because the estimate
    dives below the true residual).
    """
    import numpy as np

    if converged:
        return "converged"
    r = np.asarray(residuals, dtype=np.float64)
    r = r[np.isfinite(r)]
    if len(r) >= 2 and r[-1] > 10.0 * max(r[0], 1e-300):
        return "diverged"
    dt = np.dtype(dtype)
    if (
        final_rel is not None
        and dt.kind == "f"
        and tol < float(final_rel) <= 10.0 * tolerance_floor(dt)
    ):
        return "stalled"
    if len(r) >= 8:
        w = max(4, len(r) // 4)  # tail window: last quarter, >= 4 entries
        best_before = float(np.min(r[:-w]))
        best_tail = float(np.min(r[-w:]))
        if best_tail > 0.9 * best_before:  # <10% improvement in the tail
            return "stalled"
    return "maxiter"


def krylov_info(
    it, history, converged, tol, dtype, floor_warned, final_rel=None, **extra
):
    """The ONE Krylov info-dict builder (host loops, compiled drivers,
    early returns alike): iterations/residuals/converged plus the
    `status` classification and the tolerance-floor flag when it fired.
    ``final_rel`` must be a TRUE relative residual or None — recurrence
    estimates (CG's rs, Lanczos) drift below the true residual on
    ill-conditioned problems and would misclassify a genuine failure as
    a floor-stall."""
    import numpy as np

    residuals = np.array(history)
    converged = bool(converged)
    if (
        converged
        and floor_warned
        and final_rel is not None
        and final_rel > tol
    ):
        # the RECURRENCE residual underflowed past a below-floor tol
        # while the TRUE residual still sits above it (f32 CG's version
        # of the footgun: rs keeps shrinking on paper after b - Ax has
        # floored) — converged would be a lie here
        converged = False
    info = {
        "iterations": int(it),
        "residuals": residuals,
        "converged": converged,
        "status": krylov_status(
            residuals, converged, tol, dtype, final_rel=final_rel
        ),
        **extra,
    }
    if floor_warned:
        info["tol_below_dtype_floor"] = True
    return info


def check(condition, msg: str = "check failed") -> None:
    """Cheap contract assertion, strippable via PA_TPU_CHECKS=0.

    Reference: src/Helpers.jl:50-61 (`@check`).
    """
    if _CHECKS_ENABLED and not condition:
        raise AssertionError(msg)
