"""Phase-attributed solver profiling — where an iteration's time goes.

`patrace` (PR 6) made a solve's *wire* legible (static-vs-measured
collective inventories) and `pamon` (PR 9) made the *service* legible
(latency distributions, SLO attainment), but neither answers the
question every optimization PR starts from: of one compiled CG
iteration's wall time, how much is SpMV compute, how much halo
exchange, how much the dot all_gathers, how much the axpy sweeps?

One capture method, the deterministic **split-timer**: time each phase
as its OWN compiled k-step chain (`_marginal_s`: warm, min-of-reps,
difference two trip counts so dispatch cancels) built from the same
`DeviceMatrix` the solver lowers from: the halo exchange body, the full
SpMV (halo included — the local share is the difference), one
deterministic dot all_gather, and the three-update axpy sweep.
Op-level truth from a captured profile is read elsewhere: the compiled
programs carry the SAME four phases as `jax.named_scope`s
(``pa.spmv_local`` ... — `parallel/tpu.py` ``SCOPE_*``), and
`benchmark/layer_metrics/_scoped.py` reduces a chip trace by them.

The exported `PhaseProfile` is schema-versioned, keyed by the palint
lowering-case name and the operator fingerprint, and carries BOTH
bands of honesty the rest of the repo runs on:

* the per-phase collective inventories must RECONCILE per kind with
  `telemetry.comms.cg_comms_profile`'s per-iteration inventory (the
  same plan-level model palint pins against the lowered program), and
* the attributed phase sum must land within ``PHASE_SUM_BAND`` of the
  independently measured per-iteration total of the real compiled CG
  body (split chains re-pay loop-carry overheads the fused body
  amortizes, so the pinned band is a ratio band, not an equality).

Profiling builds STANDALONE programs — it never touches the solver
path. ``PA_PROF=0`` turns `capture_phase_profile` into a no-op
(returns None); the block program's StableHLO is byte-identical with
profiling on, off, or unset (pinned in tests/test_paprof.py).

Env knobs (host-side, NON_LOWERING-exempt with reasons):

* ``PA_PROF`` (default ``1``) — master switch for profile capture.
* ``PA_PROF_REPS`` (default ``5``) — timed repetitions per chain
  measurement (median taken).
"""
from __future__ import annotations

import json
import math
import os
import time
from typing import Callable, Dict, Optional

from .comms import COMM_KINDS, cg_comms_profile

__all__ = [
    "PHASE_SCHEMA_VERSION",
    "PHASES",
    "PHASE_SUM_BAND",
    "PHASE_SUM_BAND_WIDE",
    "prof_enabled",
    "prof_reps",
    "lowering_descriptor",
    "phase_case_name",
    "phase_case_of",
    "capture_phase_profile",
    "reconcile_phases",
    "phase_trace_events",
    "render_phase_profile",
]

#: v2: the committed PHASE_PROFILE.json is a multi-case container
#: ``{"phase_schema_version": 2, "profiles": {case: profile}}``.
PHASE_SCHEMA_VERSION = 2

#: The attribution axes of one CG iteration. ``spmv_local`` is the
#: operator-apply compute (full SpMV minus its embedded halo update),
#: so the four sum to one iteration's work.
PHASES = ("spmv_local", "halo_exchange", "dot_allgather", "axpy_sweep")

#: Pinned acceptance band for attributed_sum / measured_total. The
#: split chains re-pay per-phase loop-carry and buffer-roundtrip costs
#: the real body's single while loop amortizes (and the fused body
#: folds the axpy sweep into the SpMV stream entirely), and on a tiny
#: conformance-scale fixture the wall-clock marginals jitter with host
#: load, so the honest claim is same-SCALE, not equality: the
#: attributed sum must land within [0.15x, 6x] of the measured
#: per-iteration total (capture takes the best of up to 3 attempts —
#: a genuinely broken attribution is off by orders of magnitude and
#: stays out of this band on every attempt).
PHASE_SUM_BAND = (0.15, 6.0)

#: The looser band of the block (rhs_batch) bodies: they carry
#: K-column while-carry and pfold costs the four phase chains
#: deliberately do not model (measured ~0.07-0.14 on the CPU probe, vs
#: >= 0.15 for the scalar bodies). Same role as
#: `PHASE_SUM_BAND` (same-scale, catches orders-of-magnitude
#: attribution breakage), looser floor; each profile records the band
#: it was checked against.
PHASE_SUM_BAND_WIDE = (0.05, 6.0)


def prof_enabled() -> bool:
    """The PA_PROF master switch (host-side; profiling never touches a
    staged solver program either way)."""
    return os.environ.get("PA_PROF", "1") != "0"


def prof_reps() -> int:
    """PA_PROF_REPS timed repetitions per chain (>= 3 for a median)."""
    try:
        v = int(os.environ.get("PA_PROF_REPS", "5") or "5")
    except ValueError:
        return 5
    return max(3, v)


def lowering_descriptor(dA) -> Dict[str, str]:
    """The operator's selected lowering, as the palint axes name it:
    which A_oo path staged and which exchange-plan family the column
    plan is — the identity a phase profile is only comparable under."""
    from ..parallel.tpu_box import BoxExchangePlan

    if dA.dia_mode == "coded":
        a_oo = "dia-coded"
    elif dA.dia_offsets is not None:
        a_oo = "dia"
    elif dA.sd_bs is not None:
        a_oo = "sd"
    elif dA.bsr_bs is not None:
        a_oo = "bsr"
    else:
        a_oo = "ell"
    plan = "box" if isinstance(dA.col_plan, BoxExchangePlan) else "generic"
    return {"a_oo": a_oo, "plan": plan}


def phase_case_name(fused: bool, rhs_batch: Optional[int] = None,
                    abft: bool = False) -> str:
    """The palint lowering-matrix case name this profile is keyed by
    (`parallel.tpu.lowering_matrix` naming: body form + K + mode)."""
    body = "fused" if fused else "standard"
    name = f"block_k{int(rhs_batch)}_{body}" if rhs_batch else body
    return name + ("_abft" if abft else "")


def phase_case_of(name: str) -> str:
    """Map ANY lowering-matrix CG case name to the committed
    PHASE_PROFILE.json entry that represents its body shape — the
    coverage key `tools/paprof.py --check` fails on when a matrix case
    has no committed phase entry. Mode suffixes (_nobox/_abft/_f32,
    strict_) share their base body's profile: they change operands or
    rounding, not the phase structure."""
    for k in ("block_k1", "block_k4"):
        if k in name:
            return f"{k}_fused"
    if "fused" in name:
        return "fused"
    return "standard"


# ---------------------------------------------------------------------------
# the split-body timer: one compiled k-step chain per phase
# ---------------------------------------------------------------------------


def _marginal_s(run_chain: Callable[[int], float], k1: int, k2: int,
                reps: int) -> float:
    """Marginal per-step cost of a compiled chain: warm both trip
    counts, MIN-of-reps each, difference so dispatch/fetch overhead
    cancels. Min, not median: on a shared/loaded host, contention only
    ever INFLATES a run, so the min of each side is the least-contended
    estimate and the difference is far more stable under load than
    median-of-reps. One doubling retry absorbs timer-noise inversions
    on very cheap chains."""
    def timed(k: int) -> float:
        run_chain(k)
        run_chain(k)
        return min(_one_timing(run_chain, k) for _ in range(reps))

    t1 = timed(k1)
    kk2 = k2
    for _ in range(2):
        t2 = timed(kk2)
        dt = (t2 - t1) / (kk2 - k1)
        if dt > 0:
            return dt
        kk2 *= 2
    # still inverted (a chain cheaper than timer noise): conservative
    # whole-chain bound of the last measured length — overestimates,
    # which the same-scale band absorbs; more doublings would mean
    # more compiles for signal the band does not need
    return max(t2 / max(kk2 // 2, 1), 1e-12)


def _one_timing(run_chain, k) -> float:
    t0 = time.perf_counter()
    run_chain(k)
    return time.perf_counter() - t0


def _phase_chains(dA, rhs_batch: Optional[int]) -> Dict[str, Callable]:
    """Build the four phase chains from ``dA``'s own plan/operands —
    the same `_shard_exchange` / `_spmv_body` / `_pdot_factory`
    building blocks the CG bodies compile from, each wrapped in a
    jitted k-step ``fori_loop`` ending in a scalar fetch. Every chain
    carries a tiny owned<-ghost / state feedback so XLA cannot hoist
    the phase work out of the loop."""
    import functools

    import jax
    import numpy as np

    from ..parallel.tpu import (
        _matrix_operands,
        _pdot_factory,
        _shard_exchange,
        _shard_ops,
        _spmv_body,
    )

    shard_map = jax.shard_map
    layout = dA.col_plan.layout
    P, W = layout.P, layout.W
    o0, g0 = layout.o0, layout.g0
    ro0, no = dA.row_layout.o0, layout.no_max
    mesh = dA.backend.mesh(P)
    spec = dA.backend.parts_spec()
    ops = _matrix_operands(dA)
    specs = jax.tree.map(lambda _: spec, ops)
    K = int(rhs_batch) if rhs_batch else 0
    dtype = np.float64

    shape = (P, W, K) if K else (P, W)
    x0 = np.zeros(shape, dtype=dtype)
    x0[:, o0:g0] = 1.0
    x = jax.device_put(
        x0, jax.sharding.NamedSharding(mesh, spec)
    )
    eps = dtype(1e-30)

    exch_body = _shard_exchange(dA.col_plan, "set")

    def _feedback(xv):
        # one-element ghost->owned coupling: each step's pack depends
        # on the previous step's permute, so nothing is loop-invariant
        return xv.at[o0].add(xv[g0] * eps)

    @functools.partial(jax.jit, static_argnums=2)
    def exch_chain(xv, m, k):
        def shard_fn(xs, ms):
            mm = _shard_ops(jax, ms)

            def step(_, v):
                return _feedback(
                    exch_body(v, mm["si"], mm["sm"], mm["ri"])
                )

            return jax.lax.fori_loop(0, k, step, xs[0])[None]

        return shard_map(
            shard_fn, mesh=mesh, in_specs=(spec, specs),
            out_specs=spec, check_vma=False,
        )(xv, m).sum()

    spmv_body = _spmv_body(dA)

    @functools.partial(jax.jit, static_argnums=2)
    def spmv_chain(xv, m, k):
        def shard_fn(xs, ms):
            mm = _shard_ops(jax, ms)

            def step(_, v):
                # the product lives on the ROW layout; re-embed its
                # owned region into the column-layout operand so the
                # chain stays square (ghosts are refreshed by the
                # body's own halo update each step)
                y, _aux = spmv_body(v, mm)
                return v.at[o0:o0 + no].set(y[ro0:ro0 + no])

            return jax.lax.fori_loop(0, k, step, xs[0])[None]

        return shard_map(
            shard_fn, mesh=mesh, in_specs=(spec, specs),
            out_specs=spec, check_vma=False,
        )(xv, m).sum()

    pdot = _pdot_factory(o0, layout.no_max)

    @functools.partial(jax.jit, static_argnums=1)
    def dot_chain(xv, k):
        def shard_fn(xs):
            def step(_, v):
                s = pdot(v, v)
                return v.at[o0].add(s * eps)

            return jax.lax.fori_loop(0, k, step, xs[0])[None]

        return shard_map(
            shard_fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
            check_vma=False,
        )(xv).sum()

    a, bcoef = dtype(1e-3), dtype(0.5)

    @functools.partial(jax.jit, static_argnums=1)
    def axpy_chain(xv, k):
        def shard_fn(xs):
            def step(_, carry):
                xc, rc, pc = carry
                # the CG update sweep's three vector passes:
                # x += alpha p ; r -= alpha q ; p = z + beta p
                xc = xc + a * pc
                rc = rc - a * (pc * bcoef)
                pc = rc + bcoef * pc
                return (xc, rc, pc)

            xc, rc, pc = jax.lax.fori_loop(
                0, k, step, (xs[0], xs[0], xs[0])
            )
            return (xc + rc + pc)[None]

        return shard_map(
            shard_fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
            check_vma=False,
        )(xv).sum()

    return {
        "exchange": lambda k: float(exch_chain(x, ops, k)),
        "spmv": lambda k: float(spmv_chain(x, ops, k)),
        "dot": lambda k: float(dot_chain(x, k)),
        "axpy": lambda k: float(axpy_chain(x, k)),
    }


def _body_chain(dA, b, x0, fused, precond, rhs_batch,
                comms_kwargs: dict) -> Callable[[int], float]:
    """The REAL compiled CG body as a `_marginal_s` chain: one
    fixed-trip (tol=0) solve per call, programs cached per trip count
    by `_krylov_fn_for`. Side effect: fills ``comms_kwargs`` with the
    body's plan-level inventory kwargs (`run.comms_kwargs`)."""
    import numpy as np

    from ..parallel.tpu import make_cg_fn

    def run_chain(k: int) -> float:
        fn = make_cg_fn(
            dA, tol=0.0, maxiter=k, fused=fused, precond=precond,
            rhs_batch=rhs_batch,
        )
        comms_kwargs.update(fn.comms_kwargs)
        out = fn(b, x0, None)
        return float(np.asarray(out[1]).ravel()[0])  # host fetch

    return run_chain


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------


def capture_phase_profile(
    A,
    backend,
    fused: Optional[bool] = None,
    precond: bool = False,
    rhs_batch: Optional[int] = None,
    k1: int = 4,
    k2: int = 24,
    reps: Optional[int] = None,
) -> Optional[dict]:
    """Capture one `PhaseProfile` of the compiled CG body for ``A`` on
    ``backend`` (see module docstring). Returns the schema-versioned
    dict, or None when ``PA_PROF=0``.

    The profile is keyed by the palint lowering-case name + the
    operator fingerprint, and self-checks both honesty bands: the
    per-phase comms inventories sum per kind to
    `cg_comms_profile`'s per-iteration inventory (exact), and
    ``attributed_s_per_it / measured_s_per_it`` lands in
    `PHASE_SUM_BAND` (recorded as ``in_band``)."""
    import numpy as np

    from ..parallel.pvector import PVector
    from ..parallel.tpu import (
        DeviceVector,
        _block_on_cols_layout,
        _resolve_fused,
        device_matrix,
    )
    from .throughput import operator_fingerprint

    if not prof_enabled():
        return None
    reps = prof_reps() if reps is None else max(3, int(reps))
    dA = device_matrix(A, backend)
    dtype = np.float64
    fused_resolved = _resolve_fused(fused)

    bvec = PVector.full(1.0, A.cols, dtype=dtype)
    zvec = PVector.full(0.0, A.cols, dtype=dtype)
    if rhs_batch:
        b = _block_on_cols_layout([bvec] * int(rhs_batch), dA)
        x0 = _block_on_cols_layout(
            [zvec] * int(rhs_batch), dA, with_ghosts=True
        )
    else:
        b = DeviceVector.from_pvector(bvec, backend, dA.col_layout).data
        x0 = DeviceVector.from_pvector(zvec, backend, dA.col_layout).data

    band = PHASE_SUM_BAND_WIDE if rhs_batch else PHASE_SUM_BAND
    comms_kwargs: dict = {}
    body_chain = _body_chain(
        dA, b, x0, fused, precond, rhs_batch, comms_kwargs
    )
    measured = _marginal_s(body_chain, k1, k2, reps)
    if rhs_batch:
        comms_kwargs["rhs_batch"] = int(rhs_batch)
    per_it = cg_comms_profile(dA, dtype, **comms_kwargs)["per_iteration"]
    n_gathers = per_it["all_gather"]["ops"]

    method = "split-timer"
    # wall-clock timings on a shared host can still catch a load
    # spike between the total and the phase chains; re-measure the
    # WHOLE attempt (phases AND total, same protocol) up to 3
    # times, accept the first in-band ratio, and otherwise keep
    # the attempt closest to band-center — a consistently-broken
    # attribution still lands (and stays) out of band
    chains = _phase_chains(dA, rhs_batch)
    best = None
    for attempts in range(1, 4):
        t_exch = _marginal_s(chains["exchange"], k1, k2, reps)
        t_spmv = _marginal_s(chains["spmv"], k1, k2, reps)
        t_dot1 = _marginal_s(chains["dot"], k1, k2, reps)
        t_axpy = _marginal_s(chains["axpy"], k1, k2, reps)
        cand = {
            "halo_exchange": t_exch,
            "spmv_local": max(t_spmv - t_exch, 0.0),
            "dot_allgather": n_gathers * t_dot1,
            "axpy_sweep": t_axpy,
        }
        r = sum(cand.values()) / measured if measured > 0 else (
            float("inf")
        )
        dist = abs(math.log(r)) if r > 0 else float("inf")
        if best is None or dist < best[0]:
            best = (dist, cand, measured)
        if band[0] <= r <= band[1]:
            break
        if attempts < 3:  # the final attempt keeps `best` as-is
            measured = _marginal_s(body_chain, k1, k2, reps)
    _, phase_s, measured = best

    # the per-phase collective split of the per-iteration inventory:
    # permutes ride the halo update, gathers ride the dots, and any
    # kind neither phase owns lands in `unattributed` — which must be
    # EMPTY for the profile to reconcile (a future body introducing
    # e.g. reduce_scatter fails loudly here instead of vanishing)
    def _entry(kind, take):
        return {
            "ops": per_it[kind]["ops"] if take else 0,
            "bytes": per_it[kind]["bytes"] if take else 0,
        }

    phase_comms = {
        "halo_exchange": {
            k: _entry(k, k == "collective_permute") for k in COMM_KINDS
        },
        "dot_allgather": {
            k: _entry(k, k == "all_gather") for k in COMM_KINDS
        },
        "spmv_local": {k: _entry(k, False) for k in COMM_KINDS},
        "axpy_sweep": {k: _entry(k, False) for k in COMM_KINDS},
    }
    unattributed = {
        k: dict(per_it[k]) for k in COMM_KINDS
        if k not in ("collective_permute", "all_gather")
        and (per_it[k]["ops"] or per_it[k]["bytes"])
    }

    attributed = sum(phase_s.values())
    ratio = attributed / measured if measured > 0 else float("inf")
    return {
        "phase_schema_version": PHASE_SCHEMA_VERSION,
        "case": phase_case_name(
            fused_resolved, rhs_batch, bool(comms_kwargs.get("abft"))
        ),
        "fingerprint": operator_fingerprint(A),
        "lowering": lowering_descriptor(dA),
        "dtype": str(np.dtype(dtype)),
        "method": method,
        "trips": {"k1": int(k1), "k2": int(k2), "reps": int(reps)},
        "attempts": int(attempts),
        "phases": {
            p: {
                "s_per_it": round(phase_s[p], 9),
                "comms": phase_comms[p],
            }
            for p in PHASES
        },
        "unattributed_comms": unattributed,
        "per_iteration_comms": per_it,
        "comms_kwargs": dict(
            comms_kwargs, rhs_batch=comms_kwargs.get("rhs_batch")
        ),
        "measured_s_per_it": round(measured, 9),
        "attributed_s_per_it": round(attributed, 9),
        "ratio_attributed_over_measured": round(ratio, 6),
        "band": list(band),
        "in_band": bool(band[0] <= ratio <= band[1]),
    }


# ---------------------------------------------------------------------------
# verification / export
# ---------------------------------------------------------------------------


def reconcile_phases(profile: dict, dA=None) -> list:
    """Cross-check a `PhaseProfile` (fresh or loaded from disk) the
    same way `telemetry.comms.reconcile` checks a solve record.
    Returns human-readable mismatch strings (empty = reconciled):

    1. per kind, the phase inventories (+ unattributed) must sum to the
       profile's recorded per-iteration inventory;
    2. nothing may hide in ``unattributed_comms``;
    3. with ``dA`` given, the recorded per-iteration inventory must
       equal a freshly derived `cg_comms_profile` under the profile's
       own ``comms_kwargs`` (a stale committed profile fails here);
    4. the attributed/measured ratio must sit in the recorded band.
    """
    out = []
    if profile.get("phase_schema_version") != PHASE_SCHEMA_VERSION:
        return [
            f"phase_schema_version {profile.get('phase_schema_version')!r}"
            f" != {PHASE_SCHEMA_VERSION}"
        ]
    per_it = profile["per_iteration_comms"]
    for kind in COMM_KINDS:
        for field in ("ops", "bytes"):
            total = sum(
                profile["phases"][p]["comms"][kind][field] for p in PHASES
            ) + profile.get("unattributed_comms", {}).get(kind, {}).get(
                field, 0
            )
            if total != per_it[kind][field]:
                out.append(
                    f"{kind}.{field}: phase sum {total} != per-iteration "
                    f"inventory {per_it[kind][field]}"
                )
    if profile.get("unattributed_comms"):
        out.append(
            "unattributed collectives present: "
            f"{sorted(profile['unattributed_comms'])}"
        )
    if dA is not None:
        import numpy as np

        kwargs = dict(profile.get("comms_kwargs") or {})
        fresh = cg_comms_profile(
            dA, np.dtype(profile["dtype"]), **kwargs
        )["per_iteration"]
        if fresh != per_it:
            out.append(
                "recorded per-iteration inventory drifted from "
                f"cg_comms_profile: recorded {per_it} != fresh {fresh}"
            )
    lo, hi = profile.get("band", PHASE_SUM_BAND)
    ratio = profile["ratio_attributed_over_measured"]
    if not (lo <= ratio <= hi):
        out.append(
            f"attributed/measured ratio {ratio} outside the pinned "
            f"band [{lo}, {hi}]"
        )
    if profile.get("in_band") != (lo <= ratio <= hi):
        out.append("in_band flag inconsistent with ratio and band")
    return out


def phase_trace_events(profile: dict, pid: int = 3,
                       iterations: int = 1) -> list:
    """Chrome-trace spans of one profile: ``iterations`` synthetic
    iterations, each phase a consecutive span scaled by its measured
    s_per_it — the `tools/patrace.py --phases` merge feed, landing the
    attribution on the same Perfetto timeline as the solve records."""
    out = [
        {"name": "process_name", "ph": "M", "pid": pid,
         "args": {"name": "partitionedarrays_jl_tpu phase profile "
                          f"({profile.get('case')})"}},
    ]
    t = 0.0
    for it in range(max(1, int(iterations))):
        for p in PHASES:
            dur = profile["phases"][p]["s_per_it"] * 1e6
            out.append(
                {
                    "name": p,
                    "ph": "X",
                    "ts": t,
                    "dur": max(dur, 0.01),
                    "pid": pid,
                    "tid": 0,
                    "cat": "phase",
                    "args": {
                        "iteration": it,
                        "case": profile.get("case"),
                        "fingerprint": profile.get("fingerprint"),
                        "comms": profile["phases"][p]["comms"],
                        "method": profile.get("method"),
                    },
                }
            )
            t += max(dur, 0.01)
    return out


def render_phase_profile(profile: dict) -> str:
    """The operator-facing phase table."""
    lines = [
        f"phase profile: case={profile['case']} "
        f"operator={profile['fingerprint']} "
        f"lowering={profile['lowering']['a_oo']}/"
        f"{profile['lowering']['plan']} method={profile['method']}",
    ]
    total = profile["attributed_s_per_it"]
    for p in PHASES:
        ph = profile["phases"][p]
        share = ph["s_per_it"] / total if total > 0 else 0.0
        comms = ", ".join(
            f"{k}:{v['ops']} ops/{v['bytes']} B"
            for k, v in ph["comms"].items() if v["ops"]
        )
        lines.append(
            f"  {p:14s} {ph['s_per_it'] * 1e6:12.2f} us/it "
            f"({share:6.1%})" + (f"  [{comms}]" if comms else "")
        )
    lines.append(
        f"  {'attributed':14s} {total * 1e6:12.2f} us/it vs measured "
        f"{profile['measured_s_per_it'] * 1e6:.2f} us/it "
        f"(ratio {profile['ratio_attributed_over_measured']:.3f}, "
        f"band {profile['band']}, "
        f"{'in band' if profile['in_band'] else 'OUT OF BAND'})"
    )
    return "\n".join(lines)
