"""Measured per-neighbor, per-round exchange cost matrix.

Every exchange plan in the repo is COSTED as if all neighbors were
equidistant: `telemetry.comms` counts rounds and per-device bytes, and
the palint contracts pin those counts — but nothing else records what
each edge actually COSTS on the fabric it crosses. This module does:

* **Static side** — `static_matrix` walks the plan's round schedule
  (generic `DeviceExchangePlan`: the edge-colored `ppermute` rounds;
  box plan: one round per geometric direction) into per-edge rows:
  source part, destination part, payload slots (real ghost entries),
  wire slots (the padded slab the round actually ships), bytes of
  each. The per-round totals must RECONCILE exactly with
  `comms._exchange_inventory` — the same accounting the palint
  runtime contract pins — so the matrix can never drift from the
  counts the rest of the repo trusts.
* **Measured side** — `measure_comms_matrix` times each round as its
  own compiled `ppermute` chain (generic plan; the box plan's slice
  rounds share one fused program, so its rounds are attributed
  proportionally to wire bytes and flagged so) with the marginal-chain
  protocol, then splits each round's cost over its edges by payload
  share.
* **Fabric classification** — every edge is labeled by the link it
  crosses (``self`` / ``ici`` [same process] / ``dcn`` [cross-process]
  by default; pass ``classify`` to override with topology knowledge).

The export (`COMMS_MATRIX.json` via the shared artifacts writer) is
schema-versioned and carries the static reconciliation verdict inline.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

__all__ = [
    "COMMS_MATRIX_SCHEMA_VERSION",
    "classify_edge",
    "fabric_summary",
    "static_matrix",
    "reconcile_matrix",
    "measure_comms_matrix",
    "render_comms_matrix",
]

#: v2: the record carries a recomputable per-fabric ``fabric_summary``
#: block.
COMMS_MATRIX_SCHEMA_VERSION = 2

def classify_edge(src: int, dst: int, backend=None,
                  P: Optional[int] = None) -> str:
    """Default fabric label of one exchange edge: ``self`` loops stay
    on-device, parts whose devices share a process are ``ici``
    neighbors, cross-process edges are ``dcn``. The hook point for
    topology-aware classifiers (mesh-axis distance, rack locality)."""
    if src == dst:
        return "self"
    if backend is None or P is None:
        return "unknown"
    try:
        devs = list(backend.mesh(P).devices.flat)
        return (
            "ici"
            if devs[src].process_index == devs[dst].process_index
            else "dcn"
        )
    except Exception:
        return "unknown"


def fabric_summary(edges: Sequence[dict]) -> dict:
    """The v2 per-fabric rollup — recomputed from the edge rows, never
    stored independently (test_doc_consistency pins committed summary
    == this recomputation both ways)."""
    out: dict = {}
    for e in edges:
        s = out.setdefault(
            e["fabric"],
            {"edges": 0, "payload_bytes": 0, "wire_bytes": 0,
             "measured_s": 0.0},
        )
        s["edges"] += 1
        s["payload_bytes"] += int(e["payload_bytes"])
        s["wire_bytes"] += int(e["wire_bytes"])
        s["measured_s"] = round(
            s["measured_s"] + float(e.get("measured_s") or 0.0), 12
        )
    return out


def _plan_rounds(plan):
    """Normalize either plan family into
    ``[(wire_slots, [(src, dst, payload_slots), ...]), ...]``."""
    import numpy as np

    from ..parallel.tpu_box import BoxExchangePlan

    if isinstance(plan, BoxExchangePlan):
        out = []
        for d in plan.info.dirs:
            out.append(
                (int(d.size), [(int(p), int(q), int(d.size))
                               for p, q in d.perm])
            )
        return out
    out = []
    L = int(plan.snd_idx.shape[-1])
    for r, perm in enumerate(plan.perms):
        edges = []
        for src, dst in perm:
            payload = int(np.count_nonzero(plan.snd_mask[src, r]))
            edges.append((int(src), int(dst), payload))
        out.append((L, edges))
    return out


def static_matrix(
    plan,
    dtype,
    K: int = 1,
    backend=None,
    classify: Optional[Callable[[int, int], str]] = None,
) -> dict:
    """The plan-derived half of the matrix: per-round, per-edge byte
    accounting (no timing). ``classify(src, dst)`` overrides the
    default fabric labeling."""
    import numpy as np

    from ..parallel.tpu_box import BoxExchangePlan

    itemsize = int(np.dtype(dtype).itemsize)
    K = max(1, int(K))
    P = plan.layout.P
    rounds = _plan_rounds(plan)
    label = classify or (
        lambda s, d: classify_edge(s, d, backend=backend, P=P)
    )
    edges: List[dict] = []
    per_device_bytes = 0
    for r, (wire_slots, edge_list) in enumerate(rounds):
        per_device_bytes += wire_slots * K * itemsize
        for src, dst, payload in edge_list:
            edges.append(
                {
                    "round": r,
                    "src": src,
                    "dst": dst,
                    "fabric": label(src, dst),
                    "payload_slots": payload,
                    "wire_slots": wire_slots,
                    "payload_bytes": payload * K * itemsize,
                    "wire_bytes": wire_slots * K * itemsize,
                }
            )
    return {
        "comms_matrix_schema_version": COMMS_MATRIX_SCHEMA_VERSION,
        "plan": "box" if isinstance(plan, BoxExchangePlan) else "generic",
        "P": int(P),
        "K": K,
        "dtype": str(np.dtype(dtype)),
        "rounds": len(rounds),
        "edges": edges,
        "fabric_summary": fabric_summary(edges),
        "static": {
            "ops": len(rounds),
            "per_device_bytes": per_device_bytes,
        },
    }


def reconcile_matrix(matrix: dict, dA, abft: bool = False) -> list:
    """Cross-check a matrix (fresh or loaded) against
    `comms._exchange_inventory` — the per-halo (ops, bytes) accounting
    every SolveRecord and palint contract already runs on. Returns
    mismatch strings (empty = the two derivations agree)."""
    import numpy as np

    from .comms import _exchange_inventory

    out = []
    if matrix.get("comms_matrix_schema_version") != (
        COMMS_MATRIX_SCHEMA_VERSION
    ):
        return [
            "comms_matrix_schema_version "
            f"{matrix.get('comms_matrix_schema_version')!r} != "
            f"{COMMS_MATRIX_SCHEMA_VERSION}"
        ]
    ops, nbytes = _exchange_inventory(
        dA, abft, int(matrix["K"]), np.dtype(matrix["dtype"]).itemsize
    )
    if matrix["static"]["ops"] != ops:
        out.append(
            f"rounds: matrix {matrix['static']['ops']} != "
            f"_exchange_inventory {ops}"
        )
    if matrix["static"]["per_device_bytes"] != nbytes:
        out.append(
            f"per-device bytes: matrix "
            f"{matrix['static']['per_device_bytes']} != "
            f"_exchange_inventory {nbytes}"
        )
    by_round: dict = {}
    for e in matrix["edges"]:
        by_round.setdefault(e["round"], []).append(e)
    if sorted(by_round) != list(range(matrix["rounds"])):
        out.append(
            f"edge rows cover rounds {sorted(by_round)} but the matrix "
            f"declares {matrix['rounds']} rounds"
        )
    for r, edges in by_round.items():
        wires = {e["wire_slots"] for e in edges}
        if len(wires) != 1:
            out.append(f"round {r}: inconsistent wire slots {wires}")
        for e in edges:
            if e["payload_slots"] > e["wire_slots"]:
                out.append(
                    f"round {r} edge {e['src']}->{e['dst']}: payload "
                    f"{e['payload_slots']} exceeds wire {e['wire_slots']}"
                )
    summary = matrix.get("fabric_summary")
    if summary is not None and summary != fabric_summary(
        matrix["edges"]
    ):
        out.append(
            "fabric_summary does not recompute from the edge rows"
        )
    return out


def _round_chains(plan, backend, K: int):
    """One jitted k-step chain per GENERIC-plan round: that round's
    pack + `ppermute` + unpack, with an owned<-ghost feedback so the
    pack stays inside the loop."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..parallel.tpu import _stage

    shard_map = jax.shard_map
    layout = plan.layout
    P, W = layout.P, layout.W
    o0, g0, trash = layout.o0, layout.g0, layout.trash
    mesh = backend.mesh(P)
    spec = backend.parts_spec()
    si = _stage(backend, plan.snd_idx, P)
    sm = _stage(backend, plan.snd_mask, P)
    ri = _stage(backend, plan.rcv_idx, P)
    shape = (P, W, K) if K > 1 else (P, W)
    x0 = np.zeros(shape, dtype=np.float64)
    x0[:, o0:g0] = 1.0
    x = jax.device_put(x0, jax.sharding.NamedSharding(mesh, spec))
    eps = np.float64(1e-30)

    chains = []
    for r, perm in enumerate(plan.perms):

        @functools.partial(jax.jit, static_argnums=4)
        def chain(xv, siv, smv, riv, k, _r=r, _perm=perm):
            def shard_fn(xs, sis, sms, ris):
                v, s_i, s_m, r_i = xs[0], sis[0], sms[0], ris[0]

                def step(_, vv):
                    mask = s_m[_r].reshape(
                        s_m[_r].shape + (1,) * (vv.ndim - 1)
                    )
                    buf = jnp.where(mask, vv[s_i[_r]], 0)
                    buf = jax.lax.ppermute(buf, "parts", perm=_perm)
                    vv = vv.at[r_i[_r]].set(buf)
                    vv = vv.at[trash].set(0)
                    return vv.at[o0].add(vv[g0] * eps)

                return jax.lax.fori_loop(0, k, step, v)[None]

            return shard_map(
                shard_fn, mesh=mesh, in_specs=(spec,) * 4,
                out_specs=spec, check_vma=False,
            )(xv, siv, smv, riv).sum()

        chains.append(
            lambda k, _c=chain: float(_c(x, si, sm, ri, k))
        )
    return chains


def _full_exchange_chain(plan, dA, backend, K: int):
    """One chain running the WHOLE exchange per step (the box plan's
    rounds compile into one fused slice program — per-round programs
    would not measure what ships)."""
    import functools

    import jax
    import numpy as np

    from ..parallel.tpu import (
        _matrix_operands,
        _shard_exchange,
        _shard_ops,
    )

    shard_map = jax.shard_map
    layout = plan.layout
    P, W = layout.P, layout.W
    o0, g0 = layout.o0, layout.g0
    mesh = backend.mesh(P)
    spec = backend.parts_spec()
    ops = _matrix_operands(dA)
    specs = jax.tree.map(lambda _: spec, ops)
    body = _shard_exchange(plan, "set")
    shape = (P, W, K) if K > 1 else (P, W)
    x0 = np.zeros(shape, dtype=np.float64)
    x0[:, o0:g0] = 1.0
    x = jax.device_put(x0, jax.sharding.NamedSharding(mesh, spec))
    eps = np.float64(1e-30)

    @functools.partial(jax.jit, static_argnums=2)
    def chain(xv, m, k):
        def shard_fn(xs, ms):
            mm = _shard_ops(jax, ms)

            def step(_, vv):
                vv = body(vv, mm["si"], mm["sm"], mm["ri"])
                return vv.at[o0].add(vv[g0] * eps)

            return jax.lax.fori_loop(0, k, step, xs[0])[None]

        return shard_map(
            shard_fn, mesh=mesh, in_specs=(spec, specs),
            out_specs=spec, check_vma=False,
        )(xv, m).sum()

    return lambda k: float(chain(x, ops, k))


def measure_comms_matrix(
    A,
    backend,
    dtype=None,
    K: int = 1,
    k1: int = 8,
    k2: int = 64,
    reps: Optional[int] = None,
    classify: Optional[Callable[[int, int], str]] = None,
) -> dict:
    """The full matrix: `static_matrix` of the operator's column plan
    plus measured per-round timings (marginal-chain protocol,
    `PA_PROF_REPS` medians) split over edges by payload share.
    Generic plans get true per-round chains
    (``attribution="measured-round"``); box plans ship all directions
    in one fused program, so rounds carry proportional shares of the
    full-exchange cost (``attribution="proportional"``)."""
    import numpy as np

    from ..parallel.tpu import device_matrix
    from ..parallel.tpu_box import BoxExchangePlan
    from .profile import _marginal_s, prof_reps
    from .throughput import operator_fingerprint

    dtype = np.float64 if dtype is None else np.dtype(dtype)
    reps = prof_reps() if reps is None else max(3, int(reps))
    dA = device_matrix(A, backend)
    plan = dA.col_plan
    matrix = static_matrix(
        plan, dtype, K=K, backend=backend, classify=classify
    )
    matrix["fingerprint"] = operator_fingerprint(A)
    matrix["trips"] = {"k1": int(k1), "k2": int(k2), "reps": int(reps)}

    if isinstance(plan, BoxExchangePlan):
        total = _marginal_s(
            _full_exchange_chain(plan, dA, backend, K), k1, k2, reps
        )
        wire_total = matrix["static"]["per_device_bytes"]
        round_s = []
        for r in range(matrix["rounds"]):
            share = next(
                e["wire_bytes"] for e in matrix["edges"]
                if e["round"] == r
            ) / max(wire_total, 1)
            round_s.append(total * share)
        matrix["attribution"] = "proportional"
    else:
        chains = _round_chains(plan, backend, K)
        round_s = [_marginal_s(c, k1, k2, reps) for c in chains]
        total = sum(round_s)
        matrix["attribution"] = "measured-round"

    for e in matrix["edges"]:
        peers = [
            x for x in matrix["edges"] if x["round"] == e["round"]
        ]
        payload_total = sum(x["payload_bytes"] for x in peers)
        share = (
            e["payload_bytes"] / payload_total
            if payload_total
            else 1.0 / len(peers)
        )
        e["measured_s"] = round(round_s[e["round"]] * share, 12)
    matrix["round_s"] = [round(v, 12) for v in round_s]
    matrix["exchange_s"] = round(total, 12)
    matrix["fabric_summary"] = fabric_summary(matrix["edges"])
    matrix["static_check"] = reconcile_matrix(matrix, dA)
    return matrix


def render_comms_matrix(matrix: dict) -> str:
    """Operator-facing table: one line per edge, grouped by round."""
    lines = [
        f"comms matrix: operator={matrix.get('fingerprint', '?')} "
        f"plan={matrix['plan']} P={matrix['P']} K={matrix['K']} "
        f"dtype={matrix['dtype']} rounds={matrix['rounds']} "
        f"(attribution: {matrix.get('attribution', 'static-only')})"
    ]
    for e in matrix["edges"]:
        t = e.get("measured_s")
        bw = (
            f"  {e['payload_bytes'] / t / 1e6:10.2f} MB/s"
            if t else ""
        )
        lines.append(
            f"  round {e['round']}: {e['src']:>2} -> {e['dst']:<2} "
            f"[{e['fabric']:>4}] "
            f"payload {e['payload_bytes']:>8} B / "
            f"wire {e['wire_bytes']:>8} B"
            + (f"  {t * 1e6:10.2f} us" if t is not None else "")
            + bw
        )
    for fabric, s in sorted(
        (matrix.get("fabric_summary") or {}).items()
    ):
        lines.append(
            f"  [{fabric}] {s['edges']} edges, payload "
            f"{s['payload_bytes']} B, wire {s['wire_bytes']} B, "
            f"{s['measured_s'] * 1e6:.2f} us"
        )
    if matrix.get("exchange_s") is not None:
        lines.append(
            f"  full exchange: {matrix['exchange_s'] * 1e6:.2f} us/halo, "
            f"{matrix['static']['per_device_bytes']} B/device"
        )
    check = matrix.get("static_check")
    if check is not None:
        lines.append(
            "  static reconciliation vs comms inventory: "
            + ("OK" if not check else "; ".join(check))
        )
    return "\n".join(lines)
