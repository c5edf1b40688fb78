#!/usr/bin/env python
"""Flat vs two-level exchange A/B bench -> TWOLEVEL_BENCH.json.

The node-aware PR's perf artifact (ISSUE 18), same discipline as the
s-step/ABFT/OBS ones: the SAME operator built twice on one dcn-weighted
probe —

* ``flat``      the generic edge-colored plan (``PA_TPU_BOX=0``), every
                cross-node edge its own slow-fabric message;
* ``twolevel``  the node-aware schedule (``PA_TPU_TWOLEVEL=1`` with the
                row-based ``PA_TPU_NODE_MAP``): outbound slow-fabric
                slots gathered to one per-node representative, ONE
                rep-to-rep transfer per ordered (node, node) pair,
                scattered on arrival; ICI-class neighbors keep their
                direct ppermute rounds.

Probe: 8 parts in a (2, 4) box partition with the node map splitting
the two part ROWS across two nodes — every part has a cross-node
neighbor, so the flat schedule pays 8 slow-fabric edges (4 face + 4
corner) that aggregation collapses to 2 node-pair transfers shipping
only the payload-packed stage slab.

What the artifact pins:

* **Static reductions** (deterministic plan structure, band kind
  ``static`` — gates on every platform): the slow-fabric edge count
  drops 4x (8 -> 2) and slow-fabric wire bytes drop 2x (the flat
  rounds ship the full padded slab per edge; the node tier ships the
  packed stage), both read off `telemetry.commsmatrix.static_matrix`
  fabric summaries with the SAME node map classifying both plans.
* **The measured-not-guessed decision** (band kind ``static``): a
  synthetic dcn-weighted cost matrix — the flat plan's edge rows
  stamped with `SYNTH_MODEL` timings — is fit back through
  `fit_fabric_model` (linear data, so the lstsq recovery is exact) and
  fed to `twolevel_decision` via ``matrix_path``, exercising the same
  committed-matrix path ``PA_TPU_COMMS_MATRIX`` feeds in ``auto``
  mode. The modeled speedup it derives is deterministic and
  band-checked.
* **Measured exchange ratio**: per-round marginal-chain timings
  (`measure_comms_matrix`) of both schedules. On real TPUs the ratio
  is the device acceptance band; on the cpu platform it is only the
  wide structural canary — XLA-CPU "fabrics" are all memcpys, so the
  two-level detour's extra intra-node hops make it SLOWER on the host,
  exactly as the cost model predicts when alpha_dcn == alpha_ici
  (the established ABFT/OBS/SSTEP gating).

``tools/pareg.py`` folds the committed artifact into PERF_LEDGER.json.

Usage:
    python tools/bench_twolevel.py            # refresh TWOLEVEL_BENCH.json
    python tools/bench_twolevel.py --dry-run  # print without writing
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np

METHODOLOGY = "v1-twolevel"

#: Probe geometry: 8 parts, two rows of four, the node map splitting
#: the rows across two nodes — 8 flat cross-node edges, 2 node pairs.
PARTS = (2, 4)
NS = (8, 8)
NODE_MAP = "0,0,0,0,1,1,1,1"

#: The synthetic dcn-weighted per-fabric cost model stamped onto the
#: flat matrix's edge rows (``s = alpha + payload_bytes * beta``):
#: slow-fabric latency 30x the fast fabric's, bandwidth 20x lower —
#: the regime the TAPSpMV split targets. `fit_fabric_model` must
#: recover the dcn entry from the stamped rows (the dcn edges carry 2
#: distinct payload sizes — face and corner — so the slow-fabric fit
#: engages; the single-size ici edges keep the documented prior).
SYNTH_MODEL = {
    "ici": {"alpha_s": 1.0e-6, "beta_s_per_byte": 1.0 / 40.0e9},
    "dcn": {"alpha_s": 30.0e-6, "beta_s_per_byte": 1.0 / 2.0e9},
}

#: Guard bands for the committed artifact; keys match
#: TWOLEVEL_BENCH.json["bands"] (tests/test_doc_consistency.py asserts
#: the committed artifact and this table agree). The static kinds are
#: deterministic plan/model structure and gate on EVERY platform; the
#: device kind gates only records measured on real TPUs.
TWOLEVEL_BANDS = {
    "dcn_edge_reduction": (3.9, 4.1, "static"),
    "dcn_wire_reduction": (1.9, 2.1, "static"),
    "modeled_speedup": (3.0, 4.2, "static"),
    "twolevel_exchange_speedup": (1.1, 32.0, "device"),
}

#: Wide sanity bounds for the cpu-canary row: the measured ratio on
#: the host pins "both schedules compile, run, and time within a sane
#: ratio", never a perf claim (module docstring — the host detour is
#: legitimately slower).
CANARY_BANDS = {
    "twolevel_exchange_cpu_canary": (0.02, 50.0, "canary"),
}


def _mesh():
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        jax.config.update("jax_enable_x64", True)
    return jax, platform


def _fabric_block(matrix: dict) -> dict:
    """The per-fabric rollup the record carries per schedule, plus the
    wire-round tier structure."""
    return {
        "rounds": matrix["rounds"],
        "round_tiers": matrix["round_tiers"],
        "per_device_bytes": matrix["static"]["per_device_bytes"],
        "fabric_summary": matrix["fabric_summary"],
        "exchange_s": matrix["exchange_s"],
        "round_s": matrix["round_s"],
    }


def main():
    argv = sys.argv[1:]
    dry = "--dry-run" in argv
    jax, platform = _mesh()

    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.models import assemble_poisson
    from partitionedarrays_jl_tpu.parallel.tpu import (
        TPUBackend,
        _env_overrides,
        device_matrix,
    )
    from partitionedarrays_jl_tpu.telemetry import artifacts
    from partitionedarrays_jl_tpu.telemetry import commsmatrix as cm

    pa.enable_compilation_cache()

    backend = TPUBackend(devices=jax.devices()[: int(np.prod(PARTS))])
    node_of = [int(x) for x in NODE_MAP.split(",")]

    ENV_FLAT = {"PA_TPU_BOX": "0"}
    ENV_TWO = {
        "PA_TPU_BOX": "0",
        "PA_TPU_TWOLEVEL": "1",
        "PA_TPU_NODE_MAP": NODE_MAP,
    }

    def build(env):
        def driver(parts):
            A, b, xe, x0 = assemble_poisson(parts, NS)
            return A

        with _env_overrides(env):
            A = pa.prun(driver, backend, PARTS)
            dA = device_matrix(A, backend)
        return A, dA

    A_f, dA_f = build(ENV_FLAT)
    A_t, dA_t = build(ENV_TWO)
    plan_t = dA_t.col_plan
    assert hasattr(plan_t, "tl_rounds"), (
        "probe did not build a two-level plan"
    )

    # both schedules under the SAME fabric view: the flat plan carries
    # no node map, so classify it with the probe's (the two-level plan
    # labels through its own — they must be the identical function).
    # the env scopes stay up through measurement: measure_comms_matrix
    # re-resolves the plan from the environment
    classify = lambda s, d: cm.classify_edge(s, d, node_of=node_of)
    with _env_overrides(ENV_FLAT):
        m_flat = cm.measure_comms_matrix(A_f, backend, classify=classify)
    with _env_overrides(ENV_TWO):
        m_two = cm.measure_comms_matrix(A_t, backend)
    for label, m in (("flat", m_flat), ("twolevel", m_two)):
        assert m["static_check"] == [], (label, m["static_check"])

    dcn_f = m_flat["fabric_summary"]["dcn"]
    dcn_t = m_two["fabric_summary"]["dcn"]
    edge_red = dcn_f["edges"] / dcn_t["edges"]
    wire_red = dcn_f["wire_bytes"] / dcn_t["wire_bytes"]
    extra_ici_rounds = sum(
        1 for t in m_two["round_tiers"] if t in ("gather", "scatter")
    )
    speedup = m_flat["exchange_s"] / m_two["exchange_s"]
    print(
        f"[bench_twolevel] dcn edges {dcn_f['edges']} -> "
        f"{dcn_t['edges']} ({edge_red:.2f}x), wire bytes "
        f"{dcn_f['wire_bytes']} -> {dcn_t['wire_bytes']} "
        f"({wire_red:.2f}x), +{extra_ici_rounds} ici hops",
        flush=True,
    )
    print(
        f"[bench_twolevel] exchange: flat "
        f"{m_flat['exchange_s'] * 1e6:.1f} us vs twolevel "
        f"{m_two['exchange_s'] * 1e6:.1f} us ({speedup:.3f}x, "
        f"platform={platform})",
        flush=True,
    )

    # the synthetic dcn-weighted matrix: flat edge rows stamped from
    # SYNTH_MODEL, round-tripped through a file so the decision takes
    # the same path a committed PA_TPU_COMMS_MATRIX does
    synth = json.loads(json.dumps(m_flat))
    for e in synth["edges"]:
        mod = SYNTH_MODEL.get(e["fabric"])
        if mod is None:  # self edges never leave the chip
            e["measured_s"] = 0.0
            continue
        e["measured_s"] = round(
            mod["alpha_s"]
            + e["payload_bytes"] * mod["beta_s_per_byte"], 12
        )
    synth["fabric_summary"] = cm.fabric_summary(synth["edges"])
    profile = [
        (e["src"], e["dst"], e["payload_slots"])
        for e in m_flat["edges"]
    ]
    with tempfile.NamedTemporaryFile(
        "w", suffix=".json", delete=False
    ) as fh:
        json.dump(synth, fh)
        synth_path = fh.name
    try:
        fit = cm.fit_fabric_model(synth)
        decision = cm.twolevel_decision(
            profile, node_of, matrix_path=synth_path
        )
    finally:
        os.unlink(synth_path)
    assert decision["model_source"] == synth_path
    # the dcn weighting is what drives the decision: its fit must
    # engage and recover the synthetic model (linear data -> exact
    # lstsq). The probe's ici edges all carry one payload size, so the
    # ici entry legitimately keeps the prior (`fit_fabric_model`'s
    # documented single-size fallback) — recorded, not hidden.
    assert fit["dcn"]["source"] == "fit", fit
    assert abs(
        fit["dcn"]["alpha_s"] - SYNTH_MODEL["dcn"]["alpha_s"]
    ) <= 0.05 * SYNTH_MODEL["dcn"]["alpha_s"], fit
    modeled = decision["flat_modeled_s"] / decision["twolevel_modeled_s"]
    assert decision["use"], decision
    print(
        f"[bench_twolevel] synthetic-fit decision: use={decision['use']} "
        f"flat {decision['flat_modeled_s'] * 1e6:.1f} us vs twolevel "
        f"{decision['twolevel_modeled_s'] * 1e6:.1f} us "
        f"({modeled:.3f}x modeled)",
        flush=True,
    )

    measured = {
        "dcn_edge_reduction": round(edge_red, 4),
        "dcn_wire_reduction": round(wire_red, 4),
        "modeled_speedup": round(modeled, 4),
        "twolevel_exchange_speedup": (
            round(speedup, 4) if platform == "tpu" else None
        ),
    }
    bands = {}
    for key, (lo, hi, kind) in TWOLEVEL_BANDS.items():
        v = measured[key]
        bands[key] = {
            "lo": lo, "hi": hi, "kind": kind, "measured": v,
            "in_band": None if v is None else bool(lo <= v <= hi),
        }
    if platform != "tpu":
        for key, (lo, hi, kind) in CANARY_BANDS.items():
            v = round(speedup, 4)
            bands[key] = {
                "lo": lo, "hi": hi, "kind": kind, "measured": v,
                "in_band": bool(lo <= v <= hi),
            }

    rec = {
        "methodology": METHODOLOGY,
        "protocol": (
            "per-round marginal-chain timings "
            "(telemetry.commsmatrix.measure_comms_matrix) of the SAME "
            "operator built flat and two-level; static reductions read "
            "off the per-fabric summaries with one shared node map; "
            "the modeled decision fit from a synthetic dcn-weighted "
            "matrix through the PA_TPU_COMMS_MATRIX file path"
        ),
        "platform": platform,
        "dtype": m_flat["dtype"],
        "probe": (
            f"Poisson FDM on a {NS} grid, ({PARTS[0]},{PARTS[1]}) box "
            f"partition, node map {NODE_MAP} (2 nodes x 4 parts: every "
            "part has a cross-node neighbor)"
        ),
        "node_map": NODE_MAP,
        "synth_model": SYNTH_MODEL,
        "synthetic_fit": {
            "model": fit,
            "decision": decision,
        },
        "flat": _fabric_block(m_flat),
        "twolevel": dict(
            _fabric_block(m_two),
            node_of=m_two["node_of"],
            decision=m_two["decision"],
        ),
        "reductions": {
            "dcn_edge_reduction": round(edge_red, 4),
            "dcn_wire_reduction": round(wire_red, 4),
            "extra_ici_wire_rounds": extra_ici_rounds,
        },
        "exchange_speedup": round(speedup, 4),
        "bands": bands,
        "bands_ok_device": (
            all(
                b["in_band"]
                for b in bands.values()
                if b["kind"] == "device" and b["measured"] is not None
            )
            if platform == "tpu"
            else None
        ),
        "note": (
            "static-kind bands are deterministic plan/model structure "
            "and gate on every platform; the device-kind exchange "
            "speedup gates only records measured on real TPUs — the "
            "cpu-platform record carries the wide structural canary "
            "instead (XLA-CPU collectives are memcpys, so the "
            "two-level detour's extra intra-node hops legitimately "
            "cost more on the host, exactly what the cost model "
            "predicts for alpha_dcn == alpha_ici)"
        ),
    }
    artifacts.write(
        os.path.join(REPO, "TWOLEVEL_BENCH.json"), rec,
        tool="bench_twolevel", dry_run=dry,
    )


if __name__ == "__main__":
    main()
