"""Doc/artifact traceability guard (round-5 rule: every number in the
docs traces to a committed artifact or carries its round tag).

Two stale-doc classes have actually shipped in this repo's history —
a capability claim that code had already obsoleted (docs/roadmap.md §1
"still require equal per-part boxes", contradicted by the shape-variant
`lax.switch` transfers in tpu_gmg.py and GMG_BENCH.json), and
historical bench numbers quoted without their round tag (the round-4
"11.1 GFLOP/s" lived only in a commit message). This file makes the
traceability rule enforce itself:

* known-stale claim patterns must not reappear in committed docs;
* superseded historical figures may only appear in a paragraph that
  carries a round/era tag;
* the committed artifacts and the bench guards that gate them must
  agree (band bounds in the artifact == the guard tables in tools/).
"""
import importlib.util
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOC_FILES = [
    "README.md",
    "docs/performance.md",
    "docs/roadmap.md",
    "docs/design.md",
    "docs/api.md",
    "docs/migration.md",
    "docs/resilience.md",
    "docs/static_analysis.md",
    "docs/observability.md",
    "docs/service.md",
]

#: Claims proven wrong by shipped code: these exact phrases must never
#: come back (each entry documents what obsoleted it).
BANNED_PATTERNS = [
    (
        r"still require equal per-part boxes",
        "obsoleted by the shape-variant lax.switch transfers "
        "(tpu_gmg.py, round 5; GMG_BENCH.json records the paths)",
    ),
    (
        r"practical floor under current XLA\s+while-loop semantics",
        "the round-2 conclusion was size-specific; superseded by the "
        "round-6 fused streaming CG body at large N",
    ),
]

#: Historical figures superseded by later rounds: quoting one is fine
#: ONLY in a paragraph that names its era (round N / rN / historical).
HISTORICAL_FIGURES = [
    "876 s",      # r2 assembly, now 30-108 s
    "365 s",      # r3 GMG hierarchy, now 54-139 s
    "299 s",      # r2 lowering, now 27-77 s
    "797 ms",     # r1 V-cycle, now 7.7 ms
    "9.32 ms",    # r5 standard-body CG iteration, now 6.77 ms fused
    "9.323",      # same figure as recorded in the r5 artifact
]
ERA_TAG = re.compile(r"(historical|rounds?\s*[0-9]|\br[0-9]\b)", re.I)


def _doc_paragraphs():
    for rel in DOC_FILES:
        path = os.path.join(REPO, rel)
        if not os.path.exists(path):
            continue
        text = open(path, encoding="utf-8").read()
        for para in re.split(r"\n\s*\n", text):
            yield rel, para


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_no_banned_stale_claims():
    hits = []
    for rel, para in _doc_paragraphs():
        for pat, why in BANNED_PATTERNS:
            if re.search(pat, para):
                hits.append((rel, pat, why))
    assert not hits, (
        "stale claims back in the docs (each was proven wrong by shipped "
        f"code): {hits}"
    )


def test_historical_figures_carry_their_round_tag():
    untagged = []
    for rel, para in _doc_paragraphs():
        for fig in HISTORICAL_FIGURES:
            if fig in para and not ERA_TAG.search(para):
                untagged.append((rel, fig, para[:120]))
    assert not untagged, (
        "superseded figures quoted without a round/era tag — either tag "
        f"the paragraph or update the number: {untagged}"
    )


def test_scale_bench_artifact_agrees_with_guard_bands():
    """The committed flagship artifact and the bench guard must agree:
    identical band bounds, and the recorded device metrics inside them
    (a lowered band with a stale artifact — or vice versa — is exactly
    the drift this file exists to catch)."""
    bench_scale = _load_tool("bench_scale")
    rec = json.load(open(os.path.join(REPO, "SCALE_BENCH.json")))
    for key, (lo, hi, kind) in bench_scale.SCALE_BANDS.items():
        band = rec["bands"].get(key)
        assert band is not None, f"artifact missing band {key}"
        assert (band["lo"], band["hi"]) == (lo, hi), (
            f"band bounds for {key} drifted: guard ({lo}, {hi}) vs "
            f"artifact ({band['lo']}, {band['hi']})"
        )
        if kind == "device":
            assert band["in_band"], (key, band)
    assert rec["bands_ok_device"] is True


def test_multirhs_artifact_agrees_with_guard_bands():
    """The committed multi-RHS flagship artifact and the bench guard
    must agree: identical band bounds, recorded device metrics inside
    them, and the curve rows the bands were derived from actually
    present and self-consistent (per_rhs = block / K; the K=8 speedup
    claim in the docs traces to THIS record)."""
    bench_mr = _load_tool("bench_multirhs")
    rec = json.load(open(os.path.join(REPO, "MULTIRHS_BENCH.json")))
    assert rec["methodology"] == bench_mr.METHODOLOGY
    assert rec["ks"] == list(bench_mr.KS)
    by_k = {row["K"]: row for row in rec["curve"]}
    assert set(by_k) == set(rec["ks"])
    for row in rec["curve"]:
        assert abs(
            row["per_rhs_s_per_it"] - row["block_s_per_it"] / row["K"]
        ) <= 1e-4 * row["per_rhs_s_per_it"], row  # artifact rounding
    for key, (lo, hi, kind) in bench_mr.MULTIRHS_BANDS.items():
        band = rec["bands"].get(key)
        assert band is not None, f"artifact missing band {key}"
        assert (band["lo"], band["hi"]) == (lo, hi), (key, band)
        k = int(key.rsplit("k", 1)[-1])
        assert band["measured"] == by_k[k]["per_rhs_speedup_vs_k1"], (
            key, band, by_k[k],
        )
        if kind == "device":
            assert band["in_band"], (key, band)
    # the acceptance floor: >= 1.5x per-RHS at K=8 on a >= 320^3 size
    assert rec["n"] >= 320 and rec["dofs"] == rec["n"] ** 3
    assert by_k[8]["per_rhs_speedup_vs_k1"] >= 1.5
    assert rec["bands_ok_device"] is True


def test_metric_catalog_agrees_with_registry_both_directions():
    """docs/observability.md's '### Metric catalog' table is the
    exhaustive declared-metric surface, machine-checked against
    `telemetry.registry.CATALOG` in BOTH directions: a metric the
    package declares (and bumps) that the table omits is an
    undocumented signal; a row naming an undeclared metric is a ghost.
    Type, unit, labels, and the bumped-at site must match the spec —
    the table may not claim an instrumentation point the code moved."""
    import re as _re

    from partitionedarrays_jl_tpu.telemetry import CATALOG

    text = open(
        os.path.join(REPO, "docs", "observability.md"), encoding="utf-8"
    ).read()
    m = re.search(
        r"### Metric catalog(.*?)\n## ", text, flags=re.S
    )
    assert m, "docs/observability.md lost its '### Metric catalog'"
    rows = _re.findall(
        r"^\| `([^`]+)` \| (\w+) \| (\S+) \| (.+?) \| `([^`]+)` \|",
        m.group(1), flags=_re.M,
    )
    assert rows, "metric catalog table unparsable (format drifted?)"
    documented = {r[0] for r in rows}
    declared = set(CATALOG)
    assert declared - documented == set(), (
        f"declared metrics missing from the doc table: "
        f"{declared - documented}"
    )
    assert documented - declared == set(), (
        f"ghost rows documenting undeclared metrics: "
        f"{documented - declared}"
    )
    for name, kind, unit, labels, where in rows:
        spec = CATALOG[name]
        assert kind == spec.kind, (name, kind, spec.kind)
        assert unit == spec.unit, (name, unit, spec.unit)
        assert where == spec.where, (name, where, spec.where)
        doc_labels = (
            () if labels.strip() in ("—", "-", "")
            else tuple(s.strip() for s in labels.split(","))
        )
        assert doc_labels == spec.labels, (name, doc_labels, spec.labels)


def test_throughput_model_ties_to_multirhs():
    """The committed THROUGHPUT_MODEL.json (round 12 — the adaptive-K
    input) must be the real thing: schema-versioned under the shared
    artifact envelope, its online-measured entries internally
    consistent (per_rhs = s_per_it/K, EWMA fed by >= 2 samples — a
    one-shot value is a bench row, not an online model), measured at
    every K the SERVICE_BENCH sweep ran, and its reference curve EQUAL
    to the committed MULTIRHS device record at every overlapping K —
    the committed model can never drift from the device curve it
    converges to."""
    from partitionedarrays_jl_tpu import telemetry

    bench_svc = _load_tool("bench_service")
    rec = json.load(open(os.path.join(REPO, "THROUGHPUT_MODEL.json")))
    mr = json.load(open(os.path.join(REPO, "MULTIRHS_BENCH.json")))
    assert rec["throughput_schema_version"] == (
        telemetry.THROUGHPUT_SCHEMA_VERSION
    )
    # the shared artifact envelope
    assert rec.get("schema_version") == telemetry.ARTIFACT_SCHEMA_VERSION
    assert rec.get("generated_by") == "bench_service"
    assert rec.get("platform") and isinstance(rec.get("pa_env"), dict)
    assert 0.0 < rec["ewma_alpha"] <= 1.0
    # online-measured entries: loadable, consistent, covering the sweep
    model = telemetry.ThroughputModel.load(rec)
    entries = rec["entries"]
    assert entries, "committed model must hold measured entries"
    for e in entries:
        assert abs(
            e["per_rhs_s_per_it"] - e["s_per_it"] / e["K"]
        ) <= 1e-6 * e["per_rhs_s_per_it"], e
        assert e["samples"] >= 2, (e, "an online EWMA needs >= 2 samples")
        assert e["iterations"] >= e["samples"], e
    fp = rec["operator_fingerprint"]
    dtype = rec["dtype"]
    measured_ks = set(model.curve(fp, dtype))
    assert measured_ks == set(bench_svc.KS), (measured_ks, bench_svc.KS)
    # suggest_k reads the committed curve coherently: never wider than
    # the queue, and the argmin of the measured per-RHS curve when wide
    curve = model.curve(fp, dtype)
    best = min(curve, key=lambda k: (curve[k], -k))
    assert model.suggest_k(fp, dtype, queue_depth=64, kmax=64) == best
    assert model.suggest_k(fp, dtype, queue_depth=1, kmax=64) == 1
    # the reference curve IS the MULTIRHS device record
    ref = rec["reference_curve"]
    assert ref["source"] == "MULTIRHS_BENCH.json"
    assert (ref["n"], ref["dtype"]) == (mr["n"], mr["dtype"])
    mr_by_k = {str(r["K"]): r for r in mr["curve"]}
    assert set(ref["per_rhs_s_per_it"]) == set(mr_by_k)
    for k, row in mr_by_k.items():
        assert ref["per_rhs_s_per_it"][k] == row["per_rhs_s_per_it"], k
        assert ref["per_rhs_speedup_vs_k1"][k] == (
            row["per_rhs_speedup_vs_k1"]
        ), k


def test_service_artifact_inherits_multirhs_floor():
    """The committed solve-service artifact (round 10) and its bench
    guard must agree — and the artifact's device claim must be
    TRACEABLE: the per-RHS gains it records are inherited from the
    committed MULTIRHS_BENCH.json record (the service feeds the
    identical compiled block program — tests/test_service.py pins the
    program-cache hit), so the two artifacts must carry EQUAL values,
    with the K=8 ≥ 1.5x acceptance floor intact. The locally measured
    service rows must be internally consistent (requests/s = K / wall,
    ratio = solo/service)."""
    bench_svc = _load_tool("bench_service")
    rec = json.load(open(os.path.join(REPO, "SERVICE_BENCH.json")))
    mr = json.load(open(os.path.join(REPO, "MULTIRHS_BENCH.json")))
    assert rec["methodology"] == bench_svc.METHODOLOGY
    assert rec["ks"] == list(bench_svc.KS)
    mr_by_k = {row["K"]: row for row in mr["curve"]}
    inh = rec["inherited"]
    assert inh["source"] == "MULTIRHS_BENCH.json"
    assert inh["per_rhs_gain_k8"] == mr_by_k[8]["per_rhs_speedup_vs_k1"]
    assert inh["per_rhs_gain_k16"] == mr_by_k[16]["per_rhs_speedup_vs_k1"]
    for key, (lo, hi, kind) in bench_svc.SERVICE_BANDS.items():
        band = rec["bands"].get(key)
        assert band is not None, f"artifact missing band {key}"
        assert (band["lo"], band["hi"], band["kind"]) == (lo, hi, kind), (
            key, band,
        )
        assert band["measured"] == inh[key]
        if kind == "device":
            assert band["in_band"], (key, band)
    # the acceptance floor, traceable to the MULTIRHS device record
    assert inh["per_rhs_gain_k8"] >= 1.5
    assert rec["bands_ok_device"] is True
    by_k = {row["K"]: row for row in rec["service_rows"]}
    assert set(by_k) == set(rec["ks"])
    for row in rec["service_rows"]:
        for leg in ("service", "solo"):
            rps = row[f"{leg}_requests_per_s"]
            assert abs(rps - row["K"] / row[f"{leg}_wall_s"]) <= 1e-3 * rps
        ratio = row["solo_wall_s"] / row["service_wall_s"]
        assert abs(row["service_vs_solo"] - ratio) <= 1e-2 * ratio, row
    # round 12: the metrics-on/off marginal — the drained requests/s
    # with the observability plane on vs killed must be recorded,
    # internally consistent, and inside its committed canary band (the
    # PR 9 acceptance criterion: metrics are measurably ~free)
    marg = rec["metrics_marginal"]
    ratio = marg["on_requests_per_s"] / marg["off_requests_per_s"]
    assert abs(marg["ratio_on_off"] - ratio) <= 1e-2 * ratio, marg
    for key, (lo, hi, kind) in bench_svc.METRICS_BANDS.items():
        band = rec["bands"][key]
        assert (band["lo"], band["hi"], band["kind"]) == (lo, hi, kind)
        assert band["measured"] == marg["ratio_on_off"]
        assert band["in_band"] and lo <= band["measured"] <= hi, band
    # round 16: the tracing-on/off marginal (patx) — same canary
    # convention; the ledger sentinel picks the band up like every
    # other (test_perf_ledger_covers_every_bench_artifact below)
    tx = rec["tracing_marginal"]
    ratio = tx["on_requests_per_s"] / tx["off_requests_per_s"]
    assert abs(tx["ratio_on_off"] - ratio) <= 1e-2 * ratio, tx
    for key, (lo, hi, kind) in bench_svc.TRACING_BANDS.items():
        band = rec["bands"][key]
        assert (band["lo"], band["hi"], band["kind"]) == (lo, hi, kind)
        assert band["measured"] == tx["ratio_on_off"]
        assert band["in_band"] and lo <= band["measured"] <= hi, band
    # the locally measured per-RHS table agrees with itself and covers
    # the sweep (its committed twin is THROUGHPUT_MODEL.json, checked
    # in test_throughput_model_ties_to_multirhs)
    per_rhs = {r["K"]: r for r in rec["measured_per_rhs"]}
    assert set(per_rhs) == set(rec["ks"])
    for r in rec["measured_per_rhs"]:
        assert abs(
            r["per_rhs_s_per_it"] - r["s_per_it"] / r["K"]
        ) <= 1e-6 * r["per_rhs_s_per_it"], r


def test_scale_curve_fused_headline_consistent_with_bench():
    """SCALE_CURVE's 464^3 fused marginal and SCALE_BENCH's full-solve
    per-iteration must describe the same kernel: marginal <= full-solve
    (the full solve carries dispatch overhead) and within ~15%."""
    curve = json.load(open(os.path.join(REPO, "SCALE_CURVE.json")))
    rec = json.load(open(os.path.join(REPO, "SCALE_BENCH.json")))
    row = next(r for r in curve["sizes"] if r["n"] == rec["n"])
    marginal_ms = row["cg_s_per_it"] * 1e3
    full_ms = rec["per_iteration_ms"]
    assert marginal_ms <= full_ms <= 1.15 * marginal_ms, (
        marginal_ms, full_ms,
    )
    # the A/B leg is present wherever the fused default is the headline
    assert "cg_unfused_s_per_it" in row and "cg_fused_speedup" in row


def test_abft_artifact_agrees_with_guard_bands():
    """The committed ABFT clean-path artifact (round 8) and the bench
    guard must agree: identical band bounds, the recorded
    collective-count parity (the zero-extra-collectives claim) actually
    TRUE with identical per-kind counts, and the overhead rows
    self-consistent. Device-kind bands gate only records measured on
    real TPUs — a cpu-platform record is the structural canary (its
    note must say so), never silently passed off as the acceptance
    number."""
    bench_abft = _load_tool("bench_abft")
    rec = json.load(open(os.path.join(REPO, "ABFT_BENCH.json")))
    assert rec["methodology"] == bench_abft.METHODOLOGY
    for key, (lo, hi, kind) in bench_abft.ABFT_BANDS.items():
        band = rec["bands"].get(key)
        assert band is not None, f"artifact missing band {key}"
        assert (band["lo"], band["hi"], band["kind"]) == (lo, hi, kind), (
            key, band,
        )
    par = rec["collective_parity"]
    assert par["parity"] is True
    assert par["counts_on"] == par["counts_off"]
    assert any(par["counts_on"].values()), "parity probe saw no collectives"
    for row in rec["sizes"]:
        assert row["dofs"] == row["n"] ** 3
        ratio = row["abft_on_s_per_it"] / row["abft_off_s_per_it"]
        assert abs(row["overhead_ratio"] - ratio) <= 1e-3 * ratio, row
    if rec["platform"] == "tpu":
        ns = {row["n"] for row in rec["sizes"]}
        assert set(bench_abft.DEVICE_SIZES) <= ns
        assert rec["bands_ok_device"] is True
    else:
        # the canary must declare itself: platform recorded, device
        # verdict left open, and the note explains the gating
        assert rec["bands_ok_device"] is None
        assert "real TPUs" in rec["note"]


def test_env_var_table_agrees_with_source_both_directions():
    """docs/api.md's '## Environment variables' table is the exhaustive
    env-flag surface, machine-checked against the package's actual
    reads (analysis.env_lint AST inventory) in BOTH directions: a flag
    the source reads but the table omits is an undocumented knob; a row
    the source no longer reads is a ghost. (The same invariant gates
    tools/palint.py --check; this copy keeps the doc-consistency suite
    self-contained.)"""
    from partitionedarrays_jl_tpu.analysis import (
        documented_env_names,
        env_read_inventory,
    )

    documented = documented_env_names(os.path.join(REPO, "docs", "api.md"))
    read = {r.name for r in env_read_inventory()}
    assert documented, "docs/api.md lost its '## Environment variables' table"
    assert read - documented == set(), (
        f"flags read in the package but undocumented: {read - documented}"
    )
    assert documented - read == set(), (
        f"ghost rows documenting flags never read: {documented - read}"
    )


def test_env_table_lowering_rows_name_their_key_site():
    """Every table row classed `lowering` must name the key site the
    lint actually resolves it through — the docs may not claim a
    coverage the AST cannot see."""
    from partitionedarrays_jl_tpu.analysis import key_coverage
    from partitionedarrays_jl_tpu.analysis.env_lint import (
        classify,
        env_table_rows,
    )

    cov = key_coverage()
    cls = classify()
    rows = env_table_rows(os.path.join(REPO, "docs", "api.md"))
    # parser-rot guard: a table reformat that breaks the shared row
    # extraction must fail here, not silently skip the invariants below
    assert len(rows) >= len(cls), (len(rows), len(cls))
    for name, rest in rows:
        entry = cls.get(name)
        # a ghost row (flag never read) is the both-directions test's
        # finding — skip here so each failure stays self-explanatory
        if entry is None:
            continue
        if entry["class"] == "lowering":
            assert name in cov, f"{name} documented lowering but unkeyed"
            assert f"`{cov[name]}`" in rest, (
                f"row for {name} must name its key site `{cov[name]}`"
            )
        else:
            assert "| lowering |" not in rest, name


def test_obs_artifact_agrees_with_guard_bands():
    """The committed telemetry-overhead artifact (round 9) and the
    bench guard must agree: identical band bounds, the recorded
    HLO-identity and collective-parity probes actually TRUE (telemetry
    off is the pre-telemetry program; the trace ring adds zero
    collectives), and the overhead rows self-consistent. Device-kind
    bands gate only records measured on real TPUs — a cpu-platform
    record is the structural canary (its note must say so)."""
    bench_obs = _load_tool("bench_obs")
    rec = json.load(open(os.path.join(REPO, "OBS_BENCH.json")))
    assert rec["methodology"] == bench_obs.METHODOLOGY
    assert rec["trace_depth"] == bench_obs.TRACE_DEPTH
    for key, (lo, hi, kind) in bench_obs.OBS_BANDS.items():
        band = rec["bands"].get(key)
        assert band is not None, f"artifact missing band {key}"
        assert (band["lo"], band["hi"], band["kind"]) == (lo, hi, kind), (
            key, band,
        )
    ident = rec["identity"]
    assert ident["hlo_identity"] is True
    assert ident["parity"] is True
    assert ident["counts_on"] == ident["counts_off"]
    assert any(ident["counts_on"].values()), "probe saw no collectives"
    for row in rec["sizes"]:
        assert row["dofs"] == row["n"] ** 3
        ratio = row["trace_on_s_per_it"] / row["trace_off_s_per_it"]
        assert abs(row["overhead_ratio"] - ratio) <= 1e-3 * ratio, row
    if rec["platform"] == "tpu":
        ns = {row["n"] for row in rec["sizes"]}
        assert set(bench_obs.DEVICE_SIZES) <= ns
        assert rec["bands_ok_device"] is True
    else:
        assert rec["bands_ok_device"] is None
        assert "real TPUs" in rec["note"]


def test_committed_comms_matrix_fabric_summaries_pin_both_ways():
    """The v2 schema's per-fabric summary is DERIVED state: for the
    committed COMMS_MATRIX.json the stored summary must equal the
    recomputation from the stored edge rows (stale-summary direction),
    and every fabric in the summary must be present among the edges
    (phantom-summary direction)."""
    from partitionedarrays_jl_tpu.telemetry import commsmatrix as cmx

    rec = json.load(open(os.path.join(REPO, "COMMS_MATRIX.json")))
    assert rec["comms_matrix_schema_version"] == (
        cmx.COMMS_MATRIX_SCHEMA_VERSION
    )
    assert rec["fabric_summary"] == cmx.fabric_summary(rec["edges"])
    assert set(rec["fabric_summary"]) == {
        e["fabric"] for e in rec["edges"]
    }
    # a single-process host has no slow-fabric traffic to record
    assert "dcn" not in rec["fabric_summary"]


def test_memory_footprint_artifact_agrees_with_budgets():
    """The committed static-memory footprint table (the paplan
    tentpole's admission-budget artifact, written by
    ``tools/palint.py --write-memory``) and the ``memory-budget``
    contract's pinned budgets must agree: identical budget tables
    (artifact == analysis.memory_report.MEMORY_BUDGETS), one row per
    FULL-matrix case, every recorded peak inside its budget, and the
    rows internally consistent (a compiled-leg peak comes from the
    buffer assignment, everything else from the conservative
    shape-sum)."""
    from partitionedarrays_jl_tpu.analysis import memory_report
    from partitionedarrays_jl_tpu.parallel.tpu import lowering_matrix

    rec = json.load(open(os.path.join(REPO, "MEMORY_FOOTPRINT.json")))
    assert rec["memory_schema_version"] == (
        memory_report.MEMORY_SCHEMA_VERSION
    )
    assert rec["budgets"] == {
        k: v for k, v in memory_report.MEMORY_BUDGETS.items()
    }, "artifact budgets drifted from MEMORY_BUDGETS — regenerate with "\
       "tools/palint.py --write-memory"
    names = {c["name"] for c in lowering_matrix(fast=False)}
    assert set(rec["cases"]) == names, (
        f"+{set(rec['cases']) - names} -{names - set(rec['cases'])}"
    )
    for name, fp in rec["cases"].items():
        budget = rec["budgets"][name]
        assert 0 < fp["peak_bytes"] <= budget, (name, fp, budget)
        assert fp["carry_bytes"] > 0, (name, "solve case must carry state")
        assert fp["plan_bytes"] > 0 and fp["operand_bytes"] > 0, (name, fp)
        assert fp["peak_source"] in ("hlo-buffer-assignment", "shape-sum")
        if fp["peak_source"] == "shape-sum":
            assert fp["peak_bytes"] == (
                fp["operand_bytes"] + 2 * fp["carry_bytes"]
            ), (name, fp)
    # the shared artifact envelope (telemetry.artifacts)
    assert rec.get("schema_version") and rec.get("generated_by")
    assert rec.get("platform") and isinstance(rec.get("pa_env"), dict)


def test_repro_artifacts_carry_the_shared_envelope():
    """tools/bench_repro.py writes through the shared schema-versioned
    artifact writer — the committed ``docs/repro_r*.json`` records must
    carry the full envelope like every ``*_BENCH.json`` (round-11
    port of the two straggler bench tools)."""
    paths = sorted(
        f for f in os.listdir(os.path.join(REPO, "docs"))
        if re.fullmatch(r"repro_r\d+\.json", f)
    )
    assert paths, "no committed repro records found"
    for name in paths:
        rec = json.load(open(os.path.join(REPO, "docs", name)))
        assert rec.get("schema_version"), name
        assert rec.get("generated_by") == "bench_repro", name
        assert rec.get("platform"), name
        assert isinstance(rec.get("pa_env"), dict), name
        # the record body the study documents is still intact
        assert rec["reps"] == len(rec["halo"]) == len(rec["spmv"]), name
        for k in ("halo", "halo_host_oracle", "spmv"):
            s = rec[k + "_stats"]
            assert s["min"] <= s["median"] <= s["max"], (name, k)


def test_perf_ledger_covers_every_bench_artifact_and_equals_sources():
    """The committed PERF_LEDGER.json (round 13 — the perf trajectory
    as a machine-checked object) must COVER every committed
    ``*_BENCH.json`` and carry, as each series' latest point, exactly
    the value its source artifact records — the ledger can never fork
    from the artifacts it summarizes. It also rides the shared
    artifact envelope like everything else committed."""
    from partitionedarrays_jl_tpu.telemetry import (
        ARTIFACT_SCHEMA_VERSION,
        ledger,
    )

    led = json.load(open(os.path.join(REPO, "PERF_LEDGER.json")))
    assert led["ledger_schema_version"] == ledger.LEDGER_SCHEMA_VERSION
    assert led.get("schema_version") == ARTIFACT_SCHEMA_VERSION
    assert led.get("generated_by") == "pareg"
    assert led.get("platform") and isinstance(led.get("pa_env"), dict)
    # the tracked set: every *_BENCH.json plus the banded extras the
    # ledger declares (round 17 added SPECTRUM.json)
    names = sorted(
        os.path.basename(p) for p in ledger.artifact_paths(REPO)
    )
    assert names, "no committed bench artifacts found"
    assert any(n.endswith("_BENCH.json") for n in names)
    assert "SPECTRUM.json" in names
    assert sorted(led["artifacts"]) == names, (
        "ledger coverage drifted — run tools/pareg.py --update"
    )
    for name in names:
        rec = json.load(open(os.path.join(REPO, name)))
        metrics = ledger.extract_metrics(name, rec)
        assert metrics, f"{name}: no extractable metrics"
        assert sorted(metrics) == led["artifacts"][name]["metrics"]
        assert led["artifacts"][name]["source_hash"] == (
            ledger.content_hash(rec)
        ), f"{name}: ledger is stale — run tools/pareg.py --update"
        for key, row in metrics.items():
            points = led["series"][f"{name}:{key}"]
            assert points[-1]["value"] == row["value"], (name, key)
            assert points[-1]["lo"] == row["lo"], (name, key)
            assert points[-1]["hi"] == row["hi"], (name, key)
    # the sentinel itself is green on the committed set (the same
    # invariant tools/pareg.py --check gates in tier-1)
    assert ledger.check_repo(REPO) == []


def test_every_committed_bench_artifact_is_schema_versioned():
    """Every committed ``*_BENCH.json`` carries the FULL shared artifact
    envelope (telemetry.artifacts): ``schema_version``, the generating
    tool, the accelerator ``platform``, and the ``pa_env`` snapshot —
    everything the writer unconditionally stamps. An artifact written
    around the shared writer (or hand-stamped with only the two
    eyeball-able keys) fails here, keeping the schema claim in
    docs/observability.md enforceable."""
    from partitionedarrays_jl_tpu.telemetry import ARTIFACT_SCHEMA_VERSION

    paths = sorted(
        f for f in os.listdir(REPO) if f.endswith("_BENCH.json")
    )
    assert paths, "no committed *_BENCH.json artifacts found"
    for name in paths:
        rec = json.load(open(os.path.join(REPO, name)))
        assert rec.get("schema_version") == ARTIFACT_SCHEMA_VERSION, (
            f"{name} missing/mismatched schema_version "
            f"(want {ARTIFACT_SCHEMA_VERSION}, "
            f"got {rec.get('schema_version')!r})"
        )
        assert rec.get("generated_by"), (
            f"{name} must name its generating tool"
        )
        assert rec.get("platform"), (
            f"{name} must record the platform it was measured on"
        )
        assert isinstance(rec.get("pa_env"), dict), (
            f"{name} must carry the PA_* environment snapshot "
            "(the writer stamps it unconditionally — empty is fine)"
        )


def test_gate_artifact_agrees_with_guard_bands():
    """The committed front-door artifact (round 14 — ROADMAP item 1's
    acceptance leg) and the bench guard must agree: identical band
    bounds, a multi-client leg with N>=2 tenants under a budget that
    FORCED at least one eviction during load, the per-class attainment
    read from the pamon registry deltas equal to the client-side
    outcome table, and the interactive class meeting its target WHILE
    shedding was active — measured, not asserted. Canary-kind bands
    gate on every platform."""
    bench_gate = _load_tool("bench_gate")
    rec = json.load(open(os.path.join(REPO, "GATE_BENCH.json")))
    assert rec["methodology"] == bench_gate.METHODOLOGY
    for key, (lo, hi, kind) in bench_gate.GATE_BANDS.items():
        band = rec["bands"].get(key)
        assert band is not None, f"artifact missing band {key}"
        assert (band["lo"], band["hi"], band["kind"]) == (lo, hi, kind), (
            key, band,
        )
        assert band["in_band"], (key, band)
    # N>=2 operators under a budget that cannot hold them all resident
    assert len(rec["tenants"]) >= 2
    assert rec["budget_bytes"] < sum(
        t["footprint_bytes"] for t in rec["tenants"]
    )
    multi = rec["multi_client"]
    assert multi["clients"] >= 2
    assert multi["evictions_during_load"] >= 1
    # shedding was ACTIVE, absorbed entirely by the lowest class,
    # and the interactive target held while it was
    assert multi["shed_total"] >= 1
    per = multi["per_class"]
    assert per["besteffort"]["shed"] == multi["shed_total"]
    assert per["interactive"]["shed"] == 0
    target = multi["attainment_target"]
    assert rec["bands"]["interactive_attainment"]["lo"] == target
    assert per["interactive"]["attainment"] >= target
    # attainment is the pamon readout, consistent with the client side
    for cls, row in per.items():
        assert row["pamon_requests"] == row["submitted"] - row["shed"], (
            cls, row,
        )
        assert row["pamon_hits"] == row["done"], (cls, row)
        if row["pamon_requests"]:
            want = row["pamon_hits"] / row["pamon_requests"]
            assert abs(row["attainment"] - want) <= 1e-6, (cls, row)
    # eviction cost is internally consistent
    ev = rec["eviction_cost"]
    ratio = ev["cold_solve_s"] / ev["warm_solve_s"]
    assert abs(ev["ratio"] - ratio) <= 1e-2 * max(ratio, 1.0), ev
    assert abs(
        ev["page_in_overhead_s"]
        - max(0.0, ev["cold_solve_s"] - ev["warm_solve_s"])
    ) <= 2e-6, ev  # fields round independently of their difference
    # round 18's saturation leg: an open-loop offered-load curve with
    # a measured knee — the knee is the LAST level that met the SLO
    # (all done, interactive attainment >= target, sustained/offered
    # >= ratio target), and the knee bands are derived from it, not
    # asserted independently
    sat = rec["saturation"]
    assert sat["probe_base_rps"] > 0
    curve = sat["curve"]
    assert [lv["capacity_multiple"] for lv in curve] == list(
        sat["levels_capacity_multiples"]
    )
    for lv in curve:
        assert lv["requests"] == sat["requests_per_level"]
        assert lv["offered_rps"] > 0 and lv["window_s"] > 0
        want_sust = lv["sustained_rps"] / lv["offered_rps"]
        # fields round to 6 decimals independently of their quotient
        assert abs(lv["sustained_ratio"] - want_sust) <= 1e-4, lv
        want_ok = (
            lv["done"] == lv["requests"]
            and lv["attainment"]["interactive"]
            >= sat["attainment_target"]
            and lv["sustained_ratio"] >= sat["sustain_ratio_target"]
        )
        assert lv["meets_slo"] == want_ok, lv
        # pamon saw every completed request of the window
        assert lv["pamon_count"] == lv["done"], lv
        assert lv["pamon_p99_s"] >= lv["pamon_p50_s"], lv
    knee = sat["knee"]
    assert knee is not None, "the committed curve must exhibit a knee"
    ok_levels = [lv for lv in curve if lv["meets_slo"]]
    assert ok_levels and knee == ok_levels[-1]
    assert rec["bands"]["saturation_knee_rps"]["measured"] == (
        knee["offered_rps"]
    )
    assert rec["bands"]["saturation_attainment_at_knee"]["measured"] == (
        knee["attainment"]["interactive"]
    )
    # the shared artifact envelope
    assert rec.get("schema_version") and rec.get("generated_by") == (
        "bench_gate"
    )
    assert rec.get("platform") and isinstance(rec.get("pa_env"), dict)


def test_spectrum_artifact_agrees_with_analytic_and_bands():
    """The committed SPECTRUM.json (round 17 — the convergence
    observatory) is the real thing: shared artifact envelope, a
    loadable schema-versioned store, a conformance block whose
    ANALYTIC eigenvalues equal a fresh closed-form recomputation, a κ̂
    band whose measured ratio is arithmetically consistent with its
    own numbers AND the documented [0.5, 1.05] window (Ritz converges
    from inside — the ratio may never exceed ~1), and >= 3 forecast
    (operator, tol) pairs with the worst relative error in band. The
    perf ledger covers it like every bench artifact (the coverage test
    above picks it up via telemetry.ledger.artifact_paths)."""
    from partitionedarrays_jl_tpu import telemetry
    from partitionedarrays_jl_tpu.telemetry import ledger

    path = os.path.join(REPO, "SPECTRUM.json")
    rec = json.load(open(path))
    # envelope + schema + store round-trip
    assert rec.get("schema_version") == telemetry.ARTIFACT_SCHEMA_VERSION
    assert rec.get("generated_by") == "paspec"
    assert rec.get("platform") and isinstance(rec.get("pa_env"), dict)
    assert rec["spectrum_schema_version"] == (
        telemetry.SPECTRUM_SCHEMA_VERSION
    )
    store = telemetry.SpectrumStore.load(rec)
    conf = rec["conformance"]
    spec = store.spec(conf["fingerprint"], conf["dtype"],
                      conf["minv_class"])
    assert spec is not None and spec["samples"] >= 1
    # the analytic pin: closed form recomputed fresh, not trusted
    lo, hi = telemetry.poisson_fdm_analytic_extremes(rec["probe"]["ns"])
    assert conf["analytic_lam_min"] == lo
    assert conf["analytic_lam_max"] == hi
    assert conf["analytic_kappa"] == pytest.approx(hi / lo, rel=1e-12)
    # Ritz estimates lie INSIDE the analytic spectrum (to rounding)
    assert conf["estimated_lam_min"] >= 0.99 * lo
    assert conf["estimated_lam_max"] <= 1.01 * hi
    band = rec["bands"]["spectrum_kappa_ratio"]
    ratio = conf["estimated_kappa"] / conf["analytic_kappa"]
    assert band["measured"] == pytest.approx(ratio, abs=1e-6)
    assert (band["lo"], band["hi"]) == (0.5, 1.05)
    assert band["in_band"] is True
    assert band["lo"] <= band["measured"] <= band["hi"]
    # the forecast acceptance: >= 3 pairs, worst error banded
    fband = rec["bands"]["spectrum_forecast_rel_error_max"]
    pairs = rec["forecast"]
    assert len(pairs) >= 3
    errs = [p["rel_error"] for p in pairs]
    assert all(e is not None for e in errs)
    assert fband["measured"] == pytest.approx(max(errs), abs=1e-6)
    assert fband["in_band"] is True and max(errs) <= fband["hi"]
    for p in pairs:
        assert p["rel_error"] == pytest.approx(
            abs(p["predicted"] - p["actual"]) / max(1, p["actual"]),
            abs=1e-6,
        )
    # tighter tol may never forecast FEWER iterations (monotonicity)
    preds = [p["predicted"] for p in sorted(
        pairs, key=lambda p: -p["tol"]
    )]
    assert preds == sorted(preds)
    # the ledger folds it in (extract_metrics sees the bands table)
    assert path in ledger.artifact_paths(REPO)
    metrics = ledger.extract_metrics("SPECTRUM.json", rec)
    assert set(metrics) == {
        "spectrum_kappa_ratio", "spectrum_forecast_rel_error_max"
    }
