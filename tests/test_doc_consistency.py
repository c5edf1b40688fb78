"""Doc/record traceability guard (round-5 rule: every number in the
docs traces to a committed record or carries its round tag).

Stale-doc classes that have actually shipped in this repo's history —
a capability claim that code had already obsoleted ("still require
equal per-part boxes", contradicted by the shape-variant `lax.switch`
transfers in tpu_gmg.py), historical numbers quoted without their round
tag (the round-4 "11.1 GFLOP/s" lived only in a commit message), and
instructions naming a tool or record that is gone. This file makes the
traceability rule enforce itself:

* known-stale claim patterns must not reappear in committed docs;
* superseded historical figures may only appear in a paragraph that
  carries a round/era tag;
* every repo path a document names exists;
* the committed records carry the shared envelope and agree with the
  code that derives them (budgets, fabric summaries, the analytic κ).
"""
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOC_FILES = [
    "README.md",
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
        "(tpu_gmg.py, round 5)",
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


#: ``docs/<name>.md|json``, ``tools/<name>.py``, ``benchmark/<path>`` or
#: an upper-case top-level ``<NAME>.json``; a name with a wildcard or a
#: placeholder (``*``, ``<``) does not match and is skipped.
REPO_PATH = re.compile(
    r"(?<![\w/.-])("
    r"tools/[\w-]+\.py"
    r"|docs/[\w-]+\.(?:md|json)"
    r"|benchmark/[\w./-]*\w"
    r"|[A-Z][A-Z0-9_]*\.json"
    r")(?![\w*<])"
)
#: The documents that describe the system as it is. CHANGES.md, PERF.md
#: and ROADMAP.md hold history, which may name a deleted file.
PATH_CHECKED_DOCS = [
    "README.md",
    "docs/api.md",
    "docs/design.md",
    "docs/migration.md",
    "docs/observability.md",
    "docs/resilience.md",
    "docs/service.md",
    "docs/static_analysis.md",
]


def _code_spans(text):
    """The text of every fenced block and every inline code span."""
    fenced = re.compile(r"^```[^\n]*\n(.*?)^```", re.S | re.M)
    for m in fenced.finditer(text):
        yield m.group(1)
    for m in re.finditer(r"`([^`\n]+)`", fenced.sub("", text)):
        yield m.group(1)


@pytest.mark.parametrize("doc", PATH_CHECKED_DOCS)
def test_every_repo_path_a_document_names_exists(doc):
    """A tool, record or document that is deleted must not live on as a
    dangling instruction: every repo path named in code in ``doc``
    exists in the tree."""
    text = open(os.path.join(REPO, doc), encoding="utf-8").read()
    named = {
        m.group(1)
        for span in _code_spans(text)
        for m in REPO_PATH.finditer(span)
    }
    missing = sorted(
        p for p in named if not os.path.exists(os.path.join(REPO, p))
    )
    assert not missing, f"{doc} names paths that do not exist: {missing}"


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


@pytest.mark.parametrize(
    "name,tool",
    [
        ("MEMORY_FOOTPRINT.json", "palint"),
        ("COMMS_MATRIX.json", "paprof"),
        ("SPECTRUM.json", "paspec"),
        ("PHASE_PROFILE.json", "paprof"),
        ("ELASTIC_BENCH.json", "paelastic"),
    ],
)
def test_every_committed_record_carries_the_envelope(name, tool):
    """Every committed record carries the FULL shared envelope
    (telemetry.artifacts): ``schema_version``, the generating tool, the
    ``platform`` and the ``pa_env`` snapshot — everything the writer
    stamps unconditionally. A record written around the shared writer
    (or hand-stamped with only the two eyeball-able keys) fails here,
    keeping the schema claim in docs/observability.md enforceable."""
    from partitionedarrays_jl_tpu.telemetry import ARTIFACT_SCHEMA_VERSION

    rec = json.load(open(os.path.join(REPO, name)))
    assert rec.get("schema_version") == ARTIFACT_SCHEMA_VERSION, (
        f"{name}: schema_version {rec.get('schema_version')!r}, "
        f"want {ARTIFACT_SCHEMA_VERSION}"
    )
    assert rec.get("generated_by") == tool, (name, rec.get("generated_by"))
    assert rec.get("platform"), f"{name} must record its platform"
    assert isinstance(rec.get("pa_env"), dict), (
        f"{name} must carry the PA_* environment snapshot "
        "(the writer stamps it unconditionally — empty is fine)"
    )


def test_spectrum_artifact_agrees_with_analytic_and_bands():
    """The committed SPECTRUM.json (round 17 — the convergence
    observatory) is the real thing: shared artifact envelope, a
    loadable schema-versioned store, a conformance block whose
    ANALYTIC eigenvalues equal a fresh closed-form recomputation, a κ̂
    band whose measured ratio is arithmetically consistent with its
    own numbers AND the documented [0.5, 1.05] window (Ritz converges
    from inside — the ratio may never exceed ~1), and >= 3 forecast
    (operator, tol) pairs with the worst relative error in band."""
    from partitionedarrays_jl_tpu import telemetry

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
