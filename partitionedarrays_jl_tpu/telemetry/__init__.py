"""patrace — runtime solver telemetry (the observability subsystem).

Four layers, each importable on its own (docs/observability.md has the
full catalog; `tools/patrace.py` is the CLI):

* `telemetry.record` — typed `SolveRecord`s replacing the ad-hoc info
  plumbing: config snapshot (lowering env key + ``PA_*`` env), residual
  and α/β trajectories, the structured event log (health guards, fault
  injections, SDC detections/rollbacks, checkpoint save/restore,
  compile-cache hit/miss/stale, recovery restarts). The legacy ``info``
  dict remains the return contract (`InfoDict` — a dict subclass with
  the record at ``info.record``).
* `telemetry.metrics` — process-wide named counters (cache hit/miss/
  stale-rekey, persistent-XLA-cache bridge, event tallies).
* `telemetry.comms` — static-vs-measured comms accounting: the
  plan-level collective inventory of each compiled CG body, reconciled
  against the lowered program's per-iteration/setup split (the palint
  runtime contract).
* `telemetry.trace` / `telemetry.artifacts` — Chrome-trace/Perfetto
  export of records + PTimer sections, and the shared schema-versioned
  record writer.

Hard contract (same discipline as ABFT): telemetry OFF is HLO-identical
to the pre-telemetry programs; telemetry ON adds ZERO collectives — the
α/β trace ring rides the while-loop carry (``PA_TRACE_ITERS``, a keyed
lowering flag), everything else is host-side.
"""
from .artifacts import ARTIFACT_SCHEMA_VERSION, stamp, write  # noqa: F401
from .histogram import (  # noqa: F401
    HISTOGRAM_SCHEMA_VERSION,
    LatencyHistogram,
    apply_delta,
)
from .registry import (  # noqa: F401
    CATALOG,
    REGISTRY_SCHEMA_VERSION,
    MetricSpec,
    Registry,
    mon_ewma,
    monitoring_enabled,
    registry,
)
from .throughput import (  # noqa: F401
    THROUGHPUT_SCHEMA_VERSION,
    ThroughputModel,
    operator_fingerprint,
    reset_model,
)
from .throughput import model as throughput_model  # noqa: F401
from .comms import (  # noqa: F401
    COMM_KINDS,
    cg_comms_profile,
    expected_from_report,
    observed_comms,
    reconcile,
)
from .metrics import (  # noqa: F401
    bump,
    install_jax_cache_listeners,
)
from .metrics import get as counter  # noqa: F401
from .metrics import reset as reset_counters  # noqa: F401
from .metrics import snapshot as counters  # noqa: F401
from .record import (  # noqa: F401
    RECORD_SCHEMA_VERSION,
    InfoDict,
    SolveRecord,
    TelemetryEvent,
    begin_record,
    clear_history,
    current_record,
    emit_event,
    last_record,
    list_persisted_records,
    load_record,
    metrics_dir,
    record_history,
    solve_scope,
    telemetry_enabled,
)
from .trace import (  # noqa: F401
    TRACE_SCHEMA_VERSION,
    annotate,
    chrome_trace,
    record_trace_events,
    write_chrome_trace,
)
from .profile import (  # noqa: F401
    PHASE_SCHEMA_VERSION,
    PHASE_SUM_BAND,
    PHASES,
    capture_phase_profile,
    phase_trace_events,
    reconcile_phases,
    render_phase_profile,
)
from .commsmatrix import (  # noqa: F401
    COMMS_MATRIX_SCHEMA_VERSION,
    classify_edge,
    measure_comms_matrix,
    reconcile_matrix,
    render_comms_matrix,
    static_matrix,
)
from . import spectrum  # noqa: F401
from .spectrum import (  # noqa: F401
    ANOMALY_KINDS,
    SPECTRUM_SCHEMA_VERSION,
    SpectrumStore,
    check_deadline_feasible,
    detect_anomalies,
    estimate_solve,
    lanczos_tridiagonal,
    measured_rate,
    observe_solve,
    poisson_fdm_analytic_extremes,
    predict_iters,
    reset_store,
    residual_norm,
    ritz_values,
    spec_admit_enabled,
    spec_enabled,
    spectrum_fingerprint,
)
from .spectrum import store as spectrum_store  # noqa: F401
from . import tracing  # noqa: F401
from .tracing import (  # noqa: F401
    SPAN_KINDS,
    TX_SCHEMA_VERSION,
    Span,
    TraceContext,
    mint_trace,
    parse_traceparent,
    start_span,
    tracing_enabled,
    verify_trace,
)

__all__ = [
    "ANOMALY_KINDS",
    "ARTIFACT_SCHEMA_VERSION",
    "SPECTRUM_SCHEMA_VERSION",
    "SpectrumStore",
    "check_deadline_feasible",
    "detect_anomalies",
    "estimate_solve",
    "lanczos_tridiagonal",
    "measured_rate",
    "observe_solve",
    "poisson_fdm_analytic_extremes",
    "predict_iters",
    "reset_store",
    "residual_norm",
    "ritz_values",
    "spec_admit_enabled",
    "spec_enabled",
    "spectrum",
    "spectrum_fingerprint",
    "spectrum_store",
    "CATALOG",
    "COMMS_MATRIX_SCHEMA_VERSION",
    "COMM_KINDS",
    "PHASES",
    "PHASE_SCHEMA_VERSION",
    "PHASE_SUM_BAND",
    "HISTOGRAM_SCHEMA_VERSION",
    "InfoDict",
    "LatencyHistogram",
    "MetricSpec",
    "RECORD_SCHEMA_VERSION",
    "REGISTRY_SCHEMA_VERSION",
    "Registry",
    "SPAN_KINDS",
    "Span",
    "SolveRecord",
    "THROUGHPUT_SCHEMA_VERSION",
    "TRACE_SCHEMA_VERSION",
    "TX_SCHEMA_VERSION",
    "TelemetryEvent",
    "TraceContext",
    "ThroughputModel",
    "annotate",
    "apply_delta",
    "begin_record",
    "bump",
    "capture_phase_profile",
    "cg_comms_profile",
    "chrome_trace",
    "classify_edge",
    "clear_history",
    "measure_comms_matrix",
    "phase_trace_events",
    "reconcile_matrix",
    "reconcile_phases",
    "render_comms_matrix",
    "render_phase_profile",
    "static_matrix",
    "counter",
    "counters",
    "current_record",
    "emit_event",
    "expected_from_report",
    "install_jax_cache_listeners",
    "last_record",
    "list_persisted_records",
    "load_record",
    "metrics_dir",
    "mint_trace",
    "mon_ewma",
    "monitoring_enabled",
    "parse_traceparent",
    "start_span",
    "tracing",
    "tracing_enabled",
    "verify_trace",
    "observed_comms",
    "operator_fingerprint",
    "reconcile",
    "record_history",
    "record_trace_events",
    "registry",
    "reset_counters",
    "reset_model",
    "solve_scope",
    "stamp",
    "telemetry_enabled",
    "throughput_model",
    "write",
    "write_chrome_trace",
]
