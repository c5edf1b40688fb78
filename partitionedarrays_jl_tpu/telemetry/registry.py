"""The typed process-wide metric registry (pamon's data plane).

PR 6 left the process with ONE metric type — the ad-hoc counter dict in
`telemetry.metrics` — and the solve service (PR 7) runs blind: no queue
depth, no latency distributions, no SLO accounting. This module is the
typed successor: counters (monotonic), gauges (set/inc/dec), and
histograms (`telemetry.histogram.LatencyHistogram` — fixed buckets,
mergeable, deterministic), all behind ONE lock, with JSON and
Prometheus-text exporters and a declared CATALOG that
docs/observability.md's metric table is machine-checked against
(tests/test_doc_consistency.py).

Design rules:

* **One lock.** `Registry.lock` serializes every mutation — counters,
  gauges, histogram observations, AND the telemetry history ring in
  `record.py` (which used to carry its own lock; the service background
  worker mutates both from its thread, so they share this one —
  hammer-tested in tests/test_pamon.py).
* **Counters are always on** (a guarded int increment): the PA 6
  contract that tests assert cache behavior on counters holds under
  every env. The richer instrumentation — histograms/gauges bumped by
  the service hot path — is gated by ``PA_MON`` (default on; `0` turns
  the observe/set calls into no-ops at the call sites). ``PA_METRICS``
  keeps its PR 6 meaning untouched: it kills the RECORD/EVENT layer
  only, never the registry.
* **Declared metrics.** Everything the package itself bumps is declared
  in `CATALOG` (name -> kind/unit/labels/where/desc). Undeclared names
  still work (tests, ad-hoc probes) but are invisible to the doc
  check — the catalog is the reviewed metric surface.
* **Zero device impact.** Nothing here can reach a traced program:
  the registry is host-side Python; the overhead pin (service slab is
  a program-cache HIT with the registry fully enabled) lives in
  tests/test_pamon.py.

Env knobs (host-side, NON_LOWERING-exempt with reasons):

* ``PA_MON`` (default ``1``) — service/solver instrumentation switch:
  `0` stops histogram/gauge recording and throughput-model updates
  (counters and the PR 6 record layer are unaffected).
* ``PA_MON_EWMA`` (default ``0.25``) — EWMA smoothing factor of the
  online throughput model (`telemetry.throughput`).
"""
from __future__ import annotations

import json
import os
import threading
from typing import Dict, Iterable, Optional, Tuple

from ..utils.locksan import sanitized
from .histogram import LatencyHistogram

__all__ = [
    "REGISTRY_SCHEMA_VERSION",
    "CATALOG",
    "MetricSpec",
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "registry",
    "monitoring_enabled",
    "mon_ewma",
]

REGISTRY_SCHEMA_VERSION = 1


def monitoring_enabled() -> bool:
    """The PA_MON switch: gates histogram/gauge instrumentation and
    throughput-model updates (NOT counters, NOT the record layer)."""
    return os.environ.get("PA_MON", "1") != "0"


def mon_ewma() -> float:
    """PA_MON_EWMA in (0, 1]; out-of-range or unparsable -> 0.25."""
    try:
        v = float(os.environ.get("PA_MON_EWMA", "0.25") or "0.25")
    except ValueError:
        return 0.25
    return v if 0.0 < v <= 1.0 else 0.25


class MetricSpec:
    """One catalog row: the reviewed identity of a declared metric."""

    __slots__ = ("name", "kind", "unit", "labels", "where", "desc")

    def __init__(self, name: str, kind: str, unit: str, where: str,
                 desc: str, labels: Tuple[str, ...] = ()):
        assert kind in ("counter", "gauge", "histogram"), kind
        self.name = name
        self.kind = kind
        self.unit = unit
        self.labels = tuple(labels)
        self.where = where
        self.desc = desc


def _spec(name, kind, unit, where, desc, labels=()):
    return MetricSpec(name, kind, unit, where, desc, labels)


#: The reviewed metric surface. docs/observability.md's catalog table is
#: machine-checked against THIS dict both directions
#: (tests/test_doc_consistency.py) — add the doc row when you add an
#: entry. ``events.*`` is the one wildcard family (one counter per
#: telemetry event kind; the kinds are docs/observability.md's event
#: catalog).
CATALOG: Dict[str, MetricSpec] = {
    s.name: s
    for s in [
        # -- PR 6 cache/event counters (absorbed from metrics.py) -----
        _spec("lowering_cache.hit", "counter", "1",
              "parallel/tpu.py:device_matrix",
              "per-matrix staging cache hit"),
        _spec("lowering_cache.miss", "counter", "1",
              "parallel/tpu.py:device_matrix",
              "first staging of a matrix onto a backend"),
        _spec("lowering_cache.stale_rekey", "counter", "1",
              "parallel/tpu.py:device_matrix",
              "staging re-run because a lowering env flag flipped"),
        _spec("program_cache.hit", "counter", "1",
              "parallel/tpu.py:_krylov_fn_for",
              "compiled-program cache hit on a DeviceMatrix"),
        _spec("program_cache.miss", "counter", "1",
              "parallel/tpu.py:_krylov_fn_for",
              "compiled-program cache miss (build + compile)"),
        _spec("persistent_cache.hit", "counter", "1",
              "telemetry/metrics.py:install_jax_cache_listeners",
              "JAX on-disk XLA executable cache hit (jax.monitoring)"),
        _spec("persistent_cache.miss", "counter", "1",
              "telemetry/metrics.py:install_jax_cache_listeners",
              "JAX on-disk XLA executable cache miss"),
        # -- what JAX reports of its own compile path, summed (PR 36) --
        _spec("compile.trace_us", "counter", "us",
              "telemetry/metrics.py:install_jax_cache_listeners",
              "tracing of jitted functions to jaxprs, as JAX reports it "
              "(/jax/core/compile/jaxpr_trace_duration), each span's "
              "self time: a jit traced inside another counts once"),
        _spec("compile.lower_us", "counter", "us",
              "telemetry/metrics.py:install_jax_cache_listeners",
              "lowering of jaxprs to MLIR modules "
              "(/jax/core/compile/jaxpr_to_mlir_module_duration)"),
        _spec("compile.backend_us", "counter", "us",
              "telemetry/metrics.py:install_jax_cache_listeners",
              "backend compilation "
              "(/jax/core/compile/backend_compile_duration), less the "
              "cache retrievals JAX times inside it: XLA and Mosaic at "
              "work, and a miss's look in the cache"),
        _spec("compile.cache_load_us", "counter", "us",
              "telemetry/metrics.py:install_jax_cache_listeners",
              "retrieval of executables from the persistent cache, hits "
              "only (/jax/compilation_cache/cache_retrieval_time_sec)"),
        _spec("compile.programs", "counter", "1",
              "telemetry/metrics.py:install_jax_cache_listeners",
              "backend compile events: programs compiled or loaded"),
        _spec("events.*", "counter", "1",
              "telemetry/record.py:emit_event",
              "one counter per telemetry event kind emitted"),
        # -- device solve boundaries (one bump site per solve) ---------
        _spec("solve.calls", "counter", "1",
              "parallel/tpu.py:_count_staged",
              "device solves whose vectors were staged (single and "
              "block alike)"),
        _spec("solve.staged_bytes", "counter", "bytes",
              "parallel/tpu.py:_count_staged",
              "bytes of the device frames the solves' vectors were "
              "staged into"),
        _spec("solve.fetched_bytes", "counter", "bytes",
              "parallel/tpu.py:_answer_to_host, _outputs_to_host",
              "bytes of the solves' outputs that crossed to the host: "
              "the parts' values and the scalars, or a whole frame "
              "where the answer is lifted on the host"),
        _spec("solve.device_packs", "counter", "1",
              "parallel/tpu.py:_count_vector",
              "vectors whose device frame a program on the parts' "
              "devices made from the parts' values"),
        _spec("solve.host_packs", "counter", "1",
              "parallel/tpu.py:_count_vector",
              "vectors whose frame was filled on the host and staged "
              "whole (lids not owned-first, or several processes)"),
        _spec("solve.device_lifts", "counter", "1",
              "parallel/tpu.py:_count_vector",
              "vectors whose parts' values a program on the parts' "
              "devices took out of the frame before the fetch"),
        _spec("solve.host_lifts", "counter", "1",
              "parallel/tpu.py:_count_vector",
              "vectors fetched as a whole frame and lifted on the host"),
        _spec("solve.block_lane_major", "counter", "1",
              "parallel/tpu.py:tpu_block_cg",
              "block solves whose program held the K columns lane-major, "
              "(K, W), each through the solo solve's coded kernel (the "
              "record's block_layout says 'lanes'; every other block "
              "solve says 'columns' and leaves this unchanged)"),
        # -- one operator's lowering, DeviceMatrix.__init__ (PR 36) ------
        _spec("lowering.wall_us", "counter", "us",
              "parallel/tpu.py:_LowerSpans",
              "wall time of DeviceMatrix.__init__ (span pa:lower), "
              "summed over the operators lowered"),
        _spec("lowering.detect_us", "counter", "us",
              "parallel/tpu.py:_LowerSpans",
              "of it, inside the _detect_* analyses (span "
              "pa:lower:detect)"),
        _spec("lowering.upload_us", "counter", "us",
              "parallel/tpu.py:_LowerSpans",
              "of it, inside _stage of the operands (span "
              "pa:lower:upload); the rest is pa:lower:layout"),
        _spec("lowering.upload_bytes", "counter", "bytes",
              "parallel/tpu.py:_LowerSpans",
              "bytes of the host operands handed to _stage there"),
        # -- the supernode-dense lowering, where an operator is staged --
        _spec("lowering.sd.nnz", "counter", "1",
              "parallel/tpu.py:_count_sd_lowering",
              "stored non-zeros of the operators staged in the "
              "supernode-dense form"),
        _spec("lowering.sd.dense_entries", "counter", "1",
              "parallel/tpu.py:_count_sd_lowering",
              "entries of the dense group blocks made of them (nnz over "
              "this is the fill)"),
        _spec("lowering.sd.bytes", "counter", "bytes",
              "parallel/tpu.py:_count_sd_lowering",
              "bytes of those blocks: what one product streams"),
        _spec("lowering.sd.groups", "counter", "1",
              "parallel/tpu.py:_count_sd_lowering",
              "supernode groups (one dense block each, pad groups "
              "included)"),
        _spec("lowering.sd.gather_slots", "counter", "1",
              "parallel/tpu.py:_count_sd_lowering",
              "padded external node slots the products gather"),
        # -- streamed diagonals, where an operator is staged ------------
        _spec("lowering.stream.operators", "counter", "1",
              "parallel/tpu.py:_count_stream_lowering",
              "operators staged as streamed diagonals (dia_mode "
              "'stream'), 1 each"),
        _spec("lowering.stream.diagonals", "counter", "1",
              "parallel/tpu.py:_count_stream_lowering",
              "stored diagonals of the operators staged as streamed "
              "diagonals (dia_mode 'stream')"),
        _spec("lowering.stream.value_bytes", "counter", "bytes",
              "parallel/tpu.py:_count_stream_lowering",
              "bytes uploaded for them, all parts, the kernel's padding "
              "in: what one product streams"),
        _spec("lowering.stream.pallas", "counter", "1",
              "parallel/tpu.py:_count_stream_lowering",
              "of those operators, the ones the Mosaic kernel takes (the "
              "others take the XLA shifted-slice form)"),
        _spec("lowering.stream.block_rows", "counter", "1",
              "parallel/tpu.py:_count_stream_lowering",
              "lane rows of a block of the kernel's plan"),
        _spec("lowering.stream.x_window_rows", "counter", "1",
              "parallel/tpu.py:_count_stream_lowering",
              "lane rows of x the kernel fetches for each block: the "
              "block and the halo on both sides (over block_rows: how "
              "often x is read)"),
        _spec("lowering.stream.blocks", "counter", "1",
              "parallel/tpu.py:_count_stream_lowering",
              "blocks of the kernel's plan"),
        _spec("lowering.stream.window_slots", "counter", "1",
              "parallel/tpu.py:_count_stream_lowering",
              "VMEM slots of the kernel's x window: 2, block i+1's "
              "window in flight while block i computes"),
        # -- coded diagonals on the padded frame, where staged ----------
        _spec("lowering.coded.operators", "counter", "1",
              "parallel/tpu.py:_count_coded_lowering",
              "coded operators staged on the padded frame (the coded "
              "Mosaic kernel's plan made)"),
        _spec("lowering.coded.block_rows", "counter", "1",
              "parallel/tpu.py:_count_coded_lowering",
              "lane rows of a block of the kernel's plan"),
        _spec("lowering.coded.halo_rows", "counter", "1",
              "parallel/tpu.py:_count_coded_lowering",
              "lane rows of the operator's halo on either side of a "
              "block"),
        _spec("lowering.coded.x_window_rows", "counter", "1",
              "parallel/tpu.py:_count_coded_lowering",
              "lane rows of the operand the kernel fetches for each "
              "block in a CG solve of the default body (over "
              "block_rows: how often the operand is read): the block "
              "where the fused body folds in the kernel, else the "
              "block and the halo on both sides"),
        _spec("lowering.coded.plan_vmem_bytes", "counter", "bytes",
              "parallel/tpu.py:_count_coded_lowering",
              "VMEM the plan declares for the plain kernel's buffers"),
        _spec("lowering.coded.pfold", "counter", "1",
              "parallel/tpu.py:_count_coded_lowering",
              "of those operators, the ones whose fused CG direction "
              "fold runs inside the kernel (pfold_vmem_ok admits the "
              "plan); the others fold in XLA"),
        # -- the boundary (A_oh) block, where an operator is staged ---
        _spec("lowering.oh.nnz", "counter", "1",
              "parallel/tpu.py:_count_oh_lowering",
              "stored entries of the staged boundary blocks, whichever "
              "of the three forms they took (ghost-coupled entries, all "
              "parts)"),
        _spec("lowering.oh.slab_classes", "counter", "1",
              "parallel/tpu.py:_count_oh_lowering",
              "classes of the face-slab form: one static slice pair "
              "each in every compiled program"),
        _spec("lowering.oh.slab_entries", "counter", "1",
              "parallel/tpu.py:_count_oh_lowering",
              "dense coefficient entries of those classes, a part"),
        _spec("lowering.oh.ell_entries", "counter", "1",
              "parallel/tpu.py:_count_oh_lowering",
              "padded entries of the boundary blocks kept in the ELL "
              "form (all parts): one gathered element each"),
        _spec("lowering.oh.block_entries", "counter", "1",
              "parallel/tpu.py:_count_oh_lowering",
              "padded entries of the bs x bs blocks of the boundary "
              "blocks staged in the node-block form (all parts): one "
              "gathered ghost node a block"),
        # -- the generic exchange plan, where an operator takes it -----
        _spec("exchange.plan.rounds", "counter", "1",
              "parallel/tpu.py:_count_exchange_plan",
              "edge-coloured ppermute rounds of the generic plans"),
        _spec("exchange.plan.edges", "counter", "1",
              "parallel/tpu.py:_count_exchange_plan",
              "directed neighbour edges of those plans"),
        _spec("exchange.plan.slots", "counter", "1",
              "parallel/tpu.py:_count_exchange_plan",
              "real slots the edges send, all parts (the ghosts)"),
        _spec("exchange.plan.padded_slots", "counter", "1",
              "parallel/tpu.py:_count_exchange_plan",
              "P x R x L: slots the padded rounds gather, ship and "
              "scatter (slots over this is the fill)"),
        _spec("exchange.plan.max_edge", "counter", "1",
              "parallel/tpu.py:_count_exchange_plan",
              "slots of the longest edge, to which every round is padded"),
        _spec("exchange.plan.min_edge", "counter", "1",
              "parallel/tpu.py:_count_exchange_plan",
              "slots of the shortest edge"),
        # -- the box exchange plan, where an operator takes it ---------
        _spec("exchange.box.dirs", "counter", "1",
              "parallel/tpu.py:_count_box_plan",
              "directions of the box plans: one ppermute each"),
        _spec("exchange.box.flat_dirs", "counter", "1",
              "parallel/tpu.py:_count_box_plan",
              "forward packs (one a direction and box-shape variant) "
              "taken as a slice of the flat frame: faces normal to the "
              "slowest axis"),
        _spec("exchange.box.lane_dirs", "counter", "1",
              "parallel/tpu.py:_count_box_plan",
              "forward packs taken as a block of 128-lane rows: faces "
              "normal to an inner axis whose planes are whole lane rows"),
        _spec("exchange.box.boxview_dirs", "counter", "1",
              "parallel/tpu.py:_count_box_plan",
              "forward packs taken from the box's own view of the owned "
              "block (one view an exchange): every other sub-box"),
        # -- the V-cycle's transfers, where a hierarchy is staged -----
        _spec("gmg.transfer.levels", "counter", "1",
              "parallel/tpu_gmg.py:_count_transfer",
              "V-cycle levels whose transfer (restriction and "
              "prolongation) was staged for the device"),
        _spec("gmg.transfer.stencil", "counter", "1",
              "parallel/tpu_gmg.py:_count_transfer",
              "of those levels, the ones applying S matrix-free in one "
              "pass of 3^d shifted slices (the full shell arrives)"),
        _spec("gmg.transfer.separable", "counter", "1",
              "parallel/tpu_gmg.py:_count_transfer",
              "of those levels, the ones applying S matrix-free as one "
              "1-D pass an axis, each behind its face exchange (the "
              "halo carries faces only)"),
        _spec("gmg.transfer.operator", "counter", "1",
              "parallel/tpu_gmg.py:_count_transfer",
              "of those levels, the ones applying S as an operator "
              "through device_matrix"),
        _spec("gmg.transfer.assembled", "counter", "1",
              "parallel/tpu_gmg.py:_count_transfer",
              "of those levels, the ones applying the assembled R and P"),
        # -- service lifecycle counters -------------------------------
        _spec("service.admitted", "counter", "1",
              "service/service.py:_admit",
              "requests admitted past the bounded queue"),
        _spec("service.rejected", "counter", "1",
              "service/admission.py:AdmissionRejected",
              "typed admission backpressure, split by reason "
              "(queue_full or draining) — load shedding counts under "
              "gate.shed, never here",
              labels=("reason",)),
        _spec("service.completed", "counter", "1",
              "service/service.py:_finish",
              "requests resolved with a result"),
        _spec("service.failed", "counter", "1",
              "service/service.py:_fail",
              "requests terminated with a typed error"),
        _spec("service.ejected", "counter", "1",
              "service/service.py:_eject",
              "poisoned columns ejected from a shared slab"),
        _spec("service.retried_solo", "counter", "1",
              "service/service.py:_eject",
              "ejected requests healed by a solo retry"),
        _spec("service.deadline_expired", "counter", "1",
              "service/service.py:_expire",
              "requests failed typed at a chunk boundary past deadline"),
        _spec("service.checkpointed", "counter", "1",
              "service/service.py:_checkpoint",
              "in-flight iterates checkpointed by a non-drain shutdown"),
        _spec("service.suspended", "counter", "1",
              "service/service.py:_suspend",
              "never-started requests suspended by a non-drain shutdown"),
        _spec("service.slabs", "counter", "1",
              "service/service.py:_run_slab",
              "slabs formed (top-up re-formations extend an existing "
              "slab and are not re-counted)"),
        _spec("service.slabs_ragged", "counter", "1",
              "service/service.py:_run_slab",
              "slabs narrower than kmax (ragged leftovers)"),
        _spec("service.slab_columns", "counter", "1",
              "service/service.py:_count_columns",
              "columns of every slab run: requests that joined a slab "
              "at its formation or at a top-up (over service.slabs x "
              "kmax: how full the slabs ran)"),
        _spec("service.slab_trips", "counter", "1",
              "service/service.py:_slab_loop",
              "block iterations: the largest column's count of each "
              "block solve, summed"),
        _spec("service.queue_wait_us", "counter", "us",
              "service/service.py:_count_columns",
              "submission to slab formation (or top-up), summed over "
              "requests, in whole microseconds of the service clock"),
        # -- a request's path outside its slab (PR 36; service clock) --
        _spec("service.submit_us", "counter", "us",
              "service/service.py:submit",
              "wall time of submit calls, rejected or admitted (span "
              "pa:service:submit), in whole microseconds; the forecast "
              "is inside, the queue wait starts after"),
        _spec("service.forecast_us", "counter", "us",
              "service/service.py:_admit/_forecast_from_report",
              "inside the paspec forecast: the part of a submit under "
              "span pa:submit:forecast, and a deferred prediction on "
              "the worker's thread (span pa:forecast:deferred)"),
        _spec("service.forecasts", "counter", "1",
              "service/service.py:_forecast",
              "forecasts that took the residual norm on the host "
              "inside submit (a measured operator, a deadline under "
              "PA_SPEC_ADMIT=1, no r0_norm given)"),
        _spec("service.forecasts_deferred", "counter", "1",
              "service/service.py:_forecast_from_report",
              "requests whose prediction was made from their first "
              "column report, residuals[0] of the slab's block solve, "
              "and not from a norm taken inside submit"),
        _spec("service.idle_us", "counter", "us",
              "service/service.py:_work",
              "the worker thread with an empty queue, from finding it "
              "empty to the next slab or the stop (span "
              "pa:service:idle)"),
        _spec("service.handoff_us", "counter", "us",
              "service/request.py:wait",
              "a request's terminal stamp (finished_at) to its answer "
              "in the waiting caller's hands, summed over the waits "
              "that returned an answer"),
        _spec("service.answers", "counter", "1",
              "service/request.py:wait",
              "waits that returned an answer"),
        # -- service gauges (PA_MON-gated) ----------------------------
        _spec("service.queue_depth", "gauge", "requests",
              "service/service.py:_admit/_pop_slab",
              "queued requests right now"),
        _spec("service.inflight_slabs", "gauge", "slabs",
              "service/service.py:_run_slab",
              "slabs currently executing"),
        _spec("service.slab_utilization", "gauge", "fraction",
              "service/service.py:_run_slab",
              "K-used / kmax of the most recent slab"),
        _spec("service.ragged_fraction", "gauge", "fraction",
              "service/service.py:_run_slab",
              "cumulative slabs_ragged / slabs"),
        # -- service latency histograms (PA_MON-gated) ----------------
        _spec("service.queue_wait_s", "histogram", "s",
              "service/service.py:_run_slab",
              "submit -> slab formation wait per request"),
        _spec("service.slab_wait_s", "histogram", "s",
              "service/service.py:_run_slab",
              "slab formation -> block-solve dispatch per slab"),
        _spec("service.solve_s", "histogram", "s",
              "service/service.py:_run_slab",
              "block-solve wall per slab chunk"),
        _spec("service.total_s", "histogram", "s",
              "service/service.py:_finish/_fail",
              "submit -> terminal state per request"),
        _spec("service.deadline_slack_s", "histogram", "s",
              "service/service.py:_slo_account",
              "deadline minus elapsed at terminal state (met deadlines; "
              "clamped at 0 for missed ones)"),
        # -- SLO accounting (labeled by tolerance class) --------------
        _spec("service.slo.requests", "counter", "1",
              "service/service.py:_slo_account",
              "deadline-carrying requests reaching a terminal state",
              labels=("tol_class",)),
        _spec("service.slo.hits", "counter", "1",
              "service/service.py:_slo_account",
              "deadline-carrying requests that finished within deadline",
              labels=("tol_class",)),
        # -- the front door (pagate) ----------------------------------
        _spec("gate.shed", "counter", "1",
              "frontdoor/scheduler.py:LoadShedded",
              "requests refused by SLO-class load shedding (typed "
              "LoadShedded with Retry-After — distinct from the "
              "queue-full/draining service.rejected reasons)",
              labels=("slo_class",)),
        _spec("gate.budget_rejected", "counter", "1",
              "frontdoor/tenancy.py:TenantBudgetError",
              "operator registrations refused because the footprint "
              "exceeds PA_GATE_MEM_BUDGET outright"),
        _spec("gate.evictions", "counter", "1",
              "frontdoor/tenancy.py:evict",
              "tenants paged out (in-flight slabs drained via the "
              "checkpoint path, device buffers dropped)"),
        _spec("gate.page_ins", "counter", "1",
              "frontdoor/tenancy.py:_page_in",
              "tenants made resident (registration or re-stage after "
              "an eviction)"),
        _spec("gate.slo.requests", "counter", "1",
              "frontdoor/scheduler.py:account",
              "gate requests reaching a terminal state, per SLO class",
              labels=("slo_class",)),
        _spec("gate.slo.hits", "counter", "1",
              "frontdoor/scheduler.py:account",
              "gate requests that resolved (done — deadline misses "
              "fail typed and do not count), per SLO class",
              labels=("slo_class",)),
        _spec("gate.queue_depth", "gauge", "requests",
              "frontdoor/scheduler.py:submit/pump",
              "requests in the cross-tenant EDF queue right now"),
        _spec("gate.resident_bytes", "gauge", "bytes",
              "frontdoor/tenancy.py:_update_gauges",
              "sum of resident tenants' static footprints"),
        _spec("gate.mem_budget_bytes", "gauge", "bytes",
              "frontdoor/tenancy.py:_update_gauges",
              "the PA_GATE_MEM_BUDGET bound (0 = unbounded)"),
        _spec("gate.tenant_resident", "gauge", "1",
              "frontdoor/tenancy.py:_update_gauges",
              "1 while the tenant is resident, 0 while evicted",
              labels=("tenant",)),
        _spec("gate.tenant_footprint_bytes", "gauge", "bytes",
              "frontdoor/tenancy.py:_update_gauges",
              "the tenant's declared static footprint",
              labels=("tenant",)),
        # -- durability (padur): write-ahead journal + recovery --------
        _spec("journal.appends", "counter", "1",
              "frontdoor/journal.py:append",
              "request lifecycle records appended (fsync'd before the "
              "transition is acknowledged to the client)"),
        _spec("journal.rotations", "counter", "1",
              "frontdoor/journal.py:_rotate",
              "journal segments rotated (close + fsync + publish)"),
        _spec("journal.truncated", "counter", "1",
              "frontdoor/journal.py:_truncate_tail",
              "torn tail records truncated at replay (the expected "
              "crash artifact — mid-file corruption raises typed "
              "JournalCorruptError instead)"),
        _spec("gate.idempotent_hits", "counter", "1",
              "frontdoor/scheduler.py:submit",
              "submits answered from an existing idempotency key — "
              "the original id/result served, no second solve"),
        _spec("gate.recovered", "counter", "1",
              "frontdoor/scheduler.py:recover",
              "journaled requests replayed at recovery, by outcome "
              "(completed/failed served from the record, resumed from "
              "a checkpointed iterate, requeued from the original "
              "payload, expired typed)",
              labels=("outcome",)),
        # -- PR 14 distributed tracing (patx) -------------------------
        _spec("tx.spans", "counter", "1",
              "telemetry/tracing.py:start_span",
              "spans captured by the patx tracing plane (PA_TX=0 "
              "stops capture and this counter with it)"),
        _spec("gate.traceparent_invalid", "counter", "1",
              "frontdoor/rpc.py:do_POST",
              "malformed W3C traceparent headers on POST /v1/solve — "
              "refused at parse, a fresh trace minted instead (a "
              "hostile header can never 500 a submit)"),
        # -- PR 16 convergence observatory (paspec) -------------------
        _spec("spec.predictions", "counter", "1",
              "service/service.py:_stamp_forecast",
              "requests with an iterations-to-tolerance forecast "
              "stamped on their record, at submit or when their first "
              "column reported (the operator was spectrally measured "
              "at submit)"),
        _spec("spec.infeasible", "counter", "1",
              "telemetry/spectrum.py:check_deadline_feasible",
              "deadline-carrying requests refused typed at admission "
              "because the forecast cost exceeds the deadline "
              "(PA_SPEC_ADMIT=1; DeadlineInfeasible — distinct from "
              "deadline expiry, queue-full, and load shedding)"),
        _spec("spec.anomalies", "counter", "1",
              "telemetry/spectrum.py:observe_solve",
              "convergence anomalies detected post-solve over the "
              "residual trajectory and Ritz drift",
              labels=("kind",)),
        _spec("spec.iters_rel_error", "histogram", "fraction",
              "service/service.py:_slo_account",
              "per-request |predicted - actual| / actual iteration "
              "forecast error, labeled by tenant (operator fingerprint "
              "for unnamed services) — the pamon --conv feed",
              labels=("tenant",)),
        # -- PR 18 gate fleet (pafleet) -------------------------------
        _spec("fleet.forwarded", "counter", "1",
              "frontdoor/rpc.py:do_POST",
              "shed submits 307-redirected to a peer replica with "
              "headroom instead of 429 backoff (the peer admits the "
              "identical body: same idempotency key, same trace)"),
        _spec("fleet.adopted", "counter", "1",
              "frontdoor/scheduler.py:adopt",
              "a dead peer's journaled requests adopted by this "
              "survivor, by outcome (same keys as gate.recovered, "
              "plus skipped for already-adopted/unservable rids)",
              labels=("outcome",)),
        _spec("fleet.lease_missed", "counter", "1",
              "frontdoor/fleet.py:check_peers",
              "peer replicas declared dead after a stale lease "
              "(> 3x PA_FLEET_LEASE_S) — each increments once and "
              "triggers journal adoption by the ranked survivor"),
        _spec("journal.pruned", "counter", "1",
              "frontdoor/journal.py:prune",
              "journal segment files unlinked by retention "
              "(PA_GATE_JOURNAL_KEEP) — only epochs at or behind the "
              "recovered frontier; otherwise typed "
              "JournalRetentionError and nothing is dropped"),
        _spec("elastic.shrink", "counter", "1",
              "parallel/elastic.py:shrink_system",
              "elastic degraded-mode shrinks: the system was migrated "
              "onto a smaller survivor part grid (PA_ELASTIC=1) — one "
              "increment per shrink, labelled by what forced it",
              labels=("reason",)),
        _spec("elastic.crosspart_restores", "counter", "1",
              "parallel/checkpoint.py:load_solver_state",
              "solver-state checkpoints restored onto a DIFFERENT part "
              "count than they were written at (allowed only under "
              "PA_ELASTIC=1; otherwise typed CheckpointShapeError)"),
    ]
}


def _labels_key(labels: Optional[dict]) -> Tuple[Tuple[str, str], ...]:
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class Counter:
    """Monotonic named counter (one label set)."""

    __slots__ = ("_lock", "value")

    def __init__(self, lock):
        self._lock = lock
        self.value = 0

    def inc(self, n: int = 1) -> int:
        with self._lock:
            self.value += int(n)
            return self.value


class Gauge:
    """Last-value gauge with inc/dec."""

    __slots__ = ("_lock", "value")

    def __init__(self, lock):
        self._lock = lock
        self.value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self.value = float(v)

    def inc(self, n: float = 1.0) -> float:
        with self._lock:
            self.value += float(n)
            return self.value

    def dec(self, n: float = 1.0) -> float:
        return self.inc(-n)


class Histogram:
    """A registry-held `LatencyHistogram` (shared lock)."""

    __slots__ = ("_lock", "hist")

    def __init__(self, lock):
        self._lock = lock
        self.hist = LatencyHistogram()

    def observe(self, v: float) -> None:
        with self._lock:
            self.hist.observe(v)

    @property
    def count(self) -> int:
        return self.hist.total

    def quantile(self, q: float):
        with self._lock:
            return self.hist.quantile(q)

    def snapshot(self) -> dict:
        with self._lock:
            return self.hist.snapshot()


class Registry:
    """The typed metric registry (see module docstring). Metrics are
    created on first touch; a declared name must be touched with its
    declared kind (a `lowering_cache.hit` gauge is a bug, not a new
    metric)."""

    def __init__(self):
        #: THE lock: every registry mutation AND the telemetry history
        #: ring (record.py) serialize on it.
        self.lock = sanitized(threading.RLock(), "Registry.lock")
        self._metrics: Dict[Tuple[str, tuple], object] = {}

    # -- creation / access ----------------------------------------------
    def _get(self, name: str, labels: Optional[dict], cls):
        kind = {Counter: "counter", Gauge: "gauge",
                Histogram: "histogram"}[cls]
        spec = CATALOG.get(name) or (
            CATALOG.get("events.*") if name.startswith("events.") else None
        )
        if spec is not None and spec.kind != kind:
            raise TypeError(
                f"metric {name!r} is declared a {spec.kind}, not a {kind}"
            )
        key = (name, _labels_key(labels))
        with self.lock:
            m = self._metrics.get(key)
            if m is None:
                m = self._metrics[key] = cls(self.lock)
            return m

    def counter(self, name: str, labels: Optional[dict] = None) -> Counter:
        return self._get(name, labels, Counter)

    def gauge(self, name: str, labels: Optional[dict] = None) -> Gauge:
        return self._get(name, labels, Gauge)

    def histogram(self, name: str,
                  labels: Optional[dict] = None) -> Histogram:
        return self._get(name, labels, Histogram)

    # -- reading ---------------------------------------------------------
    def counter_value(self, name: str,
                      labels: Optional[dict] = None) -> int:
        with self.lock:
            m = self._metrics.get((name, _labels_key(labels)))
        return m.value if isinstance(m, Counter) else 0

    def snapshot(self, prefix: Optional[str] = None) -> dict:
        """One JSON-safe dict of everything (optionally name-filtered):
        the exchange format `tools/pamon.py` renders and `--watch`
        diffs. Deterministic ordering, no wall-clock fields."""
        with self.lock:
            items = sorted(
                (k, m) for k, m in self._metrics.items()
                if prefix is None or k[0].startswith(prefix)
            )
            out: dict = {
                "registry_schema_version": REGISTRY_SCHEMA_VERSION,
                "counters": {},
                "gauges": {},
                "histograms": {},
            }
            for (name, lk), m in items:
                full = name if not lk else (
                    name + "{" + ",".join(f"{k}={v}" for k, v in lk) + "}"
                )
                if isinstance(m, Counter):
                    out["counters"][full] = m.value
                elif isinstance(m, Gauge):
                    out["gauges"][full] = m.value
                else:
                    out["histograms"][full] = m.hist.snapshot()
            return out

    def to_json(self, prefix: Optional[str] = None) -> str:
        return json.dumps(self.snapshot(prefix), sort_keys=True, indent=1)

    def to_prometheus(self) -> str:
        """Prometheus text exposition: dotted names become
        ``pa_``-prefixed underscore names; histograms render cumulative
        ``le`` buckets + ``_sum``/``_count`` per convention (every
        series of one labeled histogram carries the IDENTICAL escaped
        label set). Label values are escaped per the exposition format
        (backslash, double quote, newline) — a hostile tol-class or
        request tag can no longer corrupt the scrape."""
        from .histogram import BUCKET_BOUNDS

        lines = []
        typed = set()

        def pname(name):
            return "pa_" + name.replace(".", "_").replace("*", "all")

        def esc(v):
            return (
                str(v)
                .replace("\\", "\\\\")
                .replace('"', '\\"')
                .replace("\n", "\\n")
            )

        def plabels(lk, extra=None):
            parts = [f'{k}="{esc(v)}"' for k, v in lk]
            if extra:
                parts.append(extra)
            return "{" + ",".join(parts) + "}" if parts else ""

        # render UNDER the lock: a histogram observed mid-scrape must
        # not emit le-buckets disagreeing with its _count/_sum (the
        # torn-read class the one-lock contract exists to close)
        with self.lock:
            for (name, lk), m in sorted(self._metrics.items()):
                pn = pname(name)
                kind = (
                    "counter" if isinstance(m, Counter)
                    else "gauge" if isinstance(m, Gauge)
                    else "histogram"
                )
                if pn not in typed:
                    spec = CATALOG.get(name)
                    if spec is not None:
                        desc = spec.desc.replace("\\", "\\\\").replace(
                            "\n", "\\n"
                        )
                        lines.append(f"# HELP {pn} {desc}")
                    lines.append(f"# TYPE {pn} {kind}")
                    typed.add(pn)
                if isinstance(m, Counter):
                    lines.append(f"{pn}{plabels(lk)} {m.value}")
                elif isinstance(m, Gauge):
                    lines.append(f"{pn}{plabels(lk)} {m.value:g}")
                else:
                    cum = 0
                    for i, edge in enumerate(BUCKET_BOUNDS):
                        cum += m.hist.counts[i]
                        le = 'le="%g"' % edge
                        lines.append(
                            f"{pn}_bucket{plabels(lk, le)} {cum}"
                        )
                    cum += m.hist.counts[len(BUCKET_BOUNDS)]
                    inf = 'le="+Inf"'
                    lines.append(f"{pn}_bucket{plabels(lk, inf)} {cum}")
                    lines.append(f"{pn}_sum{plabels(lk)} {m.hist.sum:g}")
                    lines.append(
                        f"{pn}_count{plabels(lk)} {m.hist.total}"
                    )
        return "\n".join(lines) + ("\n" if lines else "")

    # -- maintenance -----------------------------------------------------
    def reset(self, prefix: Optional[str] = None) -> None:
        with self.lock:
            if prefix is None:
                self._metrics.clear()
            else:
                for k in [k for k in self._metrics
                          if k[0].startswith(prefix)]:
                    del self._metrics[k]

    def names(self) -> Iterable[str]:
        with self.lock:
            return sorted({k[0] for k in self._metrics})


#: THE process-wide registry instance.
_REGISTRY = Registry()


def registry() -> Registry:
    return _REGISTRY
