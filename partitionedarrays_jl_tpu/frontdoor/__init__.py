"""pagate — the out-of-process multi-tenant front door (ROADMAP item 1).

The layer that makes one process look like a SERVICE: many operators,
many clients, deadlines, and graceful behavior under overload. It
composes OVER — never reaches into — the in-process service stack
(PR 7 `service.SolveService`, PR 8 `MEMORY_FOOTPRINT.json` admission
budgets, PR 9 pamon metrics/SLO accounting, PR 10 adaptive K):

* `frontdoor.tenancy`  — `OperatorRegistry`: N named operators admitted
  against ``PA_GATE_MEM_BUDGET`` (sum of resident static footprints —
  the committed MEMORY_FOOTPRINT.json shape-sum convention), routed to
  per-tenant `SolveService`s, with LRU operator paging/eviction
  (in-flight slabs drain through the PR 7 checkpoint path, device
  buffers drop, the next request re-stages — counted and evented).
* `frontdoor.scheduler` — `Gate`: the EDF cross-tenant queue (earliest
  absolute deadline dispatches first; the PR 9 deadline-slack/SLO
  metrics are the asserted measured feed) and SLO-class load shedding
  (``PA_GATE_CLASSES``/``PA_GATE_SHED_DEPTH``): past the watermark the
  lowest class is refused with the typed, ``Retry-After``-carrying
  `LoadShedded` — distinct from queue-full `AdmissionRejected` — while
  higher classes keep their SLO.
* `frontdoor.rpc`      — the stdlib HTTP/JSON surface (``/v1/solve``
  submit-poll-fetch, ``/v1/tenants``, ``/healthz``, ``/metrics``) with
  exact-float serialization: an HTTP solve returns bitwise the same
  iterate as the same request in-process, and the tenants' compiled
  block programs stay byte-identical StableHLO (tests/test_pagate.py).
* `frontdoor.journal`  — the round-15 (padur) durability layer: the
  write-ahead request journal (CRC'd fsync'd JSONL, PR 4 checkpoint
  conventions) every lifecycle transition lands in BEFORE the client
  ack, idempotency keys on submit (a retried request returns the
  original id and bitwise result — never a second solve), and
  ``Gate.recover()``: after a kill -9, completed requests serve their
  recorded results, in-flight requests resume from chunk-checkpointed
  iterates (deadline clock resumed), queued requests re-enter EDF —
  zero lost, zero duplicated (tools/padur.py --drill is the proof).
* `frontdoor.fleet`    — the round-16 (pafleet) replication layer: N
  gate replicas behind rendezvous tenant routing, CRC'd lease-file
  heartbeats, journal-backed peer failover (``Gate.adopt`` replays a
  dead peer's journal into a survivor — zero lost, zero duplicated,
  one stitched trace across the hop), and shed-forwarding (HTTP 307
  to a peer with headroom before 429 backoff; `http_solve` follows).
  Journal retention (``PA_GATE_JOURNAL_KEEP``) prunes fully-recovered
  epochs with a typed refusal otherwise.

CLI: ``tools/pagate.py serve|submit|loadgen`` (``--check`` is the
tier-1 smoke); durability drills: ``tools/padur.py`` (``--check``
tier-1, ``--drill`` the SIGKILL harness under ``-m slow``); fleet:
``tools/pafleet.py serve|kill|--check|--drill``.
Protocol docs: docs/service.md (Front door, Gate fleet),
docs/resilience.md (Durability).
"""
from .fleet import (  # noqa: F401
    FleetMap,
    FleetMember,
    LeaseCorruptError,
    fleet_lease_s,
    fleet_replicas,
    read_lease,
    rendezvous_rank,
    route,
    write_lease,
)
from .journal import (  # noqa: F401
    JournalCorruptError,
    JournalRetentionError,
    RecoveredError,
    RequestJournal,
    journal_enabled,
    journal_env_dir,
    journal_fsync,
    journal_keep,
    read_journal,
)
from .rpc import (  # noqa: F401
    GateServer,
    gate_port,
    http_solve,
    serve_gate,
    serve_until_signalled,
)
from .scheduler import (  # noqa: F401
    Gate,
    GateHandle,
    LoadShedded,
    gate_classes,
    shed_classes,
    shed_depth,
)
from .tenancy import (  # noqa: F401
    OperatorRegistry,
    Tenant,
    TenantBudgetError,
    UnknownTenantError,
    mem_budget,
    operator_footprint_bytes,
)

__all__ = [
    "FleetMap",
    "FleetMember",
    "Gate",
    "GateHandle",
    "GateServer",
    "JournalCorruptError",
    "JournalRetentionError",
    "LeaseCorruptError",
    "LoadShedded",
    "OperatorRegistry",
    "RecoveredError",
    "RequestJournal",
    "Tenant",
    "TenantBudgetError",
    "UnknownTenantError",
    "fleet_lease_s",
    "fleet_replicas",
    "gate_classes",
    "gate_port",
    "http_solve",
    "journal_enabled",
    "journal_env_dir",
    "journal_fsync",
    "journal_keep",
    "mem_budget",
    "operator_footprint_bytes",
    "read_journal",
    "read_lease",
    "rendezvous_rank",
    "route",
    "serve_gate",
    "serve_until_signalled",
    "shed_classes",
    "shed_depth",
    "write_lease",
]
