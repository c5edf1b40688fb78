"""Source lint: every ``PA_*`` environment flag, inventoried and proven
cache-safe.

The bug class this closes has shipped three times: a ``PA_*`` flag that
changes what gets TRACED or STAGED (a lowering mode, a baked-in
tolerance, an audit cadence) is added without folding it into
`_lowering_env_key()` (parallel/tpu.py) or one of the other registered
cache-key sites — so flipping the flag silently serves a stale compiled
program from a cache keyed before the flip. PRs 2–4 each patched one
instance by hand (`PA_TPU_FUSED_CG`, `PA_TPU_OH_BUCKETS`,
`PA_TPU_ABFT`); this pass makes the next instance a test failure
instead of a debugging session.

Three static computations over the package AST:

1. **Inventory** (`env_read_inventory`): every literal-name read of a
   ``PA_*`` env var — ``os.environ.get/[]``, ``os.getenv``,
   ``environ.get`` — with file, line, and enclosing function.
2. **Reachability** (`lowering_reads`): a name-resolution-by-identifier
   call graph from the staging/tracing entrypoints (`make_cg_fn`,
   `device_matrix` / `DeviceMatrix`, `_spmv_body`, the GMG/LOBPCG
   stagers, ...). An env read inside a reachable function *candidates*
   as lowering-affecting; `NON_LOWERING` downgrades reads that are
   reachable but provably cannot change a staged program (each entry
   carries its reason — the table is itself a pinned fixture, so an
   unclassified new flag FAILS the lint until a human classifies it).
3. **Key coverage** (`key_coverage`): the transitive, MODULE-QUALIFIED
   closure of ``PA_*`` literals read by the registered cache-key sites
   (`_lowering_env_key`, `_gmg_env_key`, `_sdc_config`) — i.e. the set
   of flags whose flip provably re-keys every derived cache. (Qualified
   so a same-named helper in an unrelated module cannot donate its
   literals and fake coverage.)

`lint_env_keys` ties them together: every lowering-affecting flag must
be key-covered AND documented in the docs/api.md environment table
(both directions — the table may not name flags the source no longer
reads).
"""
from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))
#: The package root this lint walks (…/partitionedarrays_jl_tpu).
PACKAGE_ROOT = os.path.dirname(_HERE)
#: The repo root (for docs/api.md).
REPO_ROOT = os.path.dirname(PACKAGE_ROOT)

ENV_PREFIX = "PA_"

#: Cache-key sites: flags transitively read by any of these functions
#: are considered key-covered. `_lowering_env_key` is the canonical one
#: (every DeviceMatrix-derived cache includes it); `_gmg_env_key` wraps
#: it for the GMG/LOBPCG staging caches; `_sdc_config` builds the
#: compiled-program cache-key fragment for the SDC defense
#: (`_krylov_fn_for` keys on ``sdccfg["key"]``); `_trace_config`
#: resolves the telemetry α/β trace-ring depth (`_krylov_fn_for` folds
#: its value into the program key — a flipped PA_TRACE_ITERS rebuilds
#: the program instead of serving one with the wrong carry).
KEY_SITES = (
    "_lowering_env_key", "_gmg_env_key", "_sdc_config", "_trace_config",
)

#: Staging/tracing entrypoints: the roots of the reachability pass.
#: Anything these (transitively, by identifier) call runs at trace or
#: stage time, so an env read there is a lowering-affecting candidate.
LOWERING_ROOTS = (
    "make_cg_fn",
    "make_block_cg_fn",
    "make_spmv_fn",
    "make_exchange_fn",
    "make_bicgstab_fn",
    "make_gmres_fn",
    "make_fgmres_gmg_fn",
    "make_minres_fn",
    "make_lobpcg_fn",
    "make_diff_solve_fn",
    "device_matrix",
    "device_layout",
    "DeviceMatrix",
    "DeviceExchangePlan",
    "_spmv_body",
    "_sdc_config",
    "_device_hierarchy",
    "_krylov_fn_for",
)

#: Reads that reachability flags but that provably cannot change a
#: staged program — each with the reason a human signed off on. A flag
#: that is reachable and NOT here (and not key-covered) fails the lint:
#: this table is the pinned clean-state fixture the first lint run left
#: behind (ISSUE 5 satellite), and the reason column is the review
#: record for the next flag someone adds.
NON_LOWERING: Dict[str, str] = {
    "PA_TPU_CHECKS": (
        "validation toggle — check() raises or passes; a stripped check "
        "never changes the program that stages for valid inputs"
    ),
    "PA_TPU_NATIVE": (
        "host planning accelerator with a bit-identical Python fallback "
        "(tests/test_native.py pins parity) — changes who computes the "
        "plan, never the plan"
    ),
    "PA_TPU_PLAN_PROCS": (
        "multiprocess planning fan-out — checksum-pinned to the "
        "in-process path (tools/plan_multiproc.py)"
    ),
    "PA_TPU_STENCIL_FAST": (
        "host assembly fast path (COO-free stencil emission) — emits the "
        "identical operator, pinned by the models tests; runs before any "
        "device staging"
    ),
    "PA_TPU_GMG_CLASSED": (
        "host Galerkin assembly collapse — bit-identical coarse operators "
        "by the row-class proof (models/gmg.py); the hierarchy is built "
        "before staging and holds the resulting values either way"
    ),
    "PA_HEALTH_CHECKS": (
        "host-loop scalar guard toggle — runs outside compiled programs"
    ),
    "PA_HEALTH_EXCHANGE": (
        "host wire post-exchange finiteness guard — validates received "
        "buffers on the host path, never traced"
    ),
    "PA_HEALTH_STAGNATION": (
        "host-loop stagnation detector — outside compiled programs"
    ),
    "PA_HEALTH_STAGNATION_WINDOW": (
        "host-loop stagnation detector parameter — outside compiled "
        "programs"
    ),
    "PA_HEALTH_STAGNATION_FACTOR": (
        "host-loop stagnation detector parameter — outside compiled "
        "programs"
    ),
    "PA_RETRY_ATTEMPTS": (
        "host I/O / init retry policy — never part of a staged program"
    ),
    "PA_RETRY_BACKOFF": (
        "host I/O / init retry policy — never part of a staged program"
    ),
    "PA_RETRY_JITTER": (
        "host retry-delay jitter seed (decorrelated backoff) — shapes "
        "WHEN a retry happens, never what a program stages"
    ),
    "PA_SERVE_QUEUE_DEPTH": (
        "solve-service admission bound — host-side queueing policy; "
        "compiled programs are keyed by (tol, maxiter, K) regardless"
    ),
    "PA_SERVE_KMAX": (
        "solve-service slab-width bound — selects WHICH cached block "
        "program (rhs_batch=K) runs, each keyed by its own K through "
        "_krylov_fn_for; never alters a staged program"
    ),
    "PA_SERVE_CHUNK": (
        "solve-service chunk length for deadline enforcement — the "
        "chunk is passed as the block solve's maxiter argument (an "
        "explicit program parameter, keyed), not a hidden staging input"
    ),
    "PA_SERVE_RETRIES": (
        "solve-service solo-retry budget for ejected columns — "
        "host-side recovery policy, outside compiled programs"
    ),
    "PA_PLAN_VERIFY": (
        "construction-time plan-soundness gate (analysis.plan_verifier "
        "at the three plan build sites) — the verifier raises the typed "
        "PlanSoundnessError or passes; it never changes which plan is "
        "built or what a program stages from it"
    ),
    "PA_FAULT_SPEC": (
        "host wire chaos injection — corrupts exchange payloads at run "
        "time on the host path (parallel/faults.py); the compiled-loop "
        "seam is PA_FAULT_DEVICE, which IS keyed (_sdc_config)"
    ),
    "PA_FAULT_SEED": (
        "host wire chaos injection seed — same path as PA_FAULT_SPEC"
    ),
    "PA_METRICS": (
        "telemetry kill switch — gates host-side SolveRecord/event "
        "bookkeeping only; compiled programs are built identically "
        "either way (the device-visible knob is PA_TRACE_ITERS, which "
        "IS keyed via _trace_config)"
    ),
    "PA_MON": (
        "metric-registry instrumentation switch — gates host-side "
        "histogram/gauge recording and throughput-model updates in the "
        "solve service; never part of a staged program (the service "
        "slab stays a program-cache hit against the bare block body "
        "either way — tests/test_pamon.py)"
    ),
    "PA_MON_EWMA": (
        "EWMA smoothing factor of the host-side online throughput "
        "model (telemetry/throughput.py) — shapes a measured-cost "
        "table, never a staged program"
    ),
    "PA_SERVE_ADAPTIVE_K": (
        "adaptive slab-width policy switch — selects WHICH cached "
        "block program (rhs_batch=K) a slab runs from the measured "
        "per-RHS curve (telemetry.throughput.suggest_k); like "
        "PA_SERVE_KMAX, each candidate program is keyed by its own K "
        "through _krylov_fn_for, so the policy never alters a staged "
        "program"
    ),
    "PA_PROF": (
        "phase-profiling master switch (telemetry/profile.py) — "
        "capture builds STANDALONE chain programs; the solver path "
        "never reads it (StableHLO-identity pinned in "
        "tests/test_paprof.py)"
    ),
    "PA_PROF_REPS": (
        "phase-profiling timing repetitions — host-side measurement "
        "parameter of the standalone profiling chains"
    ),
    "PA_GATE_MEM_BUDGET": (
        "front-door tenancy budget (frontdoor/tenancy.py) — bounds how "
        "many operators stay RESIDENT (LRU paging of whole tenants); "
        "which cached programs exist per tenant is unchanged, and a "
        "re-staged tenant rebuilds plan_fingerprint-identical plans "
        "(tests/test_pagate.py)"
    ),
    "PA_GATE_CLASSES": (
        "front-door SLO class vocabulary (frontdoor/scheduler.py) — "
        "pure admission policy: which requests are refused under "
        "overload, never what any program stages"
    ),
    "PA_GATE_SHED_DEPTH": (
        "front-door shed watermark (frontdoor/scheduler.py) — queue-"
        "depth threshold for SLO-class load shedding; host-side "
        "admission policy only"
    ),
    "PA_GATE_PORT": (
        "front-door HTTP listen port (frontdoor/rpc.py) — transport "
        "configuration; the RPC surface adds zero in-graph work "
        "(byte-identical StableHLO pinned in tests/test_pagate.py)"
    ),
    "PA_GATE_JOURNAL": (
        "front-door write-ahead journal master switch "
        "(frontdoor/journal.py) — host-side durability bookkeeping "
        "only; the journal-off program path is byte-identical "
        "StableHLO (tests/test_padur.py)"
    ),
    "PA_GATE_JOURNAL_DIR": (
        "default journal directory for Gate(journal_dir=None) "
        "(frontdoor/journal.py) — where host-side JSONL segments "
        "land, never part of a staged program"
    ),
    "PA_GATE_JOURNAL_FSYNC": (
        "journal append fsync policy (frontdoor/journal.py) — trades "
        "the power-loss guarantee for append speed on the host path; "
        "no staged program reads it"
    ),
    "PA_METRICS_DIR": (
        "telemetry record persistence directory — where finished "
        "SolveRecord JSONs land on the host, never part of a staged "
        "program"
    ),
    "PA_METRICS_HISTORY": (
        "depth of the host-side in-memory ring of finished "
        "SolveRecords — pure host bookkeeping"
    ),
    "PA_TX": (
        "distributed-tracing span capture switch (telemetry/"
        "tracing.py) — spans are host-side objects opened by the "
        "gate/service request path; no solver staging or tracing code "
        "reads it, and the block program is byte-identical StableHLO "
        "on/off (tests/test_patx.py)"
    ),
    "PA_TX_DIR": (
        "span persistence directory (telemetry/tracing.py) — where "
        "the per-process span JSONL lands for tools/patx.py; pure "
        "host I/O policy, never part of a staged program"
    ),
    "PA_SPEC": (
        "convergence-observatory master switch (telemetry/spectrum.py)"
        " — gates HOST-side post-solve spectral estimation, store "
        "feeding, and anomaly detection on already-downloaded "
        "rings/histories; the solver path never reads it and the block "
        "program is byte-identical StableHLO on/off "
        "(tests/test_paspec.py)"
    ),
    "PA_FLEET_REPLICAS": (
        "gate-fleet replica count (frontdoor/fleet.py) — how many "
        "gate PROCESSES tools/pafleet.py launches; pure host-side "
        "process topology, no staged program ever reads it"
    ),
    "PA_FLEET_LEASE_S": (
        "fleet lease heartbeat period (frontdoor/fleet.py) — failure-"
        "detection cadence for the per-replica lease files; host-side "
        "liveness bookkeeping only"
    ),
    "PA_GATE_JOURNAL_KEEP": (
        "journal retention depth (frontdoor/journal.py) — how many "
        "fully-recovered epochs of host-side JSONL segments survive "
        "pruning; disk-hygiene policy, never part of a staged program"
    ),
    "PA_SPEC_ADMIT": (
        "deadline-feasibility admission switch (telemetry/spectrum.py)"
        " — pure admission policy: refuses a request typed "
        "DeadlineInfeasible BEFORE dispatch when the forecast cost "
        "exceeds the deadline; never touches what any program stages "
        "(byte-identity pinned in tests/test_paspec.py)"
    ),
    "PA_ELASTIC": (
        "elastic degraded-mode switch (parallel/elastic.py) — host-side "
        "recovery POLICY: whether a PartLossError shrinks the partition "
        "and resumes or escalates typed; every program on the shrunken "
        "partition is built through the ordinary staging path with its "
        "own keys, nothing staged reads the flag"
    ),
    "PA_ELASTIC_MIN_PARTS": (
        "elastic shrink floor (parallel/elastic.py) — host-side policy "
        "bound on how small the survivor grid may get before the loss "
        "escalates instead; same staging story as PA_ELASTIC"
    ),
    "PA_LOCK_CHECK": (
        "runtime lock-order sanitizer switch (utils/locksan.py, the "
        "palock dynamic half) — read ONCE at lock construction to "
        "decide whether `sanitized` wraps a serving-stack lock in the "
        "order-recording shim; acquisition paths and the solver path "
        "never read it, and the block program is byte-identical "
        "StableHLO on/off (tests/test_palock.py)"
    ),
}


@dataclass
class EnvRead:
    """One literal-name env read site."""

    name: str
    path: str  # repo-relative
    line: int
    func: Optional[str]  # outermost enclosing scope, None = module level
    #: EVERY enclosing scope name (outermost..innermost) — reachability
    #: matches any of them, so a read inside a method is found both via
    #: its class name and via the method name an attr-call resolves to.
    owners: Tuple[str, ...] = ()

    def __repr__(self):
        where = self.func or "<module>"
        return f"{self.name} @ {self.path}:{self.line} in {where}"


@dataclass
class _FuncInfo:
    qualname: str
    module: str
    env_literals: Set[str] = field(default_factory=set)
    calls: Set[str] = field(default_factory=set)


def _package_files(root: Optional[str] = None) -> List[str]:
    root = root or PACKAGE_ROOT
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                out.append(os.path.join(dirpath, fn))
    return out


def _env_name_from_call(node: ast.AST) -> Optional[str]:
    """The literal env-var name if ``node`` is an env read, else None.

    Recognized shapes: ``os.environ.get(NAME[, d])``, ``os.getenv(NAME
    [, d])``, ``environ.get(NAME)``, ``os.environ[NAME]``,
    ``environ[NAME]``.
    """
    def _lit(args):
        if args and isinstance(args[0], ast.Constant) and isinstance(
            args[0].value, str
        ):
            return args[0].value
        return None

    if isinstance(node, ast.Call):
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr == "get":
                v = f.value
                if (
                    isinstance(v, ast.Attribute) and v.attr == "environ"
                ) or (isinstance(v, ast.Name) and v.id == "environ"):
                    return _lit(node.args)
            if f.attr == "getenv":
                return _lit(node.args)
        elif isinstance(f, ast.Name) and f.id == "getenv":
            return _lit(node.args)
    if isinstance(node, ast.Subscript):
        v = node.value
        if (isinstance(v, ast.Attribute) and v.attr == "environ") or (
            isinstance(v, ast.Name) and v.id == "environ"
        ):
            sl = node.slice
            if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
                return sl.value
    return None


class _Scanner(ast.NodeVisitor):
    """One pass per module: env reads + per-scope call/literal sets.

    Every enclosing scope — the outermost def, any nested defs, AND
    class bodies — gets its own `_FuncInfo`, and a read or call inside
    a scope is attributed to EVERY scope on the stack. That closes the
    two blind spots a name-only attribution has: a method's reads are
    reachable both through its class name (a `DeviceMatrix` root) and
    through the bare method name an attribute call resolves to
    (`planner.pick_mode()` → edge to ``pick_mode``), and a closure
    traced inside `make_cg_fn` is found through `make_cg_fn` itself.
    """

    def __init__(self, module: str, reads: List[EnvRead],
                 funcs: Dict[str, List[_FuncInfo]]):
        self.module = module
        self.reads = reads
        self.funcs = funcs
        self._stack: List[_FuncInfo] = []

    def visit_FunctionDef(self, node):
        self._enter_scope(node)

    def visit_AsyncFunctionDef(self, node):
        self._enter_scope(node)

    def visit_ClassDef(self, node):
        # the class name stands for "anything that runs when this class
        # is instantiated or used" — its methods' reads/calls are
        # attributed to the class entry too (stack attribution below)
        self._enter_scope(node)

    def _enter_scope(self, node):
        info = _FuncInfo(qualname=node.name, module=self.module)
        self.funcs.setdefault(node.name, []).append(info)
        # the enclosing scopes can invoke this one
        for outer in self._stack:
            outer.calls.add(node.name)
        self._stack.append(info)
        self.generic_visit(node)
        self._stack.pop()

    def visit_Call(self, node):
        name = _env_name_from_call(node)
        if name and name.startswith(ENV_PREFIX):
            self._add_read(name, node.lineno)
        if self._stack:
            f = node.func
            target = None
            if isinstance(f, ast.Name):
                target = f.id
            elif isinstance(f, ast.Attribute):
                target = f.attr
            if target:
                for info in self._stack:
                    info.calls.add(target)
        self.generic_visit(node)

    def visit_Subscript(self, node):
        name = _env_name_from_call(node)
        if name and name.startswith(ENV_PREFIX):
            self._add_read(name, node.lineno)
        self.generic_visit(node)

    def _add_read(self, name: str, lineno: int):
        owners = tuple(info.qualname for info in self._stack)
        self.reads.append(
            EnvRead(
                name=name, path=self.module, line=lineno,
                func=owners[0] if owners else None, owners=owners,
            )
        )
        for info in self._stack:
            info.env_literals.add(name)


#: Scan memo: one AST walk per distinct package STATE — the signature
#: is stat-only (path + mtime_ns + size), so the gate's several entry
#: points (lint, classification pin, both doc-consistency tests) read
#: and parse the ~40 modules once; a rewritten file (the
#: synthetic-package negative tests) still invalidates.
_SCAN_CACHE: Dict[tuple, tuple] = {}


def _scan_package(root: Optional[str] = None):
    base = root or PACKAGE_ROOT
    files = _package_files(base)
    sig = tuple(
        (path, st.st_mtime_ns, st.st_size)
        for path, st in ((p, os.stat(p)) for p in files)
    )
    hit = _SCAN_CACHE.get(base)
    if hit is not None and hit[0] == sig:
        return hit[1]
    reads: List[EnvRead] = []
    funcs: Dict[str, List[_FuncInfo]] = {}
    for path in files:
        rel = os.path.relpath(path, os.path.dirname(base))
        with open(path, encoding="utf-8") as f:
            src = f.read()
        tree = ast.parse(src, filename=rel)
        _Scanner(rel, reads, funcs).visit(tree)
    _SCAN_CACHE[base] = (sig, (reads, funcs))  # one state per root
    return reads, funcs


def env_read_inventory(root: Optional[str] = None) -> List[EnvRead]:
    """Every literal ``PA_*`` env read in the package, sorted."""
    reads, _ = _scan_package(root)
    return sorted(reads, key=lambda r: (r.name, r.path, r.line))


def _closure(funcs: Dict[str, List[_FuncInfo]], roots) -> Set[str]:
    """Name-only call closure — every definition of a called name, in
    ANY module, joins. Over-approximate, which is the SAFE direction for
    the reachability pass (more reachable → more lowering candidates →
    a stricter lint); `key_coverage` must not use it (see
    `_module_closure`)."""
    seen: Set[str] = set()
    todo = list(roots)
    while todo:
        name = todo.pop()
        if name in seen or name not in funcs:
            continue
        seen.add(name)
        for info in funcs[name]:
            todo.extend(info.calls - seen)
    return seen


def _module_closure(
    funcs: Dict[str, List[_FuncInfo]], roots
) -> Set[Tuple[str, str]]:
    """Module-QUALIFIED call closure: nodes are ``(module, name)``.

    A call target defined in the calling module resolves there ONLY (a
    local definition shadows any import); otherwise it resolves to
    every package definition of the name (the import case). This is the
    closure `key_coverage` walks: a name-only union would let an
    unrelated module's same-named helper donate its env literals to a
    key site and falsely mark a flag key-covered — a green lint on
    exactly the stale-cache bug class the lint exists to catch. The
    residual over-approximation (a non-local name defined in several
    OTHER modules still unions) only survives where the AST alone
    cannot rank the candidates, and erring wide there keeps coverage —
    not the lint — optimistic for names a key site genuinely imports.
    """
    seen: Set[Tuple[str, str]] = set()
    todo: List[Tuple[str, str]] = [
        (info.module, root)
        for root in roots
        for info in funcs.get(root, [])
    ]
    while todo:
        node = todo.pop()
        if node in seen:
            continue
        seen.add(node)
        mod, name = node
        for info in funcs.get(name, []):
            if info.module != mod:
                continue
            for callee in info.calls:
                defs = funcs.get(callee)
                if not defs:
                    continue
                mods = {d.module for d in defs}
                if mod in mods:
                    todo.append((mod, callee))
                else:
                    todo.extend((m, callee) for m in mods)
    return seen


def key_coverage(root: Optional[str] = None) -> Dict[str, str]:
    """``PA_*`` name -> key site whose transitive literal set covers it.

    Walks the module-qualified call closure of each registered key site
    and collects every env literal read inside it — the set of flags
    whose flip provably re-keys the caches that include that site's
    tuple. Module-qualified because coverage errs in the DANGEROUS
    direction: an over-wide closure hides unkeyed flags.
    """
    _, funcs = _scan_package(root)
    covered: Dict[str, str] = {}
    for site in KEY_SITES:
        for mod, fname in _module_closure(funcs, [site]):
            for info in funcs.get(fname, []):
                if info.module != mod:
                    continue
                for lit in info.env_literals:
                    covered.setdefault(lit, site)
    return covered


def _is_candidate(read: EnvRead, reachable: Set[str]) -> bool:
    """Lowering-affecting candidate: read inside any scope reachable
    from a staging root, OR read at module level — an import-time read
    is frozen before any cache key can see a flip, which is the exact
    staleness hazard, so it must be exempted explicitly or keyed."""
    if not read.owners:
        return True
    return any(o in reachable for o in read.owners)


def lowering_reads(root: Optional[str] = None) -> List[EnvRead]:
    """Env reads reachable (by the identifier call graph) from the
    staging/tracing entrypoints, plus module-level (import-time) reads
    — the lowering-affecting CANDIDATES, before `NON_LOWERING`
    downgrades."""
    reads, funcs = _scan_package(root)
    reachable = _closure(funcs, LOWERING_ROOTS)
    return sorted(
        (r for r in reads if _is_candidate(r, reachable)),
        key=lambda r: (r.name, r.path, r.line),
    )


def classify(root: Optional[str] = None) -> Dict[str, dict]:
    """Full classification: name -> {class, keyed_by, reads, reason}.

    ``class`` is one of:

    * ``"lowering"`` — reachable from a staging root and not exempted:
      the flag alters what gets traced/staged and MUST be key-covered;
    * ``"host"`` — exempted by `NON_LOWERING` (reason attached) or
      never reachable from a staging root.
    """
    reads, funcs = _scan_package(root)
    reachable = _closure(funcs, LOWERING_ROOTS)
    covered = key_coverage(root)
    out: Dict[str, dict] = {}
    for r in reads:
        entry = out.setdefault(
            r.name,
            {"class": "host", "keyed_by": covered.get(r.name),
             "reads": [], "reason": NON_LOWERING.get(r.name, "")},
        )
        entry["reads"].append(r)
        if (
            _is_candidate(r, reachable) or r.name in covered
        ) and r.name not in NON_LOWERING:
            entry["class"] = "lowering"
    return out


def env_table_section(api_md: Optional[str] = None) -> str:
    """The raw text of docs/api.md's '## Environment variables' section
    — the ONE extraction both the lint and the doc-consistency tests
    parse, so a heading rename breaks every checker loudly instead of
    one silently. Empty string when the section is missing."""
    path = api_md or os.path.join(REPO_ROOT, "docs", "api.md")
    with open(path, encoding="utf-8") as f:
        text = f.read()
    m = re.search(
        r"^## Environment variables\n(.*?)(?=^## |\Z)",
        text, re.M | re.S,
    )
    return m.group(1) if m else ""


def env_table_rows(api_md: Optional[str] = None) -> List[Tuple[str, str]]:
    """(name, rest-of-row) per table row of the env section."""
    return re.findall(
        r"^\|\s*`(PA_\w+)`\s*\|([^\n]*)$", env_table_section(api_md), re.M
    )


def documented_env_names(api_md: Optional[str] = None) -> Set[str]:
    """``PA_*`` names listed in docs/api.md's environment-variable
    table (the section the doc-consistency test enforces)."""
    return {name for name, _ in env_table_rows(api_md)}


def lint_env_keys(
    root: Optional[str] = None, api_md: Optional[str] = None,
    check_docs: bool = True,
) -> List[str]:
    """The gate. Returns human-readable violations (empty = green):

    1. every ``PA_*`` read classified ``lowering`` is covered by a
       registered key site;
    2. every `NON_LOWERING` exemption still corresponds to a real read
       (a stale exemption hides the next regression);
    3. (``check_docs``) the docs/api.md env table lists exactly the
       inventoried names — no undocumented flag, no ghost row.
    """
    cls = classify(root)
    covered = key_coverage(root)
    violations: List[str] = []
    for name, entry in sorted(cls.items()):
        if entry["class"] == "lowering" and name not in covered:
            sites = ", ".join(str(r) for r in entry["reads"][:3])
            violations.append(
                f"{name}: alters tracing/lowering (read at {sites}) but no "
                f"registered cache-key site ({', '.join(KEY_SITES)}) "
                "resolves it — fold it into _lowering_env_key() or an "
                "auxiliary key, or exempt it in "
                "analysis.env_lint.NON_LOWERING with a reason"
            )
    if root is None or os.path.abspath(root) == PACKAGE_ROOT:
        # the exemption table describes THIS package — checking it for
        # staleness against a synthetic root (the lint's own negative
        # tests) would always fire
        for name in sorted(NON_LOWERING):
            if name not in cls:
                violations.append(
                    f"{name}: exempted in NON_LOWERING but no longer read "
                    "anywhere in the package — delete the stale exemption"
                )
    if check_docs:
        documented = documented_env_names(api_md)
        inventoried = set(cls)
        for name in sorted(inventoried - documented):
            violations.append(
                f"{name}: read in the package but missing from the "
                "docs/api.md '## Environment variables' table"
            )
        for name in sorted(documented - inventoried):
            violations.append(
                f"{name}: documented in docs/api.md but never read in the "
                "package — drop the row or restore the flag"
            )
    return violations
