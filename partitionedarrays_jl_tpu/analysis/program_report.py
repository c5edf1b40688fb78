"""Structural analysis of lowered solver programs.

The communication structure of a compiled body — how many collectives
of each kind per iteration, what rides them, what dtype the arithmetic
runs in, how many buffer copies the while-loop carries pay — IS the
contract that matters at scale (cf. arXiv:1612.08060 on node-aware
SpMV communication structure). Until this module, those invariants were
asserted ad hoc: three copy-pasted regex helpers in the test tree and
humans eyeballing HLO dumps. `ProgramReport` parses the lowered text of
any compiled program into the structured inventory the contract layer
(`analysis.contracts`) checks declaratively.

Two dialects are understood, because the two interesting program forms
live in different ones:

* **StableHLO MLIR** — ``run_fn.jit_fn.lower(...).as_text()``, the
  pre-optimization program. Collective counts, payload bytes, dtype
  inventory, while-loop carry shapes and host-transfer ops are all
  visible and STABLE here (XLA has not yet rewritten anything), so
  every per-kind counting contract reads this form. Ops appear as
  ``stablehlo.all_gather`` / ``"stablehlo.collective_permute"(...)``
  with ``tensor<8x82xf64>``-style types.
* **Optimized HLO** — ``.lower(...).compile().as_text()``, the
  post-optimization program. ``copy`` ops only exist here (the PR 2
  buffer-copy-anomaly canary: XLA materializes while-loop carry copies
  in this form), as do the fusion decisions. Ops appear as
  ``%name = f64[9]{0} collective-permute(...)``.

`analyze_text` auto-detects the dialect; `collective_counts` keeps the
exact raw-substring semantics of the three historical test helpers it
replaces (`len(re.findall(kind, text))`) so migrated tests pin the
same numbers they pinned before the refactor.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

#: The collective kinds every counting contract speaks about, in the
#: spelling of the StableHLO dialect (the optimized-HLO spelling swaps
#: ``_`` for ``-``). ``reduce_scatter`` rounds out the family even
#: though no current lowering emits one — a program that suddenly does
#: emit one should trip a parity contract, not be invisible to it.
COLLECTIVE_KINDS = (
    "all_gather",
    "collective_permute",
    "all_reduce",
    "reduce_scatter",
)

_ITEMSIZE = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "i64": 8, "u64": 8, "i32": 4, "u32": 4, "s64": 8, "s32": 4,
    "i16": 2, "u16": 2, "s16": 2, "i8": 1, "u8": 1, "s8": 1,
    "i1": 1, "pred": 1,
}

#: SPMD partitioning markers jax inserts around every shard_map program;
#: they are bookkeeping, not host transfers.
_SPMD_CUSTOM_CALLS = {
    "Sharding",
    "SPMDFullToShardShape",
    "SPMDShardToFullShape",
}

# tensor<8x82xf64> / tensor<f64>  (StableHLO)
_MLIR_TENSOR = re.compile(r"tensor<(?:([0-9x]+)x)?([a-z][a-z0-9]+)>")
# f64[9]{0} / f64[] / s32[7,3]{1,0}  (optimized HLO)
_HLO_TENSOR = re.compile(r"\b([a-z][a-z0-9]+)\[([0-9,]*)\]")


def _mlir_tensor_bytes(dims: Optional[str], dtype: str) -> int:
    n = 1
    if dims:
        for d in dims.split("x"):
            n *= int(d)
    return n * _ITEMSIZE.get(dtype, 0)


def _hlo_tensor_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _ITEMSIZE.get(dtype, 0)


@dataclass
class WhileLoop:
    """One while loop: where it starts in the text and what it carries."""

    line: int
    #: (dims, dtype) per carry slot, e.g. ``("82", "f64")`` — dims is
    #: the raw dimension spelling of the dialect ("7x3" / "7,3"), ""
    #: for scalars.
    carries: List[Tuple[str, str]] = field(default_factory=list)
    #: Total carry payload in bytes (the while loop's working set).
    carry_bytes: int = 0
    #: Raw text of the loop's regions (cond+body) — used by the
    #: no-host-transfer-inside-loop contract.
    region_text: str = ""


@dataclass
class ProgramReport:
    """The structured inventory of one lowered program."""

    dialect: str  # "stablehlo" | "hlo"
    #: Per-kind collective OP counts (op sites, not raw substring hits).
    collectives: Dict[str, int] = field(default_factory=dict)
    #: Per-kind total payload bytes (sum over op result tensors).
    collective_bytes: Dict[str, int] = field(default_factory=dict)
    #: Every tensor element dtype appearing in the program.
    dtypes: set = field(default_factory=set)
    #: Float dtypes only — the dtype-closure contract's subject.
    float_dtypes: set = field(default_factory=set)
    #: Lines (1-based) of ops producing/consuming f64 tensors.
    f64_lines: List[int] = field(default_factory=list)
    #: infeed/outfeed ops + custom_calls that are not SPMD markers.
    host_transfer_ops: List[Tuple[int, str]] = field(default_factory=list)
    while_loops: List[WhileLoop] = field(default_factory=list)
    #: ``copy`` op count (optimized HLO only; 0 in StableHLO, where the
    #: op does not exist yet — the PR 2 canary needs the compiled form).
    copies: int = 0
    n_lines: int = 0

    @property
    def collective_count_total(self) -> int:
        return sum(self.collectives.values())

    def summary(self) -> str:
        cols = ", ".join(
            f"{k}={v}" for k, v in sorted(self.collectives.items()) if v
        ) or "none"
        loops = "; ".join(
            f"while@{w.line}: {len(w.carries)} carries, {w.carry_bytes} B"
            for w in self.while_loops
        ) or "no while loops"
        return (
            f"[{self.dialect}] collectives: {cols} | dtypes: "
            f"{sorted(self.dtypes)} | copies: {self.copies} | "
            f"host transfers: {len(self.host_transfer_ops)} | {loops}"
        )


def collective_counts(run_fn, *args, kinds=None) -> Dict[str, int]:
    """The shared successor of the three historical test helpers
    (tests/test_fused_cg.py, test_block_cg.py, test_abft.py each carried
    a private copy): lower the compiled program and count raw substring
    hits per collective kind — `len(re.findall(kind, text))`, the EXACT
    semantics the migrated tests pinned their counts with.

    ``run_fn`` is anything `make_cg_fn`-shaped (exposes ``jit_fn``) or a
    bare jitted fn; strings are treated as already-lowered text."""
    if isinstance(run_fn, str):
        txt = run_fn
    else:
        txt = lower_text(run_fn, *args)
    if kinds is None:
        kinds = ("collective_permute", "all_gather", "all_reduce")
    return {k: len(re.findall(k, txt)) for k in kinds}


def lower_text(run_fn, *args, compiled: bool = False) -> str:
    """Lowered text of a compiled-program wrapper (or jitted fn):
    StableHLO by default, optimized HLO with ``compiled=True``."""
    fn = getattr(run_fn, "jit_fn", run_fn)
    low = fn.lower(*args)
    if compiled:
        return low.compile().as_text()
    return low.as_text()


def analyze(run_fn, *args, compiled: bool = False) -> ProgramReport:
    """Lower (and optionally compile) a program and analyze its text."""
    return analyze_text(lower_text(run_fn, *args, compiled=compiled))


def analyze_text(text: str) -> ProgramReport:
    """Parse lowered program text (either dialect) into a report."""
    if "stablehlo." in text or "mhlo." in text or "func.func" in text:
        return _analyze_stablehlo(text)
    return _analyze_hlo(text)


def _scan_braced_region(lines: List[str], start: int) -> Tuple[str, int]:
    """Collect the text from ``lines[start]`` to the line closing its
    brace nesting (tolerant: bails at EOF)."""
    depth = 0
    out = []
    i = start
    while i < len(lines):
        line = lines[i]
        out.append(line)
        depth += line.count("{") - line.count("}")
        if depth <= 0 and i > start:
            break
        i += 1
    return "\n".join(out), i


def _analyze_stablehlo(text: str) -> ProgramReport:
    rep = ProgramReport(dialect="stablehlo")
    lines = text.splitlines()
    rep.n_lines = len(lines)
    for k in COLLECTIVE_KINDS:
        rep.collectives[k] = 0
        rep.collective_bytes[k] = 0
    for i, line in enumerate(lines):
        for dims, dt in _MLIR_TENSOR.findall(line):
            rep.dtypes.add(dt)
            if dt.startswith("f") or dt == "bf16":
                rep.float_dtypes.add(dt)
            if dt == "f64":
                if not rep.f64_lines or rep.f64_lines[-1] != i + 1:
                    rep.f64_lines.append(i + 1)
        for k in COLLECTIVE_KINDS:
            if f"stablehlo.{k}" in line:
                rep.collectives[k] += 1
                # payload = the op's RESULT tensor: first tensor after
                # `->` in the `(operands) -> result` form; in the
                # compact same-type form (no arrow) the trailing type
                # is operand AND result, so the last tensor is right
                has_arrow = "->" in line
                found = _MLIR_TENSOR.findall(
                    line.split("->")[-1] if has_arrow else line
                )
                if found:
                    dims, dt = found[0] if has_arrow else found[-1]
                    rep.collective_bytes[k] += _mlir_tensor_bytes(dims, dt)
        if "stablehlo.infeed" in line or "stablehlo.outfeed" in line:
            rep.host_transfer_ops.append((i + 1, line.strip()[:120]))
        if "stablehlo.custom_call" in line:
            m = re.search(r"custom_call\s+@(\w+)", line)
            target = m.group(1) if m else "?"
            if target not in _SPMD_CUSTOM_CALLS:
                rep.host_transfer_ops.append((i + 1, f"custom_call @{target}"))
        if "stablehlo.while" in line:
            w = WhileLoop(line=i + 1)
            # carry types: `) : tensor<...>, tensor<...>, ...` on the op line
            tail = line.rsplit(") :", 1)[-1]
            for dims, dt in _MLIR_TENSOR.findall(tail):
                w.carries.append((dims or "", dt))
                w.carry_bytes += _mlir_tensor_bytes(dims, dt)
            w.region_text, _ = _scan_braced_region(lines, i)
            rep.while_loops.append(w)
    return rep


def _analyze_hlo(text: str) -> ProgramReport:
    rep = ProgramReport(dialect="hlo")
    lines = text.splitlines()
    rep.n_lines = len(lines)
    hlo_kind = {k: k.replace("_", "-") for k in COLLECTIVE_KINDS}
    for k in COLLECTIVE_KINDS:
        rep.collectives[k] = 0
        rep.collective_bytes[k] = 0
    for i, line in enumerate(lines):
        for dt, dims in _HLO_TENSOR.findall(line):
            if dt in _ITEMSIZE:
                rep.dtypes.add(dt)
                if dt.startswith("f") or dt == "bf16":
                    rep.float_dtypes.add(dt)
                if dt == "f64":
                    if not rep.f64_lines or rep.f64_lines[-1] != i + 1:
                        rep.f64_lines.append(i + 1)
        for k, spelled in hlo_kind.items():
            # op sites only — three result spellings XLA prints:
            #   `= f64[9]{0} collective-permute(`          plain
            #   `= (f64[3]{0}, f64[3]{0}) collective-permute(`  tuple
            #   `= (...) collective-permute-start(`        async pair
            # The async DONE op consumes the start's handle, so counting
            # `-start` alone keeps one count per collective; a bare \S+
            # result capture would miss the spaced tuple forms entirely
            # and silently undercount.
            for m in re.finditer(
                rf"=\s*(\([^)]*\)|\S+)\s+{spelled}(?:-start)?\(", line
            ):
                rep.collectives[k] += 1
                # payload: every tensor in the result expression (an
                # async-start tuple also lists the aliased operand slot
                # and u32 contexts — byte totals are structure signals,
                # asserted > 0, not exact contracts, so erring wide
                # beats reporting 0)
                for dt, dims in _HLO_TENSOR.findall(m.group(1)):
                    rep.collective_bytes[k] += _hlo_tensor_bytes(dt, dims)
        # async spelling too (`copy-start`/`copy-done` pairs, one copy,
        # counted at start — done consumes the handle), mirroring the
        # collective counter above
        if re.search(r"\bcopy(?:-start)?\(", line):
            rep.copies += 1
        if re.search(r"\b(infeed|outfeed)\(", line):
            rep.host_transfer_ops.append((i + 1, line.strip()[:120]))
        m = re.search(r"custom-call\(.*custom_call_target=\"(\w+)\"", line)
        if m and m.group(1) not in _SPMD_CUSTOM_CALLS:
            rep.host_transfer_ops.append(
                (i + 1, f"custom-call {m.group(1)}")
            )
        m = re.search(r"=\s*(\([^)]*\))\s+while\(", line)
        if m:
            w = WhileLoop(line=i + 1)
            for dt, dims in _HLO_TENSOR.findall(m.group(1)):
                w.carries.append((dims or "", dt))
                w.carry_bytes += _hlo_tensor_bytes(dt, dims)
            rep.while_loops.append(w)
    return rep
