"""Static memory-footprint accounting for the lowering matrix.

ROADMAP item 1's multi-operator tenancy needs an ADMISSION input: how
many bytes does serving one more compiled program cost? This module
derives it statically, per lowering-matrix case, with no timer and no
device run:

* ``carry_bytes`` — the while-loop carry payload from the StableHLO
  report (the while loop's working set);
* ``plan_bytes`` — the staged exchange-plan buffers (index/mask
  operands of the generic plan; segment frame + masks of the box
  plan);
* ``operand_bytes`` — every staged operand array the compiled program
  holds alive (matrix streams, plan operands, preconditioner);
* ``peak_bytes`` — the best static peak-live estimate available:
  the compiled program's XLA buffer assignment
  (``compile().memory_analysis()`` — argument + output + temp bytes)
  where a compiled leg exists, else the conservative shape-sum
  ``operand_bytes + 2 x carry_bytes`` (operands + carry in and out of
  the loop). ``peak_source`` records which.

The ``memory-budget`` contract (analysis.contracts) pins
`MEMORY_BUDGETS` over every case: a case whose static peak grows past
its pinned budget fails palint even when every timer still looks fine
— and a NEW matrix case without a pinned budget fails loudly, the same
discipline the env lint applies to new flags. The per-case table is
committed as the schema-versioned ``MEMORY_FOOTPRINT.json`` artifact
(the admission-budget input; checked by tests/test_doc_consistency.py).
"""
from __future__ import annotations

import math
from typing import Dict, Optional

__all__ = [
    "MEMORY_BUDGETS",
    "MEMORY_SCHEMA_VERSION",
    "artifact_record",
    "attach_footprints",
    "case_footprint",
    "footprint_table",
    "plan_buffer_bytes",
    "write_artifact",
]

#: Version of the footprint-table schema INSIDE the artifact (the
#: envelope has its own telemetry.artifacts.ARTIFACT_SCHEMA_VERSION).
MEMORY_SCHEMA_VERSION = 1

#: Pinned per-case ``peak_bytes`` budgets over the fixed
#: (6,6,6)/(2,2,2) probe (bytes). Measured values get ~2x headroom so
#: routine XLA drift passes but a structural regression — a carry that
#: doubles, a plan that stops deduplicating, an operand stream staged
#: twice — trips loudly. Budgets are PROBE-scale: they guard structure
#: (bytes per case at fixed N), not production sizing; the committed
#: MEMORY_FOOTPRINT.json carries the measured values.
MEMORY_BUDGETS: Dict[str, int] = {
    "standard": 16_000,
    "fused": 18_500,
    "block_k1_fused": 22_000,
    "block_k4_fused": 50_000,
    "standard_nobox": 20_000,
    "standard_abft": 36_000,
    "standard_f32": 9_000,
    "block_k1_standard": 22_000,
    "block_k4_standard": 50_000,
    "fused_nobox": 20_000,
    "block_k4_fused_nobox": 37_000,
    "fused_abft": 36_000,
    "block_k4_fused_abft": 80_000,
    "strict_standard": 59_000,
    "fused_f32": 10_000,
}


def _nbytes(arr) -> int:
    """Works for numpy AND jax arrays without forcing a transfer."""
    shape = getattr(arr, "shape", None)
    dt = getattr(arr, "dtype", None)
    if shape is None or dt is None:
        return 0
    return int(math.prod(shape)) * int(getattr(dt, "itemsize", 0) or
                                       _dtype_itemsize(dt))


def _dtype_itemsize(dt) -> int:
    import numpy as np

    return np.dtype(dt).itemsize


def plan_buffer_bytes(plan) -> int:
    """Bytes the exchange plan itself stages into the program: index /
    mask operands for the generic plan, the segment bookkeeping for
    the box plan (whose pack/unpack geometry is compiled in — only the
    masks and slot maps occupy memory)."""
    from ..parallel.tpu_box import BoxExchangePlan

    if isinstance(plan, BoxExchangePlan):
        info = plan.info
        total = _nbytes(info.seg_mask) + _nbytes(info.variants)
        for rel in info.ghost_rel_slots:
            total += _nbytes(rel)
        return total
    return (
        _nbytes(plan.snd_idx) + _nbytes(plan.snd_mask)
        + _nbytes(plan.rcv_idx)
    )


def case_footprint(
    backend, case: dict, report=None, mem_stats: Optional[dict] = None,
) -> dict:
    """The static footprint of one matrix case (see module docstring).
    ``report`` is the case's StableHLO `ProgramReport` (carry bytes);
    ``mem_stats`` the compiled buffer-assignment numbers when a
    compiled leg exists (`parallel.tpu.case_program_texts`)."""
    from ..parallel.tpu import (
        _MATRIX_BASE_ENV,
        _env_overrides,
        _matrix_operands,
        _matrix_probe_system,
        device_matrix,
    )

    env = dict(_MATRIX_BASE_ENV)
    env.update(case.get("env", {}))
    with _env_overrides(env):
        A, _b, _x0 = _matrix_probe_system(backend, case.get("dtype", "f64"))
        dA = device_matrix(A, backend)
        ops = _matrix_operands(dA)
        plan_bytes = plan_buffer_bytes(dA.col_plan)
        operand_bytes = 0
        import jax

        for leaf in jax.tree_util.tree_leaves(ops):
            operand_bytes += _nbytes(leaf)
    carry_bytes = max(
        (w.carry_bytes for w in report.while_loops), default=0
    ) if report is not None else 0
    fp = {
        "carry_bytes": int(carry_bytes),
        "plan_bytes": int(plan_bytes),
        "operand_bytes": int(operand_bytes),
    }
    if mem_stats:
        fp["peak_bytes"] = int(
            mem_stats.get("argument_bytes", 0)
            + mem_stats.get("output_bytes", 0)
            + mem_stats.get("temp_bytes", 0)
        )
        fp["peak_source"] = "hlo-buffer-assignment"
        fp.update({k: int(v) for k, v in mem_stats.items()})
    else:
        fp["peak_bytes"] = int(operand_bytes + 2 * carry_bytes)
        fp["peak_source"] = "shape-sum"
    return fp


def attach_footprints(backend, cases: dict, reports: dict,
                      verbose=None) -> None:
    """Compute and stash each case's footprint at
    ``cases[name]["memory"]`` — the ``memory-budget`` contract's input
    (mirrors the ``runtime_comms`` stash of the reconciliation
    contract). Compiled-leg cases carry their buffer-assignment stats
    at ``cases[name]["memory_stats"]`` (set by
    `analysis.matrix.build_reports`)."""
    for name, case in cases.items():
        if verbose:
            verbose(f"memory footprint {name} ...")
        case["memory"] = case_footprint(
            backend, case, report=reports.get(name),
            mem_stats=case.get("memory_stats"),
        )


def footprint_table(cases: dict) -> str:
    """The per-case footprint table ``tools/palint.py --report``
    prints (and the artifact commits)."""
    rows = [
        ("case", "carry B", "plan B", "operands B", "peak B", "source",
         "budget B"),
    ]
    for name in sorted(cases):
        fp = cases[name].get("memory")
        if fp is None:
            continue
        rows.append((
            name, str(fp["carry_bytes"]), str(fp["plan_bytes"]),
            str(fp["operand_bytes"]), str(fp["peak_bytes"]),
            fp["peak_source"], str(MEMORY_BUDGETS.get(name, "-")),
        ))
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    return "\n".join(
        "  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
        for r in rows
    )


def artifact_record(cases: dict) -> dict:
    """The committed-artifact payload: the footprint table plus the
    budgets it was pinned against (test_doc_consistency asserts the
    budget copy equals `MEMORY_BUDGETS`, so artifact and gate can
    never drift apart silently)."""
    table = {
        name: dict(case["memory"])
        for name, case in sorted(cases.items())
        if case.get("memory") is not None
    }
    return {
        "memory_schema_version": MEMORY_SCHEMA_VERSION,
        "probe": "(6,6,6) Poisson on a (2,2,2) box partition, 8 parts",
        "cases": table,
        "budgets": {k: int(v) for k, v in sorted(MEMORY_BUDGETS.items())},
        "note": (
            "static per-program footprints for the service admission "
            "budget (ROADMAP item 1); peak_source 'hlo-buffer-"
            "assignment' = XLA buffer assignment of the compiled leg, "
            "'shape-sum' = conservative operands + 2x carry"
        ),
    }


def write_artifact(path: str, cases: dict, tool: str = "palint",
                   dry_run: bool = False) -> dict:
    from ..telemetry import artifacts

    return artifacts.write(
        path, artifact_record(cases), tool=tool, dry_run=dry_run
    )
