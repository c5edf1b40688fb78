"""The ONE schema-versioned record writer.

The tools that commit a record (palint's memory report, paspec,
paprof's profile and comms matrix, paelastic) write it through this
module. `stamp` adds the provenance envelope — ``schema_version``, the
generating tool, the accelerator platform, and the ``PA_*`` environment
snapshot — WITHOUT overwriting anything the tool already recorded (the
committed records' existing keys are the contract
`tests/test_doc_consistency.py` pins). `write` serializes with one
canonical format (indent=1, sorted keys — byte-stable diffs) and honors
the tools' shared ``--dry-run`` convention.

``ARTIFACT_SCHEMA_VERSION`` history:

* **1** — the envelope above; carried by every committed record
  (test_doc_consistency asserts presence on each).
"""
from __future__ import annotations

import json
import os
from typing import Optional

__all__ = ["ARTIFACT_SCHEMA_VERSION", "stamp", "write"]

ARTIFACT_SCHEMA_VERSION = 1


def _platform() -> str:
    try:
        import jax

        return jax.devices()[0].platform
    except Exception:
        return "unknown"


def stamp(rec: dict, tool: Optional[str] = None) -> dict:
    """Add the provenance envelope to a record, in place and returned.
    ``setdefault`` throughout: a tool that records its own ``platform``
    keeps it."""
    rec.setdefault("schema_version", ARTIFACT_SCHEMA_VERSION)
    if tool:
        rec.setdefault("generated_by", tool)
    if "platform" not in rec:  # lazy: _platform() imports jax
        rec["platform"] = _platform()
    rec.setdefault(
        "pa_env",
        {k: v for k, v in sorted(os.environ.items())
         if k.startswith("PA_")},
    )
    return rec


def write(path: str, rec: dict, tool: Optional[str] = None,
          dry_run: bool = False, echo: bool = True) -> dict:
    """Stamp and serialize one artifact. ``dry_run`` prints the record
    (the tools' shared convention) without touching ``path``."""
    rec = stamp(rec, tool=tool)
    out = json.dumps(rec, indent=1, sort_keys=True)
    if dry_run:
        if echo:
            print(out)
        return rec
    with open(path, "w", encoding="utf-8") as f:
        f.write(out + "\n")
    if echo:
        print(f"wrote {path} (schema_version={rec['schema_version']})")
    return rec
