"""ICI-mode bench legs on the virtual 8-device CPU mesh (round-4
directive 8): exercises `bench.bench_ici` — true neighbor-`ppermute`
halo exchange + mesh CG — today, without real multi-chip hardware.
Records are labeled ``fabric="virtual-cpu"``: they validate the kernels
and the measurement path, NOT interconnect bandwidth. On a machine with
a real TPU slice, `python bench.py` runs the same legs automatically
with ``fabric="ici"``.

The legs land in ``ICI_BENCH.json`` through the shared schema-versioned
artifact writer (`telemetry.artifacts` — the same envelope every other
committed ``*_BENCH.json`` carries and tests/test_doc_consistency.py
checks); ``--dry-run`` prints the record without committing.

The record also carries a schema-v2 ``comms_matrix`` block — the
static per-edge byte accounting of the mesh operator's exchange plan
(`telemetry.commsmatrix.static_matrix`), reconciled against
`comms._exchange_inventory` before writing.

    python tools/bench_ici.py            # 64^3, 8 virtual CPU devices
    PA_ICI_N=96 python tools/bench_ici.py
    python tools/bench_ici.py --dry-run
"""
from __future__ import annotations

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def comms_record(pa, backend, ns=(6, 6, 6), pshape=(2, 2, 2)):
    """The v2 matrix block: static per-edge accounting of the mesh
    operator's column plan."""
    import numpy as np

    from partitionedarrays_jl_tpu.models import assemble_poisson
    from partitionedarrays_jl_tpu.parallel.tpu import device_matrix
    from partitionedarrays_jl_tpu.telemetry import commsmatrix as cm

    def driver(parts):
        A, _b, _xe, _x0 = assemble_poisson(parts, ns)
        return A

    A = pa.prun(driver, backend, pshape)
    dA = device_matrix(A, backend)
    m = cm.static_matrix(dA.col_plan, np.float64, backend=backend)
    m["static_check"] = cm.reconcile_matrix(m, dA)
    assert m["static_check"] == [], m["static_check"]
    return m


def main():
    import partitionedarrays_jl_tpu as pa
    from partitionedarrays_jl_tpu.parallel.tpu import TPUBackend
    from partitionedarrays_jl_tpu.telemetry import artifacts
    import bench

    dry = "--dry-run" in sys.argv[1:]
    n = int(os.environ.get("PA_ICI_N", "64"))
    devs = jax.devices()
    assert len(devs) == 8 and devs[0].platform == "cpu", devs
    legs = bench.bench_ici(n, devs, pa, "virtual-cpu")
    matrix = comms_record(pa, TPUBackend(devices=devs))
    rec = {
        "methodology": bench.METHODOLOGY,
        "n": n,
        "dofs": n ** 3,
        "fabric": "virtual-cpu",
        "devices": 8,
        "legs": legs,
        "comms_matrix": matrix,
        "note": (
            "virtual-cpu fabric: validates the multi-device ppermute "
            "halo/CG kernels and the measurement path, not interconnect "
            "bandwidth — real-slice records come from `python bench.py` "
            "with fabric='ici' (ROADMAP item 3)"
        ),
    }
    artifacts.write(
        os.path.join(REPO, "ICI_BENCH.json"), rec, tool="bench_ici",
        dry_run=dry,
    )


if __name__ == "__main__":
    main()
