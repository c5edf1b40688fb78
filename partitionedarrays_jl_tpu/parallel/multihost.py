"""Multi-host (multi-slice) execution over ICI + DCN.

The reference scales past one machine through MPI: every rank is one
process, `MPIData` holds the rank's chunk, and the MPI library moves
bytes (reference: src/MPIBackend.jl:1-309). The TPU-native analog is
JAX's multi-controller runtime: one Python process per host, every
process runs the SAME driver (SPMD, exactly like `mpirun`), and a global
`jax.sharding.Mesh` spans all hosts' devices — XLA routes mesh-axis
collectives over ICI within a slice and DCN across slices. Nothing else
in the framework changes:

* **Planning** is replicated: every controller executes the same
  host-side plan (PRange construction, Exchanger build, COO migration)
  on the same metadata, so all controllers compile identical programs —
  the same property that lets the reference run one driver per rank.
* **`_stage`** (tpu.py) materializes only each controller's addressable
  shard rows via `jax.make_array_from_callback`, so staging never ships
  the full (P, W) array across hosts.
* **Compiled execution** (`make_exchange_fn`, `make_spmv_fn`,
  `make_cg_fn`, ...) is `shard_map` over the global mesh; the
  `ppermute` halo rounds between co-located parts ride ICI and the
  slice-crossing edges ride DCN automatically.

What is NOT multi-host transparent is pulling a whole distributed object
back to one host (`DeviceVector.to_pvector`, `gather_pvector` on device
data): those need the non-addressable shards. `fetch_global` below wraps
the `process_allgather` escape hatch for debug-sized data, mirroring the
reference's explicit gather-to-MAIN debug path
(reference: src/Interfaces.jl:2664-2732).

Typical launch (one process per host, same script everywhere):

    import partitionedarrays_jl_tpu as pa
    pa.multihost_init()                      # jax.distributed.initialize
    backend = pa.TPUBackend()                # global devices, all hosts
    pa.prun(driver, backend, len(jax.devices()))
"""
from __future__ import annotations

from typing import Optional

import numpy as np


def multihost_init(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    attempts: Optional[int] = None,
    backoff: Optional[float] = None,
) -> None:
    """Join the multi-controller runtime (idempotent).

    With no arguments, relies on the cluster environment (TPU pods set
    everything automatically); arguments are forwarded for manual
    clusters. Call once per process, before any other JAX use. The
    single-host case is a no-op so drivers can call it unconditionally.

    An EXPLICIT cluster spec is retried with exponential backoff before
    failing: in practice the coordinator process is usually still coming
    up when the workers first dial it, and one refused connection must
    not kill an N-host launch. ``attempts``/``backoff`` default to the
    shared retry knobs (``PA_RETRY_ATTEMPTS``/``PA_RETRY_BACKOFF``,
    parallel/health.py). A spec that still fails after the budget raises
    — it must not silently degrade into N independent single-host runs."""
    import jax

    from jax._src.distributed import global_state

    if global_state.client is not None:
        return  # already joined the cluster
    # NOTE: do not probe jax.process_count() here — it would initialize the
    # local-only backend first, making the subsequent cluster join fail.
    explicit = (
        coordinator_address is not None
        or num_processes is not None
        or process_id is not None
    )

    def _init():
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )

    if explicit:
        from .health import retry_with_backoff

        retry_with_backoff(
            _init,
            attempts=attempts,
            backoff=backoff,
            exceptions=(RuntimeError,),  # ValueError = bad spec: no retry
            describe=f"multihost_init (coordinator {coordinator_address})",
        )
        return
    try:
        _init()
    except (RuntimeError, ValueError):
        pass  # no cluster environment: single-process run, keep local runtime


def is_main_process() -> bool:
    """The multi-controller analog of `i_am_main` (process 0 is MAIN)."""
    import jax

    return jax.process_index() == 0


def fetch_global(data) -> np.ndarray:
    """Replicate a (possibly non-addressable) sharded array onto every
    host as NumPy — the debug/checkpoint escape hatch for multi-host runs.
    On a single host this is a plain device->host copy."""
    import jax

    if jax.process_count() == 1:
        return np.asarray(data)
    from jax.experimental import multihost_utils

    # tiled=True: reassemble the GLOBAL array (the only mode supported
    # for non-fully-addressable inputs) — shape matches the single-host
    # np.asarray path
    return np.asarray(multihost_utils.process_allgather(data, tiled=True))
