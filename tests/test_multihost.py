"""Two-process multi-host smoke test: the DCN story exercised with REAL
processes (reference analog: the mpiexec suite, test/mpi/runtests.jl:1-20
— each test spawns a real multi-rank job and asserts clean completion).

Two `jax.distributed` CPU processes x 4 virtual devices each form one
8-device global mesh; both run the identical FDM driver (replicated
planning), the compiled CG executes over the global mesh, and each
controller checks the solve plus cross-process agreement of the result.
The leg carries a named skip for jaxlib CPU runtimes without
cross-process collectives (the documented backend limitation).
"""
import os
import socket
import subprocess
import sys

import pytest

WORKER = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "_multihost_worker.py"
)

#: jaxlib builds whose CPU runtime lacks cross-process collectives fail
#: the compiled solve with exactly this error. That is a missing BACKEND
#: capability, not a bug in this library's multi-host story — skip with
#: the reason instead of failing, and keep the full assertion strength
#: wherever the capability exists (real multiprocess CPU builds, TPU
#: slices). The string is jaxlib's own message, matched verbatim.
_NO_MULTIPROCESS_BACKEND = (
    "Multiprocess computations aren't implemented on the CPU backend"
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def test_two_process_fdm_solve():
    """The compiled CG over a true two-process global mesh (named skip
    below when the jaxlib CPU runtime cannot execute cross-process
    programs)."""
    port = _free_port()
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_ENABLE_X64")
    }
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(port), str(pid), "2"],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if any(
        p.returncode != 0 and _NO_MULTIPROCESS_BACKEND in out
        for p, out in zip(procs, outs)
    ):
        # the cluster formed (jax.distributed handshake succeeded) but
        # the runtime cannot EXECUTE cross-process programs — a
        # documented jaxlib CPU-backend limitation in this environment
        pytest.skip(
            "jaxlib CPU runtime lacks multiprocess collectives "
            f"({_NO_MULTIPROCESS_BACKEND!r}); the two-process DCN smoke "
            "test needs a multiprocess-capable backend (TPU slice or a "
            "jaxlib CPU build with cross-process support)"
        )
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out}"
        assert f"MULTIHOST_OK pid={pid}" in out, out
