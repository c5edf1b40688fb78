"""K-process stencil emission into shared memory (round-5 directive 6).

The per-part CSR of a Cartesian stencil is emitted row-slab by row-slab:
each row's nnz is known in closed form (identity rows carry 1 entry,
interior rows 2*dim+1 — planning.cpp emits decoupled values in place,
pattern preserved), so every slab's output offset is computable before
any emission runs. K spawned workers therefore write DISJOINT slices of
one preallocated shared-memory CSR with zero stitching, and the result
is byte-identical to the one-shot `native.stencil_emit` — pinned by
`tests/test_multiproc_planning.py`.

`spawn` context by design: forking a process with live JAX threads is
deadlock-prone (the round-4 advisor flagged the tool's `fork` pool), and
under spawn the workers import fresh interpreters. A worker imports this
package and nothing of JAX: importing the package does not import jax
(pinned by tests/test_multiproc_planning.py), so a worker can never
initialize a backend — the parent process may hold the chip, which
belongs to one process at a time.

On a 1-core host the K-process wall time is ~1x the serial emission (the
documented no-op); the same flag scales on multi-core planning hosts.
Reference anchor: per-rank local assembly, test/test_fdm.jl:52-81.
"""
from __future__ import annotations

import math
from multiprocessing import get_context, shared_memory

import numpy as np

__all__ = ["stencil_emit_parallel", "slab_nnz"]

# one spawn pool per worker count, reused across parts and calls — each
# spawned child pays the package import once, not once per part
# (review r5). Terminated at interpreter exit.
_pools: dict = {}


def _pool(k: int):
    import atexit

    p = _pools.get(k)
    if p is None:
        p = _pools[k] = get_context("spawn").Pool(k)
        if len(_pools) == 1:
            atexit.register(_shutdown_pools)
    return p


def _shutdown_pools():
    for p in _pools.values():
        p.terminate()
        p.join()
    _pools.clear()


def slab_nnz(dims, lo, hi, i0, i1):
    """Exact nnz of box row-slab i in [i0, i1) (slab along box dim 0):
    interior grid cells emit 2*dim+1 entries, grid-boundary cells 1."""
    dim = len(dims)

    def interior_count(d, a, b):
        # grid coords [a, b) clipped to the interior band [1, dims[d]-1)
        return max(0, min(b, dims[d] - 1) - max(a, 1))

    rows = (i1 - i0) * math.prod(hi[d] - lo[d] for d in range(1, dim))
    inter = interior_count(0, lo[0] + i0, lo[0] + i1)
    for d in range(1, dim):
        inter *= interior_count(d, lo[d], hi[d])
    return inter * (2 * dim + 1) + (rows - inter) * 1


def _worker(args):
    """Emit rows [row0, row1) into the shared CSR at offset nnz0.

    Top-level so `spawn` can import it; attaches the shm segments by
    name, wraps zero-copy views, and calls the native range kernel."""
    (
        shm_names, dims, lo, hi, center, arm_vals, ghost_gids, dt_name,
        decouple, xtab, row0, row1, nnz0, nnz_slab, with_b, nnz_total,
    ) = args
    from partitionedarrays_jl_tpu import native

    no = math.prod(h - l for h, l in zip(hi, lo))
    segs = {k: shared_memory.SharedMemory(name=v) for k, v in shm_names.items()}
    # NOTE on cpython <=3.12 attach-registration (bpo-38119): pool
    # workers spawned by _pool() inherit the PARENT'S resource tracker,
    # so their attach-registrations land in the same (idempotent) cache
    # entry the parent's create made — the parent's unlink() unregisters
    # it once, no "leaked shared_memory" warnings and no double
    # unregister (a worker-side unregister here would KeyError the
    # shared tracker daemon)
    try:
        dt = np.dtype(dt_name)
        # shm segments are page-rounded: size the views from geometry,
        # never from seg.size
        indptr = np.ndarray(no + 1, dtype=np.int32, buffer=segs["indptr"].buf)
        cols = np.ndarray(nnz_total, dtype=np.int32, buffer=segs["cols"].buf)
        vals = np.ndarray(nnz_total, dtype=dt, buffer=segs["vals"].buf)
        b = (
            np.ndarray(no, dtype=dt, buffer=segs["b"].buf)
            if with_b
            else None
        )
        ip_slab = np.empty(row1 - row0 + 1, dtype=np.int32)
        w = native.stencil_emit_range(
            dims, lo, hi, center, arm_vals, ghost_gids, dt,
            row0, row1,
            ip_slab,
            cols[nnz0 : nnz0 + nnz_slab],
            vals[nnz0 : nnz0 + nnz_slab],
            b_out=b[row0:row1] if with_b else None,
            decouple=decouple,
            xtab=xtab,
        )
        if w is None or w != nnz_slab:
            return (row0, -1 if w is None else w)
        # absolute indptr: every slab's relative pointers + its offset.
        # Slab k writes indptr[row0] == nnz0, which slab k-1 also wrote
        # as its LAST entry — same value, benign overlap.
        indptr[row0 : row1 + 1] = ip_slab + np.int32(nnz0)
        return (row0, w)
    finally:
        for s in segs.values():
            s.close()


def stencil_emit_parallel(
    dims, lo, hi, center, arm_vals, ghost_gids, dtype, procs,
    decouple=False, xtab=None,
):
    """`native.stencil_emit` semantics, emitted by `procs` spawned
    workers over row slabs. Returns (indptr, cols, vals[, b]) or None
    when ineligible (callers use the serial path)."""
    from partitionedarrays_jl_tpu import native

    dim = len(dims)
    dims = tuple(int(d) for d in dims)
    lo = tuple(int(x) for x in lo)
    hi = tuple(int(x) for x in hi)
    box0 = hi[0] - lo[0]
    if not native.available() or dim > 3 or procs < 2 or box0 < 2:
        return None
    dt = np.dtype(dtype)
    if dt.name not in ("float64", "float32"):
        return None
    no = math.prod(h - l for h, l in zip(hi, lo))
    inner = math.prod(hi[d] - lo[d] for d in range(1, dim))
    nnz_total = slab_nnz(dims, lo, hi, 0, box0)
    if nnz_total >= 2**31 or no + len(ghost_gids) >= 2**31 or no == 0:
        return None
    with_b = xtab is not None

    K = min(procs, box0)
    cuts = [round(k * box0 / K) for k in range(K + 1)]
    gg = np.ascontiguousarray(ghost_gids, dtype=np.int64)
    av = np.ascontiguousarray(arm_vals, dtype=np.float64)
    xt = np.ascontiguousarray(xtab, dtype=np.float64) if with_b else None

    shm = {}
    try:
        # created INSIDE the try: a partial creation (e.g. ENOSPC on
        # /dev/shm at 464^3) must roll back the segments already made
        shm["indptr"] = shared_memory.SharedMemory(
            create=True, size=(no + 1) * 4
        )
        shm["cols"] = shared_memory.SharedMemory(
            create=True, size=nnz_total * 4
        )
        shm["vals"] = shared_memory.SharedMemory(
            create=True, size=nnz_total * dt.itemsize
        )
        if with_b:
            shm["b"] = shared_memory.SharedMemory(
                create=True, size=max(no, 1) * dt.itemsize
            )
        names = {k: s.name for k, s in shm.items()}
        tasks = []
        nnz0 = 0
        for k in range(K):
            i0, i1 = cuts[k], cuts[k + 1]
            if i0 == i1:
                continue
            nz = slab_nnz(dims, lo, hi, i0, i1)
            tasks.append(
                (
                    names, dims, lo, hi, float(center), av, gg, dt.name,
                    bool(decouple), xt, i0 * inner, i1 * inner, nnz0, nz,
                    with_b, nnz_total,
                )
            )
            nnz0 += nz
        assert nnz0 == nnz_total, (nnz0, nnz_total)
        # one pool keyed by the REQUESTED worker count: parts whose dim-0
        # extent caps K below procs would otherwise spawn a second pool
        # per distinct task count (review r5) — submitting fewer tasks to
        # a procs-wide pool is free
        results = _pool(procs).map(_worker, tasks)
        if any(w < 0 or w != t[13] for (_, w), t in zip(results, tasks)):
            return None
        indptr = np.ndarray(
            no + 1, dtype=np.int32, buffer=shm["indptr"].buf
        ).copy()
        cols = np.ndarray(
            nnz_total, dtype=np.int32, buffer=shm["cols"].buf
        ).copy()
        vals = np.ndarray(
            nnz_total, dtype=dt, buffer=shm["vals"].buf
        ).copy()
        out = (indptr, cols, vals)
        if with_b:
            out = out + (
                np.ndarray(no, dtype=dt, buffer=shm["b"].buf).copy(),
            )
        return out
    finally:
        for s in shm.values():
            try:
                s.close()
            finally:
                s.unlink()
