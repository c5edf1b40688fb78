"""TPU backend: parts are devices of a `jax.sharding.Mesh` (L3').

The TPU-native execution model (BASELINE.md north star; SURVEY.md §7):

* **Planning on host.** `TPUData` extends the sequential PData, so every
  planning-phase algorithm (PRange construction, Exchanger build, COO
  assembly, neighbor discovery) runs unchanged — metadata is host NumPy in
  both backends, mirroring the reference's plan/execute split.
* **Execution compiled.** A lowering layer ("graft" of the host objects
  onto the mesh) turns a PRange+Exchanger into static pack/`ppermute`/
  unpack index programs, a PSparseMatrix into stacked padded-ELL blocks in
  HBM, and a PVector into one (P, W) array sharded over the mesh's
  ``'parts'`` axis. Halo exchange is a fixed sequence of `ppermute` rounds
  over ICI (host-side greedy edge coloring of the neighbor graph);
  reductions are deterministic `all_gather` + fixed-order folds so results
  match the sequential oracle; the whole CG loop is ONE `shard_map`-ped
  jitted program (`lax.while_loop`), with the A_oo partial SpMV issued
  before the halo unpack so XLA's latency-hiding scheduler overlaps compute
  with the collectives — the compiled analog of the reference's task-graph
  overlap (reference: src/Interfaces.jl:2246-2275).

Layout of a device vector row (one part), width ``W = no_max + nh_max + 1``:

    [ owned values (padded to no_max) | ghosts (padded to nh_max) | trash ]

Padding stays zero by construction; the final "trash" slot absorbs masked
scatter lanes so no dynamic shapes or bound checks reach the compiled code.
"""
from __future__ import annotations

import contextlib
import itertools
import math
import os
import time
from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import numpy as np

from ..utils.helpers import check, strict_bits
from ..utils.table import INDEX_DTYPE
from .backends import AbstractBackend, PartShape, _as_shape
from .exchanger import Exchanger
from .prange import PRange
from .sequential import SequentialData
from .pvector import PVector, _ghost, _owned
from .psparse import PSparseMatrix


def _jax():
    import jax

    return jax


#: Named scopes of the compiled programs' phases: the vocabulary of
#: `telemetry.profile.PHASES`, prefixed ``pa.``. A scope is op metadata
#: only (the lowered StableHLO is the same with and without it); it puts
#: the phase into every device op's ``op_name``, so a profile's device
#: time has an owner that survives a recompile. Scopes nest and the
#: innermost names the phase: the exchange inside an SpMV is
#: ``pa.halo_exchange``, a dot inside a loop body is ``pa.dot_allgather``.
SCOPE_SPMV = "pa.spmv_local"
SCOPE_HALO = "pa.halo_exchange"
SCOPE_DOTS = "pa.dot_allgather"
SCOPE_AXPY = "pa.axpy_sweep"
#: Inside `SCOPE_SPMV`, on the two node-block lowerings: the gathers of
#: the operand (with the concatenation that lays them out for the
#: product) and the batched products (with the concatenate / slice that
#: lays out the result). The names carry no ``pa.`` of their own, so a
#: reader that takes an op's innermost ``pa.`` component still reads
#: `SCOPE_SPMV` (benchmark/layer_metrics/_scoped.py `phase_of`).
SCOPE_SD_GATHER, SCOPE_SD_EINSUM = "sd.gather", "sd.einsum"
SCOPE_BSR_GATHER, SCOPE_BSR_EINSUM = "bsr.gather", "bsr.einsum"
#: Inside `SCOPE_SPMV` as well: the boundary (A_oh) rows in whichever of
#: their three forms (face slabs, node blocks, ELL), which `oh_rows_us` reads.
SCOPE_OH = "oh"
#: Inside `SCOPE_SPMV` too, where the operator's diagonals are streamed
#: (``dia_mode == "stream"``): the Mosaic kernel; the copies around it (the
#: owned region cut out of the frame and padded into the kernel's
#: ``(x_rows, 128)`` operand, the product cut to its owned length and
#: embedded in a frame again), which `stream_embed_share` reads; and the
#: XLA shifted-slice form the same operator takes off the chip and for a
#: ``(W, K)`` block.
SCOPE_DIA_STREAM, SCOPE_DIA_EMBED, SCOPE_DIA_XLA = (
    "dia.stream", "dia.embed", "dia.xla"
)
#: Inside `SCOPE_HALO`, in the generic (index-vector) exchange body: a
#: round's gather of its send slots and its scatter into its receive
#: slots, which `halo_index_share` reads; the permutes stay the phase's own.
SCOPE_EX_PACK, SCOPE_EX_UNPACK = "ex.pack", "ex.unpack"
#: What `jax.vmap` over the columns of a lane-major block operand traces
#: under (`make_block_cg_fn`): the transform renames the FIRST scope it
#: meets to ``vmap(<name>)``, which no reader would find under ``pa.``,
#: so it is given this one to rename and the phases inside keep theirs.
SCOPE_COLUMN = "column"
#: Outside the solve's program: the small per-part programs that write a
#: part's values into its ``(1, W)`` frame and take them out again
#: (`_vector_program`).
SCOPE_PACK = "pa.stage_pack"
SCOPE_LIFT = "pa.fetch_lift"


def _scoped(scope: str, fn: Callable) -> Callable:
    """``fn`` traced under ``jax.named_scope(scope)``."""

    def scoped(*args, **kwargs):
        with _jax().named_scope(scope):
            return fn(*args, **kwargs)

    return scoped


def _krylov_loop(cond, step, init):
    """`lax.while_loop` of a compiled Krylov program, traced under
    `SCOPE_AXPY`: what a step does outside its SpMV, exchange and dots
    (each under its own, inner, scope) is the vector updates and the
    packing and unpacking of the carry. The `while` op is inside the
    scope too, so what XLA derives from it (the select it fuses around
    an in-place carry update takes the `while`'s metadata) stays owned."""
    jax = _jax()
    with jax.named_scope(SCOPE_AXPY):
        return jax.lax.while_loop(cond, step, init)


_backend_tokens = itertools.count()


class TPUBackend(AbstractBackend):
    """Each part is one device of a 1-D mesh over axis ``'parts'``.

    Works identically on real TPU chips and on virtual CPU devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — the CI story,
    SURVEY.md §4)."""

    def __init__(self, devices=None):
        self._devices = devices
        self._meshes = {}
        self._mesh_grid = {}  # nparts -> part-grid shape the mesh was ordered for
        self._part_devices = {}  # nparts -> the device of each part's row
        # stable cache identity: id(backend) can be recycled after GC,
        # which would hand back device buffers staged for a dead backend
        self._token = next(_backend_tokens)

    def devices(self):
        return self._devices if self._devices is not None else _jax().devices()

    def mesh(self, nparts: int, grid=None):
        grid = tuple(grid) if grid is not None else None
        if nparts not in self._meshes:
            jax = _jax()
            devs = self.devices()
            check(
                nparts <= len(devs),
                f"TPUBackend: {nparts} parts requested but only {len(devs)} devices",
            )
            ordered = self._topology_order(nparts, devs, grid)
            self._meshes[nparts] = jax.sharding.Mesh(
                np.array(ordered), ("parts",)
            )
            self._mesh_grid[nparts] = grid
        elif (
            grid is not None
            and len(grid) > 1
            and self._mesh_grid.get(nparts) != grid
            and all(
                getattr(d, "platform", "") == "tpu"
                for d in self.devices()[:nparts]
            )
        ):
            import warnings

            warnings.warn(
                f"TPUBackend: the {nparts}-device mesh was ordered for part "
                f"grid {self._mesh_grid.get(nparts)} and is reused for "
                f"{grid}; halo neighbors may take multi-hop ICI routes. Use "
                "a fresh TPUBackend per part-grid shape for topology-aware "
                "placement.",
                stacklevel=3,
            )
        return self._meshes[nparts]

    def _topology_order(self, nparts: int, devs, grid):
        """Device order for the flat ``'parts'`` axis. When the part ids
        come from an N-D Cartesian grid and the devices are real TPUs, ask
        `mesh_utils` for a topology-aware N-D device mesh of that shape
        and flatten it in C order: flat part p then sits on the device at
        p's grid coordinate of the physical torus, so the halo
        `ppermute`s between Cartesian neighbors ride single-hop ICI
        links. CPU meshes use list order; so does a grid `mesh_utils`
        declines for this slice's physical topology (its
        NotImplementedError / ValueError), with a warning that names the
        device order actually used — chip_smoke.py treats that warning
        as a failure on a multi-chip host."""
        if (
            grid is not None
            and len(grid) > 1
            and math.prod(grid) == nparts == len(devs)
            and all(getattr(d, "platform", "") == "tpu" for d in devs)
        ):
            from jax.experimental import mesh_utils

            try:
                nd = mesh_utils.create_device_mesh(grid, devices=devs)
                return list(np.asarray(nd).reshape(-1))
            except (NotImplementedError, ValueError) as e:
                import warnings

                warnings.warn(
                    f"TPUBackend: topology-aware device ordering for part "
                    f"grid {grid} failed ({e!r}); using list order "
                    f"{[getattr(d, 'id', d) for d in devs[:nparts]]} — "
                    "halo neighbors may take multi-hop ICI routes.",
                    stacklevel=3,
                )
        return list(devs[:nparts])

    def parts_spec(self):
        jax = _jax()
        return jax.sharding.PartitionSpec("parts")

    def sharding(self, nparts: int):
        jax = _jax()
        return jax.sharding.NamedSharding(self.mesh(nparts), self.parts_spec())

    def part_devices(self, nparts: int) -> list:
        """The device that holds part p's row of a ``(P, ...)`` array under
        `sharding`, for every p, read from the sharding's own index map:
        the mesh may order the devices otherwise than `devices()` does
        (`_topology_order`)."""
        if nparts not in self._part_devices:
            devs = [None] * nparts
            index_map = self.sharding(nparts).devices_indices_map((nparts, 1))
            for d, idx in index_map.items():
                devs[idx[0].start or 0] = d
            self._part_devices[nparts] = devs
        return self._part_devices[nparts]

    def get_part_ids(self, nparts: PartShape) -> "TPUData":
        shape = _as_shape(nparts)
        n = math.prod(shape)
        self.mesh(n, grid=shape)  # validate devices; order the grid on ICI
        return TPUData(list(range(n)), shape, self)

    def prun(self, driver, nparts, *args, **kwargs):
        """Fail-fast entry point: any driver exception is logged with its
        traceback before propagating, so a failure kills the whole job
        instead of wedging devices mid-collective — the single-controller
        analog of the reference's catch + `MPI.Abort`
        (reference: src/MPIBackend.jl:21-36)."""
        parts = self.get_part_ids(nparts)
        try:
            return driver(parts, *args, **kwargs)
        except Exception:
            import traceback

            print("[partitionedarrays_jl_tpu] driver failed; aborting job:")
            traceback.print_exc()
            raise

    def __repr__(self):
        return f"TPUBackend(ndevices={len(self.devices())})"


#: Default-singleton, the analog of `sequential` (uses all visible devices).
tpu = TPUBackend()


def _stage(backend: TPUBackend, arr: np.ndarray, nparts: int):
    """Host (P, ...) array -> array sharded part-per-device. Uses
    `make_array_from_callback` so each process materializes only its
    *addressable* shards — under a multi-host mesh (`jax.distributed`, DCN
    between slices) every controller holds the same host-side plan and
    contributes just its local devices' rows; on one host it degenerates to
    a plain device_put."""
    from .. import telemetry

    jax = _jax()
    _note_narrowing(jax, arr.dtype)
    sh = backend.sharding(nparts)
    with telemetry.annotate("pa:stage:put"):
        return jax.make_array_from_callback(
            arr.shape, sh, lambda idx: arr[idx]
        )


_narrowing_noted = False


def _note_narrowing(jax, dtype) -> None:
    """Say ONCE per process that float64 host data is about to live on
    the device as float32: without ``jax_enable_x64`` (TPUs have no
    native float64) staging narrows, and a tolerance chosen for float64
    (the public drivers' default dtype) is then unreachable."""
    global _narrowing_noted
    if (
        _narrowing_noted
        or np.dtype(dtype) != np.float64
        or jax.config.jax_enable_x64
    ):
        return
    _narrowing_noted = True
    import warnings

    warnings.warn(
        "partitionedarrays_jl_tpu: staging float64 host data onto a "
        "backend without jax_enable_x64 — it is stored and computed in "
        "float32 on the device (float32 resolution floor applies to "
        "every tolerance). Assemble in float32 (e.g. "
        "assemble_poisson(..., dtype=np.float32)) to make this explicit, "
        "or enable x64 where the platform supports it.",
        RuntimeWarning,
        stacklevel=4,
    )


def _device_dtype(dtype):
    """The dtype a staged array of host ``dtype`` really has: float64
    host data is float32 on a backend without x64, and THAT resolution
    floor is the one a solver tolerance has to clear."""
    return _jax().dtypes.canonicalize_dtype(dtype)


class TPUData(SequentialData):
    """Host-side per-part metadata under the TPU backend: planning values
    live on host exactly as in the sequential backend; only the lowered
    hot-path arrays live in HBM. Collective semantics are inherited — the
    device collectives appear in the *compiled* programs, not here."""

    __slots__ = ("_backend",)

    def __init__(self, parts, shape=None, backend: TPUBackend = None):
        super().__init__(parts, shape)
        self._backend = backend if backend is not None else tpu

    @property
    def backend(self) -> TPUBackend:
        return self._backend

    def _like(self, parts: list) -> "TPUData":
        return TPUData(parts, self._shape, self._backend)


# ---------------------------------------------------------------------------
# lowering: host plan -> static device programs
# ---------------------------------------------------------------------------


class DeviceLayout:
    """Slot layout shared by every device object over one PRange.

    Two geometries:

    * compact (host/CPU): ``[owned | ghosts | trash]`` — minimal storage.
    * padded (real TPU): ``[zero block | owned blocks | zero reserve
      block | ghosts | trash | zero tail]`` in units of 2048x128-element
      blocks (ops/pallas_dia.py:PAD_BLOCK_ROWS). The padded form IS the
      coded SpMV kernel's operand/result frame, so the hot loop runs with
      zero layout copies; the pads are the shifted-read halo (invariant:
      every non-owned, non-ghost slot OUTSIDE the ghost segment region is
      exactly 0 — under a box layout, orphan slab slots INSIDE the ghost
      region hold sender values after a forward exchange and are real
      only where `box_info.seg_mask` is True; never read the ghost
      region except through slot maps or the mask).
    """

    __slots__ = (
        "P", "W", "no_max", "nh_max", "noids", "nhids", "lid_slots",
        "hid_slots", "o0", "g0", "padded", "box_info", "programs",
        "dev_hid_slots", "part_shapes",
    )

    def __init__(self, rows: PRange, padded: bool = False, box_info=None):
        isets = rows.partition.part_values()
        self.P = len(isets)
        self.noids = np.array([i.num_oids for i in isets], dtype=np.int64)
        self.nhids = np.array([i.num_hids for i in isets], dtype=np.int64)
        self.no_max = int(self.noids.max())
        self.nh_max = int(self.nhids.max()) if self.P else 0
        self.padded = bool(padded)
        self.box_info = box_info
        # the jitted pack and lift of the parts' values (`_vector_program`),
        # and their ghost slots on the devices (`_ghost_operands`)
        self.programs = {}
        self.dev_hid_slots = {}
        # the box layout reorders the ghost region into per-direction
        # segments (slot maps only — see tpu_box.py); the segment frame
        # can be wider than nh_max (missing-neighbor segments stay zero)
        nh_span = box_info.nh_total if box_info is not None else self.nh_max
        if padded:
            from ..ops.pallas_dia import LANES, PAD_BLOCK_ROWS

            blk = PAD_BLOCK_ROWS * LANES
            n_blocks = -(-self.no_max // blk)
            self.o0 = blk
            self.g0 = (n_blocks + 2) * blk
            self.W = -(-(self.g0 + nh_span + 1) // blk) * blk
        else:
            self.o0 = 0
            self.g0 = self.no_max
            self.W = self.no_max + nh_span + 1
        # lid -> slot per part, from the signed lid_to_ohid map — any lid
        # order is supported (owned-first layouts, the common case, just
        # produce the identity-prefix mapping)
        self.lid_slots = []
        self.hid_slots = []  # ghost slots in hid order (staging + A_oh)
        for p, i in enumerate(isets):
            ohid = np.asarray(i.lid_to_ohid)
            if box_info is not None:
                rel = box_info.ghost_rel_slots[p]
                if rel.size:
                    gslot = self.g0 + rel[
                        np.clip(-ohid - 1, 0, rel.size - 1)
                    ]
                else:
                    gslot = np.zeros_like(ohid) + self.g0
            else:
                gslot = self.g0 + (-ohid - 1)
            slots = np.where(ohid >= 0, self.o0 + ohid, gslot).astype(
                INDEX_DTYPE
            )
            self.lid_slots.append(slots)
            h = ohid < 0
            hs = np.empty(int(self.nhids[p]), dtype=INDEX_DTYPE)
            hs[-ohid[h] - 1] = slots[h]
            self.hid_slots.append(hs)
        # per part: owned entries, ghosts, and where the ghost slots are
        # one run (`_ghost_run`), else None
        self.part_shapes = [
            (int(no), int(nh), _ghost_run(hs))
            for no, nh, hs in zip(self.noids, self.nhids, self.hid_slots)
        ]

    @property
    def trash(self) -> int:
        return self.W - 1


def _color_edges(edges):
    """Greedy edge coloring of the directed neighbor graph into rounds
    where each part sends to at most one part and receives from at most one
    — each round is one partial permutation, i.e. one `ppermute` over ICI.
    Cartesian halo graphs color into (#offsets) rounds, matching the torus
    neighbor structure."""
    edges = sorted(edges, key=lambda e: -len(e[2]))  # big payloads first
    rounds = []
    for src, dst, snd, rcv in edges:
        placed = False
        for r in rounds:
            if all(s != src for s, _, _, _ in r) and all(d != dst for _, d, _, _ in r):
                r.append((src, dst, snd, rcv))
                placed = True
                break
        if not placed:
            rounds.append([(src, dst, snd, rcv)])
    return rounds


def _exchange_edges(exchanger: Exchanger, layout) -> list:
    """The directed slot-level neighbor edges of an Exchanger over a
    device layout: ``(src, dst, snd_slots, rcv_slots)`` per edge — the
    input of the plan's coloring."""
    P = layout.P
    edges = []
    parts_snd = exchanger.parts_snd.part_values()
    parts_rcv = exchanger.parts_rcv.part_values()
    lids_snd = exchanger.lids_snd.part_values()
    lids_rcv = exchanger.lids_rcv.part_values()
    for p in range(P):
        for j, q in enumerate(np.asarray(parts_snd[p])):
            q = int(q)
            hits = np.nonzero(np.asarray(parts_rcv[q]) == p)[0]
            check(len(hits) == 1, "device plan: inconsistent neighbor graphs")
            i = int(hits[0])
            snd_slots = layout.lid_slots[p][lids_snd[p][j]]
            rcv_slots = layout.lid_slots[q][lids_rcv[q][i]]
            check(len(snd_slots) == len(rcv_slots), "device plan: edge size mismatch")
            edges.append((p, q, snd_slots, rcv_slots))
    return edges


class DeviceExchangePlan:
    """Static halo-exchange program: R `ppermute` rounds with pack/unpack
    index matrices (the compiled form of an Exchanger)."""

    __slots__ = ("layout", "perms", "snd_idx", "snd_mask", "rcv_idx", "R", "L")

    def __init__(self, exchanger: Exchanger, layout: DeviceLayout):
        P, W = layout.P, layout.W
        edges = _exchange_edges(exchanger, layout)
        rounds = _color_edges(edges)
        self.layout = layout
        self.R = len(rounds)
        self.L = max((len(e[2]) for e in edges), default=0)
        R, L = max(self.R, 1), max(self.L, 1)
        self.snd_idx = np.zeros((P, R, L), dtype=INDEX_DTYPE)
        self.snd_mask = np.zeros((P, R, L), dtype=bool)
        self.rcv_idx = np.full((P, R, L), layout.trash, dtype=INDEX_DTYPE)
        self.perms = []
        for r, edges_r in enumerate(rounds):
            perm = []
            for src, dst, snd, rcv in edges_r:
                k = len(snd)
                self.snd_idx[src, r, :k] = snd
                self.snd_mask[src, r, :k] = True
                self.rcv_idx[dst, r, :k] = rcv
                perm.append((src, dst))
            self.perms.append(tuple(perm))
        self.perms = tuple(self.perms)


def _shard_exchange(plan, combine: str, abft: bool = False):
    """Per-shard halo exchange body (used inside shard_map): R static
    `ppermute` rounds. `combine='set'` for owner->ghost halo updates,
    `'add'` for ghost->owner assembly scatter-accumulation (which, like the
    host `assemble`, zeroes the ghost region afterwards —
    reference: src/Interfaces.jl:2078-2106).

    Dispatch: a BoxExchangePlan (Cartesian partitions, tpu_box.py) gets
    the gather-free slice body; the generic plan keeps the index-vector
    form below. Both bodies share the (xv, si, sm, ri) signature, and
    both are RANK-POLYMORPHIC over the operand: ``xv`` is ``(W,)`` for a
    single vector or ``(W, K)`` for a multi-RHS block — slot indexing
    stays on the leading axis, so one wire round ships all K columns of
    a slot at once (the node-aware amortization of arxiv 1612.08060:
    the latency/coloring cost of a round is paid once per K columns).

    ``abft=True`` (generic plan only — ABFT mode pins the generic plan,
    see `_box_exchange_enabled`) returns the checksummed variant
    ``body(...) -> (xv, delta, scale)``: each round's permuted payload
    carries ONE extra slot holding the sender-side slab sum, and the
    receiver accumulates ``|Σ received - shipped sum|`` into ``delta``
    (per column for a block operand). Zero extra collectives — the same
    R ppermutes, each one slot wider; the deltas then ride the CG dot's
    existing all_gather (`_pdot_extra_factory`)."""
    import jax
    import jax.numpy as jnp

    from .tpu_box import BoxExchangePlan, shard_box_exchange

    if isinstance(plan, BoxExchangePlan):
        check(not abft, "ABFT exchange checksums require the generic plan")
        return _scoped(SCOPE_HALO, shard_box_exchange(plan, combine))

    R = plan.R
    perms = plan.perms
    g0 = plan.layout.g0
    L = plan.snd_idx.shape[-1]

    def pack(xv, si, sm, r):
        with jax.named_scope(SCOPE_EX_PACK):
            mask = sm[r].reshape(sm[r].shape + (1,) * (xv.ndim - 1))
            return jnp.where(mask, xv[si[r]], 0)

    def unpack(xv, ri, r, buf):
        with jax.named_scope(SCOPE_EX_UNPACK):
            if combine == "add":
                return xv.at[ri[r]].add(buf)
            return xv.at[ri[r]].set(buf)

    def tidy(xv):
        """Once an exchange, behind its last round: the pads of every
        round landed in the trash slot, which is kept clean so padding
        invariants hold (no round reads it: send pads are masked)."""
        with jax.named_scope(SCOPE_EX_UNPACK):
            if combine == "add":
                # ghost contributions now live on owners; the trash slot
                # lies behind the ghosts
                return xv.at[g0:].set(0)
            return xv.at[plan.layout.trash].set(0) if R else xv

    def body(xv, si, sm, ri):
        for r in range(R):
            buf = jax.lax.ppermute(
                pack(xv, si, sm, r), "parts", perm=perms[r]
            )
            xv = unpack(xv, ri, r, buf)
        return tidy(xv)

    if not abft:
        return _scoped(SCOPE_HALO, body)

    def body_abft(xv, si, sm, ri):
        # delta/scale follow the operand rank: () or per-column (K,)
        delta = jnp.zeros(xv.shape[1:], dtype=xv.dtype)
        scale = jnp.zeros(xv.shape[1:], dtype=xv.dtype)
        for r in range(R):
            buf = pack(xv, si, sm, r)
            cs = jnp.sum(buf, axis=0, keepdims=True)
            payload = jax.lax.ppermute(
                jnp.concatenate([buf, cs], axis=0), "parts", perm=perms[r]
            )
            buf, rcs = payload[:L], payload[L]
            delta = delta + jnp.abs(jnp.sum(buf, axis=0) - rcs)
            scale = scale + jnp.sum(jnp.abs(buf), axis=0) + jnp.abs(rcs)
            xv = unpack(xv, ri, r, buf)
        return tidy(xv), delta, scale

    return _scoped(SCOPE_HALO, body_abft)


def _count_vector(kind: str, on_device: bool) -> None:
    """One vector packed (``kind='packs'``) or lifted (``'lifts'``):
    ``solve.device_<kind>`` or ``solve.host_<kind>`` grows by one, and
    the other is there to be read as unchanged."""
    from .. import telemetry

    telemetry.bump(f"solve.device_{kind}", int(on_device))
    telemetry.bump(f"solve.host_{kind}", int(not on_device))


def _parts_on_device_path(rows: PRange) -> bool:
    """Whether a vector over ``rows`` is packed and lifted on the device
    (`_pack_on_device`, `_lift_on_device`): every part numbers its owned
    lids first, so a part's values ARE ``[owned | ghosts in hid order]``
    as the caller holds them, and one process addresses every shard. Any
    other vector keeps the host frame (`_pack_on_host`,
    `_host_frame_to_pvector`)."""
    owned_first = getattr(rows, "_owned_first", None)
    if owned_first is None:
        # kept on the rows (as `_device_layout` is, and dropped with it):
        # the test walks every owned lid of every part
        owned_first = rows._owned_first = all(
            i.owned_first for i in rows.partition.part_values()
        )
    return owned_first and _jax().process_count() == 1


def _ghost_run(slots: np.ndarray) -> Optional[int]:
    """The first of ``slots`` where they are one run of consecutive
    slots (the ghost region of a layout without box segments), so that
    they are read and written as a slice; else None."""
    if slots.size and np.array_equal(
        slots, np.arange(slots[0], slots[0] + slots.size, dtype=slots.dtype)
    ):
        return int(slots[0])
    return None


def _vector_program(layout: DeviceLayout, kind: str, no: int, nh: int,
                    dtype, run: Optional[int], mesh=None):
    """The jitted pack (``kind='pack'``: a part's values -> its ``(1, W)``
    frame) or lift (``'lift'``: the frame -> the part's values) of a part
    with ``no`` owned and ``nh`` ghost entries, built once per layout and
    shape. The owned run is a static slice at ``o0``; the ghosts are a
    static slice at ``run`` where their slots are consecutive, else they
    go through the operands `_ghost_operands` keeps on the device (box
    layouts: the ghost region is direction segments): the lift gathers
    them at their slots; the pack permutes them into slot order and
    scatters them sorted and unique, the one form of a scatter XLA does
    not put a sort in front of (at 72,580 slots the sort alone took 15 s
    to compile for a v5e, a device, against 0.4 s). ``nh=0`` moves owned
    entries only (b, and every vector of a single part).

    With ``mesh`` the program is the one of all parts, which then share
    this shape: it runs over the mesh (`shard_map`), on the parts' values
    and ghost operands laid end to end in 1-D arrays sharded over the
    parts, and compiles once where a program of a part compiles once a
    device."""
    key = (kind, no, nh, np.dtype(dtype).str, run, mesh)
    if key in layout.programs:
        return layout.programs[key]
    jax = _jax()
    import jax.numpy as jnp

    W, o0 = layout.W, layout.o0

    def pack(vals, order=None, sorted_slots=None):
        vals = vals.astype(dtype)
        frame = jax.lax.dynamic_update_slice(
            jnp.zeros((W,), dtype), vals[:no], (o0,)
        )
        ghosts = vals[no : no + nh]
        if nh and run is not None:
            frame = jax.lax.dynamic_update_slice(frame, ghosts, (run,))
        elif nh:
            frame = frame.at[sorted_slots].set(
                ghosts[order], indices_are_sorted=True, unique_indices=True
            )
        return frame[None, :]

    def lift(frame, slots=None):
        owned = frame[0, o0 : o0 + no]
        if not nh:
            return owned
        at = slice(run, run + nh) if run is not None else slots
        return jnp.concatenate([owned, frame[0, at]])

    fn, scope = (pack, SCOPE_PACK) if kind == "pack" else (lift, SCOPE_LIFT)
    of_a_part = _scoped(scope, fn)
    if mesh is None:
        layout.programs[key] = jax.jit(of_a_part)
        return layout.programs[key]
    spec = jax.sharding.PartitionSpec("parts")

    def of_all_parts(*operands):
        return jax.shard_map(
            of_a_part, mesh=mesh, in_specs=(spec,) * len(operands),
            out_specs=spec, check_vma=False,
        )(*operands)

    # the sharding named: of one part JAX would else call it replicated,
    # another sharding to the compiled solve than a staged frame's
    layout.programs[key] = jax.jit(
        of_all_parts, out_shardings=jax.sharding.NamedSharding(mesh, spec)
    )
    return layout.programs[key]


def _ghost_operands(layout: DeviceLayout, kind: str, parts: tuple, where):
    """The operands a `_vector_program` of ``kind`` takes for the ghosts
    of ``parts`` (one part, or all of them end to end), put on ``where``
    (the part's device, or the parts' sharding) once per layout and kept
    there: the slots in hid order for a lift, the permutation into slot
    order and the sorted slots for a pack. None where the ghosts are a
    run and read as a slice."""
    if layout.part_shapes[parts[0]][2] is not None:
        return ()
    key = (parts, where)
    if key not in layout.dev_hid_slots:
        slots = [layout.hid_slots[p] for p in parts]
        order = [
            np.argsort(hs, kind="stable").astype(INDEX_DTYPE) for hs in slots
        ]
        sorted_slots = [hs[o] for hs, o in zip(slots, order)]
        check(
            all(bool(np.all(np.diff(ss) > 0)) for ss in sorted_slots),
            "device layout: two ghosts of a part share a slot",
        )
        slots, order, sorted_slots = _jax().device_put(
            [np.concatenate(a) for a in (slots, order, sorted_slots)], where
        )
        layout.dev_hid_slots[key] = {
            "lift": (slots,), "pack": (order, sorted_slots),
        }
    return layout.dev_hid_slots[key][kind]


def _pack_on_device(v: PVector, layout: DeviceLayout, backend: TPUBackend,
                    with_ghosts: bool):
    """The ``(P, W)`` frame of ``v`` made on the devices: each part's
    values go to the part's device as the caller holds them (a view, no
    host copy), a `_vector_program` there writes them into the part's
    ``(1, W)`` row (one program over the mesh where the parts share a
    shape, else one a part and the rows assembled), under
    `backend.sharding`, the sharding the compiled solve takes. Nothing is
    waited for: the transfers read the caller's arrays after this
    returns, so they must stay as they are until the frame (or what was
    computed from it) is ready. `_run_krylov` waits for its solve;
    `DeviceVector.from_pvector` waits for the frame."""
    from .. import telemetry

    jax = _jax()
    P = layout.P
    devices, sharding = backend.part_devices(P), backend.sharding(P)
    dtype = _device_dtype(v.dtype)
    _note_narrowing(jax, v.dtype)
    isets = v.rows.partition.part_values()
    shapes = layout.part_shapes
    for iset, (no, nh, _run) in zip(isets, shapes):
        # the slots are the layout's: a vector of other index sets would
        # be written to wrong slots, and silently (a gather clamps)
        check(
            iset.num_oids == no and (not with_ghosts or iset.num_hids == nh),
            "device pack: the vector's parts are not the layout's",
        )
    if not with_ghosts:
        shapes = [(no, 0, None) for no, _nh, _run in shapes]
    with telemetry.annotate("pa:stage:put"):
        vals = jax.device_put(
            [
                np.asarray(vals)[: no + nh]
                for vals, (no, nh, _run) in zip(v.values.part_values(), shapes)
            ],
            devices,
        )
    with telemetry.annotate("pa:stage:pack"):
        if len(set(shapes)) == 1:
            no, nh, run = shapes[0]
            pack = _vector_program(
                layout, "pack", no, nh, dtype, run, backend.mesh(P)
            )
            data = pack(
                jax.make_array_from_single_device_arrays(
                    (P * (no + nh),), sharding, vals
                ),
                *(_ghost_operands(layout, "pack", tuple(range(P)), sharding)
                  if nh else ()),
            )
        else:
            rows = [
                _vector_program(layout, "pack", no, nh, dtype, run)(
                    vals[p],
                    *(_ghost_operands(layout, "pack", (p,), devices[p])
                      if nh else ()),
                )
                for p, (no, nh, run) in enumerate(shapes)
            ]
            data = jax.make_array_from_single_device_arrays(
                (P, layout.W), sharding, rows
            )
    _count_vector("packs", on_device=True)
    return data


def _pack_on_host(v: PVector, layout: DeviceLayout, backend: TPUBackend,
                  with_ghosts: bool):
    """The ``(P, W)`` frame of ``v`` filled on the host and staged whole:
    the path of parts that do not number their owned lids first, and of
    a run of several processes (`_stage` hands each its own rows)."""
    from .. import telemetry

    o0 = layout.o0
    with telemetry.annotate("pa:stage:pack"):
        stacked = np.zeros((layout.P, layout.W), dtype=v.dtype)
        for p, (iset, vals) in enumerate(
            zip(v.rows.partition.part_values(), v.values.part_values())
        ):
            vals = np.asarray(vals)
            stacked[p, o0 : o0 + iset.num_oids] = _owned(iset, vals)
            if with_ghosts:
                # hid_slots, not g0+hid: the box layout reorders the ghost
                # region into direction segments
                stacked[p, layout.hid_slots[p]] = _ghost(iset, vals)
    _count_vector("packs", on_device=False)
    return _stage(backend, stacked, layout.P)


def _pack(v: PVector, layout: DeviceLayout, backend: TPUBackend,
          with_ghosts: bool = True):
    """``v`` as a ``(P, W)`` device frame of ``layout``: owned entries at
    ``o0``, ghosts (where ``with_ghosts``) at the layout's ghost slots,
    zero elsewhere. On the device where `_parts_on_device_path`."""
    pack = _pack_on_device if _parts_on_device_path(v.rows) else _pack_on_host
    return pack(v, layout, backend, with_ghosts)


def _zero_frame(layout: DeviceLayout, backend: TPUBackend, dtype):
    """The frame of an all-zero vector, made on the devices."""
    from .. import telemetry
    import jax.numpy as jnp

    with telemetry.annotate("pa:stage:pack"):
        data = jnp.zeros(
            (layout.P, layout.W), _device_dtype(dtype),
            device=backend.sharding(layout.P),
        )
    _count_vector("packs", on_device=True)
    return data


def _lifts_on_device(data, rows: PRange, layout: DeviceLayout) -> bool:
    """Whether the frame ``data`` is lifted on the device: its vector is
    on the device path (`_parts_on_device_path`) and ``data`` is one
    ``(1, W)`` row a part."""
    shards = getattr(data, "addressable_shards", ())
    return (
        _parts_on_device_path(rows)
        and len(shards) == layout.P
        and all(s.data.shape == (1, layout.W) for s in shards)
    )


def _lift_on_device(data, layout: DeviceLayout, backend: TPUBackend) -> list:
    """Each part's values ``[owned | ghosts in hid order]`` taken out of
    its row of the frame ``data`` by a `_vector_program` on the row's
    device (one over the mesh where the parts share a shape): P device
    arrays in part order, dispatched and not waited for."""
    P = layout.P
    shapes = layout.part_shapes
    if len(set(shapes)) == 1:
        no, nh, run = shapes[0]
        lift = _vector_program(
            layout, "lift", no, nh, data.dtype, run, backend.mesh(P)
        )
        lifted = lift(
            data,
            *(_ghost_operands(
                layout, "lift", tuple(range(P)), backend.sharding(P)
            ) if nh else ()),
        )
        return [
            s.data for s in sorted(
                lifted.addressable_shards, key=lambda s: s.index[0].start or 0
            )
        ]
    lifted = [None] * P
    for s in data.addressable_shards:
        p = s.index[0].start or 0
        no, nh, run = shapes[p]
        lift = _vector_program(layout, "lift", no, nh, data.dtype, run)
        lifted[p] = lift(
            s.data,
            *(_ghost_operands(layout, "lift", (p,), s.device) if nh else ()),
        )
    return lifted


def _answer_to_host(out, rows: PRange, layout: DeviceLayout,
                    backend: TPUBackend):
    """A compiled solve's outputs on the host: the answer frame ``out[0]``
    as a PVector over ``rows``, and the rest as arrays. Where
    `_lifts_on_device`, the parts' values only are fetched, every
    transfer started before the first is read, and each fetched array
    becomes the caller's (`_as_callers_array`). Else the whole frame is
    fetched and lifted on the host (`_outputs_to_host`,
    `_host_frame_to_pvector`)."""
    from .. import telemetry

    on_device = _lifts_on_device(out[0], rows, layout)
    _count_vector("lifts", on_device)
    if not on_device:
        host = _outputs_to_host(out)
        return _host_frame_to_pvector(host[0], rows, layout), host[1:]
    with telemetry.annotate("pa:fetch:d2h"):
        arrays = _lift_on_device(out[0], layout, backend) + list(out[1:])
        for a in arrays:
            a.copy_to_host_async()
        host = [np.asarray(a) for a in arrays]
        # JAX keeps a fetched array on the `jax.Array` it came from: the
        # lifted parts go here, before their arrays change hands
        del arrays, a
    telemetry.bump("solve.fetched_bytes", sum(h.nbytes for h in host))
    with telemetry.annotate("pa:fetch:lift"):
        vals = [_as_callers_array(h) for h in host[: layout.P]]
        return PVector(rows.partition._like(vals), rows), host[layout.P :]


def _as_callers_array(fetched: np.ndarray) -> np.ndarray:
    """A fetched part as the array the caller gets: writable, owning its
    data, shared with nobody. Where the fetch made a fresh NumPy array of
    its own (the TPU runtime's transfer: ``owndata``), that array itself,
    made writable: JAX marks it read-only only to guard the copy it keeps
    on the `jax.Array`, and `_answer_to_host` has dropped that one (the
    lift's own temporary). This is what jax 0.9.0 does and no documented
    contract: `chip_smoke.py` holds a real fetched answer to it on the
    chip. Else (the CPU backend hands out a view of the device buffer)
    one copy."""
    if fetched.flags.owndata:
        fetched.flags.writeable = True
        return fetched
    return np.array(fetched)


class DeviceVector:
    """A PVector lowered to one (P, W) array sharded over the mesh."""

    __slots__ = ("data", "rows", "layout", "backend")

    def __init__(self, data, rows: PRange, layout: DeviceLayout, backend: TPUBackend):
        self.data = data
        self.rows = rows
        self.layout = layout
        self.backend = backend

    @classmethod
    def from_pvector(cls, v: PVector, backend: TPUBackend, layout=None) -> "DeviceVector":
        layout = layout or device_layout(v.rows, _padded_for(backend))
        # waited for: a put reads the caller's arrays after it returns,
        # and who stages a vector by hand may change it next
        data = _jax().block_until_ready(_pack(v, layout, backend))
        return cls(data, v.rows, layout, backend)

    def to_pvector(self) -> PVector:
        return _answer_to_host(
            [self.data], self.rows, self.layout, self.backend
        )[0]


def _host_frame_to_pvector(host: np.ndarray, rows: PRange, layout) -> PVector:
    """A fetched (P, W) host frame lifted back to a PVector: the host
    path of `_answer_to_host`, and the multi-RHS block unstaging, which
    fetches one (P, W, K) slab and lifts each column."""
    from .. import telemetry

    o0 = layout.o0
    vals = []
    with telemetry.annotate("pa:fetch:lift"):
        for p, iset in enumerate(rows.partition.part_values()):
            owned = host[p, o0 : o0 + iset.num_oids]
            ghost = host[p, layout.hid_slots[p]]
            if iset.owned_first:
                v = np.concatenate([owned, ghost])
            else:
                v = np.empty(iset.num_lids, dtype=host.dtype)
                v[np.asarray(iset.oid_to_lid)] = owned
                v[np.asarray(iset.hid_to_lid)] = ghost
            vals.append(v)
        parts = rows.partition
        return PVector(parts._like(vals), rows)


def _padded_for(backend: TPUBackend) -> bool:
    """Real TPUs get the padded (kernel-frame) layout; host/CPU meshes the
    compact one."""
    return backend.devices()[0].platform == "tpu"


def _stream_kernel_for(backend: TPUBackend) -> bool:
    """Real TPUs stream an operator's stored diagonals through the Mosaic
    kernel (`ops/pallas_dia.py:dia_spmv_pallas`); host/CPU meshes take the
    XLA shifted-slice form of the same sum."""
    return backend.devices()[0].platform == "tpu"


def _box_exchange_enabled() -> bool:
    """The slice-based box exchange (tpu_box.py), default ON. Strict-bits
    keeps the generic plan: the box 'add' path accumulates ghost
    contributions in direction order, not the host assemble's edge
    order, so its bits can differ on multiply-received cells. ABFT mode
    also keeps the generic plan this round — its per-round slab
    checksums are implemented on the index-plan body (the box slices
    would need per-variant checksum lanes; same precedent as
    strict-bits, noted in docs/resilience.md)."""
    from .health import abft_enabled

    return (
        os.environ.get("PA_TPU_BOX", "1") != "0"
        and not strict_bits()
        and not abft_enabled()
    )


def _plan_verify_enabled() -> bool:
    """One-helper-per-mode indirection for ``PA_PLAN_VERIFY`` (the
    literal read lives in `analysis.plan_verifier.plan_verify_enabled`
    so the build-site gate and the CLI resolve it identically). A
    validation toggle: the verifier raises or passes, it never changes
    which plan is built or what stages."""
    from ..analysis.plan_verifier import plan_verify_enabled

    return plan_verify_enabled()


def _fused_cg_enabled() -> bool:
    """The fused streaming CG body (one-sweep
    x/r updates + shared-gather dot partials, direction fold riding the
    SpMV pass — see `make_cg_fn`), default ON. Strict-bits keeps the
    standard body as the bit-exact oracle; strict tests opt back in
    explicitly via ``make_cg_fn(..., fused=True)`` to pin trajectory
    identity. ``PA_TPU_FUSED_CG=0`` reverts to the standard body."""
    return os.environ.get("PA_TPU_FUSED_CG", "1") != "0" and not strict_bits()


def _resolve_fused(fused) -> bool:
    """The ONE resolution of the CG body choice: an explicit ``fused``
    wins; ``None`` takes the env default. Every layer (`tpu_cg`, the
    program cache key, `make_cg_fn`) resolves through here so the
    compiled program, the cache key, and the reported ``cg_body`` can
    never disagree."""
    if fused is None:
        return _fused_cg_enabled()
    return bool(fused)


def _trace_config() -> int:
    """The ONE resolution of the device α/β trace-ring depth
    (``PA_TRACE_ITERS``, default 0 = off). A nonzero depth adds a
    ``(depth, 2)`` ring to the compiled CG while-carry — alpha/beta per
    committed iteration, downloaded once at solve exit — so the flag is
    LOWERING-affecting and this helper is a registered env-key site
    (analysis.env_lint.KEY_SITES): `_krylov_fn_for` folds its value
    into the compiled-program cache key and `make_cg_fn` resolves the
    depth through this same function, so the traced program and its
    cache key can never disagree. Depth 0 builds the exact
    pre-telemetry program (the HLO-identity pin in
    tests/test_telemetry.py). The ring carries NO collectives: scalars
    already replicated by the existing dot gathers are written into a
    replicated carry."""
    try:
        v = int(os.environ.get("PA_TRACE_ITERS", "0") or "0")
    except ValueError:
        raise ValueError(
            "PA_TRACE_ITERS must be an integer trace depth (iterations)"
        )
    return max(0, v)


def _sdc_config(maxiter: int) -> Optional[dict]:
    """Build-time resolution of the in-graph SDC defense for the
    compiled CG bodies — None when inactive (``PA_TPU_ABFT`` off and no
    audit period), in which case the builders emit exactly the pre-SDC
    program. Active config carries: ``abft`` (checksum lanes on),
    ``ae`` (audit period in real iterations), ``R``/``mrb`` (ring depth
    and rollback budget), the graph-injection clause (`PA_FAULT_DEVICE`,
    the compiled loop's chaos seam), and ``trip_max`` — the static bound
    on while-loop trips: real iterations + audit stall-trips + the
    worst-case replay budget of ``mrb`` rollbacks (each rewinds at most
    R·ae iterations, or to the start when audits are off)."""
    from .faults import device_fault_clause
    from .health import audit_every, max_rollbacks, rollback_depth

    abft = _abft_enabled()
    ae = audit_every()
    if not abft and ae <= 0:
        return None
    R = rollback_depth()
    mrb = max_rollbacks()
    fault = device_fault_clause()
    audits = (maxiter // ae + 2) if ae > 0 else 0
    replay = (R * ae + 2) if ae > 0 else maxiter + 1
    return {
        "abft": abft,
        "ae": ae,
        "R": R,
        "mrb": mrb,
        "fault": fault,
        # clamped: the trip counter is an int32 loop carry
        "trip_max": int(
            min(maxiter + audits + (mrb + 1) * replay, 2**31 - 1)
        ),
        # tolerance env strings join the program cache key so an
        # override retraces instead of serving a stale threshold
        "key": (
            abft, ae, R, mrb,
            os.environ.get("PA_TPU_ABFT_TOL", ""),
            os.environ.get("PA_HEALTH_AUDIT_TOL", ""),
            tuple(sorted(fault.items())) if fault else None,
        ),
    }


def _sdc_tolerances(dtype, P: int, no_max: int):
    """Trace-time detection thresholds. The SpMV checksum compares two
    n-term f.p. sums, whose rounding grows ~ sqrt(n)·eps of the term
    magnitude — the relative threshold scales with sqrt(P·no_max) (100x
    headroom; ``PA_TPU_ABFT_TOL`` overrides with an absolute relative
    threshold). Corruption below it is inside the solve's own rounding
    noise — the audit tier catches what accumulates, and what never
    accumulates was harmless. The audit threshold is the host
    `audit_tolerance` (drift relative to the initial residual norm)."""
    from .health import audit_tolerance

    v = os.environ.get("PA_TPU_ABFT_TOL")
    if v:
        cs_tol = float(v)
    else:
        cs_tol = 100.0 * float(np.finfo(np.dtype(dtype)).eps) * float(
            np.sqrt(max(1, P * no_max))
        )
    return cs_tol, audit_tolerance(dtype)


class ELLFootprintError(RuntimeError):
    """The generic padded-ELL lowering was refused: its per-row gather
    program at this operator size is past the footprint ceiling that has
    faulted a real TPU worker (the 64^3-node tet-elasticity operator,
    786432 rows: the ELL program faulted the device, SD and BSR on the
    same operator ran). Raised INSTEAD of staging the program, so no
    documented env-flag combination can reach the device-fault path."""


#: Ceiling on the padded-ELL A_oo gather footprint (``no_max * L_oo``
#: elements per part). The generic ELL SpMV gathers element-at-a-time;
#: past this scale its gather kernels have faulted the TPU worker
#: outright (isolated by probe at the 64^3 tet-elasticity operator —
#: 786432 rows at mean width 35.5, so the padded footprint is >= 28M
#: elements; SD and BSR on the same operator are fine). The ceiling sits
#: between the largest ELL program ever measured healthy (32^3, ~6M
#: padded elements) and that fault's proven lower bound, conservative
#: side. Override with PA_TPU_ELL_MAX_GATHER; PA_TPU_ELL_GUARD=0
#: disables the guard, =1 enforces it even off-TPU (CPU meshes only
#: WARN by default — they are slow there, not unsafe).
ELL_MAX_GATHER = int(2.5e7)


def _ell_guard_env() -> tuple:
    """The ONE resolution of the padded-ELL admission guard's env pair
    (the one-helper-per-mode rule: the staging-admission site and the
    cache-key site must never disagree): ``(mode, ceiling)`` with the
    ceiling NORMALIZED to an int — so spelling the default explicitly
    (``PA_TPU_ELL_MAX_GATHER=25000000`` vs ``2.5e7`` vs unset) yields
    the same key and does not spuriously invalidate compiled-program
    caches."""
    mode = os.environ.get("PA_TPU_ELL_GUARD", "auto")
    raw = os.environ.get("PA_TPU_ELL_MAX_GATHER")
    if raw in (None, ""):
        ceiling = ELL_MAX_GATHER
    else:
        try:
            ceiling = int(float(raw))
        except (ValueError, OverflowError):
            # unparseable (or inf — int(float("inf")) raises
            # OverflowError): key on the raw string (each distinct
            # spelling still rekeys); only the ACTIVE guard site turns this
            # into an error — with the guard disabled the knob stays
            # ignored, as it always was
            ceiling = raw
    return mode, ceiling


def _ell_guard_check(P: int, no_max: int, L_oo: int, backend) -> None:
    """Refuse (real TPU) or warn (host mesh) when the padded-ELL gather
    footprint is past the device-fault ceiling. Called by the lowering
    BEFORE the ELL arrays are built, whether ELL was auto-selected (every
    fast path declined) or forced by strict-bits mode."""
    mode, ceiling = _ell_guard_env()
    if mode == "0":
        return
    if isinstance(ceiling, str):
        raise ValueError(
            f"PA_TPU_ELL_MAX_GATHER={ceiling!r} is not a finite integer "
            "and the ELL guard is active — fix the override or set "
            "PA_TPU_ELL_GUARD=0"
        )
    footprint = int(no_max) * int(L_oo)
    if footprint <= ceiling:
        return
    why = (
        "strict-bits mode forces the pure-ELL lowering"
        if strict_bits()
        else "every fast-path lowering (DIA/SD/BSR) declined this operator"
    )
    msg = (
        f"padded-ELL lowering refused: gather footprint no_max*L = "
        f"{no_max}*{L_oo} = {footprint} elements/part exceeds the "
        f"device-fault ceiling {ceiling} (P={P}). {why}. The generic ELL "
        "gather program at this scale has faulted TPU workers outright. "
        "Options: relax the operator so a fast path engages "
        "(PA_TPU_SD=1 / PA_TPU_BSR=1, node-block-aligned dofs), drop "
        "PA_TPU_STRICT_BITS for this size, run on the host backend, or "
        "raise PA_TPU_ELL_MAX_GATHER explicitly if your worker tolerates "
        "it."
    )
    on_tpu = backend.devices()[0].platform == "tpu"
    if on_tpu or mode == "1":
        raise ELLFootprintError(msg)
    import warnings

    warnings.warn(
        "partitionedarrays_jl_tpu: " + msg + " (host mesh: continuing — "
        "slow but safe)",
        stacklevel=3,
    )


def device_layout(rows: PRange, padded: bool = False) -> DeviceLayout:
    from .tpu_box import box_structure

    cache = getattr(rows, "_device_layout", None)
    if cache is None:
        cache = rows._device_layout = {}
    box = _box_exchange_enabled()
    key = (padded, box)
    if key not in cache:
        info = box_structure(rows) if box else None
        cache[key] = DeviceLayout(rows, padded, box_info=info)
    return cache[key]


def device_exchange_plan(rows: PRange, padded: bool = False):
    """Build (and cache on ``rows``) the device halo-exchange plan: the
    slice-based `BoxExchangePlan` where the layout found a box structure
    (`device_layout`), the index-vector `DeviceExchangePlan` otherwise."""
    from .tpu_box import BoxExchangePlan

    cache = getattr(rows, "_device_plan", None)
    if cache is None:
        cache = rows._device_plan = {}
    layout = device_layout(rows, padded)
    key = (padded, layout.box_info is not None)
    if key not in cache:
        if layout.box_info is not None:
            plan = BoxExchangePlan(layout, layout.box_info)
        else:
            plan = DeviceExchangePlan(rows.exchanger, layout)
        if _plan_verify_enabled():
            # opt-in construction-time soundness gate (PA_PLAN_VERIFY=1):
            # a malformed plan raises the typed PlanSoundnessError HERE,
            # before any program is lowered from it — zero cost when off,
            # and never mutates the plan (analysis.plan_verifier)
            from ..analysis.plan_verifier import check_plan

            check_plan(plan, context="device_exchange_plan")
        cache[key] = plan
    return cache[key]


class OhSlab(NamedTuple):
    """One class of the face-slab form of A_oh
    (`DeviceMatrix._detect_oh_slabs`), all static: the ghost segment's
    offset into the ghost region and its slab shape, the class's sub-box
    of that slab and of the owned box (one shape, two corners), and where
    its coefficients start in the staged ``(P, dense)`` array."""

    seg: int
    slab: Tuple[int, ...]
    ghost_lo: Tuple[int, ...]
    row_lo: Tuple[int, ...]
    shape: Tuple[int, ...]
    v0: int


class DeviceMatrix:
    """A PSparseMatrix lowered to stacked padded-ELL blocks in HBM:
    A_oo and A_oh as (P, no_max, L) val/col arrays, cols indexing the
    (P, W) vector slots. The owned/ghost split keeps the overlap structure
    of the reference SpMV (src/Interfaces.jl:2246-2275) visible to XLA."""

    __slots__ = (
        "oo_vals", "oo_cols", "oh_vals", "oh_cols", "oh_rows", "oh_nnz",
        "dia_offsets", "dia_vals", "pallas_plan",
        "dia_mode", "dia_cb", "dia_no", "dia_codes", "dia_kk", "dia_code_row",
        "dia_cls_pattern",
        "bsr_cols", "bsr_vals", "bsr_bs",
        "sd_idx", "sd_vals", "sd_g", "sd_bs",
        "ohb_rows", "ohb_cols", "ohb_vals", "ohb_bs",
        "ohs_vals", "ohs_geo",
        "abft_w",
        "rows", "cols", "row_layout", "col_layout", "col_plan", "backend",
        "padded", "flops_per_spmv", "_cg_cache", "_ops_cache",
    )

    #: Accept the node-block BSR lowering when the dense bs x bs blocks
    #: are at least this full (irregular FE operators with vector dofs —
    #: e.g. 3-D elasticity — are ~100% full; scalar operators fall well
    #: below and stay on ELL).
    BSR_MIN_FILL = 0.6

    #: Use the diagonal (DIA) fast path when the union of A_oo band offsets
    #: across parts is at most this. TPUs have no fast random-gather unit —
    #: a generic ELL gather runs element-at-a-time — but a banded SpMV is a
    #: sum of rolled slices, pure VPU streaming at HBM bandwidth. Stencil
    #: operators (FDM/FVM) are exactly this shape.
    DIA_MAX_OFFSETS = 64

    #: Use the coded-diagonal SpMV when every A_oo diagonal draws its
    #: values from at most this many distinct floats (per part). Bounds
    #: the in-kernel decode select chain; genuinely variable-coefficient
    #: operators exceed it and take the streaming path instead.
    CODE_MAX_VALUES = 8

    #: Row-class cap of the fused (dense-DIA-free) band analysis. The
    #: kernel probes the previous row's class first (C-order runs), so
    #: the cap bounds only the rare class-change scan; 64 covers the
    #: decoupled-Dirichlet stencil family (3^d interior adjacency
    #: variants + identity) with headroom. Operators with more distinct
    #: row tuples fall back to the dense-diagonal detection path. Note
    #: this is an ANALYSIS cap only — the row-class COMPRESSION mode
    #: still requires <= CODE_MAX_VALUES classes, as before.
    _CLS_CAP = 64

    def __init__(self, A: PSparseMatrix, backend: TPUBackend, padded=None):
        # the whole lowering under `pa:lower` and its three leaves, timed
        # for the record of the solve that pays it and counted always
        with _LowerSpans() as low:
            self._lower(A, backend, padded, low)

    def _lower(self, A, backend, padded, low) -> None:
        from ..ops.sparse import CSRMatrix, ELLMatrix
        from .. import native

        jax = _jax()
        isets = A.rows.partition.part_values()
        P = len(isets)
        noids = np.array([i.num_oids for i in isets], dtype=np.int64)
        no_max = int(noids.max()) if P else 0
        dt = A.dtype
        # strict-bits mode forces the pure-ELL lowering: its two-phase
        # (A_oo fold, then A_oh fold added) left-to-right accumulation is
        # the exact order of the host csr_spmv + mul_into pair, whereas
        # the DIA kernels sum in frame-offset order, which interleaves
        # ghost terms on boundary rows (equal only to rounding)
        det = None
        oo = oh = None
        full = A.values.part_values()
        if (
            not strict_bits()
            and A._blocks is None
            and all(
                full[p].shape[0] == int(noids[p]) for p in range(P)
            )
        ):
            # NO-SPLIT fast path (round 4): analyze the band structure
            # straight off the full (column-sorted, owned-first) local
            # CSRs — each part's sorted ghost tail is skipped by column
            # limit — and extract only the surface-sized A_oh side. The
            # owned/ghost block split it avoids materializes a second
            # full copy of the operator in fresh pages (~65 s of the
            # 1e8-DOF assembly+lowering on the slow-fault bench host).
            with low.leaf("detect"):
                det = self._detect_dia(
                    A, full, P, noids, no_max, np.dtype(dt).itemsize,
                    col_limits=noids, fused_only=True,
                )
            if det is not None:
                oh = []
                for p in range(P):
                    M = full[p]
                    res = native.csr_extract_hi(
                        M.indptr, M.indices, M.data, M.shape[0],
                        int(noids[p]),
                    )
                    if res is None:
                        oh = None
                        break
                    ip_hi, c_hi, v_hi = res
                    oh.append(
                        CSRMatrix(
                            ip_hi, c_hi, v_hi,
                            (M.shape[0], M.shape[1] - int(noids[p])),
                        )
                    )
                if oh is None:
                    det = None
        if det is None:
            oo = A.owned_owned_values.part_values()
            oh = A.owned_ghost_values.part_values()
            if not strict_bits():
                with low.leaf("detect"):
                    det = self._detect_dia(
                        A, oo, P, noids, no_max, np.dtype(dt).itemsize
                    )
        if padded is None:
            # the padded vector frame only pays off when the in-frame coded
            # kernel can actually run; otherwise stay compact even on TPU
            padded = _padded_for(backend) and det is not None and det["pplan"] is not None
        self.padded = bool(padded)
        row_layout = device_layout(A.rows, self.padded)
        col_layout = device_layout(A.cols, self.padded)
        check(row_layout.no_max == no_max, "rows layout mismatch")
        self.rows, self.cols = A.rows, A.cols
        self.row_layout, self.col_layout = row_layout, col_layout
        self.col_plan = device_exchange_plan(A.cols, self.padded)
        _count_exchange_plan(self.col_plan)
        _count_box_plan(self.col_plan)
        self.backend = backend
        L_oh = max((int(m.row_lengths().max()) if m.nnz else 0 for m in oh), default=0)
        L_oh = max(L_oh, 1)
        self.flops_per_spmv = 2 * (
            sum(m.nnz for m in full)
            if oo is None
            else sum(oo[p].nnz + oh[p].nnz for p in range(P))
        )
        self.bsr_cols = self.bsr_vals = self.bsr_bs = None
        self.sd_idx = self.sd_vals = self.sd_g = self.sd_bs = None
        if det is None:
            with low.leaf("detect"):
                sd = self._detect_sd(oo, P, noids, no_max, dt)
            if sd is not None:
                self.sd_bs = sd["bs"]
                self.sd_g = sd["G"]
                # one staged (idx, vals) pair per width bucket
                self.sd_idx = tuple(
                    low.upload(backend, c["idx"], P) for c in sd["chunks"]
                )
                self.sd_vals = tuple(
                    low.upload(backend, c["vals"], P) for c in sd["chunks"]
                )
                _count_sd_lowering(sd, sum(m.nnz for m in oo))
            else:
                with low.leaf("detect"):
                    bsr = self._detect_bsr(oo, P, noids, no_max, dt)
                if bsr is not None:
                    self.bsr_bs = bsr["bs"]
                    self.bsr_cols = low.upload(backend, bsr["cols"], P)
                    self.bsr_vals = low.upload(backend, bsr["vals"], P)
        if det is None and self.bsr_bs is None and self.sd_bs is None:
            # pure-ELL path: the only mode whose compiled program reads
            # the O(N x row_width) oo value/col arrays — banded operators
            # (coded or streamed DIA) skip this build and staging entirely
            L_oo = max(
                (int(m.row_lengths().max()) if m.nnz else 0 for m in oo),
                default=0,
            )
            L_oo = max(L_oo, 1)
            # device-fault guard:
            # the library must never stage an ELL gather program past the
            # footprint that faults real TPU workers — neither by
            # auto-selection nor forced by strict-bits
            _ell_guard_check(P, no_max, L_oo, backend)
            oo_vals = np.zeros((P, no_max, L_oo))
            oo_cols = np.full(
                (P, no_max, L_oo), col_layout.trash, dtype=INDEX_DTYPE
            )
            for p in range(P):
                Eoo = ELLMatrix.from_csr(oo[p], row_width=L_oo)
                m = Eoo.vals.shape[0]
                oo_vals[p, :m] = Eoo.vals
                # ELL pad cols are 0 with val 0 — safe: o0 is a real slot
                oo_cols[p, :m] = col_layout.o0 + Eoo.cols
            self.oo_vals = low.upload(backend, oo_vals.astype(dt), P)
            self.oo_cols = low.upload(backend, oo_cols, P)
        else:
            self.oo_vals = self.oo_cols = None
        # A_oh, compact boundary-row form. Only rows touching the ghost
        # layer carry entries — a surface set (~n^2 of n^3 rows for a 3-D
        # stencil). TPU gathers run element-at-a-time, so gathering per
        # boundary row instead of per owned row is the difference between
        # O(surface) and O(volume) serial work; an empty block (single
        # part, or interior-only coupling) skips the gather entirely.
        self.oh_nnz = sum(m.nnz for m in oh)
        self.ohb_rows = self.ohb_cols = self.ohb_vals = self.ohb_bs = None
        self.ohs_vals = self.ohs_geo = None
        self.oh_vals = self.oh_cols = self.oh_rows = None
        self._cg_cache = {}
        self._ops_cache = None
        ohb = None
        if self.oh_nnz and (self.sd_bs or self.bsr_bs):
            # round-4 directive 7: the boundary block blocks the same
            # way as A_oo — ghost dofs arrive node-triple-contiguous
            with low.leaf("detect"):
                ohb = self._detect_oh_blocks(
                    A, oh, P, self.sd_bs or self.bsr_bs, row_layout,
                    col_layout, dt,
                )
        if ohb is not None:
            # one staged (rows, cols, vals) triple per width bucket —
            # the same per-bucket padding the owned SD groups get
            self.ohb_bs = ohb["bs"]
            self.ohb_rows = tuple(
                low.upload(backend, c["rows"], P) for c in ohb["chunks"]
            )
            self.ohb_cols = tuple(
                low.upload(backend, c["cols"], P) for c in ohb["chunks"]
            )
            self.ohb_vals = tuple(
                low.upload(backend, c["vals"], P) for c in ohb["chunks"]
            )
            _count_oh_lowering(
                self.oh_nnz,
                block_entries=sum(c["vals"].size for c in ohb["chunks"]),
            )
        # a box layout keeps the ghosts of a direction in the sender's
        # scan order: where the boundary block is made of face slabs its
        # rows need no index at all
        ohs = None
        if self.oh_nnz and ohb is None:
            with low.leaf("detect"):
                ohs = self._detect_oh_slabs(A, oh, P, col_layout, dt)
        if ohs is not None:
            self.ohs_geo = ohs["geo"]
            self.ohs_vals = low.upload(backend, ohs["vals"], P)
            _count_oh_lowering(self.oh_nnz, slabs=ohs["geo"])
        elif ohb is None and self.oh_nnz:
            nb_max = max(
                (int(np.count_nonzero(m.row_lengths())) for m in oh),
                default=0,
            )
            nb_max = max(nb_max, 1)
            # pad slots target the ROW frame's trash slot — the SpMV
            # result lives in the row layout, whose width can be smaller
            # than the column frame's for rectangular operators
            oh_rows = np.full(
                (P, nb_max), row_layout.trash, dtype=INDEX_DTYPE
            )
            oh_vals = np.zeros((P, nb_max, L_oh))
            oh_cols = np.full(
                (P, nb_max, L_oh), col_layout.trash, dtype=INDEX_DTYPE
            )
            for p in range(P):
                br = np.nonzero(oh[p].row_lengths())[0]
                if len(br):
                    Eoh = ELLMatrix.from_csr(oh[p], row_width=L_oh)
                    oh_rows[p, : len(br)] = row_layout.o0 + br
                    oh_vals[p, : len(br)] = Eoh.vals[br]
                    # hid -> slot through the layout map (the box layout
                    # reorders ghosts into direction segments); ELL pad
                    # cols are hid 0 with value 0 — a real slot, safe
                    oh_cols[p, : len(br)] = col_layout.hid_slots[p][
                        Eoh.cols[br]
                    ]
            self.oh_vals = low.upload(backend, oh_vals.astype(dt), P)
            self.oh_cols = low.upload(backend, oh_cols, P)
            self.oh_rows = low.upload(backend, oh_rows, P)
            _count_oh_lowering(self.oh_nnz, ell_entries=oh_vals.size)

        # ABFT checksum row: w = 1ᵀA per part over the local COLUMN
        # frame, precomputed once per lowering — the compiled CG then
        # verifies c·(A x) against (c·A)·x = w·x each iteration with two
        # reduction lanes that ride the existing dot all_gather
        # (_pdot_extra_factory). Staged in f64 when available: the
        # checksum's own rounding is the detection floor.
        self.abft_w = None
        if _abft_enabled():
            wdt = np.float64 if jax.config.jax_enable_x64 else dt
            self.abft_w = low.upload(
                backend,
                self._abft_checksum_row(
                    A, oo, oh, full, P, noids, col_layout
                ).astype(wdt),
                P,
            )

        self.dia_mode = None
        self.dia_offsets = None
        self.pallas_plan = None
        self.dia_cb = self.dia_no = self.dia_codes = None
        self.dia_kk = self.dia_code_row = None
        self.dia_cls_pattern = None
        self.dia_vals = None  # set by the streaming-DIA staging below
        if det is None:
            return
        from ..ops.pallas_dia import LANES, plan_dia_pallas

        offsets, dia, uniq, kk = det["offsets"], det["dia"], det["uniq"], det["kk"]
        code_row, coded, Dc = det["code_row"], det["coded"], det["Dc"]
        D = len(offsets)
        self.dia_offsets = offsets
        if det["coded_ok"] and not (self.padded and det["pplan"] is None):
            pplan = det["pplan"] if self.padded else None
            if pplan is not None:
                # the kernel frame and the vector layout are derived
                # independently (ops/pallas_dia.py:plan_dia_padded vs
                # DeviceLayout) — they must agree exactly or the kernel
                # would read ghosts as halo zeros / mask the wrong rows
                check(
                    pplan["o0"] == row_layout.o0
                    and pplan["g0"] == row_layout.g0
                    and pplan["o0"] == col_layout.o0,
                    "padded-frame geometry drifted between plan and layout",
                )
            self.dia_mode = "coded"
            self.dia_kk = kk
            self.dia_code_row = tuple(code_row)
            self.pallas_plan = pplan
            kmax = max(kk)
            cls_uniq, cls_ids = det["cls_uniq"], det["cls_ids"]
            cb = np.zeros((P, D, kmax))
            for p in range(P):
                for d in range(D):
                    if cls_uniq is not None and code_row[d] >= 0:
                        # class mode: slot k of diagonal d = d's value in
                        # row class k of this part
                        u = cls_uniq[p][:, d]
                    else:
                        u = uniq[p][d]
                    if len(u) == 0:
                        u = np.zeros(1)
                    cb[p, d, : len(u)] = u
                    cb[p, d, len(u):] = u[0]
            nlen = pplan["code_len"] if pplan is not None else no_max
            n_streams = 1 if cls_uniq is not None else max(Dc, 1)
            codes = np.zeros((P, n_streams, nlen), dtype=np.uint8)
            if cls_uniq is not None:
                codes[:, 0, :no_max] = cls_ids
            elif dia is None:
                # fused analysis: per-diagonal codes via the tiny
                # class->code map composed with the per-row class ids
                # (identical values to the dense searchsorted below —
                # dia[p, d, r] IS cls_tables[p][cls_codes[p, r], d]).
                # Rows past a part's noids stay code 0; they are masked
                # by dia_no in the kernel either way.
                for p in range(P):
                    n_o = int(noids[p])
                    for j, d in enumerate(coded):
                        u = uniq[p][d]
                        if len(u):
                            m_ = np.clip(
                                np.searchsorted(
                                    u, det["cls_tables"][p][:, d]
                                ),
                                0,
                                len(u) - 1,
                            ).astype(np.uint8)
                            codes[p, j, :n_o] = m_[
                                det["cls_codes"][p, :n_o]
                            ]
            else:
                for p in range(P):
                    for j, d in enumerate(coded):
                        u = uniq[p][d]
                        if len(u):
                            codes[p, j, :no_max] = np.clip(
                                np.searchsorted(u, dia[p, d]), 0, len(u) - 1
                            )
            if pplan is not None:
                from ..ops.pallas_dia import pack_nibble_codes

                packed = pack_nibble_codes(codes)
                codes = packed.reshape(
                    P, packed.shape[1], nlen // LANES, LANES
                )
            else:
                codes = codes.view(np.int8)
            # row-class fast path (see ops/pallas_dia.py:_padded_kernel):
            # per-class static nonzero masks over the diagonals. A slot is
            # skippable only when zero in EVERY part (one compiled program
            # serves all shards); K is capped so the K live accumulator
            # blocks stay within VMEM pressure limits.
            self.dia_cls_pattern = None
            if (
                cls_uniq is not None
                and 1 < kmax <= 4
                and os.environ.get("PA_TPU_CLASS_ACC", "1") != "0"
            ):
                self.dia_cls_pattern = tuple(
                    tuple(bool(np.any(cb[:, d, k] != 0)) for d in range(D))
                    for k in range(kmax)
                )
            self.dia_cb = low.upload(backend, cb.astype(dt), P)
            self.dia_no = low.upload(
                backend, noids.astype(np.int32).reshape(P, 1), P
            )
            self.dia_codes = low.upload(backend, codes, P)
            if pplan is not None:
                _count_coded_lowering(
                    pplan, _pfold_fits(self), _fused_cg_enabled()
                )
        else:
            self.dia_mode = "stream"
            if dia is None:
                # fused analysis skipped the dense diagonals, but this
                # branch (explicit padded=True with no padded plan) needs
                # them as the staging source — rebuild here (review r4).
                # The no-split path also skipped the block split; this
                # rare branch materializes it (correctness over speed)
                from .. import native as _native

                if oo is None:
                    oo = A.owned_owned_values.part_values()
                off_arr = np.array(offsets)
                dia = np.zeros((P, D, no_max))
                for p in range(P):
                    M = oo[p]
                    if M.nnz and not _native.dia_fill(
                        M.indptr, M.indices, M.data, M.shape[0], off_arr,
                        dia[p],
                    ):
                        r = M.row_of_nz()
                        d_ = np.searchsorted(
                            off_arr, M.indices.astype(np.int64) - r
                        )
                        dia[p, d_, r] = M.data
            self.pallas_plan = (
                plan_dia_pallas(offsets, no_max, itemsize=np.dtype(dt).itemsize)
                if _stream_kernel_for(backend)
                else None
            )
            if self.pallas_plan is not None:
                R = self.pallas_plan["n_rows"]
                dia_stage = np.zeros((P, D, R * LANES))
                dia_stage[:, :, :no_max] = dia
                dia_stage = dia_stage.reshape(P, D, R, LANES)
            else:
                dia_stage = dia
            dia_stage = dia_stage.astype(dt)
            _count_stream_lowering(dia_stage, self.pallas_plan)
            self.dia_vals = low.upload(backend, dia_stage, P)

    @staticmethod
    def _abft_checksum_row(A, oo, oh, full, P, noids, col_layout):
        """Per-part column sums of the owned-row block, placed at their
        frame slots: ``w[p, slot(j)] = Σ_i A_p[i, j]`` over part p's
        owned rows i — the staged ``(c·A)`` row of the ABFT identity
        ``c·(A x) == (c·A)·x`` with c the all-ones vector. Works off
        whichever host form this lowering kept: the oo/oh owned/ghost
        block split (oid-/hid-indexed columns), or the no-split full
        local CSRs (lid columns, mapped through the cols IndexSet so
        non-owned-first layouts stay correct). Accumulated in f64: the
        row is computed once, its accuracy bounds the detection floor."""
        W = col_layout.W
        w = np.zeros((P, W), dtype=np.float64)
        col_isets = A.cols.partition.part_values()
        for p in range(P):
            iset = col_isets[p]
            if oo is not None:
                M = oo[p]
                if M.nnz:
                    w[p, col_layout.o0 : col_layout.o0 + M.shape[1]] += (
                        np.bincount(
                            M.indices,
                            weights=M.data.astype(np.float64),
                            minlength=M.shape[1],
                        )
                    )
                Mh = oh[p]
                if Mh.nnz:
                    np.add.at(
                        w[p],
                        col_layout.hid_slots[p],
                        np.bincount(
                            Mh.indices,
                            weights=Mh.data.astype(np.float64),
                            minlength=len(col_layout.hid_slots[p]),
                        ),
                    )
            else:
                M = full[p]  # owned rows only (the no-split invariant)
                if not M.nnz:
                    continue
                lid2slot = np.full(iset.num_lids, col_layout.trash)
                lid2slot[np.asarray(iset.oid_to_lid)] = (
                    col_layout.o0 + np.arange(iset.num_oids)
                )
                lid2slot[np.asarray(iset.hid_to_lid)] = col_layout.hid_slots[p]
                colsum = np.bincount(
                    M.indices,
                    weights=M.data.astype(np.float64),
                    minlength=iset.num_lids,
                )
                np.add.at(w[p], lid2slot, colsum)
        # the trash slot absorbs masked scatter lanes and must stay an
        # exact zero in every staged operand
        w[:, col_layout.trash] = 0.0
        return w

    #: Node rows per supernode group of the SD lowering (the MXU tile's
    #: row extent is G*bs = 192 at bs=3 — a multiple of the 128x128 MXU
    #: with decent utilization, and big enough that Morton-local column
    #: reuse shrinks the gathered union well below G * mean-degree).
    SD_GROUP = 64

    #: HBM budget for the densified group blocks, summed over parts.
    SD_MAX_BYTES = int(2.5e9)

    #: Width buckets for the SD lowering: contiguous group ranges padded
    #: to their own union maximum (one einsum per bucket) instead of one
    #: global width — see _detect_sd (round-5 directive 3).
    SD_BUCKETS = 8

    @classmethod
    def _detect_sd(cls, oo, P, noids, no_max, dt):
        """Supernode-dense lowering for irregular node-block operators
        (round-4 directive 2): group G consecutive (Morton-ordered) node
        rows, densify each group's rows over its EXACT column union
        (self nodes first — they arrive by reshape, not gather — then
        the sorted external neighbors), and run SpMV as one batched
        (G*bs x U*bs) @ (U*bs) einsum per group on the MXU. The gather
        count drops from nnz/bs^2 block gathers (BSR) to the per-group
        external unions — ~4x fewer on the tet-elasticity benchmark —
        which is the whole cost on a TPU (gathers are element-at-a-time;
        the dense FLOPs are MXU noise). Declines to BSR/ELL when blocks
        aren't dense enough, the densified values blow the HBM budget,
        or the union sharing is too weak to pay for the padding."""
        if strict_bits() or os.environ.get("PA_TPU_SD", "1") == "0":
            return None
        nnz = sum(m.nnz for m in oo)
        if nnz == 0:
            return None
        G = cls.SD_GROUP
        for bs in (4, 3, 2):
            if no_max % bs or any(int(n) % bs for n in noids):
                continue
            if any(m.shape[1] % bs for m in oo):
                continue
            nb = 0
            for m in oo:
                if not m.nnz:
                    continue
                keys = (m.row_of_nz().astype(np.int64) // bs) * (
                    m.shape[1] // bs
                ) + m.indices.astype(np.int64) // bs
                nb += len(np.unique(keys))
            if nnz / max(nb * bs * bs, 1) < cls.BSR_MIN_FILL:
                continue
            # per-part group unions (self excluded: those columns arrive
            # as a reshape of the owned region, gather-free)
            unions, ngr_max = [], 1
            for p in range(P):
                m = oo[p]
                nn = m.shape[0] // bs
                ngr = -(-nn // G) if nn else 0
                ngr_max = max(ngr_max, ngr)
                us = []
                for g in range(ngr):
                    r0, r1 = g * G * bs, min((g + 1) * G * bs, m.shape[0])
                    bc = np.unique(
                        m.indices[m.indptr[r0] : m.indptr[r1]] // bs
                    )
                    ext = bc[(bc < g * G) | (bc >= g * G + G)]
                    us.append(ext)
                unions.append(us)
            # BUCKETED group widths (round-5 directive 3): pad each
            # CONTIGUOUS chunk of groups to its own union maximum
            # instead of the global one — Morton order keeps neighboring
            # groups' unions similar, so equal-range chunks recover most
            # of the padding the global width wasted (the reason bigger
            # meshes kept tripping SD_MAX_BYTES / the gather-count guard)
            B = int(min(cls.SD_BUCKETS, ngr_max))
            bounds = [round(i * ngr_max / B) for i in range(B + 1)]
            chunks = []  # (r0, r1, emax_c)
            sd_bytes = 0
            pad_ext = 0
            for c in range(B):
                r0c, r1c = bounds[c], bounds[c + 1]
                if r0c == r1c:
                    continue
                emax_c = 1
                for p in range(P):
                    for g in range(r0c, min(r1c, len(unions[p]))):
                        emax_c = max(emax_c, len(unions[p][g]))
                width = (G + emax_c) * bs
                sd_bytes += (
                    P * (r1c - r0c) * (G * bs) * width
                    * np.dtype(dt).itemsize
                )
                pad_ext += P * (r1c - r0c) * emax_c
                chunks.append((r0c, r1c, emax_c))
            if sd_bytes > cls.SD_MAX_BYTES:
                continue  # a smaller bs may still fit the budget
            # padding must not reintroduce the gathers it saves: require
            # the padded external gather count to beat BSR's block count
            if pad_ext * bs * bs > 0.7 * nnz:
                continue
            out_chunks = []
            for r0c, r1c, emax_c in chunks:
                out_chunks.append(
                    {
                        "idx": np.zeros(
                            (P, r1c - r0c, emax_c), dtype=INDEX_DTYPE
                        ),
                        # operator dtype directly: an f64 temp would
                        # double the peak against SD_MAX_BYTES (review r4)
                        "vals": np.zeros(
                            (P, r1c - r0c, G * bs, (G + emax_c) * bs),
                            dtype=dt,
                        ),
                        "r0": r0c,
                    }
                )
            import bisect

            starts = [c["r0"] for c in out_chunks]
            for p in range(P):
                m = oo[p]
                for g, ext in enumerate(unions[p]):
                    ch = out_chunks[bisect.bisect_right(starts, g) - 1]
                    r0, r1 = g * G * bs, min((g + 1) * G * bs, m.shape[0])
                    s, e = m.indptr[r0], m.indptr[r1]
                    rr = (
                        np.repeat(
                            np.arange(r0, r1),
                            np.diff(m.indptr[r0 : r1 + 1]),
                        )
                        - r0
                    )
                    cc = m.indices[s:e]
                    bc = cc // bs
                    self_mask = (bc >= g * G) & (bc < g * G + G)
                    lc = np.where(
                        self_mask,
                        cc - g * G * bs,
                        (np.searchsorted(ext, bc) + G) * bs + cc % bs,
                    )
                    gl = g - ch["r0"]
                    ch["idx"][p, gl, : len(ext)] = ext
                    ch["vals"][p, gl][rr, lc] = m.data[s:e]
            return {"bs": bs, "G": G, "chunks": out_chunks}
        return None

    @staticmethod
    def _detect_oh_blocks(A, oh, P, bs, row_layout, col_layout, dt):
        """Node-block (bs x bs) staging of the A_oh boundary block
        (round-4 directive 7): when the ghost layer arrives as whole
        aligned node triples (vector-dof FE assembly touches all of a
        node's dofs together, so add_gids appends them contiguously) and
        the ghost slots are the identity layout (no box-segment
        reordering), the ghost gather runs at one index per NODE instead
        of per element — the same ~bs^2 serial-gather reduction the
        A_oo block already gets. Returns None whenever any precondition
        fails; callers keep the per-element ELL boundary path.

        BUCKETED widths (the round-4 directive-7 leftover): boundary
        rows are padded per contiguous BUCKET of boundary nodes to that
        bucket's own blocks-per-row maximum, not the global one —
        corner/edge nodes with deep ghost coupling no longer inflate the
        padded gather count of every face node (the same treatment
        `_detect_sd` gives the owned groups). ``PA_TPU_OH_BUCKETS=0``
        collapses to one global-width bucket (the pre-bucketing program)
        for A/B runs."""
        from scipy.sparse import csr_matrix

        if col_layout.box_info is not None:
            return None  # segment-reordered ghost slots break triples
        isets = A.cols.partition.part_values()
        nb_max, Lb_max = 1, 1
        plans = []
        for p in range(P):
            m = oh[p]
            nh = m.shape[1]
            if nh % bs or m.shape[0] % bs:
                return None
            iset = isets[p]
            g = np.asarray(iset.lid_to_gid[iset.num_oids :], dtype=np.int64)
            if len(g) != nh:
                return None
            if nh:
                g3 = g.reshape(-1, bs)
                if not np.array_equal(
                    g3, (g3[:, :1] // bs) * bs + np.arange(bs)
                ):
                    return None  # ghosts not aligned node triples
            if not m.nnz:
                plans.append(None)
                continue
            S = csr_matrix(
                (m.data, m.indices, m.indptr), shape=m.shape
            ).tobsr((bs, bs))
            lens = np.diff(S.indptr)
            bn = np.nonzero(lens)[0]
            plans.append((S, bn, lens))
            nb_max = max(nb_max, len(bn))
            Lb_max = max(Lb_max, int(lens.max()))
        B = (
            1
            if os.environ.get("PA_TPU_OH_BUCKETS", "1") == "0"
            else int(min(DeviceMatrix.SD_BUCKETS, nb_max))
        )
        bounds = [round(i * nb_max / B) for i in range(B + 1)]
        # two passes: size every bucket FIRST so the byte guard runs
        # before any padded array exists — an over-budget boundary block
        # must be rejected to the ELL path without the multi-GB host
        # allocation spike it is rejecting
        geom = []  # (b0, b1, Lb_c)
        total_bytes = 0
        for c in range(B):
            b0, b1 = bounds[c], bounds[c + 1]
            if b0 == b1:
                continue
            # per-bucket width: the max blocks-per-row over every part's
            # boundary nodes landing in this bucket's slot range
            Lb_c = 1
            for pl in plans:
                if pl is None:
                    continue
                _S, bn, lens = pl
                sel = lens[bn[b0:b1]]
                if sel.size:
                    Lb_c = max(Lb_c, int(sel.max()))
            total_bytes += P * (b1 - b0) * Lb_c * bs * bs * 8
            geom.append((b0, b1, Lb_c))
        if total_bytes > DeviceMatrix.SD_MAX_BYTES:
            return None
        chunks = [
            {
                "b0": b0,
                "rows": np.full(
                    (P, b1 - b0, bs), row_layout.trash, dtype=INDEX_DTYPE
                ),
                "cols": np.zeros((P, b1 - b0, Lb_c), dtype=INDEX_DTYPE),
                # operator dtype directly: no f64 transient (review r4)
                "vals": np.zeros((P, b1 - b0, Lb_c, bs, bs), dtype=dt),
            }
            for b0, b1, Lb_c in geom
        ]
        starts = [c["b0"] for c in chunks]
        for p, pl in enumerate(plans):
            if pl is None:
                continue
            S, bn, lens = pl
            slot = np.arange(len(S.indices)) - np.repeat(S.indptr[:-1], lens)
            rr = np.repeat(np.arange(len(lens)), lens)
            inv = np.full(len(lens), -1)
            inv[bn] = np.arange(len(bn))
            bpos = inv[rr]  # boundary-LIST position of each block
            ci = np.searchsorted(starts, bpos, side="right") - 1
            for k, ch in enumerate(chunks):
                b0 = ch["b0"]
                b1 = b0 + ch["rows"].shape[1]
                j = np.arange(b0, min(b1, len(bn)))
                if j.size:
                    ch["rows"][p, j - b0] = (
                        row_layout.o0 + bn[j][:, None] * bs + np.arange(bs)
                    )
                e = ci == k
                ch["cols"][p, bpos[e] - b0, slot[e]] = S.indices[e]
                ch["vals"][p, bpos[e] - b0, slot[e]] = S.data[e]
        return {"bs": bs, "chunks": chunks}

    #: Accept the face-slab form of A_oh while the dense entries of all
    #: its classes stay within this many times the stored entries of the
    #: fullest part (a Cartesian part touches half the directions the
    #: union of the parts does, so a stencil reads about 2) ...
    OH_SLAB_MAX_FILL = 4

    #: ... and while it has at most this many classes: each is a handful
    #: of slice ops compiled into every program (a 27-point operator on a
    #: 3-D part grid has 98).
    OH_SLAB_MAX_CLASSES = 128

    @classmethod
    def _detect_oh_slabs(cls, A, oh, P, col_layout, dt):
        """Face-slab staging of the A_oh boundary block on a box layout.

        The box layout keeps a direction's ghosts as one segment in the
        sender's C-order slab scan, and the owned block is the C-order
        box scan, so a stored entry has coordinates on both sides: its
        row in the owned box and its ghost in the direction's slab. The
        entries of one direction whose two coordinates differ by the
        same vector form a CLASS: rows and ghosts are then the same
        sub-box shifted, in the same order, and the class applies as
        ``y[row sub-box] += coef * x[ghost sub-box]`` on static slices,
        with no gather, scatter or index operand. The sub-box of a class
        is the bounding box of its ghosts over all parts (one compiled
        program serves every shard); positions a part has no entry at
        (Dirichlet-trimmed rows, a direction with no neighbour) carry
        coefficient zero. A depth-1 star stencil gives one class a
        face, a 27-point operator nine shifted ones a face and three an
        edge.

        Returns ``{"geo": tuple of OhSlab, "vals": (P, dense)}`` or None,
        and the caller keeps the ELL form: under strict-bits (whose
        left-to-right fold the ELL form is), with no box layout or more
        than one box-shape variant, with rows that are not the owned
        columns' box scan (a transfer), past `OH_SLAB_MAX_CLASSES`, or
        when the classes' dense entries pass `OH_SLAB_MAX_FILL` times
        the stored entries of the fullest part."""
        info = col_layout.box_info
        if strict_bits() or info is None or len(info.box_shapes) != 1:
            return None
        box = info.box_shape
        no = math.prod(box)
        for r, c in zip(
            A.rows.partition.part_values(), A.cols.partition.part_values()
        ):
            if (
                getattr(r, "box_shape", None) != box
                or r.box_lo != c.box_lo
                or r.num_oids != no
            ):
                return None
        offs = np.array([d.off for d in info.dirs], dtype=np.int64)
        # (direction, row - ghost) -> [lo, hi, [(part, ghosts, values)]]
        found = {}
        for p in range(P):
            m = oh[p]
            if not m.nnz:
                continue
            rel = np.asarray(info.ghost_rel_slots[p], dtype=np.int64)[
                m.indices
            ]
            which = np.searchsorted(offs, rel, side="right") - 1
            rows = np.stack(np.unravel_index(m.row_of_nz(), box))
            for k in np.unique(which):
                d = info.dirs[k]
                e = np.nonzero(which == k)[0]
                ghosts = np.stack(np.unravel_index(rel[e] - d.off, d.shape))
                deltas, cls_of = np.unique(
                    rows[:, e] - ghosts, axis=1, return_inverse=True
                )
                cls_of = cls_of.reshape(-1)
                for c in range(deltas.shape[1]):
                    sel = cls_of == c
                    g = ghosts[:, sel]
                    lo, hi = g.min(axis=1), g.max(axis=1) + 1
                    ent = found.setdefault(
                        (int(k), tuple(int(x) for x in deltas[:, c])),
                        [lo, hi, []],
                    )
                    ent[0] = np.minimum(ent[0], lo)
                    ent[1] = np.maximum(ent[1], hi)
                    ent[2].append((p, g, m.data[e[sel]]))
        dense = sum(
            int(np.prod(hi - lo)) for lo, hi, _ in found.values()
        )
        if (
            len(found) > cls.OH_SLAB_MAX_CLASSES
            or dense > cls.OH_SLAB_MAX_FILL * max(m.nnz for m in oh)
        ):
            return None
        vals = np.zeros((P, dense), dtype=dt)
        geo = []
        v0 = 0
        for k, delta in sorted(found):
            lo, hi, entries = found[k, delta]
            d = info.dirs[k]
            shape = tuple(int(x) for x in hi - lo)
            for p, g, v in entries:
                pos = np.ravel_multi_index(tuple(g - lo[:, None]), shape)
                # one entry a position in a compressed CSR; summed if not
                np.add.at(vals[p], v0 + pos, v)
            geo.append(
                OhSlab(
                    seg=d.off, slab=tuple(d.shape),
                    ghost_lo=tuple(int(x) for x in lo),
                    row_lo=tuple(int(x) + dx for x, dx in zip(lo, delta)),
                    shape=shape, v0=v0,
                )
            )
            v0 += math.prod(shape)
        return {"geo": tuple(geo), "vals": vals}

    @classmethod
    def _detect_bsr(cls, oo, P, noids, no_max, dt):
        """Node-block (BSR) lowering for irregular vector-dof operators:
        one gather index per bs×bs block instead of per element cuts the
        TPU's element-at-a-time gather count ~bs²×, and the block
        products become vectorized einsum fmas. Chosen when the blocks
        are dense enough (`BSR_MIN_FILL`); strict-bits mode keeps the
        fold-order-matching ELL path, and `PA_TPU_BSR=0` disables."""
        if strict_bits() or os.environ.get("PA_TPU_BSR", "1") == "0":
            return None
        from scipy.sparse import csr_matrix

        nnz = sum(m.nnz for m in oo)
        if nnz == 0:
            return None
        for bs in (4, 3, 2):
            if no_max % bs or any(int(n) % bs for n in noids):
                continue
            if any(m.shape[1] % bs for m in oo):
                continue
            # structure-only fill gate first: count distinct blocks from
            # integer keys — no O(nnz) value materialization for block
            # sizes that will be rejected anyway
            nb = 0
            for m in oo:
                if not m.nnz:
                    continue
                keys = (m.row_of_nz().astype(np.int64) // bs) * (
                    m.shape[1] // bs
                ) + m.indices.astype(np.int64) // bs
                nb += len(np.unique(keys))
            if nnz / max(nb * bs * bs, 1) < cls.BSR_MIN_FILL:
                continue
            S = [
                csr_matrix(
                    (m.data, m.indices, m.indptr), shape=m.shape
                ).tobsr((bs, bs))
                for m in oo
            ]
            Lb = max(
                (
                    int(np.diff(s.indptr).max()) if s.indptr.size > 1 else 0
                    for s in S
                ),
                default=0,
            )
            Lb = max(Lb, 1)
            nn_max = no_max // bs
            cols = np.zeros((P, nn_max, Lb), dtype=INDEX_DTYPE)
            vals = np.zeros((P, nn_max, Lb, bs, bs))
            for p, s in enumerate(S):
                lens = np.diff(s.indptr)
                if not lens.size or not s.data.size:
                    continue
                slot = np.arange(len(s.indices)) - np.repeat(
                    s.indptr[:-1], lens
                )
                rr = np.repeat(np.arange(len(lens)), lens)
                cols[p, rr, slot] = s.indices
                vals[p, rr, slot] = s.data
            return {"bs": bs, "cols": cols, "vals": vals.astype(dt)}
        return None

    @classmethod
    def _analyze_dia_classes(
        cls, oo, P, noids, no_max, offsets, off_arr, itemsize,
        col_limits=None,
    ):
        """Dense-DIA-free coded-diagonal analysis (round-4): one fused
        pass per part classifies rows by their diagonal-value tuple
        (planning.cpp:dia_classify_impl — identical classes, identical
        first-touch order as dia_fill + row_classes); the per-diagonal
        codebooks, the coded set, and the row-class compression all
        derive from the tiny class tables, so the (P, D, no_max) float64
        diagonal matrix (5.6 GB at 1e8 DOFs) is never materialized.
        Returns the det dict with ``det["dia"] = None``, or None when
        the fused analysis doesn't apply (native off, > _CLS_CAP
        classes, a diagonal over CODE_MAX_VALUES) — the caller then
        runs the dense-diagonal path, which also serves streaming."""
        from .. import native
        from ..ops.pallas_dia import plan_dia_padded

        D = len(offsets)
        KMAX = cls.CODE_MAX_VALUES
        tables = []
        codes_all = np.zeros((P, no_max), dtype=np.uint8)
        for p in range(P):
            M = oo[p]
            n_o = int(noids[p])
            if M.nnz:
                t, c, ok = native.dia_classify(
                    M.indptr, M.indices, M.data, M.shape[0], off_arr,
                    cls._CLS_CAP,
                    col_limit=(
                        int(col_limits[p]) if col_limits is not None
                        else 2**31
                    ),
                )
                if not ok:
                    return None
                tables.append(t)
                codes_all[p, :n_o] = c
            else:
                tables.append(np.zeros((1, D)))
        uniq = [
            [np.unique(tables[p][:, d]) for d in range(D)] for p in range(P)
        ]
        kk = tuple(
            max((len(uniq[p][d]) for p in range(P)), default=1) or 1
            for d in range(D)
        )
        if max(kk) > KMAX:
            return None  # streaming staging needs the dense diagonals
        code_row, coded = [], []
        for d in range(D):
            if kk[d] > 1:
                code_row.append(len(coded))
                coded.append(d)
            else:
                code_row.append(-1)
        cls_uniq = cls_ids = None
        if len(coded) >= 3 and all(len(t) <= KMAX for t in tables):
            cls_uniq = tables
            cls_ids = codes_all
            n_class = max((len(t) for t in tables), default=1) or 1
            kk = tuple(n_class if kk[d] > 1 else 1 for d in range(D))
            code_row = [0 if c >= 0 else -1 for c in code_row]
        n_streams = 1 if cls_uniq is not None else -(-len(coded) // 2)
        return {
            "offsets": offsets,
            "dia": None,
            "uniq": uniq,
            "kk": kk,
            "code_row": code_row,
            "coded": coded,
            "Dc": len(coded),
            "coded_ok": True,
            "cls_uniq": cls_uniq,
            "cls_ids": cls_ids,
            "cls_tables": tables,
            "cls_codes": codes_all,
            "pplan": plan_dia_padded(
                offsets, no_max, n_streams, itemsize=itemsize
            ),
        }

    @classmethod
    def _detect_dia(
        cls, A, oo, P, noids, no_max, itemsize, col_limits=None,
        fused_only=False,
    ):
        """Band structure analysis of the A_oo block, run *before* the
        layout choice (the padded frame is only worth it when the coded
        kernel applies). Returns None when A_oo is not a (square, narrow)
        band; otherwise the dense per-diagonal values plus the
        coded-diagonal decomposition.

        Coded diagonals: stencil operators (FD/FV, and FE on structured
        meshes) draw each diagonal's values from a tiny set — one interior
        value plus a few boundary / Dirichlet variants. When every diagonal
        has at most CODE_MAX_VALUES distinct values, SpMV streams 1 BYTE
        per element per non-constant diagonal (an index into a per-diagonal
        codebook decoded in VMEM) instead of a 4-byte float — and fully
        constant diagonals stream nothing at all. Bits are preserved:
        decoding returns the exact stored values and the ascending-offset
        accumulation order is unchanged."""
        from ..ops.pallas_dia import plan_dia_padded
        from .. import native

        def _oids_eq(ri, ci):
            # box partitions answer the square check from metadata — the
            # volume-sized oid_to_gid materialization + compare was ~10%
            # of the 1e8-DOF lowering profile
            if (
                hasattr(ri, "box_lo")
                and hasattr(ci, "box_lo")
                and ri.grid_shape == ci.grid_shape
                and ri.box_lo == ci.box_lo
                and ri.box_hi == ci.box_hi
            ):
                return True
            return np.array_equal(ri.oid_to_gid, ci.oid_to_gid)

        square = all(
            _oids_eq(ri, ci)
            for ri, ci in zip(
                A.rows.partition.part_values(), A.cols.partition.part_values()
            )
        )
        if not square:
            return None
        offs = set()
        for p in range(P):
            M = oo[p]
            if M.nnz:
                # fused one-pass scan (planning.cpp:band_offsets_impl) —
                # the nnz-sized astype + row repeat + unique sort it
                # replaces dominated band detection at 1e8 DOFs.
                # col_limits: `oo` is then the FULL local CSR per part
                # and the sorted ghost tail is skipped per row (the
                # no-split lowering; `fused_only` declines instead of
                # running the dense path, which needs real blocks)
                u, ok = native.band_offsets(
                    M.indptr, M.indices, M.shape[0], cls.DIA_MAX_OFFSETS,
                    col_limit=(
                        int(col_limits[p]) if col_limits is not None
                        else 2**31
                    ),
                )
                if not ok:
                    return None
                offs.update(u.tolist())
        if not (0 < len(offs) <= cls.DIA_MAX_OFFSETS):
            return None
        offsets = tuple(sorted(offs))
        D = len(offsets)
        off_arr = np.array(offsets)

        fused = cls._analyze_dia_classes(
            oo, P, noids, no_max, offsets, off_arr, itemsize,
            col_limits=col_limits,
        )
        if fused is not None:
            return fused
        if fused_only:
            return None  # dense detection needs the real A_oo blocks
        # dense per-diagonal values on host: detection + staging source.
        # Entry (r, r+o) of part p goes to diagonal o; ascending offsets ==
        # ascending column order per row, so the accumulation order (and
        # the bits) match the ELL/CSR kernels; absent diagonals contribute
        # exact +0 terms.
        dia = np.zeros((P, D, no_max))
        for p in range(P):
            M = oo[p]
            if M.nnz:
                # fused native fill (one pass); NumPy fallback is a
                # searchsorted + fancy scatter — two nnz-sized passes
                # that dominate the 1e8-DOF lowering profile
                from .. import native

                if not native.dia_fill(
                    M.indptr, M.indices, M.data, M.shape[0], off_arr, dia[p]
                ):
                    r = M.row_of_nz()
                    d = np.searchsorted(
                        off_arr, M.indices.astype(np.int64) - r
                    )
                    dia[p, d, r] = M.data
        # distinct values per diagonal, capped at CODE_MAX_VALUES: the
        # native single-pass kernel avoids an np.unique sort per diagonal
        # (7 x O(n log n) over 1e8 rows otherwise). A diagonal with more
        # distinct values than the cap reports a sentinel count that sends
        # the whole matrix to the streaming path without finishing the scan.
        from .. import native

        KMAX = cls.CODE_MAX_VALUES
        uniq = []
        for p in range(P):
            row = []
            n_o = int(noids[p])
            for d in range(D):
                u, ok = native.unique_small(dia[p, d, :n_o], KMAX)
                if not ok:
                    # sentinel of KMAX+1 entries: forces coded_ok False
                    # (streaming path); never read by the staging code
                    u = np.arange(KMAX + 1, dtype=float)
                row.append(u)
            uniq.append(row)
        kk = tuple(
            max((len(uniq[p][d]) for p in range(P)), default=1) or 1
            for d in range(D)
        )
        code_row, coded = [], []
        for d in range(D):
            if kk[d] > 1:
                code_row.append(len(coded))
                coded.append(d)
            else:
                code_row.append(-1)
        coded_ok = max(kk) <= cls.CODE_MAX_VALUES
        # row-class compression: when the rows of each part fall into few
        # distinct stencil-value tuples (e.g. interior vs Dirichlet-identity
        # for the FDM operator), every coded diagonal can read ONE shared
        # per-row class stream instead of its own — codes shrink from
        # ceil(Dc/2) byte-streams per row to one, at a select chain of
        # n_class per diagonal. Only worth it when it removes streams.
        cls_uniq = cls_ids = None
        if coded_ok and len(coded) >= 3:
            cls_uniq, cls_ids, n_class = [], np.zeros((P, no_max), np.uint8), 1
            for p in range(P):
                n_o = int(noids[p])
                u, inv, ok = native.row_classes(dia[p], n_o, KMAX)
                if not ok:
                    cls_uniq = cls_ids = None  # > KMAX classes
                    break
                cls_uniq.append(u)
                cls_ids[p, :n_o] = inv
                n_class = max(n_class, len(u))
        if cls_uniq is not None:
            kk = tuple(n_class if kk[d] > 1 else 1 for d in range(D))
            code_row = [0 if c >= 0 else -1 for c in code_row]
        n_streams = 1 if cls_uniq is not None else -(-len(coded) // 2)
        pplan = (
            plan_dia_padded(offsets, no_max, n_streams, itemsize=itemsize)
            if coded_ok
            else None
        )
        return {
            "offsets": offsets,
            "dia": dia,
            "uniq": uniq,
            "kk": kk,
            "code_row": code_row,
            "coded": coded,
            "Dc": len(coded),
            "coded_ok": coded_ok,
            "cls_uniq": cls_uniq,
            "cls_ids": cls_ids,
            "pplan": pplan,
        }


def _lowering_env_key() -> tuple:
    """The ONE resolution of every env mode that changes a DeviceMatrix
    lowering. Each cache of anything staged/compiled from a DeviceMatrix
    must include this tuple in its key (device_matrix itself, the GMG
    hierarchy/fn caches, ...), or a flipped flag silently serves a stale
    lowering. Adding a new lowering-affecting mode? Add it HERE — every
    keyed cache picks it up."""
    return (
        strict_bits(),
        os.environ.get("PA_TPU_BSR", "1") != "0",
        os.environ.get("PA_TPU_SD", "1") != "0",
        os.environ.get("PA_TPU_CLASS_ACC", "1") != "0",
        os.environ.get("PA_TPU_OH_BUCKETS", "1") != "0",
        _box_exchange_enabled(),
        # the fused-CG mode does not change the MATRIX lowering itself
        # (the program caches re-key on the concrete body choice), but
        # keying it here means every derived cache — including future
        # ones that bake a CG body without threading the flag — rekeys
        # on a flip. Cost: an env-flip A/B restages the matrix; the
        # bench tooling therefore A/Bs via make_cg_fn(fused=...), not
        # the env var.
        _fused_cg_enabled(),
        # ABFT changes the lowering twice over: the staged checksum row
        # (c·A) joins the operand pytree, and the exchange falls back to
        # the generic index plan (see _box_exchange_enabled)
        _abft_enabled(),
        # staging-ADMISSION guards key too (the first palint env-lint
        # finding): the ELL footprint guard is evaluated once, at stage
        # time — without this entry a matrix staged under a raised
        # PA_TPU_ELL_MAX_GATHER ceiling (or a disabled guard) keeps
        # being served from cache after the override is dropped, i.e.
        # the exact program the guard exists to refuse. Keying the
        # RESOLVED guard pair re-runs admission on a real flip
        # (tests/test_static_analysis.py pins the re-guard).
        _ell_guard_env(),
    )


def _abft_enabled() -> bool:
    from .health import abft_enabled

    return abft_enabled()


def device_matrix(A: PSparseMatrix, backend: TPUBackend) -> DeviceMatrix:
    # cached ON the matrix object so the lowering's lifetime is tied to A;
    # keyed by the backend's stable token (an id() key could be recycled
    # after GC and hand back buffers staged for a dead backend) plus
    # every lowering-affecting env mode
    from .. import telemetry

    key = (backend._token,) + _lowering_env_key()
    if key not in A._device:
        # stale_rekey: this matrix WAS staged on THIS backend before,
        # under a different lowering env key — the flip re-runs staging
        # admission (the palint bug class, now a measurable counter).
        # First staging onto a new backend is a plain miss regardless
        # of what other backends hold.
        rekeyed = any(k[0] == backend._token for k in A._device)
        action = "stale_rekey" if rekeyed else "miss"
        telemetry.bump(f"lowering_cache.{action}")
        telemetry.emit_event(
            "compile_cache", label=f"lowering_{action}", cache="lowering",
            action=action,
        )
        A._device[key] = DeviceMatrix(A, backend)
    else:
        telemetry.bump("lowering_cache.hit")
        telemetry.emit_event(
            "compile_cache", label="lowering_hit", cache="lowering",
            action="hit",
        )
    return A._device[key]


# ---------------------------------------------------------------------------
# compiled programs
# ---------------------------------------------------------------------------


def _strict_rounded_product(t):
    """Strict mode: force `t` (a product about to be accumulated) to its
    own IEEE rounding, blocking XLA's mul+add -> FMA contraction. Two
    fences are needed: an `optimization_barrier` at the HLO level, and a
    data-dependent select at codegen level — the CPU backend's LLVM
    pipeline contracts straight through a bare barrier (measured: 321/1000
    elements differ on a random axpy), while the select breaks the
    fadd(fmul(..)) pattern it matches on. The select's false branch is an
    explicit NaN (not 0) so a NaN-poisoned operand keeps poisoning the
    result as it does in default mode and on the host; the true branch is
    `t` itself, so finite values — including -0.0, which the host oracle
    produces for e.g. a -1·0 product — pass through bit-unchanged."""
    import jax
    import jax.numpy as jnp

    t = jax.lax.optimization_barrier(t)
    return jnp.where(t == t, t, jnp.full_like(t, jnp.nan))


def _strict_pairwise_partial(t, no_max: int):
    """Per-shard strict partial: the fixed-tree pairwise sum of the
    (already separately-rounded) products — `utils.helpers.pairwise_sum`
    runs the identical tree on host. The ONE definition both dot
    factories share; the bit-exactness contract lives here."""
    import jax.numpy as jnp

    n = 1 << int(no_max - 1).bit_length() if no_max > 1 else 1
    t = jnp.pad(t, (0, n - no_max))
    while n > 1:
        t = t[0::2] + t[1::2]
        n //= 2
    return t[0] if no_max else jnp.zeros((), t.dtype)


def _strict_partial_any(t, no_max: int):
    """`_strict_pairwise_partial` lifted over an optional trailing batch
    axis: ``(no_max,) -> scalar`` or ``(no_max, K) -> (K,)`` with the
    IDENTICAL fixed tree per column — each column's partial is
    bit-identical to the single-vector partial of that column alone."""
    import jax.numpy as jnp

    if t.ndim == 1:
        return _strict_pairwise_partial(t, no_max)
    return jnp.stack(
        [
            _strict_pairwise_partial(t[:, k], no_max)
            for k in range(t.shape[1])
        ]
    )


def _pdot_factory(o0: int, no_max: int):
    """Deterministic across-parts dot: per-shard partial (owned region;
    padding is zero by invariant), `all_gather`, fold in part order — the
    compiled form of the sequential `preduce` left-fold, so the reduction
    order (and hence bits) matches the oracle.

    Rank-polymorphic: operands may carry a trailing multi-RHS batch axis
    (``(W, K)``), in which case the partial is per-column, ONE
    all_gather ships the whole ``(K,)`` payload, and the part-order fold
    runs per column — the per-iteration collective COUNT is
    K-independent while each column's reduction order (and bits) stays
    exactly the single-vector order.

    In strict-bits mode the per-shard partial is the fixed-tree pairwise
    sum of separately-rounded products (`_strict_pairwise_partial`), and
    the cross-part fold is an explicit left fold — bit-identical to the
    sequential `PVector.dot`."""
    import jax
    import jax.numpy as jnp

    if strict_bits():

        def pdot(a, b):
            t = _strict_rounded_product(
                a[o0 : o0 + no_max] * b[o0 : o0 + no_max]
            )
            allp = jax.lax.all_gather(
                _strict_partial_any(t, no_max), "parts"
            )
            acc = allp[0]
            for i in range(1, allp.shape[0]):
                acc = acc + allp[i]
            return acc

        return _scoped(SCOPE_DOTS, pdot)

    def pdot(a, b):
        partial_ = jnp.sum(
            a[o0 : o0 + no_max] * b[o0 : o0 + no_max], axis=0
        )
        allp = jax.lax.all_gather(partial_, "parts")
        return jnp.sum(allp, axis=0)

    return _scoped(SCOPE_DOTS, pdot)


def _pdot_owned_factory(no_max: int):
    """Deterministic dots over ALREADY-SLICED owned arrays, for the fused
    CG body whose update sweep holds the owned slices in hand: returns
    ``(dot1, dot2)`` where ``dot1(a, b)`` IS `_pdot_factory`'s pdot at
    offset 0 (an owned array is its own owned region), and
    ``dot2(a, b, c, d)`` computes TWO dots (a·b, c·d) riding ONE
    all_gather of a stacked partial pair — the preconditioned loop's
    r·z / r·r reductions share a collective instead of paying two.
    Per-component partials and the cross-part fold order are identical
    to two separate dot1 calls, so the pairing changes collective count,
    not bits.

    Like `_pdot_factory`, both dots are rank-polymorphic: ``(no_max, K)``
    operands produce per-column results, with dot2's shared all_gather
    widened from a partial pair to a ``(K, 2)`` payload — the block-CG
    loop's whole reduction set still rides ONE collective per
    iteration."""
    import jax
    import jax.numpy as jnp

    dot1 = _pdot_factory(0, no_max)

    if strict_bits():

        def dot2(a, b, c, d):
            p1 = _strict_partial_any(
                _strict_rounded_product(a * b), no_max
            )
            p2 = _strict_partial_any(
                _strict_rounded_product(c * d), no_max
            )
            allp = jax.lax.all_gather(
                jnp.stack([p1, p2], axis=-1), "parts"
            )
            acc1, acc2 = allp[0, ..., 0], allp[0, ..., 1]
            for i in range(1, allp.shape[0]):
                acc1 = acc1 + allp[i, ..., 0]
                acc2 = acc2 + allp[i, ..., 1]
            return acc1, acc2

        return dot1, _scoped(SCOPE_DOTS, dot2)

    def dot2(a, b, c, d):
        p_ = jnp.stack(
            [jnp.sum(a * b, axis=0), jnp.sum(c * d, axis=0)], axis=-1
        )
        s = jnp.sum(jax.lax.all_gather(p_, "parts"), axis=0)
        return s[..., 0], s[..., 1]

    return dot1, _scoped(SCOPE_DOTS, dot2)


def _pdot_extra_factory(o0: int, no_max: int):
    """The deterministic dot with EXTRA scalar lanes riding the SAME
    all_gather — the ABFT/audit transport: ``pdotx(a, b, extras)``
    returns ``(a·b, folded extras)`` where ``extras`` is a tuple of
    per-part partials (checksum delta/scale) stacked into the gather
    payload as additional trailing lanes and summed across parts.

    Lane 0's partial and cross-part fold arithmetic is EXACTLY
    `_pdot_factory`'s (strict mode: the same fixed-tree pairwise partial
    and explicit left fold, per lane), so carrying the extras widens the
    collective's payload bytes, never its count, and never moves the
    dot's bits — the property the ABFT-on/off bitwise identity test
    pins. Rank-polymorphic like the other factories: ``(no_max, K)``
    operands with ``(K,)`` extras produce per-column results."""
    import jax
    import jax.numpy as jnp

    if strict_bits():

        def pdotx(a, b, extras):
            t = _strict_rounded_product(
                a[o0 : o0 + no_max] * b[o0 : o0 + no_max]
            )
            p0 = _strict_partial_any(t, no_max)
            lanes = [p0] + [
                jnp.broadcast_to(e, p0.shape).astype(p0.dtype) for e in extras
            ]
            allp = jax.lax.all_gather(jnp.stack(lanes, axis=-1), "parts")
            acc = allp[0]
            for i in range(1, allp.shape[0]):
                acc = acc + allp[i]
            return acc[..., 0], tuple(
                acc[..., i + 1] for i in range(len(extras))
            )

        return _scoped(SCOPE_DOTS, pdotx)

    def pdotx(a, b, extras):
        p0 = jnp.sum(a[o0 : o0 + no_max] * b[o0 : o0 + no_max], axis=0)
        lanes = [p0] + [
            jnp.broadcast_to(e, p0.shape).astype(p0.dtype) for e in extras
        ]
        allp = jax.lax.all_gather(jnp.stack(lanes, axis=-1), "parts")
        s = jnp.sum(allp, axis=0)
        return s[..., 0], tuple(s[..., i + 1] for i in range(len(extras)))

    return _scoped(SCOPE_DOTS, pdotx)


def make_exchange_fn(rows: PRange, backend: TPUBackend, combine: str = "set") -> Callable:
    """Compiled halo update: (P, W) sharded array -> same with ghosts
    current (combine='set') or owners accumulated (combine='add', reverse
    plan) — the device form of exchange!/assemble!."""
    import jax
    shard_map = jax.shard_map

    from .tpu_box import BoxExchangePlan

    plan = device_exchange_plan(rows, _padded_for(backend))
    if combine == "add":
        if isinstance(plan, BoxExchangePlan):
            plan = plan.reverse()
        else:
            # reverse plan: swap pack/unpack roles
            plan = DeviceExchangePlan(rows.exchanger.reverse(), plan.layout)
    mesh = backend.mesh(plan.layout.P)
    spec = backend.parts_spec()
    body = _shard_exchange(plan, combine)

    @jax.jit
    def fn(x, si, sm, ri):
        def shard_fn(xs, sis, sms, ris):
            return body(xs[0], sis[0], sms[0], ris[0])[None]

        return shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec, spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(x, si, sm, ri)

    if isinstance(plan, BoxExchangePlan):
        # everything is compiled in; tiny dummies keep the fn signature —
        # except the reverse path's sm slot, which carries the real
        # segment mask (orphan slab slots must not accumulate into owners)
        si, sm, ri = _box_dummy_operands(
            backend,
            plan.layout.P,
            plan.info.seg_mask if plan.reverse_mode else None,
            variants=plan.info.variants,
        )
    else:
        si = _stage(backend, plan.snd_idx, plan.layout.P)
        sm = _stage(backend, plan.snd_mask, plan.layout.P)
        ri = _stage(backend, plan.rcv_idx, plan.layout.P)
    return lambda x: fn(x, si, sm, ri)


def _box_dummy_operands(backend: TPUBackend, P: int, seg_mask=None,
                        variants=None):
    """(si, sm, ri) operands for box-plan programs. The slice bodies
    ignore ri (a tiny dummy keeps the operand pytree uniform so every
    caller passes m['si']/m['sm']/m['ri'] unconditionally); si carries
    each shard's box-shape VARIANT index (read only by multi-variant
    plans — unequal Cartesian splits); sm is the staged real segment
    mask when the caller holds a reverse plan, a dummy otherwise."""
    z = np.zeros((P, 1), dtype=INDEX_DTYPE)
    si = (
        np.asarray(variants, dtype=INDEX_DTYPE).reshape(P, 1)
        if variants is not None
        else z
    )
    sm = seg_mask if seg_mask is not None else np.zeros((P, 1), dtype=bool)
    return (
        _stage(backend, si, P),
        _stage(backend, sm, P),
        _stage(backend, z, P),
    )


def _matrix_operands(dA: DeviceMatrix) -> dict:
    """The sharded operand pytree fed to compiled programs — only what the
    selected A_oo path actually reads (coded mode drops the O(D*N) values
    stream entirely: codebook + int8 codes instead)."""
    from .tpu_box import BoxExchangePlan

    if dA._ops_cache is not None:
        return dA._ops_cache
    plan = dA.col_plan
    P = plan.layout.P
    if isinstance(plan, BoxExchangePlan):
        si, sm, ri = _box_dummy_operands(
            dA.backend, P, variants=plan.info.variants
        )
    else:
        si = _stage(dA.backend, plan.snd_idx, P)
        sm = _stage(dA.backend, plan.snd_mask, P)
        ri = _stage(dA.backend, plan.rcv_idx, P)
    ops = {"si": si, "sm": sm, "ri": ri}
    if dA.abft_w is not None:
        ops["abft_w"] = dA.abft_w
    if dA.ohb_bs is not None:
        ops.update(ohb_r=dA.ohb_rows, ohb_c=dA.ohb_cols, ohb_v=dA.ohb_vals)
    elif dA.ohs_geo is not None:
        ops["ohs_v"] = dA.ohs_vals
    elif dA.oh_vals is not None:
        ops.update(oh_v=dA.oh_vals, oh_c=dA.oh_cols, oh_r=dA.oh_rows)
    if dA.dia_mode == "coded":
        ops.update(cb=dA.dia_cb, no=dA.dia_no, codes=dA.dia_codes)
    elif dA.dia_offsets is not None:
        ops["oo_v"] = dA.dia_vals
    elif dA.sd_bs is not None:
        ops.update(sd_i=dA.sd_idx, sd_v=dA.sd_vals)
    elif dA.bsr_bs is not None:
        ops.update(bsr_c=dA.bsr_cols, bsr_v=dA.bsr_vals)
    else:
        ops.update(oo_v=dA.oo_vals, oo_c=dA.oo_cols)
    dA._ops_cache = ops
    return ops


def _spmv_body(dA: DeviceMatrix, pfold: bool = False,
               abft: bool = False, audit: bool = False,
               columns: bool = False):
    """Per-shard overlapped SpMV: pack+permute the halo, compute the A_oo
    partial on pre-exchange owned values (independent of the collective —
    XLA overlaps them), then unpack and add the A_oh ghost contribution
    on the compact boundary-row set.

    With ``pfold=True`` (fused CG, `make_cg_fn(fused=True)`) the body is
    ``body(rv, pv, beta, m, mvv=None) -> (y, p)``: the next search
    direction ``p = z + beta*pv`` materializes inside the SpMV's own
    streaming pass instead of its own HBM sweep: in the Pallas kernel on
    the padded coded path, as a jnp fold on the BSR/SD/ELL/XLA-DIA
    lowerings.

    Every body is RANK-POLYMORPHIC over the operand: ``(W,)`` applies the
    operator to one vector, ``(W, K)`` to a K-column multi-RHS block —
    SpMV becomes SpMM. The operator stream (DIA values/codebooks, SD
    group blocks, BSR blocks, ELL arrays) is read ONCE per K columns:
    DIA diagonals broadcast over the block's trailing axis, the SD/BSR
    group products widen to one batched ``(rows, U) @ (U, K)`` MXU
    einsum, and the halo exchange ships ``(…, K)`` slabs per wire round
    (JITSPMM, arxiv 2312.05639 — amortize the operand stream across
    columns and feed the MXU). The Pallas kernels (coded padded frame,
    streaming DIA, in-kernel pfold) take a 1-D frame only, and a
    ``(W, K)`` operand falls back to the equivalent XLA forms of the
    same arithmetic.

    ``columns=True`` (the lane-major block body of `make_block_cg_fn`)
    builds the body for `jax.vmap` over K 1-D frames instead: the two
    callers of the coded kernel then take the K frames as ``(K, W)`` and
    the kernel walks them on a leading grid axis, one after the other;
    everything else batches by itself. Without it the body, and the
    text it lowers to, is what it was.

    ``abft=True`` builds the checksummed variant: the halo exchange runs
    with per-round slab checksums (`_shard_exchange(abft=True)`) and the
    body returns ``(y, exchanged operand, exchange delta, exchange
    scale)`` — the caller (the CG builders) completes the ABFT identity
    ``c·(A x)`` vs ``(c·A)·x`` against the staged checksum row, so a
    graph-injected fault lands in the SAME ``q`` both the recurrence and
    the checksum see. ``audit=True`` (with ``pfold``) adds the
    ``aud``/``audx`` operand switch that lets the true-residual audit's
    ``A x`` reuse this body's one SpMV call site; both flags keep the
    Pallas pfold kernel off (ABFT-off guard with XLA fallback, the PR-3
    K>1 precedent)."""
    import jax
    import jax.numpy as jnp

    plan = dA.col_plan
    exch = _shard_exchange(plan, "set", abft=abft)
    layout = dA.row_layout
    no_max = layout.no_max
    o0, g0 = layout.o0, layout.g0

    strict = strict_bits()  # captured at trace/build time

    def _rp(t):
        # strict mode: round each product separately before accumulation
        # (the one rounding difference vs the NumPy oracle)
        return _strict_rounded_product(t) if strict else t

    def _bc(a, xv):
        """Lift a per-row (rows,) coefficient/mask array to broadcast
        over the operand's trailing multi-RHS axis (no-op at K=1)."""
        return a[:, None] if xv.ndim == 2 else a

    def _tpad(xv, lo, hi):
        """Leading-axis pad, rank-generic over the trailing batch axis."""
        return jnp.pad(xv, ((lo, hi),) + ((0, 0),) * (xv.ndim - 1))

    def _ell_rowsum(vals, cols, xv):
        # strict left-to-right fold over the (static, small) row width, the
        # same accumulation order as the host CSR kernel's reduceat — keeps
        # the device result bit-comparable with the sequential oracle
        L = vals.shape[-1]
        acc = _rp(_bc(vals[:, 0], xv) * xv[cols[:, 0]])
        for l in range(1, L):
            acc = acc + _rp(_bc(vals[:, l], xv) * xv[cols[:, l]])
        return acc

    offsets = dA.dia_offsets
    pad = max((abs(o) for o in offsets), default=0) if offsets else 0
    pplan = dA.pallas_plan
    mode = dA.dia_mode

    def _pad_lanes(xv):
        from ..ops.pallas_dia import LANES

        hp = pplan["halo_rows"] * LANES
        return jnp.pad(
            xv[o0 : o0 + no_max], (hp, pplan["x_rows"] * LANES - hp - no_max)
        ).reshape(-1, LANES)

    def _dia_rowsum_pallas(vals, xv):
        # Pallas streaming path (real TPU, variable-coefficient band):
        # see ops/pallas_dia.py for the memory schedule. K=1-only — the
        # block path reads the same staged values through the XLA
        # shifted-slice form instead (`_dia_vals_dense`).
        from ..ops.pallas_dia import dia_spmv_pallas

        with jax.named_scope(SCOPE_DIA_EMBED):
            xw = _pad_lanes(xv)
        with jax.named_scope(SCOPE_DIA_STREAM):
            y = dia_spmv_pallas(
                vals, xw, offsets, pplan["n_rows"], pplan["halo_rows"],
                pplan["block_rows"], interpret=interpret,
            )
        with jax.named_scope(SCOPE_DIA_EMBED):
            return y.reshape(-1)[:no_max]

    def _dia_vals_dense(vals):
        # the streaming-DIA staging is lane-tiled (D, R, LANES) when a
        # Pallas plan exists; flatten back to the (D, no_max) dense form
        # the XLA shifted-slice body reads (block fallback path)
        if pplan is not None:
            return vals.reshape(vals.shape[0], -1)[:, :no_max]
        return vals

    def _dia_rowsum(vals, xv):
        # banded fast path: no gather — one zero-padded copy of the owned
        # region, then each diagonal is a *static slice* of it, so XLA
        # fuses the whole band sum into one streaming VPU kernel (rolls
        # would materialize a full copy per diagonal). Ascending-offset
        # order == ascending-column order per row, so bits match the ELL
        # fold; pad/absent-diagonal terms are exact zeros (val 0). With a
        # trailing batch axis each diagonal broadcasts over the K
        # columns — the band values stream once per K.
        xp = _tpad(xv[o0 : o0 + no_max], pad, pad)
        o = pad + offsets[0]
        acc = _bc(vals[0], xv) * xp[o : o + no_max]
        for d in range(1, len(offsets)):
            o = pad + offsets[d]
            acc = acc + _bc(vals[d], xv) * xp[o : o + no_max]
        return acc

    kk = dA.dia_kk
    code_row = dA.dia_code_row
    interpret = dA.backend.devices()[0].platform != "tpu"

    def _lane_rows(xv):
        # the kernel's view of a frame, or of K of them as (K, W)
        from ..ops.pallas_dia import LANES

        return xv.reshape(xv.shape[:-1] + (-1, LANES))

    def _dia_coded_full(cb, no, codes, xv):
        # zero-copy hot path: xv IS the kernel frame (padded layout); the
        # result is a full vector with every non-owned slot exactly zero
        from ..ops.pallas_dia import LANES, dia_coded_padded_pallas

        y = dia_coded_padded_pallas(
            cb, no.astype(jnp.int32), codes, _lane_rows(xv), offsets,
            kk, code_row, pplan, xv.shape[-1] // LANES, interpret=interpret,
            cls_pattern=dA.dia_cls_pattern,
        )
        return y.reshape(xv.shape)

    def _codes_stream(codes, j):
        """Stream ``j`` of the staged codes as (no_max,) int32: unpacked
        (S, no_max) bytes off-plan, nibble-unpacked from the kernel's
        packed (ceil(S/2), nlen//LANES, LANES) staging on the padded
        plan (`pack_nibble_codes`: two streams per byte, low nibble =
        even stream index)."""
        if pplan is None:
            return codes[j].astype(jnp.int32)
        raw = codes.reshape(codes.shape[0], -1).astype(jnp.uint8)
        byte = raw[j // 2, :no_max]
        nib = (byte >> 4) if (j % 2) else (byte & 0xF)
        return nib.astype(jnp.int32)

    def _dia_coded_xla(cb, no, codes, xv):
        xp = _tpad(xv[o0 : o0 + no_max], pad, pad)
        acc = None
        for d in range(len(offsets)):
            o = pad + offsets[d]
            shifted = xp[o : o + no_max]
            if kk[d] == 1:
                term = cb[d, 0] * shifted
            else:
                term = (
                    _bc(jnp.take(cb[d], _codes_stream(codes, code_row[d])), xv)
                    * shifted
                )
            acc = term if acc is None else acc + term
        return jnp.where(_bc(jnp.arange(no_max) < no[0], xv), acc, 0)

    # the plan's VMEM gate did not include the direction-fold variant's
    # operand rings / combined-copy / p-output blocks: `_pfold_fits`
    # re-checks them and the body falls back to the jnp fold where they
    # do not fit. The SDC modes (abft/audit) keep this kernel OFF: the
    # audit's operand switch and the checksum's exchanged-operand capture
    # both live in the XLA fold — the ABFT-off guard with XLA fallback,
    # mirroring the K>1 precedent
    _pfold_in_kernel = (
        pfold and pplan is not None and dA.dia_cb is not None
        and not abft and not audit and _pfold_fits(dA)
    )

    def _dia_coded_full_pfold(cb, no, codes, rv, pv, beta):
        from ..ops.pallas_dia import LANES, dia_coded_padded_pallas

        y, pnew = dia_coded_padded_pallas(
            cb, no.astype(jnp.int32), codes, _lane_rows(rv),
            offsets, kk, code_row, pplan, rv.shape[-1] // LANES,
            interpret=interpret, cls_pattern=dA.dia_cls_pattern,
            pfold=(
                _lane_rows(pv),
                jnp.reshape(beta, (-1,)).astype(rv.dtype),
            ),
        )
        return y.reshape(rv.shape), pnew.reshape(rv.shape)

    if columns:
        # `jax.vmap` over the columns of a lane-major block operand
        # (`make_block_cg_fn`): Pallas on the TPU cannot batch an operand
        # it leaves in HBM for the kernel's own DMAs, so the two kernel
        # callers take the K frames as they are, (K, W), and the kernel
        # walks them on a leading grid axis; everything around them (the
        # exchange, the boundary rows, the jnp fold) batches by itself
        def _over_columns(caller):
            batched = jax.custom_batching.custom_vmap(caller)

            @batched.def_vmap
            def _(axis_size, in_batched, cb, no, codes, *frames):
                assert not any(in_batched[:3]), "one operator, K columns"
                frames = [
                    f if b else jnp.broadcast_to(f, (axis_size,) + f.shape)
                    for f, b in zip(frames, in_batched[3:])
                ]
                out = caller(cb, no, codes, *frames)
                return out, jax.tree.map(lambda _: True, out)

            return batched

        _dia_coded_full = _over_columns(_dia_coded_full)
        _dia_coded_full_pfold = _over_columns(_dia_coded_full_pfold)

    def _aoo(xv, m):
        """The A_oo block applied to xv: ``(full, partial_)`` with
        exactly one non-None — `full` is a complete row-frame vector
        (padded coded kernel), `partial_` an owned-region array."""
        if mode == "coded":
            # coded-diagonal path: 1 byte/element per non-constant
            # diagonal, decoded against the SMEM codebook — independent of
            # the wire, so it still overlaps the halo collective. The
            # Pallas kernel takes 1-D frames; a (W, K) block operand
            # decodes the same codebooks through the XLA
            # shifted-broadcast form.
            if pplan is not None and xv.ndim == 1:
                return _dia_coded_full(m["cb"], m["no"], m["codes"], xv), None
            return None, _dia_coded_xla(m["cb"], m["no"], m["codes"], xv)
        if offsets is not None:  # owned block first: overlaps the wire
            if pplan is not None and xv.ndim == 1:
                return None, _dia_rowsum_pallas(m["oo_v"], xv)
            with jax.named_scope(SCOPE_DIA_XLA):
                return None, _dia_rowsum(_dia_vals_dense(m["oo_v"]), xv)
        if dA.sd_bs is not None:
            # supernode-dense path: self blocks arrive by RESHAPE of the
            # owned region (no gather), only the per-group external
            # unions are gathered (~4x fewer element-at-a-time gather
            # steps than BSR), and the products run as one batched MXU
            # einsum per WIDTH BUCKET over the densified group blocks
            # (each contiguous chunk of groups padded to its own union
            # maximum — round-5 directive 3)
            bs, G = dA.sd_bs, dA.sd_g
            cl = dA.col_plan.layout
            tail = xv.shape[1:]  # () or (K,)
            yn = xv[cl.o0 : cl.o0 + cl.no_max].reshape((-1, bs) + tail)
            ngr = sum(i.shape[0] for i in m["sd_i"])
            nn = yn.shape[0]
            yp = (
                jnp.pad(
                    yn,
                    ((0, ngr * G - nn), (0, 0)) + ((0, 0),) * len(tail),
                )
                if ngr * G > nn
                else yn
            )
            outs = []
            g0_ = 0
            # block operands widen the per-bucket group product from a
            # (G·bs, U·bs) @ (U·bs,) matvec to ONE (G·bs, U·bs) @
            # (U·bs, K) MXU einsum — the densified group blocks stream
            # from HBM once per K columns
            eq = "grc,gck->grk" if tail else "grc,gc->gr"
            for idx_c, val_c in zip(m["sd_i"], m["sd_v"]):
                len_c, emax_c = idx_c.shape
                with jax.named_scope(SCOPE_SD_GATHER):
                    xs = yp[g0_ * G : (g0_ + len_c) * G].reshape(
                        (len_c, G * bs) + tail
                    )
                    xe = yn[idx_c].reshape((len_c, emax_c * bs) + tail)
                    xg = jnp.concatenate([xs, xe], axis=1)
                with jax.named_scope(SCOPE_SD_EINSUM):
                    outs.append(
                        jnp.einsum(
                            eq, val_c, xg,
                            preferred_element_type=xv.dtype,
                            precision=jax.lax.Precision.HIGHEST,
                        )
                    )
                g0_ += len_c
            with jax.named_scope(SCOPE_SD_EINSUM):
                return None, jnp.concatenate(outs, axis=0).reshape(
                    (-1,) + tail
                )[:no_max]
        if dA.bsr_bs is not None:
            # node-block gather: one index per bs×bs block (~bs²× fewer
            # element-at-a-time gathers than ELL), block products as one
            # batched einsum — the irregular-graph fast path
            bs = dA.bsr_bs
            cl = dA.col_plan.layout
            tail = xv.shape[1:]
            yn = xv[cl.o0 : cl.o0 + cl.no_max].reshape((-1, bs) + tail)
            with jax.named_scope(SCOPE_BSR_GATHER):
                xg = yn[m["bsr_c"]]  # (nn, Lb, bs[, K])
            # HIGHEST precision: at DEFAULT the TPU MXU would run this f32
            # dot as lossy bf16 passes, silently breaking the "matches the
            # sequential oracle to FMA rounding" accuracy contract
            with jax.named_scope(SCOPE_BSR_EINSUM):
                return None, jnp.einsum(
                    "nlij,nljk->nik" if tail else "nlij,nlj->ni",
                    m["bsr_v"], xg,
                    preferred_element_type=xv.dtype,
                    precision=jax.lax.Precision.HIGHEST,
                ).reshape((-1,) + tail)
        return None, _ell_rowsum(m["oo_v"], m["oo_c"], xv)

    def _oh_slabs(y, xv, coef):
        """The boundary rows in their face-slab form (see
        `DeviceMatrix._detect_oh_slabs`): per class, the ghost sub-box
        of the direction's segment times its coefficients, added into
        the row sub-box of the owned block. Static slices only.

        How a row sub-box is addressed (PERF.md, PR 29, has what each
        form read on the chip). Flattened from its axis ``a`` on, the
        owned block is ``box[:a] + (prod(box[a:]),)``, and a sub-box
        padded to whole steps of axis ``a`` is one run of that last
        axis. A class takes the smallest ``a`` whose run stays within
        `OH_SLAB_MAX_FILL` times the class: 0 for a face normal to the
        slowest axis, which is then a slice of the flat frame itself,
        updated in place; 1 for the next axis, and so on. The classes
        of one ``a`` > 0 share one view of the owned block, and where
        the flattened axis is whole 128-lane rows the view splits it
        into ``(rows, LANES)``: on the padded frame that is the flat
        order itself (a reshape to the box's own shape is a relayout of
        the whole block there, in and out), and a run widens to the
        lane rows it touches."""
        from ..ops.pallas_dia import LANES
        from .tpu_box import slab_split_axis

        cl = dA.col_layout
        box = cl.box_info.box_shape
        dim = len(box)
        no = math.prod(box)
        tail = xv.shape[1:]
        keep = [(0, 0)] * len(tail)

        def _slice_add(view, starts, upd):
            starts = tuple(starts) + (0,) * (view.ndim - len(starts))
            limits = tuple(a + n for a, n in zip(starts, upd.shape))
            return jax.lax.dynamic_update_slice(
                view, jax.lax.slice(view, starts, limits) + upd, starts
            )

        by_split = {}
        for s in dA.ohs_geo:
            by_split.setdefault(slab_split_axis(box, s.shape), []).append(s)
        for a, classes in sorted(by_split.items()):
            step = math.prod(box[a + 1 :])  # elements a step of axis a spans
            run = box[a] * step
            lanes = a > 0 and run % LANES == 0
            if a == 0:
                view, base = y, o0
            else:
                last = (run // LANES, LANES) if lanes else (run,)
                view = y[o0 : o0 + no].reshape(box[:a] + last + tail)
                base = 0
            for s in classes:
                seg = xv[
                    cl.g0 + s.seg : cl.g0 + s.seg + math.prod(s.slab)
                ].reshape(s.slab + tail)[
                    tuple(slice(g, g + n) for g, n in zip(s.ghost_lo, s.shape))
                ]
                c = coef[s.v0 : s.v0 + math.prod(s.shape)]
                upd = c.reshape(s.shape + (1,) * len(tail)) * seg
                # whole steps of axis a: the axes behind it out to the box
                upd = jnp.pad(
                    upd,
                    [(0, 0)] * (a + 1)
                    + [
                        (s.row_lo[j], box[j] - s.row_lo[j] - s.shape[j])
                        for j in range(a + 1, dim)
                    ]
                    + keep,
                ).reshape(s.shape[:a] + (s.shape[a] * step,) + tail)
                lo = base + s.row_lo[a] * step
                hi = lo + s.shape[a] * step
                if lanes:
                    r0, r1 = lo // LANES, -(-hi // LANES)
                    upd = jnp.pad(
                        upd,
                        [(0, 0)] * a
                        + [(lo - r0 * LANES, r1 * LANES - hi)]
                        + keep,
                    ).reshape(s.shape[:a] + (r1 - r0, LANES) + tail)
                    view = _slice_add(view, s.row_lo[:a] + (r0,), upd)
                else:
                    view = _slice_add(view, s.row_lo[:a] + (lo,), upd)
            y = (
                view if a == 0
                else jax.lax.dynamic_update_slice_in_dim(
                    y, view.reshape((no,) + tail), o0, 0
                )
            )
        return y

    def _oh_rows(y, xv, m):
        """The ghost (A_oh) contribution, added on the boundary rows only
        (padded rows target the trash slot with exact-zero values), in
        the form the operator was staged in; every form under `SCOPE_OH`."""
        tail = xv.shape[1:]
        if dA.ohb_bs is not None:
            # node-block boundary path (directive 7): one gather per
            # ghost NODE, block products as a batched einsum — same
            # structure as the A_oo SD/BSR paths. BUCKETED like the
            # owned SD groups: each contiguous chunk of boundary
            # nodes is padded to its own block-row maximum, one
            # einsum per bucket (round-4 directive 7 leftover).
            bs_ = dA.ohb_bs
            cl2 = dA.col_plan.layout
            nhn = (cl2.W - cl2.g0 - 1) // bs_
            gh = xv[cl2.g0 : cl2.g0 + nhn * bs_].reshape((-1, bs_) + tail)
            for rows_c, cols_c, vals_c in zip(
                m["ohb_r"], m["ohb_c"], m["ohb_v"]
            ):
                xb = gh[cols_c]
                yb = jnp.einsum(
                    "nlij,nljk->nik" if tail else "nlij,nlj->ni",
                    vals_c, xb,
                    preferred_element_type=xv.dtype,
                    precision=jax.lax.Precision.HIGHEST,
                )
                y = y.at[rows_c].add(yb.reshape(rows_c.shape + tail))
            return y
        if dA.ohs_geo is not None:
            return _oh_slabs(y, xv, m["ohs_v"])
        return y.at[m["oh_r"]].add(_ell_rowsum(m["oh_v"], m["oh_c"], xv))

    _oh_rows = _scoped(SCOPE_OH, _oh_rows)

    def _embed_scope():
        # the streamed kernel's product embedded in a frame again is one of
        # its copies; every other lowering's embedding keeps the phase's name
        if mode == "stream" and pplan is not None:
            return jax.named_scope(SCOPE_DIA_EMBED)
        return contextlib.nullcontext()

    def _finish(full, partial_, xv, m):
        """Shared SpMV tail: halo-exchange the operand, embed the A_oo
        product in the row frame, add the boundary (A_oh) contribution.
        Returns (y, exchanged operand, exchange checksum delta, scale) —
        the checksum pair is None unless ``abft``."""
        if abft:
            xv, exd, exs = exch(xv, m["si"], m["sm"], m["ri"])
        else:
            exd = exs = None
            xv = exch(xv, m["si"], m["sm"], m["ri"])
        tail = xv.shape[1:]  # () or (K,) for a multi-RHS block
        if full is not None:
            y = full  # already a complete vector, pads exactly zero
        else:
            # the product lives in the ROW-layout frame: for rectangular
            # operators (restriction/prolongation transfers) the column
            # frame can be narrower than the row count
            with _embed_scope():
                y = jnp.zeros((layout.W,) + tail, dtype=xv.dtype).at[
                    o0 : o0 + no_max
                ].set(partial_)
        if dA.oh_nnz:
            y = _oh_rows(y, xv, m)
            y = y.at[g0:].set(0)
        return y, xv, exd, exs

    def body(xv, m):
        full, partial_ = _aoo(xv, m)
        y, xv, exd, exs = _finish(full, partial_, xv, m)
        return (y, xv, exd, exs) if abft else (y, xv)

    def body_pfold(rv, pv, beta, m, mvv=None, aud=None, audx=None):
        """Fused-CG leading-edge fold: materialize the next search
        direction ``p = z + beta*pv`` (``z = mvv*rv`` when a diagonal
        preconditioner row is supplied, else ``rv``) INSIDE the SpMV
        pass, and return ``(A p, p)``. On the coded padded path the fold
        rides the Pallas kernel's window DMA (`_padded_kernel`
        has_pfold) so p is never read back for the band sum; on every
        other lowering the fold is a jnp expression adjacent to the A_oo
        read, which XLA fuses into the operand's first touch. Note the
        halo pack depends on the folded p, so the wire no longer fully
        overlaps the A_oo compute — a surface-sized effect that the
        fused body's saved volume sweeps dominate.

        Contract of the returned pair, on the Pallas fold and the jnp
        fold alike: ``p`` and ``A p`` are whole frames, exactly zero off
        the owned band (ghost and trash slots always; a part's pad rows
        whenever ``rv`` and ``pv`` are zero there, as the loop's are).
        `make_cg_fn`'s fused body rests on it: ``p`` is carried to the
        next trip as it is, and x and r are updated on the owned slice
        alone.

        ``aud``/``audx`` (the SDC audit switch, built only under
        ``audit``): on an audit trip the folded direction is REPLACED by
        ``audx`` (the current iterate), so the body's one SpMV call site
        computes ``A x`` for the true-residual cross-check while the
        recurrence state stays frozen — no second SpMV, no extra
        collectives in the lowered program."""
        colL = dA.col_plan.layout
        cs = slice(colL.o0, colL.o0 + colL.no_max)
        if _pfold_in_kernel and mvv is None and rv.ndim == 1:
            # has_pfold Pallas kernel, 1-D frames only: a (W, K) block
            # operand takes the fused jnp fold below instead
            full, pnew = _dia_coded_full_pfold(
                m["cb"], m["no"], m["codes"], rv, pv, beta
            )
            partial_ = None
        else:
            # beta is a scalar (K=1) or a (K,) per-column vector — both
            # broadcast against the trailing axis of the owned slice
            with jax.named_scope(SCOPE_AXPY):
                z = _bc(mvv[cs], rv) * rv[cs] if mvv is not None else rv[cs]
                pnew = jnp.zeros_like(rv).at[cs].set(
                    z + _rp(beta * pv[cs])
                )
                if aud is not None:
                    # audit trips stream A·x through the same call site;
                    # a non-audit trip selects the folded direction
                    # bit-exactly
                    pnew = jnp.where(aud, audx, pnew)
            full, partial_ = _aoo(pnew, m)
        y, xpost, exd, exs = _finish(full, partial_, pnew, m)
        return (y, pnew, xpost, exd, exs) if abft else (y, pnew)

    return _scoped(SCOPE_SPMV, body_pfold if pfold else body)


def _shard_ops(jax, ms):
    """Strip the leading (length-1) shard axis from every operand leaf
    (dicts of arrays, and the SD lowering's per-bucket tuples)."""
    return jax.tree.map(lambda v: v[0], ms)


def make_spmv_fn(dA: DeviceMatrix) -> Callable:
    """Compiled y = A @ x over the mesh: returns a function mapping the
    (P, Wc) column-range vector to the (P, Wr) row-range product (ghost
    slots of y zero, like the host mul). A (P, Wc, K) multi-RHS block
    maps to the (P, Wr, K) block product — one operator stream per K
    columns (the body is rank-polymorphic; jit re-traces per rank)."""
    import jax
    shard_map = jax.shard_map

    mesh = dA.backend.mesh(dA.row_layout.P)
    spec = dA.backend.parts_spec()
    body = _spmv_body(dA)
    ops = _matrix_operands(dA)
    specs = jax.tree.map(lambda _: spec, ops)
    shape = (dA.col_plan.layout.P, dA.col_plan.layout.W)

    @jax.jit
    def fn(x, m):
        def shard_fn(xs, ms):
            y, _ = body(xs[0], _shard_ops(jax, ms))
            return y[None]

        return shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec, specs),
            out_specs=spec,
            check_vma=False,
        )(x, m)

    def run(x):
        check(
            tuple(x.shape[:2]) == shape and x.ndim in (2, 3),
            f"spmv: vector laid out {tuple(x.shape)}, matrix expects "
            f"{shape} (optionally + a trailing rhs-batch axis) — build "
            "vectors with the matrix's col_layout",
        )
        return fn(x, ops)

    return run


def make_cg_fn(
    dA: DeviceMatrix, tol: float, maxiter: int, precond: bool = False,
    fused: Optional[bool] = None, rhs_batch: Optional[int] = None,
) -> Callable:
    """The whole CG solve as ONE compiled shard_map program:
    `lax.while_loop` whose body does the overlapped SpMV, deterministic
    all-gather dots, and owned-region axpys. With ``precond`` the loop is
    preconditioned CG against a diagonal preconditioner supplied as an
    extra (P, W) operand (owned slots = inverse diagonal). Returns
    (x_stacked, iterations, final_residual). PERF.md section 5 has where
    an iteration's time goes on the chip.

    ``fused`` (default: `_fused_cg_enabled()` — ON except strict-bits,
    ``PA_TPU_FUSED_CG=0`` reverts) selects the fused streaming body:

    * the solution/residual updates ``x += α·p``, ``r -= α·q`` and the
      ``r·r`` (and ``r·z``) dot partials run in ONE sweep over the owned
      region — a structured jnp block XLA fuses (collective count pinned
      by tests/test_fused_cg.py); the preconditioned pair of reductions
      rides one shared all_gather;
    * the direction update ``p = z + β·p`` folds into the leading edge
      of the NEXT SpMV pass (`_spmv_body(pfold=True)` — in-kernel on the
      coded padded path, a fused jnp expression on the BSR/SD/ELL/XLA
      lowerings);
    * x, r and the previous direction are three (W,) while-loop carries,
      x and r updated where they lie; the fold's ``p`` is the next
      trip's carry as the SpMV pass wrote it.

    Every scalar follows the textbook recurrence on the same dots in the
    same order, so the iteration trajectory is IDENTICAL to the standard
    body (bit-identical under strict-bits arithmetic — pinned on the
    4-part conformance fixture by tests/test_fused_cg.py). The standard
    (unfused) body remains the strict-bits oracle and the default when
    ``PA_TPU_FUSED_CG=0``.

    ``rhs_batch=K`` selects the BLOCK (multi-RHS) program instead: the
    operands become (P, W, K) slabs, the operator streams once per K
    columns (`_spmv_body`'s rank-polymorphic lowerings), and every
    column runs the textbook single-vector recurrence with per-column
    scalars — see `make_block_cg_fn`, to which this delegates."""
    import jax
    import jax.numpy as jnp
    shard_map = jax.shard_map

    if rhs_batch is not None:
        return make_block_cg_fn(
            dA, tol, maxiter, rhs_batch, precond=precond, fused=fused
        )
    fused = _resolve_fused(fused)
    mesh = dA.backend.mesh(dA.row_layout.P)
    spec = dA.backend.parts_spec()
    none_spec = jax.sharding.PartitionSpec()
    # the SDC defense (in-graph ABFT checksums + true-residual audit +
    # device-resident rollback ring) — None resolves to the exact
    # pre-SDC program.
    sdccfg = _sdc_config(maxiter)
    abft_on = bool(sdccfg and sdccfg["abft"])
    # device α/β trace ring (PA_TRACE_ITERS, telemetry): a (Ht, 2)
    # replicated carry written on committed iterations only — no new
    # collectives (alpha/beta are scalars the dot gathers already
    # replicated). Depth 0 (the default) leaves the traced program
    # byte-identical to the pre-telemetry one.
    Ht = int(min(_trace_config(), maxiter))
    body_spmv = _spmv_body(dA, abft=abft_on)
    body_pfold = (
        _spmv_body(dA, pfold=True, abft=abft_on, audit=sdccfg is not None)
        if fused
        else None
    )
    no_max = dA.row_layout.no_max
    o0 = dA.row_layout.o0
    g0 = dA.row_layout.g0
    pdot = _pdot_factory(o0, no_max)
    odot1, odot2 = _pdot_owned_factory(no_max)
    dox = _pdot_extra_factory(0, no_max) if sdccfg is not None else None
    ops = _matrix_operands(dA)
    specs = jax.tree.map(lambda _: spec, ops)
    strict = strict_bits()

    def _rp(t):
        # strict mode: round the axpy products separately (block FMA
        # contraction) so the update arithmetic matches the host loop's
        return _strict_rounded_product(t) if strict else t

    # per-iteration residual history, fixed-shape for the while_loop carry
    # (capped: a convergence curve beyond this many entries is truncated)
    H = int(min(maxiter + 1, 4096))

    @jax.jit
    def fn(b, x0, mv, m):
        def shard_fn(bs, x0s, mvs, ms):
            bv, xv = bs[0], x0s[0]
            mats = _shard_ops(jax, ms)
            mvv = mvs[0]

            def spmv(z):
                if abft_on:
                    y, _, _, _ = body_spmv(z, mats)
                else:
                    y, _ = body_spmv(z, mats)
                return y

            def apply_minv(r):
                if not precond:
                    return r
                return jnp.zeros_like(r).at[o0 : o0 + no_max].set(
                    mvv[o0 : o0 + no_max] * r[o0 : o0 + no_max]
                )

            q = spmv(xv)
            # rows-range residual, owned region only (pads stay zero)
            r = jnp.zeros_like(xv).at[o0 : o0 + no_max].set(
                bv[o0 : o0 + no_max] - q[o0 : o0 + no_max]
            )
            z = apply_minv(r)
            p = jnp.zeros_like(xv).at[o0 : o0 + no_max].set(z[o0 : o0 + no_max])
            rs0 = pdot(r, r)
            rz0 = pdot(r, z) if precond else rs0
            hist = jnp.full(H, jnp.nan, dtype=bv.dtype).at[0].set(jnp.sqrt(rs0))

            if sdccfg is not None:
                # ---- SDC-defended loop (ABFT + audit + rollback) ----
                # Same recurrence arithmetic as the plain bodies below;
                # on a clean run every commit-trip value is selected
                # bit-exactly (jnp.where with a False predicate), so the
                # trajectory is bitwise identical to sdccfg=None — the
                # test_abft.py strict-bits pin. Three trip kinds:
                #   commit — a real iteration (state advances),
                #   audit  — every `ae` real iterations the ONE SpMV
                #            call site streams A·x instead of A·p (an
                #            operand select, so the lowered program has
                #            the same collectives), the true residual is
                #            cross-checked, and a passing state is
                #            pushed onto the device-resident ring,
                #   restore — a detection (checksum trip or failed
                #            audit) re-selects the newest ring state:
                #            the in-memory rollback, escalating via the
                #            `esc` exit flag once `mrb` rollbacks are
                #            spent.
                ae = sdccfg["ae"]
                R = sdccfg["R"]
                mrb = sdccfg["mrb"]
                fault = sdccfg["fault"]
                trip_max = sdccfg["trip_max"]
                cs_tol, audit_tol = _sdc_tolerances(
                    bv.dtype, dA.row_layout.P, no_max
                )
                tiny = float(np.finfo(np.dtype(bv.dtype)).tiny)
                athr2 = (
                    audit_tol * jnp.maximum(1.0, jnp.sqrt(rs0))
                ) ** 2
                i32 = jnp.int32
                slf = slice(o0, o0 + no_max)
                false = jnp.bool_(False)

                def inject(q, trip):
                    """PA_FAULT_DEVICE: the compiled loop's chaos seam —
                    a finite perturbation of q's first owned slot at ONE
                    trip index (trips never replay, so it is one-shot),
                    applied before the checksum so detection and
                    recurrence see the same corrupted product."""
                    if fault is None:
                        return q
                    hit = jnp.logical_and(
                        trip == fault["trip"],
                        jax.lax.axis_index("parts") == fault["part"],
                    )
                    bump = jnp.where(
                        hit, fault["factor"] * (1.0 + jnp.abs(q[o0])), 0.0
                    )
                    return q.at[o0].add(bump.astype(q.dtype))

                def cs_lanes(q, xpost, exd, exs):
                    """The ABFT identity c·(A x) vs (c·A)·x plus the
                    exchange-round deltas, as two reduction lanes for
                    the dot gather (f64 accumulation when staged so)."""
                    wv = mats["abft_w"]
                    t = wv * xpost.astype(wv.dtype)
                    qo = q[slf].astype(wv.dtype)
                    delta = jnp.abs(jnp.sum(qo) - jnp.sum(t)) + jnp.abs(
                        exd
                    ).astype(wv.dtype)
                    scale = (
                        jnp.sum(jnp.abs(qo))
                        + jnp.sum(jnp.abs(t))
                        + exs.astype(wv.dtype)
                    )
                    return (
                        delta.astype(bv.dtype),
                        scale.astype(bv.dtype),
                    )

                def sdc_init(S0, sc0):
                    return (
                        jnp.stack([S0] * R),
                        jnp.stack([sc0] * R),
                        jnp.zeros((R,), i32),
                        i32(0),  # since last audit
                        i32(0),  # strike (ring slot to restore)
                        i32(0),  # rollbacks
                        i32(0),  # detections
                        i32(0),  # audits
                        false,   # escalated
                        i32(0),  # trip
                    )

                def sdc_next(sdcst, aud, detect, cur_fn, cursc, it):
                    """Shared carry transition: ring push on audit pass,
                    strike/rollback bookkeeping, escalation latch. The
                    ring shift sits behind a lax.cond so commit trips
                    (the overwhelmingly common case) pass the R·3·W ring
                    buffers through untouched instead of paying a
                    full-ring select every iteration; ``cur_fn`` builds
                    the pushed snapshot INSIDE the taken branch, so the
                    stack never materializes on commit trips."""
                    (ring, ringsc, ringit, since, strike, rollbacks,
                     dets, audits, esc, trip) = sdcst
                    exhausted = rollbacks >= mrb
                    restore = jnp.logical_and(
                        detect, jnp.logical_not(exhausted)
                    )
                    esc2 = jnp.logical_or(
                        esc, jnp.logical_and(detect, exhausted)
                    )
                    apass = jnp.logical_and(aud, jnp.logical_not(detect))
                    ring2, ringsc2, ringit2 = jax.lax.cond(
                        apass,
                        lambda: (
                            jnp.concatenate(
                                [cur_fn()[None], ring[:-1]], axis=0
                            ),
                            jnp.concatenate(
                                [cursc[None], ringsc[:-1]], axis=0
                            ),
                            jnp.concatenate(
                                [it[None].astype(i32), ringit[:-1]], axis=0
                            ),
                        ),
                        lambda: (ring, ringsc, ringit),
                    )
                    since2 = jnp.where(
                        jnp.logical_or(aud, restore), 0, since + 1
                    )
                    strike2 = jnp.where(
                        restore,
                        jnp.minimum(strike + 1, R - 1),
                        jnp.where(apass, 0, strike),
                    )
                    sdc2 = (
                        ring2, ringsc2, ringit2, since2, strike2,
                        rollbacks + restore.astype(i32),
                        dets + detect.astype(i32),
                        audits + aud.astype(i32),
                        esc2, trip + 1,
                    )
                    return sdc2, restore

                def sdc_out(sdcst):
                    (_r1, _r2, _r3, _s, _k, rollbacks, dets, audits,
                     esc, trip) = sdcst
                    return jnp.stack(
                        [dets, rollbacks, audits, esc.astype(i32), trip]
                    )

                def cs_detect(ex_out):
                    if not abft_on:
                        return false
                    delta, scale = ex_out
                    return delta > cs_tol * (scale + tiny)

                if fused:
                    S0 = jnp.stack([xv, r, jnp.zeros_like(xv)])
                    zero = jnp.zeros((), bv.dtype)
                    sdc0 = sdc_init(S0, jnp.stack([rs0, rz0, zero]))

                    def cond_fs(state):
                        _S, rz_, rs_, _beta, it_ = state[:5]
                        sdcst = state[6]
                        esc_, trip_ = sdcst[8], sdcst[9]
                        go = jnp.logical_and(
                            jnp.sqrt(rs_)
                            > tol * jnp.maximum(1.0, jnp.sqrt(rs0)),
                            it_ < maxiter,
                        )
                        go = jnp.logical_and(go, jnp.isfinite(rs_))
                        if precond:
                            go = jnp.logical_and(go, rz_ != 0)
                        go = jnp.logical_and(go, trip_ < trip_max)
                        return jnp.logical_and(
                            go, jnp.logical_not(esc_)
                        )

                    def step_fs(state):
                        if Ht:
                            S, rz, rs, beta, it, hist, sdcst, ab = state
                        else:
                            S, rz, rs, beta, it, hist, sdcst = state
                            ab = None
                        trip = sdcst[9]
                        since = sdcst[3]
                        aud = (since >= ae) if ae > 0 else false
                        x, r_, p_prev = S[0], S[1], S[2]
                        pf = body_pfold(
                            r_, p_prev, beta, mats,
                            mvv if precond else None,
                            aud=aud if ae > 0 else None, audx=x,
                        )
                        if abft_on:
                            q, p_, xpost, exd, exs = pf
                            q = inject(q, trip)
                            extras = cs_lanes(q, xpost, exd, exs)
                        else:
                            q, p_ = pf
                            q = inject(q, trip)
                            extras = ()
                        if ae > 0:
                            # audit trips stream d = (b - A x) - r into
                            # BOTH dot operands (the site computes
                            # ||d||²); lax.cond keeps the subtraction
                            # sweeps off the commit trips entirely
                            def _aud_ops():
                                d = bv[slf] - q[slf] - r_[slf]
                                return d, d

                            s1a, s1b = jax.lax.cond(
                                aud, _aud_ops,
                                lambda: (p_[slf], q[slf]),
                            )
                        else:
                            s1a, s1b = p_[slf], q[slf]
                        pqdd, ex_out = dox(s1a, s1b, extras)
                        cs_trip = cs_detect(ex_out)
                        alpha = rz / pqdd
                        xo = x[slf] + _rp(alpha * p_[slf])
                        ro = r_[slf] + _rp(-alpha * q[slf])
                        if precond:
                            zo = mvv[slf] * ro
                            rz_new, rs_new = odot2(ro, zo, ro, ro)
                        else:
                            rs_new = odot1(ro, ro)
                            rz_new = rs_new
                        beta_new = rz_new / rz
                        audit_fail = jnp.logical_and(aud, pqdd > athr2)
                        detect = jnp.logical_or(cs_trip, audit_fail)
                        commit = jnp.logical_and(
                            jnp.logical_not(aud), jnp.logical_not(detect)
                        )
                        sdc2, restore = sdc_next(
                            sdcst, aud, detect, lambda: S,
                            jnp.stack([rs, rz, beta]), it,
                        )
                        j = jnp.minimum(sdcst[4], R - 1)
                        S_step = (
                            S.at[0, slf].set(xo)
                            .at[1, slf].set(ro)
                            .at[2, slf].set(p_[slf])
                        )
                        # one 3-way branch instead of nested full-frame
                        # selects: commit trips return the stepped state
                        # directly, bit-exactly
                        branch = jnp.where(
                            commit, 0, jnp.where(restore, 2, 1)
                        ).astype(jnp.int32)
                        S3, rs3, rz3, beta3, it3 = jax.lax.switch(
                            branch,
                            [
                                lambda: (
                                    S_step, rs_new, rz_new, beta_new,
                                    it + 1,
                                ),
                                lambda: (S, rs, rz, beta, it),
                                lambda: (
                                    sdcst[0][j], sdcst[1][j, 0],
                                    sdcst[1][j, 1], sdcst[1][j, 2],
                                    sdcst[2][j],
                                ),
                            ],
                        )
                        idx = jnp.minimum(it + 1, H - 1)
                        hist2 = hist.at[idx].set(
                            jnp.where(commit, jnp.sqrt(rs_new), hist[idx])
                        )
                        out = (S3, rz3, rs3, beta3, it3, hist2, sdc2)
                        if Ht:
                            # α/β of real iteration `it`, committed trips
                            # only (audit/restore trips change no state);
                            # true ring — keeps the LAST Ht iterations
                            ti = it % Ht
                            out = out + (ab.at[ti].set(jnp.where(
                                commit, jnp.stack([alpha, beta_new]),
                                ab[ti],
                            )),)
                        return out

                    init_fs = (S0, rz0, rs0, jnp.zeros((), bv.dtype),
                               jnp.int32(0), hist, sdc0)
                    if Ht:
                        init_fs = init_fs + (
                            jnp.zeros((Ht, 2), dtype=bv.dtype),
                        )
                    fin = _krylov_loop(cond_fs, step_fs, init_fs)
                    S, rs, it, hist, sdcst = (
                        fin[0], fin[2], fin[4], fin[5], fin[6]
                    )
                    out = (S[0][None], rs, rs0, it, hist, sdc_out(sdcst))
                    return out + ((fin[7],) if Ht else ())

                sdc0 = sdc_init(
                    jnp.stack([xv, r, p]),
                    jnp.stack([rs0, rz0, jnp.zeros((), bv.dtype)]),
                )

                def cond_ss(state):
                    _x, _r, _p, rz_, rs_, it_ = state[:6]
                    sdcst = state[7]
                    esc_, trip_ = sdcst[8], sdcst[9]
                    go = jnp.logical_and(
                        jnp.sqrt(rs_)
                        > tol * jnp.maximum(1.0, jnp.sqrt(rs0)),
                        it_ < maxiter,
                    )
                    go = jnp.logical_and(go, jnp.isfinite(rs_))
                    if precond:
                        go = jnp.logical_and(go, rz_ != 0)
                    go = jnp.logical_and(go, trip_ < trip_max)
                    return jnp.logical_and(go, jnp.logical_not(esc_))

                def step_ss(state):
                    if Ht:
                        x, r_, p_, rz, rs, it, hist, sdcst, ab = state
                    else:
                        x, r_, p_, rz, rs, it, hist, sdcst = state
                        ab = None
                    trip = sdcst[9]
                    since = sdcst[3]
                    aud = (since >= ae) if ae > 0 else false
                    opnd = jnp.where(aud, x, p_) if ae > 0 else p_
                    if abft_on:
                        q, xpost, exd, exs = body_spmv(opnd, mats)
                        q = inject(q, trip)
                        extras = cs_lanes(q, xpost, exd, exs)
                    else:
                        q, _ = body_spmv(opnd, mats)
                        q = inject(q, trip)
                        extras = ()
                    if ae > 0:
                        # see step_fs: d computed only on audit trips
                        def _aud_ops():
                            d = bv[slf] - q[slf] - r_[slf]
                            return d, d

                        s1a, s1b = jax.lax.cond(
                            aud, _aud_ops,
                            lambda: (p_[slf], q[slf]),
                        )
                    else:
                        s1a, s1b = p_[slf], q[slf]
                    pqdd, ex_out = dox(s1a, s1b, extras)
                    cs_trip = cs_detect(ex_out)
                    alpha = rz / pqdd
                    x2 = x.at[slf].add(_rp(alpha * p_[slf]))
                    r2 = r_.at[slf].add(_rp(-alpha * q[slf]))
                    z2 = apply_minv(r2)
                    rz_new = pdot(r2, z2) if precond else None
                    rs_new = pdot(r2, r2)
                    if not precond:
                        rz_new = rs_new
                    beta = rz_new / rz
                    p2 = p_.at[slf].set(
                        z2[slf] + _rp(beta * p_[slf])
                    )
                    audit_fail = jnp.logical_and(aud, pqdd > athr2)
                    detect = jnp.logical_or(cs_trip, audit_fail)
                    commit = jnp.logical_and(
                        jnp.logical_not(aud), jnp.logical_not(detect)
                    )
                    sdc2, restore = sdc_next(
                        sdcst, aud, detect,
                        lambda: jnp.stack([x, r_, p_]),
                        jnp.stack([rs, rz, jnp.zeros((), bv.dtype)]),
                        it,
                    )
                    j = jnp.minimum(sdcst[4], R - 1)
                    branch = jnp.where(
                        commit, 0, jnp.where(restore, 2, 1)
                    ).astype(jnp.int32)
                    x3, r3, p3, rs3, rz3, it3 = jax.lax.switch(
                        branch,
                        [
                            lambda: (x2, r2, p2, rs_new, rz_new, it + 1),
                            lambda: (x, r_, p_, rs, rz, it),
                            lambda: (
                                sdcst[0][j, 0], sdcst[0][j, 1],
                                sdcst[0][j, 2], sdcst[1][j, 0],
                                sdcst[1][j, 1], sdcst[2][j],
                            ),
                        ],
                    )
                    idx = jnp.minimum(it + 1, H - 1)
                    hist2 = hist.at[idx].set(
                        jnp.where(commit, jnp.sqrt(rs_new), hist[idx])
                    )
                    out = (x3, r3, p3, rz3, rs3, it3, hist2, sdc2)
                    if Ht:
                        ti = it % Ht
                        out = out + (ab.at[ti].set(jnp.where(
                            commit, jnp.stack([alpha, beta]), ab[ti],
                        )),)
                    return out

                init_ss = (xv, r, p, rz0, rs0, jnp.int32(0), hist, sdc0)
                if Ht:
                    init_ss = init_ss + (
                        jnp.zeros((Ht, 2), dtype=bv.dtype),
                    )
                fin = _krylov_loop(cond_ss, step_ss, init_ss)
                x, rs, it, hist, sdcst = (
                    fin[0], fin[4], fin[5], fin[6], fin[7]
                )
                out = (x[None], rs, rs0, it, hist, sdc_out(sdcst))
                return out + ((fin[8],) if Ht else ())

            if fused:
                slf = slice(o0, o0 + no_max)
                # x, r and the previous direction are three (W,) carries,
                # updated where they lie. p_prev starts at 0 with beta 0,
                # so the first fold yields p_0 = z_0 exactly like the
                # standard body.
                zero = jnp.zeros((), bv.dtype)

                def cond_fused(state):
                    rz, rs, _beta, it = state[3:7]
                    go = jnp.logical_and(
                        jnp.sqrt(rs) > tol * jnp.maximum(1.0, jnp.sqrt(rs0)),
                        it < maxiter,
                    )
                    # same in-graph health guard as the standard body
                    go = jnp.logical_and(go, jnp.isfinite(rs))
                    if precond:
                        go = jnp.logical_and(go, rz != 0)
                    return go

                def step_fused(state):
                    x, r_, p_prev, rz, rs, beta, it, hist = state[:8]
                    # (b) direction fold rides the SpMV pass itself; its
                    # p is the next trip's p_prev as it is (whole frame,
                    # zero off the owned band: body_pfold's contract)
                    q, p = body_pfold(
                        r_, p_prev, beta, mats, mvv if precond else None
                    )
                    pq = pdot(p, q)
                    alpha = rz / pq
                    # (a) ONE sweep: both vector updates and the dot
                    # partial(s); the preconditioned pair of reductions
                    # shares one all_gather (odot2)
                    x = x.at[slf].add(_rp(alpha * p[slf]))
                    r_ = r_.at[slf].add(_rp(-alpha * q[slf]))
                    ro = r_[slf]
                    if precond:
                        zo = mvv[slf] * ro
                        rz_new, rs_new = odot2(ro, zo, ro, ro)
                    else:
                        rs_new = odot1(ro, ro)
                        rz_new = rs_new
                    beta_new = rz_new / rz
                    hist2 = hist.at[jnp.minimum(it + 1, H - 1)].set(
                        jnp.sqrt(rs_new)
                    )
                    out = (x, r_, p, rz_new, rs_new, beta_new, it + 1, hist2)
                    if Ht:
                        out = out + (state[8].at[it % Ht].set(
                            jnp.stack([alpha, beta_new])
                        ),)
                    return out

                init_f = (
                    xv, r, jnp.zeros_like(xv), rz0, rs0, zero, jnp.int32(0),
                    hist,
                )
                if Ht:
                    init_f = init_f + (jnp.zeros((Ht, 2), dtype=bv.dtype),)
                fin = _krylov_loop(cond_fused, step_fused, init_f)
                x, rs, it, hist = fin[0], fin[4], fin[6], fin[7]
                out = (x[None], rs, rs0, it, hist)
                return out + ((fin[8],) if Ht else ())

            def cond(state):
                _x, _r, _p, rz, rs, it = state[:6]
                go = jnp.logical_and(
                    jnp.sqrt(rs) > tol * jnp.maximum(1.0, jnp.sqrt(rs0)),
                    it < maxiter,
                )
                # in-graph health guard, folded into the reduction the
                # loop already carries (NaN exits via the > test; this
                # also stops an Inf blow-up within one iteration). The
                # host wrapper (_run_krylov) turns the non-finite exit
                # into a typed NonFiniteError.
                go = jnp.logical_and(go, jnp.isfinite(rs))
                if precond:
                    # r'M^-1 r == 0 with rs > 0 is a preconditioner
                    # breakdown (indefinite/zero minv): exit, converged
                    # stays honest (the host loop raises here instead)
                    go = jnp.logical_and(go, rz != 0)
                return go

            def step(state):
                if Ht:
                    x, r, p, rz, rs, it, hist, ab = state
                else:
                    x, r, p, rz, rs, it, hist = state
                    ab = None
                q = spmv(p)
                pq = pdot(p, q)
                alpha = rz / pq
                x = x.at[o0 : o0 + no_max].add(_rp(alpha * p[o0 : o0 + no_max]))
                r = r.at[o0 : o0 + no_max].add(_rp(-alpha * q[o0 : o0 + no_max]))
                z = apply_minv(r)
                rz_new = pdot(r, z) if precond else None
                rs_new = pdot(r, r)
                if not precond:
                    rz_new = rs_new
                beta = rz_new / rz
                p = p.at[o0 : o0 + no_max].set(
                    z[o0 : o0 + no_max] + _rp(beta * p[o0 : o0 + no_max])
                )
                hist = hist.at[jnp.minimum(it + 1, H - 1)].set(jnp.sqrt(rs_new))
                out = (x, r, p, rz_new, rs_new, it + 1, hist)
                if Ht:
                    out = out + (ab.at[it % Ht].set(
                        jnp.stack([alpha, beta])
                    ),)
                return out

            init_s = (xv, r, p, rz0, rs0, jnp.int32(0), hist)
            if Ht:
                init_s = init_s + (jnp.zeros((Ht, 2), dtype=bv.dtype),)
            fin = _krylov_loop(cond, step, init_s)
            x, rs, it, hist = fin[0], fin[4], fin[5], fin[6]
            out = (x[None], rs, rs0, it, hist)
            return out + ((fin[7],) if Ht else ())

        nouts = 4 + (1 if sdccfg is not None else 0) + (1 if Ht else 0)
        return shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec, spec, spec, specs),
            out_specs=(spec,) + (none_spec,) * nouts,
            check_vma=False,
        )(b, x0, mv, m)

    shape = (dA.col_plan.layout.P, dA.col_plan.layout.W)

    def run(b, x0, mv=None):
        check(
            tuple(b.shape) == shape and tuple(x0.shape) == shape,
            f"cg: vectors laid out {tuple(b.shape)}/{tuple(x0.shape)}, matrix "
            f"expects {shape} — build vectors with the matrix's col_layout",
        )
        if precond:
            check(mv is not None and tuple(mv.shape) == shape,
                  "pcg: preconditioner vector must share the matrix layout")
        else:
            check(
                mv is None,
                "this compiled CG was built without preconditioning — "
                "rebuild with make_cg_fn(..., precond=True) to use minv",
            )
        return fn(b, x0, b if mv is None else mv, ops)

    # introspection hooks (tests/benches): the inner jitted program and
    # its staged operands, so callers can `jit_fn.lower(...)` and count
    # collectives/fusions without reaching into closures
    run.jit_fn = fn
    run.operands = ops
    run.fused = bool(fused)
    run.has_sdc = sdccfg is not None
    run.trace_iters = Ht
    # the plan-level collective inventory of this body (telemetry.comms)
    # — the measured half of the static-vs-measured accounting
    run.comms_kwargs = dict(
        precond=bool(precond), fused=bool(fused), rhs_batch=None,
        sdc=sdccfg is not None, abft=abft_on,
    )
    return run


def _block_lane_major(dA: DeviceMatrix, fused: bool, sdc: bool) -> bool:
    """Whether the block CG program holds its operands lane-major,
    ``(K, W)`` with a column a row, and not ``(W, K)``. Read from the
    operator's lowering, the body and the SDC mode, and from nothing
    else: the A_oo block runs the coded Mosaic kernel on the padded frame
    (K=1-only: `_spmv_body._aoo`), the body is the fused one, and no SDC
    mode is on. `make_block_cg_fn` has which lowering wins what."""
    return bool(
        fused and not sdc
        and dA.dia_mode == "coded" and dA.pallas_plan is not None
    )


def make_block_cg_fn(
    dA: DeviceMatrix, tol: float, maxiter: int, rhs_batch: int,
    precond: bool = False, fused: Optional[bool] = None,
) -> Callable:
    """Block (multi-RHS) CG: ONE compiled shard_map program solving
    ``A X = B`` for K = ``rhs_batch`` right-hand sides against the SAME
    operator. Inside the program the block takes one of two layouts,
    chosen by what the operator lowered to (`_block_lane_major`; no flag
    or argument chooses, and ``run.block_layout`` says which):

    * ``"columns"``, operands ``(W, K)``: `_spmv_body`'s rank-polymorphic
      lowerings turn SpMV into SpMM, and the per-iteration operator
      stream is read ONCE per K columns. That holds for the SD group
      blocks and BSR blocks (one ``(rows, U) @ (U, K)`` product), for
      streamed DIA values and ELL arrays (broadcast over the trailing
      axis) and for the halo slabs, which is what makes the
      HBM-bound large-N iteration cheaper PER RHS as K grows there.
      Every operator but the one below, the standard body, strict-bits
      and the SDC-defended loops.
    * ``"lanes"``, operands ``(K, W)`` with a column a row (held as the
      kernel reads a frame, ``(K, W // 128, 128)``): where the A_oo
      block runs the coded Mosaic kernel on the padded frame and the body
      is the fused one. The operator there is a codebook and a byte a
      row, so there is next to nothing to amortize, and K minor would
      leave 4 lanes of 128 to every sweep, select and dot (PERF.md,
      PR 34: a K=4 iteration 6.16 ms where four solo ones take 1.59).
      Each column goes through the solo solve's own kernel and direction
      fold on its 1-D frame (the codes are read once a COLUMN), the
      sweeps and dots have full lanes, and K=1 is `make_cg_fn`'s fused
      program plus the freeze selects.

    Semantics contract, in both layouts: every column follows the
    TEXTBOOK single-vector recurrence exactly, with per-column α/β from
    per-column dots, so column k's trajectory is the trajectory
    `make_cg_fn` at K=1 would produce for (b_k, x0_k): bit-for-bit under
    strict-bits arithmetic (which lowers to ELL and so to the columns
    layout; pinned by tests/test_block_cg.py on the 4-part conformance
    fixture), to rounding in the lane-major body, whose row reductions
    sum in another order than the solo frame's.
    Converged (or broken-down / non-finite) columns FREEZE — their α is
    zeroed and their state re-selected unchanged — rather than exiting,
    keeping the loop shape static; the loop ends when every column is
    frozen or maxiter hits. Collective count per iteration is
    K-INDEPENDENT: the dot payloads widen from scalars to (K,) /
    (K, 2) stacks riding the same all_gathers, and the halo ppermutes
    ship the K columns' faces as one payload — pinned by the HLO A/B in
    tests/test_block_cg.py, for both layouts.

    ``fused`` selects the fused streaming body exactly as in
    `make_cg_fn` (default: env-resolved): one update+dot sweep, the
    direction fold riding the SpMV pass (the Pallas has_pfold kernel in
    the lane-major body; the jnp fold on every other lowering, and
    under a preconditioner row), and the preconditioned reduction pair
    sharing ONE all_gather as a (K, 2) payload.

    Returns ``run(b, x0, mv=None) -> (x, rs, rs0, iters, hist)`` with
    b/x0/x of shape (P, W, K), per-column ``rs``/``rs0``/``iters`` of
    shape (K,), and an (H, K) residual history (NaN past each column's
    freeze point)."""
    import jax
    import jax.numpy as jnp
    shard_map = jax.shard_map

    from ..ops.pallas_dia import LANES

    K = int(rhs_batch)
    check(K >= 1, "make_block_cg_fn: rhs_batch must be >= 1")
    fused = _resolve_fused(fused)
    mesh = dA.backend.mesh(dA.row_layout.P)
    spec = dA.backend.parts_spec()
    none_spec = jax.sharding.PartitionSpec()
    # the SDC defense, K-polymorphic: checksum/audit lanes are (K,)
    # per-column stacks riding the same gathers, detection is
    # per-column, rollback restores the WHOLE block state (frozen
    # columns restore to their frozen bits — re-freezing is a no-op)
    sdccfg = _sdc_config(maxiter)
    abft_on = bool(sdccfg and sdccfg["abft"])
    # block α/β trace ring: an (Ht, 2, K) replicated carry, committed
    # iterations only. The SDC-defended block loop is trace-exempt this
    # round (its per-column freeze/rollback bookkeeping has no committed
    # α/β slot per trip), noted in docs/observability.md.
    Ht = 0 if sdccfg is not None else int(min(_trace_config(), maxiter))
    lanes = _block_lane_major(dA, fused, sdccfg is not None)
    body_spmv = _spmv_body(dA, abft=abft_on, columns=lanes)
    body_pfold = (
        _spmv_body(
            dA, pfold=True, abft=abft_on, audit=sdccfg is not None,
            columns=lanes,
        )
        if fused
        else None
    )
    no_max = dA.row_layout.no_max
    o0 = dA.row_layout.o0
    pdot = _pdot_factory(o0, no_max)
    odot1, odot2 = _pdot_owned_factory(no_max)
    dox = _pdot_extra_factory(0, no_max) if sdccfg is not None else None
    ops = _matrix_operands(dA)
    specs = jax.tree.map(lambda _: spec, ops)
    strict = strict_bits()

    def _rp(t):
        return _strict_rounded_product(t) if strict else t

    H = int(min(maxiter + 1, 4096))

    def still_active(rs, rz, rs0):
        # the SAME per-column predicate the K=1 cond tests: a column
        # below tol, non-finite, or (preconditioned) broken down is
        # permanently inactive — its state is frozen, so the predicate
        # stays False once it trips
        go = jnp.sqrt(rs) > tol * jnp.maximum(1.0, jnp.sqrt(rs0))
        go = jnp.logical_and(go, jnp.isfinite(rs))
        if precond:
            go = jnp.logical_and(go, rz != 0)
        return go

    def per_column(f):
        """``f`` of 1-D frames over K of them, taken and returned in the
        kernel's view ``(K, W // LANES, LANES)`` (scalars a column as
        ``(K,)``): the kernel's call gains a leading grid axis and takes
        that view as it is (`_spmv_body` ``columns``), a ppermute ships
        the K columns' payload at once."""

        def column(*args):
            with jax.named_scope(SCOPE_COLUMN):
                return f(*args)

        def over(*args):
            out = jax.vmap(column)(
                *[a.reshape((K, -1)) if a.ndim == 3 else a for a in args]
            )
            return jax.tree.map(lambda y: y.reshape((K, -1, LANES)), out)

        return over

    def shard_lanes(bs, x0s, mvs, ms):
        """The lane-major fused body (`_block_lane_major`): `make_cg_fn`'s
        ``step_fused`` with three carries of K frames each and the
        per-column freeze. A frame is held as the kernel reads it,
        ``(W // LANES, LANES)``, so that K of them, ``(K, W)`` with a
        column a row, are the kernel's operand with no relayout between
        the sweeps and the product (a 2-D ``(K, W)`` array of K <= 4 rows
        is tiled K rows deep on the chip, which interleaves the columns).
        The sweeps and dots run over the lane rows that hold the owned
        band; the slots of its last row past ``no_max`` are pads, zero in
        every operand (`body_pfold`'s contract for p and A p)."""
        lo, hi = o0 // LANES, -(-(o0 + no_max) // LANES)
        own = slice(lo, hi)

        def lane_rows(t):  # (W, K) -> (K, W // LANES, LANES)
            return t.T.reshape((K, -1, LANES))

        bv, xv = lane_rows(bs[0]), lane_rows(x0s[0])
        mats = _shard_ops(jax, ms)
        mvo = mvs[0].reshape((-1, LANES))[own] if precond else None

        def gathered(partials):
            # (K,) or (K, 2) partials of this part: ONE all_gather, the
            # parts folded in their order
            return jnp.sum(jax.lax.all_gather(partials, "parts"), axis=0)

        def dot(a, b):
            return gathered(jnp.sum(a[:, own] * b[:, own], axis=(1, 2)))

        def dots(ro):
            # r.z and r.r of the owned rows, on one all_gather
            rr = jnp.sum(ro * ro, axis=(1, 2))
            if not precond:
                rs = gathered(rr)
                return rs, rs
            s = gathered(
                jnp.stack([jnp.sum(ro * (mvo * ro), axis=(1, 2)), rr], axis=-1)
            )
            return s[:, 0], s[:, 1]

        dot, dots = _scoped(SCOPE_DOTS, dot), _scoped(SCOPE_DOTS, dots)
        q = per_column(lambda c: body_spmv(c, mats)[0])(xv)
        ro = bv[:, own] - q[:, own]
        r = jnp.zeros_like(xv).at[:, own].set(ro)
        rz0, rs0 = dots(ro)
        hist = (
            jnp.full((H, K), jnp.nan, dtype=bv.dtype).at[0].set(jnp.sqrt(rs0))
        )

        def active(rs, rz):
            return still_active(rs, rz, rs0)

        def cond_l(state):
            rz, rs, _beta, _itk, it = state[3:8]
            return jnp.logical_and(jnp.any(active(rs, rz)), it < maxiter)

        fold = per_column(
            lambda rk, pk, bk: body_pfold(
                rk, pk, bk, mats, mvs[0] if precond else None
            )
        )

        def step_l(state):
            x, r_, p_prev, rz, rs, beta, itk, it, hist = state[:9]
            act = active(rs, rz)
            # every column's fold and product are the solo solve's own. A
            # frozen column folds with beta 0: its direction is then its
            # residual, finite, and never read again (its alpha is 0 and
            # its x and r are re-selected), so the direction needs no
            # select pass of its own.
            q, p = fold(r_, p_prev, jnp.where(act, beta, 0))
            alpha = jnp.where(act, rz / dot(p, q), 0)
            # one sweep: both updates where the carries lie, and the dot
            # partials. The freeze is a select, not `+ 0 * p`: a frozen
            # column's x and r never move a bit.
            a_, al = act[:, None, None], alpha[:, None, None]
            x = x.at[:, own].set(
                jnp.where(a_, x[:, own] + al * p[:, own], x[:, own])
            )
            r_ = r_.at[:, own].set(
                jnp.where(a_, r_[:, own] - al * q[:, own], r_[:, own])
            )
            rz_new, rs_new = dots(r_[:, own])
            rz2 = jnp.where(act, rz_new, rz)
            rs2 = jnp.where(act, rs_new, rs)
            beta2 = jnp.where(act, rz_new / rz, beta)
            idx = jnp.minimum(it + 1, H - 1)
            hist2 = hist.at[idx].set(
                jnp.where(act, jnp.sqrt(rs2), hist[idx])
            )
            out = (
                x, r_, p, rz2, rs2, beta2, itk + act.astype(jnp.int32),
                it + 1, hist2,
            )
            if Ht:
                out = out + (state[9].at[it % Ht].set(
                    jnp.stack([alpha, beta2])
                ),)
            return out

        init_l = (
            xv, r, jnp.zeros_like(xv), rz0, rs0,
            jnp.zeros((K,), bv.dtype), jnp.zeros((K,), jnp.int32),
            jnp.int32(0), hist,
        )
        if Ht:
            init_l = init_l + (jnp.zeros((Ht, 2, K), dtype=bv.dtype),)
        fin = _krylov_loop(cond_l, step_l, init_l)
        x, rs, itk, hist = fin[0], fin[4], fin[6], fin[8]
        out = (x.reshape((K, -1)).T[None], rs, rs0, itk, hist)
        return out + ((fin[9],) if Ht else ())

    @jax.jit
    def fn(b, x0, mv, m):
        def shard_fn(bs, x0s, mvs, ms):
            bv, xv = bs[0], x0s[0]  # (W, K)
            mats = _shard_ops(jax, ms)
            mvv = mvs[0]  # (W,) — ONE preconditioner for all columns
            slf = slice(o0, o0 + no_max)

            def spmv(z):
                if abft_on:
                    y, _, _, _ = body_spmv(z, mats)
                else:
                    y, _ = body_spmv(z, mats)
                return y

            def apply_minv(r):
                if not precond:
                    return r
                return jnp.zeros_like(r).at[slf].set(
                    mvv[slf][:, None] * r[slf]
                )

            q = spmv(xv)
            r = jnp.zeros_like(xv).at[slf].set(bv[slf] - q[slf])
            z = apply_minv(r)
            p = jnp.zeros_like(xv).at[slf].set(z[slf])
            rs0 = pdot(r, r)  # (K,)
            rz0 = pdot(r, z) if precond else rs0
            hist = (
                jnp.full((H, K), jnp.nan, dtype=bv.dtype)
                .at[0]
                .set(jnp.sqrt(rs0))
            )
            it0 = jnp.zeros((K,), jnp.int32)

            def active(rs, rz):
                return still_active(rs, rz, rs0)

            def _sel(act, new, old):
                # per-column freeze: re-select the OLD value so a frozen
                # column's bits never move (x + 0*p could still flip a
                # -0.0; the select cannot)
                return jnp.where(act, new, old)

            if sdccfg is not None:
                # ---- SDC-defended block loop (see make_cg_fn's sdc
                # branch for the trip classes) — (K,) per-column
                # checksum/audit lanes, whole-block ring restore ----
                ae = sdccfg["ae"]
                R = sdccfg["R"]
                mrb = sdccfg["mrb"]
                fault = sdccfg["fault"]
                trip_max = sdccfg["trip_max"]
                cs_tol, audit_tol = _sdc_tolerances(
                    bv.dtype, dA.row_layout.P, no_max
                )
                tiny = float(np.finfo(np.dtype(bv.dtype)).tiny)
                athr2 = (
                    audit_tol * jnp.maximum(1.0, jnp.sqrt(rs0))
                ) ** 2  # (K,)
                i32 = jnp.int32
                false = jnp.bool_(False)

                def inject(q, trip):
                    if fault is None:
                        return q
                    hit = jnp.logical_and(
                        trip == fault["trip"],
                        jax.lax.axis_index("parts") == fault["part"],
                    )
                    bump = jnp.where(
                        hit,
                        fault["factor"] * (1.0 + jnp.abs(q[o0, 0])),
                        0.0,
                    )
                    # column 0 of the first owned slot — one wire word,
                    # the same entry the host hook's K-polymorphic
                    # selection pins
                    return q.at[o0, 0].add(bump.astype(q.dtype))

                def cs_lanes(q, xpost, exd, exs):
                    wv = mats["abft_w"][:, None]
                    t = wv * xpost.astype(wv.dtype)
                    qo = q[slf].astype(wv.dtype)
                    delta = jnp.abs(
                        jnp.sum(qo, axis=0) - jnp.sum(t, axis=0)
                    ) + jnp.abs(exd).astype(wv.dtype)
                    scale = (
                        jnp.sum(jnp.abs(qo), axis=0)
                        + jnp.sum(jnp.abs(t), axis=0)
                        + exs.astype(wv.dtype)
                    )
                    return (
                        delta.astype(bv.dtype),
                        scale.astype(bv.dtype),
                    )

                def cs_detect(ex_out):
                    if not abft_on:
                        return jnp.zeros((K,), bool)
                    delta, scale = ex_out
                    return delta > cs_tol * (scale + tiny)

                def sdc_init(S0, sc0):
                    return (
                        jnp.stack([S0] * R),       # (R, 3, W, K)
                        jnp.stack([sc0] * R),      # (R, 3, K)
                        jnp.stack([it0] * R),      # (R, K)
                        jnp.zeros((R,), i32),      # ring global it
                        i32(0), i32(0), i32(0), i32(0), i32(0),
                        false, i32(0),
                    )

                def sdc_next(sdcst, aud, detect, cur_fn, cursc, itk, it):
                    (ring, ringsc, ringitk, ringit, since, strike,
                     rollbacks, dets, audits, esc, trip) = sdcst
                    exhausted = rollbacks >= mrb
                    restore = jnp.logical_and(
                        detect, jnp.logical_not(exhausted)
                    )
                    esc2 = jnp.logical_or(
                        esc, jnp.logical_and(detect, exhausted)
                    )
                    apass = jnp.logical_and(aud, jnp.logical_not(detect))

                    def _shift(buf, new):
                        return jnp.concatenate([new[None], buf[:-1]], axis=0)

                    # lax.cond: commit trips pass the ring buffers
                    # through untouched (no full-ring select per trip);
                    # cur_fn builds the snapshot inside the taken branch
                    ring2, ringsc2, ringitk2, ringit2 = jax.lax.cond(
                        apass,
                        lambda: (
                            _shift(ring, cur_fn()),
                            _shift(ringsc, cursc),
                            _shift(ringitk, itk),
                            _shift(ringit, it.astype(i32)),
                        ),
                        lambda: (ring, ringsc, ringitk, ringit),
                    )
                    sdc2 = (
                        ring2, ringsc2, ringitk2, ringit2,
                        jnp.where(jnp.logical_or(aud, restore), 0, since + 1),
                        jnp.where(
                            restore,
                            jnp.minimum(strike + 1, R - 1),
                            jnp.where(apass, 0, strike),
                        ),
                        rollbacks + restore.astype(i32),
                        dets + detect.astype(i32),
                        audits + aud.astype(i32),
                        esc2, trip + 1,
                    )
                    return sdc2, restore

                def sdc_out(sdcst):
                    rollbacks, dets, audits, esc, trip = (
                        sdcst[6], sdcst[7], sdcst[8], sdcst[9], sdcst[10]
                    )
                    return jnp.stack(
                        [dets, rollbacks, audits, esc.astype(i32), trip]
                    )

                if fused:
                    S0 = jnp.stack([xv, r, jnp.zeros_like(xv)])
                    beta0 = jnp.zeros((K,), bv.dtype)
                    sdc0 = sdc_init(S0, jnp.stack([rs0, rz0, beta0]))

                    def cond_fs(state):
                        _S, rz_, rs_, _beta, _itk, it_, _h, sdcst = state
                        esc_, trip_ = sdcst[9], sdcst[10]
                        go = jnp.logical_and(
                            jnp.any(active(rs_, rz_)), it_ < maxiter
                        )
                        go = jnp.logical_and(go, trip_ < trip_max)
                        return jnp.logical_and(
                            go, jnp.logical_not(esc_)
                        )

                    def step_fs(state):
                        S, rz, rs, beta, itk, it, hist, sdcst = state
                        since, strike = sdcst[4], sdcst[5]
                        trip = sdcst[10]
                        aud = (since >= ae) if ae > 0 else false
                        act = active(rs, rz)
                        x, r_, p_prev = S[0], S[1], S[2]
                        pf = body_pfold(
                            r_, p_prev, beta, mats,
                            mvv if precond else None,
                            aud=aud if ae > 0 else None, audx=x,
                        )
                        if abft_on:
                            q, p_, xpost, exd, exs = pf
                            q = inject(q, trip)
                            extras = cs_lanes(q, xpost, exd, exs)
                        else:
                            q, p_ = pf
                            q = inject(q, trip)
                            extras = ()
                        if ae > 0:
                            # audit trips stream d = (b - A x) - r into
                            # BOTH dot operands (the site computes
                            # ||d||²); lax.cond keeps the subtraction
                            # sweeps off the commit trips entirely
                            def _aud_ops():
                                d = bv[slf] - q[slf] - r_[slf]
                                return d, d

                            s1a, s1b = jax.lax.cond(
                                aud, _aud_ops,
                                lambda: (p_[slf], q[slf]),
                            )
                        else:
                            s1a, s1b = p_[slf], q[slf]
                        pqdd, ex_out = dox(s1a, s1b, extras)
                        cs_trip = cs_detect(ex_out)
                        alpha = jnp.where(act, rz / pqdd, 0)
                        xo = _sel(act, x[slf] + _rp(alpha * p_[slf]), x[slf])
                        ro = _sel(
                            act, r_[slf] + _rp(-alpha * q[slf]), r_[slf]
                        )
                        if precond:
                            zo = mvv[slf][:, None] * ro
                            rz_new, rs_new = odot2(ro, zo, ro, ro)
                        else:
                            rs_new = odot1(ro, ro)
                            rz_new = rs_new
                        audit_fail = jnp.logical_and(aud, pqdd > athr2)
                        detect = jnp.any(
                            jnp.logical_or(cs_trip, audit_fail)
                        )
                        commit = jnp.logical_and(
                            jnp.logical_not(aud), jnp.logical_not(detect)
                        )
                        sdc2, restore = sdc_next(
                            sdcst, aud, detect, lambda: S,
                            jnp.stack([rs, rz, beta]), itk, it,
                        )
                        j = jnp.minimum(strike, R - 1)
                        S_step = (
                            S.at[0, slf].set(xo)
                            .at[1, slf].set(ro)
                            .at[2, slf].set(
                                _sel(act, p_[slf], p_prev[slf])
                            )
                        )
                        branch = jnp.where(
                            commit, 0, jnp.where(restore, 2, 1)
                        ).astype(jnp.int32)
                        S3, rs3, rz3, beta3, itk3, it3 = jax.lax.switch(
                            branch,
                            [
                                lambda: (
                                    S_step,
                                    _sel(act, rs_new, rs),
                                    _sel(act, rz_new, rz),
                                    _sel(act, rz_new / rz, beta),
                                    itk + act.astype(jnp.int32),
                                    it + 1,
                                ),
                                lambda: (S, rs, rz, beta, itk, it),
                                lambda: (
                                    sdcst[0][j], sdcst[1][j, 0],
                                    sdcst[1][j, 1], sdcst[1][j, 2],
                                    sdcst[2][j], sdcst[3][j],
                                ),
                            ],
                        )
                        idx = jnp.minimum(it + 1, H - 1)
                        hist2 = hist.at[idx].set(
                            jnp.where(
                                jnp.logical_and(act, commit),
                                jnp.sqrt(_sel(act, rs_new, rs)),
                                hist[idx],
                            )
                        )
                        return (S3, rz3, rs3, beta3, itk3, it3, hist2, sdc2)

                    S, rz, rs, beta, itk, it, hist, sdcst = (
                        _krylov_loop(
                            cond_fs, step_fs,
                            (S0, rz0, rs0, beta0, it0, jnp.int32(0),
                             hist, sdc0),
                        )
                    )
                    return (
                        S[0][None], rs, rs0, itk, hist, sdc_out(sdcst)
                    )

                sdc0 = sdc_init(
                    jnp.stack([xv, r, p]),
                    jnp.stack([rs0, rz0, jnp.zeros((K,), bv.dtype)]),
                )

                def cond_ss(state):
                    _x, _r, _p, rz_, rs_, _itk, it_, _h, sdcst = state
                    esc_, trip_ = sdcst[9], sdcst[10]
                    go = jnp.logical_and(
                        jnp.any(active(rs_, rz_)), it_ < maxiter
                    )
                    go = jnp.logical_and(go, trip_ < trip_max)
                    return jnp.logical_and(go, jnp.logical_not(esc_))

                def step_ss(state):
                    x, r_, p_, rz, rs, itk, it, hist, sdcst = state
                    since, strike = sdcst[4], sdcst[5]
                    trip = sdcst[10]
                    aud = (since >= ae) if ae > 0 else false
                    act = active(rs, rz)
                    opnd = jnp.where(aud, x, p_) if ae > 0 else p_
                    if abft_on:
                        q, xpost, exd, exs = body_spmv(opnd, mats)
                        q = inject(q, trip)
                        extras = cs_lanes(q, xpost, exd, exs)
                    else:
                        q, _ = body_spmv(opnd, mats)
                        q = inject(q, trip)
                        extras = ()
                    if ae > 0:
                        # see step_fs: d computed only on audit trips
                        def _aud_ops():
                            d = bv[slf] - q[slf] - r_[slf]
                            return d, d

                        s1a, s1b = jax.lax.cond(
                            aud, _aud_ops,
                            lambda: (p_[slf], q[slf]),
                        )
                    else:
                        s1a, s1b = p_[slf], q[slf]
                    pqdd, ex_out = dox(s1a, s1b, extras)
                    cs_trip = cs_detect(ex_out)
                    alpha = jnp.where(act, rz / pqdd, 0)
                    x2 = x.at[slf].set(
                        _sel(act, x[slf] + _rp(alpha * p_[slf]), x[slf])
                    )
                    r2 = r_.at[slf].set(
                        _sel(act, r_[slf] + _rp(-alpha * q[slf]), r_[slf])
                    )
                    z2 = apply_minv(r2)
                    rz_new = pdot(r2, z2) if precond else None
                    rs_new = pdot(r2, r2)
                    if not precond:
                        rz_new = rs_new
                    p2 = p_.at[slf].set(
                        _sel(
                            act,
                            z2[slf]
                            + _rp(
                                jnp.where(act, rz_new / rz, 0) * p_[slf]
                            ),
                            p_[slf],
                        )
                    )
                    audit_fail = jnp.logical_and(aud, pqdd > athr2)
                    detect = jnp.any(jnp.logical_or(cs_trip, audit_fail))
                    commit = jnp.logical_and(
                        jnp.logical_not(aud), jnp.logical_not(detect)
                    )
                    sdc2, restore = sdc_next(
                        sdcst, aud, detect,
                        lambda: jnp.stack([x, r_, p_]),
                        jnp.stack([rs, rz, jnp.zeros((K,), bv.dtype)]),
                        itk, it,
                    )
                    j = jnp.minimum(strike, R - 1)
                    branch = jnp.where(
                        commit, 0, jnp.where(restore, 2, 1)
                    ).astype(jnp.int32)
                    x3, r3, p3, rs3, rz3, itk3, it3 = jax.lax.switch(
                        branch,
                        [
                            lambda: (
                                x2, r2, p2,
                                _sel(act, rs_new, rs),
                                _sel(act, rz_new, rz),
                                itk + act.astype(jnp.int32),
                                it + 1,
                            ),
                            lambda: (x, r_, p_, rs, rz, itk, it),
                            lambda: (
                                sdcst[0][j, 0], sdcst[0][j, 1],
                                sdcst[0][j, 2], sdcst[1][j, 0],
                                sdcst[1][j, 1], sdcst[2][j],
                                sdcst[3][j],
                            ),
                        ],
                    )
                    idx = jnp.minimum(it + 1, H - 1)
                    hist2 = hist.at[idx].set(
                        jnp.where(
                            jnp.logical_and(act, commit),
                            jnp.sqrt(_sel(act, rs_new, rs)),
                            hist[idx],
                        )
                    )
                    return (x3, r3, p3, rz3, rs3, itk3, it3, hist2, sdc2)

                x, r, p, rz, rs, itk, it, hist, sdcst = _krylov_loop(
                    cond_ss, step_ss,
                    (xv, r, p, rz0, rs0, it0, jnp.int32(0), hist, sdc0),
                )
                return x[None], rs, rs0, itk, hist, sdc_out(sdcst)

            if fused:
                S0 = jnp.stack([xv, r, jnp.zeros_like(xv)])
                beta0 = jnp.zeros((K,), bv.dtype)

                def cond_f(state):
                    _S, rz, rs, _beta, _itk, it = state[:6]
                    return jnp.logical_and(
                        jnp.any(active(rs, rz)), it < maxiter
                    )

                def step_f(state):
                    if Ht:
                        S, rz, rs, beta, itk, it, hist, ab = state
                    else:
                        S, rz, rs, beta, itk, it, hist = state
                        ab = None
                    act = active(rs, rz)
                    x, r_, p_prev = S[0], S[1], S[2]
                    q, p = body_pfold(
                        r_, p_prev, beta, mats, mvv if precond else None
                    )
                    pq = pdot(p, q)
                    alpha = jnp.where(act, rz / pq, 0)
                    xo = _sel(act, x[slf] + _rp(alpha * p[slf]), x[slf])
                    ro = _sel(act, r_[slf] + _rp(-alpha * q[slf]), r_[slf])
                    if precond:
                        zo = mvv[slf][:, None] * ro
                        rz_new, rs_new = odot2(ro, zo, ro, ro)
                    else:
                        rs_new = odot1(ro, ro)
                        rz_new = rs_new
                    S2 = (
                        S.at[0, slf].set(xo)
                        .at[1, slf].set(ro)
                        .at[2, slf].set(_sel(act, p[slf], p_prev[slf]))
                    )
                    rz2 = _sel(act, rz_new, rz)
                    rs2 = _sel(act, rs_new, rs)
                    beta2 = _sel(act, rz_new / rz, beta)
                    itk2 = itk + act.astype(jnp.int32)
                    idx = jnp.minimum(it + 1, H - 1)
                    hist2 = hist.at[idx].set(
                        _sel(act, jnp.sqrt(rs2), hist[idx])
                    )
                    out = (S2, rz2, rs2, beta2, itk2, it + 1, hist2)
                    if Ht:
                        out = out + (ab.at[it % Ht].set(
                            jnp.stack([alpha, beta2])
                        ),)
                    return out

                init_f = (S0, rz0, rs0, beta0, it0, jnp.int32(0), hist)
                if Ht:
                    init_f = init_f + (
                        jnp.zeros((Ht, 2, K), dtype=bv.dtype),
                    )
                fin = _krylov_loop(cond_f, step_f, init_f)
                S, rs, itk, hist = fin[0], fin[2], fin[4], fin[6]
                out = (S[0][None], rs, rs0, itk, hist)
                return out + ((fin[7],) if Ht else ())

            def cond(state):
                _x, _r, _p, rz, rs, _itk, it = state[:7]
                return jnp.logical_and(
                    jnp.any(active(rs, rz)), it < maxiter
                )

            def step(state):
                if Ht:
                    x, r_, p_, rz, rs, itk, it, hist, ab = state
                else:
                    x, r_, p_, rz, rs, itk, it, hist = state
                    ab = None
                act = active(rs, rz)
                q = spmv(p_)
                pq = pdot(p_, q)
                alpha = jnp.where(act, rz / pq, 0)
                x2 = x.at[slf].set(
                    _sel(act, x[slf] + _rp(alpha * p_[slf]), x[slf])
                )
                r2 = r_.at[slf].set(
                    _sel(act, r_[slf] + _rp(-alpha * q[slf]), r_[slf])
                )
                z = apply_minv(r2)
                rz_new = pdot(r2, z) if precond else None
                rs_new = pdot(r2, r2)
                if not precond:
                    rz_new = rs_new
                beta_b = jnp.where(act, rz_new / rz, 0)
                p2 = p_.at[slf].set(
                    _sel(act, z[slf] + _rp(beta_b * p_[slf]), p_[slf])
                )
                rz2 = _sel(act, rz_new, rz)
                rs2 = _sel(act, rs_new, rs)
                itk2 = itk + act.astype(jnp.int32)
                idx = jnp.minimum(it + 1, H - 1)
                hist2 = hist.at[idx].set(
                    _sel(act, jnp.sqrt(rs2), hist[idx])
                )
                out = (x2, r2, p2, rz2, rs2, itk2, it + 1, hist2)
                if Ht:
                    out = out + (ab.at[it % Ht].set(
                        jnp.stack([alpha, beta_b])
                    ),)
                return out

            init_s = (xv, r, p, rz0, rs0, it0, jnp.int32(0), hist)
            if Ht:
                init_s = init_s + (jnp.zeros((Ht, 2, K), dtype=bv.dtype),)
            fin = _krylov_loop(cond, step, init_s)
            x, rs, itk, hist = fin[0], fin[4], fin[5], fin[7]
            out = (x[None], rs, rs0, itk, hist)
            return out + ((fin[8],) if Ht else ())

        nouts = 4 + (1 if sdccfg is not None else 0) + (1 if Ht else 0)
        return shard_map(
            shard_lanes if lanes else shard_fn,
            mesh=mesh,
            in_specs=(spec, spec, spec, specs),
            out_specs=(spec,) + (none_spec,) * nouts,
            check_vma=False,
        )(b, x0, mv, m)

    shape = (dA.col_plan.layout.P, dA.col_plan.layout.W, K)

    def run(b, x0, mv=None):
        check(
            tuple(b.shape) == shape and tuple(x0.shape) == shape,
            f"block cg: operands laid out {tuple(b.shape)}/"
            f"{tuple(x0.shape)}, program expects {shape} — stage the "
            "RHS block with the matrix's col_layout and this rhs_batch",
        )
        vshape = shape[:2]
        if precond:
            check(
                mv is not None and tuple(mv.shape) == vshape,
                "block pcg: the (single, shared) preconditioner vector "
                "must share the matrix layout",
            )
        else:
            check(
                mv is None,
                "this compiled block CG was built without preconditioning"
                " — rebuild with precond=True to use minv",
            )
        return fn(b, x0, b[..., 0] if mv is None else mv, ops)

    run.jit_fn = fn
    run.operands = ops
    run.fused = bool(fused)
    run.rhs_batch = K
    run.block_layout = "lanes" if lanes else "columns"
    run.has_sdc = sdccfg is not None
    run.trace_iters = Ht
    run.comms_kwargs = dict(
        precond=bool(precond), fused=bool(fused), rhs_batch=K,
        sdc=sdccfg is not None, abft=abft_on,
    )
    return run


def make_diff_solve_fn(
    dA: DeviceMatrix,
    tol: float = 1e-10,
    maxiter: Optional[int] = None,
    minv=None,
) -> Callable:
    """Differentiable ``x = A^{-1} b`` as a compiled solve with a custom
    adjoint — the TPU-native feature the reference cannot offer: the whole
    Krylov solve participates in `jax.grad`/`jax.vjp` pipelines
    (PDE-constrained optimization, learned preconditioners) at the cost
    of ONE extra solve per backward pass, via the implicit function
    theorem: for SPD ``A``, ``b̄ = A^{-T} x̄ = A^{-1} x̄`` — so the
    backward pass reuses the same compiled CG program.

    ``A`` (and ``minv``) are constants of the closure; only ``b`` is
    differentiated. ``A`` must be **truly symmetric** positive definite:
    note that Dirichlet conditions imposed as identity rows (the FDM/FEM
    driver pattern) leave interior-to-boundary couplings in place and are
    NOT symmetric — eliminate boundary columns first if you need exact
    adjoints through such systems. The returned function maps a (P, W) column-layout
    vector to the (P, W) solution with every non-owned slot exactly
    zero; cotangents are masked to the owned region accordingly, which
    also re-establishes the zero-padding invariant on whatever arrives
    from upstream autodiff."""
    import jax
    import jax.numpy as jnp

    if maxiter is None:
        maxiter = 4 * int(dA.rows.ngids)  # same headroom as tpu_cg
    solve = _krylov_fn_for(dA, "cg", tol, maxiter, precond=minv is not None)
    L = dA.col_plan.layout
    mask_np = np.zeros((L.P, L.W))
    for p in range(L.P):
        mask_np[p, L.o0 : L.o0 + int(L.noids[p])] = 1.0
    # operator dtype: oh_vals is None on the node-block boundary path
    # (review r4), so read it from whichever A_oo staging is live
    op_dt = next(
        a.dtype
        for a in (
            dA.oh_vals,
            dA.ohs_vals,
            dA.ohb_vals[0] if dA.ohb_vals else None,  # per-bucket tuple
            dA.sd_vals[0] if dA.sd_vals else None,  # per-bucket tuple
            dA.bsr_vals, dA.dia_cb, dA.dia_vals, dA.oo_vals,
        )
        if a is not None
    )
    mask = _stage(dA.backend, mask_np.astype(op_dt), L.P)

    def _warn_unconverged(rs, rs0, it):
        if not np.sqrt(rs) <= tol * max(1.0, np.sqrt(rs0)):
            import warnings

            warnings.warn(
                f"make_diff_solve_fn: CG stopped at {int(it)} iterations "
                f"with residual {float(np.sqrt(rs)):.3e} (tol {tol:.1e}) — "
                "the value AND its gradient are inaccurate",
                stacklevel=2,
            )

    def _solve_masked(v):
        x, rs, rs0, it, _hist = solve(v * mask, jnp.zeros_like(v), minv)
        jax.debug.callback(_warn_unconverged, rs, rs0, it)
        return x * mask

    @jax.custom_vjp
    def f(b):
        return _solve_masked(b)

    def fwd(b):
        return f(b), None

    def bwd(_, xbar):
        return (_solve_masked(xbar),)

    f.defvjp(fwd, bwd)
    return f


def make_bicgstab_fn(
    dA: DeviceMatrix, tol: float, maxiter: int, precond: bool = False
) -> Callable:
    """BiCGStab as ONE compiled shard_map program — the Krylov method for
    nonsymmetric operators (CG's companion in the solver suite). Two
    overlapped SpMVs per iteration; deterministic fixed-order dots;
    breakdown (rho or omega denominators hitting zero) exits the loop with
    converged=False instead of poisoning the state with NaNs. With
    ``precond`` the loop is RIGHT-preconditioned against an
    inverse-diagonal operand (residuals stay true residuals)."""
    import jax
    import jax.numpy as jnp
    shard_map = jax.shard_map

    mesh = dA.backend.mesh(dA.row_layout.P)
    spec = dA.backend.parts_spec()
    none_spec = jax.sharding.PartitionSpec()
    body_spmv = _spmv_body(dA)
    no_max = dA.row_layout.no_max
    o0 = dA.row_layout.o0
    pdot = _pdot_factory(o0, no_max)
    ops = _matrix_operands(dA)
    specs = jax.tree.map(lambda _: spec, ops)
    H = int(min(maxiter + 1, 4096))

    @jax.jit
    def fn(b, x0, mv, m):
        def shard_fn(bs, x0s, mvs, ms):
            bv, xv = bs[0], x0s[0]
            mats = _shard_ops(jax, ms)
            mvv = mvs[0]
            sl = slice(o0, o0 + no_max)

            def spmv(z):
                y, _ = body_spmv(z, mats)
                return y

            def apply_k(z):
                """right preconditioner K^-1 z in the column frame."""
                if not precond:
                    return z
                return jnp.zeros_like(z).at[sl].set(mvv[sl] * z[sl])

            def owned(vec, vals):
                return jnp.zeros_like(vec).at[sl].set(vals)

            q = spmv(xv)
            r = owned(xv, bv[sl] - q[sl])
            rhat = r
            rs0 = pdot(r, r)
            one = jnp.asarray(1.0, dtype=bv.dtype)
            hist = jnp.full(H, jnp.nan, dtype=bv.dtype).at[0].set(jnp.sqrt(rs0))
            zero_v = jnp.zeros_like(xv)

            def cond(state):
                _x, _r, _p, _v, _rho, _alpha, _omega, rs, it, ok, _h = state
                return (
                    (jnp.sqrt(rs) > tol * jnp.maximum(1.0, jnp.sqrt(rs0)))
                    & (it < maxiter)
                    & ok
                )

            def step(state):
                x0_, r0_, p0_, v0_, rho0_, alpha0_, omega0_, rs0_, it, ok0, hist = state
                rho_new = pdot(rhat, r0_)
                ok = ok0 & (rho_new != 0) & (omega0_ != 0)
                beta = jnp.where(ok, (rho_new / rho0_) * (alpha0_ / omega0_), 0)
                p = p0_.at[sl].set(
                    r0_[sl] + beta * (p0_[sl] - omega0_ * v0_[sl])
                )
                # right preconditioning: v = A K^-1 p. Re-embed the
                # row-frame product into the column frame: v rides the
                # while_loop carry alongside col-frame vectors
                phat = apply_k(p)
                v = jnp.zeros_like(p).at[sl].set(spmv(phat)[sl])
                rv = pdot(rhat, v)
                ok = ok & (rv != 0)
                alpha = jnp.where(ok, rho_new / jnp.where(rv == 0, one, rv), 0)
                s = owned(r0_, r0_[sl] - alpha * v[sl])
                shat = apply_k(s)
                t = spmv(shat)
                tt = pdot(t, t)
                omega = jnp.where(
                    tt == 0, 0, pdot(t, s) / jnp.where(tt == 0, one, tt)
                )
                # the solution update uses the PRECONDITIONED directions
                x = x0_.at[sl].add(alpha * phat[sl] + omega * shat[sl])
                r = owned(r0_, s[sl] - omega * t[sl])
                rs_new = pdot(r, r)
                hist_new = hist.at[jnp.minimum(it + 1, H - 1)].set(
                    jnp.sqrt(rs_new)
                )
                # on breakdown the step must be a no-op (the host loop
                # breaks before mutating state): keep the pre-step values,
                # don't count the iteration, don't log it — cond then
                # exits with rs unchanged, so converged stays honest
                keep = lambda new_, old_: jax.tree.map(
                    lambda a, b: jnp.where(ok, a, b), new_, old_
                )
                return (
                    keep(x, x0_), keep(r, r0_), keep(p, p0_), keep(v, v0_),
                    jnp.where(ok, rho_new, rho0_),
                    jnp.where(ok, alpha, alpha0_),
                    jnp.where(ok, omega, omega0_),
                    jnp.where(ok, rs_new, rs0_),
                    jnp.where(ok, it + 1, it), ok,
                    keep(hist_new, hist),
                )

            state = (
                xv, r, zero_v, zero_v, one, one, one, rs0, jnp.int32(0),
                jnp.bool_(True), hist,
            )
            x, r, p, v, rho, alpha, omega, rs, it, ok, hist = (
                _krylov_loop(cond, step, state)
            )
            return x[None], rs, rs0, it, hist

        return shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec, spec, spec, specs),
            out_specs=(spec, none_spec, none_spec, none_spec, none_spec),
            check_vma=False,
        )(b, x0, mv, m)

    shape = (dA.col_plan.layout.P, dA.col_plan.layout.W)

    def run(b, x0, mv=None):
        check(
            tuple(b.shape) == shape and tuple(x0.shape) == shape,
            f"bicgstab: vectors laid out {tuple(b.shape)}/{tuple(x0.shape)}, "
            f"matrix expects {shape} — build vectors with the matrix's "
            "col_layout",
        )
        if precond:
            check(mv is not None and tuple(mv.shape) == shape,
                  "bicgstab: preconditioner vector must share the matrix layout")
        else:
            check(
                mv is None,
                "this compiled BiCGStab was built without preconditioning — "
                "rebuild with make_bicgstab_fn(..., precond=True) to use minv",
            )
        return fn(b, x0, b if mv is None else mv, ops)

    return run


def make_gmres_fn(
    dA: DeviceMatrix, restart: int, tol: float, maxiter: int,
    precond: bool = False,
) -> Callable:
    """Restarted GMRES(m) as ONE compiled shard_map program. The Arnoldi
    basis is an (m+1, no_max) owned-region array per shard; basis dots run
    as (m+1, no_max) @ (no_max,) matvecs — MXU work instead of the host's
    sequential modified-Gram-Schmidt dot chain — with classical
    Gram-Schmidt *reorthogonalized* (CGS2), whose stability matches MGS.
    The (m+1) partial dots per orthogonalization ride ONE all-gather.
    Givens rotations, the small triangular solve, and the restart logic
    all live in the same program, so a whole restart cycle is a single
    XLA dispatch loop iteration. With ``precond`` the loop is
    left-preconditioned by an inverse-diagonal operand (owned slots)."""
    import jax
    import jax.numpy as jnp
    shard_map = jax.shard_map

    m = int(restart)
    # m < 1 would compile an inner loop that never advances `it`, leaving
    # the outer while spinning on-device forever — reject it up front
    check(m >= 1, "gmres: restart dimension must be >= 1")
    mesh = dA.backend.mesh(dA.row_layout.P)
    spec = dA.backend.parts_spec()
    none_spec = jax.sharding.PartitionSpec()
    body_spmv = _spmv_body(dA)
    no_max = dA.row_layout.no_max
    o0 = dA.row_layout.o0
    ops = _matrix_operands(dA)
    specs = jax.tree.map(lambda _: spec, ops)
    H = int(min(maxiter + 1, 4096))

    @jax.jit
    def fn(b, x0, mv, mats_in):
        def shard_fn(bs, x0s, mvs, ms):
            bv, xv = bs[0], x0s[0]
            mats = _shard_ops(jax, ms)
            mvv = mvs[0]
            sl = slice(o0, o0 + no_max)
            dt = bv.dtype

            def ogather_sum(partial_):
                return jnp.sum(jax.lax.all_gather(partial_, "parts"), axis=0)

            def odot(a, b_):
                return ogather_sum(jnp.sum(a * b_))

            def apply_op(v_owned):
                """owned (no_max,) -> M^{-1} A v owned (no_max,); the SpMV
                halo exchange happens inside body_spmv."""
                z = jnp.zeros_like(bv).at[sl].set(v_owned)
                y, _ = body_spmv(z, mats)
                w = y[sl]
                if precond:
                    w = mvv[sl] * w
                return w

            def residual_owned(x):
                y, _ = body_spmv(x, mats)
                r = bv[sl] - y[sl]
                if precond:
                    r = mvv[sl] * r
                return r

            r0 = residual_owned(xv)
            rs0 = odot(r0, r0)
            tolcmp = tol * jnp.maximum(1.0, jnp.sqrt(rs0))
            hist = jnp.full(H, jnp.nan, dtype=dt).at[0].set(jnp.sqrt(rs0))

            def inner_cond(st):
                _V, _R, _cs, _sn, _g, j, it, _h, res, ok = st
                return (j < m) & (it < maxiter) & ok & (res > tolcmp)

            def inner_step(st):
                V, R, cs, sn, g, j, it, hist, _res, _ok = st
                vj = jax.lax.dynamic_slice_in_dim(V, j, 1, 0)[0]
                w = apply_op(vj)
                # CGS2: rows of V beyond j are exact zeros, so their dots
                # vanish — no masking needed anywhere
                h1 = ogather_sum(jnp.dot(V, w))
                w = w - jnp.dot(h1, V)
                h2 = ogather_sum(jnp.dot(V, w))
                w = w - jnp.dot(h2, V)
                h = h1 + h2
                hj1 = jnp.sqrt(odot(w, w))

                def rot(i, hv):
                    hi, hi1 = hv[i], hv[i + 1]
                    t = cs[i] * hi + sn[i] * hi1
                    u = -sn[i] * hi + cs[i] * hi1
                    on = i < j
                    return (
                        hv.at[i].set(jnp.where(on, t, hi))
                        .at[i + 1].set(jnp.where(on, u, hi1))
                    )

                h = jax.lax.fori_loop(0, m, rot, h)
                hjj = h[j]
                rho = jnp.sqrt(hjj * hjj + hj1 * hj1)
                safe = rho > 0
                c_new = jnp.where(safe, hjj / jnp.where(safe, rho, 1.0), 1.0)
                s_new = jnp.where(safe, hj1 / jnp.where(safe, rho, 1.0), 0.0)
                cs = cs.at[j].set(c_new)
                sn = sn.at[j].set(s_new)
                col = h[:m].at[j].set(rho)
                R = jax.lax.dynamic_update_slice(
                    R, col[:, None], (jnp.int32(0), j)
                )
                gj = g[j]
                g = g.at[j].set(c_new * gj).at[j + 1].set(-s_new * gj)
                res = jnp.abs(g[j + 1])
                ok = hj1 > 0  # hj1 == 0: lucky breakdown, exit after solve
                vnext = jnp.where(ok, w / jnp.where(ok, hj1, 1.0), 0.0 * w)
                V = jax.lax.dynamic_update_slice(
                    V, vnext[None], (j + 1, jnp.int32(0))
                )
                it = it + 1
                hist = hist.at[jnp.minimum(it, H - 1)].set(res)
                return (V, R, cs, sn, g, j + 1, it, hist, res, ok)

            def outer_cond(st):
                _x, _r, it, res, _h, ok = st
                return (res > tolcmp) & (it < maxiter) & ok

            def outer_step(st):
                # the residual vector rides the carry: it was honestly
                # recomputed at the end of the previous cycle (or at loop
                # entry), so the cycle does not re-derive it
                x, r, it, beta, hist, _ok = st
                bsafe = beta > 0
                v0 = jnp.where(bsafe, r / jnp.where(bsafe, beta, 1.0), 0.0 * r)
                V = jnp.zeros((m + 1, no_max), dtype=dt).at[0].set(v0)
                R = jnp.zeros((m, m), dtype=dt)
                cs = jnp.zeros(m, dtype=dt)
                sn = jnp.zeros(m, dtype=dt)
                g = jnp.zeros(m + 1, dtype=dt).at[0].set(beta)
                V, R, cs, sn, g, j, it, hist, res, ok = _krylov_loop(
                    inner_cond, inner_step,
                    (V, R, cs, sn, g, jnp.int32(0), it, hist,
                     jnp.asarray(beta, dt), jnp.bool_(True)),
                )
                # solve the j x j system embedded in the m x m frame:
                # unused columns are zero — patch their diagonal to 1 and
                # zero their rhs so back-substitution leaves y there at 0
                used = jnp.arange(m) < j
                Rp = R + jnp.diag(jnp.where(used, 0.0, 1.0).astype(dt))
                gp = jnp.where(used, g[:m], 0.0)
                y = jax.scipy.linalg.solve_triangular(Rp, gp, lower=False)
                x = x.at[sl].add(jnp.dot(y, V[:m]))
                # the Givens residual estimate drifts from the true
                # residual under roundoff; the restart recomputes honestly
                r = residual_owned(x)
                res = jnp.sqrt(odot(r, r))
                hist = hist.at[jnp.minimum(it, H - 1)].set(res)
                return (x, r, it, res, hist, ok)

            x, r_c, it, res, hist, ok = _krylov_loop(
                outer_cond, outer_step,
                (xv, r0, jnp.int32(0), jnp.sqrt(rs0), hist, jnp.bool_(True)),
            )
            return x[None], res * res, rs0, it, hist

        return shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec, spec, spec, specs),
            out_specs=(spec, none_spec, none_spec, none_spec, none_spec),
            check_vma=False,
        )(b, x0, mv, mats_in)

    shape = (dA.col_plan.layout.P, dA.col_plan.layout.W)

    def run(b, x0, mv=None):
        check(
            tuple(b.shape) == shape and tuple(x0.shape) == shape,
            f"gmres: vectors laid out {tuple(b.shape)}/{tuple(x0.shape)}, "
            f"matrix expects {shape} — build vectors with the matrix's "
            "col_layout",
        )
        if precond:
            check(mv is not None and tuple(mv.shape) == shape,
                  "gmres: preconditioner vector must share the matrix layout")
        else:
            check(
                mv is None,
                "this compiled GMRES was built without preconditioning — "
                "rebuild with make_gmres_fn(..., precond=True) to use minv",
            )
        return fn(b, x0, b if mv is None else mv, ops)

    return run


def make_minres_fn(dA: DeviceMatrix, tol: float, maxiter: int) -> Callable:
    """MINRES (Paige–Saunders) as ONE compiled shard_map program: the
    three-term Lanczos recurrence plus one Givens rotation per step, for
    symmetric — possibly indefinite — operators. Constant memory (no
    stored basis); per iteration: one overlapped SpMV plus two
    deterministic all-gather dots. The update sequence is identical to
    the host loop in models/solvers.py, so iteration counts match the
    sequential oracle the same way CG's do."""
    import jax
    import jax.numpy as jnp
    shard_map = jax.shard_map

    mesh = dA.backend.mesh(dA.row_layout.P)
    spec = dA.backend.parts_spec()
    none_spec = jax.sharding.PartitionSpec()
    body_spmv = _spmv_body(dA)
    no_max = dA.row_layout.no_max
    o0 = dA.row_layout.o0
    pdot = _pdot_factory(o0, no_max)
    ops = _matrix_operands(dA)
    specs = jax.tree.map(lambda _: spec, ops)
    H = int(min(maxiter + 1, 4096))

    @jax.jit
    def fn(b, x0, m):
        def shard_fn(bs, x0s, ms):
            bv, xv = bs[0], x0s[0]
            mats = _shard_ops(jax, ms)
            sl = slice(o0, o0 + no_max)
            one = jnp.asarray(1.0, dtype=bv.dtype)

            def spmv(z):
                y, _ = body_spmv(z, mats)
                return y

            def owned(vals):
                return jnp.zeros_like(xv).at[sl].set(vals)

            q = spmv(xv)
            r = owned(bv[sl] - q[sl])
            rs0 = pdot(r, r)
            beta0 = jnp.sqrt(rs0)
            bsafe = beta0 > 0
            v = owned(jnp.where(bsafe, r[sl] / jnp.where(bsafe, beta0, one), 0.0))
            zero_v = jnp.zeros_like(xv)
            hist = jnp.full(H, jnp.nan, dtype=bv.dtype).at[0].set(beta0)

            def cond(st):
                (_x, _v, _vo, _w, _wo, _co, _so, _c, _s, _eta, _bk, res,
                 it, ok, _h) = st
                return (
                    (res > tol * jnp.maximum(1.0, beta0)) & (it < maxiter) & ok
                )

            def step(st):
                (x, v, v_old, w, w_old, c_old, s_old, c, s, eta, beta_k,
                 _res, it, ok, hist) = st
                av = spmv(v)
                alpha = pdot(v, av)
                lan = owned(av[sl] - alpha * v[sl] - beta_k * v_old[sl])
                beta_new = jnp.sqrt(pdot(lan, lan))
                delta = c * alpha - c_old * s * beta_k
                gamma2 = s * alpha + c_old * c * beta_k
                gamma3 = s_old * beta_k
                rho = jnp.sqrt(delta * delta + beta_new * beta_new)
                # valid: this iteration's updates hold (rho == 0 is the
                # hard-breakdown no-op; the host loop breaks out with
                # converged=False on it, matching this path). Lucky
                # breakdown (beta_new == 0 but rho != 0) is a VALID final
                # iteration — apply it, then exit via ok.
                valid = rho != 0
                cont = valid & (beta_new > 0)
                rho_s = jnp.where(valid, rho, one)
                c_new = delta / rho_s
                s_new = beta_new / rho_s
                w_new = owned(
                    (v[sl] - gamma2 * w[sl] - gamma3 * w_old[sl]) / rho_s
                )
                x_new = x.at[sl].add(c_new * eta * w_new[sl])
                eta_new = -s_new * eta
                nsafe = beta_new > 0
                v_new = owned(
                    jnp.where(
                        nsafe, lan[sl] / jnp.where(nsafe, beta_new, one), 0.0
                    )
                )
                res_new = jnp.abs(eta_new)
                it_new = jnp.where(valid, it + 1, it)
                keep = lambda new_, old_: jnp.where(valid, new_, old_)
                hist_new = hist.at[jnp.minimum(it_new, H - 1)].set(
                    keep(res_new, hist[jnp.minimum(it_new, H - 1)])
                )
                return (
                    keep(x_new, x), keep(v_new, v), keep(v, v_old),
                    keep(w_new, w), keep(w, w_old),
                    keep(c, c_old), keep(s, s_old),
                    keep(c_new, c), keep(s_new, s),
                    keep(eta_new, eta),
                    keep(beta_new, beta_k),
                    keep(res_new, _res),
                    it_new, ok & cont, hist_new,
                )

            state = (
                xv, v, zero_v, zero_v, zero_v, one, 0 * one, one, 0 * one,
                beta0, 0 * one, beta0, jnp.int32(0), jnp.bool_(True), hist,
            )
            (x, v, v_old, w, w_old, c_old, s_old, c, s, eta, beta_k, res,
             it, ok, hist) = _krylov_loop(cond, step, state)
            return x[None], res * res, rs0, it, hist

        return shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec, spec, specs),
            out_specs=(spec, none_spec, none_spec, none_spec, none_spec),
            check_vma=False,
        )(b, x0, m)

    shape = (dA.col_plan.layout.P, dA.col_plan.layout.W)

    def run(b, x0):
        check(
            tuple(b.shape) == shape and tuple(x0.shape) == shape,
            f"minres: vectors laid out {tuple(b.shape)}/{tuple(x0.shape)}, "
            f"matrix expects {shape} — build vectors with the matrix's "
            "col_layout",
        )
        return fn(b, x0, ops)

    return run


def tpu_minres(
    A: PSparseMatrix,
    b: PVector,
    x0: Optional[PVector] = None,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    verbose: bool = False,
) -> Tuple[PVector, dict]:
    """Device MINRES (symmetric indefinite Krylov), one compiled program."""
    backend = b.values.backend
    check(isinstance(backend, TPUBackend), "tpu_minres needs a TPU-backend PVector")
    maxiter = maxiter if maxiter is not None else 4 * A.rows.ngids
    dA = device_matrix(A, backend)
    key = ("minres", float(tol), int(maxiter))
    if key not in dA._cg_cache:
        dA._cg_cache[key] = make_minres_fn(dA, tol, maxiter)
    return _run_krylov(A, b, x0, tol, verbose, dA._cg_cache[key], name="minres")


def tpu_gmres(
    A: PSparseMatrix,
    b: PVector,
    x0: Optional[PVector] = None,
    restart: int = 30,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    minv: Optional[PVector] = None,
    verbose: bool = False,
) -> Tuple[PVector, dict]:
    """Device restarted GMRES (see make_gmres_fn), one compiled program."""
    backend = b.values.backend
    check(isinstance(backend, TPUBackend), "tpu_gmres needs a TPU-backend PVector")
    maxiter = maxiter if maxiter is not None else 4 * A.rows.ngids
    dA = device_matrix(A, backend)
    key = ("gmres", int(restart), float(tol), int(maxiter), minv is not None)
    if key not in dA._cg_cache:
        dA._cg_cache[key] = make_gmres_fn(
            dA, restart, tol, maxiter, precond=minv is not None
        )
    return _run_krylov(
        A, b, x0, tol, verbose, dA._cg_cache[key], minv=minv, name="gmres"
    )


# ---------------------------------------------------------------------------
# high-level entry points (used by solvers.cg dispatch and PVector methods)
# ---------------------------------------------------------------------------


def make_chebyshev_fn(
    dA: DeviceMatrix,
    lmin: float,
    lmax: float,
    tol: float,
    maxiter: int,
    leg: int = 16,
) -> Callable:
    """Chebyshev iteration as ONE compiled program. The distinguishing
    property on a mesh: the inner loop runs `leg` iterations with NO
    reductions — the only collective is the SpMV halo `ppermute` — and a
    single deterministic residual all-gather happens once per leg to
    decide termination. Spectrum bounds are compile-time constants."""
    import jax
    import jax.numpy as jnp
    shard_map = jax.shard_map

    mesh = dA.backend.mesh(dA.row_layout.P)
    spec = dA.backend.parts_spec()
    none_spec = jax.sharding.PartitionSpec()
    body_spmv = _spmv_body(dA)
    no_max = dA.row_layout.no_max
    o0 = dA.row_layout.o0
    pdot = _pdot_factory(o0, no_max)
    ops = _matrix_operands(dA)
    specs = jax.tree.map(lambda _: spec, ops)
    theta = (lmax + lmin) / 2.0
    delta = (lmax - lmin) / 2.0
    sigma1 = theta / delta
    n_legs = -(-maxiter // leg)
    H = int(min(n_legs + 1, 4096))

    @jax.jit
    def fn(b, x0, m):
        def shard_fn(bs, x0s, ms):
            bv, xv = bs[0], x0s[0]
            mats = _shard_ops(jax, ms)

            def spmv(z):
                y, _ = body_spmv(z, mats)
                return y

            o = slice(o0, o0 + no_max)
            q = spmv(xv)
            r = jnp.zeros_like(xv).at[o].set(bv[o] - q[o])
            rs0 = pdot(r, r)
            d = jnp.zeros_like(xv).at[o].set(r[o] / theta)
            hist = jnp.full(H, jnp.nan, dtype=bv.dtype).at[0].set(
                jnp.sqrt(rs0)
            )

            def one_iter(_i, st):
                x, r, d, rho = st
                x = x.at[o].add(d[o])
                q = spmv(d)
                r = r.at[o].add(-q[o])
                rho_new = 1.0 / (2.0 * sigma1 - rho)
                d = d.at[o].set(
                    rho_new * rho * d[o] + (2.0 * rho_new / delta) * r[o]
                )
                return (x, r, d, rho_new)

            def cond(state):
                _x, _r, _d, _rho, rs, it, _h = state
                return jnp.logical_and(
                    jnp.sqrt(rs) > tol * jnp.maximum(1.0, jnp.sqrt(rs0)),
                    it < maxiter,
                )

            def step(state):
                x, r, d, rho, rs, it, hist = state
                x, r, d, rho = jax.lax.fori_loop(
                    0, leg, one_iter, (x, r, d, rho)
                )
                rs = pdot(r, r)
                it = it + leg
                hist = hist.at[jnp.minimum(it // leg, H - 1)].set(
                    jnp.sqrt(rs)
                )
                return (x, r, d, rho, rs, it, hist)

            x, r, d, rho, rs, it, hist = _krylov_loop(
                cond,
                step,
                (xv, r, d, 1.0 / sigma1, rs0, jnp.int32(0), hist),
            )
            return x[None], rs, rs0, it, hist

        return shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec, spec, specs),
            out_specs=(spec, none_spec, none_spec, none_spec, none_spec),
            check_vma=False,
        )(b, x0, m)

    shape = (dA.col_plan.layout.P, dA.col_plan.layout.W)

    def run(b, x0):
        check(
            tuple(b.shape) == shape and tuple(x0.shape) == shape,
            f"chebyshev: vectors laid out {tuple(b.shape)}/{tuple(x0.shape)},"
            f" matrix expects {shape} — build vectors with the matrix's "
            "col_layout",
        )
        return fn(b, x0, ops)

    return run


def tpu_chebyshev(
    A: PSparseMatrix,
    b: PVector,
    lmin: float,
    lmax: float,
    x0: Optional[PVector] = None,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    verbose: bool = False,
):
    """Compiled Chebyshev solve (see make_chebyshev_fn). The residual
    history is per-leg (one entry per 16 iterations), not per-iteration."""
    from ..utils.helpers import warn_tol_below_floor

    backend = b.values.backend
    dev_dtype = _device_dtype(b.dtype)
    floor_warned = warn_tol_below_floor(tol, dev_dtype, name="chebyshev")
    dA = device_matrix(A, backend)
    if maxiter is None:
        maxiter = 10 * int(A.rows.ngids)
    key = ("chebyshev", float(lmin), float(lmax), float(tol), int(maxiter))
    if key not in dA._cg_cache:
        dA._cg_cache[key] = make_chebyshev_fn(dA, lmin, lmax, tol, maxiter)
    solve = dA._cg_cache[key]
    layout = dA.col_layout
    x_data, rs, rs0, it, hist = solve(
        _pack(b, layout, backend, with_ghosts=False),
        _pack(x0, layout, backend) if x0 is not None
        else _zero_frame(layout, backend, b.dtype),
    )
    x = DeviceVector(x_data, A.cols, dA.col_layout, backend).to_pvector()
    rs, rs0, it = float(rs), float(rs0), int(it)
    # hist is per 16-iteration leg (reductions happen once per leg);
    # compact out the untouched NaN tail instead of _run_krylov's
    # one-entry-per-iteration slicing
    hist = np.asarray(hist)
    residuals = hist[~np.isnan(hist)]
    if verbose:
        for i, r in enumerate(residuals[1:], start=1):
            print(f"chebyshev leg={i} (it={16 * i}) residual={r:.3e}")
    from ..utils.helpers import krylov_info

    converged = bool(np.sqrt(rs) <= tol * max(1.0, np.sqrt(rs0)))
    return x, krylov_info(
        it, residuals, converged, tol, dev_dtype, floor_warned,
        final_rel=_final_true_rel(
            A, x, b, np.sqrt(rs) / max(1.0, np.sqrt(rs0)), np.sqrt(rs0),
            tol, force=floor_warned,
        ),
        residuals_every=16,
    )


def _decode_sdc_outputs(name: str, sdcvec, it=None) -> dict:
    """The ONE decode of a compiled program's SDC output lane (shared by
    `_run_krylov` and `tpu_block_cg` so the counter contract cannot
    diverge): returns the ``info["sdc"]`` dict, or raises the typed
    escalation when the loop latched its flag — corruption kept firing
    past the in-memory rollback budget, so the same
    `SilentCorruptionError` the host loop raises escalates to
    `solve_with_recovery`'s checkpoint tier."""
    from .health import SilentCorruptionError

    dets, rollbacks, audits, escal, trips = (
        int(v) for v in np.asarray(sdcvec)
    )
    sdc_info = {
        "detections": dets,
        "rollbacks": rollbacks,
        "escalations": int(bool(escal)),
        "audit_iterations": audits,
        "trips": trips,
    }
    if dets or rollbacks or escal:
        # the compiled loop only reports counters (its detections fired
        # in-graph); surface them as one structured event so no device
        # recovery is silent in the record's event log
        from .. import telemetry

        telemetry.emit_event(
            "sdc_detection", label=name,
            iteration=None if it is None else int(it), **sdc_info,
        )
        if rollbacks:
            telemetry.emit_event(
                "sdc_rollback", label=name,
                iteration=None if it is None else int(it),
                rollbacks=rollbacks,
            )
    if escal:
        diag = {"context": name, "sdc": sdc_info}
        if it is not None:
            diag["iteration"] = int(it)
        raise SilentCorruptionError(
            f"{name}: in-graph SDC detection exhausted the rollback "
            f"budget ({rollbacks} rollbacks, {dets} detections)"
            + (f" at device iteration {it}" if it is not None else "")
            + " — escalating to checkpoint restart",
            diagnostics=diag,
        )
    return sdc_info


class _LowerSpans:
    """The spans and counters of one `DeviceMatrix.__init__`: ``pa:lower``
    around it and, under it, exactly one leaf open at any time:
    ``pa:lower:layout`` (layouts, plans, codebooks: what is neither of
    the two others) except inside ``leaf("detect")`` (every `_detect_*`)
    and `upload` (every `_stage` of an operand; its ``pa:stage:put`` nests
    there). Through `telemetry.annotate`, so the solve that pays the
    lowering reads them as ``timings["lower"]`` / ``["detect"]`` /
    ``["layout"]`` / ``["upload"]``. Always-on counters beside them, in
    whole microseconds: ``lowering.wall_us``, ``.detect_us``,
    ``.upload_us``, and ``.upload_bytes`` (the host operands' bytes)."""

    def __enter__(self):
        from .. import telemetry
        from ..telemetry.metrics import whole_us

        self._annotate = telemetry.annotate
        self._bump, self._whole_us = telemetry.bump, whole_us
        self._t0 = time.perf_counter()
        self._root = self._annotate("pa:lower")
        self._root.__enter__()
        self._open_layout()
        return self

    def _open_layout(self) -> None:
        self._layout = self._annotate("pa:lower:layout")
        self._layout.__enter__()

    def _count_us(self, name: str, t0: float) -> None:
        self._bump(
            f"lowering.{name}_us",
            self._whole_us(time.perf_counter() - t0),
        )

    @contextlib.contextmanager
    def leaf(self, name: str):
        """``pa:lower:<name>`` in the layout leaf's place."""
        self._layout.__exit__(None, None, None)
        t0 = time.perf_counter()
        try:
            with self._annotate(f"pa:lower:{name}"):
                yield
        finally:
            self._count_us(name, t0)
            self._open_layout()

    def upload(self, backend, arr, nparts: int):
        """`_stage` of one operand under ``pa:lower:upload``."""
        with self.leaf("upload"):
            self._bump("lowering.upload_bytes", int(arr.nbytes))
            return _stage(backend, arr, nparts)

    def __exit__(self, *exc):
        self._layout.__exit__(None, None, None)
        self._root.__exit__(None, None, None)
        self._count_us("wall", self._t0)
        return False


def _count_sd_lowering(sd: dict, nnz: int) -> None:
    """The ``lowering.sd.*`` counters of one operator staged in the
    supernode-dense form: the non-zeros it densified, the dense entries
    and bytes it made of them, its groups, and the padded external node
    slots its products gather (``sd`` as `DeviceMatrix._detect_sd`
    returned it). ``nnz / dense_entries`` is the fill."""
    from .. import telemetry

    vals = [c["vals"] for c in sd["chunks"]]
    telemetry.bump("lowering.sd.nnz", int(nnz))
    telemetry.bump("lowering.sd.dense_entries", sum(int(v.size) for v in vals))
    telemetry.bump("lowering.sd.bytes", sum(int(v.nbytes) for v in vals))
    telemetry.bump(
        "lowering.sd.groups", sum(int(v.shape[0] * v.shape[1]) for v in vals)
    )
    telemetry.bump(
        "lowering.sd.gather_slots",
        sum(int(c["idx"].size) for c in sd["chunks"]),
    )


def _pfold_fits(dA: DeviceMatrix) -> bool:
    """Whether the coded kernel's direction-fold variant fits the VMEM
    gate for ``dA``'s padded plan (`ops/pallas_dia.py:pfold_vmem_ok`):
    the one answer from which `_spmv_body` builds the fused CG body and
    the ``lowering.coded.pfold`` counter records."""
    from ..ops.pallas_dia import pfold_vmem_ok

    return pfold_vmem_ok(
        dA.pallas_plan, itemsize=np.dtype(dA.dia_cb.dtype).itemsize
    )


def _count_coded_lowering(plan: dict, pfold: bool, fused: bool) -> None:
    """The ``lowering.coded.*`` counters of one coded operator staged on
    the padded frame (``plan`` as `plan_dia_padded` returned it): the
    operator, the kernel's block and halo, the rows of the operand it
    fetches for each block in a CG solve of the default body
    (``x_window_rows / block_rows`` is how often the operand is read:
    the block alone where the fused body, ``fused``, folds the direction
    in the kernel, whose rings fetch every block once; else the plain
    kernel's window, the block and the halo on both sides), the VMEM the
    plan declares, and whether the fused CG body's direction fold runs
    inside the kernel (``pfold``, from `_pfold_fits`)."""
    from .. import telemetry
    from ..ops.pallas_dia import _win_rows

    br, halo = plan["block_rows"], plan["halo_rows"]
    telemetry.bump("lowering.coded.operators", 1)
    telemetry.bump("lowering.coded.block_rows", br)
    telemetry.bump("lowering.coded.halo_rows", halo)
    telemetry.bump(
        "lowering.coded.x_window_rows",
        br if pfold and fused else _win_rows(br, halo),
    )
    telemetry.bump("lowering.coded.plan_vmem_bytes", plan["vmem"])
    telemetry.bump("lowering.coded.pfold", int(pfold))


def _count_stream_lowering(vals: np.ndarray, plan: Optional[dict]) -> None:
    """The ``lowering.stream.*`` counters of one operator staged as
    streamed diagonals: the operator, its diagonals, the bytes uploaded
    for them (all parts, the kernel's padding in), whether the Mosaic
    kernel takes them (``plan`` as `plan_dia_pallas` returned it) or the
    XLA form (``.pallas / .operators`` is the share it takes); and with a
    plan the kernel's block, the rows of x it fetches for each block (the
    block and its halo on both sides: ``x_window_rows / block_rows`` is how
    often x is read), its blocks and the VMEM slots of its x window (two:
    the next block's window is fetched while this one computes)."""
    from .. import telemetry
    from ..ops.pallas_dia import WINDOW_SLOTS, _win_rows

    telemetry.bump("lowering.stream.operators", 1)
    telemetry.bump("lowering.stream.diagonals", int(vals.shape[1]))
    telemetry.bump("lowering.stream.value_bytes", int(vals.nbytes))
    telemetry.bump("lowering.stream.pallas", int(plan is not None))
    if plan is not None:
        br = plan["block_rows"]
        telemetry.bump("lowering.stream.block_rows", br)
        telemetry.bump(
            "lowering.stream.x_window_rows", _win_rows(br, plan["halo_rows"])
        )
        telemetry.bump("lowering.stream.blocks", plan["n_rows"] // br)
        telemetry.bump("lowering.stream.window_slots", WINDOW_SLOTS)


def _count_exchange_plan(plan) -> None:
    """The ``exchange.plan.*`` counters of one operator staged with the
    generic plan: its rounds, its directed edges, the real slots they send
    (all parts), the ``P x R x L`` slots its padded rounds gather, ship
    and scatter, and its longest and shortest edge. ``slots /
    padded_slots`` is the fill. A box plan (`_count_box_plan` counts
    it), or a plan with no edge (one part), counts nothing."""
    if not isinstance(plan, DeviceExchangePlan) or not plan.R:
        return
    from .. import telemetry

    sent = plan.snd_mask.sum(axis=-1)  # (P, R): what a part sends in a round
    edges = [
        int(sent[src, r])
        for r, perm in enumerate(plan.perms) for src, _dst in perm
    ]
    telemetry.bump("exchange.plan.rounds", plan.R)
    telemetry.bump("exchange.plan.edges", len(edges))
    telemetry.bump("exchange.plan.slots", sum(edges))
    telemetry.bump("exchange.plan.padded_slots", int(plan.snd_mask.size))
    telemetry.bump("exchange.plan.max_edge", max(edges))
    telemetry.bump("exchange.plan.min_edge", min(edges))


def _count_box_plan(plan) -> None:
    """The ``exchange.box.*`` counters of one operator staged with a box
    plan that has an edge: its directions (one `ppermute` each), and how
    the forward body addresses the face each packs
    (`tpu_box.face_form`), one count a direction and box-shape variant,
    so that the three forms add up to the directions on an equal-box
    plan. A generic plan, or a box plan of one part, counts nothing."""
    from .tpu_box import FACE_FORMS, BoxExchangePlan

    if not isinstance(plan, BoxExchangePlan) or not plan.R:
        return
    from .. import telemetry

    forms = plan.pack_forms()
    telemetry.bump("exchange.box.dirs", plan.R)
    for form in FACE_FORMS:
        telemetry.bump(f"exchange.box.{form}_dirs", forms.count(form))


def _count_oh_lowering(nnz: int, slabs=None, ell_entries=None,
                       block_entries=None) -> None:
    """The ``lowering.oh.*`` counters of one operator's boundary block:
    its stored entries, and what they were staged as: the classes and
    dense entries of the face-slab form (``slabs`` as
    `DeviceMatrix._detect_oh_slabs` returned them), the padded entries
    of the node-block form's ``bs x bs`` blocks (all parts), or the
    padded entries of the ELL form."""
    from .. import telemetry

    telemetry.bump("lowering.oh.nnz", int(nnz))
    if slabs is not None:
        telemetry.bump("lowering.oh.slab_classes", len(slabs))
        telemetry.bump(
            "lowering.oh.slab_entries",
            sum(math.prod(s.shape) for s in slabs),
        )
    elif block_entries is not None:
        telemetry.bump("lowering.oh.block_entries", int(block_entries))
    else:
        telemetry.bump("lowering.oh.ell_entries", int(ell_entries))


def _count_staged(*frames) -> None:
    """The ``solve.*`` staging counters of one device solve: the call,
    and the bytes of the device frames its vectors went into (``None``
    entries skipped)."""
    from .. import telemetry

    telemetry.bump("solve.calls")
    telemetry.bump(
        "solve.staged_bytes",
        sum(int(f.nbytes) for f in frames if f is not None),
    )


def _outputs_to_host(out) -> list:
    """A compiled solve's outputs as host arrays (the answer frame,
    first, through `fetch_global`), under the ``pa:fetch:d2h`` span,
    their bytes counted in ``solve.fetched_bytes``."""
    from .. import telemetry
    from .multihost import fetch_global

    with telemetry.annotate("pa:fetch:d2h"):
        host = [fetch_global(out[0])] + [np.asarray(o) for o in out[1:]]
    telemetry.bump("solve.fetched_bytes", sum(h.nbytes for h in host))
    return host


def _run_krylov(A, b, x0, tol, verbose, solve, minv=None, name="cg",
                info_extra=None):
    """Shared device-Krylov driver: stage vectors in the matrix's col
    layout, run the single compiled program, lift the result back to a
    host PVector. The info dict matches the host solvers' contract:
    `residuals` has iterations+1 entries (capped at the compiled history
    length); ``info_extra`` keys (e.g. the CG body variant) merge into
    it.

    Every boundary is a `telemetry.annotate` span (stage with its
    operator / pack / put leaves, solve = the dispatch, wait, fetch with
    its d2h / lift leaves, finish), so the host time of a call has an
    owner in a profile and in ``info.record.timings``."""
    from .. import telemetry
    from ..utils.helpers import krylov_info, warn_tol_below_floor

    backend = b.values.backend
    dev_dtype = _device_dtype(b.dtype)
    floor_warned = warn_tol_below_floor(tol, dev_dtype, name=name)
    rec = telemetry.current_record()
    with telemetry.annotate(f"pa:{name}:stage"):
        with telemetry.annotate("pa:stage:operator"):
            dA = device_matrix(A, backend)
        layout = dA.col_layout
        frames = [
            _pack(b, layout, backend, with_ghosts=False),
            _pack(x0, layout, backend) if x0 is not None
            else _zero_frame(layout, backend, b.dtype),
        ]
        if minv is not None:
            frames.append(_pack(minv, layout, backend))
        _count_staged(*frames)
    with telemetry.annotate(f"pa:{name}:solve"):
        out = solve(*frames)
    with telemetry.annotate(f"pa:{name}:wait"):
        # the host waits here while the device works, so that the fetch
        # below times the copy and not the solve
        out = _jax().block_until_ready(list(out))
    with telemetry.annotate(f"pa:{name}:fetch"):
        x, out = _answer_to_host(out, A.cols, layout, backend)
    with telemetry.annotate(f"pa:{name}:finish"):
        rs, rs0, it, hist = out[:4]
        k = 4
        sdcvec = None
        if getattr(solve, "has_sdc", False):
            sdcvec = out[k]
            k += 1
        trace_n = int(getattr(solve, "trace_iters", 0))
        ab = out[k] if trace_n else None
        rs, rs0, it = float(rs), float(rs0), int(it)
        residuals = hist[: min(it + 1, len(hist))]
        if rec is not None and rec.enabled:
            # attach BEFORE the typed-raise paths below: an aborted record
            # still carries its trace and comms accounting for post-mortems
            if ab is not None:
                abh = ab
                n = min(it, trace_n)
                if it > trace_n:
                    # true ring: the buffer holds the LAST trace_n committed
                    # iterations, rotated — unroll so entry j is absolute
                    # iteration trace_start + j
                    abh = np.roll(abh, -(it % trace_n), axis=0)
                    rec.trace_start = it - trace_n
                rec.alpha = [float(v) for v in abh[:n, 0]]
                rec.beta = [float(v) for v in abh[:n, 1]]
            ck = getattr(solve, "comms_kwargs", None)
            if ck is not None:
                profile = telemetry.cg_comms_profile(dA, b.dtype, **ck)
                # the SDC-defended loop pays its per-iteration collectives
                # on EVERY while trip (commit, audit, restore alike) — the
                # wire accounting counts trips, not committed iterations
                comm_it = int(sdcvec[4]) if sdcvec is not None else it
                rec.comms = telemetry.observed_comms(profile, comm_it)
        if verbose:
            for i, r in enumerate(residuals[1:], start=1):
                print(f"{name} it={i} residual={r:.3e}")
        from .health import NonFiniteError, health_enabled

        if sdcvec is not None:
            info_extra = {
                **(info_extra or {}),
                "sdc": _decode_sdc_outputs(name, sdcvec, it=it),
            }

        if health_enabled() and not (np.isfinite(rs) and np.isfinite(rs0)):
            # the compiled loop exited on its in-graph finite guard (one
            # iteration after the poison entered); surface it typed, with
            # the history tail as the diagnostic
            raise NonFiniteError(
                f"{name}: non-finite residual after {it} device iterations "
                f"(rs={rs!r}) — solver state was NaN/Inf-poisoned",
                diagnostics={
                    "context": name,
                    "iteration": it,
                    "rs": rs,
                    "residual_tail": [float(v) for v in residuals[-4:]],
                },
            )
        converged = bool(np.sqrt(rs) <= tol * max(1.0, np.sqrt(rs0)))
        info = krylov_info(
            it, residuals, converged, tol, dev_dtype, floor_warned,
            final_rel=_final_true_rel(
                A, x, b, np.sqrt(rs) / max(1.0, np.sqrt(rs0)), np.sqrt(rs0),
                tol, force=floor_warned,
            ),
            **(info_extra or {}),
        )
        # paspec: spectral estimate (α/β ring when carried, residual-history
        # rate always) + anomaly detection — host-side, on the still-active
        # record so convergence_anomaly events land in it. CG family ONLY:
        # the store's Lanczos/κ-rate semantics are CG's, and a bicgstab
        # rate EWMAing into the same key would skew CG forecasts
        if name in ("cg", "pcg"):
            telemetry.observe_solve(
                A, rec, info=info, dtype=b.dtype, minv=minv
            )
        return x, info


def _final_true_rel(A, x, b, rel_est, rs0_norm, tol, force=False):
    from ..models.solvers import _final_true_rel as impl

    return impl(A, x, b, rel_est, rs0_norm, tol, force=force)


def tpu_cg(
    A: PSparseMatrix,
    b: PVector,
    x0: Optional[PVector] = None,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    verbose: bool = False,
    minv: Optional[PVector] = None,
    fused: Optional[bool] = None,
) -> Tuple[PVector, dict]:
    """Device (preconditioned) CG: the whole loop is one compiled
    shard_map program. `minv` is an optional diagonal preconditioner (a
    PVector over A.cols holding the inverse diagonal in its owned
    entries). ``fused`` (default: resolved from ``PA_TPU_FUSED_CG``, ON
    outside strict-bits) selects the fused streaming body (see
    `make_cg_fn`). The info dict records which body ran under
    ``cg_body``."""
    from .. import telemetry

    backend = b.values.backend
    check(isinstance(backend, TPUBackend), "tpu_cg needs a TPU-backend PVector")
    maxiter = maxiter if maxiter is not None else 4 * A.rows.ngids
    fused = _resolve_fused(fused)
    body = "fused" if fused else "standard"
    name = "pcg" if minv is not None else "cg"
    with telemetry.solve_scope(
        name, backend="tpu", tol=float(tol), maxiter=int(maxiter),
        cg_body=body, dtype=str(np.dtype(b.dtype)),
        env_key=_lowering_env_key(),
    ) as rec:
        dA = device_matrix(A, backend)
        solve = _krylov_fn_for(
            dA, "cg", tol, maxiter, precond=minv is not None, fused=fused,
        )
        x, info = _run_krylov(
            A, b, x0, tol, verbose, solve, minv=minv, name=name,
            info_extra={"cg_body": body},
        )
        return x, rec.finish(info)


def _block_on_cols_layout(Bs, dA: DeviceMatrix, with_ghosts: bool = False):
    """Stage K column PVectors as ONE (P, W, K) device slab in the
    matrix's col layout (owned values; ``with_ghosts`` also places the
    ghost slots — used for start vectors that already carry a halo)."""
    from .. import telemetry

    layout = dA.col_layout
    K = len(Bs)
    dt = np.result_type(*[b.dtype for b in Bs])
    with telemetry.annotate("pa:stage:pack"):
        stacked = np.zeros((layout.P, layout.W, K), dtype=dt)
        for k, b in enumerate(Bs):
            for p, (iset, vals) in enumerate(
                zip(b.rows.partition.part_values(), b.values.part_values())
            ):
                vals = np.asarray(vals)
                stacked[
                    p, layout.o0 : layout.o0 + iset.num_oids, k
                ] = _owned(iset, vals)
                if with_ghosts:
                    stacked[p, layout.hid_slots[p], k] = _ghost(iset, vals)
    return _stage(dA.backend, stacked, layout.P)


def tpu_block_cg(
    A: PSparseMatrix,
    B,
    X0=None,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    verbose: bool = False,
    minv: Optional[PVector] = None,
    fused: Optional[bool] = None,
    column_errors: str = "raise",
) -> Tuple[list, dict]:
    """Device block (multi-RHS) CG: solve ``A x_k = b_k`` for every
    right-hand side in ``B`` (a sequence of PVectors over ``A.rows``) as
    ONE compiled program whose SpMV streams the operator once per K
    columns (`make_block_cg_fn`). ``minv`` is the usual shared diagonal
    preconditioner. Returns ``(xs, info)``: a list of K solution
    PVectors and an info dict whose ``columns`` entry holds one
    per-column krylov info each (iterations, residual history, status —
    each column's trajectory is its solo `tpu_cg` trajectory); the
    top-level fields aggregate (worst column).

    ``column_errors`` selects the per-column health contract:
    ``"raise"`` (default) raises `NonFiniteError` naming the poisoned
    columns — the single-caller semantics every pre-service test pins;
    ``"report"`` never raises for a column-local failure and instead
    exports per-column VERDICTS under ``info["column_health"]`` (one
    ``{"status", "converged", "iterations"}`` dict per column, status
    ``"ok"`` or ``"nonfinite"``) — the containment contract the solve
    service reads at its chunk boundaries to eject exactly the poisoned
    columns while the frozen-select block program has already let every
    other column finish bitwise equal to its solo solve."""
    from .. import telemetry

    check(
        column_errors in ("raise", "report"),
        "tpu_block_cg: column_errors is 'raise' or 'report'",
    )
    B = list(B)
    K = len(B)
    check(K >= 1, "tpu_block_cg: B must hold at least one right-hand side")
    backend = B[0].values.backend
    check(
        isinstance(backend, TPUBackend),
        "tpu_block_cg needs TPU-backend PVectors",
    )
    maxiter = maxiter if maxiter is not None else 4 * A.rows.ngids
    fused = _resolve_fused(fused)
    dt = np.result_type(*[b.dtype for b in B])
    name = "block-pcg" if minv is not None else "block-cg"
    with telemetry.solve_scope(
        name, backend="tpu", tol=float(tol), maxiter=int(maxiter),
        rhs_batch=K, cg_body="fused" if fused else "standard",
        dtype=str(np.dtype(dt)), env_key=_lowering_env_key(),
    ) as rec:
        xs, info = _tpu_block_cg_impl(
            A, B, X0, tol, maxiter, verbose, minv, fused, K, backend,
            dt, name, rec, column_errors=column_errors,
        )
        # which layout the program held the block in (`_block_lane_major`;
        # the operator is staged by now, so this is a look-up)
        lanes = _block_lane_major(
            device_matrix(A, backend), fused,
            _sdc_config(int(maxiter)) is not None,
        )
        info["block_layout"] = rec.config["block_layout"] = (
            "lanes" if lanes else "columns"
        )
        telemetry.bump("solve.block_lane_major", int(lanes))
        return xs, rec.finish(info)


def _tpu_block_cg_impl(
    A, B, X0, tol, maxiter, verbose, minv, fused, K, backend, dt, name,
    rec, column_errors="raise",
):
    from .. import telemetry
    from ..utils.helpers import krylov_info, warn_tol_below_floor

    # the spans of `_run_krylov`, boundary for boundary
    with telemetry.annotate(f"pa:{name}:stage"):
        with telemetry.annotate("pa:stage:operator"):
            dA = device_matrix(A, backend)
        solve = _krylov_fn_for(
            dA, "cg", tol, maxiter, precond=minv is not None, fused=fused,
            rhs_batch=K,
        )
        dev_dtype = _device_dtype(dt)
        floor_warned = warn_tol_below_floor(tol, dev_dtype, name="block-cg")
        db = _block_on_cols_layout(B, dA)
        if X0 is None:
            X0 = [PVector.full(0.0, A.cols, dtype=dt) for _ in range(K)]
        else:
            X0 = list(X0)
            check(
                len(X0) == K, "tpu_block_cg: X0 must hold one start per RHS"
            )
        dx0 = _block_on_cols_layout(X0, dA, with_ghosts=True)
        dmv = (
            DeviceVector.from_pvector(minv, backend, dA.col_layout)
            if minv is not None
            else None
        )
        _count_staged(db, dx0, None if dmv is None else dmv.data)
    with telemetry.annotate(f"pa:{name}:solve"):
        if dmv is not None:
            out = solve(db, dx0, dmv.data)
        else:
            out = solve(db, dx0)
    with telemetry.annotate(f"pa:{name}:wait"):
        out = _jax().block_until_ready(list(out))
    with telemetry.annotate(f"pa:{name}:fetch"):
        out = _outputs_to_host(out)
        xs = [
            _host_frame_to_pvector(out[0][..., k], A.cols, dA.col_layout)
            for k in range(K)
        ]
    with telemetry.annotate(f"pa:{name}:finish"):
        rs, rs0, itk, hist = out[1:5]
        k_out = 5
        sdcvec = None
        if getattr(solve, "has_sdc", False):
            sdcvec = out[k_out]
            k_out += 1
        trace_n = int(getattr(solve, "trace_iters", 0))
        ab = out[k_out] if trace_n else None
        if rec is not None and rec.enabled:
            trips = (
                int(np.asarray(sdcvec)[4])
                if sdcvec is not None
                else int(np.asarray(itk).max())
            )
            if ab is not None:
                abh = np.asarray(ab)  # (Ht, 2, K)
                # ring slots are indexed by the GLOBAL trip counter, which
                # equals the slowest column's committed count
                itks = np.asarray(itk).astype(int).ravel()
                itmax = int(itks.max())
                n = min(itmax, trace_n)
                if itmax > trace_n:
                    abh = np.roll(abh, -(itmax % trace_n), axis=0)
                    rec.trace_start = itmax - trace_n
                # per-column traces: alpha[k]/beta[k] is column k's list;
                # entries on trips AFTER column k converged are the frozen
                # α=0/stale-β selects, not recurrence values — masked None
                rec.alpha = [
                    [
                        float(abh[j, 0, k])
                        if rec.trace_start + j < itks[k] else None
                        for j in range(n)
                    ]
                    for k in range(K)
                ]
                rec.beta = [
                    [
                        float(abh[j, 1, k])
                        if rec.trace_start + j < itks[k] else None
                        for j in range(n)
                    ]
                    for k in range(K)
                ]
            ck = getattr(solve, "comms_kwargs", None)
            if ck is not None:
                profile = telemetry.cg_comms_profile(dA, dt, **ck)
                rec.comms = telemetry.observed_comms(profile, trips)
        sdc_info = (
            _decode_sdc_outputs("block-cg", sdcvec)
            if sdcvec is not None
            else None
        )
        rs = np.asarray(rs, dtype=np.float64)
        rs0 = np.asarray(rs0, dtype=np.float64)
        itk = np.asarray(itk, dtype=np.int64)
        hist = np.asarray(hist)
        columns = []
        for k in range(K):
            x = xs[k]
            it_k = int(itk[k])
            residuals = hist[: min(it_k + 1, hist.shape[0]), k]
            if verbose:
                for i, rv in enumerate(residuals[1:], start=1):
                    print(f"{name} col={k} it={i} residual={rv:.3e}")
            converged = bool(
                np.sqrt(rs[k]) <= tol * max(1.0, np.sqrt(rs0[k]))
            )
            columns.append(
                krylov_info(
                    it_k, residuals, converged, tol, dev_dtype, floor_warned,
                    final_rel=_final_true_rel(
                        A, x, B[k],
                        np.sqrt(rs[k]) / max(1.0, np.sqrt(rs0[k])),
                        np.sqrt(rs0[k]), tol, force=floor_warned,
                    ),
                )
            )
        from .health import NonFiniteError, health_enabled

        # per-column verdict export: the service's chunk-boundary contract
        # (status is per column, so ONE poisoned request never forces its
        # co-batched neighbors onto an error path). PA_HEALTH_CHECKS=0
        # disables the verdict along with the guards — matching the host
        # oracle, where no SolverHealthError fires (and so no verdict is
        # recorded) with health off — so the two per-column exports never
        # disagree.
        bad = (
            [k for k in range(K) if not np.isfinite(rs[k])]
            if health_enabled()
            else []
        )
        column_health = [
            {
                "status": "nonfinite" if k in bad else "ok",
                "converged": bool(columns[k]["converged"]),
                "iterations": int(itk[k]),
            }
            for k in range(K)
        ]
        if bad:
            if column_errors == "report":
                for k in bad:
                    columns[k]["status"] = "nonfinite"
                    columns[k]["converged"] = False
                telemetry.emit_event(
                    "column_verdict", label=name, columns=bad,
                    iterations=[int(itk[k]) for k in bad],
                )
            else:
                raise NonFiniteError(
                    f"{name}: non-finite residual in column(s) {bad} — those "
                    "columns' solver state was NaN/Inf-poisoned (each froze one "
                    "iteration after the poison entered; the other columns "
                    "completed normally)",
                    diagnostics={
                        "context": name,
                        "columns": bad,
                        "iterations": [int(itk[k]) for k in bad],
                        "rs": [float(rs[k]) for k in bad],
                    },
                )
        # the aggregate's "worst" column: an UNCONVERGED column wins over a
        # merely-slow converged one (a broken-down column frozen at 3
        # iterations must not let argmax(iterations) stamp the aggregate
        # status 'converged' while converged is False)
        bad_cols = [k for k in range(K) if not columns[k]["converged"]]
        worst = (
            max(bad_cols, key=lambda k: int(itk[k]))
            if bad_cols
            else int(np.argmax(itk))
        )
        info = {
            "iterations": int(itk.max()),
            "iterations_per_column": [int(v) for v in itk],
            "residuals": columns[worst]["residuals"],
            "converged": not bad_cols,
            "status": columns[worst]["status"],
            "columns": columns,
            "column_health": column_health,
            "rhs_batch": K,
            "cg_body": "fused" if fused else "standard",
        }
        if sdc_info is not None:
            info["sdc"] = sdc_info
        if floor_warned:
            info["tol_below_dtype_floor"] = True
        # paspec: per-column spectral estimates from the block ring (masked
        # post-convergence trips truncate), host-side, before rec.finish
        telemetry.observe_solve(A, rec, info=info, dtype=dt, minv=minv)
        return xs, info


def tpu_bicgstab(
    A: PSparseMatrix,
    b: PVector,
    x0: Optional[PVector] = None,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    minv: Optional[PVector] = None,
    verbose: bool = False,
) -> Tuple[PVector, dict]:
    """Device BiCGStab (nonsymmetric Krylov), one compiled program;
    ``minv`` is an optional inverse-diagonal RIGHT preconditioner."""
    from .. import telemetry

    backend = b.values.backend
    check(
        isinstance(backend, TPUBackend), "tpu_bicgstab needs a TPU-backend PVector"
    )
    maxiter = maxiter if maxiter is not None else 4 * A.rows.ngids
    with telemetry.solve_scope(
        "bicgstab", backend="tpu", tol=float(tol), maxiter=int(maxiter),
        dtype=str(np.dtype(b.dtype)), env_key=_lowering_env_key(),
    ) as rec:
        dA = device_matrix(A, backend)
        solve = _krylov_fn_for(
            dA, "bicgstab", tol, maxiter, precond=minv is not None
        )
        x, info = _run_krylov(
            A, b, x0, tol, verbose, solve, minv=minv, name="bicgstab"
        )
        return x, rec.finish(info)


def _krylov_fn_for(
    dA: DeviceMatrix, method: str, tol: float, maxiter: int,
    precond: bool = False, fused: Optional[bool] = None,
    rhs_batch: Optional[int] = None,
):
    # the SDC config (audit period, budgets, tolerance overrides, the
    # device fault clause) is resolved at build time — key it so an env
    # flip rebuilds the program instead of serving a stale defense
    sdccfg = _sdc_config(int(maxiter))
    if method == "cg":
        # the cache key must be the CONCRETE body choice (the env mode is
        # also part of _lowering_env_key, which rekeys the DeviceMatrix
        # itself on a flip)
        fused = _resolve_fused(fused)
    # the trace-ring depth changes the traced program (an extra carry),
    # so it joins the key through the same helper make_cg_fn resolves
    # it with (_trace_config — a registered env-key site). Key the
    # EFFECTIVE depth, mirroring the builders' clamps: the SDC-defended
    # block body and bicgstab have no ring, and depth saturates at
    # maxiter — a PA_TRACE_ITERS flip must not rebuild a program the
    # flip cannot reach.
    from .. import telemetry

    if method != "cg" or (rhs_batch is not None and sdccfg is not None):
        trace_ht = 0
        requested = _trace_config()
        if requested > 0:
            # trace-ring exemption HONESTY: a body that cannot carry
            # the α/β ring must say so — a typed event names the body,
            # so a missing spectrum is explained, never mysterious
            # (tools/paspec.py and tools/patrace.py surface it)
            body = "sdc-block" if method == "cg" else method
            telemetry.emit_event(
                "trace_unavailable", label=body, requested=requested,
                method=method,
                reason="this body carries no alpha/beta trace ring — "
                       "spectral estimates fall back to the residual "
                       "history",
            )
    else:
        trace_ht = int(min(_trace_config(), int(maxiter)))
    key = (
        method, float(tol), int(maxiter), bool(precond), bool(fused),
        rhs_batch, sdccfg["key"] if sdccfg else None, trace_ht,
    )

    if key not in dA._cg_cache:
        telemetry.bump("program_cache.miss")
        telemetry.emit_event(
            "compile_cache", label="program_miss", cache="program",
            action="miss", method=method,
        )
        if method == "cg":
            dA._cg_cache[key] = make_cg_fn(
                dA, tol, maxiter, precond=precond, fused=fused,
                rhs_batch=rhs_batch,
            )
        else:
            dA._cg_cache[key] = make_bicgstab_fn(
                dA, tol, maxiter, precond=precond
            )
    else:
        telemetry.bump("program_cache.hit")
        telemetry.emit_event(
            "compile_cache", label="program_hit", cache="program",
            action="hit", method=method,
        )
    return dA._cg_cache[key]


def _b_on_cols_layout(b: PVector, dA: DeviceMatrix) -> DeviceVector:
    """b lives on A.rows (no ghosts); the compiled CG keeps every vector in
    the cols layout (same owned gids). Its owned values go there."""
    layout = dA.col_layout
    data = _jax().block_until_ready(
        _pack(b, layout, dA.backend, with_ghosts=False)
    )
    return DeviceVector(data, dA.cols, layout, dA.backend)


# ---------------------------------------------------------------------------
# the lowering matrix: palint's program enumeration (analysis/)
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _env_overrides(env: dict):
    """Apply env-var overrides (value ``None`` deletes) for the scope of
    a with-block, restoring the previous state on exit. Used by the
    lowering-matrix report hook so each case's programs are built under
    exactly the case's mode set, whatever the ambient environment."""
    old = {k: os.environ.get(k) for k in env}
    try:
        for k, v in env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


#: EVERY lowering-affecting flag, pinned to its default for matrix
#: cases unless the case explicitly overrides — a case's program (and
#: the contracts/copy-budgets pinned against it) must not depend on
#: what the ambient shell happened to export. This list must stay the
#: full lowering-affecting set the env lint classifies;
#: tests/test_static_analysis.py pins the agreement.
_MATRIX_BASE_ENV = {
    "PA_TPU_ABFT": None,
    "PA_TPU_STRICT_BITS": None,
    "PA_HEALTH_AUDIT_EVERY": None,
    "PA_TPU_FUSED_CG": None,
    "PA_TPU_BOX": None,
    "PA_FAULT_DEVICE": None,
    "PA_TPU_ABFT_TOL": None,
    "PA_HEALTH_AUDIT_TOL": None,
    "PA_TPU_BSR": None,
    "PA_TPU_SD": None,
    "PA_TPU_CLASS_ACC": None,
    "PA_TPU_OH_BUCKETS": None,
    "PA_TPU_ELL_GUARD": None,
    "PA_TPU_ELL_MAX_GATHER": None,
    "PA_HEALTH_ROLLBACK_DEPTH": None,
    "PA_HEALTH_MAX_ROLLBACKS": None,
    "PA_TPU_GMG_BOX": None,
    "PA_TPU_GMG_STENCIL": None,
    "PA_TRACE_ITERS": None,
}


def lowering_matrix(fast: bool = False):
    """Enumerate the compiled-CG lowering variants whose structural
    contracts palint checks (analysis/contracts.py): the CG body forms
    (standard / fused / block rhs_batch∈{1,4}) crossed with the mode
    axes that restructure their programs (ABFT on/off on the like-plan
    PA_TPU_BOX=0 baseline — the same A/B discipline as
    tests/test_abft.py — and strict-bits, which pins the unfused ELL
    oracle).

    Each case is a plain dict: ``name``, ``env`` (overrides layered on
    `_MATRIX_BASE_ENV`), ``kwargs`` (forwarded to `make_cg_fn`),
    ``dtype`` (probe-system dtype), and ``tags`` (the contract layer's
    grouping labels). ``fast=True`` returns the tier-1 subset (the
    cheap cases every CI run lowers); the full set is palint's.
    """
    nobox = {"PA_TPU_BOX": "0"}
    abft = {"PA_TPU_ABFT": "1", "PA_TPU_BOX": "0"}
    cases = [
        dict(name="standard", env={}, kwargs={"fused": False},
             dtype="f64", tags={"body": "standard"}),
        dict(name="fused", env={}, kwargs={"fused": True},
             dtype="f64", tags={"body": "fused"}),
        dict(name="block_k1_fused", env={},
             kwargs={"fused": True, "rhs_batch": 1},
             dtype="f64", tags={"body": "block", "K": 1, "block_of": "fused"}),
        dict(name="block_k4_fused", env={},
             kwargs={"fused": True, "rhs_batch": 4},
             dtype="f64", tags={"body": "block", "K": 4, "block_of": "fused"}),
        dict(name="standard_nobox", env=nobox, kwargs={"fused": False},
             dtype="f64", tags={"body": "standard", "plan": "generic"}),
        dict(name="standard_abft", env=abft, kwargs={"fused": False},
             dtype="f64",
             tags={"body": "standard", "abft": True,
                   "abft_off": "standard_nobox"}),
        dict(name="standard_f32", env={}, kwargs={"fused": False},
             dtype="f32", tags={"body": "standard", "staged": "f32"}),
    ]
    if fast:
        return cases
    cases += [
        dict(name="block_k1_standard", env={},
             kwargs={"fused": False, "rhs_batch": 1},
             dtype="f64",
             tags={"body": "block", "K": 1, "block_of": "standard"}),
        dict(name="block_k4_standard", env={},
             kwargs={"fused": False, "rhs_batch": 4},
             dtype="f64",
             tags={"body": "block", "K": 4, "block_of": "standard"}),
        dict(name="fused_nobox", env=nobox, kwargs={"fused": True},
             dtype="f64", tags={"body": "fused", "plan": "generic"}),
        dict(name="block_k4_fused_nobox", env=nobox,
             kwargs={"fused": True, "rhs_batch": 4},
             dtype="f64",
             tags={"body": "block", "K": 4, "block_of": "fused",
                   "plan": "generic"}),
        dict(name="fused_abft", env=abft, kwargs={"fused": True},
             dtype="f64",
             tags={"body": "fused", "abft": True, "abft_off": "fused_nobox"}),
        dict(name="block_k4_fused_abft", env=abft,
             kwargs={"fused": True, "rhs_batch": 4},
             dtype="f64",
             tags={"body": "block", "K": 4, "block_of": "fused",
                   "abft": True, "abft_off": "block_k4_fused_nobox"}),
        dict(name="strict_standard", env={"PA_TPU_STRICT_BITS": "1"},
             kwargs={"fused": False}, dtype="f64",
             tags={"body": "standard", "strict": True}),
        dict(name="fused_f32", env={}, kwargs={"fused": True},
             dtype="f32", tags={"body": "fused", "staged": "f32"}),
    ]
    return cases


def _matrix_probe_system(backend: "TPUBackend", dtype: str):
    """The small fixed probe operator every matrix case lowers: the
    (6, 6, 6) Poisson system on a (2, 2, 2) box partition — big enough
    that every exchange round and both dot gathers appear, small enough
    that the full matrix lowers in seconds. Returns ``(A, b, x0)`` (the
    Dirichlet start vector — the probe system needs its boundary lift;
    a zero start diverges). Cached per (backend token, dtype) — the
    DeviceMatrix env-rekeying happens downstream in `device_matrix`,
    not here."""
    from ..models import assemble_poisson
    from .backends import prun

    np_dtype = np.float32 if dtype == "f32" else np.float64

    def driver(parts):
        A, b, xe, x0 = assemble_poisson(parts, (6, 6, 6), dtype=np_dtype)
        return A, b, x0

    cache = getattr(backend, "_palint_probe", None)
    if cache is None:
        cache = backend._palint_probe = {}
    if dtype not in cache:
        cache[dtype] = prun(driver, backend, (2, 2, 2))
    return cache[dtype]


def case_program_texts(
    backend: "TPUBackend", case: dict, with_compiled: bool = False,
    tol: float = 1e-9, maxiter: int = 50,
) -> Tuple[str, Optional[str], Optional[dict]]:
    """The lowering-matrix report hook: build ``case``'s compiled-CG
    program against the fixed probe system ONCE and return
    ``(stablehlo_text, hlo_text, memory_stats)`` — the optimized-HLO
    leg (where the ``copy``-budget canary lives) is derived from the
    same `Lowered` object, not a second trace; it and the memory stats
    are None unless ``with_compiled``. ``memory_stats`` is the
    compiled program's XLA buffer assignment
    (``compile().memory_analysis()`` — argument/output/temp bytes, the
    static-peak input of `analysis.memory_report`), or None where the
    runtime does not expose it. The case's env overrides are applied
    around BOTH the matrix staging and the program build, so the
    program really is the one a user under that environment gets —
    including the `_lowering_env_key` rekeying path."""
    env = dict(_MATRIX_BASE_ENV)
    env.update(case.get("env", {}))
    with _env_overrides(env):
        A, b, _x0 = _matrix_probe_system(backend, case.get("dtype", "f64"))
        dA = device_matrix(A, backend)
        ops = _matrix_operands(dA)
        kwargs = dict(case.get("kwargs", {}))
        rhs_batch = kwargs.get("rhs_batch")
        fn = make_cg_fn(dA, tol, maxiter, **kwargs)
        L = dA.col_plan.layout
        np_dtype = np.float32 if case.get("dtype") == "f32" else np.float64
        if rhs_batch:
            z = np.zeros((L.P, L.W, rhs_batch), dtype=np_dtype)
            args = (z, z, z[..., 0], ops)
        else:
            z = np.zeros((L.P, L.W), dtype=np_dtype)
            args = (z, z, z, ops)
        low = fn.jit_fn.lower(*args)
        if not with_compiled:
            return low.as_text(), None, None
        compiled = low.compile()
        ma = compiled.memory_analysis()
        mem = None if ma is None else {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        return low.as_text(), compiled.as_text(), mem


def case_probe_solve(
    backend: "TPUBackend", case: dict, tol: Optional[float] = None,
    maxiter: int = 50,
):
    """Run ``case``'s compiled-CG program against the fixed probe
    system under the case's pinned env and return the finished
    telemetry `SolveRecord` — the MEASURED half of the
    static-vs-measured comms reconciliation contract
    (analysis.contracts: ``static-measured-reconciliation``). The
    solve goes through the public drivers (`tpu_cg` /
    `tpu_block_cg`), so the record's comms accounting is exactly what
    a user's solve would report."""
    from .. import telemetry

    env = dict(_MATRIX_BASE_ENV)
    env.update(case.get("env", {}))
    with _env_overrides(env):
        A, b, x0 = _matrix_probe_system(backend, case.get("dtype", "f64"))
        kwargs = dict(case.get("kwargs", {}))
        rhs_batch = kwargs.pop("rhs_batch", None)
        if tol is None:
            # stay above the f32 resolution floor so the probe solve
            # converges quietly in either dtype
            tol = 1e-4 if case.get("dtype") == "f32" else 1e-9
        if rhs_batch:
            _, info = tpu_block_cg(
                A, [b] * rhs_batch, X0=[x0] * rhs_batch, tol=tol,
                maxiter=maxiter, **kwargs,
            )
        else:
            _, info = tpu_cg(A, b, x0=x0, tol=tol, maxiter=maxiter, **kwargs)
    rec = getattr(info, "record", None)
    check(
        rec is not None and rec.comms is not None,
        "case_probe_solve: the probe solve produced no telemetry comms "
        "accounting (PA_METRICS=0 in the ambient environment?)",
    )
    return rec


def case_program_text(
    backend: "TPUBackend", case: dict, compiled: bool = False,
    tol: float = 1e-9, maxiter: int = 50,
) -> str:
    """One dialect of `case_program_texts` (StableHLO by default,
    optimized HLO with ``compiled=True``)."""
    stablehlo, hlo, _mem = case_program_texts(
        backend, case, with_compiled=compiled, tol=tol, maxiter=maxiter
    )
    return hlo if compiled else stablehlo
