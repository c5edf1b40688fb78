"""Extended-box halo exchange: slice-based pack/unpack for Cartesian
partitions.

The generic device exchange (DeviceExchangePlan in tpu.py) packs with a
gather ``xv[snd_idx]`` and unpacks with a scatter ``xv.at[rcv_idx].set``
— on TPU both run element-at-a-time (~4.5 ns/element, measured), which
left the compiled halo path SLOWER than the host oracle (144 MB/s at
192³, round-2 bench). This module detects the box structure almost every
real workload has — Cartesian partitions whose per-part owned ids are a
C-order box scan (reference: the N-D block constructors,
src/Interfaces.jl:1114-1231, and the FDM ghost discovery of
test/test_fdm.jl:82-100) — and lowers the same Exchanger plan to:

* pack: a static slice of the part's owned box (no gather), every
  direction's taken from the operand as it arrives and addressed in
  the cheapest form its geometry allows (`face_form`: a run of the flat
  frame, a block of 128-lane rows, or the box's own view taken once —
  the rule of `_spmv_body._oh_slabs` in tpu.py, which has what each
  form read on the chip),
* wire: one `ppermute` per geometric direction (the same partial
  permutation per round the generic plan's edge coloring produces),
* unpack: a static contiguous store into a per-direction ghost SEGMENT.

The ghost region of the device layout is reordered into those segments
(slot maps only — host lid order, and hence every conformance result, is
untouched; the reorder lives in DeviceLayout.lid_slots exactly like the
generic layout's owned-first maps). Each direction's segment is the
sender's sub-box in C-order scan, so sender slice order == receiver slot
order by construction and the unpack needs no index vector at all.

SPMD constraint: one compiled program serves every shard, so the pack
slice bounds must be shard-invariant. The analysis therefore requires
equal per-part box shapes and per-direction-uniform sub-boxes (the
standard evenly-divided Cartesian split); anything else — unequal boxes,
irregular graphs, partial shells — returns None and the caller keeps the
generic gather plan.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import numpy as np

from ..utils.table import INDEX_DTYPE
from .prange import PRange


class BoxDir:
    """One geometric direction of the box exchange: a static sender
    sub-box PER BOX-SHAPE VARIANT (start/shape relative to the owned
    box — unequal Cartesian splits produce <= 2^d variants and each
    shard packs with its own variant's static slice), the receiver
    segment offset into the ghost region, and the ppermute pairs. The
    segment is sized to the LARGEST variant's slab; smaller variants
    pad (receiver-side slot maps, computed host-side from the SENDER's
    geometry, only ever address real positions)."""

    __slots__ = ("dir", "geo", "off", "size", "perm")

    def __init__(self, dir, geo, off, perm):
        self.dir = tuple(dir)
        #: per variant: (start, shape) of the pack slice, or a (0..,
        #: 1..) degenerate slice for variants with no edge in this dir
        self.geo = tuple(
            (tuple(int(x) for x in s), tuple(int(x) for x in sh))
            for s, sh in geo
        )
        self.off = int(off)
        self.size = max(int(math.prod(sh)) for _, sh in self.geo)
        self.perm = tuple(perm)

    # single-variant convenience (the equal-box fast consumers)
    @property
    def start(self):
        return self.geo[0][0]

    @property
    def shape(self):
        return self.geo[0][1]


class BoxInfo:
    """Result of `analyze_box_structure`: everything the device layout
    and the exchange body need, all host-side."""

    __slots__ = (
        "box_shapes", "variants", "dirs", "nh_total", "ghost_rel_slots",
        "seg_mask", "P",
    )

    def __init__(
        self, box_shapes, variants, dirs, nh_total, ghost_rel_slots,
        seg_mask, P,
    ):
        #: distinct per-part owned-box shapes (sorted; <= 2^d for
        #: Cartesian splits) and each part's index into them
        self.box_shapes = tuple(tuple(s) for s in box_shapes)
        self.variants = np.asarray(variants, dtype=np.int32)
        self.dirs = tuple(dirs)
        self.nh_total = int(nh_total)
        #: per part: hid -> slot index relative to g0 (segment layout)
        self.ghost_rel_slots = ghost_rel_slots
        #: (P, nh_total) bool: True where a segment slot is a REAL ghost.
        #: Slab packing ships whole bounding slabs, so boundary-trimmed
        #: shells leave orphan slots holding sender values after a
        #: forward exchange; the reverse (assembly) path multiplies by
        #: this mask so orphans never accumulate into owners.
        self.seg_mask = seg_mask
        self.P = int(P)

    @property
    def box_shape(self):
        """The single box shape of an equal-box plan (the consumers that
        read this — the stencil-transfer staging, the halo bench — only
        operate on single-variant plans)."""
        assert len(self.box_shapes) == 1, "multi-variant plan"
        return self.box_shapes[0]


def _logical_coords(gids, gdims, lo, hi):
    """Global gids -> logical coordinates relative to a part's box
    [lo, hi): periodic ghosts wrap, so per dimension the logical cell is
    whichever of {c, c-n, c+n} lies NEAREST the box (distance 0 inside).
    Returns None when two candidates tie — geometric ambiguity the
    generic plan handles instead."""
    coords = np.stack(np.unravel_index(np.asarray(gids, dtype=np.int64), gdims))
    out = np.empty_like(coords)
    for d, n in enumerate(gdims):
        c = coords[d]
        cands = np.stack([c, c - n, c + n])  # (3, m)
        dist = np.maximum(np.maximum(lo[d] - cands, cands - (hi[d] - 1)), 0)
        pick = dist.argmin(axis=0)
        m = np.arange(cands.shape[1])
        best_d = dist[pick, m]
        # ambiguity: another candidate at the same distance (a domain so
        # small the wrap is geometrically ambiguous)
        if ((dist == best_d[None, :]).sum(axis=0) > 1).any():
            return None
        out[d] = cands[pick, m]
    return out


def analyze_box_structure(rows: PRange) -> Optional[BoxInfo]:
    """Detect the uniform-box halo structure of a Cartesian PRange (see
    module docstring). Pure host analysis; returns None whenever ANY
    precondition fails, so callers can fall back silently."""
    isets = rows.partition.part_values()
    P = len(isets)
    if P == 0:
        return None
    gdims = getattr(isets[0], "grid_shape", None)
    if gdims is None:
        return None
    dim = len(gdims)
    for i in isets:
        if getattr(i, "grid_shape", None) != gdims:
            return None
        if not getattr(i, "owned_first", True):
            return None
    # unequal Cartesian splits (floor/ceil interval lengths per dim)
    # produce <= 2^d distinct box shapes: each becomes a pack-slice
    # VARIANT selected per shard by a lax.switch in the exchange body.
    # EMPTY boxes are the agglomerated-coarse-level case (tpu_gmg
    # part_stride parks whole parts): an INACTIVE part — no owned ids
    # AND no ghosts — is admitted as a degenerate variant that never
    # sends or receives, so slab-shaped transfer ghost sets on the
    # active parts still get the slice plan (the matrix-S fallback used
    # to drop to the generic gather plan here).
    # An empty box WITH ghosts is not that case — decline.
    for i in isets:
        if math.prod(i.box_shape) == 0 and i.num_hids:
            return None
    box_shapes = sorted({i.box_shape for i in isets})
    if sum(1 for s in box_shapes if math.prod(s) > 0) > (1 << dim):
        return None  # not a tensor-product split
    variants = np.array(
        [box_shapes.index(i.box_shape) for i in isets], dtype=np.int32
    )
    # owned ids must be the C-order box scan (slot = o0 + ohid relies on
    # it). CartesianIndexSet guarantees this by contract (the owned block
    # IS the box scan — index_sets.py), so an O(1) spot check suffices:
    # materializing the full meshgrid here costs GBs at 1e8 DOFs
    for i in isets:
        og = np.asarray(i.oid_to_gid)
        if len(og) != math.prod(i.box_shape):
            return None
        if len(og):
            first = np.ravel_multi_index(i.box_lo, gdims)
            last = np.ravel_multi_index(
                tuple(h - 1 for h in i.box_hi), gdims
            )
            if og[0] != first or og[-1] != last:
                return None

    exchanger = rows.exchanger
    parts_snd = [np.asarray(t) for t in exchanger.parts_snd.part_values()]
    parts_rcv = [np.asarray(t) for t in exchanger.parts_rcv.part_values()]
    lids_snd = exchanger.lids_snd.part_values()
    lids_rcv = exchanger.lids_rcv.part_values()

    # directional groups: dir tuple -> list of (p, q, rel_coords, hids)
    # where rel_coords are sender-box-relative logical coordinates —
    # comparable across parts, which is what makes slab packing SPMD-safe
    groups = {}
    covered = [np.zeros(i.num_hids, dtype=bool) for i in isets]
    for p in range(P):
        iset_p = isets[p]
        for j, q in enumerate(parts_snd[p]):
            q = int(q)
            hits = np.nonzero(parts_rcv[q] == p)[0]
            if len(hits) != 1:
                return None
            i_edge = int(hits[0])
            snd_l = np.asarray(lids_snd[p][j])
            rcv_l = np.asarray(lids_rcv[q][i_edge])
            if len(snd_l) != len(rcv_l) or len(snd_l) == 0:
                return None
            gids = np.asarray(iset_p.lid_to_gid)[snd_l]
            # sender side: all owned -> global coords ARE logical coords
            sc = _logical_coords(gids, gdims, iset_p.box_lo, iset_p.box_hi)
            if sc is None:
                return None
            if ((sc < np.array(iset_p.box_lo)[:, None])
                    | (sc >= np.array(iset_p.box_hi)[:, None])).any():
                return None  # exchanger sends non-owned ids?
            # receiver side: logical position relative to q's box gives
            # the geometric direction of each element
            iset_q = isets[q]
            qc = _logical_coords(gids, gdims, iset_q.box_lo, iset_q.box_hi)
            if qc is None:
                return None
            dir_of = np.zeros((dim, len(gids)), dtype=np.int8)
            for d in range(dim):
                dir_of[d] = (qc[d] >= iset_q.box_hi[d]).astype(np.int8) - (
                    qc[d] < iset_q.box_lo[d]
                ).astype(np.int8)
            if (dir_of == 0).all(axis=0).any():
                return None  # a "ghost" inside the receiver's own box
            rel = sc - np.array(iset_p.box_lo, dtype=np.int64)[:, None]
            hids_all = -np.asarray(iset_q.lid_to_ohid)[rcv_l] - 1
            if (hids_all < 0).any():
                return None  # receiver lid not a ghost
            # split the edge by direction (periodic k=2 sends both faces
            # of one axis to the same neighbor in a single edge)
            keys = [tuple(dir_of[:, e]) for e in range(len(gids))]
            uniq = {}
            for e, k in enumerate(keys):
                uniq.setdefault(k, []).append(e)
            for k, idx in uniq.items():
                idx = np.asarray(idx)
                hids = hids_all[idx]
                if covered[q][hids].any():
                    return None
                covered[q][hids] = True
                groups.setdefault(k, []).append((p, q, rel[:, idx], hids))
    for p in range(P):
        if not covered[p].all():
            return None  # some ghost never receives (stale-slot hazard)

    # per direction: the bounding SLAB over every edge's sub-box, PER
    # SENDER VARIANT — one static pack slice per (direction, box shape)
    # serving every shard (boundary-trimmed shells, e.g. Dirichlet-
    # decoupled stencils whose domain-boundary rows request no ghosts,
    # simply leave orphan slab slots — see seg_mask). Each receiver's
    # slot map is computed from its SENDER's slab geometry host-side, so
    # the device-side unpack stays one contiguous segment store.
    dirs = []
    ghost_rel = [np.full(i.num_hids, -1, dtype=INDEX_DTYPE) for i in isets]
    off = 0
    V = len(box_shapes)
    for k in sorted(groups):
        entries = groups[k]
        # bounding slab per sender variant
        slab_lo = [None] * V
        slab_hi = [None] * V
        for p, q, rel, hids in entries:
            v = int(variants[p])
            lo_e, hi_e = rel.min(axis=1), rel.max(axis=1) + 1
            slab_lo[v] = lo_e if slab_lo[v] is None else np.minimum(slab_lo[v], lo_e)
            slab_hi[v] = hi_e if slab_hi[v] is None else np.maximum(slab_hi[v], hi_e)
        geo = []
        for v in range(V):
            if slab_lo[v] is None:
                # variant never sends in this direction: any in-bounds
                # degenerate slice keeps the switch branch well-formed.
                # An EMPTY (inactive-part) variant has no in-bounds
                # element at all — its branch slices zero elements.
                if math.prod(box_shapes[v]) == 0:
                    geo.append(((0,) * dim, (0,) * dim))
                else:
                    geo.append(((0,) * dim, (1,) * dim))
            else:
                geo.append(
                    (
                        tuple(int(x) for x in slab_lo[v]),
                        tuple(int(x) for x in (slab_hi[v] - slab_lo[v])),
                    )
                )
        senders, receivers = set(), set()
        perm = []
        for p, q, rel, hids in entries:
            if p in senders or q in receivers:
                return None  # not a partial permutation
            senders.add(p)
            receivers.add(q)
            perm.append((p, q))
            v = int(variants[p])
            lo_v, shape_v = geo[v]
            pos = np.ravel_multi_index(
                tuple(rel - np.asarray(lo_v)[:, None]), shape_v
            )
            if len(np.unique(pos)) != len(pos):
                return None
            ghost_rel[q][hids] = off + pos
        d = BoxDir(k, geo, off, sorted(perm))
        dirs.append(d)
        off += d.size
    nh_total = off
    seg_mask = np.zeros((P, max(nh_total, 1)), dtype=bool)
    for p in range(P):
        if (ghost_rel[p] < 0).any():
            return None
        seg_mask[p, ghost_rel[p]] = True
    return BoxInfo(
        box_shapes, variants, dirs, nh_total, ghost_rel, seg_mask, P
    )


def box_structure(rows: PRange) -> Optional[BoxInfo]:
    """Cached `analyze_box_structure` (the analysis walks every edge)."""
    cache = getattr(rows, "_box_info", None)
    if cache is None:
        rows._box_info = cache = [None, False]  # [info, computed]
    if not cache[1]:
        cache[0] = analyze_box_structure(rows)
        cache[1] = True
    return cache[0]


def slab_split_axis(box, shape) -> int:
    """The axis a sub-box of ``shape`` is addressed from inside an owned
    block ``box`` (C-order scan). Flattened from axis ``a`` on, the
    block is ``box[:a] + (prod(box[a:]),)``, and the sub-box padded out
    to whole steps of ``a`` is ONE run of that last axis: the smallest
    ``a`` whose run stays within `DeviceMatrix.OH_SLAB_MAX_FILL` times
    the sub-box. 0 for a face normal to the slowest axis, 1 for the
    next, and so on; the boundary rows (`_spmv_body._oh_slabs`) and the
    exchange's pack (`face_form`) split by this one rule."""
    from .tpu import DeviceMatrix

    return next(
        a for a in range(len(box))
        if shape[a] * math.prod(box[a + 1 :])
        <= DeviceMatrix.OH_SLAB_MAX_FILL * math.prod(shape[a:])
    )


#: how `shard_box_exchange` addresses the face a direction packs
FACE_FORMS = ("flat", "lane", "boxview")


def face_form(box, shape) -> Tuple[str, int]:
    """``(form, a)`` of the pack of a sub-box of ``shape`` out of the
    owned block ``box``, ``a`` its split axis (`slab_split_axis`, as the
    boundary rows split):

    * ``'flat'`` (``a == 0``): the covering run is a slice of the flat
      frame itself;
    * ``'lane'`` (an inner ``a`` whose flattened axis is whole 128-lane
      rows): the lane rows the run touches, out of the view
      ``box[:a] + (rows, LANES)``, which on the padded frame is the flat
      order itself;
    * ``'boxview'`` (anything else: a face normal to the fastest axis,
      an edge or corner whose covering run would pass the fill, planes
      that are no whole lane rows): a slice of the box's own shape.

    Shapes only: every shard, rank of operand and frame takes the same
    form, and the compact frame (nothing aligned) takes it as a plain
    reshape."""
    from ..ops.pallas_dia import LANES

    a = slab_split_axis(box, shape)
    if a == 0:
        return "flat", a
    if a < len(box) - 1 and math.prod(box[a:]) % LANES == 0:
        return "lane", a
    return "boxview", a


class BoxExchangePlan:
    """Slice-based halo program over a box layout: one `ppermute` per
    direction, static pack slices, static unpack segments. Drop-in for
    DeviceExchangePlan inside `_shard_exchange` (the body ignores the
    si/sm/ri index operands — everything is compiled in)."""

    __slots__ = ("layout", "info", "reverse_mode")

    def __init__(self, layout, info: BoxInfo, reverse_mode: bool = False):
        self.layout = layout
        self.info = info
        self.reverse_mode = bool(reverse_mode)

    @property
    def R(self) -> int:  # round count, for parity with the generic plan
        return len(self.info.dirs)

    def reverse(self) -> "BoxExchangePlan":
        return BoxExchangePlan(self.layout, self.info, not self.reverse_mode)

    def pack_forms(self) -> list:
        """The `face_form` of every forward pack the body compiles: one a
        direction and box-shape variant, directions outermost."""
        return [
            face_form(box, shape)[0]
            for d in self.info.dirs
            for box, (_start, shape) in zip(self.info.box_shapes, d.geo)
        ]


def shard_box_exchange(plan: BoxExchangePlan, combine: str):
    """Per-shard exchange body with the SAME signature as tpu.py's
    `_shard_exchange` bodies: body(xv, si, sm, ri) — the three index
    operands are ignored (dummies keep the operand pytree uniform).

    Forward (owner->ghost, combine='set'): every direction's face is
    taken from the operand AS IT ARRIVES (a pack reads owned slots only
    and a store writes ghost slots only, so no pack waits for a store),
    each in its `face_form`, the addressing rule of the boundary rows
    (`_spmv_body._oh_slabs` in tpu.py); then one `ppermute` a direction;
    unpack = static contiguous segment store.
    Reverse (ghost->owner, combine='add'): pack = the contiguous segment,
    unpack = static strided `.add` into the owned box; ghosts zeroed
    after, like the generic plan and the host `assemble`.

    Rank-polymorphic over the operand: ``xv`` is ``(W,)`` for a single
    vector or ``(W, K)`` for a multi-RHS block — slot geometry stays on
    the leading axis (the owned box reshapes to ``box_shape + (K,)``),
    so each direction's `ppermute` ships the whole K-column slab in one
    wire round."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas_dia import LANES
    from ..utils.helpers import check
    from .tpu import SCOPE_EX_PACK, SCOPE_EX_UNPACK

    # reversal is explicit for box plans (no reversed index vectors to
    # encode it in): forward plans pair with 'set', reversed with 'add'
    check(
        plan.reverse_mode == (combine == "add"),
        "box exchange: combine mode does not match the plan direction — "
        "use plan.reverse() for ghost->owner assembly",
    )
    layout = plan.layout
    info = plan.info
    o0, g0 = layout.o0, layout.g0
    shapes = info.box_shapes
    V = len(shapes)

    def _tail(xv):
        return tuple(xv.shape[1:])  # () or (K,)

    def _pack_all(xv, v):
        """Variant v's static packs, one a direction, all out of the one
        operand: each face in its `face_form`, trimmed to its slab,
        flattened to the segment's order and padded to its size. The
        directions of one lane-row split share that view of the owned
        block, the box-view ones the box's own."""
        box = shapes[v]
        dim = len(box)
        no_v = int(math.prod(box))
        tail = _tail(xv)
        views = {}

        def view(shape):
            if shape not in views:
                views[shape] = xv[o0 : o0 + no_v].reshape(shape + tail)
            return views[shape]

        def cut(start, shape, axes):
            return tuple(
                slice(start[j], start[j] + shape[j]) for j in axes
            )

        bufs = []
        for d in info.dirs:
            start, shape = d.geo[v]
            form, a = face_form(box, shape)
            if form == "boxview":
                buf = view(box)[cut(start, shape, range(dim))]
            else:
                step = math.prod(box[a + 1 :])  # a step of axis a
                lo = start[a] * step
                hi = lo + shape[a] * step
                if form == "flat":
                    run = xv[o0 + lo : o0 + hi]
                else:
                    r0, r1 = lo // LANES, -(-hi // LANES)
                    rows = view(box[:a] + (box[a] * step // LANES, LANES))
                    run = jax.lax.slice_in_dim(
                        rows[
                            cut(start, shape, range(a)) + (slice(r0, r1),)
                        ].reshape(shape[:a] + ((r1 - r0) * LANES,) + tail),
                        lo - r0 * LANES, hi - r0 * LANES, axis=a,
                    )
                # whole steps of axis a; the axes behind it cut to the slab
                buf = run.reshape(shape[: a + 1] + box[a + 1 :] + tail)[
                    (slice(None),) * (a + 1)
                    + cut(start, shape, range(a + 1, dim))
                ]
            buf = buf.reshape((-1,) + tail)
            pad = d.size - buf.shape[0]
            if pad:
                buf = jnp.pad(
                    buf, ((0, pad),) + ((0, 0),) * (buf.ndim - 1)
                )
            bufs.append(buf)
        return tuple(bufs)

    def _unpack_add(xv, buf, d, v):
        """Variant v's static reverse unpack: accumulate the (sender-
        geometry) slab back into the owned box."""
        bs_v = shapes[v]
        no_v = int(math.prod(bs_v))
        start, shape = d.geo[v]
        n_v = int(math.prod(shape))
        own = xv[o0 : o0 + no_v].reshape(bs_v + _tail(xv))
        sl = tuple(slice(a, a + s) for a, s in zip(start, shape))
        own = own.at[sl].add(buf[:n_v].reshape(tuple(shape) + _tail(xv)))
        return xv.at[o0 : o0 + no_v].set(
            own.reshape((-1,) + _tail(xv))
        )

    if not plan.reverse_mode:

        def body(xv, si, sm, ri):
            # `si` carries the shard's box-shape VARIANT index (a single
            # int32; equal-box plans have V == 1 and never read it)
            del sm, ri
            with jax.named_scope(SCOPE_EX_PACK):
                if V == 1:
                    bufs = _pack_all(xv, 0)
                else:
                    bufs = jax.lax.switch(
                        si[0].astype(jnp.int32),
                        [
                            (lambda x, v=v: _pack_all(x, v))
                            for v in range(V)
                        ],
                        xv,
                    )
            bufs = [
                jax.lax.ppermute(buf, "parts", perm=d.perm)
                for d, buf in zip(info.dirs, bufs)
            ]
            with jax.named_scope(SCOPE_EX_UNPACK):
                for d, buf in zip(info.dirs, bufs):
                    xv = xv.at[g0 + d.off : g0 + d.off + d.size].set(buf)
            return xv

        return body

    def body(xv, si, sm, ri):
        # `sm` is the REAL (nh_total,) segment mask here (staged from
        # info.seg_mask): slab packing leaves orphan slots holding
        # sender values after a forward exchange — they must not
        # accumulate into owners
        del ri
        for d in info.dirs:
            buf = xv[g0 + d.off : g0 + d.off + d.size]
            mask = sm[d.off : d.off + d.size]
            buf = jnp.where(
                mask.reshape(mask.shape + (1,) * (buf.ndim - 1)), buf, 0
            )
            rperm = tuple((q, p) for p, q in d.perm)
            buf = jax.lax.ppermute(buf, "parts", perm=rperm)
            if V == 1:
                xv = _unpack_add(xv, buf, d, 0)
            else:
                xv = jax.lax.switch(
                    si[0].astype(jnp.int32),
                    [
                        (lambda x, b, d=d, v=v: _unpack_add(x, b, d, v))
                        for v in range(V)
                    ],
                    xv,
                    buf,
                )
        # ghost contributions now live on owners; region cleared like the
        # generic 'add' body (and the host assemble)
        xv = xv.at[g0:].set(0)
        return xv

    return body
