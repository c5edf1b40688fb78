"""Compiled geometric multigrid: the WHOLE cycle (V or W) — every level's
overlapped SpMV, halo `ppermute` rounds, Jacobi sweeps, inter-level
transfers, and the dense coarse solve — as one `shard_map` program, and a
V-cycle-preconditioned CG whose entire iteration (outer Krylov loop +
inner multigrid preconditioner) is a single XLA dispatch.

This is the TPU-native payoff of building the hierarchy from static
plans: the host V-cycle in models/gmg.py issues ~#levels × #sweeps eager
ops per cycle, while here XLA sees the full dataflow — every exchange is
a static `ppermute` round schedule, every transfer a static slice copy —
and can fuse/overlap across level boundaries.

Layout invariants this file relies on (see DeviceLayout): all layouts
over the same owned partition share `o0` and `no_max`, so moving a
vector between the A/R/P operand frames of one level is a static
owned-slice copy. The coarse solve is a replicated dense mat-vec against
the host-precomputed inverse (every shard computes the identical coarse
correction — deterministic by construction)."""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ..utils.helpers import check
from .pvector import PVector
from .tpu import (
    DeviceVector,
    _shard_ops,
    TPUBackend,
    _krylov_loop,
    _matrix_operands,
    _pdot_factory,
    _spmv_body,
    _stage,
    device_matrix,
)


def _box_enabled(backend: TPUBackend) -> bool:
    """The ONE resolution of PA_TPU_GMG_BOX (used by both the staging
    site and every cache key — they must never disagree, or a stale
    lowering is served): default ON for host/CPU meshes, OFF on real
    TPUs where the A/B measured the box path slower (Mosaic relayouts on
    minor-axis strides; see _stage_structured_transfer)."""
    import os

    on_tpu = backend.devices()[0].platform == "tpu"
    return os.environ.get("PA_TPU_GMG_BOX", "0" if on_tpu else "1") != "0"


def _stencil_enabled() -> bool:
    """The ONE resolution of PA_TPU_GMG_STENCIL (matrix-free transfers),
    used by both the staging site and the cache key — they must never
    disagree, or a stale lowering is served."""
    import os

    return os.environ.get("PA_TPU_GMG_STENCIL", "1") != "0"


def _gmg_env_key(backend: TPUBackend):
    """Every env mode that changes the staged lowering must key the
    caches: the resolved PA_TPU_GMG_BOX value (it selects the emb_fast
    descriptor), PA_TPU_GMG_STENCIL (it selects the matrix-free
    transfers), plus the shared DeviceMatrix lowering modes — ONE
    helper per mode, so the key sites can never drift apart."""
    from .tpu import _lowering_env_key

    return (_box_enabled(backend), _stencil_enabled()) + _lowering_env_key()


def _device_hierarchy(h, backend: TPUBackend):
    """Stage every level of a models.gmg.GMGHierarchy for the device:
    DeviceMatrix per operator, the inverse diagonal in the level's column
    frame, and the dense coarse inverse + gid maps. Cached on the
    hierarchy per backend and per lowering-affecting env mode."""
    cache = getattr(h, "_device_cache", None)
    if cache is None:
        cache = h._device_cache = {}
    key = (backend._token,) + _gmg_env_key(backend)
    if key in cache:
        return cache[key]

    from ..models.solvers import gather_psparse

    levels = []
    for li, lvl in enumerate(h.levels):
        dA = device_matrix(lvl.A, backend)
        dinv = DeviceVector.from_pvector(lvl.dinv, backend, dA.col_layout).data
        entry = {"dA": dA, "dinv": dinv}
        st = _stage_stencil_transfer(h, li, dA)
        if st is None:
            st = _stage_structured_transfer(h, li, backend)
        _count_transfer("assembled" if st is None else st["form"])
        if st is not None:
            sm_host = st.pop("shmask_host", None)
            if sm_host is not None:
                st["shmask"] = _stage(
                    backend, np.asarray(sm_host, dtype=dinv.dtype),
                    sm_host.shape[0],
                )
            dsel_host = st.pop("dsel_host", None)
            if dsel_host is not None and len(st["stencil"]) > 1:
                st["dsel"] = _stage(
                    backend,
                    np.asarray(dsel_host, dtype=np.int32).reshape(-1, 1),
                    len(dsel_host),
                )
            entry.update(st)
        else:
            # fallback: the assembled rectangular transfers (gather-bound
            # on real TPUs)
            entry["form"] = "assembled"
            entry["dR"] = device_matrix(lvl.R, backend)
            entry["dP"] = device_matrix(lvl.P, backend)
        levels.append(entry)

    Ac = gather_psparse(h.coarse_A).toarray()
    cinv = np.linalg.inv(Ac)
    # per-part global positions of the coarsest owned slots (pad -> nc,
    # the extra zero slot of the padded global vector)
    coarse_isets = h.coarse_A.rows.partition.part_values()
    P_parts = len(coarse_isets)
    ncmax = max((i.num_oids for i in coarse_isets), default=0)
    nc = h.coarse_A.rows.ngids
    gmap = np.full((P_parts, max(ncmax, 1)), nc, dtype=np.int32)
    for p, iset in enumerate(coarse_isets):
        gmap[p, : iset.num_oids] = np.asarray(iset.oid_to_gid, dtype=np.int32)
    dt = levels[0]["dinv"].dtype
    staged = {
        "levels": levels,
        "cinv": np.asarray(cinv, dtype=dt),  # replicated, not sharded
        "gmap": _stage(backend, gmap, P_parts),
        "nc": int(nc),
    }
    cache[key] = staged
    return staged


#: how a level's transfer is staged: the one-pass stencil (full shell),
#: the separable face-only stencil, S through `device_matrix` (the
#: factored operator), or the assembled R and P
TRANSFER_FORMS = ("stencil", "separable", "operator", "assembled")


def _count_transfer(form: str) -> None:
    """The ``gmg.transfer.*`` counters of one level's staged transfer:
    ``levels`` and the one of `TRANSFER_FORMS` it took. Bumped where
    `_device_hierarchy` stages (a cached hierarchy bumps nothing)."""
    from .. import telemetry

    telemetry.bump("gmg.transfer.levels", 1)
    telemetry.bump(f"gmg.transfer.{form}", 1)


def _stage_stencil_transfer(h, li: int, dA):
    """MATRIX-FREE factored transfer P = S·E: when the level's partition
    is the box Cartesian case, the interpolation stencil S (w(δ) =
    0.5^|δ|₀ truncated at the global boundary) is applied from the owned
    box and its neighbours' values instead of through an assembled S
    operator. Kills the O(3^d · N) S staging entirely (43 GB of COO at
    464³, the round-3 OOM) and replaces its gathers with pure slices.
    Two forms, chosen from the level's box plan:

    * ``"stencil"``, where the halo covers the full in-grid shell (every
      Galerkin level; one part): 3^d shifted slice-reads of the part's
      extended box, assembled from the owned box plus the box exchange's
      ghost SEGMENTS, in one pass;
    * ``"separable"``, where the plan carries faces only (the assembled
      7-point level 0 on several parts, whose halo has no edge or corner
      slabs and whose faces a decoupled Dirichlet operator trims at the
      global boundary): S = S_{d-1}···S_0 with S_a the 1-D stencil
      (0.5, 1, 0.5) along axis a, one pass an axis, each behind an
      exchange of that axis's whole faces along the plan's own face
      permutations (`_separable_apply`). Edge and corner values reach
      their receivers through the sequence of passes.

    Round-5 directive 4 closes the two declines round 3 left: UNEQUAL
    Cartesian splits stage one descriptor per box-shape variant (≤ 2^d,
    the exchange's own variant machinery) and the apply switches on the
    shard's variant index; PERIODIC partitions place their wrapped
    segments through a per-(shard, direction) in-grid mask — wrapped
    values are zeroed so the apply reproduces S's boundary truncation
    (the assembled-S oracle truncates; it does not wrap weights).

    Returns the descriptor dict or None (fall back to the matrix S /
    assembled transfers):
    * ``form``: ``"stencil"`` or ``"separable"``,
    * ``stencil``: per-variant (fb, cb, st) embedding boxes,
    * ``shell`` (stencil form): per-variant tuple of (ext_slice,
      seg_off, seg_shape) placements of the ghost segments into the
      (b+2)^d extended array,
    * ``axes`` (separable form): per axis, the ppermute pairs of its
      low face (direction -e_a) and its high face (+e_a), None where no
      part has that neighbour,
    * ``shmask_host``: (P, ndirs) float mask (stencil form; (P, 2·dim),
      low and high face of each axis, in the separable form), present
      only when some shard receives a wrapped (out-of-grid) segment."""
    from .tpu_box import BoxExchangePlan

    if not _stencil_enabled():
        return None
    lvl = h.levels[li]
    if lvl.nfs is None or lvl.ncs is None:
        return None
    dim = len(lvl.nfs)
    if dim > 3:
        return None
    plan = dA.col_plan
    if not isinstance(plan, BoxExchangePlan):
        return None
    info = plan.info
    coarse_rows = (
        h.levels[li + 1].A.rows if li + 1 < len(h.levels) else h.coarse_A.rows
    )
    # the COLS partition carries the ghosts the stencil apply reads (rows
    # are ghost-free); its owned boxes coincide with the rows'
    fsets = lvl.A.cols.partition.part_values()
    csets = coarse_rows.partition.part_values()
    P = len(fsets)
    variants = np.asarray(info.variants)
    dir_index = {d_.dir: k for k, d_ in enumerate(info.dirs)}
    # receiver -> sender per direction (partial permutation: at most one)
    senders = [
        {q: s for s, q in d_.perm} for d_ in info.dirs
    ]
    # descriptor variants are keyed by the FULL embedding (fb, cb, st),
    # not by the exchange's fine-box variant: equal fine boxes over an
    # odd coarse grid still split into floor/ceil coarse boxes, and each
    # distinct embedding needs its own static branch
    descs = []
    dsel = np.zeros(P, dtype=np.int32)
    for p, (fi, ci) in enumerate(zip(fsets, csets)):
        if getattr(fi, "box_shape", None) is None:
            return None
        if getattr(ci, "box_shape", None) is None:
            return None
        fb = info.box_shapes[int(variants[p])]
        if fi.box_shape != fb:
            return None
        cb = ci.box_shape
        if any(s == 0 for s in cb):
            return None  # agglomerated coarse level: matrix path
        st = tuple(
            2 * cl - fl for cl, fl in zip(ci.box_lo, fi.box_lo)
        )
        if any(s < 0 or s > 1 for s in st):
            return None
        if any(st[d] + 2 * (cb[d] - 1) >= fb[d] for d in range(dim)):
            return None
        cand = (fb, tuple(cb), st)
        if cand in descs:
            dsel[p] = descs.index(cand)
        else:
            if len(descs) >= 16:
                return None  # implausible split: keep the matrix path
            dsel[p] = len(descs)
            descs.append(cand)
    out = {"stencil": tuple(descs), "dsel_host": dsel}
    shell = _full_shell(info, fsets, dim, dir_index, senders)
    if shell is not None:
        shmask, any_wrapped = shell
        out.update(form="stencil", shell=_shell_placements(info, descs))
    else:
        faces = _face_axes(info, fsets, dim, dir_index, senders)
        if faces is None:
            return None
        axes, shmask, any_wrapped = faces
        out.update(form="separable", axes=axes)
    if any_wrapped:
        out["shmask_host"] = shmask
    return out


def _in_grid(fi, dvec) -> bool:
    """Whether the cell one step from part ``fi``'s box in direction
    ``dvec`` lies inside the global grid (False where it would wrap)."""
    gdims = fi.grid_shape
    return all(
        (c != -1 or fi.box_lo[j] > 0)
        and (c != 1 or fi.box_hi[j] < gdims[j])
        for j, c in enumerate(dvec)
    )


def _full_shell(info, fsets, dim, dir_index, senders):
    """``(shmask, any_wrapped)`` where every in-grid shell piece (faces,
    edges and corners) of every part arrives through the box plan as a
    segment of its exact extent, else None.

    Direction by direction: every IN-GRID shell piece must arrive as a
    segment of the exact face/edge/corner extent (else the shifted reads
    would see zeros where S needs neighbor values); a WRAPPED segment
    (periodic) is allowed but masked to zero — S truncates at the global
    boundary, it does not wrap. Directions ABSENT from the plan entirely
    (a 7-point level whose halo has no corner slabs) fail here, and so
    does a face the operator trims at the global boundary."""
    variants = np.asarray(info.variants)
    shmask = np.ones((len(fsets), len(info.dirs)), dtype=np.float64)
    any_wrapped = False
    all_dirs = [
        d_ for d_ in np.ndindex(*(3,) * dim)
        if any(c != 1 for c in d_)
    ]
    for p, fi in enumerate(fsets):
        fb = info.box_shapes[int(variants[p])]
        for delta in all_dirs:
            dvec = tuple(c - 1 for c in delta)
            in_grid = _in_grid(fi, dvec)
            k = dir_index.get(dvec)
            s = senders[k].get(p) if k is not None else None
            if s is None:
                if in_grid:
                    return None  # shell piece exists but never arrives
                continue  # no segment: ppermute zero-fills — matches S
            d_ = info.dirs[k]
            exp_shape = tuple(
                1 if c != 0 else fb[j] for j, c in enumerate(dvec)
            )
            if d_.geo[int(variants[s])][1] != exp_shape:
                return None  # sender slab is not the exact face extent
            n_seg = int(np.prod(exp_shape))
            if not info.seg_mask[p, d_.off : d_.off + n_seg].all():
                return None  # orphan slots inside the face: stale values
            if not in_grid:
                shmask[p, k] = 0.0
                any_wrapped = True
    return shmask, any_wrapped


def _shell_placements(info, descs):
    """Per-descriptor segment placements into the (b+2)^d extended
    array: each direction δ maps to the shell slice [0,1) / [1,1+b) /
    [1+b,2+b) per dim."""
    shells = []
    for fb, _cb, _st in descs:
        shell_put = []
        for d_ in info.dirs:
            exp_shape = tuple(
                1 if c != 0 else fb[k] for k, c in enumerate(d_.dir)
            )
            sl = tuple(
                slice(0, 1) if c == -1
                else (slice(1 + fb[k], 2 + fb[k]) if c == 1
                      else slice(1, 1 + fb[k]))
                for k, c in enumerate(d_.dir)
            )
            shell_put.append((sl, d_.off, exp_shape))
        shells.append(tuple(shell_put))
    return tuple(shells)


def _face_axes(info, fsets, dim, dir_index, senders):
    """``(axes, shmask, any_wrapped)`` of the separable form where the
    box plan names, for every part, the neighbour across each of its
    in-grid faces, else None. ``axes[a]`` holds the ppermute pairs of
    direction -e_a (the sender's high face becomes the receiver's low
    neighbour plane) and of +e_a; ``shmask`` is (P, 2·dim), 0 where the
    plan delivers a WRAPPED face (periodic), which S must not read.

    Only the plan's face permutations are used, never its slabs: the
    separable apply ships each face whole, so a face the operator trims
    at the global boundary (decoupled Dirichlet rows request no ghost
    there) is no obstacle. Each sender is checked to be the geometric
    neighbour, equal to the receiver on every other axis."""
    shmask = np.ones((len(fsets), 2 * dim), dtype=np.float64)
    any_wrapped = False
    axes = []
    for a in range(dim):
        pair = []
        for side, c in enumerate((-1, 1)):
            dvec = tuple(c if j == a else 0 for j in range(dim))
            k = dir_index.get(dvec)
            for p, fi in enumerate(fsets):
                s = senders[k].get(p) if k is not None else None
                in_grid = _in_grid(fi, dvec)
                if s is None:
                    if in_grid:
                        return None  # the face exists but never arrives
                    continue  # no neighbour: zeros, S's truncation
                if not in_grid:
                    shmask[p, 2 * a + side] = 0.0
                    any_wrapped = True
                    continue
                fs = fsets[s]
                touching = (
                    fs.box_hi[a] == fi.box_lo[a] if c == -1
                    else fs.box_lo[a] == fi.box_hi[a]
                )
                if not touching or any(
                    (fs.box_lo[j], fs.box_hi[j]) != (fi.box_lo[j], fi.box_hi[j])
                    for j in range(dim) if j != a
                ):
                    return None  # sender is not the face neighbour
            pair.append(None if k is None else tuple(info.dirs[k].perm))
        axes.append(tuple(pair))
    return tuple(axes), shmask, any_wrapped


def _stencil_apply(jnp, layout, shell_put, xv, fb, dirmask=None):
    """S·x over one part: embed the owned box and the ghost segments into
    the zero-padded (b+2)^d extended array, then sum the 3^d shifted
    slices with weights 0.5^|δ|₀. Reads beyond the global boundary see
    the zero pad — exactly S's dropped-weight truncation. ``dirmask``
    (ndirs,) zeroes WRAPPED segments on periodic partitions: the values
    arrive (the exchange wraps) but S's truncation must not read them."""
    dim = len(fb)
    o0, g0 = layout.o0, layout.g0
    no = 1
    for b in fb:
        no *= b
    ext = jnp.zeros(tuple(b + 2 for b in fb), dtype=xv.dtype)
    core = tuple(slice(1, 1 + b) for b in fb)
    ext = ext.at[core].set(xv[o0 : o0 + no].reshape(fb))
    for k, (sl, off, shape) in enumerate(shell_put):
        seg = xv[g0 + off : g0 + off + int(np.prod(shape))]
        if dirmask is not None:
            seg = seg * dirmask[k]
        ext = ext.at[sl].set(seg.reshape(shape))
    acc = None
    for delta in np.ndindex(*(3,) * dim):
        d = tuple(c - 1 for c in delta)
        w = 0.5 ** sum(1 for c in d if c != 0)
        sl = tuple(slice(1 + c, 1 + c + b) for c, b in zip(d, fb))
        term = ext[sl] if w == 1.0 else w * ext[sl]
        acc = term if acc is None else acc + term
    return acc.reshape(-1)


def _variant(m, descs):
    """The shard's descriptor index, where the level has several."""
    return m["dsel"][0].astype(np.int32) if len(descs) > 1 else None


def _separable_apply(jax, jnp, u, fbs, axes, sel=None, facemask=None):
    """S·u over one part's owned box as one 1-D pass of (0.5, 1, 0.5) an
    axis, S = S_{d-1}···S_0: the weight 0.5^|δ|₀ is the product of the
    1-D weights of its axes, and S's truncation at the global boundary is
    each axis's own. Before the pass along axis a, every part sends its two faces normal
    to a (the current iterate's first and last planes) along the box
    plan's face permutations (``axes[a]``, `_face_axes`), under
    `SCOPE_HALO`; the pass then reads its own box between the planes it
    received. A part with no neighbour on a side reads zeros there (the
    ppermute zero-fills), which is S's truncation, and ``facemask`` (2·d,)
    zeroes a WRAPPED plane on periodic partitions. Edge and corner terms
    arrive through the sequence of passes: after the pass along a, a
    face plane normal to b already holds its a-neighbours' values.

    ``u`` is the owned slice of the level's frame, (no,) with ``no`` the
    largest box; ``fbs`` the box shape of each descriptor and ``sel`` the
    shard's descriptor (`lax.switch` over unequal boxes; the permutes
    stay outside it, one program for every shard). Returns S·u, (no,)."""
    from .tpu import SCOPE_HALO

    no = u.shape[0]
    dim = len(fbs[0])

    def per_box(fn, *args):
        if len(fbs) == 1:
            return fn(0, *args)
        return jax.lax.switch(
            sel, [(lambda *a_, v=v: fn(v, *a_)) for v in range(len(fbs))],
            *args,
        )

    def box(v, u_):
        fb = fbs[v]
        return u_[: int(np.prod(fb))].reshape(fb)

    for a in range(dim):
        nface = max(int(np.prod(fb)) // fb[a] for fb in fbs)

        def faces(v, u_, a=a, nface=nface):
            X = box(v, u_)
            out = []
            for i in (X.shape[a] - 1, 0):  # high plane goes up, low down
                f = jax.lax.slice_in_dim(X, i, i + 1, axis=a).reshape(-1)
                out.append(jnp.pad(f, (0, nface - f.shape[0])))
            return tuple(out)

        with jax.named_scope(SCOPE_HALO):
            high, low = per_box(faces, u)
            got = []
            for side, (perm, f) in enumerate(zip(axes[a], (high, low))):
                g = (
                    jnp.zeros_like(f) if perm is None
                    else jax.lax.ppermute(f, "parts", perm=perm)
                )
                if facemask is not None:
                    g = g * facemask[2 * a + side]
                got.append(g)

        def one_pass(v, u_, lo_, hi_, a=a):
            X = box(v, u_)
            fshape = X.shape[:a] + (1,) + X.shape[a + 1 :]
            n = int(np.prod(fshape))
            ext = jnp.concatenate(
                [lo_[:n].reshape(fshape), X, hi_[:n].reshape(fshape)],
                axis=a,
            )
            m = X.shape[a]
            mid = jax.lax.slice_in_dim(ext, 1, m + 1, axis=a)
            below = jax.lax.slice_in_dim(ext, 0, m, axis=a)
            above = jax.lax.slice_in_dim(ext, 2, m + 2, axis=a)
            y = (mid + 0.5 * (below + above)).reshape(-1)
            return jnp.pad(y, (0, no - y.shape[0]))

        u = per_box(one_pass, u, got[0], got[1])
    return u


def _stage_structured_transfer(h, li: int, backend: TPUBackend):
    """Stage the factored transfer P = S·E for level `li`: the square
    constant-coefficient interpolation stencil S (coded-DIA fast path)
    plus the even-point embedding index maps and the ghost→owner
    assembly plan. Returns None — falling back to the assembled
    P/R matrices — when the level has no grid dims or an embedded coarse
    point falls outside a part's fine halo (pathological partitions).

    Why: the assembled rectangular transfers lower to per-row column
    gathers, which run element-at-a-time on TPU and dominated the
    measured V-cycle cost 100:1 (round 1); the factored form
    replaces 8N gathered elements with one stencil SpMV plus N/8
    scatter/gather elements."""
    from ..models.gmg import interp_stencil_cartesian
    from .tpu import DeviceExchangePlan

    lvl = h.levels[li]
    if lvl.nfs is None or lvl.ncs is None:
        return None
    coarse_rows = (
        h.levels[li + 1].A.rows if li + 1 < len(h.levels) else h.coarse_A.rows
    )
    # S inherits the level dtype: an f32 hierarchy stages f32 transfer
    # operators end-to-end (the stencil weights — powers of 1/2 — are
    # exact in both widths), with no f64 detour
    S = interp_stencil_cartesian(lvl.nfs, lvl.A.rows, dtype=lvl.A.dtype)
    dS = device_matrix(S, backend)
    LS = dS.col_plan.layout
    nc_max = max(
        (i.num_oids for i in coarse_rows.partition.part_values()), default=0
    )
    emb = np.full((LS.P, max(nc_max, 1)), LS.trash, dtype=np.int32)
    for p, (ci, fi) in enumerate(
        zip(
            coarse_rows.partition.part_values(),
            S.cols.partition.part_values(),
        )
    ):
        kg = np.asarray(ci.oid_to_gid, dtype=np.int64)
        if len(kg) == 0:
            continue
        kc = np.unravel_index(kg, lvl.ncs)
        fg = np.ravel_multi_index(tuple(2 * c for c in kc), lvl.nfs)
        lids = fi.gids_to_lids(fg)
        if (lids < 0).any():
            return None  # embedded point beyond this part's fine halo
        emb[p, : len(kg)] = LS.lid_slots[p][lids]
    from .tpu import _box_dummy_operands
    from .tpu_box import BoxExchangePlan

    cp = dS.col_plan
    if isinstance(cp, BoxExchangePlan):
        # slice-based ghost->owner assembly: reverse of the same box
        # plan; rsm carries the segment mask (orphan slab slots must not
        # accumulate into owners), rsi/rri are ignored dummies
        rev = cp.reverse()
        rsi, rsm, rri = _box_dummy_operands(
            backend, LS.P, cp.info.seg_mask, variants=cp.info.variants
        )
    else:
        rev = DeviceExchangePlan(S.cols.exchanger.reverse(), LS)
        rsi = _stage(backend, rev.snd_idx, LS.P)
        rsm = _stage(backend, rev.snd_mask, LS.P)
        rri = _stage(backend, rev.rcv_idx, LS.P)
    out = {
        "form": "operator",
        "dS": dS,
        "rev_plan": rev,
        "emb_host": emb,
        "emb": _stage(backend, emb, LS.P),
        "rsi": rsi,
        "rsm": rsm,
        "rri": rri,
    }
    # The strided-box embedding measured SLOWER on the real chip than the
    # element gathers it replaces (A/B at 192³ f32: 11.31 vs 7.91 ms per
    # GMG-PCG iteration): the stride-2 extraction on the minor (lane)
    # axis forces Mosaic relayouts that cost more than the N/8 gathers.
    # _box_enabled defaults it ON for host/CPU meshes, OFF on real TPUs;
    # PA_TPU_GMG_BOX overrides either way.
    if _box_enabled(backend):
        fast = _embedding_box_fast_path(lvl, coarse_rows, S, LS, emb)
        if fast is not None:
            out["emb_fast"] = fast
    return out


def _embedding_box_fast_path(lvl, coarse_rows, S, LS, emb):
    """When every part's owned fine/coarse regions are EQUAL axis-aligned
    boxes whose coarse points are exactly the part's own even fine points
    (the common evenly-split Cartesian case), the embedding extraction /
    scatter is a strided reshape-slice — no per-element gathers (measured
    dominant in the 192³ V-cycle: ~1.8M gathered+scattered elements per
    level-0 transfer pair) and no cross-part ghost traffic. Returns
    ``(fine_box, coarse_box, starts)`` — one static descriptor valid for
    ALL shards (SPMD uniformity) — or None."""
    dim = len(lvl.nfs)
    descr = None
    for p, (ci, fi) in enumerate(
        zip(
            coarse_rows.partition.part_values(),
            S.cols.partition.part_values(),
        )
    ):
        if fi.num_oids == 0 or ci.num_oids == 0:
            return None
        fg = np.asarray(fi.oid_to_gid, dtype=np.int64)
        cg = np.asarray(ci.oid_to_gid, dtype=np.int64)
        fc = np.stack(np.unravel_index(fg, lvl.nfs))  # (dim, no_f)
        cc = np.stack(np.unravel_index(cg, lvl.ncs))
        lo_f, hi_f = fc.min(axis=1), fc.max(axis=1) + 1
        lo_c, hi_c = cc.min(axis=1), cc.max(axis=1) + 1
        fb = tuple(int(x) for x in hi_f - lo_f)
        cb = tuple(int(x) for x in hi_c - lo_c)
        if int(np.prod(fb)) != fi.num_oids or int(np.prod(cb)) != ci.num_oids:
            return None  # owned set is not a box
        st = tuple(int(2 * lo_c[d] - lo_f[d]) for d in range(dim))
        if any(s < 0 or s > 1 for s in st):
            return None  # a coarse point falls outside this part's box
        if any(st[d] + 2 * (cb[d] - 1) >= fb[d] for d in range(dim)):
            return None
        cand = (fb, cb, st)
        if descr is None:
            descr = cand
        elif cand != descr:
            return None  # shards differ: one compiled program can't serve
        # the reshape path reads slots o0+lid directly — owned slots must
        # be the contiguous identity map (owned-first layouts are, but
        # verify rather than assume)
        if not np.array_equal(
            LS.lid_slots[p][: fi.num_oids],
            LS.o0 + np.arange(fi.num_oids, dtype=LS.lid_slots[p].dtype),
        ):
            return None
        # verify ORDER: emb row p must equal the slots of the box's even
        # points in row-major (coarse-scan) order, with no ghost reads
        fine_idx = np.arange(fi.num_oids, dtype=np.int64).reshape(fb)
        sl = tuple(slice(st[d], st[d] + 2 * cb[d], 2) for d in range(dim))
        lids = fine_idx[sl].reshape(-1)
        expect = LS.lid_slots[p][lids]
        if not np.array_equal(emb[p, : len(expect)], expect):
            return None
        if (emb[p, len(expect):] != LS.trash).any():
            return None
    return descr


def _box_extract(jnp, flat, fb, cb, st):
    """Even-point extraction from a row-major box, lane-stride-free: each
    axis is rotated to the MAJOR position (XLA transpose — a tiled,
    bandwidth-speed copy on TPU) before its stride-2 slice. Measured at
    192³ f32: 155 µs vs 6.4 ms for the equivalent gather and 11.2 ms for
    a direct strided slice (minor-axis strides force Mosaic relayouts)."""
    dim = len(fb)
    t = flat.reshape(fb)
    if dim == 1:
        return t[st[0] : st[0] + 2 * cb[0] : 2]
    # rotate the LAST axis to front, stride it, repeat for every axis;
    # after dim rounds the axis order is fully restored
    for d in range(dim - 1, -1, -1):
        t = jnp.moveaxis(t, -1, 0)
        t = t[st[d] :: 2][: cb[d]]
    return t.reshape(-1)


def _box_interleave(jnp, flat, fb, cb, st):
    """Mirror of `_box_extract`: place coarse values at the even points
    of the fine box (zeros elsewhere) via major-axis zero interleaves —
    stack+reshape on the leading axis, parity shift, crop — rotating
    each axis to front exactly like the extraction does in reverse."""
    dim = len(cb)
    t = flat.reshape(cb)
    for d in range(dim):
        t = jnp.stack([t, jnp.zeros_like(t)], axis=1).reshape(
            (2 * t.shape[0],) + t.shape[1:]
        )
        if st[d]:
            t = jnp.pad(t, [(st[d], 0)] + [(0, 0)] * (t.ndim - 1))
        if t.shape[0] < fb[d]:
            t = jnp.pad(
                t, [(0, fb[d] - t.shape[0])] + [(0, 0)] * (t.ndim - 1)
            )
        t = jnp.moveaxis(t[: fb[d]], 0, -1)
    return t.reshape(-1)


def _gmg_operands(dh):
    """The sharded operand pytree for the compiled programs (the coarse
    inverse rides separately — it is replicated, not sharded)."""
    lv = []
    for l in dh["levels"]:
        entry = {"A": _matrix_operands(l["dA"]), "dinv": l["dinv"]}
        if "stencil" in l:
            # matrix-free transfers: everything is compiled in except
            # the periodic wrapped-segment mask and the multi-variant
            # descriptor selector (per-shard data)
            if "shmask" in l:
                entry["shmask"] = l["shmask"]
            if "dsel" in l:
                entry["dsel"] = l["dsel"]
        elif "dS" in l:
            entry.update(
                S=_matrix_operands(l["dS"]),
                emb=l["emb"], rsi=l["rsi"], rsm=l["rsm"], rri=l["rri"],
            )
        else:
            entry.update(
                R=_matrix_operands(l["dR"]), P=_matrix_operands(l["dP"])
            )
        lv.append(entry)
    return {"lv": lv, "gmap": dh["gmap"]}


def _vcycle_shard_body(h, dh):
    """Returns vcycle(b_vec, mats, cinv) -> correction, both in level-0's
    A column frame, usable inside any shard_map program. `mats` is the
    per-shard (leading part axis stripped) form of `_gmg_operands`."""
    import jax
    import jax.numpy as jnp

    from .tpu import _shard_exchange

    bodies = []
    for l in dh["levels"]:
        b = {"A": _spmv_body(l["dA"])}
        if l["form"] == "stencil":
            # the one-pass stencil refreshes ghosts through the level's
            # own box exchange before each apply (the separable form
            # makes its face permutes in `_separable_apply`)
            b["exch_A"] = _shard_exchange(l["dA"].col_plan, "set")
        elif l["form"] == "operator":
            b["S"] = _spmv_body(l["dS"])
            b["exch_add"] = _shard_exchange(l["rev_plan"], "add")
            b["exch_set"] = _shard_exchange(l["dS"].col_plan, "set")
        elif l["form"] == "assembled":
            b["R"] = _spmv_body(l["dR"])
            b["P"] = _spmv_body(l["dP"])
        bodies.append(b)
    pre, post, omega = h.pre, h.post, h.omega
    w_cycle = h.cycle == "w"
    nc = dh["nc"]
    L = len(dh["levels"])

    def vcycle(b_vec, mats, cinv):
        def solve_level(level, b_l, x0_l=None):
            # one scope per level, nested for the coarser ones: an op's
            # level is its innermost `pa.gmg.l<k>`; inside, the phases
            # `pa.gmg.smooth|restrict|coarse|prolong`
            with jax.named_scope(f"pa.gmg.l{level}"):
                return level_body(level, b_l, x0_l)

        def level_body(level, b_l, x0_l):
            lv = dh["levels"][level]
            m = mats["lv"][level]
            # every operand frame has its OWN geometry: on real TPU the
            # (coded, square) level operator takes the padded layout
            # while the rectangular transfers take the compact one, so
            # o0 differs between frames — every cross-frame move below
            # names its source and destination slices explicitly
            LA = lv["dA"].col_plan.layout  # level vectors live here
            LAr = lv["dA"].row_layout  # A product frame
            structured = "dS" in lv
            no = LA.no_max
            sl = slice(LA.o0, LA.o0 + no)
            dinv = m["dinv"]

            def spmv_A(z):
                # product re-embedded into the level's column frame
                y, _ = bodies[level]["A"](z, m["A"])
                return jnp.zeros_like(z).at[sl].set(
                    y[LAr.o0 : LAr.o0 + no]
                )

            # pre-smooth. From x = 0 (the V entry) the first sweep
            # collapses to x = omega * dinv * b (A @ 0 == 0 exactly —
            # same values the host loop computes, minus the wasted
            # SpMV); a warm start (the second W-cycle pass) runs full
            # sweeps.
            with jax.named_scope("pa.gmg.smooth"):
                if x0_l is None:
                    if pre == 0:
                        x = jnp.zeros_like(b_l)
                    else:
                        x = jnp.zeros_like(b_l).at[sl].set(
                            omega * dinv[sl] * b_l[sl]
                        )
                    sweeps_left = max(pre - 1, 0)
                else:
                    x = x0_l
                    sweeps_left = pre
                for _ in range(sweeps_left):
                    q = spmv_A(x)
                    x = x.at[sl].add(omega * dinv[sl] * (b_l[sl] - q[sl]))
            with jax.named_scope("pa.gmg.restrict"):
                q = spmv_A(x)
                if "stencil" in lv:
                    # MATRIX-FREE factored restriction R = Eᵀ·S: apply S
                    # to the residual (the stencil form refreshes its ghosts
                    # through the level's box exchange and reads 3^d shifted
                    # slices of the extended box; the separable form makes
                    # one pass an axis), extract the even points — no
                    # operators staged at all. Multi-variant plans (unequal
                    # boxes) switch on the shard's descriptor (m["dsel"]);
                    # every branch pads to the coarse frame width
                    descs = lv["stencil"]
                    shmask = m.get("shmask")
                    if level + 1 == L:
                        nc_pad = mats["gmap"].shape[-1]
                    else:
                        nc_pad = dh["levels"][level + 1][
                            "dA"
                        ].col_plan.layout.no_max
                    if lv["form"] == "separable":
                        # faces only: S as one pass an axis, each behind
                        # its own face exchange, then the even points
                        rv = _separable_apply(
                            jax, jnp, b_l[sl] - q[sl],
                            [d_[0] for d_ in descs], lv["axes"],
                            _variant(m, descs), shmask,
                        )
                    else:
                        shells = lv["shell"]
                        rv = jnp.zeros_like(b_l).at[sl].set(
                            b_l[sl] - q[sl]
                        )
                        rv = bodies[level]["exch_A"](
                            rv, m["A"]["si"], m["A"]["sm"], m["A"]["ri"]
                        )

                    def _restrict(v, x_, nc_pad=nc_pad):
                        fbx, cbx, stx = descs[v]
                        if lv["form"] == "separable":
                            w = x_[: int(np.prod(fbx))]
                        else:
                            w = _stencil_apply(
                                jnp, LA, shells[v], x_, fbx, shmask
                            )
                        rc = _box_extract(jnp, w, fbx, cbx, stx)
                        pad = nc_pad - rc.shape[0]
                        return jnp.pad(rc, (0, pad)) if pad else rc

                    if len(descs) == 1:
                        rc_own = _restrict(0, rv)
                    else:
                        rc_own = jax.lax.switch(
                            m["dsel"][0].astype(jnp.int32),
                            [
                                (lambda x_, v=v: _restrict(v, x_))
                                for v in range(len(descs))
                            ],
                            rv,
                        )
                elif structured:
                    # factored restriction R = Eᵀ·S: stencil-apply the fine
                    # residual (coded-DIA speed), refresh ghosts so embedded
                    # points owned elsewhere are readable, extract the
                    # even-point slots — no per-row gathers
                    LS = lv["dS"].col_plan.layout
                    LSr = lv["dS"].row_layout
                    rS = jnp.zeros(LS.W, dtype=b_l.dtype).at[
                        LS.o0 : LS.o0 + no
                    ].set(b_l[sl] - q[sl])
                    w, _ = bodies[level]["S"](rS, m["S"])
                    fast = lv.get("emb_fast")
                    if fast is not None:
                        # equal-box shards: the even-point extraction runs as
                        # transpose/major-stride rounds — each axis is rotated
                        # to the MAJOR position before its stride-2 slice, so
                        # no lane-axis stride ever happens (measured 155 µs vs
                        # 6.4 ms for the gather and 11.2 ms for a direct
                        # strided slice at 192³ — Mosaic relayouts dwarf the
                        # transpose copies). No ghost refresh needed: staging
                        # verified every embedded point is an own even point.
                        fb, cb, st = fast
                        rc_own = _box_extract(
                            jnp, w[LSr.o0 : LSr.o0 + no], fb, cb, st
                        )
                    else:
                        v = jnp.zeros(LS.W, dtype=b_l.dtype).at[
                            LS.o0 : LS.o0 + no
                        ].set(w[LSr.o0 : LSr.o0 + no])
                        v = bodies[level]["exch_set"](
                            v, m["S"]["si"], m["S"]["sm"], m["S"]["ri"]
                        )
                        rc_own = v[m["emb"]]  # pads read the (zero) trash slot
                else:
                    # assembled restriction matrix (fallback path)
                    LR = lv["dR"].col_plan.layout
                    LRr = lv["dR"].row_layout
                    r = jnp.zeros(LR.W, dtype=b_l.dtype).at[
                        LR.o0 : LR.o0 + no
                    ].set(b_l[sl] - q[sl])
                    rc, _ = bodies[level]["R"](r, m["R"])
                    rc_own = rc[LRr.o0 : LRr.o0 + LRr.no_max]
            if level + 1 == L:
                with jax.named_scope("pa.gmg.coarse"):
                    # dense coarse solve, replicated: gather every shard's
                    # owned coarse residual AND gid map (the gmap operand is
                    # sharded — each shard holds only its own row), place by
                    # gid, one mat-vec with the host-precomputed inverse,
                    # read back my slots. Identical on every shard.
                    rc_all = jax.lax.all_gather(rc_own, "parts")  # (P, no_c)
                    gm_all = jax.lax.all_gather(mats["gmap"], "parts")
                    glob = jnp.zeros(nc + 1, dtype=b_l.dtype).at[
                        gm_all.reshape(-1)
                    ].set(rc_all.reshape(-1))
                    ec_glob = jnp.concatenate(
                        [cinv @ glob[:nc], jnp.zeros(1, dtype=b_l.dtype)]
                    )
                    ec_own = ec_glob[mats["gmap"]]
            else:
                nxt = dh["levels"][level + 1]["dA"].col_plan.layout
                bc = jnp.zeros(nxt.W, dtype=b_l.dtype).at[
                    nxt.o0 : nxt.o0 + nxt.no_max
                ].set(rc_own)
                ec = solve_level(level + 1, bc)
                if w_cycle:
                    # second coarse pass, warm-started (W-cycle γ = 2)
                    ec = solve_level(level + 1, bc, ec)
                ec_own = ec[nxt.o0 : nxt.o0 + nxt.no_max]
            with jax.named_scope("pa.gmg.prolong"):
                if "stencil" in lv:
                    # matrix-free prolongation P = S·E: interleave the
                    # coarse correction onto the even fine points, then S
                    # (stencil form: refresh ghosts, the neighbor parts'
                    # interleaved values, and read the shell; separable
                    # form: one pass an axis behind its face exchange)
                    descs = lv["stencil"]
                    shmask = m.get("shmask")

                    def _interleave(v, e_):
                        fbx, cbx, stx = descs[v]
                        t_ = _box_interleave(
                            jnp, e_[: int(np.prod(cbx))], fbx, cbx, stx
                        )
                        pad = no - t_.shape[0]
                        return jnp.pad(t_, (0, pad)) if pad else t_

                    if len(descs) == 1:
                        t = _interleave(0, ec_own)
                    else:
                        t = jax.lax.switch(
                            m["dsel"][0].astype(jnp.int32),
                            [
                                (lambda e_, v=v: _interleave(v, e_))
                                for v in range(len(descs))
                            ],
                            ec_own,
                        )
                    if lv["form"] == "separable":
                        ef_own = _separable_apply(
                            jax, jnp, t, [d_[0] for d_ in descs],
                            lv["axes"], _variant(m, descs), shmask,
                        )
                    else:
                        shells = lv["shell"]

                        def _apply_S(v, z_):
                            ef_ = _stencil_apply(
                                jnp, LA, shells[v], z_, descs[v][0], shmask
                            )
                            pad = no - ef_.shape[0]
                            return jnp.pad(ef_, (0, pad)) if pad else ef_

                        z = jnp.zeros_like(b_l).at[sl].set(t)
                        z = bodies[level]["exch_A"](
                            z, m["A"]["si"], m["A"]["sm"], m["A"]["ri"]
                        )
                        if len(descs) == 1:
                            ef_own = _apply_S(0, z)
                        else:
                            ef_own = jax.lax.switch(
                                m["dsel"][0].astype(jnp.int32),
                                [
                                    (lambda z_, v=v: _apply_S(v, z_))
                                    for v in range(len(descs))
                                ],
                                z,
                            )
                    x = x.at[sl].add(ef_own)
                elif structured:
                    # factored prolongation P = S·E: scatter the coarse
                    # correction onto the even fine points (N/8 elements),
                    # assemble embedded-into-ghost values to their owners,
                    # then one stencil SpMV
                    LS = lv["dS"].col_plan.layout
                    LSr = lv["dS"].row_layout
                    fast = lv.get("emb_fast")
                    if fast is not None:
                        # scatter-free interleave, mirror of _box_extract:
                        # each axis rotates to MAJOR position for its zero
                        # interleave (stack+reshape), parity shift, crop
                        fb, cb, st = fast
                        t = _box_interleave(jnp, ec_own, fb, cb, st)
                        z = jnp.zeros(LS.W, dtype=b_l.dtype).at[
                            LS.o0 : LS.o0 + no
                        ].set(t)
                    else:
                        z = jnp.zeros(LS.W, dtype=b_l.dtype).at[m["emb"]].set(
                            ec_own
                        ).at[LS.trash].set(0.0)
                        z = bodies[level]["exch_add"](
                            z, m["rsi"], m["rsm"], m["rri"]
                        )
                    ef, _ = bodies[level]["S"](z, m["S"])
                    x = x.at[sl].add(ef[LSr.o0 : LSr.o0 + no])
                else:
                    LP = lv["dP"].col_plan.layout
                    LPr = lv["dP"].row_layout
                    ecp = jnp.zeros(LP.W, dtype=b_l.dtype).at[
                        LP.o0 : LP.o0 + LP.no_max
                    ].set(ec_own)
                    ef, _ = bodies[level]["P"](ecp, m["P"])
                    x = x.at[sl].add(ef[LPr.o0 : LPr.o0 + no])
            with jax.named_scope("pa.gmg.smooth"):
                for _ in range(post):
                    q = spmv_A(x)
                    x = x.at[sl].add(omega * dinv[sl] * (b_l[sl] - q[sl]))
            return x

        return solve_level(0, b_vec)

    return vcycle


def make_gmg_solve_fn(h, backend: TPUBackend, tol: float, maxiter: int):
    """The stationary V-cycle iteration x <- x + Vcycle(b - A x) as ONE
    compiled program (the device form of models.gmg.gmg_solve)."""
    import jax
    import jax.numpy as jnp
    shard_map = jax.shard_map

    dh = _device_hierarchy(h, backend)
    dA0 = dh["levels"][0]["dA"]
    mesh = backend.mesh(dA0.row_layout.P)
    spec = backend.parts_spec()
    none_spec = jax.sharding.PartitionSpec()
    L0 = dA0.col_plan.layout
    pdot = _pdot_factory(L0.o0, L0.no_max)
    body_A0 = _spmv_body(dA0)
    vcycle = _vcycle_shard_body(h, dh)
    ops = _gmg_operands(dh)
    specs = jax.tree.map(lambda _: spec, ops)
    H = int(min(maxiter + 1, 4096))

    @jax.jit
    def fn(b, x0, cinv, m):
        def shard_fn(bs, x0s, cinv_r, ms):
            bv, xv = bs[0], x0s[0]
            mats = _shard_ops(jax, ms)
            no = L0.no_max
            sl = slice(L0.o0, L0.o0 + no)
            Lr = dA0.row_layout  # the A product frame (o0 may differ)

            def residual(x):
                y, _ = body_A0(x, mats["lv"][0]["A"])
                return jnp.zeros_like(x).at[sl].set(
                    bv[sl] - y[Lr.o0 : Lr.o0 + no]
                )

            r0 = residual(xv)
            rs0 = pdot(r0, r0)
            hist = jnp.full(H, jnp.nan, dtype=bv.dtype).at[0].set(
                jnp.sqrt(rs0)
            )

            def cond(st):
                _x, _r, rs, it, _h = st
                return (
                    jnp.sqrt(rs) > tol * jnp.maximum(1.0, jnp.sqrt(rs0))
                ) & (it < maxiter)

            def step(st):
                # the residual rides the carry — computed once per
                # iteration (like the host loop), not re-derived on entry
                x, r, _rs, it, hist = st
                e = vcycle(r, mats, cinv_r)
                x = x.at[sl].add(e[sl])
                r = residual(x)
                rs = pdot(r, r)
                it = it + 1
                hist = hist.at[jnp.minimum(it, H - 1)].set(jnp.sqrt(rs))
                return (x, r, rs, it, hist)

            x, r, rs, it, hist = _krylov_loop(
                cond, step, (xv, r0, rs0, jnp.int32(0), hist)
            )
            return x[None], rs, rs0, it, hist

        return shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec, spec, none_spec, specs),
            out_specs=(spec, none_spec, none_spec, none_spec, none_spec),
            check_vma=False,
        )(b, x0, cinv, m)

    def run(b, x0):
        return fn(b, x0, dh["cinv"], ops)

    return run


def make_gmg_pcg_fn(h, backend: TPUBackend, tol: float, maxiter: int):
    """V-cycle-preconditioned CG as ONE compiled program: the classic
    outer PCG recurrence with z = Vcycle(r) inlined — Krylov loop,
    multigrid preconditioner, halo exchanges and coarse solve all inside
    a single `lax.while_loop`."""
    import jax
    import jax.numpy as jnp
    shard_map = jax.shard_map

    dh = _device_hierarchy(h, backend)
    dA0 = dh["levels"][0]["dA"]
    mesh = backend.mesh(dA0.row_layout.P)
    spec = backend.parts_spec()
    none_spec = jax.sharding.PartitionSpec()
    L0 = dA0.col_plan.layout
    pdot = _pdot_factory(L0.o0, L0.no_max)
    body_A0 = _spmv_body(dA0)
    vcycle = _vcycle_shard_body(h, dh)
    ops = _gmg_operands(dh)
    specs = jax.tree.map(lambda _: spec, ops)
    H = int(min(maxiter + 1, 4096))

    @jax.jit
    def fn(b, x0, cinv, m):
        def shard_fn(bs, x0s, cinv_r, ms):
            bv, xv = bs[0], x0s[0]
            mats = _shard_ops(jax, ms)
            no = L0.no_max
            sl = slice(L0.o0, L0.o0 + no)
            Lr = dA0.row_layout  # the A product frame (o0 may differ)

            def spmv(z):
                # product re-embedded into the column frame every vector
                # of the loop lives in
                y, _ = body_A0(z, mats["lv"][0]["A"])
                return jnp.zeros_like(z).at[sl].set(
                    y[Lr.o0 : Lr.o0 + no]
                )

            def apply_minv(r):
                return vcycle(r, mats, cinv_r)

            q = spmv(xv)
            r = jnp.zeros_like(xv).at[sl].set(bv[sl] - q[sl])
            p = jnp.zeros_like(xv)
            rs0 = pdot(r, r)
            hist = jnp.full(H, jnp.nan, dtype=bv.dtype).at[0].set(
                jnp.sqrt(rs0)
            )

            # z = Minv(r) computed at the TOP of the body (beta = 0 on
            # the first pass), not once outside the loop and once inside:
            # the iterates are the textbook PCG sequence either way, but
            # this form instantiates the ENTIRE V-cycle ONCE in the
            # program. TPU codegen emits size-dependent code for the
            # transfer slices, so the doubled V-cycle literally doubled
            # the executable (111 MB at 464³, loaded on every warm start
            # — round-5 directive 1).
            def cond(st):
                _x, _r, _p, rz_prev, rs, it, _h = st
                go = (
                    jnp.sqrt(rs) > tol * jnp.maximum(1.0, jnp.sqrt(rs0))
                ) & (it < maxiter)
                return go & (rz_prev != 0)

            def step(st):
                x, r, p, rz_prev, rs, it, hist = st
                z = apply_minv(r)
                rz = pdot(r, z)
                beta = jnp.where(it == 0, 0.0, rz / rz_prev)
                p = p.at[sl].set(z[sl] + beta * p[sl])
                q = spmv(p)
                pq = pdot(p, q)
                alpha = rz / pq
                x = x.at[sl].add(alpha * p[sl])
                r = r.at[sl].add(-alpha * q[sl])
                rs_new = pdot(r, r)
                hist = hist.at[jnp.minimum(it + 1, H - 1)].set(
                    jnp.sqrt(rs_new)
                )
                return (x, r, p, rz, rs_new, it + 1, hist)

            x, r, p, rz, rs, it, hist = _krylov_loop(
                cond, step,
                (xv, r, p, jnp.asarray(1.0, bv.dtype), rs0,
                 jnp.int32(0), hist),
            )
            return x[None], rs, rs0, it, hist

        return shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec, spec, none_spec, specs),
            out_specs=(spec, none_spec, none_spec, none_spec, none_spec),
            check_vma=False,
        )(b, x0, cinv, m)

    def run(b, x0):
        return fn(b, x0, dh["cinv"], ops)

    # the jitted program, for `jit_fn.lower(b, x0, dh["cinv"], ops)` (the
    # convention of `make_cg_fn`)
    run.jit_fn = fn
    return run


def make_fgmres_gmg_fn(
    h, backend: TPUBackend, tol: float, maxiter: int, restart: int = 30
):
    """FLEXIBLE restarted GMRES with the ENTIRE multigrid V-cycle inlined
    as the right preconditioner — one compiled program (the device form
    of models.solvers.fgmres(A, b, minv=hierarchy)). The Arnoldi loop
    follows the host algorithm step for step (modified Gram-Schmidt in
    fixed order, sequential Givens rotations, true-residual restart
    test), with fixed shapes: the V/Z bases are dense (m+1, W)/(m, W)
    carries and inactive steps are masked rather than skipped, so one
    `lax.while_loop` over restart cycles serves any trip count."""
    import jax
    import jax.numpy as jnp
    shard_map = jax.shard_map

    dh = _device_hierarchy(h, backend)
    dA0 = dh["levels"][0]["dA"]
    mesh = backend.mesh(dA0.row_layout.P)
    spec = backend.parts_spec()
    none_spec = jax.sharding.PartitionSpec()
    L0 = dA0.col_plan.layout
    pdot = _pdot_factory(L0.o0, L0.no_max)
    body_A0 = _spmv_body(dA0)
    vcycle = _vcycle_shard_body(h, dh)
    ops = _gmg_operands(dh)
    specs = jax.tree.map(lambda _: spec, ops)
    m = int(restart)
    H_cap = int(min(maxiter + 1, 4096))

    @jax.jit
    def fn(b, x0, cinv, mats_in):
        def shard_fn(bs, x0s, cinv_r, ms):
            bv, xv = bs[0], x0s[0]
            mats = _shard_ops(jax, ms)
            no = L0.no_max
            sl = slice(L0.o0, L0.o0 + no)
            Lr = dA0.row_layout
            dt = bv.dtype

            def spmv(z):
                y, _ = body_A0(z, mats["lv"][0]["A"])
                return jnp.zeros_like(z).at[sl].set(y[Lr.o0 : Lr.o0 + no])

            def residual(x):
                y = spmv(x)
                return jnp.zeros_like(x).at[sl].set(bv[sl] - y[sl])

            r0 = residual(xv)
            beta0 = jnp.sqrt(pdot(r0, r0))
            rs0 = jnp.maximum(1.0, beta0)
            hist = jnp.full(H_cap, jnp.nan, dtype=dt).at[0].set(beta0)
            W = xv.shape[0]

            def cycle(st):
                x, beta, it, hist, _conv = st
                r = residual(x)
                b2 = jnp.sqrt(pdot(r, r))
                safe = jnp.where(b2 > 0, b2, 1.0)
                V = jnp.zeros((m + 1, W), dt).at[0].set(r / safe)
                Z = jnp.zeros((m, W), dt)
                Hm = jnp.zeros((m + 1, m), dt)
                cs = jnp.zeros(m, dt)
                sn = jnp.zeros(m, dt)
                g = jnp.zeros(m + 1, dt).at[0].set(b2)
                active0 = b2 > tol * rs0

                def arnoldi(j, car):
                    V, Z, Hm, cs, sn, g, it, hist, active, j_used = car
                    active = active & (it < maxiter)
                    vj = jax.lax.dynamic_slice(V, (j, 0), (1, W))[0]
                    z = vcycle(vj, mats, cinv_r)
                    w = spmv(z)
                    # modified Gram-Schmidt, fixed order (i <= j live)
                    hcol = jnp.zeros(m + 1, dt)
                    for i in range(m):
                        live = i <= j
                        hij = jnp.where(live, pdot(w, V[i]), 0.0)
                        w = w - hij * V[i]
                        hcol = hcol.at[i].set(hij)
                    hj1 = jnp.sqrt(pdot(w, w))
                    hcol = hcol.at[j + 1].set(hj1)
                    # apply the accumulated Givens rotations (i < j)
                    for i in range(m):
                        live = i < j
                        t = cs[i] * hcol[i] + sn[i] * hcol[i + 1]
                        u = -sn[i] * hcol[i] + cs[i] * hcol[i + 1]
                        hcol = hcol.at[i].set(jnp.where(live, t, hcol[i]))
                        hcol = hcol.at[i + 1].set(
                            jnp.where(live, u, hcol[i + 1])
                        )
                    hjj = jax.lax.dynamic_slice(hcol, (j,), (1,))[0]
                    rho = jnp.hypot(hjj, hj1)
                    csj = jnp.where(rho == 0, 1.0, hjj / rho)
                    snj = jnp.where(rho == 0, 0.0, hj1 / rho)
                    hcol = jax.lax.dynamic_update_slice(
                        hcol, jnp.stack([rho, jnp.zeros((), dt)]), (j,)
                    )
                    gj = jax.lax.dynamic_slice(g, (j,), (1,))[0]
                    g_new = jax.lax.dynamic_update_slice(
                        g, jnp.stack([csj * gj, -snj * gj]), (j,)
                    )
                    res = jnp.abs(-snj * gj)
                    # masked commits
                    Z = jnp.where(active, Z.at[j].set(z), Z)
                    Hm = jnp.where(active, Hm.at[:, j].set(hcol), Hm)
                    cs = jnp.where(active, cs.at[j].set(csj), cs)
                    sn = jnp.where(active, sn.at[j].set(snj), sn)
                    g = jnp.where(active, g_new, g)
                    safe_w = jnp.where(hj1 > 0, hj1, 1.0)
                    V = jnp.where(active, V.at[j + 1].set(w / safe_w), V)
                    it = it + active.astype(it.dtype)
                    hist = jnp.where(
                        active,
                        hist.at[jnp.minimum(it, H_cap - 1)].set(res),
                        hist,
                    )
                    j_used = jnp.where(active, j + 1, j_used)
                    # the host breaks AFTER committing step j on
                    # convergence or lucky breakdown
                    active = active & (res > tol * rs0) & (hj1 > 0)
                    return (V, Z, Hm, cs, sn, g, it, hist, active, j_used)

                V, Z, Hm, cs, sn, g, it, hist, _a, j_used = jax.lax.fori_loop(
                    0,
                    m,
                    arnoldi,
                    (V, Z, Hm, cs, sn, g, it, hist, active0,
                     jnp.int32(0)),
                )
                # back-substitute the j_used x j_used triangular system
                y = jnp.zeros(m, dt)
                for i in range(m - 1, -1, -1):
                    live = i < j_used
                    s = g[i] - jnp.sum(Hm[i, :] * y)
                    d = jnp.where(Hm[i, i] != 0, Hm[i, i], 1.0)
                    y = y.at[i].set(jnp.where(live, s / d, 0.0))
                # flexible update: x rides the PRECONDITIONED basis Z,
                # applied in host order (sequential axpys) over the OWNED
                # slice only — Z rows are raw V-cycle outputs whose ghost
                # slots carry transfer-internal values
                for i in range(m):
                    x = x.at[sl].add(y[i] * Z[i][sl])
                r = residual(x)
                beta = jnp.sqrt(pdot(r, r))
                conv = beta <= tol * rs0
                return (x, beta, it, hist, conv)

            def cond(st):
                _x, _beta, it, _h, conv = st
                return (~conv) & (it < maxiter)

            x, beta, it, hist, _conv = _krylov_loop(
                cond,
                cycle,
                (xv, beta0, jnp.int32(0), hist, beta0 <= tol * rs0),
            )
            return x[None], beta * beta, beta0 * beta0, it, hist

        return shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec, spec, none_spec, specs),
            out_specs=(spec, none_spec, none_spec, none_spec, none_spec),
            check_vma=False,
        )(b, x0, cinv, mats_in)

    def run(b, x0):
        return fn(b, x0, dh["cinv"], ops)

    return run


def tpu_fgmres_gmg(
    h,
    b: PVector,
    x0: Optional[PVector] = None,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    restart: int = 30,
    verbose: bool = False,
) -> Tuple[PVector, dict]:
    """Compiled flexible GMRES with the V-cycle preconditioner inlined
    (device form of fgmres(A, b, minv=hierarchy))."""
    backend = b.values.backend
    check(
        isinstance(backend, TPUBackend), "tpu_fgmres_gmg needs the TPU backend"
    )
    if maxiter is None:
        maxiter = 4 * int(h.levels[0].A.rows.ngids)
    return _run_gmg(
        h, b, x0, tol, maxiter, verbose,
        lambda: make_fgmres_gmg_fn(
            h, backend, tol, maxiter, restart=restart
        ),
        f"fgmres+gmg(m={restart})",
    )


def _run_gmg(h, b, x0, tol, maxiter, verbose, make_fn, name):
    from .. import telemetry
    from .tpu import _run_krylov

    backend = b.values.backend
    cache = getattr(h, "_fn_cache", None)
    if cache is None:
        cache = h._fn_cache = {}
    env_key = _gmg_env_key(backend)
    key = (name, backend._token, float(tol), int(maxiter)) + env_key
    with telemetry.solve_scope(
        name, backend="tpu", tol=float(tol), maxiter=int(maxiter),
        dtype=str(np.dtype(b.dtype)), env_key=env_key,
    ) as rec:
        if key not in cache:
            cache[key] = make_fn()
        # the compiled fns share the Krylov (b, x0) -> 5-tuple contract,
        # so the staging/lifting/info logic is _run_krylov's verbatim
        x, info = _run_krylov(
            h.levels[0].A, b, x0, tol, verbose, cache[key], name=name
        )
        # The record (timings, events) retires into the history ring:
        # `telemetry.last_record(name)`. The info stays the plain dict it
        # was, so a caller that keeps every info keeps no record alive:
        # with an `InfoDict` here, `poisson7_192.gmg_pcg`'s closed loop
        # (it keeps them all) read `solve_p95_s` 29 % higher on the chip
        # host, every 43rd to 45th solve repaying its staging buffers'
        # page faults (PERF.md, PR 26).
        rec.finish(info)
        return x, info


def tpu_gmg_solve(
    h,
    b: PVector,
    x0: Optional[PVector] = None,
    tol: float = 1e-8,
    maxiter: int = 100,
    verbose: bool = False,
) -> Tuple[PVector, dict]:
    """Compiled stationary cycle iteration (device form of gmg_solve)."""
    backend = b.values.backend
    check(isinstance(backend, TPUBackend), "tpu_gmg_solve needs the TPU backend")
    return _run_gmg(
        h, b, x0, tol, maxiter, verbose,
        lambda: make_gmg_solve_fn(h, backend, tol, maxiter), "gmg",
    )


def tpu_gmg_pcg(
    h,
    b: PVector,
    x0: Optional[PVector] = None,
    tol: float = 1e-8,
    maxiter: Optional[int] = None,
    verbose: bool = False,
) -> Tuple[PVector, dict]:
    """Compiled V-cycle-preconditioned CG (device form of
    pcg(A, b, minv=hierarchy))."""
    backend = b.values.backend
    check(isinstance(backend, TPUBackend), "tpu_gmg_pcg needs the TPU backend")
    if maxiter is None:
        maxiter = 4 * int(h.levels[0].A.rows.ngids)
    return _run_gmg(
        h, b, x0, tol, maxiter, verbose,
        lambda: make_gmg_pcg_fn(h, backend, tol, maxiter), "pcg+gmg",
    )
