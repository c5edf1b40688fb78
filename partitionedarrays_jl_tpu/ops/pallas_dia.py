"""Pallas TPU kernel for the banded (DIA) SpMV hot loop.

The device form of the reference's local SpMV kernels
(reference: src/SparseUtils.jl:157-187, :222-252) for *banded* operators —
the shape every FD/FV stencil matrix has. The XLA fallback in
`parallel/tpu.py` computes ``sum_d vals[d] * x[i + off_d]`` with one padded
copy plus static slices; XLA materializes intermediates for the misaligned
(±1-ish) offsets, so the op runs several times over the bandwidth bound.
This kernel makes the memory schedule explicit:

* all operands are viewed as ``(rows, 128)`` lane-major tiles;
* the diagonal values ``(D, R, 128)`` and the output stream through VMEM
  via the grid pipeline (auto double-buffered);
* the x window (block rows + halo rows) is DMA'd HBM→VMEM once per block,
  double-buffered by hand: block i+1's window is in flight while block i
  computes (`WINDOW_SLOTS` slots, the grid walked in order);
* each diagonal offset ``s = q*128 + r`` becomes a *row shift* (q) plus a
  *lane rotation* (r) computed entirely in VMEM: two shifted row views
  concatenated at lane boundary r.

Accumulation is a strict ascending-offset fold — the same per-row order as
the host CSR kernel (column-sorted rows), so results stay bit-comparable
with the sequential oracle; padding and absent-diagonal terms are exact
zeros.

HBM traffic per SpMV ≈ vals (D·N) + x (N + halo) + y (N) words — the
streaming lower bound for a general banded operator.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import numpy as np

LANES = 128

#: Scoped-VMEM limit handed to Mosaic with both kernels. The plans below
#: budget the DMA buffers a kernel declares (<= 12-13 MiB; the coded
#: kernel's fold variant <= PFOLD_VMEM_BYTES); the kernel
#: body's own temporaries — the int32 upcast of every packed code stream,
#: the shifted windows, the class accumulators — come on top of that, and
#: the compiler's default limit does not hold them. Found on device_kind
#: "TPU v5 lite" (v5e; jax 0.9.0, libtpu 0.0.34; default limit 16 MiB):
#: the 27-diagonal interpolation stencil of the GMG hierarchy on a
#: (2,2,1) partition at 192^3 cells per chip (26 coded diagonals = 13
#: nibble streams) needs 26.8 MiB and failed to compile ("exceeded scoped
#: vmem limit by 10.80M"). The widest kernel the 13 MiB budget admits (16
#: streams) needs ~40 MiB by the same arithmetic; 64 MiB holds it with
#: room and is half of a v5e core's 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 64 * 2**20
#: block rows per grid step (vals block = D * BR * 128 * 4B in VMEM,
#: double-buffered by the pipeline; 1,024 rows -> 3.7 MB per diagonal-7
#: block). Tuned on a v5e with the window double-buffered: one call at
#: 192^3 and seven diagonals reads 451 / 408 / 387 us at 256 / 512 /
#: 1,024 rows. A band too wide for the VMEM gate at this size is planned at
#: MIN_BLOCK_ROWS, where up to 20 diagonals of a 288-row halo fit; a band
#: too wide for that too (the 27-point Galerkin operators of a multigrid
#: hierarchy) takes the 8-aligned block between FLOOR_BLOCK_ROWS and
#: MIN_BLOCK_ROWS that fits and pads the fewest rows (`plan_dia_pallas`).
DEF_BLOCK_ROWS = 1024
MIN_BLOCK_ROWS = 512
#: the least block a plan takes. At 128 rows a grid step still moves
#: 64 KiB a diagonal, some 2.5 us of DMA for a 27-point band at the
#: kernel's 700 GB/s against a step's fixed cost of a few tenths of a
#: microsecond, and its window fetches x at most 2.2 times for a
#: 73-row halo (96^3). Below it both costs grow as the block shrinks; a
#: band that needs a smaller block keeps the XLA form
FLOOR_BLOCK_ROWS = 128
#: VMEM slots of the streamed kernel's x window: block i+1's window is
#: fetched into one while block i's band sum reads the other
WINDOW_SLOTS = 2
#: block slots of each operand ring of the coded kernel's direction-fold
#: variant: blocks j-1, j and j+1 make step j's window, and block j+2 is
#: fetched into block j-1's slot once step j has combined its rows
PFOLD_RING_SLOTS = 3


def _win_rows(block_rows: int, halo_rows: int) -> int:
    """Rows of the per-block x window (block + halo above/below + one spill
    row for lane rotation), rounded up to 8 — TPU DMAs want 8-aligned
    sublane counts."""
    return -(-(block_rows + 2 * halo_rows + 1) // 8) * 8


def _kernel(vals_ref, xw_ref, y_ref, xs_ref, sem, *, qr: Tuple[Tuple[int, int], ...],
            block_rows: int, halo_rows: int, n_blocks: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    # x window of block blk: rows [blk*BR, blk*BR + win_rows) of the padded
    # x — one DMA, reused by every diagonal. The window is rounded up to a
    # multiple of 8 rows: a DMA whose sublane count is not 8-aligned
    # faults the chip.
    win_rows = _win_rows(block_rows, halo_rows)

    def x_dma(slot, blk):
        return pltpu.make_async_copy(
            xw_ref.at[pl.ds(blk * block_rows, win_rows), :],
            xs_ref.at[slot],
            sem.at[slot],
        )

    slot = jax.lax.rem(i, jnp.int32(WINDOW_SLOTS))

    @pl.when(i == 0)
    def _():
        x_dma(0, 0).start()

    # block i+1's window goes into the other slot before block i's is
    # waited on: the fetch overlaps this block's band sum (the grid runs
    # in order, so the slot's last reader, block i-1, is done)
    @pl.when(i + 1 < n_blocks)
    def _():
        x_dma(1 - slot, i + 1).start()

    x_dma(slot, i).wait()

    acc = None
    for d, (q, r) in enumerate(qr):
        a = xs_ref[slot, pl.ds(q, block_rows), :]
        if r == 0:
            shifted = a
        else:
            b = xs_ref[slot, pl.ds(q + 1, block_rows), :]
            # lane rotation: lanes [r:] of row q  ++  lanes [:r] of row q+1
            shifted = jnp.concatenate([a[:, r:], b[:, :r]], axis=1)
        term = vals_ref[d] * shifted
        acc = term if acc is None else acc + term
    y_ref[:] = acc


def dia_spmv_pallas(
    vals: "jax.Array",  # noqa: F821
    x: "jax.Array",  # noqa: F821
    offsets: Tuple[int, ...],
    n_rows: int,
    halo_rows: int,
    block_rows: int = DEF_BLOCK_ROWS,
    interpret: bool = False,
):
    """y = sum_d diag(vals[d]) @ shift(x, offsets[d]) on the lane-tiled form.

    vals: (D, R, 128) diagonal values, R = n_rows (a multiple of block_rows).
    x:    (R + win_rows - block_rows, 128) with the owned region starting at
          flat element halo_rows*128, zero-padded on both sides so every
          shifted read stays in range (use plan_dia_pallas()["x_rows"]).
    offsets: ascending flat-element diagonal offsets; |off| <= halo_rows*128.
    Returns y: (R, 128).
    """
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    D, R, _ = vals.shape
    assert R == n_rows and n_rows % block_rows == 0
    qr = tuple(divmod(halo_rows * LANES + off, LANES) for off in offsets)
    n_blocks = n_rows // block_rows
    win_rows = _win_rows(block_rows, halo_rows)
    assert x.shape[0] >= n_rows + win_rows - block_rows, (x.shape, n_rows, win_rows)
    kernel = functools.partial(
        _kernel, qr=qr, block_rows=block_rows, halo_rows=halo_rows,
        n_blocks=n_blocks,
    )
    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(
                (D, block_rows, LANES), lambda i: (0, i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(memory_space=pl.ANY),  # x stays in HBM; manual DMA
        ],
        out_specs=pl.BlockSpec(
            (block_rows, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((n_rows, LANES), vals.dtype),
        scratch_shapes=[
            pltpu.VMEM((WINDOW_SLOTS, win_rows, LANES), vals.dtype),
            pltpu.SemaphoreType.DMA((WINDOW_SLOTS,)),
        ],
        compiler_params=pltpu.CompilerParams(
            # the window prefetch crosses grid steps: they run in order
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="pa_dia_stream_spmv",
    )(vals, x)


#: Fixed block geometry of the padded vector layout (see
#: `parallel/tpu.py:DeviceLayout`): one zero block before the owned
#: region, one zero reserve block after it, ghosts beyond. Bounds the
#: supported diagonal offset to BLOCK_ROWS*LANES flat elements.
PAD_BLOCK_ROWS = 2048


def plan_dia_padded(
    offsets: Sequence[int],
    no_max: int,
    n_coded: int,
    itemsize: int = 4,
):
    """Geometry of the coded kernel operating *in-place* on the padded
    vector layout: vectors are (T*BR, 128) with owned elements at flat
    offset BR*128; the kernel consumes and produces full vectors, so SpMV
    does zero layout copies. Returns None when an offset exceeds the
    fixed pad reserve or VMEM would overflow (fall back to the copying
    kernels)."""
    if not offsets:
        return None
    BR = PAD_BLOCK_ROWS
    max_off = max(abs(int(o)) for o in offsets)
    if max_off > (BR - 8) * LANES:
        return None
    halo_rows = -(-max_off // LANES)
    h8 = -(-halo_rows // 8) * 8
    win_rows = _win_rows(BR, h8)
    vmem = (
        2 * win_rows * LANES * itemsize
        + 2 * BR * LANES * itemsize
        + 2 * max(n_coded, 1) * BR * LANES
    )
    if vmem > 13 * 2**20:
        return None
    n_blocks = -(-no_max // (LANES * BR))
    return {
        "vmem": int(vmem),
        "block_rows": BR,
        "halo_rows": h8,
        "n_blocks": int(n_blocks),
        "o0": int(BR * LANES),
        "g0": int((n_blocks + 2) * BR * LANES),
        "code_len": int(n_blocks * BR * LANES),
    }


def pack_nibble_codes(codes: np.ndarray) -> np.ndarray:
    """Pack per-diagonal uint8 codes (< 16) into the kernel's byte streams:
    two diagonals per byte, low nibble = even coded index. codes has the
    coded-diagonal axis at position -2: (..., Dc, N) -> (..., ceil(Dc/2), N)
    int8. This is the ONE definition of the packing convention the
    `_padded_kernel` decode relies on."""
    if codes.size and codes.max() >= 16:
        raise ValueError("nibble packing requires codes < 16 (CODE_MAX_VALUES)")
    Dc = codes.shape[-2]
    Dp = max(-(-Dc // 2), 1)
    packed = np.zeros(codes.shape[:-2] + (Dp,) + codes.shape[-1:], dtype=np.uint8)
    packed[..., : (Dc + 1) // 2, :] = codes[..., 0:Dc:2, :]
    if Dc > 1:
        packed[..., : Dc // 2, :] |= codes[..., 1:Dc:2, :] << 4
    return packed.view(np.int8)


def _padded_kernel(cb_ref, no_ref, codes_ref, xw_ref, *refs,
                   qr: Tuple[Tuple[int, int], ...],
                   kk: Tuple[int, ...], code_row: Tuple[int, ...],
                   n_blocks: int, block_rows: int, halo_rows: int,
                   n_coded: int,
                   cls_pattern: Tuple[Tuple[bool, ...], ...] = None,
                   has_pfold: bool = False, columns: bool = False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if columns:
        # K vectors, one after the other, on a leading grid axis: the
        # block walk below is a column's own from its first block to its
        # last (every DMA it starts it also waits for), so a column is
        # the single-vector call and the codes are read once a column
        col = pl.program_id(0)
        xw_ref = xw_ref.at[col]
    if has_pfold:
        # leading-edge direction fold (fused CG): the SpMV operand is
        # p = r + beta*p_prev, built IN the window pass — the kernel
        # streams r and p_prev each through a ring of PFOLD_RING_SLOTS
        # block slots (every block fetched once: a window's halo rows
        # are the neighbouring blocks', already in the ring), combines
        # the window of blocks j-1 to j+1 once in VMEM, runs the
        # shifted-read band sum on the combined window, and emits the
        # center rows as the materialized new direction. The standalone
        # p-update sweep (read r, read p, write p) of the standard loop
        # disappears into the SpMV's own streaming pass; xw_ref is the r
        # source here. The p output is p_prev's own buffer: block j is
        # stored over it behind step j, when no fetch of it is left.
        (pw_ref, beta_ref, y_ref, po_ref,
         xs_ref, ps_ref, comb_ref, cs_ref, xsem, psem, csem) = refs
        if columns:
            pw_ref = pw_ref.at[col]
    else:
        y_ref, xs_ref, cs_ref, xsem, csem = refs

    j = pl.program_id(1 if columns else 0)
    BR = block_rows
    win_rows = _win_rows(BR, halo_rows)

    def x_dma(slot, blk):
        return pltpu.make_async_copy(
            xw_ref.at[pl.ds(blk * BR - halo_rows, win_rows), :],
            xs_ref.at[slot],
            xsem.at[slot],
        )

    def ring_slot(blk):
        if isinstance(blk, int):
            return blk % PFOLD_RING_SLOTS
        return jax.lax.rem(blk, jnp.int32(PFOLD_RING_SLOTS))

    def fetch(blk):
        """block blk of r and of p_prev into its ring slot"""
        slot = ring_slot(blk)
        return [
            pltpu.make_async_copy(
                src.at[pl.ds(blk * BR, BR), :], ring.at[slot], sem.at[slot]
            )
            for src, ring, sem in (
                (xw_ref, xs_ref, xsem), (pw_ref, ps_ref, psem)
            )
        ]

    def codes_dma(slot, blk):
        return pltpu.make_async_copy(
            codes_ref.at[:, pl.ds((blk - 1) * BR, BR), :],
            cs_ref.at[slot],
            csem.at[slot],
        )

    two = jnp.int32(2)
    slot = jax.lax.rem(j, two)

    @pl.when(j == 0)
    def _():
        if has_pfold:
            # the ring's first three blocks: the leading zero block, 1, 2
            for blk in range(PFOLD_RING_SLOTS):
                for c in fetch(blk):
                    c.start()
        else:
            x_dma(1, 1).start()
        if n_coded:
            codes_dma(1, 1).start()
        if has_pfold:
            # block 0's p_prev is stored over behind this step; step j
            # waits for block j+1
            for blk in (0, 1):
                for c in fetch(blk):
                    c.wait()

    @pl.when((j >= 1) & (j < n_blocks))
    def _():
        nxt = jax.lax.rem(j + 1, two)
        if not has_pfold:
            x_dma(nxt, j + 1).start()
        if n_coded:
            codes_dma(nxt, j + 1).start()

    @pl.when((j >= 1) & (j <= n_blocks))
    def _compute():
        if has_pfold:
            # one in-VMEM pass builds the combined operand window from
            # the ring, a segment at a time: the last halo_rows of block
            # j-1, block j, the first rows of block j+1; every shifted
            # diagonal read then hits the combined copy, so the fold
            # costs ONE add per element instead of one per diagonal
            beta = beta_ref[col if columns else 0]

            def combine(dst, blk, src, n):
                if n:
                    s = ring_slot(blk)
                    comb_ref[pl.ds(dst, n), :] = (
                        xs_ref[s, pl.ds(src, n), :]
                        + beta * ps_ref[s, pl.ds(src, n), :]
                    )

            combine(0, j - 1, BR - halo_rows, halo_rows)

            # block j-1's slot is read: block j+2 goes there at once and
            # lands behind the rest of this step (the reserve block
            # n_blocks+1 is the last one fetched)
            @pl.when(j < n_blocks)
            def _():
                for c in fetch(j + 2):
                    c.start()

            combine(halo_rows, j, 0, BR)
            for c in fetch(j + 1):
                c.wait()
            combine(halo_rows + BR, j + 1, 0, win_rows - BR - halo_rows)
        else:
            x_dma(slot, j).wait()
        if n_coded:
            codes_dma(slot, j).wait()

        def shift_of(q, r):
            if has_pfold:
                a = comb_ref[pl.ds(q, BR), :]
                if r == 0:
                    return a
                b = comb_ref[pl.ds(q + 1, BR), :]
            else:
                a = xs_ref[slot, pl.ds(q, BR), :]
                if r == 0:
                    return a
                b = xs_ref[slot, pl.ds(q + 1, BR), :]
            return jnp.concatenate([a[:, r:], b[:, :r]], axis=1)

        if cls_pattern is not None:
            # row-class fast path: rows fall into K = len(cls_pattern)
            # stencil classes sharing ONE code stream. Instead of a
            # K-deep select per diagonal, accumulate one candidate sum
            # per class — skipping coefficients that are zero in every
            # part (static pattern) — and select ONCE by class id. Each
            # class sum runs the same ascending-offset term order as the
            # host CSR kernel over that class's stored entries (the
            # skipped terms are the host's absent entries), so agreement
            # with the select path and the host oracle holds to
            # FMA-contraction rounding — the determinism contract.
            sh = [shift_of(q, r) for (q, r) in qr]
            c = (cs_ref[slot, 0].astype(jnp.int32)) & 15
            accs = []
            for k, pat in enumerate(cls_pattern):
                acc_k = None
                for d in range(len(qr)):
                    if pat[d]:
                        # constant diagonals (kk == 1) store one slot,
                        # replicated across classes by the staging code
                        term = cb_ref[d, min(k, kk[d] - 1)] * sh[d]
                        acc_k = term if acc_k is None else acc_k + term
                if acc_k is None:
                    acc_k = jnp.zeros_like(sh[0])
                accs.append(acc_k)
            acc = accs[0]
            for k in range(1, len(accs)):
                acc = jnp.where(c == k, accs[k], acc)
        else:
            acc = None
            streams = {}  # packed byte stream -> int32 form, decoded once
            for d, (q, r) in enumerate(qr):
                shifted = shift_of(q, r)
                if kk[d] == 1:
                    term = cb_ref[d, 0] * shifted
                else:
                    # two diagonals share one int8 stream (4-bit codes, low
                    # nibble = even coded index). Upcast before bit ops — an
                    # i1/int8 born in 32-sublane tiling cannot be relaid out
                    # against f32 by Mosaic — and mask AFTER the shift so the
                    # int8 sign extension cannot leak into the code.
                    ci = code_row[d]
                    if ci // 2 not in streams:
                        streams[ci // 2] = cs_ref[slot, ci // 2].astype(jnp.int32)
                    c = (streams[ci // 2] >> (4 * (ci % 2))) & 15
                    v = jnp.where(c == 1, cb_ref[d, 1], cb_ref[d, 0])
                    for k in range(2, kk[d]):
                        v = jnp.where(c == k, cb_ref[d, k], v)
                    term = v * shifted
                acc = term if acc is None else acc + term
        e = (
            (j - 1) * BR * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (BR, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (BR, LANES), 1)
        )
        y_ref[:] = jnp.where(e < no_ref[0], acc, 0)

    @pl.when((j < 1) | (j > n_blocks))
    def _zero():
        y_ref[:] = jnp.zeros_like(y_ref)

    if has_pfold:
        # materialize the combined direction for the rest of the
        # iteration (pq dot, x update, next fold): the center rows of
        # the window ARE block j of p = r + beta*p_prev — no extra read.
        # Masking to the owned band keeps the zero-pad invariant exact.
        @pl.when((j >= 1) & (j <= n_blocks))
        def _pfold_out():
            e2 = (
                (j - 1) * block_rows * LANES
                + jax.lax.broadcasted_iota(
                    jnp.int32, (block_rows, LANES), 0
                ) * LANES
                + jax.lax.broadcasted_iota(
                    jnp.int32, (block_rows, LANES), 1
                )
            )
            po_ref[:] = jnp.where(
                e2 < no_ref[0],
                comb_ref[pl.ds(halo_rows, block_rows), :],
                jnp.zeros_like(po_ref),
            )

        @pl.when((j < 1) | (j > n_blocks))
        def _pfold_zero():
            po_ref[:] = jnp.zeros_like(po_ref)


def dia_coded_padded_pallas(
    codebook: "jax.Array",  # noqa: F821
    no: "jax.Array",  # noqa: F821
    codes: "jax.Array",  # noqa: F821
    x: "jax.Array",  # noqa: F821
    offsets: Tuple[int, ...],
    kk: Tuple[int, ...],
    code_row: Tuple[int, ...],
    plan: dict,
    total_rows: int,
    interpret: bool = False,
    cls_pattern: Tuple[Tuple[bool, ...], ...] = None,
    pfold: Tuple["jax.Array", "jax.Array"] = None,  # noqa: F821
):
    """Full-vector coded SpMV on the padded layout: x is a whole
    (total_rows, 128) padded vector (owned at flat offset plan['o0'],
    zeros elsewhere up to the ghost region, which the kernel never
    reads); the result is a whole padded vector with the owned band
    computed and every other slot exactly zero. codes: (Dc, n_blocks*BR,
    128) int8. ``cls_pattern`` (row-class mode only, all coded diagonals
    on stream 0): K per-class nonzero masks over the diagonals enabling
    the per-class-accumulator decode — see `_padded_kernel`.

    ``pfold=(pprev, beta)`` (fused CG) instead treats ``x`` as the
    RESIDUAL vector and computes the SpMV of the combined direction
    ``p = x + beta*pprev`` without ever reading a materialized p: each
    block of both operands is DMA'd once into a ring of
    `PFOLD_RING_SLOTS` block slots, the window of blocks j-1 to j+1 is
    combined once in VMEM, and the band sum runs on the combined copy.
    Returns ``(y, p)`` with
    ``y = A_oo p`` and ``p`` masked to the owned band (every other slot
    exactly zero) — the standard loop's standalone direction-update
    sweep is absorbed by the SpMV pass (tpu.py:make_cg_fn fused body).
    Callers must first check `pfold_vmem_ok(plan)`.

    The call updates the direction in place: ``p`` is declared aliased
    to ``pprev`` (``input_output_aliases``), so a loop that carries p
    needs no copy of it. Block j's p rows are stored over p_prev behind
    step j; block j's fetch landed a step before, and step j+1 reads
    those rows as its lower halo from the ring. That needs a window
    that lies within blocks j-1 to j+1 (``halo_rows <= block_rows``,
    and the upper edge as `_win_rows` rounds it), which every
    `plan_dia_padded` plan holds (``halo_rows + 8 <= block_rows``) and
    the call asserts.

    ``x`` may be K such vectors, ``(K, total_rows, 128)`` (with ``pprev``
    alike and ``beta`` of K entries): a leading grid axis walks them one
    after the other, each as the single-vector call would (the codes are
    read once a column), and the results keep the leading axis. The
    single-vector call is built exactly as before."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    D = codebook.shape[0]
    Dc = codes.shape[0]
    assert D == len(offsets) == len(kk) == len(code_row)
    if cls_pattern is not None:
        assert all(c <= 0 for c in code_row), "class mode uses stream 0 only"
        assert all(len(p) == D for p in cls_pattern)
    BR, H, nB = plan["block_rows"], plan["halo_rows"], plan["n_blocks"]
    qr = tuple(divmod(H * LANES + off, LANES) for off in offsets)
    columns = x.ndim == 3
    assert x.shape[-2] == total_rows and total_rows % BR == 0
    assert total_rows >= (nB + 2) * BR
    win_rows = _win_rows(BR, H)
    kernel = functools.partial(
        _padded_kernel, qr=qr, kk=tuple(int(k) for k in kk),
        code_row=tuple(int(c) for c in code_row), n_blocks=nB,
        block_rows=BR, halo_rows=H, n_coded=Dc,
        cls_pattern=cls_pattern, has_pfold=pfold is not None,
        columns=columns,
    )
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # codebook
        pl.BlockSpec(memory_space=pltpu.SMEM),  # no
        pl.BlockSpec(memory_space=pl.ANY),  # codes: manual DMA
        pl.BlockSpec(memory_space=pl.ANY),  # x: manual DMA
    ]
    if columns:
        grid = (x.shape[0], total_rows // BR)
        y_spec = pl.BlockSpec(
            (None, BR, LANES), lambda c, j: (c, j, 0),
            memory_space=pltpu.VMEM,
        )
    else:
        grid = (total_rows // BR,)
        y_spec = pl.BlockSpec(
            (BR, LANES), lambda j: (j, 0), memory_space=pltpu.VMEM
        )
    y_shape = jax.ShapeDtypeStruct(
        x.shape[:-2] + (total_rows, LANES), codebook.dtype
    )
    params = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)
    scratch = [
        pltpu.VMEM((2, win_rows, LANES), codebook.dtype),
        pltpu.VMEM((2, max(Dc, 1), BR, LANES), codes.dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    if pfold is not None:
        pprev, beta = pfold
        assert pprev.shape == x.shape
        assert H <= BR and win_rows <= H + 2 * BR, (
            "p in place from a ring: a window lies within blocks j-1 to j+1"
        )
        ring = pltpu.VMEM((PFOLD_RING_SLOTS, BR, LANES), codebook.dtype)
        ring_sem = pltpu.SemaphoreType.DMA((PFOLD_RING_SLOTS,))
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs + [
                pl.BlockSpec(memory_space=pl.ANY),  # pprev: manual DMA
                pl.BlockSpec(memory_space=pltpu.SMEM),  # beta
            ],
            out_specs=[y_spec, y_spec],
            out_shape=[
                y_shape, jax.ShapeDtypeStruct(x.shape, x.dtype),
            ],
            scratch_shapes=[
                ring,  # r (xs)
                ring,  # p_prev
                pltpu.VMEM((win_rows, LANES), codebook.dtype),  # combined
                scratch[1],  # codes
                ring_sem,  # r ring sem
                ring_sem,  # p_prev ring sem
                pltpu.SemaphoreType.DMA((2,)),  # codes sem
            ],
            # pprev (input 4) is the p output's buffer
            input_output_aliases={4: 1},
            compiler_params=params,
            interpret=interpret,
            name="pa_dia_coded_spmv_pfold",
        )(codebook, no, codes, x, pprev, beta)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=y_spec,
        out_shape=y_shape,
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
        name="pa_dia_coded_spmv",
    )(codebook, no, codes, x)


#: Budget of the direction-fold variant's declared VMEM buffers: the
#: codes and y blocks of the plan, a ring of PFOLD_RING_SLOTS blocks each
#: for r and p_prev, the combined-window copy and the double-buffered p
#: output block. The kernel body's temporaries (shifted windows, the
#: int32 upcast of the code streams, the accumulators) come on top.
#: Compiled for device_kind "TPU v5 lite" (v5e; jax 0.9.0, libtpu
#: 0.0.34), the least VMEM_LIMIT_BYTES under which the fold kernel builds
#: is 1.26 to 1.51 times its declared buffers: 16.75 MiB for 13.29
#: declared (7-point Poisson at 192^3, four code streams), 17.84 for 11.79
#: (192^3, one stream), 19.81 for 13.69 (300^3), 17.84 for 13.79 (320^3),
#: 19.16 for 14.0 (360^3), 20.25 for 15.0 (the widest halo, 2,040 rows).
#: 16 MiB declared so stays within half of VMEM_LIMIT_BYTES, the headroom
#: the plain kernels keep, and admits the 7-point Poisson operator (four
#: streams) at every halo the padded frame holds and no float64 plan.
#: The variant writes p over p_prev in place, and its p output block
#: stays a pipelined VMEM block (block j's rows are fetched once, a step
#: before they are stored over), so the alias adds nothing to this count.
PFOLD_VMEM_BYTES = 16 * 2**20


def pfold_vmem_ok(plan: dict, itemsize: int = 4) -> bool:
    """Whether the direction-fold variant's declared VMEM fits
    `PFOLD_VMEM_BYTES`: the plan's buffers less the plain kernel's two
    window slots, which the variant does not declare, and in their place
    a ring of `PFOLD_RING_SLOTS` blocks each for r and p_prev, the
    combined window, and the double-buffered p output block."""
    BR, H = plan["block_rows"], plan["halo_rows"]
    win = _win_rows(BR, H)
    # in: two rings and the p block's two slots; the combined window in
    # place of the plain kernel's two window slots
    rows = (2 * PFOLD_RING_SLOTS + 2) * BR - win
    return plan.get("vmem", 0) + rows * LANES * itemsize <= PFOLD_VMEM_BYTES


def plan_dia_pallas(
    offsets: Sequence[int],
    no_max: int,
    block_rows: int = DEF_BLOCK_ROWS,
    itemsize: int = 4,
):
    """Static geometry for the kernel: rows after lane tiling, halo rows,
    and the padded owned length. `itemsize` is the operand dtype's byte
    width (f64 doubles every VMEM figure).

    The block is ``block_rows`` (capped at the data's own tiled rows)
    where its buffers fit the 12 MiB VMEM budget, else MIN_BLOCK_ROWS;
    where neither fits, the 8-aligned block from FLOOR_BLOCK_ROWS up
    that fits and streams the fewest padded rows, the larger of two that
    pad alike (a 27-point band at 96^3 takes 384 rows, at 48^3 288: no
    padding). Returns None when no block down to the floor holds the
    band (fall back to the XLA path)."""
    if not offsets:
        return None
    max_off = max(abs(int(o)) for o in offsets)
    halo_rows = -(-max_off // LANES)
    # don't round a small operator up to a full default block: cap the
    # block at the (8-sublane-aligned) tiled row count of the data itself
    tiled_rows = -(-no_max // LANES)
    block_rows = int(min(block_rows, max(8, -(-tiled_rows // 8) * 8)))
    d = len(offsets)

    def vmem_of(br):
        # VMEM budget: vals block (double-buffered) + out (x2) + the
        # window's slots
        return (
            (2 * d + 2) * br + WINDOW_SLOTS * _win_rows(br, halo_rows)
        ) * LANES * itemsize

    def padded_rows(br):
        return -(-tiled_rows // br) * br

    budget = 12 * 2**20
    if vmem_of(block_rows) > budget and block_rows > MIN_BLOCK_ROWS:
        block_rows = MIN_BLOCK_ROWS
    if vmem_of(block_rows) > budget:
        fits = [
            br for br in range(FLOOR_BLOCK_ROWS, block_rows, 8)
            if vmem_of(br) <= budget
        ]
        if not fits:
            return None
        block_rows = min(fits, key=lambda br: (padded_rows(br), -br))
    n_rows = padded_rows(block_rows)
    win_rows = _win_rows(block_rows, halo_rows)
    return {
        "vmem": int(vmem_of(block_rows)),
        "n_rows": int(n_rows),
        "halo_rows": int(halo_rows),
        "block_rows": int(block_rows),
        "padded_len": int(n_rows * LANES),
        # total rows the padded x operand must have (last block's window)
        "x_rows": int(n_rows + win_rows - block_rows),
    }
