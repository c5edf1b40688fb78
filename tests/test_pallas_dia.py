"""Interpret-mode checks of the Pallas banded-SpMV kernel against the
reference band-sum semantics (the kernel is the real-TPU hot path; CI runs
it via the Pallas interpreter on CPU — tests/conftest.py sets JAX_PLATFORMS
to cpu)."""
import numpy as np
import pytest

from partitionedarrays_jl_tpu.ops.pallas_dia import (
    FLOOR_BLOCK_ROWS,
    LANES,
    _win_rows,
    dia_spmv_pallas,
    plan_dia_pallas,
)


def _band_reference(vals, x, offsets, n):
    """y[i] = sum_d vals[d, i] * x_padded[i + off_d] on the flat form."""
    y = np.zeros(n, dtype=vals.dtype)
    for d, off in enumerate(offsets):
        src = np.arange(n) + off
        ok = (src >= 0) & (src < n)
        y[ok] += vals[d, np.arange(n)[ok]] * x[src[ok]]
    return y


@pytest.mark.parametrize(
    "n,offsets",
    [
        (6 * LANES * 8, (-LANES * 8, -1, 0, 1, LANES * 8)),  # 2-D-ish stencil
        (4 * LANES * 8, (-3, 0, 5)),                          # asymmetric band
        (2 * LANES * 8, (0,)),                                # pure diagonal
        # the x window's two slots (PR 39): one block (only the first
        # fetch), two blocks (one prefetch), an odd count with a ragged
        # tail (both slots used, the last block in slot 0)
        (LANES * 8, (-LANES - 1, -1, 0, 1, LANES + 1)),
        (2 * LANES * 8, (-LANES * 2, -1, 0, 1, LANES * 2)),
        (7 * LANES * 8 - 37, (-LANES * 3 - 5, -1, 0, 1, LANES * 3 + 5)),
        # a halo wider than a block: consecutive windows overlap, so each
        # row of x is fetched into both slots
        (5 * LANES * 8, (-LANES * 20 - 1, -1, 0, 1, LANES * 20 + 1)),
    ],
)
def test_pallas_matches_band_reference(n, offsets):
    rng = np.random.default_rng(7)
    block_rows = 8
    plan = plan_dia_pallas(offsets, n, block_rows=block_rows)
    assert plan is not None
    R, H = plan["n_rows"], plan["halo_rows"]
    assert plan["block_rows"] == block_rows
    assert R == -(-n // (LANES * block_rows)) * block_rows
    vals = np.zeros((len(offsets), plan["padded_len"]), dtype=np.float32)
    vals[:, :n] = rng.standard_normal((len(offsets), n)).astype(np.float32)
    # zero out entries whose shifted read would fall outside [0, n): the
    # framework stores vals=0 there by construction (absent matrix entries)
    for d, off in enumerate(offsets):
        src = np.arange(n) + off
        vals[d, np.arange(n)[(src < 0) | (src >= n)]] = 0.0
    x = rng.standard_normal(n).astype(np.float32)
    xp = np.pad(x, (H * LANES, plan["x_rows"] * LANES - H * LANES - n))

    y = dia_spmv_pallas(
        np.ascontiguousarray(vals.reshape(len(offsets), R, LANES)),
        xp.reshape(-1, LANES),
        offsets,
        R,
        H,
        block_rows,
        interpret=True,
    )
    got = np.asarray(y).reshape(-1)[:n]
    want = _band_reference(vals[:, :n], x, offsets, n)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_plan_rejects_overwide_band():
    assert plan_dia_pallas((-10_000_000, 0, 10_000_000), 1 << 20) is None


def test_plan_geometry():
    plan = plan_dia_pallas((-130, 0, 130), 1000, block_rows=8)
    assert plan["halo_rows"] == 2  # ceil(130/128)
    assert plan["n_rows"] % 8 == 0
    assert plan["padded_len"] == plan["n_rows"] * LANES >= 1000
    # the x operand row count is 8-aligned relative to the block grid: the
    # DMA window (x_rows - n_rows + block_rows) must be a multiple of 8
    assert (plan["x_rows"] - plan["n_rows"] + plan["block_rows"]) % 8 == 0
    # VMEM: 3 value blocks and the output, double-buffered by the grid
    # pipeline, and the x window in both of its slots (8 + 2*2 + 1 rows,
    # rounded up to 16)
    assert plan["vmem"] == ((2 * 3 + 2) * 8 + 2 * 16) * LANES * 4


@pytest.mark.parametrize(
    "n_diagonals,block_rows",
    [(7, 1024), (9, 1024), (10, 512), (20, 512), (21, 432)],
)
def test_plan_block_for_the_band_width(n_diagonals, block_rows):
    """At 192^3 (a 288-row halo) the plan takes 1,024-row blocks where the
    VMEM gate admits them, else 512; a wider band takes the smaller block
    that fits and pads the fewest rows (21 diagonals: 432 rows, 128 whole
    blocks, where 504 would fit but pad)."""
    offsets = tuple(
        int(o) for o in np.linspace(-192 * 192, 192 * 192, n_diagonals)
    )
    plan = plan_dia_pallas(offsets, 192**3)
    assert plan["block_rows"] == block_rows and plan["halo_rows"] == 288
    assert plan["vmem"] <= 12 * 2**20
    assert plan["n_rows"] == 192**3 // LANES  # 54, 108 or 128 whole blocks


def _offsets27(n):
    """The A_oo offsets of a 27-point Galerkin operator on an n^3 box: the
    levels below the 7-point one of a GMG hierarchy."""
    return tuple(sorted(
        k * n * n + j * n + i
        for k in (-1, 0, 1) for j in (-1, 0, 1) for i in (-1, 0, 1)
    ))


@pytest.mark.parametrize(
    "n,block_rows,halo_rows", [(96, 384, 73), (48, 288, 19)],
    ids=["level-1", "level-2"],
)
def test_plan_of_the_galerkin_levels(n, block_rows, halo_rows):
    """Levels 1 and 2 of the hierarchy over a 192^3 box (96^3 and 48^3 a
    part): neither 1,024 nor 512 rows fit the gate with 27 value blocks,
    and the block that does pads nothing: 6,912 rows in 18 blocks of 384,
    864 in 3 of 288 (the largest block that fits, 416, would pad both)."""
    plan = plan_dia_pallas(_offsets27(n), n**3)
    assert plan["block_rows"] == block_rows
    assert plan["halo_rows"] == halo_rows
    assert plan["vmem"] <= 12 * 2**20
    assert plan["n_rows"] == n**3 // LANES
    assert plan["x_rows"] == plan["n_rows"] + _win_rows(block_rows, halo_rows) - block_rows


def test_plan_of_the_7_point_operator_at_192_cubed():
    """The stored-coefficient stencil of `varcoef7_192` keeps its plan:
    1,024-row blocks, a 1,608-row window (its `lowering.stream.block_rows`
    and `.x_window_rows`)."""
    plan = plan_dia_pallas(_poisson7(192), 192**3)
    assert (plan["block_rows"], plan["halo_rows"]) == (1024, 288)
    assert _win_rows(plan["block_rows"], plan["halo_rows"]) == 1608
    assert plan["n_rows"] == 54 * 1024


def test_plan_none_where_no_block_at_the_floor_holds():
    """A 27-point band on an 800^3 box has a 5,008-row halo: its window
    alone overflows the gate at FLOOR_BLOCK_ROWS, so the XLA form."""
    assert plan_dia_pallas(_offsets27(800), 800**3) is None
    assert plan_dia_pallas(_offsets27(740), 740**3)["block_rows"] == FLOOR_BLOCK_ROWS


def _plan_before_the_shrink(offsets, no_max, block_rows=1024, itemsize=4):
    """The rule the plan kept until a band too wide for 512 rows shrank
    its block: the default block (capped at the data), else 512, else
    None."""
    halo = -(-max(abs(int(o)) for o in offsets) // LANES)
    tiled = -(-no_max // LANES)
    br = int(min(block_rows, max(8, -(-tiled // 8) * 8)))
    d = len(offsets)

    def vmem_of(b):
        return ((2 * d + 2) * b + 2 * _win_rows(b, halo)) * LANES * itemsize

    if vmem_of(br) > 12 * 2**20 and br > 512:
        br = 512
    if vmem_of(br) > 12 * 2**20:
        return None
    return br


@pytest.mark.parametrize("itemsize", [4, 8])
def test_plan_keeps_every_block_the_old_rule_admitted(itemsize):
    """Every band the plan admitted before keeps its block; a band it
    refused gets a block under the one it tried, from the floor up, within
    the gate, or stays refused. Arithmetic only, over band widths 1 to 30,
    halos of 0 to 5,008 rows and boxes of 10^3 to 320^3."""
    admitted = shrunk = refused = 0
    for d in range(1, 31):
        for halo in (0, 1, 5, 19, 73, 288, 600, 1500, 5008):
            offsets = tuple(sorted(set(
                int(o) for o in np.linspace(-halo * LANES, halo * LANES, d)
            ))) or (0,)
            for n in (10, 40, 48, 96, 192, 320):
                old = _plan_before_the_shrink(offsets, n**3, itemsize=itemsize)
                plan = plan_dia_pallas(offsets, n**3, itemsize=itemsize)
                if old is not None:
                    admitted += 1
                    assert plan["block_rows"] == old, (d, halo, n)
                elif plan is None:
                    refused += 1
                else:
                    shrunk += 1
                    br = plan["block_rows"]
                    assert FLOOR_BLOCK_ROWS <= br < 512 and br % 8 == 0
                    assert plan["vmem"] <= 12 * 2**20
                    assert plan["n_rows"] % br == 0
    assert admitted and shrunk and refused


def test_pallas_at_a_shrunk_block_matches_band_reference():
    """The kernel at the block the shrunk plan picks for a 27-point band
    on a 40^3 box (500 tiled rows: 504 do not fit, 168 pad to 504, the
    fewest), three blocks with the window in both slots, against the
    reference band sum."""
    n3, offsets = 40**3, _offsets27(40)
    plan = plan_dia_pallas(offsets, n3)
    assert plan["block_rows"] == 168 and plan["n_rows"] == 504
    R, H, BR = plan["n_rows"], plan["halo_rows"], plan["block_rows"]
    rng = np.random.default_rng(11)
    vals = np.zeros((len(offsets), plan["padded_len"]), dtype=np.float32)
    vals[:, :n3] = rng.standard_normal((len(offsets), n3)).astype(np.float32)
    for d, off in enumerate(offsets):
        src = np.arange(n3) + off
        vals[d, np.arange(n3)[(src < 0) | (src >= n3)]] = 0.0
    x = rng.standard_normal(n3).astype(np.float32)
    xp = np.pad(x, (H * LANES, plan["x_rows"] * LANES - H * LANES - n3))
    y = dia_spmv_pallas(
        np.ascontiguousarray(vals.reshape(len(offsets), R, LANES)),
        xp.reshape(-1, LANES), offsets, R, H, BR, interpret=True,
    )
    got = np.asarray(y).reshape(-1)[:n3]
    want = _band_reference(vals[:, :n3], x, offsets, n3)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _poisson7(n):
    """The A_oo offsets of the 7-point Poisson operator on an n^3 block: a
    part of poisson7_192_x4 (192^3 cells a chip) has those of 192^3."""
    return (-n * n, -n, -1, 0, 1, n, n * n)


def _declared(plan, itemsize=4):
    """The fold variant's declared VMEM: the plan's (the codes, the y
    block in two, the plain kernel's window in two slots) without that
    window, a ring of three blocks each for r and p_prev, the combined
    window, the p output block in two."""
    from partitionedarrays_jl_tpu.ops.pallas_dia import _win_rows

    BR = plan["block_rows"]
    win = _win_rows(BR, plan["halo_rows"])
    # out: the plan's two window slots; in: two rings, the combined
    # window, the p block's two slots
    rows = -2 * win + 2 * 3 * BR + win + 2 * BR
    return plan["vmem"] + rows * LANES * itemsize


def _declared_two_windows(plan, itemsize=4):
    """What the fold variant declared while it fetched a window of r and
    one of p_prev for every block: the plan's, a second window in two
    slots, the combined window, the p output block in two."""
    from partitionedarrays_jl_tpu.ops.pallas_dia import _win_rows

    win = _win_rows(plan["block_rows"], plan["halo_rows"])
    extra = (3 * win + 2 * plan["block_rows"]) * LANES * itemsize
    return plan["vmem"] + extra


@pytest.mark.parametrize(
    "n_coded,vmem", [(1, 5_316_608), (4, 6_889_472)]
)
def test_padded_plan_at_192_cubed_is_unchanged(n_coded, vmem):
    """poisson7_192 and a chip of poisson7_192_x4 (four code streams: the
    28 row classes are over the class mode's cap), pure Python: the plan
    and the fold verdict are what they were under the 13 MiB gate."""
    from partitionedarrays_jl_tpu.ops.pallas_dia import (
        PFOLD_VMEM_BYTES,
        plan_dia_padded,
        pfold_vmem_ok,
    )

    plan = plan_dia_padded(_poisson7(192), 192**3, n_coded)
    assert plan == {
        "vmem": vmem, "block_rows": 2048, "halo_rows": 288, "n_blocks": 27,
        "o0": 262_144, "g0": 29 * 262_144, "code_len": 27 * 262_144,
    }
    assert _declared_two_windows(plan) <= 13 * 2**20
    assert _declared(plan) <= PFOLD_VMEM_BYTES
    assert pfold_vmem_ok(plan)


@pytest.mark.parametrize("n_coded", [1, 4])
def test_padded_plan_at_320_cubed_admits_the_fold(n_coded):
    """poisson7_320: an 800-row halo, 125 blocks; the fold variant's
    declared buffers were 13.4 / 14.9 MiB with a window of each operand,
    over the old 13 MiB gate, and are 12.3 / 13.8 MiB with the rings of
    three blocks: within PFOLD_VMEM_BYTES, so the fused body folds in the
    kernel."""
    from partitionedarrays_jl_tpu.ops.pallas_dia import (
        PFOLD_VMEM_BYTES,
        plan_dia_padded,
        pfold_vmem_ok,
    )

    plan = plan_dia_padded(_poisson7(320), 320**3, n_coded)
    assert plan["halo_rows"] == 800 and plan["n_blocks"] == 125
    assert 13 * 2**20 < _declared_two_windows(plan)
    assert _declared(plan) < _declared_two_windows(plan)
    assert _declared(plan) <= PFOLD_VMEM_BYTES
    assert pfold_vmem_ok(plan)


@pytest.mark.parametrize(
    "n,n_coded",
    [(192, 4), (300, 4), (320, 4), (360, 4), (192, 1), (510, 4)],
    ids=["192-cubed", "300-cubed", "320-cubed", "360-cubed",
         "192-cubed-1-stream", "halo-2040-rows"],
)
def test_the_ring_keeps_every_plan_the_windows_admitted(n, n_coded):
    """Every plan the gate admitted while the fold variant fetched a
    window of each operand (the 7-point Poisson operator with four code
    streams up to 360^3, one stream at 192^3) is admitted with the rings
    of three blocks. The widest halo the padded frame holds (2,040 rows,
    four streams: 16.9 MiB with the windows, refused) declares 15.0 MiB
    with the rings and is admitted: compiled for a described v5e it
    builds under VMEM_LIMIT_BYTES, as the 320^3 plan does."""
    from partitionedarrays_jl_tpu.ops.pallas_dia import (
        PFOLD_VMEM_BYTES,
        plan_dia_padded,
        pfold_vmem_ok,
    )

    plan = plan_dia_padded(_poisson7(n), n**3, n_coded)
    assert (_declared_two_windows(plan) <= PFOLD_VMEM_BYTES) == (n != 510)
    assert _declared(plan) <= PFOLD_VMEM_BYTES
    assert pfold_vmem_ok(plan)


@pytest.mark.parametrize(
    "n,n_coded,itemsize",
    [(510, 8, 4), (8, 4, 8)],
    ids=["halo-2040-rows-8-streams", "float64"],
)
def test_a_fold_over_the_budget_falls_back(n, n_coded, itemsize):
    """A band the plain kernel takes but whose fold variant declares more
    than PFOLD_VMEM_BYTES (the widest halo the padded frame holds with
    eight code streams, or float64's doubled rings) keeps the jnp fold.
    With four streams that halo is admitted since the fold variant reads
    its operands through rings of blocks
    (`test_the_ring_keeps_every_plan_the_windows_admitted`)."""
    from partitionedarrays_jl_tpu.ops.pallas_dia import (
        PFOLD_VMEM_BYTES,
        plan_dia_padded,
        pfold_vmem_ok,
    )

    plan = plan_dia_padded(_poisson7(n), n**3, n_coded, itemsize=itemsize)
    assert plan is not None
    assert _declared(plan, itemsize) > PFOLD_VMEM_BYTES
    assert not pfold_vmem_ok(plan, itemsize=itemsize)


def test_padded_kernel_matches_band_reference():
    """Direct check of the padded-frame coded kernel (the real-TPU hot
    path) via the Pallas interpreter: full padded vector in, full padded
    vector out, non-owned slots exactly zero."""
    from partitionedarrays_jl_tpu.ops.pallas_dia import (
        PAD_BLOCK_ROWS,
        dia_coded_padded_pallas,
        plan_dia_padded,
    )

    rng = np.random.default_rng(11)
    offsets = (-LANES * 16, -1, 0, 1, LANES * 16)
    kk = (1, 3, 2, 3, 1)  # two constant diagonals, three coded
    code_row = (-1, 0, 1, 2, -1)
    BRL = PAD_BLOCK_ROWS * LANES
    no = BRL + 7 * LANES + 13  # two owned blocks, ragged tail
    plan = plan_dia_padded(offsets, no, n_coded=2)
    assert plan is not None
    nB, o0, g0 = plan["n_blocks"], plan["o0"], plan["g0"]
    assert nB == 2 and o0 == BRL and g0 == 4 * BRL
    D, Dc, kmax = len(offsets), 3, 3
    cb = rng.standard_normal((D, kmax)).astype(np.float32)
    codes = np.zeros((Dc, plan["code_len"]), dtype=np.uint8)
    for d in range(D):
        if kk[d] > 1:
            codes[code_row[d], :no] = rng.integers(0, kk[d], no)
    from partitionedarrays_jl_tpu.ops.pallas_dia import pack_nibble_codes

    packed = pack_nibble_codes(codes)
    Dp = packed.shape[0]
    total = 5 * PAD_BLOCK_ROWS  # one block for ghosts + trash
    x = np.zeros(total * LANES, dtype=np.float32)
    x[o0 : o0 + no] = rng.standard_normal(no).astype(np.float32)
    x[g0 : g0 + 40] = rng.standard_normal(40).astype(np.float32)  # ghosts

    y = dia_coded_padded_pallas(
        cb,
        np.array([no], dtype=np.int32),
        packed.reshape(Dp, -1, LANES),
        x.reshape(-1, LANES),
        offsets,
        kk,
        code_row,
        plan,
        total,
        interpret=True,
    )
    got = np.asarray(y).reshape(-1)
    vals = np.empty((D, no), dtype=np.float32)
    for d in range(D):
        if kk[d] == 1:
            vals[d] = cb[d, 0]
        else:
            vals[d] = cb[d, codes[code_row[d], :no].astype(int)]
    want = _band_reference(vals, x[o0 : o0 + no], offsets, no)
    np.testing.assert_allclose(got[o0 : o0 + no], want, rtol=1e-6, atol=1e-6)
    # every slot outside the owned band — including where the ghosts were —
    # must come back exactly zero
    rest = got.copy()
    rest[o0 : o0 + no] = 0
    assert not rest.any()


def test_padded_kernel_class_accumulator_path():
    """Row-class fast path: K per-class accumulators + ONE select must
    reproduce the per-diagonal-select path bit-for-bit on rows whose
    class coefficients are dense, and match the band reference even with
    zero-skipped coefficients (the skipped terms are the host kernel's
    absent entries)."""
    from partitionedarrays_jl_tpu.ops.pallas_dia import (
        PAD_BLOCK_ROWS,
        dia_coded_padded_pallas,
        pack_nibble_codes,
        plan_dia_padded,
    )

    rng = np.random.default_rng(5)
    offsets = (-LANES * 4, -1, 0, 1, LANES * 4)
    D, K = len(offsets), 2
    kk = (K,) * D
    code_row = (0,) * D
    BRL = PAD_BLOCK_ROWS * LANES
    no = BRL + 3 * LANES + 9
    plan = plan_dia_padded(offsets, no, n_coded=1)
    assert plan is not None
    o0, g0 = plan["o0"], plan["g0"]
    # class 0: dense interior stencil; class 1: diagonal-only (Dirichlet)
    cb = np.zeros((D, K), dtype=np.float32)
    cb[:, 0] = rng.standard_normal(D).astype(np.float32)
    cb[2, 1] = 1.0
    cls_pattern = tuple(
        tuple(bool(cb[d, k] != 0) for d in range(D)) for k in range(K)
    )
    codes = np.zeros((1, plan["code_len"]), dtype=np.uint8)
    codes[0, :no] = rng.integers(0, K, no)
    packed = pack_nibble_codes(codes)
    total = plan["n_blocks"] + 3
    x = np.zeros(total * BRL, dtype=np.float32)
    x[o0 : o0 + no] = rng.standard_normal(no).astype(np.float32)

    args = (
        cb,
        np.array([no], dtype=np.int32),
        packed.reshape(packed.shape[0], -1, LANES),
        x.reshape(-1, LANES),
        offsets,
        kk,
        code_row,
        plan,
        total * PAD_BLOCK_ROWS,
    )
    y_fast = np.asarray(
        dia_coded_padded_pallas(*args, interpret=True, cls_pattern=cls_pattern)
    ).reshape(-1)
    y_sel = np.asarray(
        dia_coded_padded_pallas(*args, interpret=True)
    ).reshape(-1)
    # vs the select path: same per-row term sequence (minus exact-zero
    # skipped terms), so agreement holds to FMA-contraction rounding —
    # XLA may fuse the mul+add chains differently between the two
    # lowerings, which moves individual terms by an ulp
    np.testing.assert_allclose(y_fast, y_sel, rtol=5e-7, atol=5e-7)
    # rows of the diagonal-only class take exactly one product — both
    # paths must agree bitwise there (no accumulation to contract)
    cls1 = np.zeros_like(y_fast, dtype=bool)
    cls1[o0 : o0 + no] = codes[0, :no] == 1
    np.testing.assert_array_equal(y_fast[cls1], y_sel[cls1])
    # vs the band reference with decoded per-element values
    vals = cb[np.arange(D)[:, None], codes[0, :no][None, :].astype(int)]
    want = _band_reference(vals.astype(np.float32), x[o0 : o0 + no], offsets, no)
    np.testing.assert_allclose(y_fast[o0 : o0 + no], want, rtol=1e-6, atol=1e-6)
    rest = y_fast.copy()
    rest[o0 : o0 + no] = 0
    assert not rest.any()


def _fold_call(halo_lanes, n_blocks_owned, k=None, seed=3):
    """The fold variant's arguments on random operands: a band of
    ``halo_lanes`` lane rows either side, ``n_blocks_owned`` owned blocks
    with a ragged tail, two coded diagonals; ``k`` vectors on the
    leading grid axis (the columns form) or one."""
    from partitionedarrays_jl_tpu.ops.pallas_dia import (
        PAD_BLOCK_ROWS,
        pack_nibble_codes,
        plan_dia_padded,
    )

    rng = np.random.default_rng(seed)
    offsets = (-LANES * halo_lanes, -1, 0, 1, LANES * halo_lanes)
    kk, code_row = (1, 3, 2, 3, 1), (-1, 0, 1, 0, -1)
    BRL = PAD_BLOCK_ROWS * LANES
    no = (n_blocks_owned - 1) * BRL + 5 * LANES + 29
    plan = plan_dia_padded(offsets, no, n_coded=2)
    o0 = plan["o0"]
    codes = np.zeros((2, plan["code_len"]), dtype=np.uint8)
    codes[0, :no] = rng.integers(0, 3, no)
    codes[1, :no] = rng.integers(0, 2, no)
    packed = pack_nibble_codes(codes)
    total = (plan["n_blocks"] + 3) * PAD_BLOCK_ROWS
    lead = () if k is None else (k,)

    def frame():
        f = np.zeros(lead + (total * LANES,), dtype=np.float32)
        f[..., o0 : o0 + no] = rng.standard_normal(lead + (no,))
        return f.reshape(lead + (total, LANES))

    r, pprev = frame(), frame()
    beta = rng.standard_normal(k or 1).astype(np.float32)
    args = (
        rng.standard_normal((5, 3)).astype(np.float32),
        np.array([no], dtype=np.int32),
        packed.reshape(packed.shape[0], -1, LANES), r, offsets, kk,
        code_row, plan, total,
    )
    return plan, args, (pprev, beta)


@pytest.mark.parametrize(
    "halo_lanes,n_blocks,k",
    [(800, 3, None), (800, 3, 3), (16, 2, 3), (16, 1, None), (16, 1, 3),
     (16, 2, None), (2040, 3, None), (2040, 2, 3)],
    ids=["320-cubed-halo", "320-cubed-halo-3-columns", "3-columns",
         "one-block", "one-block-3-columns", "two-blocks", "widest-halo",
         "widest-halo-3-columns"],
)
def test_fold_in_place_gives_the_bits_of_its_own_buffer(halo_lanes, n_blocks, k):
    """The fold kernel, which writes p over p_prev, on random operands:
    its ``y`` holds the bits the plain kernel gives on the p it returned,
    held in a buffer of its own, and that p is ``r + beta p_prev`` on the
    owned band and exactly zero in every other slot; one vector and K = 3
    columns on the leading grid axis. The kernel reads each block of r
    and p_prev once through a ring of three, so one, two and three owned
    blocks walk the ring's start, its turn and its end, and the widest
    halo a plan allows (``PAD_BLOCK_ROWS - 8`` rows) takes all of block
    j-1 but eight rows and all of block j+1 into a window.
    Interpret mode runs a DMA at its start and cannot show a race: the
    store ordering itself is checked on the chip, against the kernel of
    a checkout that fetched a window a block
    (`benchmark/tests/pfold_inplace_bits.py --parent`)."""
    from partitionedarrays_jl_tpu.ops import pallas_dia

    plan, args, pfold = _fold_call(halo_lanes, n_blocks, k)
    assert plan["n_blocks"] == n_blocks and plan["halo_rows"] == halo_lanes
    y_in, p_in = pallas_dia.dia_coded_padded_pallas(
        *args, interpret=True, pfold=pfold
    )
    p_own = np.array(p_in)
    y_own = pallas_dia.dia_coded_padded_pallas(
        *args[:3], p_own, *args[4:], interpret=True
    )
    np.testing.assert_array_equal(np.asarray(y_in), np.asarray(y_own))
    r, (pprev, beta) = args[3], pfold
    bk = beta.reshape((-1,) + (1,) * (r.ndim - 1))
    flat = np.arange(r.shape[-2] * LANES).reshape(r.shape[-2:])
    o0, no = plan["o0"], int(args[1][0])
    band = np.broadcast_to((flat >= o0) & (flat < o0 + no), r.shape)
    assert not p_own[~band].any()
    np.testing.assert_allclose(
        p_own[band], (r + bk * pprev)[band], rtol=1e-6, atol=1e-6
    )
    assert np.abs(np.asarray(y_in)).max() > 1.0


@pytest.mark.parametrize(
    "halo_over_block", [False, True], ids=["halo-under-block", "halo-over-block"]
)
def test_fold_kernel_declares_p_prev_as_its_p(halo_over_block):
    """The fold variant's `pallas_call` declares p_prev (its fifth
    operand) aliased to p (its second result). A plan forced to a halo
    wider than its block is refused: block j+2's window would read rows
    block j had already stored over p_prev. `plan_dia_padded` makes no
    such plan (its halo stays under `PAD_BLOCK_ROWS`)."""
    import jax

    from partitionedarrays_jl_tpu.ops import pallas_dia

    plan, args, (pprev, beta) = _fold_call(16, 2)
    if halo_over_block:
        args = args[:7] + (dict(plan, block_rows=8, halo_rows=16),) + args[8:]

    def trace():
        return jax.make_jaxpr(
            lambda x, pp: pallas_dia.dia_coded_padded_pallas(
                *args[:3], x, *args[4:], interpret=True, pfold=(pp, beta)
            )
        )(args[3], pprev).jaxpr

    if halo_over_block:
        with pytest.raises(AssertionError, match="in place"):
            trace()
        return
    (call,) = [e for e in trace().eqns if e.primitive.name == "pallas_call"]
    assert tuple(call.params["input_output_aliases"]) == ((4, 1),)
