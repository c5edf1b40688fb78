"""Native planning accelerator: equivalence with the NumPy fallback and
graceful degradation when disabled."""
import os
import shutil

import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import native

# these tests compare the native kernels against the fallback, so they
# need the native layer; under PA_TPU_NATIVE=0 the rest of the suite IS
# the fallback coverage
pytestmark = pytest.mark.skipif(
    os.environ.get("PA_TPU_NATIVE") == "0",
    reason="native layer disabled via PA_TPU_NATIVE=0",
)


def _with_native(enabled):
    """Temporarily force the native layer on/off (restores in fixture)."""
    saved = (native._lib, native._tried)
    if not enabled:
        native._lib, native._tried = None, True
    return saved


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++ toolchain")
def test_native_builds_and_loads():
    assert native.available(), "g++ toolchain present: native layer must build"


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++ toolchain")
def test_binary_is_keyed_by_source_hash(tmp_path, monkeypatch):
    """The loaded binary is a function of `planning.cpp`'s bytes: a
    changed source forces a rebuild under a new name, and a binary left
    under the old name — even a NEWER file, the case file times get
    wrong after a tree copy — is never loaded."""
    old_so = native._so_path()
    src = tmp_path / "planning.cpp"
    shutil.copy(native._SRC, src)
    with open(src, "a") as f:
        f.write("\n// a different revision of the source\n")
    build = tmp_path / "build"
    build.mkdir()
    # stale binaries: the previous revision's hashed name and the
    # pre-hash fixed name, both unloadable on purpose
    stale = [build / os.path.basename(old_so), build / "libpa_planning.so"]
    for s in stale:
        s.write_bytes(b"not a shared object")
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_BUILD_DIR", str(build))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    new_so = native._so_path()
    assert os.path.basename(new_so) != os.path.basename(old_so)
    assert native.available()
    assert native._lib._name == new_so and os.path.exists(new_so)
    assert not any(s.exists() for s in stale), "stale binaries linger"


def test_failed_build_degrades_loudly(tmp_path, monkeypatch):
    """A compiler failure falls back to NumPy and says so, quoting the
    compiler, instead of planning 10x slower in silence."""
    src = tmp_path / "planning.cpp"
    src.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path / "build"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    if shutil.which("g++") is None:
        match = "native planning library unavailable"
    else:
        match = r"(?s)native planning library unavailable.*g\+\+ exited.*error"
    with pytest.warns(RuntimeWarning, match=match):
        assert not native.available()
    out = np.full(3, -1, dtype=np.int32)
    assert not native.box_gids_to_lids(
        np.arange(3), (4,), (0,), (4,), out
    )


def test_box_gids_to_lids_matches_fallback():
    rng = np.random.default_rng(0)
    grid, lo, hi = (13, 9, 17), (3, 0, 5), (11, 4, 16)
    gids = rng.integers(-5, 13 * 9 * 17 + 5, size=4000)
    out_native = np.full(len(gids), -1, dtype=np.int32)
    assert native.box_gids_to_lids(gids, grid, lo, hi, out_native)
    # NumPy oracle
    coords = np.unravel_index(np.clip(gids, 0, 13 * 9 * 17 - 1), grid)
    owned = (gids >= 0) & (gids < 13 * 9 * 17)
    local = []
    for c, l, h in zip(coords, lo, hi):
        owned &= (c >= l) & (c < h)
        local.append(np.clip(c - l, 0, None))
    expect = np.full(len(gids), -1, dtype=np.int32)
    expect[owned] = np.ravel_multi_index(
        [x[owned] for x in local], tuple(h - l for l, h in zip(lo, hi))
    )
    np.testing.assert_array_equal(out_native, expect)


def test_cartesian_lookup_same_with_and_without_native():
    def run():
        def driver(parts):
            rows = pa.cartesian_partition(parts, (7, 6), pa.with_ghost)
            iset = rows.partition.get_part(2)
            q = np.arange(-2, 44)
            return iset.gids_to_lids(q).copy()

        return pa.prun(driver, pa.sequential, (2, 2))

    with_native = run()
    saved = _with_native(False)
    try:
        without = run()
    finally:
        native._lib, native._tried = saved
    np.testing.assert_array_equal(with_native, without)


def test_coo_to_csr_matches_numpy_path():
    from partitionedarrays_jl_tpu.ops.sparse import compresscoo

    rng = np.random.default_rng(7)
    m, n, nnz = 50, 40, 3000  # heavy duplicates and one long row
    I = rng.integers(0, m, size=nnz)
    I[:200] = 7  # a >64-entry row to hit the comparison-sort path
    J = rng.integers(0, n, size=nnz)
    V = rng.standard_normal(nnz)
    A_nat = compresscoo(I, J, V, m, n)
    saved = _with_native(False)
    try:
        A_np = compresscoo(I, J, V, m, n)
    finally:
        native._lib, native._tried = saved
    np.testing.assert_array_equal(A_nat.indptr, A_np.indptr)
    np.testing.assert_array_equal(A_nat.indices, A_np.indices)
    # duplicate groups: native sums strictly left-to-right in original
    # order (the well-defined contract, matching Julia's sparse()); the
    # NumPy fallback's reduceat uses SIMD partial sums and may differ by
    # rounding. Bit-check native against an explicit L2R oracle instead.
    np.testing.assert_allclose(A_nat.data, A_np.data, rtol=1e-13, atol=1e-15)
    for k in range(0, len(A_nat.data), 97):
        r = np.searchsorted(A_nat.indptr, k, side="right") - 1
        c = A_nat.indices[k]
        sel = (I == r) & (J == c)
        acc = None  # strict left-to-right fold (np.add.reduce is pairwise)
        for v in V[sel]:
            acc = v if acc is None else acc + v
        assert A_nat.data[k] == acc


def test_csr_split_matches_csr_block():
    from partitionedarrays_jl_tpu.ops.sparse import compresscoo, csr_block

    rng = np.random.default_rng(8)
    m, n, nnz, thr = 60, 50, 900, 33
    A = compresscoo(
        rng.integers(0, m, nnz), rng.integers(0, n, nnz),
        rng.standard_normal(nnz), m, n,
    )
    halves = native.csr_split_by_col(A.indptr, A.indices, A.data, m, thr)
    assert halves is not None
    (ipo, co, vo), (iph, ch, vh) = halves
    rows_all = np.arange(m)
    lo = csr_block(A, rows_all, thr, want_upper=False)
    hi = csr_block(A, rows_all, thr, want_upper=True, col_offset=thr)
    np.testing.assert_array_equal(ipo, lo.indptr)
    np.testing.assert_array_equal(co, lo.indices)
    np.testing.assert_array_equal(vo, lo.data)
    np.testing.assert_array_equal(iph, hi.indptr)
    np.testing.assert_array_equal(ch, hi.indices)
    np.testing.assert_array_equal(vh, hi.data)


def test_unique_small_matches_numpy():
    rng = np.random.default_rng(11)
    few = rng.choice([1.5, -2.25, 0.0, 7.125], size=5000)
    u, ok = native.unique_small(few, 8)
    assert ok
    np.testing.assert_array_equal(u, np.unique(few))
    many, ok2 = native.unique_small(rng.standard_normal(100), 8)
    assert not ok2
    u0, ok0 = native.unique_small(np.empty(0), 8)
    assert ok0 and len(u0) == 0


def test_row_classes_matches_numpy_fallback():
    rng = np.random.default_rng(12)
    D, stride, n = 5, 9000, 8123  # n < stride exercises the strided read
    base = rng.standard_normal((4, D))  # 4 classes
    ids = rng.integers(0, 4, size=stride)
    dia = base[ids].T.copy()
    table, codes, ok = native.row_classes(dia, n, 8)
    assert ok
    saved = _with_native(False)
    try:
        t_np, c_np, ok_np = native.row_classes(dia, n, 8)
    finally:
        native._lib, native._tried = saved
    assert ok_np
    # class ORDER may differ (first-touch vs lexicographic); the decoded
    # per-row tuples must be identical
    np.testing.assert_array_equal(table[codes], t_np[c_np])
    # overflow: > K classes
    _, _, ok_over = native.row_classes(rng.standard_normal((3, 64)), 64, 8)
    assert not ok_over


def test_ic0_native_matches_fallback_and_is_exact_when_full():
    """IC(0): native kernel vs the pure-NumPy fallback, and exactness on
    a tridiagonal SPD matrix (full lower pattern -> IC(0) IS Cholesky)."""
    import scipy.sparse as sp

    n = 64
    rng = np.random.default_rng(7)
    d = 2.0 + rng.random(n)
    A = sp.diags([-np.ones(n - 1), d, -np.ones(n - 1)], [-1, 0, 1]).tocsr()
    low = sp.tril(A).tocsr()
    low.sort_indices()
    lv, fail = native.ic0(low.indptr, low.indices, low.data, n)
    assert fail == -1
    saved = _with_native(False)
    try:
        lv_np, fail_np = native.ic0(low.indptr, low.indices, low.data, n)
    finally:
        native._lib, native._tried = saved
    assert fail_np == -1
    np.testing.assert_allclose(lv, lv_np, rtol=1e-14)
    L = sp.csr_matrix((lv, low.indices, low.indptr), shape=(n, n))
    np.testing.assert_allclose((L @ L.T).toarray(), A.toarray(), atol=1e-12)
    # breakdown reporting: an indefinite diagonal fails at its row
    bad = sp.diags([np.where(np.arange(n) == 5, -1.0, 1.0)], [0]).tocsr()
    lv_b, fail_b = native.ic0(bad.indptr, bad.indices, bad.data, n)
    assert lv_b is None and fail_b == 5
