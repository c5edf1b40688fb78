"""The solve's vectors are packed and lifted on the device (PR 31): the
host hands over and takes back part values only. Each case runs what the
chip runs (the path does not depend on the platform): the device-packed
frame against the host-packed one, the lifted vector against the host
lift, who owns an answer, which path the counters name, and that a second
solve of the same shapes builds and compiles nothing."""
import importlib

import jax
import jax.monitoring
import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import telemetry

T = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")

# the 10-gid 4-part fixture of tests/test_conformance.py as the reference
# numbers it: parts 1 and 3 do not number their owned lids first
LID_TO_GID = [[0, 1, 2, 4, 6, 7], [1, 3, 4, 9], [5, 6, 7, 4, 3, 9], [0, 2, 6, 8, 9]]
LID_TO_PART = [[0, 0, 0, 1, 2, 2], [0, 1, 1, 3], [2, 2, 2, 1, 1, 3], [0, 0, 2, 3, 3]]

#: case -> (part grid, cells); None: the fixture above, vectors only (the
#: block split of a matrix needs owned-first lids)
CASES = {
    "one_part": ((1, 1, 1), (8, 8, 8)),
    "box_2x2x1": ((2, 2, 1), (16, 16, 8)),
    "ragged_1d": ((3,), (10,)),
    "not_owned_first": None,
}
SOLVED = [c for c, spec in CASES.items() if spec is not None]
COUNTERS = (
    "solve.device_packs", "solve.host_packs",
    "solve.device_lifts", "solve.host_lifts",
)

_systems = {}


def filled(rows, seed):
    """A vector over ``rows`` with another value in every lid, ghosts too."""
    rng = np.random.default_rng(seed)
    return pa.PVector(
        pa.map_parts(
            lambda i: rng.standard_normal(i.num_lids).astype(np.float32),
            rows.partition,
        ),
        rows,
    )


def system_of(case):
    """The case's backend, layout and vectors; with its operator, a
    Jacobi ``minv`` and one warm-up solve of each entry where it has one."""
    if case in _systems:
        return _systems[case]
    spec = CASES[case]
    nparts = 4 if spec is None else int(np.prod(spec[0]))
    backend = pa.TPUBackend(devices=jax.devices()[:nparts])
    out = {"backend": backend}

    def body(parts):
        if spec is None:
            partition = pa.map_parts(
                lambda p: pa.IndexSet(p, LID_TO_GID[p], LID_TO_PART[p]), parts
            )
            rows = pa.PRange(10, partition)
            out.update(rows=rows, layout=T.device_layout(rows))
            return
        A, b, _xe, x0 = pa.assemble_poisson(
            parts, spec[1], dtype=np.float32, decoupled=True
        )
        minv = pa.jacobi_preconditioner(A)
        out.update(
            A=A, b=b, x0=x0, minv=minv, rows=A.cols,
            layout=T.device_matrix(A, backend).col_layout,
        )
        pa.cg(A, b, x0=x0, tol=1e-5)
        pa.cg(A, b, tol=1e-5)
        pa.pcg(A, b, x0=x0, minv=minv, tol=1e-5)

    pa.prun(body, backend, nparts if spec is None else spec[0])
    out["v"] = filled(out["rows"], 31)
    _systems[case] = out
    return out


def grew(before):
    after = telemetry.counters("solve")
    return {k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS}


def values_of(v):
    return [np.asarray(a) for a in v.values.part_values()]


class Compiles:
    """JAX's own compile events, as `benchmark/run.py` counts them."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    events = 0

    @classmethod
    def on(cls, event, _secs, **_kw):
        cls.events += event == cls.EVENT


jax.monitoring.register_event_duration_secs_listener(Compiles.on)


@pytest.mark.parametrize("with_ghosts", [False, True], ids=["b", "x0"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_device_frame_is_the_host_frame(case, with_ghosts):
    s = system_of(case)
    layout, backend = s["layout"], s["backend"]
    before = telemetry.counters("solve")
    got = T._pack(s["v"], layout, backend, with_ghosts=with_ghosts)
    on_device = case != "not_owned_first"
    assert grew(before) == {
        "solve.device_packs": int(on_device), "solve.host_packs": int(not on_device),
        "solve.device_lifts": 0, "solve.host_lifts": 0,
    }
    want = T._pack_on_host(s["v"], layout, backend, with_ghosts)
    assert got.shape == want.shape == (layout.P, layout.W)
    assert got.dtype == want.dtype == np.float32
    assert got.sharding == want.sharding == backend.sharding(layout.P)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # zero wherever no value went: the padding invariant of the layout
    assert np.count_nonzero(np.asarray(got)) == sum(
        i.num_oids + (i.num_hids if with_ghosts else 0)
        for i in s["rows"].partition.part_values()
    )


@pytest.mark.parametrize("case", sorted(CASES))
def test_lifted_vector_is_the_host_lift(case):
    s = system_of(case)
    layout, backend, rows = s["layout"], s["backend"], s["rows"]
    dv = T.DeviceVector.from_pvector(s["v"], backend, layout)
    before = telemetry.counters("solve")
    got = dv.to_pvector()
    on_device = case != "not_owned_first"
    assert grew(before) == {
        "solve.device_packs": 0, "solve.host_packs": 0,
        "solve.device_lifts": int(on_device), "solve.host_lifts": int(not on_device),
    }
    want = T._host_frame_to_pvector(np.asarray(dv.data), rows, layout)
    for g, w, v in zip(values_of(got), values_of(want), values_of(s["v"])):
        assert g.dtype == w.dtype and np.array_equal(g, w)
        assert np.array_equal(g, v)  # and the round trip is the identity
        assert g.flags.writeable and g.flags.owndata
    again = dv.to_pvector()
    for g, a, v in zip(values_of(got), values_of(again), values_of(s["v"])):
        assert not np.shares_memory(g, a) and not np.shares_memory(g, v)


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_second_vector_of_the_same_shapes_builds_nothing(case):
    s = system_of(case)
    layout, backend = s["layout"], s["backend"]
    T.DeviceVector.from_pvector(s["v"], backend, layout).to_pvector()
    programs = dict(layout.programs)
    sizes = {k: fn._cache_size() for k, fn in programs.items()}
    compiled = Compiles.events
    T.DeviceVector.from_pvector(filled(s["rows"], 7), backend, layout).to_pvector()
    assert layout.programs == programs
    assert {k: fn._cache_size() for k, fn in programs.items()} == sizes
    assert Compiles.events == compiled
    if case != "not_owned_first":
        kinds = {k[0] for k in programs}
        assert kinds == {"pack", "lift"}
        # one program per distinct shape of a part, not one per part
        isets = s["rows"].partition.part_values()
        shapes = {(i.num_oids, i.num_hids) for i in isets}
        assert len({k[1:3] for k in programs if k[0] == "lift"}) == len(shapes)
        # and where the parts share a shape, the one program of all parts
        # over the mesh: it compiles once, not once a device
        over_mesh = {k[-1] is not None for k in programs}
        assert over_mesh == {len(shapes) == 1}
        if len(shapes) == 1:
            assert sizes == dict.fromkeys(sizes, 1)


@pytest.mark.parametrize("entry", ["cg", "pcg"])
@pytest.mark.parametrize("case", SOLVED)
def test_a_solve_packs_and_lifts_on_the_device_and_owns_its_answer(case, entry):
    s = system_of(case)
    A, b, x0, minv = s["A"], s["b"], s["x0"], s["minv"]

    def solve():
        if entry == "cg":
            return pa.cg(A, b, x0=x0, tol=1e-5)
        return pa.pcg(A, b, x0=x0, minv=minv, tol=1e-5)

    before = telemetry.counters("solve")
    compiled = Compiles.events
    programs = dict(s["layout"].programs)
    x1, info = solve()
    assert info["converged"]
    assert grew(before) == {
        "solve.device_packs": 2 if entry == "cg" else 3,  # b, x0, minv
        "solve.host_packs": 0, "solve.device_lifts": 1, "solve.host_lifts": 0,
    }
    assert Compiles.events == compiled and s["layout"].programs == programs
    x2, _info = solve()
    inputs = values_of(b) + values_of(x0) + values_of(minv)
    for a1, a2, iset in zip(values_of(x1), values_of(x2), A.cols.partition.part_values()):
        assert a1.shape == (iset.num_lids,) and a1.dtype == np.float32
        assert a1.flags.writeable and a1.flags.owndata
        assert a2.flags.writeable and a2.flags.owndata
        assert np.array_equal(a1, a2) and not np.shares_memory(a1, a2)
        assert not any(np.shares_memory(a1, v) for v in inputs)


@pytest.mark.parametrize("case", SOLVED)
def test_no_start_vector_gives_the_bits_of_a_zero_one(case):
    s = system_of(case)
    A, b = s["A"], s["b"]
    zero = pa.PVector.full(0.0, A.cols, dtype=np.float32)
    before = telemetry.counters("solve")
    x, info = pa.cg(A, b, tol=1e-5)
    assert grew(before)["solve.device_packs"] == 2  # b and the zero frame
    xz, infoz = pa.cg(A, b, x0=zero, tol=1e-5)
    assert info["iterations"] == infoz["iterations"]
    assert np.array_equal(info["residuals"], infoz["residuals"])
    for a, z in zip(values_of(x), values_of(xz)):
        assert np.array_equal(a, z)
    layout = s["layout"]
    frame = T._zero_frame(layout, s["backend"], np.float32)
    want = T._pack(zero, layout, s["backend"])
    assert frame.sharding == want.sharding and frame.dtype == want.dtype
    assert np.array_equal(np.asarray(frame), np.asarray(want))


@pytest.mark.parametrize("with_ghosts", [False, True], ids=["b", "x0"])
@pytest.mark.parametrize("case", ["box_2x2x1", "ragged_1d"])
def test_a_vector_of_other_parts_than_the_layouts_is_refused(case, with_ghosts):
    """The slots are the layout's and the counts the vector's: a vector
    with other owned or ghost counts is refused on the host, before a
    program on the device clamps its way through it."""
    s = system_of(case)
    spec = CASES[case]
    cells = tuple(n + 2 * g for n, g in zip(spec[1], spec[0]))
    other = {}
    pa.prun(
        lambda parts: other.update(rows=pa.prange(parts, cells, pa.with_ghost)),
        s["backend"], spec[0],
    )
    with pytest.raises(AssertionError, match="not the layout's"):
        T._pack(filled(other["rows"], 5), s["layout"], s["backend"], with_ghosts)


def test_parts_follow_the_shardings_own_device_order():
    """A mesh may order its devices otherwise than `devices()` does (the
    chip's (2,2,1) grid is [0, 1, 3, 2]): part p's values go to the device
    that holds row p under the sharding, and the frame says so."""
    devs = jax.devices()[:4]
    backend = pa.TPUBackend(devices=devs)
    backend._meshes[4] = jax.sharding.Mesh(
        np.array([devs[0], devs[1], devs[3], devs[2]]), ("parts",)
    )
    assert backend.part_devices(4) == [devs[0], devs[1], devs[3], devs[2]]

    def body(parts):
        rows = pa.prange(parts, (6, 6), pa.with_ghost)
        v = filled(rows, 3)
        layout = T.device_layout(rows)
        got = T._pack(v, layout, backend)
        want = T._pack_on_host(v, layout, backend, True)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        for shard in got.addressable_shards:
            p = shard.index[0].start
            assert shard.device == backend.part_devices(4)[p]
        back = T.DeviceVector(got, rows, layout, backend).to_pvector()
        for g, w in zip(values_of(back), values_of(v)):
            assert np.array_equal(g, w)
        return True

    assert pa.prun(body, backend, (2, 2))


def test_a_fetched_array_of_its_own_is_handed_on_and_a_view_is_copied():
    """What the chip's runtime fetches is a fresh read-only array that owns
    its data: the caller gets that array, writable. What the CPU backend
    fetches is a view of the device buffer: the caller gets a copy."""
    own = np.arange(5, dtype=np.float32)
    own.flags.writeable = False
    got = T._as_callers_array(own)
    assert got is own and got.flags.writeable and got.flags.owndata
    base = np.arange(8, dtype=np.float32)
    view = base[2:7]
    view.flags.writeable = False
    got = T._as_callers_array(view)
    assert got.flags.writeable and got.flags.owndata
    assert np.array_equal(got, view) and not np.shares_memory(got, base)
