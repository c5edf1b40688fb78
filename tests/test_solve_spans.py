"""Owners for a device solve's host time and device ops (PR 26): the phase
spans of `_run_krylov` land in `info.record.timings`, the `solve.*`
counters count what a solve stages, the compiled programs carry the `pa.`
named scopes in their ops' `op_name` metadata, the Pallas kernels carry
their names, and a scope changes no op."""
import contextlib
import importlib
import re

import jax
import numpy as np
import pytest

import partitionedarrays_jl_tpu as pa
from partitionedarrays_jl_tpu import telemetry

T = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
G = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu_gmg")

PHASES = ("stage", "solve", "wait", "fetch", "finish")
LEAVES = ("operator", "pack", "put", "d2h", "lift")
NS, GRID = (32, 32, 16), (2, 2, 1)


@pytest.fixture(scope="module")
def system():
    """7-point Poisson on four parts of the host mesh, float32 as on the
    chip, with its GMG hierarchy, and one warm-up solve of each entry (so
    the operator and the programs are staged)."""
    backend = pa.TPUBackend(devices=jax.devices()[:4])
    out = {}

    def body(parts):
        A, b, _xe, x0 = pa.assemble_poisson(
            parts, NS, dtype=np.float32, decoupled=True
        )
        h = pa.gmg_hierarchy(parts, A, NS)
        out.update(A=A, b=b, x0=x0, h=h, backend=backend)
        pa.cg(A, b, x0=x0, tol=1e-5)
        pa.pcg(A, b, x0=x0, minv=h, tol=1e-5)

    pa.prun(body, backend, GRID)
    return out


def solve(system, which):
    A, b, x0 = system["A"], system["b"], system["x0"]
    if which == "cg":
        return pa.cg(A, b, x0=x0, tol=1e-5)
    return pa.pcg(A, b, x0=x0, minv=system["h"], tol=1e-5)


def record_of(info, which):
    """`tpu_cg` hands its record out on the `InfoDict`; the GMG entry
    returns the plain info dict and leaves its record in the history ring
    (`_run_gmg` says why)."""
    if which == "cg":
        return info.record
    assert not hasattr(info, "record")
    return telemetry.last_record("pcg+gmg")


@pytest.mark.parametrize("which", ["cg", "gmg_pcg"])
def test_record_timings_hold_every_phase(system, which):
    before = telemetry.last_record()
    _x, info = solve(system, which)
    rec = record_of(info, which)
    assert rec is not before and rec is telemetry.last_record()
    assert rec.solver == {"cg": "cg", "gmg_pcg": "pcg+gmg"}[which]
    assert info["converged"] and rec.converged and rec.seq > 0
    assert rec.iterations == info["iterations"]
    for key in PHASES + LEAVES:
        assert rec.timings.get(key, -1.0) >= 0.0, (key, rec.timings)
    assert sum(rec.timings[k] for k in PHASES) <= rec.wall_s
    # the leaves lie inside their phase
    assert sum(rec.timings[k] for k in LEAVES[:3]) <= rec.timings["stage"]
    assert rec.timings["d2h"] + rec.timings["lift"] <= rec.timings["fetch"]
    assert rec.as_dict()["timings"] == rec.timings
    assert rec.as_dict()["seq"] == rec.seq


@pytest.mark.parametrize("which", ["cg", "gmg_pcg"])
def test_solve_counters_grow_by_one_call_and_its_frames(system, which):
    layout = T.device_matrix(system["A"], system["backend"]).col_layout
    frame = layout.P * layout.W * 4  # float32
    before = telemetry.counters("solve")
    solve(system, which)
    after = telemetry.counters("solve")
    grew = {k: after[k] - before.get(k, 0) for k in after}
    assert grew["solve.calls"] == 1
    # the device frames of b and x0, however they were made
    assert grew["solve.staged_bytes"] == 2 * frame
    # what crossed to the host: the parts' values and the scalars (the
    # residual history among them), no longer a whole frame
    parts = 4 * sum(
        i.num_lids for i in system["A"].cols.partition.part_values()
    )
    assert grew["solve.fetched_bytes"] >= parts
    # the path each vector took: packed and lifted on the devices
    assert grew["solve.device_packs"] == 2 and grew["solve.host_packs"] == 0
    assert grew["solve.device_lifts"] == 1 and grew["solve.host_lifts"] == 0
    # the names that GREW, against the seven a solo solve may move:
    # whatever ran earlier in the process (a block solve's
    # `solve.block_lane_major`, say) exists and stands still
    assert {k for k, v in grew.items() if v} <= {
        "solve.calls", "solve.staged_bytes", "solve.fetched_bytes",
        "solve.device_packs", "solve.host_packs",
        "solve.device_lifts", "solve.host_lifts",
    }


def test_timings_are_off_with_the_record(system, monkeypatch):
    monkeypatch.setenv("PA_METRICS", "0")
    _x, info = solve(system, "cg")
    assert info.record.timings == {} and not info.record.enabled


def test_block_solve_takes_the_same_spans(system):
    A, b, x0 = system["A"], system["b"], system["x0"]
    before = telemetry.counters("solve")
    _xs, info = pa.cg(A, B=[b, b], X0=[x0, x0], tol=1e-5)
    for key in PHASES + LEAVES:
        assert key in info.record.timings
    after = telemetry.counters("solve")
    assert after["solve.calls"] - before["solve.calls"] == 1
    layout = T.device_matrix(A, system["backend"]).col_layout
    assert (
        after["solve.staged_bytes"] - before["solve.staged_bytes"]
        == 2 * 2 * layout.P * layout.W * 4
    )


# -- named scopes in the compiled programs ------------------------------------


def scopes_in(hlo_text: str) -> set:
    """Every `/`-joined run of `pa.` components of an `op_name` in a
    compiled program's text."""
    out = set()
    for m in re.finditer(r'op_name="([^"]+)"', hlo_text):
        comps = [c for c in m.group(1).split("/") if c.startswith("pa.")]
        if comps:
            out.add("/".join(comps))
    return out


def cg_program(system, **kwargs):
    dA = T.device_matrix(system["A"], system["backend"])
    fn = T.make_cg_fn(dA, 1e-5, 50, **kwargs)
    L = dA.col_plan.layout
    z = np.zeros((L.P, L.W), dtype=np.float32)
    return fn.jit_fn.lower(z, z, z, T._matrix_operands(dA))


@pytest.mark.parametrize("fused", [False, True], ids=["standard", "fused"])
def test_cg_program_carries_every_phase_scope(system, fused):
    got = scopes_in(cg_program(system, fused=fused).compile().as_text())
    inside_loop = {
        "pa.axpy_sweep", "pa.axpy_sweep/pa.spmv_local",
        "pa.axpy_sweep/pa.spmv_local/pa.halo_exchange",
        "pa.axpy_sweep/pa.dot_allgather",
    }
    assert inside_loop <= got, sorted(got)
    # before the loop: the first residual's SpMV and the first dots
    assert {"pa.spmv_local", "pa.dot_allgather"} <= got
    if fused:  # the direction fold inside the SpMV body is an update
        assert "pa.axpy_sweep/pa.spmv_local/pa.axpy_sweep" in got
    innermost = {s.rsplit("/", 1)[-1] for s in got}
    assert innermost == {
        T.SCOPE_SPMV, T.SCOPE_HALO, T.SCOPE_DOTS, T.SCOPE_AXPY,
    }


def test_box_exchange_program_is_scoped(system):
    from partitionedarrays_jl_tpu.parallel.tpu_box import BoxExchangePlan

    A, backend = system["A"], system["backend"]
    plan = T.device_matrix(A, backend).col_plan
    assert isinstance(plan, BoxExchangePlan) and plan.layout.P == 4
    x = T.DeviceVector.from_pvector(system["x0"], backend, plan.layout).data
    traced = jax.jit(T.make_exchange_fn(A.cols, backend)).lower(x)
    text = traced.compile().as_text()
    assert scopes_in(text) == {"pa.halo_exchange"}
    permutes = [
        line for line in text.splitlines()
        if re.search(r"\bcollective-permute(-start)?\(", line)
    ]
    assert permutes and all("pa.halo_exchange" in p for p in permutes)


def test_gmg_pcg_program_carries_level_and_phase_scopes(system):
    h, backend = system["h"], system["backend"]
    dh = G._device_hierarchy(h, backend)
    assert len(dh["levels"]) >= 2
    ops = G._gmg_operands(dh)
    fn = G.make_gmg_pcg_fn(h, backend, 1e-5, 20)
    L = dh["levels"][0]["dA"].col_plan.layout
    z = np.zeros((L.P, L.W), dtype=np.float32)
    text = fn.jit_fn.lower(z, z, dh["cinv"], ops).compile().as_text()
    got = scopes_in(text)
    last = len(dh["levels"]) - 1
    chain = "/".join(f"pa.gmg.l{k}" for k in range(last + 1))

    def under(prefix, phase):
        return any(s.startswith(prefix) and phase in s.split("/") for s in got)

    # level 0 holds every phase but the coarse solve, which the last has
    for phase in ("pa.gmg.smooth", "pa.gmg.restrict", "pa.gmg.prolong"):
        assert under("pa.axpy_sweep/pa.gmg.l0/", phase), (phase, sorted(got))
    assert any(
        s.endswith("pa.gmg.coarse") and f"pa.gmg.l{last}" in s for s in got
    ), sorted(got)
    # the levels nest along the recursion, under the Krylov loop
    assert any(chain in s for s in got), (chain, sorted(got))
    assert any(s.endswith("pa.gmg.smooth/pa.spmv_local") for s in got)
    assert "pa.axpy_sweep/pa.dot_allgather" in got


def test_a_scope_changes_no_op(system, monkeypatch):
    """Lowered StableHLO without debug info is byte-identical with
    `jax.named_scope` made a no-op: the scopes are metadata only."""
    with_scopes = {
        fused: cg_program(system, fused=fused).as_text() for fused in (False, True)
    }
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    for fused, text in with_scopes.items():
        assert "pa." not in text
        assert cg_program(system, fused=fused).as_text() == text


# -- the Pallas kernels' names ------------------------------------------------


def pallas_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                out += pallas_names(inner)
    return out


def coded_kernel_args():
    from partitionedarrays_jl_tpu.ops import pallas_dia as P

    offsets = (-P.LANES * 16, -1, 0, 1, P.LANES * 16)
    kk, code_row = (1, 3, 2, 3, 1), (-1, 0, 1, 2, -1)
    no = P.PAD_BLOCK_ROWS * P.LANES + 7 * P.LANES + 13
    plan = P.plan_dia_padded(offsets, no, n_coded=2)
    packed = P.pack_nibble_codes(np.zeros((3, plan["code_len"]), np.uint8))
    total = 5 * P.PAD_BLOCK_ROWS
    x = np.zeros((total, P.LANES), np.float32)
    return x, (
        np.zeros((5, 3), np.float32), np.array([no], np.int32),
        packed.reshape(packed.shape[0], -1, P.LANES), x, offsets, kk,
        code_row, plan, total,
    )


@pytest.mark.parametrize("variant,name", [
    ("plain", "pa_dia_coded_spmv"),
    ("pfold", "pa_dia_coded_spmv_pfold"),
    ("stream", "pa_dia_stream_spmv"),
])
def test_each_pallas_call_has_its_name(variant, name):
    from partitionedarrays_jl_tpu.ops import pallas_dia as P

    if variant == "stream":
        offsets, n, block_rows = (-3, 0, 5), 4 * P.LANES * 8, 8
        plan = P.plan_dia_pallas(offsets, n, block_rows=block_rows)
        vals = np.zeros((3, plan["n_rows"], P.LANES), np.float32)
        x = np.zeros((plan["x_rows"], P.LANES), np.float32)
        call = lambda: P.dia_spmv_pallas(
            vals, x, offsets, plan["n_rows"], plan["halo_rows"], block_rows,
            interpret=True,
        )
    else:
        x, args = coded_kernel_args()
        one = np.zeros(1, np.float32)
        kw = {"plain": {}, "pfold": {"pfold": (x, one)}}[variant]
        call = lambda: P.dia_coded_padded_pallas(*args, interpret=True, **kw)
    assert pallas_names(jax.make_jaxpr(call)().jaxpr) == [name]
