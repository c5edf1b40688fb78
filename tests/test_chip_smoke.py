"""`chip_smoke.py`, debugged here and not on chip time.

The command itself only runs on a TPU (and must say so, loudly, anywhere
else); its leg functions are plain functions of a backend and a size, so
the 8-device CPU mesh drives them at a tiny size with the real-TPU
padded kernel frame forced on (Pallas in interpret mode) — the layout,
the lowerings, the service slabs and every residual/parity check of the
smoke run for real, only the Mosaic compile does not.
"""
import importlib
import importlib.util
import os
import subprocess
import sys
import time

import jax
import pytest

import partitionedarrays_jl_tpu as pa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=ROOT, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True,
    )
    assert p.returncode != 0
    assert "CpuDevice" in p.stderr, p.stderr  # names the devices it found
    assert p.stdout.strip() == "", "printed a result off-chip"


def test_last_line_is_the_verdict_and_the_device_only(smoke, monkeypatch, capsys):
    import json

    # main() past the platform gate, with the legs stubbed out: what it
    # prints last is one JSON object with exactly these keys
    class FakeTpu:
        platform, device_kind, id = "tpu", "TPU v5 lite", 0

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    monkeypatch.setattr(pa, "TPUBackend", lambda devices: None)
    monkeypatch.setattr(pa, "enable_compilation_cache", lambda: "/nowhere")
    for status, code in (("passed", 0), ("not run: time", 0), ("failed", 1)):
        monkeypatch.setattr(
            smoke, "run_legs", lambda *a, **k: {"coded_cg": {"status": status}}
        )
        assert smoke.main() == code
        last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert last == {
            "ok": code == 0,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        }
        assert type(last["ok"]) is bool and type(last["device"]["count"]) is int


def test_legs_pass_on_the_forced_padded_frame(smoke, monkeypatch):
    tpu_mod = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    monkeypatch.setattr(tpu_mod, "_padded_for", lambda backend: True)
    backend = pa.TPUBackend(devices=jax.devices()[:4])
    # a deadline that holds legs 1-4 and cuts leg 5: a cut leg is
    # reported as not run, never as passed
    deadline = time.monotonic() + smoke.LEG_NEEDS_S["irregular_pcg"] - 60
    assert smoke.LEG_NEEDS_S["stream_dia_cg"] < smoke.LEG_NEEDS_S[
        "irregular_pcg"
    ] - 60
    legs = smoke.run_legs(
        pa, backend, smoke.PART_GRIDS[4], cells=8, nodes=5,
        deadline=deadline,
    )
    assert list(legs) == [
        "coded_cg", "gmg_pcg", "served", "stream_dia_cg", "irregular_pcg",
    ]
    for name in ("coded_cg", "gmg_pcg", "served", "stream_dia_cg"):
        assert legs[name]["status"] == "passed", (name, legs[name])
        assert legs[name]["residual"] <= smoke.RESIDUAL_MAX
    assert legs["irregular_pcg"] == {"status": "not run: time"}

    cg = legs["coded_cg"]
    assert cg["lowering"] == "coded-dia/pallas-padded-frame"
    assert cg["cg_body"] == "fused" and cg["device_dtype"] == "float32"
    assert cg["exchange_plan"] == "BoxExchangePlan"
    assert cg["spmv_parity"] <= smoke.PARITY_MAX
    assert sorted(cg["shard_devices"]) == [0, 1, 2, 3]
    assert not cg["mosaic_call"]  # interpreted here; required on the chip
    assert legs["gmg_pcg"]["iterations"] <= smoke.GMG_MAX_ITERATIONS
    assert legs["served"]["completed"] == 8 and legs["served"]["slabs"] == 2
    assert legs["stream_dia_cg"]["lowering"] == "stream-dia/xla"


def test_irregular_leg_and_a_failing_check(smoke):
    backend = pa.TPUBackend(devices=jax.devices()[:4])
    rec = pa.prun(lambda parts: smoke.leg_irregular(pa, parts, 5), backend, 4)
    assert rec["lowering"] == "sd(bs=3)"
    assert rec["exchange_plan"] == "DeviceExchangePlan"
    assert rec["residual"] <= smoke.RESIDUAL_MAX

    # the independent residual is independent: a wrong answer fails it
    def wrong(parts):
        A, b, xe, x0 = pa.assemble_poisson(parts, (6, 6, 6))
        return smoke.independent_residual(pa, A, xe, b), (
            smoke.independent_residual(pa, A, x0, b)
        )

    right, off = pa.prun(wrong, backend, (2, 2, 1))
    assert right < 1e-12 < 1e-2 < off
    with pytest.raises(smoke.SmokeFailure, match="residual"):
        smoke.require(off <= smoke.RESIDUAL_MAX, f"residual {off:.3e}")
