"""An answer altered where the program produces it comes out as not correct,
in every rehearsal cell, now that a solve's answer is lifted on the device.

`test_run_cpu.py` and `test_run_cpu_elasticity.py` plant this fault in
`tpu._host_frame_to_pvector`, the host lift, which a solve on the device
path (PR 31) no longer runs. Here the same factor goes in at the seam that
path has: `tpu._as_callers_array`, which hands each fetched part to the
caller. Nothing of a cell's run reads this file.
"""
import importlib

import numpy as np
import pytest

from test_run_cpu import CELLS, drive as drive_poisson
from test_run_cpu_elasticity import drive as drive_elasticity

DRIVES = {
    **{which: (lambda which=which: drive_poisson(which)) for which in CELLS},
    "elasticity_pcg": drive_elasticity,
}


@pytest.mark.parametrize("which", sorted(DRIVES))
def test_an_answer_altered_where_the_device_path_produces_it_fails(which, monkeypatch):
    tpu = importlib.import_module("partitionedarrays_jl_tpu.parallel.tpu")
    from partitionedarrays_jl_tpu import telemetry

    hand_on = tpu._as_callers_array
    monkeypatch.setattr(
        tpu, "_as_callers_array",
        lambda fetched: hand_on(fetched) * np.float32(1.001),
    )
    before = telemetry.counters("solve")
    result = DRIVES[which]()
    after = telemetry.counters("solve")
    # the fault sat on the path the solves took
    assert after["solve.device_lifts"] > before.get("solve.device_lifts", 0)
    assert after["solve.host_lifts"] == before.get("solve.host_lifts", 0)
    assert result["correct"] is False
    c = result["compared"]["residual_rel"]
    assert c["value"] > c["limit"]
