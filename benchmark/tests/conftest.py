"""Rehearsals of the benchmark off the chip: `python -m pytest benchmark/tests -q`.

Not collected by the repo's tier-1 run (`pytest tests/`). The environment
is set before anything imports jax: the CPU backend with four virtual
devices, float32 as on the chip (x64 stays off).
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4"
    ).strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
