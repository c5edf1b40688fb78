"""Test harness configuration.

Mirrors the reference's CI story (SURVEY.md §4): the whole tree runs on one
machine. The TPU-backend tests run on a virtual 8-device CPU mesh via
``xla_force_host_platform_device_count`` (the `mpiexec -n 8` analog), and
float64 is enabled so correctness checks match the sequential oracle.

This file must set the environment before anything imports jax.
"""
import os
import sys

# Plain assignment, not setdefault: the suite runs on the virtual CPU mesh
# whatever the ambient platform is (a machine with a chip included). A
# plugin that imported jax before this file makes env vars alone too late,
# so the config is updated through the API as well.
os.environ["JAX_PLATFORMS"] = "cpu"
# The suite's error-path probes assert that contract checks raise; a
# stripped-checks environment (PA_TPU_CHECKS=0) is a production tuning,
# not a supported test configuration — pin checks on before the package
# reads the flag at import.
os.environ["PA_TPU_CHECKS"] = "1"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_ENABLE_X64"] = "true"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
