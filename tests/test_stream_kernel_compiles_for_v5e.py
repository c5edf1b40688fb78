"""The streamed-diagonal Mosaic kernel compiled for a described TPU v5e
(no chip attached: the TPU compiler is installed and compiles for a
topology it is given) at the blocks its plan picks for the 27-point
Galerkin levels of a 192^3 hierarchy, under the scoped-VMEM limit the
kernel hands Mosaic. Interpret mode on the CPU cannot show a kernel that
Mosaic refuses for its VMEM; this can. The topology is described inside a
fixture, so only the worker that runs this file loads the TPU library."""
import jax
import jax.numpy as jnp
import pytest

from partitionedarrays_jl_tpu.ops import pallas_dia as P


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _offsets27(n):
    return tuple(sorted(
        k * n * n + j * n + i
        for k in (-1, 0, 1) for j in (-1, 0, 1) for i in (-1, 0, 1)
    ))


@pytest.mark.parametrize(
    "n,block_rows", [(96, 384), (48, 288)], ids=["level-1", "level-2"]
)
def test_the_galerkin_plan_compiles_inside_the_vmem_limit(one_chip, n, block_rows):
    """Level 1 (96^3 a part, 384-row blocks) and level 2 (48^3, 288 rows)
    build under `VMEM_LIMIT_BYTES`; compiled so, they need some 13.3 and
    14.0 MiB of it (1.2 and 1.7 times the 11.0 and 8.2 MiB the plan
    declares)."""
    offsets = _offsets27(n)
    plan = P.plan_dia_pallas(offsets, n**3)
    assert plan["block_rows"] == block_rows
    R, H, BR = plan["n_rows"], plan["halo_rows"], plan["block_rows"]
    vals = jax.ShapeDtypeStruct((27, R, P.LANES), jnp.float32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((plan["x_rows"], P.LANES), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda v, xw: P.dia_spmv_pallas(v, xw, offsets, R, H, BR))
    # as on the chip: 32-bit indices (the test session runs with x64 on),
    # and no persistent cache, which could not read such an entry back
    was = (jax.config.jax_enable_x64, jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = fn.lower(vals, x).compile().as_text()
    finally:
        jax.config.update("jax_enable_x64", was[0])
        jax.config.update("jax_enable_compilation_cache", was[1])
    assert "pa_dia_stream_spmv" in text
