"""Main-path programs compile for a TPU v5e chip, at their real widths.

Nothing runs: each case compiles for one chip of a described ``v5e:2x2``
topology, which the TPU compiler installed with jax can target without a
chip attached.  This catches what interpret-mode tests cannot: tiling and
VMEM limits of the Pallas kernels, and programs the chip would refuse.
The kernels pick compiled (not interpreted) mode themselves when lowered
for a TPU, so these compiles are of the programs a chip would run.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

KERNEL_MARK = "tpu_custom_call"  # a Mosaic kernel in the compiled program
GRID = 288  # paper preset fine grid
BATCH = 8  # the balancer's max_batch


@pytest.fixture(scope="module")
def no_compile_cache():
    """Programs compiled for a described chip cannot be read back from the
    persistent cache here; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "strip"])
def test_swe_kernel_compiles(one_chip, fused):
    from repro.kernels.swe_flux.ops import swe_step_batched
    from repro.swe import TohokuScenario
    from repro.swe.solver import SWEState, stable_dt

    sc = TohokuScenario(nx=GRID, ny=GRID)
    cfg, b = sc.cfg, sc.bathymetry()
    dt = stable_dt(cfg, float(jnp.max(-b)))
    plane = jax.ShapeDtypeStruct((BATCH, GRID, GRID), jnp.float32, sharding=one_chip)
    text = _compile(
        lambda s: swe_step_batched(s, b, dt, cfg=cfg, fused=fused),
        SWEState(plane, plane, plane),
    )
    assert KERNEL_MARK in text


def test_matern_kernel_compiles(one_chip):
    from repro.kernels.matern.matern import matern52_pallas

    x = jax.ShapeDtypeStruct((512, 2), jnp.float32, sharding=one_chip)
    assert KERNEL_MARK in _compile(lambda a, b: matern52_pallas(a, b, 1.0), x, x)


def test_batched_fine_forward_compiles(one_chip):
    from repro.swe import TohokuScenario

    forward = TohokuScenario(nx=GRID, ny=GRID).build_stacked_forward()
    thetas = jax.ShapeDtypeStruct((BATCH, 2), jnp.float32, sharding=one_chip)
    assert "ENTRY" in _compile(forward, thetas)
