"""The main-path raster kernels compile for a TPU v5e chip.

Interpret mode (the CPU tests) accepts kernels the TPU compiler refuses:
unaligned vector loads, in-kernel gathers, more VMEM than a kernel may
use. These tests compile each kernel at its production size (R=512,
N=4096 leaves, float32 tables), and the slice and projection at R=32
(an image tile narrower than 128 lanes), for one chip of a described
``v5e:2x2`` topology — no chip attached — and check the Mosaic kernel
is in the compiled program. The topology is described inside a fixture,
so only the worker that runs this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import raster_kernel as rk

R = 512
N = 4096
LEVELS = 12
BINS = 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


TABLE_I = ((1, N), jnp.int32)
TABLE_F = ((1, N), jnp.float32)
IMAGE = ((R, R), jnp.float32)
DEPTH = ((R, R), jnp.int32)

KERNELS = {
    "slice_raster": (
        lambda *a: rk.slice_raster(*a, resolution=R),
        [TABLE_I] * 4 + [TABLE_F, TABLE_I]),
    "slice_raster_carry": (
        lambda *a: rk.slice_raster_carry(*a, resolution=R),
        [TABLE_I] * 4 + [TABLE_F, TABLE_I, IMAGE, DEPTH]),
    "projection_raster": (
        lambda *a: rk.projection_raster(*a, resolution=R),
        [TABLE_I] * 3 + [TABLE_F, TABLE_I]),
    "projection_raster_carry": (
        lambda *a: rk.projection_raster_carry(*a, resolution=R),
        [TABLE_I] * 3 + [TABLE_F, TABLE_I, IMAGE]),
    # R=32: the tile is (8, 32), narrower than the 128 lanes
    "slice_raster_r32": (
        lambda *a: rk.slice_raster(*a, resolution=32),
        [TABLE_I] * 4 + [TABLE_F, TABLE_I]),
    "projection_raster_r32": (
        lambda *a: rk.projection_raster(*a, resolution=32),
        [TABLE_I] * 3 + [TABLE_F, TABLE_I]),
    "level_hist": (
        lambda *a: rk.level_hist(*a, n_levels=LEVELS, bins=BINS),
        [TABLE_F, TABLE_I, TABLE_I, ((2, BINS + 1), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    _compile(fn, shapes, one_chip)
