"""Compile the main path's device programs for a DESCRIBED TPU v5e.

No chip is attached: the TPU compiler runs against a topology
description, so a Mosaic lowering or a program the chip's compiler
refuses fails here instead of on the chip (interpret mode, which every
other test uses off-TPU, cannot show that).  Widths are the real ones:
4M rows, a 256x128 density grid, 3 boxes, and one lean z3 generation
(``LeanZ3Index.GENERATION_SLOTS`` slots).

The topology is described only inside a module fixture: only one
process may load the TPU library, and pytest-xdist workers import every
test file, so touching it at import would break collection.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import geomesa_tpu  # noqa: F401  (x64)
from geomesa_tpu.ops import pallas_kernels as pk

N = 4 * 1024 * 1024
BOXES = 3
W, H = 256, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture()
def mosaic(monkeypatch):
    """Force Mosaic lowering: ``_interpret`` is True off-TPU."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases():
    return {
        "density": (
            ("width", "height"),
            lambda s: (_spec(s, (N,), jnp.float64),
                       _spec(s, (N,), jnp.float64),
                       _spec(s, (N,), jnp.float32),
                       _spec(s, (N,), jnp.bool_),
                       (-180.0, -90.0, 180.0, 90.0)),
            {"width": W, "height": H}, pk.density_grid_pallas),
        "hist1d": (
            ("n_bins",),
            lambda s: (_spec(s, (N,), jnp.int32),
                       _spec(s, (N,), jnp.float32),
                       _spec(s, (N,), jnp.bool_)),
            {"n_bins": W}, pk.hist1d_pallas),
        "z3_mask": (
            (),
            lambda s: (_spec(s, (N,), jnp.int64),
                       _spec(s, (BOXES, 4), jnp.int32),
                       _spec(s, (N,), jnp.int32),
                       _spec(s, (N,), jnp.int32)),
            {}, pk.z3_mask_pallas),
        "z2_mask": (
            (),
            lambda s: (_spec(s, (N,), jnp.int64),
                       _spec(s, (BOXES, 4), jnp.int32)),
            {}, pk.z2_mask_pallas),
    }


@pytest.mark.parametrize("kernel", sorted(_kernel_cases()))
def test_pallas_kernel_compiles_for_v5e(kernel, one_chip, mosaic):
    static, args, kwargs, fn = _kernel_cases()[kernel]
    jitted = jax.jit(fn.__wrapped__, static_argnames=static)
    compiled = jitted.lower(*args(one_chip), **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lean_generation_count_compiles_for_v5e(one_chip):
    """The lean z3 totals probe over two full-width generations: the
    per-query seek program every lean scan starts with."""
    from geomesa_tpu.index.z3_lean import LeanZ3Index, _lean_count_multi
    slots = LeanZ3Index.GENERATION_SLOTS
    ranges = 256
    gens = []
    for _ in range(2):
        gens += [_spec(one_chip, (slots,), jnp.int32),
                 _spec(one_chip, (slots,), jnp.int64)]
    compiled = _lean_count_multi.lower(
        _spec(one_chip, (ranges,), jnp.int32),
        _spec(one_chip, (ranges,), jnp.int64),
        _spec(one_chip, (ranges,), jnp.int64), *gens).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 2 * slots * 12
