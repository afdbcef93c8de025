"""The main-path kernels compile for a TPU v5e at real widths.

No chip is attached: the topology is *described*, and the TPU compiler
(installed with jax) compiles for it. That catches what interpret mode
cannot — layouts, casts and slices the kernel compiler refuses, and
programs that do not fit one chip's memory — at no chip time. Each test
steers ``jax.default_backend()`` to ``"tpu"`` itself, so the program's own
dispatch picks the native kernel, and asserts ``tpu_custom_call`` in the
compiled text: the kernel, not the jnp oracle, was compiled.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
every test file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

from repro.kernels import ops
from repro.wsi import convert

#: one v5e chip's HBM (16 GB, Google Cloud "TPU v5e" documentation)
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(topo, monkeypatch):
    """The program sees a TPU backend and a one-chip mesh of the described
    topology; the persistent compile cache stays off (a compile for a
    described chip cannot be read back without one)."""
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    try:
        with ops.use_mesh(mesh):
            yield mesh
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


@pytest.mark.parametrize("name,fn,shape,dtype", [
    ("jpeg_transform", ops.jpeg_transform, (64, 3, 256, 256), jnp.float32),
    ("jpeg_inverse", ops.jpeg_inverse, (64, 3, 256, 256), jnp.int32),
    ("downsample2x2", ops.downsample2x2, (3, 8192, 8192), jnp.float32),
    ("rgb2ycbcr", ops.rgb2ycbcr, (3, 256, 256), jnp.float32),
    ("jpeg_transform_ragged", ops.jpeg_transform, (4, 3, 24, 72),
     jnp.float32),
])
def test_kernel_compiles_for_v5e(on_tpu, one_chip, name, fn, shape, dtype):
    compiled = _compile(fn, jax.ShapeDtypeStruct(shape, dtype,
                                                 sharding=one_chip))
    assert "tpu_custom_call" in compiled.as_text(), name


def test_dct8x8_quant_compiles_for_v5e(on_tpu, one_chip):
    """The per-tile encoder's DCT (``encode_tile``)."""
    compiled = _compile(
        ops.dct8x8_quant,
        jax.ShapeDtypeStruct((256, 256), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((8, 8), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_pyramid_chain_8192_fits_one_chip(on_tpu, one_chip):
    """The fused pyramid of an 8192² slide at 256-px tiles: every level's
    transform and every downsample is a kernel, and the program fits in
    one chip's memory."""
    dims = convert._pyramid_dims(8192, 8192, 256)
    chain = convert._pyramid_chain(len(dims), tuple(range(len(dims))), 256,
                                   jax.default_backend() != "cpu", on_tpu)
    compiled = chain.lower(jax.ShapeDtypeStruct(
        (3, 8192, 8192), jnp.float32, sharding=one_chip)).compile()
    text = compiled.as_text()
    # one transform per level, one downsample between levels
    assert text.count("tpu_custom_call") >= 2 * len(dims) - 1
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.alias_size_in_bytes > 0  # level 0 donated to the chain
    assert total < HBM_BYTES, total
