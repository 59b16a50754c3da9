"""The serving kernels compile for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is only
described (``jax.experimental.topologies``), so these ahead-of-time compiles
catch what Pallas interpret mode cannot: block shapes that break Mosaic's
(8, 128) rule, scalar stores to VMEM, casts the chip has no instruction for.
Each compile passes ``impl="pallas", interpret=False`` explicitly, because
``jax.default_backend()`` is the CPU here, and asserts that the kernel is in
the compiled program.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.device_plane import DevicePlane
from repro.kernels import ops

MASKED_SHAPES = [(8, 128, 16), (8, 136, 32), (64, 512, 100)]
PRUNE_SHAPES = [(8, 136, 32), (64, 512, 100)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _join_args(s, p, d, sharding):
    return (jax.ShapeDtypeStruct((s, p, d), jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct((s,), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((s,), jnp.float32, sharding=sharding))


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("with_sq", [False, True])
@pytest.mark.parametrize("s,p,d", MASKED_SHAPES)
def test_masked_join_compiles(one_chip, s, p, d, with_sq):
    _assert_kernel(lambda x, n, r: ops.join_batched_masked_local(
        x, n, r, with_sq=with_sq, impl="pallas", interpret=False),
        *_join_args(s, p, d, one_chip))


@pytest.mark.parametrize("s,p,d", PRUNE_SHAPES)
def test_prune_counts_compile(one_chip, s, p, d):
    elig = jax.ShapeDtypeStruct((s, (p + 31) // 32), jnp.uint32,
                                sharding=one_chip)
    _assert_kernel(lambda x, n, r, e: ops.join_batched_counts_local(
        x, n, r, e, dtype="bf16", impl="pallas", interpret=False),
        *_join_args(s, p, d, one_chip), elig)


def test_sharded_masked_join_compiles(topo):
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1), ("data", "model"))
    plane = DevicePlane(mesh)
    _assert_kernel(lambda x, n, r: plane.join_batched_masked(
        x, n, r, impl="pallas", interpret=False),
        *_join_args(32, 256, 32, NamedSharding(mesh, P("data"))))
