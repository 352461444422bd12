"""The main-path Pallas kernels compiled for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler installed with jax compiles for
a ``v5e:2x2`` topology that is described, not attached, and refuses what the
chip's compiler would refuse (misaligned blocks, VMEM overflow, an unsupported
device id). Shapes are the real ones: a 4096-bucket stack of 65536-element
buckets, and 4039 buckets where the last row block is partial. The topology is
described inside a fixture, so only the worker that runs this file loads the
TPU library, and every test skips where it cannot be described.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import dma_ring, ef_sign

pytestmark = pytest.mark.pallas

BS = 65536


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


_KERNELS = {
    "stats": lambda nb: (
        ef_sign.bucket_stats,
        [((nb, BS), jnp.float32), ((nb, BS), jnp.float32)],
    ),
    "compress": lambda nb: (
        ef_sign.bucket_ef_sign_compress,
        [((nb, BS), jnp.float32), ((nb, BS), jnp.float32), ((nb,), jnp.float32)],
    ),
    "accumulate": lambda nb: (
        ef_sign.bucket_sign_accumulate,
        [((nb, BS), jnp.float32), ((nb, BS // 32), jnp.uint32), ((nb,), jnp.float32)],
    ),
    "mean_w1": lambda nb: (
        ef_sign.bucket_sign_decompress_mean,
        [((1, nb, BS // 32), jnp.uint32), ((1, nb), jnp.float32)],
    ),
    "mean_w4": lambda nb: (
        ef_sign.bucket_sign_decompress_mean,
        [((4, nb, BS // 32), jnp.uint32), ((4, nb), jnp.float32)],
    ),
}


@pytest.mark.parametrize("n_buckets", [4096, 4039])
@pytest.mark.parametrize("kernel", sorted(_KERNELS))
def test_bucket_kernel_compiles_for_v5e(one_chip, kernel, n_buckets):
    fn, shapes = _KERNELS[kernel](n_buckets)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    assert "tpu_custom_call" in _compile(fn, *args)


@pytest.mark.parametrize("n_buckets", [4096, 4039])
def test_dma_ring_kernel_compiles_on_tpu(topo, n_buckets):
    """The multi-device remote-DMA ring at W = 4 under ``shard_map`` over the
    four described chips, followed by the decompress-mean of its slots."""
    world = 4
    mesh = Mesh(np.array(topo.devices[:world]).reshape(world, 1), ("data", "model"))

    def body(w, s):
        widx = jax.lax.axis_index("data")
        slot_w, slot_s = dma_ring.dma_ring_gather_slots(widx, w[0], s[0], axis="data", world=world)
        return ef_sign.bucket_sign_decompress_mean(slot_w, slot_s)[None]

    step = jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P("data"), check_vma=False
    )
    sharded = NamedSharding(mesh, P("data"))
    words = jax.ShapeDtypeStruct((world, n_buckets, BS // 32), jnp.uint32, sharding=sharded)
    scales = jax.ShapeDtypeStruct((world, n_buckets), jnp.float32, sharding=sharded)
    hlo = _compile(step, words, scales)
    assert hlo.count("tpu_custom_call") >= 2  # the ring and the mean


def test_dma_ring_kernel_compiles_for_one_chip(one_chip):
    """W = 1: the own-slot copy alone, at a slot stack far above VMEM."""
    words = jax.ShapeDtypeStruct((4096, BS // 32), jnp.uint32, sharding=one_chip)
    scales = jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=one_chip)
    fn = lambda w, s: dma_ring.dma_ring_gather_slots(jnp.int32(0), w, s, world=1)  # noqa: E731
    assert "tpu_custom_call" in _compile(fn, words, scales)
