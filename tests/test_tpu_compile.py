"""The main-path Pallas kernels compiled for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler installed with jax compiles for
a ``v5e:2x2`` topology that is described, not attached, and refuses what the
chip's compiler would refuse (misaligned blocks, VMEM overflow, an unsupported
device id). Shapes are the real ones: a 4096-bucket stack of 65536-element
buckets, and 4039 buckets where the last row block is partial; granite's
leaves (six layers) for the bucket layout's flatten and apply. The topology is
described inside a fixture, so only the worker that runs this file loads the
TPU library, and every test skips where it cannot be described.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.comm import bucketize
from repro.core import optim
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


# the benchmark's two configurations, their leaves in tree order: granite-3.0-1b-a400m
# at six layers (f32), deepseek-llm-7b at two layers and 12,800 vocabulary rows (bf16,
# with rows of 11008 and 12800 that do not tile a bucket)
_LEAVES = {
    "granite": (jnp.float32, [(6, 1024, 512), (6, 1024, 1024), (6, 1024, 1024), (6, 1024, 512),
                              (6, 1024, 32), (6, 32, 1024, 512), (6, 32, 1024, 512),
                              (6, 32, 512, 1024), (6, 1024), (6, 1024), (49408, 1024), (1024,)]),
    "deepseek": (jnp.bfloat16, [(2, 4096, 4096)] * 4 + [(2, 4096, 11008), (2, 4096, 11008),
                                                        (2, 11008, 4096), (2, 4096), (2, 4096),
                                                        (12800, 4096), (4096,), (4096, 12800)]),
}
_SHAPE = re.compile(r"\b(?:f32|bf16)\[([0-9,]*)\]")


def _group_sized_shapes(hlo: str, valid: int) -> set[tuple[int, ...]]:
    """Every array shape in the compiled text with at least ``valid`` elements,
    with its unit dims dropped."""
    out = set()
    for m in _SHAPE.finditer(hlo):
        dims = tuple(int(d) for d in m.group(1).split(",") if d)
        if math.prod(dims) >= valid:
            out.add(tuple(d for d in dims if d != 1))
    return out


@pytest.mark.parametrize("config", sorted(_LEAVES))
def test_bucket_layout_flatten_and_apply_keep_no_group_wide_view(one_chip, config):
    """Flatten (over a worker axis, as the EF step runs it) and unflatten +
    apply move each leaf to and from its own rows: no array as large as the
    whole group exists but the ``(n_buckets, bucket_size)`` bucket array, and
    each program stays a few megabytes of code. Apply casts each leaf's rows
    on their own, not the whole array; flatten of a bf16 group may join its
    blocks before the one cast to float32, which moves no more bytes."""
    dtype, shapes = _LEAVES[config]
    params = [jax.ShapeDtypeStruct(s, dtype, sharding=one_chip) for s in shapes]
    layout = bucketize.build_layout(params, BS)
    (group,) = layout.groups
    assert group.rows_shared == {"granite": 2, "deepseek": 3}[config]
    updates = [jax.ShapeDtypeStruct((1, *s), dtype, sharding=one_chip) for s in shapes]
    flatten = jax.jit(jax.vmap(lambda u: bucketize.flatten_buckets(layout, u))).lower(updates).compile()
    buckets = jax.ShapeDtypeStruct((group.n_buckets, BS), jnp.float32, sharding=one_chip)
    apply = jax.jit(
        lambda p, b: optim.apply_updates(p, bucketize.unflatten_buckets(layout, (b,)))
    ).lower(params, buckets).compile()
    for compiled in (flatten, apply):
        hlo = compiled.as_text()
        assert _group_sized_shapes(hlo, group.valid) == {(group.n_buckets, BS)}
        assert compiled.memory_analysis().generated_code_size_in_bytes < 8 << 20
    assert f"bf16[{group.n_buckets},{BS}]" not in apply.as_text()
