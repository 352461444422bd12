"""Pallas TPU remote-DMA ring exchange of sign-compressed bucket payloads.

The ``pallas_dma`` collective backend (``repro.comm.backends.pallas_dma``).
Same hop structure as the ppermute ring (``repro.comm.backends.ring``): W−1
double-buffered hops circulate each worker's ORIGINAL compressed payload —
``(nb, bs/32)`` uint32 sign words + ``(nb,)`` fp32 scales — around the ring,
but the hop itself is a ``pltpu.make_async_remote_copy`` issued from inside
one Pallas kernel instead of a ``lax.ppermute`` the XLA scheduler has to
place. Two kernels, one contract:

1. :func:`dma_ring_gather_slots` — the remote-DMA kernel. It copies its own
   payload into slot ``my_id``, then per hop RDMAs the slot it received last
   (its own at the first hop) into the same slot of the right neighbor
   (per-hop DMA semaphores, neighbor barrier before the first hop). Every
   arrival lands in its canonical origin-id slot — the exact
   ``(W, nb, bs/32)`` layout ``lax.all_gather`` would produce, 32× smaller
   than a gradient stack because it never leaves the wire format. All
   operands stay in HBM; the DMA engines move them with no VMEM staging.
2. the fused decompress-mean (``kernels.ops.bucket_decompress_mean``, the
   gridded Pallas kernel) — accumulates ±scale signs straight out of the
   compressed slot words in VMEM, one bucket block at a time.

So the wire never materializes a dense per-worker gradient in HBM: HBM holds
only compressed slots (d/8 bytes per worker) and the single (nb, bs) fp32
mean. Decoding in canonical origin order makes the result bitwise-equal to
``ef_allgather`` / ``ef_ring`` on every worker — the replication-safety
argument of the ppermute ring (see its module docstring) carries over
verbatim.

Testability: ``make_async_remote_copy`` needs a real TPU interconnect, so
the multi-device kernel is compiled for a described v5e ring in
``tests/test_tpu_compile.py`` and run across chips by ``chip_smoke.py
--chips 4``. The hop/arrival schedule has a pure-jnp oracle in
:mod:`repro.kernels.ref` (``dma_ring_slots_ref`` / ``dma_ring_mean_ref``),
and the single-worker degenerate of the kernel (own-slot copy, no remote
DMA) runs in interpret mode on any backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

AxisNames = tuple[str, ...]

# one collective_id per concurrently-live ring kernel (we only ever run one)
RING_COLLECTIVE_ID = 7


def supported() -> bool:
    """True when the remote-DMA kernel can run here (a TPU backend)."""
    return jax.default_backend() == "tpu"


def _ring_slots_kernel(
    widx_ref,
    words_ref,
    scales_ref,
    slot_words_ref,
    slot_scales_ref,
    local_sems,
    send_sems,
    recv_sems,
    *,
    axis: str,
    world: int,
):
    """Own payload → slot ``my_id``, then W−1 remote-DMA hops → every slot.

    Every operand stays in HBM. Hop *t* forwards slot ``(my_id − t) mod W``
    (our own payload at t = 0, the previous hop's arrival after that) into
    the same slot of the right neighbor, so each arrival lands directly in
    its canonical origin-id slot and nothing is staged in VMEM. Payloads
    stay sign-compressed on the wire for every hop.
    """
    my_id = widx_ref[0]
    own = (
        pltpu.make_async_copy(words_ref, slot_words_ref.at[my_id], local_sems.at[0]),
        pltpu.make_async_copy(scales_ref, slot_scales_ref.at[my_id], local_sems.at[1]),
    )
    for c in own:
        c.start()
    for c in own:
        c.wait()
    if world == 1:
        return
    right = {axis: lax.rem(my_id + 1, world)}
    left = {axis: lax.rem(my_id + world - 1, world)}
    mesh_id = pltpu.DeviceIdType.MESH

    # neighbor barrier: no RDMA may land in a peer that has not yet entered
    # the kernel (its slot buffers may still belong to an earlier op)
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, inc=1, device_id=left, device_id_type=mesh_id)
    pltpu.semaphore_signal(barrier, inc=1, device_id=right, device_id_type=mesh_id)
    pltpu.semaphore_wait(barrier, 2)

    for step in range(world - 1):  # static W: unrolled
        origin = lax.rem(my_id + world - step, world)
        hops = [
            pltpu.make_async_remote_copy(
                src_ref=slots.at[origin],
                dst_ref=slots.at[origin],
                send_sem=send_sems.at[k, step],
                recv_sem=recv_sems.at[k, step],
                device_id=right,
                device_id_type=mesh_id,
            )
            for k, slots in enumerate((slot_words_ref, slot_scales_ref))
        ]
        for h in hops:
            h.start()
        # the arrival of hop t is what hop t+1 forwards
        for h in hops:
            h.wait()


def dma_ring_gather_slots(
    widx: jax.Array,
    words: jax.Array,
    scales: jax.Array,
    *,
    world: int,
    axis: str | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Gather all W compressed payloads into canonical origin-id slots.

    ``widx`` () int32 index on the ring ``axis`` (needed when W > 1),
    ``words`` (nb, bs/32) u32, ``scales`` (nb,) f32 →
    ``((W, nb, bs/32) u32, (W, nb) f32)``. Runs inside
    the fully-manual ``shard_map`` of the bucketed aggregator. ``world == 1``
    needs no DMA and is interpret-safe; the multi-device kernel requires a
    real TPU ring.
    """
    if world > 1 and axis is None:
        raise ValueError("the remote-DMA ring needs its mesh axis when world > 1")
    nb, m = words.shape
    # a slot is sliced out of its HBM stack, which Mosaic allows only for
    # whole (8, 128) tiles: pad the bucket axes, drop the padding after
    rows, cols = -(-nb // 8) * 8, -(-nb // 128) * 128
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    slot_w, slot_s = pl.pallas_call(
        functools.partial(_ring_slots_kernel, axis=axis, world=world),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), hbm, hbm],
        out_specs=[hbm, hbm],
        out_shape=[
            jax.ShapeDtypeStruct((world, rows, m), jnp.uint32),
            jax.ShapeDtypeStruct((world, 1, cols), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((2,)),  # own-slot copies (words, scales)
            pltpu.SemaphoreType.DMA((2, max(world - 1, 1))),  # send, per hop
            pltpu.SemaphoreType.DMA((2, max(world - 1, 1))),  # recv, per hop
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            collective_id=RING_COLLECTIVE_ID if world > 1 else None,
        ),
        interpret=interpret,
    )(
        jnp.asarray(widx, jnp.int32).reshape(1),
        jnp.pad(words, ((0, rows - nb), (0, 0))),
        jnp.pad(scales.astype(jnp.float32), (0, cols - nb)).reshape(1, cols),
    )
    return slot_w[:, :nb], slot_s[:, 0, :nb]


def dma_ring_slot_stack(
    words: jax.Array,
    scales: jax.Array,
    ef_axes: AxisNames,
    world: int,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Remote-DMA ring exchange → canonical origin-id slot stacks.

    The slot-native backend entry point (``PayloadStack.slots()`` on the
    ``pallas_dma`` backend): gather every worker's compressed payload into
    ``((W, nb, bs/32) u32, (W, nb) f32)`` — the exact all-gather layout,
    still in the wire format, so the robust order statistics decode from
    slots the dense gradient never touched. ``dma_ring_slots_ref`` is the
    hop-by-hop oracle; the stack is worker-invariant by construction.
    """
    axis = ef_axes[0]  # single-axis ring, validated at spec time
    widx = lax.axis_index(axis)
    return dma_ring_gather_slots(
        widx, words, scales, axis=axis, world=world, interpret=interpret
    )


def dma_ring_decode_mean(
    words: jax.Array,
    scales: jax.Array,
    ef_axes: AxisNames,
    world: int,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Remote-DMA ring exchange + fused decompress-mean → (nb, bs) fp32.

    The backend entry point: DMA-gather compressed slots in canonical order,
    then accumulate ±scale signs straight from the slot words with the
    gridded Pallas mean kernel — decode order identical to ``ef_allgather``,
    so the result is bitwise-equal on every worker.
    """
    from repro.kernels import ops

    axis = ef_axes[0]  # single-axis ring, validated at spec time
    widx = lax.axis_index(axis)
    slot_w, slot_s = dma_ring_gather_slots(
        widx, words, scales, axis=axis, world=world, interpret=interpret
    )
    force = "pallas" if interpret and jax.default_backend() != "tpu" else None
    return ops.bucket_decompress_mean(slot_w, slot_s, force=force)
