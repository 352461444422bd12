"""The Pallas remote-DMA ring backend.

Same W−1 hop structure as the ppermute ring, but the hop is a
``pltpu.make_async_remote_copy`` issued from inside one Pallas kernel
(:mod:`repro.kernels.dma_ring`), and both readings of the exchange stay in
the compressed domain: the mean reading decompress-accumulates straight off
the compressed slot words (the wire never materializes a dense
per-worker gradient in HBM), and the slot reading hands the robust
strategies the canonical origin-id slots the kernel already gathers —
``(W, nb, bs/32)`` words + ``(W, nb)`` scales, 32× smaller than a gradient
stack. Capability gates:

* needs a real TPU ring — on the CPU :func:`resolve
  <repro.comm.backends.resolve>` substitutes the ``ring`` backend (same hop
  structure, same bitwise result for both readings) and logs the reason, so
  ``backend="pallas_dma"`` specs stay portable to CPU tests; elsewhere it
  raises;
* sign wire formats only — the kernel decodes ``words``/``scale`` payloads;
* single EF axis, like the ppermute ring.
"""

from __future__ import annotations

from repro.comm import compressed, exchange
from repro.comm.backends import ring as ring_backend
from repro.comm.backends.base import CollectiveBackend
from repro.comm.errors import BackendCapabilityError
from repro.core.compressors import Compressor

AxisNames = tuple[str, ...]


class PallasDmaBackend(CollectiveBackend):
    """Remote-DMA ring: compressed payloads circulate as in-kernel RDMA hops."""

    name = "pallas_dma"
    fused_mean = True

    def available(self) -> bool:
        from repro.kernels import dma_ring

        return dma_ring.supported()

    def check(self, strategy: str, comp: Compressor, ef_axes: AxisNames, mesh) -> None:
        super().check(strategy, comp, ef_axes, mesh)
        ring_backend.ring_axis(ef_axes)  # single-axis EF world required
        if comp is not None and not compressed.is_sign(comp):
            raise BackendCapabilityError(
                "backend 'pallas_dma' decodes the sign wire format "
                f"(words/scale payloads) in-kernel; got compressor {comp!r}"
            )

    def exchange(
        self,
        comp: Compressor | None,
        payload: compressed.BucketPayload,
        bucket_size: int,
        ef_axes: AxisNames,
        world: int,
    ) -> exchange.PayloadStack:
        from repro.kernels import dma_ring
        from repro.obs import trace

        def mean_fn():
            with trace.span(f"{trace.SPAN_COLLECTIVE}.{self.name}"):
                return dma_ring.dma_ring_decode_mean(
                    payload.data["words"], payload.data["scale"], ef_axes, world
                )

        def slots_fn():
            with trace.span(f"{trace.SPAN_COLLECTIVE}.{self.name}"):
                slot_w, slot_s = dma_ring.dma_ring_slot_stack(
                    payload.data["words"], payload.data["scale"], ef_axes, world
                )
            return compressed.BucketPayload(data={"words": slot_w, "scale": slot_s})

        return exchange.PayloadStack(comp, bucket_size, world, slots_fn=slots_fn, mean_fn=mean_fn)
