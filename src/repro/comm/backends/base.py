"""The collective-backend protocol.

A *backend* is the transport of the bucket-payload exchange at the heart of
the EF strategies: given this worker's encoded bucket payload (inside the
fully-manual ``shard_map`` of the bucketed aggregator), :meth:`exchange` it
with all W workers and return a :class:`~repro.comm.exchange.PayloadStack`
view. The consumer picks the reading — ``.mean()`` for the EF mean
strategies (collapsing to the backend's fused transport+decode kernel where
one exists), ``.slots()`` / ``.decoded()`` for the Byzantine-robust order
statistics, which therefore ride every transport. Strategy semantics — EF
residual updates, wire accounting, robust combines — stay in
:mod:`repro.comm.collective`; backends only move bytes, which is what makes
XLA-collective / ppermute-ring / Pallas-remote-DMA interchangeable per mesh.

All three implementations are constructed once at import time and registered
in :mod:`repro.comm.backends` under ``BACKENDS``; selection happens through
``comm.backends.resolve(spec, mesh, ef_axes)``.

The pre-slot-native two-method surface (``decode_mean`` / ``gather_stack`` /
``supports_stack``) survives as deprecation shims below; the warnings are
tier-1 ERRORS via pyproject ``filterwarnings``.
"""

from __future__ import annotations

import warnings

import jax
from jax import lax

from repro.comm import compressed, exchange, robust
from repro.comm.errors import BackendCapabilityError
from repro.core.compressors import Compressor

AxisNames = tuple[str, ...]

# strategies whose exchange is the fused payload mean (a backend may collapse
# transport + decode into per-hop units for these)
MEAN_STRATEGIES = ("ef_allgather", "ef_ring")

# strategies a backend transports at all: the mean family plus the robust
# decodes riding the same slot exchange. dense / majority_vote / ef_alltoall
# are psum / all-to-all shapes with no per-payload hop structure — they run
# on the XLA backend only.
EXCHANGE_STRATEGIES = MEAN_STRATEGIES + robust.ROBUST_STRATEGIES


class CollectiveBackend:
    """One transport for the bucketed EF exchange. Subclasses are stateless;
    everything dynamic arrives per call."""

    name: str = "?"
    #: whether :meth:`exchange` can materialize the canonical origin-id slot
    #: stack (the robust strategies need it). Every in-tree backend can; the
    #: flag is the capability query a mean-only out-of-tree transport trips.
    supports_slots: bool = True
    #: whether the mean reading is a fused transport+decode unit (ring / DMA
    #: hops) rather than gather-then-decode — the overlap pipeline uses this
    #: to place the exchange in its phase structure.
    fused_mean: bool = False

    def available(self) -> bool:
        """Whether this backend can run on the current jax backend at all.
        ``resolve`` substitutes a fallback (with a logged reason) when not."""
        return True

    def check(self, strategy: str, comp: Compressor, ef_axes: AxisNames, mesh) -> None:
        """Raise :class:`BackendCapabilityError` if this backend cannot run
        ``strategy`` with ``comp`` on ``mesh``. Called at build time from
        ``CommSpec.validate`` / ``resolve`` — never inside the traced body."""
        if strategy in robust.ROBUST_STRATEGIES and not self.supports_slots:
            raise BackendCapabilityError(
                f"robust strategy {strategy!r} needs the canonical origin-id "
                f"payload slot stack and backend {self.name!r} declares "
                "supports_slots=False (mean-only transport)"
            )

    def exchange(
        self,
        comp: Compressor | None,
        payload: compressed.BucketPayload,
        bucket_size: int,
        ef_axes: AxisNames,
        world: int,
    ) -> exchange.PayloadStack:
        """Exchange this worker's payload with all W workers; return the
        slot-native :class:`~repro.comm.exchange.PayloadStack` view. Both
        readings must be bitwise-identical across backends (the parity tests
        pin it), so replicated out_specs stay honest."""
        raise NotImplementedError

    # ------------------------------------------------------------------
    # deprecated pre-slot-native surface (PR 10 migration shims)
    # ------------------------------------------------------------------

    @property
    def supports_stack(self) -> bool:
        warnings.warn(
            "CollectiveBackend.supports_stack is deprecated; every backend "
            "exchanges the slot stack now — query supports_slots / fused_mean",
            DeprecationWarning,
            stacklevel=2,
        )
        return self.supports_slots

    def decode_mean(
        self,
        comp: Compressor,
        payload: compressed.BucketPayload,
        bucket_size: int,
        ef_axes: AxisNames,
        world: int,
    ) -> jax.Array:
        warnings.warn(
            "CollectiveBackend.decode_mean() is deprecated; use "
            "exchange(...).mean()",
            DeprecationWarning,
            stacklevel=2,
        )
        return self.exchange(comp, payload, bucket_size, ef_axes, world).mean()

    def gather_stack(
        self, payload: compressed.BucketPayload, ef_axes: AxisNames
    ) -> compressed.BucketPayload:
        warnings.warn(
            "CollectiveBackend.gather_stack() is deprecated; use "
            "exchange(...).slots()",
            DeprecationWarning,
            stacklevel=2,
        )
        world = 1
        for a in ef_axes:
            world = world * lax.axis_size(a)
        return self.exchange(None, payload, 0, ef_axes, world).slots()
