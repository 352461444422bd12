"""Pluggable collective backends for the bucketed EF exchange.

The registry behind :func:`repro.comm.api.make_aggregator`: the same
strategy semantics can ride three transports, selected per mesh —

``xla``         ``lax`` collectives (all-gather). The all-gather *is* the
                slot stack; the mean reading decodes it. The default.
``ring``        W−1 double-buffered ``lax.ppermute`` hops (promoted from
                ``overlap/ring.py``). Fused per-hop mean; origin-id slot
                gather for the robust reading. Single EF axis.
``pallas_dma``  the remote-DMA ring kernel (:mod:`repro.kernels.dma_ring`):
                hops are ``make_async_remote_copy`` issued in-kernel and
                both readings stay in the compressed domain — no dense
                per-worker gradient ever lands in HBM. Needs a real TPU
                ring; on the CPU :func:`resolve` substitutes ``ring``
                (bitwise-equal result) and logs the reason.

Every backend exchanges payloads into one slot-native
:class:`~repro.comm.exchange.PayloadStack` view, and both readings — the
(nb, bs) mean and the canonical (W, ...) slot stack — are bitwise-identical
across transports (the parity tests pin it), so swapping backends never
perturbs a training trajectory, mean-path or robust.
``backend="auto"`` resolves deterministically: ``ef_ring`` → ``ring``,
everything else → ``xla``, except on a TPU mesh where the DMA-hop latency
model in :mod:`repro.core.aggregation` acts as the accept/reject oracle for
promoting the ``ef_allgather`` mean exchange to ``pallas_dma`` (see
:func:`recommend_backend`; the ``backends`` bench suite gates the model).
The robust strategies stay on ``xla`` under ``auto`` — their decode reads
the full slot stack anyway, so the one-collective gather is the
conservative default — but every backend accepts them explicitly.
"""

from __future__ import annotations

import logging

import jax

from repro.comm.backends.base import (
    EXCHANGE_STRATEGIES,
    MEAN_STRATEGIES,
    CollectiveBackend,
)
from repro.comm.backends.pallas_dma import PallasDmaBackend
from repro.comm.backends.ring import (
    RingBackend,
    ring_axis,
    ring_decode_mean,
    ring_gather_slots,
)
from repro.comm.backends.xla import XlaBackend, gather_payload
from repro.comm.errors import BackendCapabilityError, CommSpecError, UnknownBackendError

logger = logging.getLogger(__name__)

BACKENDS: dict[str, CollectiveBackend] = {
    "xla": XlaBackend(),
    "ring": RingBackend(),
    "pallas_dma": PallasDmaBackend(),
}

#: names accepted by ``CommSpec.backend`` ("auto" defers choice to resolve())
BACKEND_CHOICES = ("auto",) + tuple(BACKENDS)


def lookup(name: str) -> CollectiveBackend:
    """Registry lookup; unknown names fail listing the options."""
    if name not in BACKENDS:
        raise UnknownBackendError(
            f"unknown collective backend {name!r}; options: {tuple(BACKENDS)}"
        )
    return BACKENDS[name]


def recommend_backend(
    n_buckets: int, bucket_size: int, world: int, *, bytes_per_us: float | None = None
) -> str:
    """The accept/reject oracle for promoting the mean exchange to the DMA
    ring: same total bytes either way, so the analytic model compares W−1
    hop launches against one collective launch (see
    :func:`repro.core.aggregation.dma_ring_latency_model`)."""
    from repro.core import aggregation

    if world <= 1:
        return "xla"
    kw = {} if bytes_per_us is None else {"bytes_per_us": bytes_per_us}
    model = aggregation.dma_ring_latency_model(n_buckets, bucket_size, world, **kw)
    return "pallas_dma" if model["accept"] else "xla"


def _auto_backend(spec, mesh, ef_axes, layout) -> str:
    from repro.comm import compressed

    if spec.strategy == "ef_ring":
        return "ring"
    if spec.strategy != "ef_allgather":
        # psum / all-to-all shapes (no payload hop structure) and the robust
        # slot readers: one-collective gather is the conservative default
        return "xla"
    comp = spec.resolved_compressor
    sign = comp is None or compressed.is_sign(comp)
    if (
        BACKENDS["pallas_dma"].available()
        and layout is not None
        and len(ef_axes) == 1
        and sign
    ):
        return recommend_backend(layout.n_buckets, layout.bucket_size, spec.world_of(mesh, ef_axes))
    return "xla"


def resolve(spec, mesh, ef_axes=(), *, layout=None) -> CollectiveBackend:
    """Pick the backend instance for ``spec`` on ``mesh``.

    ``backend="auto"`` is deterministic per mesh (see module docstring);
    an explicit ``pallas_dma`` on the CPU degrades to ``ring`` with a logged
    reason (bitwise-equal result), so one spec serves CPU tests and
    hardware; on any other non-TPU backend it raises. The
    returned backend has passed its capability check for this spec.
    """
    name = spec.backend or "auto"
    if name == "auto":
        name = _auto_backend(spec, mesh, ef_axes, layout)
    be = lookup(name)
    if name == "pallas_dma" and not BACKENDS["pallas_dma"].available():
        if jax.default_backend() != "cpu":
            raise BackendCapabilityError(
                "backend 'pallas_dma' needs the TPU remote-DMA ring; the jax "
                f"backend here is {jax.default_backend()!r}"
            )
        logger.warning(
            "backend 'pallas_dma' needs the TPU remote-DMA ring (jax backend "
            "is %r here); falling back to the 'ring' backend — same W-1 hop "
            "structure, bitwise-equal result",
            jax.default_backend(),
        )
        be = BACKENDS["ring"]
    if spec.strategy not in EXCHANGE_STRATEGIES and be.name != "xla":
        raise BackendCapabilityError(
            f"strategy {spec.strategy!r} has no payload exchange to re-route "
            f"(backends apply to {EXCHANGE_STRATEGIES}); it runs on the "
            "'xla' backend only"
        )
    be.check(spec.strategy, spec.resolved_compressor, ef_axes, mesh)
    return be


def capability_matrix(mesh, ef_axes: tuple[str, ...] = ("data",), comp=None) -> dict:
    """strategy × backend capability table, post-resolution semantics.

    Returns ``{strategy: {backend: cell}}`` where a cell is ``"ok"``,
    ``"ok (degrades to 'ring' here)"`` for an unavailable ``pallas_dma``
    that :func:`resolve` would substitute (CPU only), or ``"-- <reason>"`` quoting the
    :class:`~repro.comm.errors.CommSpecError` the combination raises.
    ``comp=None`` probes each strategy's default (sign) wire format. Used by
    ``launch/dryrun.py`` to surface misconfigurations before compile.
    """
    from repro.comm import collective

    out: dict[str, dict[str, str]] = {}
    for strategy in collective.STRATEGIES:
        row = {}
        for name, be in BACKENDS.items():
            try:
                if strategy not in EXCHANGE_STRATEGIES and name != "xla":
                    raise BackendCapabilityError("no payload exchange to re-route; xla only")
                be.check(strategy, comp, ef_axes, mesh)
            except CommSpecError as e:
                row[name] = f"-- {e}"
            else:
                if name == "pallas_dma" and not be.available():
                    if jax.default_backend() == "cpu":
                        row[name] = "ok (degrades to 'ring' here)"
                    else:
                        row[name] = "-- needs the TPU remote-DMA ring"
                else:
                    row[name] = "ok"
        out[strategy] = row
    return out


__all__ = [
    "BACKENDS",
    "BACKEND_CHOICES",
    "CollectiveBackend",
    "EXCHANGE_STRATEGIES",
    "MEAN_STRATEGIES",
    "capability_matrix",
    "gather_payload",
    "lookup",
    "recommend_backend",
    "resolve",
    "ring_axis",
    "ring_decode_mean",
    "ring_gather_slots",
]
