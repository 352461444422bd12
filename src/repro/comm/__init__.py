"""Bucketed, overlap-ready gradient-communication layer.

Real distributed-training systems do not ship gradients leaf-by-leaf: they
flatten the gradient pytree into fixed-size, dtype-homogeneous *buckets* and
run compression + collectives per bucket (dist-EF-SGD, Zheng et al. '19;
PyTorch DDP's gradient bucketing). This package supplies that wire path for
every :class:`repro.core.compressors.Compressor`:

``api``
    :class:`CommSpec` + :func:`make_aggregator` — THE public entry point: one
    frozen spec describing strategy / compressor / bucket size / collective
    backend / byz / overlap, validated once, dispatched to the right path.
``bucketize``
    :class:`BucketLayout` — a static flatten/unflatten plan computed once per
    parameter spec — plus the flatten/unflatten executors.
``compressed``
    Per-bucket compression with error feedback: encode ``p_b = u_b + e_b``,
    decode-and-average gathered payloads, per-bucket wire/density accounting.
``collective``
    The strategy semantics, run under **fully-manual** ``shard_map`` over
    every mesh axis: buckets keep no intra-leaf sharding, so no axis needs
    to stay GSPMD-auto.
``exchange``
    :class:`PayloadStack` — the slot-native view every backend returns from
    its exchange: read ``.mean()`` (fused fast path where the transport has
    one) or ``.slots()``/``.decoded()`` (canonical origin-id worker stack).
``backends``
    Pluggable transports for the slot-native payload exchange — ``xla`` (lax
    collectives), ``ring`` (double-buffered ppermute), ``pallas_dma``
    (in-kernel remote-DMA ring) — selected per mesh via
    ``CommSpec.backend`` / ``backends.resolve``. All three serve both
    readings, so the robust strategies ride every transport.
``errors``
    The one :class:`~repro.comm.errors.CommSpecError` taxonomy every
    construction-time rejection raises from.
``robust``
    Byzantine-robust decode-side combiners (coordinate median, trimmed mean,
    distance-to-median filtering) behind the same aggregator seam — the
    ``ef_coord_median`` / ``ef_trimmed_mean`` / ``ef_norm_filter`` strategies.
``adversary``
    Fault injection for the EF-worker gradient lanes (sign flip, scaled
    noise, zero-out, colluding constant drift) driving the byz bench/tests.

The per-leaf strategies in :mod:`repro.core.aggregation` remain the
``bucket_size=None`` fallback — they preserve leaf shardings (no flatten), at
the cost of per-leaf payloads and the partial-manual collective path.
"""

# import order is cycle-load-bearing: bucketize/compressed/exchange are leaf
# modules, robust sits on compressed, collective on both, backends on
# exchange + collective's helpers, api on everything
from repro.comm.bucketize import (
    DEFAULT_BUCKET_SIZE,
    BucketLayout,
    build_layout,
    flatten_buckets,
    unflatten_buckets,
)
from repro.comm.compressed import (
    BucketPayload,
    decode_buckets_stack,
    decode_mean_buckets,
    ef_encode_buckets,
    init_error_buckets,
    init_server_buckets,
    is_sign,
)
from repro.comm.exchange import PayloadStack
from repro.comm.errors import CommSpecError
from repro.comm.robust import ROBUST_STRATEGIES, robust_combine, validate_tolerance
from repro.comm.collective import STRATEGIES, make_bucketed_aggregator
from repro.comm.backends import BACKENDS, resolve
from repro.comm.api import CommSpec, make_aggregator

__all__ = [
    "BACKENDS",
    "BucketLayout",
    "BucketPayload",
    "CommSpec",
    "CommSpecError",
    "DEFAULT_BUCKET_SIZE",
    "PayloadStack",
    "ROBUST_STRATEGIES",
    "STRATEGIES",
    "build_layout",
    "decode_buckets_stack",
    "decode_mean_buckets",
    "ef_encode_buckets",
    "flatten_buckets",
    "init_error_buckets",
    "init_server_buckets",
    "is_sign",
    "make_aggregator",
    "make_bucketed_aggregator",
    "resolve",
    "robust_combine",
    "unflatten_buckets",
    "validate_tolerance",
]
