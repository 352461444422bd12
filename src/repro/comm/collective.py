"""Bucketed gradient collectives under fully-manual ``shard_map``.

Every strategy here runs with **all** mesh axes manual. Buckets are dense
per-worker stacks with no intra-leaf sharding left to preserve, so nothing
needs to stay GSPMD-auto: the
aggregator body sees its worker's ``(n_buckets, bucket_size)`` slice, runs
per-bucket compression + EF, and exchanges fixed-size payloads with plain
manual collectives. Devices that share a worker (model-parallel replicas)
run the identical exchange redundantly — payloads are tiny (that is the
point of compression) and the result is replicated where the update needs
to land anyway.

Strategies (mirroring ``repro.core.aggregation``):

``dense``          pmean of raw buckets — wire ≈ 2·4·d bytes (ring model).
``ef_allgather``   compress → all-gather payloads → decode-mean; worker EF.
``ef_ring``        same payloads, exchanged as W−1 double-buffered
                   ``ppermute`` hops with a fused decompress-accumulate per
                   hop (:mod:`repro.comm.backends.ring`) — same total bytes
                   as ef_allgather, but in per-hop units the overlap
                   scheduler can slide under backward compute.
``ef_alltoall``    double compression: workers chunk the bucket stream,
                   all-to-all routes chunk *j* to worker *j* (the "server"
                   for those buckets), which decode-means, re-compresses with
                   a server-side EF residual, and all-gathers the result.
                   Wire ≈ 2·d/8 bytes, W-independent.
``majority_vote``  sign-of-sum-of-signs, no EF (the known-brittle baseline).
``ef_coord_median`` / ``ef_trimmed_mean`` / ``ef_norm_filter``
                   Byzantine-robust variants: identical payloads and wire
                   bill as ef_allgather, but the decode combines the
                   per-worker slot stack with an order-statistics estimator
                   (:mod:`repro.comm.robust`) parameterized by the declared
                   adversary budget ``byz_f``. Rides ANY backend's slot
                   exchange (all-gather, ppermute ring, remote-DMA ring);
                   ``byz_f=0`` is bitwise-equal to ef_allgather.

Wire accounting is exact per bucket: a payload for one bucket costs
``comp.wire_bits(bucket_size)`` bits and every strategy counts how many
bucket payloads each device *receives* per step.

The payload exchange itself (the hop structure of ef_allgather / ef_ring /
the robust strategies) is delegated to a pluggable
:class:`~repro.comm.backends.CollectiveBackend`, which returns one slot-native
:class:`~repro.comm.exchange.PayloadStack` view per dtype group — strategy
semantics (EF residual updates, wire accounting, robust combines) stay here;
backends only move bytes. Construct through
:func:`repro.comm.api.make_aggregator`; the kwarg factory below is a
deprecated shim.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.comm import bucketize, compressed, robust
from repro.core.aggregation import AggInfo
from repro.core.compressors import Compressor, ScaledSignCompressor
from repro.obs import telemetry as obs_telemetry
from repro.obs import trace as obs_trace
from repro.utils import compat

AxisNames = tuple[str, ...]

EF_STRATEGIES = ("ef_allgather", "ef_ring", "ef_alltoall") + robust.ROBUST_STRATEGIES
STRATEGIES = ("dense",) + EF_STRATEGIES + ("majority_vote",)


def world_size(mesh, ef_axes: AxisNames) -> int:
    w = 1
    for a in ef_axes:
        w *= mesh.shape[a]
    return w


def _worker_index(ef_axes: AxisNames) -> jax.Array:
    """Linearized index of this device's EF worker (row-major over ef_axes)."""
    idx = jnp.int32(0)
    for a in ef_axes:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def _gather_payload(payload, ef_axes: AxisNames):
    """all-gather every payload leaf along a new leading worker axis."""
    return jax.tree.map(lambda x: lax.all_gather(x, ef_axes, tiled=False), payload)


def _default_backend(strategy: str):
    """Backend when the caller did not resolve one (internal/legacy entry):
    the transport each strategy historically used."""
    from repro.comm import backends

    return backends.BACKENDS["ring" if strategy == "ef_ring" else "xla"]


def _pad_buckets(x: jax.Array, target: int) -> jax.Array:
    """Zero-pad the bucket axis of (nb, bs) up to ``target`` buckets."""
    return jnp.pad(x, ((0, target - x.shape[0]), (0, 0)))


def make_bucketed_aggregator(
    strategy: str,
    comp: Compressor | None,
    layout: bucketize.BucketLayout,
    mesh,
    ef_axes: AxisNames,
    *,
    byz_f: int = 0,
):
    """Deprecated legacy factory — build a :class:`repro.comm.api.CommSpec`
    and call :func:`repro.comm.api.make_aggregator` instead. This shim maps
    the old kwargs onto a spec (``byz_f`` → ``ByzConfig(f=...)``) and routes
    through the one validated construction path; returned aggregators are
    identical.
    """
    warnings.warn(
        "make_bucketed_aggregator() is deprecated; build a CommSpec and call "
        "repro.comm.make_aggregator(spec, layout, mesh, ef_axes)",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.comm import api
    from repro.configs.base import ByzConfig

    # negative budgets predate ByzConfig's own range check — surface the
    # canonical ToleranceError, not the config constructor's
    if byz_f < 0:
        robust.validate_tolerance(strategy, byz_f, world_size(mesh, ef_axes))
    spec = api.CommSpec(
        strategy=strategy,
        compressor=comp,
        bucket_size=layout.bucket_size,
        byz=ByzConfig(f=byz_f) if byz_f else None,
    )
    return api.make_aggregator(spec, layout, mesh, ef_axes)


def build_bucketed_aggregator(
    strategy: str,
    comp: Compressor | None,
    layout: bucketize.BucketLayout,
    mesh,
    ef_axes: AxisNames,
    *,
    byz_f: int = 0,
    backend=None,
    telemetry: bool = False,
):
    """Build ``fn(buckets_w, err_w, srv_w, key) -> (agg, new_err_w, new_srv_w,
    info)`` where the ``_w`` pytrees carry a leading stacked EF-world axis
    sharded over ``ef_axes`` and ``agg`` is the replicated aggregated update,
    one ``(n_buckets, bucket_size)`` fp32 array per dtype group.

    Internal constructor behind :func:`repro.comm.api.make_aggregator` —
    assumes the spec-level validation already ran there. ``backend`` is a
    resolved :class:`repro.comm.backends.CollectiveBackend` carrying the
    payload-mean transport (all-gather / ppermute ring / remote-DMA ring);
    ``None`` picks each strategy's historical default. ``byz_f`` is the
    declared adversary budget handed to the robust strategies. ``telemetry``
    adds a :class:`repro.obs.telemetry.Telemetry` aux output on
    ``info.telemetry`` — pure reads of intermediates the body already
    materializes, so the aggregated update / EF-residual trajectory is
    bitwise-identical either way (pinned by tests/test_obs.py).
    """
    comp = comp or ScaledSignCompressor()
    if backend is None:
        backend = _default_backend(strategy)
    w = world_size(mesh, ef_axes)
    bs = layout.bucket_size
    ef = ef_axes if len(ef_axes) != 1 else ef_axes[0]
    bucket_bits = comp.wire_bits(bs)
    has_err = strategy in EF_STRATEGIES
    has_srv = strategy == "ef_alltoall"

    def body(buckets, err, srv, key):
        # traced, so the (nb, bs) padding masks fuse into their consumers
        # instead of riding into the program as full-size constants
        masks = [bucketize.valid_mask(layout, gi) for gi in range(len(layout.groups))]
        outs, new_errs, new_srvs, dens = [], [], [], []
        wire_bits = 0.0
        # telemetry accumulators — per dtype group bits / residual norms,
        # per-lane robust filter weights. Pure reads; dead code when off.
        grp_bits: list[float] = []
        err_norms: list[jax.Array] = []
        lane_w = jnp.zeros((w,), jnp.float32)
        widx = _worker_index(ef_axes)
        for gi, local in enumerate(zip(buckets, err if has_err else buckets)):
            b = local[0][0]  # (nb, bs) this worker's buckets for group gi
            e = local[1][0] if has_err else None
            nb = b.shape[0]
            gkey = None
            if not comp.deterministic:
                gkey = jax.random.fold_in(jax.random.fold_in(key, widx), gi)

            if strategy == "dense":
                outs.append(lax.pmean(b, ef_axes))
                dens.append(jnp.float32(1.0))
                err_norms.append(jnp.float32(0.0))
                wire_bits += 2 * 32 * nb * bs  # fp32 ring all-reduce model
                grp_bits.append(2 * 32 * nb * bs)

            elif strategy == "majority_vote":
                s = jnp.where(b >= 0, 1.0, -1.0)
                tot = lax.psum(s, ef_axes)
                outs.append(jnp.where(tot >= 0, 1.0, -1.0) * masks[gi])
                dens.append(jnp.float32(1.0))
                err_norms.append(jnp.float32(0.0))
                wire_bits += (w - 1) * nb * bs  # d bits per peer payload
                grp_bits.append((w - 1) * nb * bs)

            elif strategy in ("ef_allgather", "ef_ring") or strategy in robust.ROBUST_STRATEGIES:
                payload, ne, d_b = compressed.ef_encode_buckets(
                    comp, b, e, mask=masks[gi], key=gkey
                )
                # ONE slot-native exchange per transport (all-gather /
                # ppermute / remote DMA); the consumer's reading below decides
                # whether the view traces the fused mean or the slot stack
                view = backend.exchange(comp, payload, bs, ef_axes, w)
                if strategy in robust.ROBUST_STRATEGIES and byz_f and telemetry:
                    # decode the stack once, feed both the combine and the
                    # per-lane filter weights — same ops as combine_view
                    stack = view.decoded()
                    outs.append(robust.combine_stack(strategy, stack, byz_f))
                    lane_w = lane_w + robust.filtered_lane_weights(strategy, stack, byz_f)
                elif strategy in robust.ROBUST_STRATEGIES:
                    # byz_f == 0 collapses to view.mean() — the declared-honest
                    # trajectory stays bitwise-equal to ef_allgather/ef_ring on
                    # every backend
                    outs.append(robust.combine_view(strategy, view, byz_f))
                else:
                    outs.append(view.mean())
                with obs_trace.span(obs_trace.SPAN_COMPRESS):  # the residual's worker axis
                    new_errs.append(ne[None])
                dens.append(jnp.mean(d_b))
                err_norms.append(obs_telemetry.residual_l2(ne))
                # every backend moves the same (w−1)·nb payloads per device
                wire_bits += (w - 1) * nb * bucket_bits
                grp_bits.append((w - 1) * nb * bucket_bits)

            else:  # ef_alltoall — double compression over bucket shards
                nbw = compressed.server_shard_buckets(nb, w)
                bp, ep = _pad_buckets(b, w * nbw), _pad_buckets(e, w * nbw)
                mp = _pad_buckets(masks[gi], w * nbw)
                payload, ne, d_b = compressed.ef_encode_buckets(comp, bp, ep, mask=mp)
                with obs_trace.span(obs_trace.SPAN_COMPRESS):
                    new_errs.append(ne[:nb][None])
                dens.append(jnp.mean(d_b[:nb]))
                err_norms.append(obs_telemetry.residual_l2(ne[:nb]))
                # route shard j of every worker's stream to worker j
                shards = jax.tree.map(lambda x: x.reshape(w, nbw, *x.shape[1:]), payload)
                routed = jax.tree.map(
                    lambda x: lax.all_to_all(x, ef_axes, split_axis=0, concat_axis=0, tiled=True),
                    shards,
                )
                s_j = compressed.decode_mean_buckets(comp, routed, bs)  # (nbw, bs)
                # server-side EF re-compression of the mean shard
                srv_mask = lax.dynamic_slice_in_dim(mp, widx * nbw, nbw, axis=0)
                q_payload, new_sv, _ = compressed.ef_encode_buckets(
                    comp, s_j, srv[gi][0], mask=srv_mask
                )
                new_srvs.append(new_sv[None])
                gathered = _gather_payload(q_payload, ef_axes)  # leaves (w, nbw, ...)
                flat = jax.tree.map(lambda x: x.reshape(w * nbw, *x.shape[2:]), gathered)
                full = compressed.decode_buckets(comp, compressed.BucketPayload(data=flat.data), bs)
                outs.append(full[:nb])
                # a2a: recv (w−1) shards of nbw payloads; ag: recv (w−1) more
                wire_bits += 2 * (w - 1) * nbw * bucket_bits
                grp_bits.append(2 * (w - 1) * nbw * bucket_bits)

        tele = None
        if telemetry:
            tele = obs_telemetry.Telemetry(
                err_l2=lax.pmean(jnp.stack(err_norms), ef_axes),
                density=lax.pmean(jnp.stack(dens), ef_axes),
                wire_bytes=jnp.float32(wire_bits / 8.0),
                group_bytes=jnp.asarray(grp_bits, jnp.float32) / 8.0,
                filtered_lanes=lane_w,
            )
        info = AggInfo(
            wire_bytes_per_device=jnp.float32(wire_bits / 8.0),
            mean_density=lax.pmean(jnp.mean(jnp.stack(dens)), ef_axes),
            telemetry=tele,
        )
        return (
            tuple(outs),
            tuple(new_errs) if has_err else (),
            tuple(new_srvs) if has_srv else (),
            info,
        )

    n_groups = len(layout.groups)
    stacked = tuple(P(ef) for _ in range(n_groups))
    in_specs = (
        stacked,
        stacked if has_err else (),
        stacked if has_srv else (),
        P(),
    )
    out_specs = (
        tuple(P() for _ in range(n_groups)),
        stacked if has_err else (),
        stacked if has_srv else (),
        AggInfo(
            wire_bytes_per_device=P(),
            mean_density=P(),
            telemetry=obs_telemetry.replicated_specs() if telemetry else None,
        ),
    )
    return compat.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, manual_axes=None
    )
