"""The overlap executor: per-group compress → collective pipelining.

``build_overlapped_aggregator`` (reached via ``repro.comm.make_aggregator``
with ``spec.overlap`` set) is a drop-in for the one-shot bucketed
aggregator that executes the
exchange per :class:`~repro.overlap.schedule.OverlapSchedule` group instead
of in one shot. Inside the (fully-manual) ``shard_map`` body the groups are
laid out in reverse-AD availability order as independent dataflow chains:

    encode(g0) → collective(g0) ─┐
    encode(g1) → collective(g1) ─┤→ decode + scatter
    encode(g2) → collective(g2) ─┘

Nothing in group k+1's encode depends on group k's collective, so the XLA
latency-hiding scheduler is free to run collective *k* while *k+1* is still
compressing (and, with the staged grad-fn of :mod:`repro.train.steps`
feeding the step, while earlier layers' backward still runs). On CPU the
fake-device collectives execute inline — the pipeline's wall-clock win there
is ~nil by construction, which is why the bench suite additionally evaluates
the measured per-group component times through :func:`exposure_report`
(the standard pipeline latency model) to report how much communication the
schedule leaves exposed.

Numerics are IDENTICAL to the one-shot path: buckets are compressed by the
same per-bucket kernels on row slices, stochastic compressors draw the same
per-bucket keys (the full ``split`` is computed once and sliced), and wire /
density accounting reduces in the same order — the 5-step trajectory test in
tests/test_overlap.py pins bitwise equality.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.comm import bucketize, compressed, exchange, robust
from repro.comm.collective import _default_backend, _worker_index, world_size
from repro.core.aggregation import AggInfo
from repro.core.compressors import Compressor, ScaledSignCompressor
from repro.obs import telemetry as obs_telemetry
from repro.overlap.schedule import OverlapSchedule
from repro.utils import compat

AxisNames = tuple[str, ...]

# strategies the pipeline can slice per group. ef_alltoall's server-sharded
# bucket streams are partitioned across workers, not availability ranks, so
# it stays on the one-shot path; dense has no compression stage to pipeline
# (train/steps.py routes it to its own GSPMD path before this is reached).
# The robust strategies pipeline their slot exchanges per group and defer the
# order-statistics combine to phase 2, where the per-dtype-group stacks are
# reassembled — same estimator input as the one-shot path, so the combine is
# bitwise-identical (slot-native exchange, PR 10).
OVERLAP_STRATEGIES = ("ef_allgather", "ef_ring", "majority_vote") + robust.ROBUST_STRATEGIES


def make_overlapped_aggregator(
    strategy: str,
    comp: Compressor | None,
    layout: bucketize.BucketLayout,
    schedule: OverlapSchedule,
    mesh,
    ef_axes: AxisNames,
):
    """Deprecated legacy factory — build a :class:`repro.comm.api.CommSpec`
    with ``overlap=OverlapConfig(...)`` and call
    :func:`repro.comm.api.make_aggregator` instead (it derives the schedule
    from the parameter tree). This shim keeps working for callers that built
    their own :class:`OverlapSchedule`."""
    warnings.warn(
        "make_overlapped_aggregator() is deprecated; build a CommSpec with "
        "overlap=OverlapConfig(...) and call repro.comm.make_aggregator(spec, "
        "layout, mesh, ef_axes, params=...)",
        DeprecationWarning,
        stacklevel=2,
    )
    return build_overlapped_aggregator(strategy, comp, layout, schedule, mesh, ef_axes)


def build_overlapped_aggregator(
    strategy: str,
    comp: Compressor | None,
    layout: bucketize.BucketLayout,
    schedule: OverlapSchedule,
    mesh,
    ef_axes: AxisNames,
    *,
    backend=None,
    telemetry: bool = False,
    byz_f: int = 0,
):
    """Schedule-driven aggregator with the same signature/contract as the
    one-shot ``build_bucketed_aggregator``: ``fn(buckets_w, err_w, srv_w,
    key) -> (agg, new_err_w, new_srv_w, info)``.

    ``backend`` carries the payload-exchange transport (see
    :mod:`repro.comm.backends`); each group's exchange is one slot-native
    :class:`~repro.comm.exchange.PayloadStack` view. Fused-mean backends
    (ring / DMA) collapse transport+decode into phase-1 per-hop units;
    gather-style backends issue the collective in phase 1 and defer the mean
    reading to phase 2 — both orders are bitwise-identical to the one-shot
    path. The robust strategies (``byz_f > 0``) stage the views, reassemble
    each dtype group's (W, nb, bs) slot stack in phase 2, and run the
    order-statistics combine on the full group — the identical estimator
    input (and result) as the one-shot robust path. ``telemetry`` adds the
    :class:`repro.obs.telemetry.Telemetry` aux output on ``info.telemetry``;
    here ``group_bytes`` splits the wire bill per *schedule* group (the unit
    the pipeline exposes or hides), feeding the comm-exposure model directly.
    """
    if strategy not in OVERLAP_STRATEGIES:
        raise ValueError(
            f"overlap supports {OVERLAP_STRATEGIES}, got {strategy!r} "
            "(ef_alltoall's server shards aren't availability-sliceable)"
        )
    if schedule.layout is not layout and schedule.layout != layout:
        raise ValueError("schedule was built for a different BucketLayout")
    comp = comp or ScaledSignCompressor()
    if backend is None:
        backend = _default_backend(strategy)
    w = world_size(mesh, ef_axes)
    bs = layout.bucket_size
    ef = ef_axes if len(ef_axes) != 1 else ef_axes[0]
    bucket_bits = comp.wire_bits(bs)
    has_err = strategy != "majority_vote"
    # byz_f == 0 robust collapses to the mean reading (bitwise ef_allgather)
    robust_mode = strategy in robust.ROBUST_STRATEGIES and byz_f > 0
    n_dtype = len(layout.groups)

    def body(buckets, err, srv, key):
        del srv
        widx = _worker_index(ef_axes)
        # traced, so the padding masks never become full-size constants
        masks = [bucketize.valid_mask(layout, gi) for gi in range(n_dtype)]
        keys_full = [None] * n_dtype
        if not comp.deterministic:
            for gi in range(n_dtype):
                gkey = jax.random.fold_in(jax.random.fold_in(key, widx), gi)
                keys_full[gi] = jax.random.split(gkey, buckets[gi][0].shape[0])

        # ---- phase 1: per group, encode slices then issue the collective.
        # Each iteration is an independent dataflow chain — collective k and
        # encode k+1 have no data dependency, which is the pipeline.
        staged = []  # [(slice, encoded/new_err/dens, collective result)]
        wire_bits = 0.0
        grp_bits: list[float] = []  # telemetry: wire split per SCHEDULE group
        for grp in schedule.groups:
            g_bits = 0.0
            for sl in grp.slices:
                b = buckets[sl.group][0][sl.start : sl.stop]
                m = masks[sl.group][sl.start : sl.stop]
                nb = sl.n_buckets
                if strategy == "majority_vote":
                    s = jnp.where(b >= 0, 1.0, -1.0)
                    tot = lax.psum(s, ef_axes)
                    staged.append((sl, None, None, jnp.where(tot >= 0, 1.0, -1.0) * m))
                    wire_bits += (w - 1) * nb * bs
                    g_bits += (w - 1) * nb * bs
                else:
                    e = err[sl.group][0][sl.start : sl.stop]
                    ks = keys_full[sl.group]
                    payload, ne, d_b = compressed.ef_encode_buckets(
                        comp, b, e, mask=m, keys=None if ks is None else ks[sl.start : sl.stop]
                    )
                    view = backend.exchange(comp, payload, bs, ef_axes, w)
                    if robust_mode or not backend.fused_mean:
                        # gather-style transports issue their collective at
                        # exchange time; the decode reading defers to phase 2
                        # (the robust combine always defers — it needs the
                        # reassembled per-group stack)
                        staged.append((sl, ne, d_b, view))
                    else:
                        # fused transports: the whole per-hop exchange is the
                        # schedulable phase-1 unit
                        staged.append((sl, ne, d_b, view.mean()))
                    wire_bits += (w - 1) * nb * bucket_bits
                    g_bits += (w - 1) * nb * bucket_bits
            grp_bits.append(g_bits)

        # ---- phase 2: read the staged exchange views, scatter into full
        # stacks. Robust mode reassembles each dtype group's (W, nb, bs)
        # slot stack from the slice decodes and combines once per group —
        # the one-shot estimator input, so the combine is value-identical.
        outs = [jnp.zeros((g.n_buckets, bs), jnp.float32) for g in layout.groups]
        new_errs = [jnp.zeros((g.n_buckets, bs), jnp.float32) for g in layout.groups]
        dens_full = [jnp.ones((g.n_buckets,), jnp.float32) for g in layout.groups]
        stacks = (
            [jnp.zeros((w, g.n_buckets, bs), jnp.float32) for g in layout.groups]
            if robust_mode
            else []
        )
        lane_w = jnp.zeros((w,), jnp.float32)
        for sl, ne, d_b, result in staged:
            if isinstance(result, exchange.PayloadStack):
                if robust_mode:
                    stacks[sl.group] = (
                        stacks[sl.group].at[:, sl.start : sl.stop].set(result.decoded())
                    )
                    result = None
                else:
                    result = result.mean()
            if result is not None:
                outs[sl.group] = outs[sl.group].at[sl.start : sl.stop].set(result)
            if ne is not None:
                new_errs[sl.group] = new_errs[sl.group].at[sl.start : sl.stop].set(ne)
                dens_full[sl.group] = dens_full[sl.group].at[sl.start : sl.stop].set(d_b)
        if robust_mode:
            for gi in range(n_dtype):
                outs[gi] = robust.combine_stack(strategy, stacks[gi], byz_f)
                if telemetry:
                    lane_w = lane_w + robust.filtered_lane_weights(strategy, stacks[gi], byz_f)

        # identical reduction order to the one-shot body: per dtype group
        # mean, then mean over groups, then pmean
        dens = [jnp.mean(d) if has_err else jnp.float32(1.0) for d in dens_full]
        tele = None
        if telemetry:
            err_norms = [
                obs_telemetry.residual_l2(ne) if has_err else jnp.float32(0.0)
                for ne in new_errs
            ]
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
            tuple(e[None] for e in new_errs) if has_err else (),
            (),
            info,
        )

    stacked = tuple(P(ef) for _ in range(n_dtype))
    in_specs = (stacked, stacked if has_err else (), (), P())
    out_specs = (
        tuple(P() for _ in range(n_dtype)),
        stacked if has_err else (),
        (),
        AggInfo(
            wire_bytes_per_device=P(),
            mean_density=P(),
            telemetry=obs_telemetry.replicated_specs() if telemetry else None,
        ),
    )
    return compat.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, manual_axes=None
    )


# ---------------------------------------------------------------------------
# pipeline latency model (exposure accounting)
# ---------------------------------------------------------------------------


def exposure_report(
    avail_us: tuple[float, ...] | list[float],
    comm_us: tuple[float, ...] | list[float],
    *,
    tail_us: float = 0.0,
) -> dict:
    """Evaluate the pipeline schedule on measured per-group component times.

    ``avail_us[k]`` — wall time (from step start) at which group *k*'s
    compressed payload is ready to ship (backward + compress progress);
    must be non-decreasing in the schedule's issue order. ``comm_us[k]`` —
    the group's collective time on a serial wire. ``tail_us`` — compute that
    still runs after the last payload is ready (decode/apply of early
    groups can hide trailing comm too).

    Standard single-wire pipeline recurrence: collective *k* starts when its
    payload is ready AND the wire is free::

        finish_k = max(finish_{k-1}, avail_k) + comm_k

    ``exposed_us`` is how much of the comm bill the step actually waits on —
    ``finish_{n-1} − (avail_{n-1} + tail_us)``, clamped at 0 — vs
    ``serial_comm_us = Σ comm_k``, the bill the one-shot path pays in full.
    One group degenerates to exposure = its full comm time.
    """
    if len(avail_us) != len(comm_us) or not comm_us:
        raise ValueError("need one availability time per comm time (>= 1 group)")
    if any(b < a for a, b in zip(avail_us, avail_us[1:])):
        raise ValueError(f"avail_us must be non-decreasing, got {avail_us!r}")
    finish = 0.0
    for a, c in zip(avail_us, comm_us):
        finish = max(finish, a) + c
    compute_end = avail_us[-1] + tail_us
    serial = float(sum(comm_us))
    exposed = max(0.0, finish - compute_end)
    return {
        "serial_comm_us": serial,
        "exposed_us": exposed,
        "exposure_frac": exposed / serial if serial else 0.0,
        "finish_us": finish,
        "compute_us": compute_end,
        "hidden_us": serial - exposed,
    }


def proportional_exposure(
    group_bytes: list[float] | tuple[float, ...],
    compute_us: float,
    serial_comm_us: float,
    *,
    tail_us: float = 0.0,
) -> dict:
    """:func:`exposure_report` under the proportional-split assumption.

    When only aggregate times are known — a backward+compress span of
    ``compute_us`` and a serial exchange bill of ``serial_comm_us`` — the
    standard simplification spreads both over the schedule by wire bytes:
    group *k*'s payload is ready at ``compute_us · cum_bytes_k/total`` and
    its hop costs ``serial_comm_us · bytes_k/total``. Both the overlap bench
    suite (measured step/exchange walls) and the ``--overlap`` example
    (analytic wire @ reference bandwidth) feed this one helper so the model
    they report is the same by construction.
    """
    total = float(sum(group_bytes))
    if total <= 0:
        raise ValueError(f"group_bytes must sum positive, got {group_bytes!r}")
    avail, comm, cum = [], [], 0.0
    for b in group_bytes:
        cum += b
        avail.append(compute_us * cum / total)
        comm.append(serial_comm_us * b / total)
    return exposure_report(avail, comm, tail_us=tail_us)
