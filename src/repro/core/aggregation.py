"""Distributed gradient aggregation strategies (per-LEAF granularity).

The paper analyses single-worker EF-SGD and explicitly names the multi-worker
extension as future work (§7). This module supplies that extension — it is the
piece that turns the paper's operator into a *distributed systems* feature.

This is the ``bucket_size=None`` fallback of the gradient-exchange stack: the
default training path runs the same strategies at fixed-size-BUCKET
granularity through :mod:`repro.comm` (realistic wire format, fully-manual
collectives). The per-leaf implementations below
remain for the giant-model dry-run because they are *sharding-preserving*.

All functions here run **inside** ``shard_map`` over the data-parallel mesh
axes (``('data',)`` single-pod tp / ``('pod',)`` multi-pod); the remaining
mesh axes stay in GSPMD-auto mode so tensor/expert/fsdp parallelism composes
below us. For that reason every tensor op here is *sharding-preserving*:
sign payloads are bit-packed along each leaf's LAST axis only (never a full
flatten, which would force XLA to replicate fsdp-sharded leaves), and
decompress-accumulate runs as a fori-loop over workers (two live buffers
instead of a (W, leaf) materialization).

Strategies
----------
dense
    ``lax.pmean`` of fp32 gradients — the SGD baseline; ring all-reduce moves
    ≈ 2·4·d bytes per device.

ef_allgather   (paper-faithful multi-worker EF)
    worker i:  p_i = u_i + e_i ;  payload_i = C(p_i) ;  e_i ← p_i − C⁻¹(payload_i)
    exchange:  all-gather payloads; every worker decompresses all W payloads
    and averages. Wire: (W−1)·(d/8 + 4) bytes received per device for sign —
    a 64/W-fold reduction vs dense; exact at small W, fades as W grows.

ef_alltoall    (beyond paper: double compression, à la DoubleSqueeze/1-bit Adam)
    worker i chunks p_i (last axis) into W pieces and sign-compresses each;
    all-to-all routes chunk j of every worker to worker j; worker j
    decompresses + averages its chunk, re-compresses the mean with a second,
    sharded error buffer (server-side EF), and the result is all-gathered.
    Wire ≈ 2·d/8 bytes — W-independent, the full ~32×.

majority_vote  (Bernstein et al. '19 baseline — known non-convergent cases)
    sign of the sum of signs; no error feedback.

Every strategy returns ``(aggregated_update, new_state, info)`` where ``info``
carries the wire-byte count (used by the roofline cross-check) and the density
φ of the corrected steps (Fig 2).
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.compressors import (
    Compressor,
    ScaledSignCompressor,
    SignPayload,
    UnscaledSignCompressor,
    density,
    packed_len,
    sign_decode,
    sign_encode,
    unpack_signs_last,
)

AxisNames = tuple[str, ...]

_SIGN_TYPES = (ScaledSignCompressor, UnscaledSignCompressor)


class AggInfo(NamedTuple):
    wire_bytes_per_device: jax.Array  # what this device receives per step
    mean_density: jax.Array  # mean φ(p) over leaves (Lemma 8 quality)
    # repro.obs.telemetry.Telemetry when CommSpec.telemetry="full"; the None
    # default is an EMPTY pytree child, so off-mode AggInfo has the same two
    # leaves (and the same shard_map out_specs) it always had
    telemetry: Any = None


def info_dict(info: AggInfo) -> dict[str, float]:
    """Pull an AggInfo off-device into plain floats.

    The bench subsystem (repro.bench) and the training-loop metric stream both
    consume this — it is the single place the wire-byte accounting crosses
    from traced values to host-side records.
    """
    return {
        "wire_bytes_per_device": float(info.wire_bytes_per_device),
        "mean_density": float(info.mean_density),
    }


def dense_wire_bytes(n_params: int) -> float:
    """Ring all-reduce wire model for fp32: ≈ 2·4·d bytes per device."""
    return 2.0 * 4.0 * n_params


def sign_allgather_wire_bytes(n_params: int, world: int) -> float:
    """§6.1 accounting: (W−1) payloads of (d + 32·#leaves) bits received;
    single-leaf approximation (d/8 + 4 bytes per payload)."""
    return (world - 1) * (n_params / 8.0 + 4.0)


def bucketed_sign_allgather_wire_bytes(n_buckets: int, bucket_size: int, world: int) -> float:
    """Bucketed ef_allgather wire model: (W−1) sign payloads per bucket, each
    bucket_size bits + one fp32 scale (repro.comm exchange granularity)."""
    return (world - 1) * n_buckets * (bucket_size / 8.0 + 4.0)


def bucketed_sign_alltoall_wire_bytes(n_buckets: int, bucket_size: int, world: int) -> float:
    """Bucketed double compression: each device receives (W−1) bucket-shard
    payloads in the all-to-all and (W−1) more in the final all-gather."""
    shard = -(-n_buckets // world)
    return 2.0 * (world - 1) * shard * (bucket_size / 8.0 + 4.0)


def fed_round_wire_bytes(n_buckets: int, bucket_size: int, cohort: int) -> float:
    """Federated-round wire model (sign family): the server receives one sign
    payload per bucket from each SAMPLED client — ``cohort`` payloads of
    bucket_size bits + one fp32 scale. Only sampled clients pay bytes; the
    bill is independent of ``n_clients`` (repro.fed exchange granularity)."""
    return cohort * n_buckets * (bucket_size / 8.0 + 4.0)


def bucketed_sign_ring_per_step_bytes(n_buckets: int, bucket_size: int) -> float:
    """One ring hop: every device receives one full sign payload per bucket
    (bucket_size bits + one fp32 scale) from its neighbor."""
    return n_buckets * (bucket_size / 8.0 + 4.0)


def bucketed_sign_ring_wire_bytes(n_buckets: int, bucket_size: int, world: int) -> float:
    """Ring exchange total: per-step bytes × (W−1) serial hops — the same
    bill as ef_allgather, paid in (W−1) independently schedulable units."""
    return (world - 1) * bucketed_sign_ring_per_step_bytes(n_buckets, bucket_size)


def bucketed_sign_robust_wire_bytes(n_buckets: int, bucket_size: int, world: int) -> float:
    """Robust variants (coord-median / trimmed-mean / norm-filter) ship
    exactly the ef_allgather payloads over the same all-gather — robustness
    is pure decode-side compute, so the wire bill is identical by design."""
    return bucketed_sign_allgather_wire_bytes(n_buckets, bucket_size, world)


def robust_decode_cost_model(
    n_buckets: int, bucket_size: int, world: int, *, byz_f: int = 1, kind: str = "ef_coord_median"
) -> dict:
    """Analytic decode-side cost of a robust combine (repro.comm.robust).

    What the robust strategies pay for the unchanged wire bill:
    ``stack_hbm_bytes`` is the (W, n_buckets, bucket_size) fp32
    materialization the two-buffer running mean of ef_allgather avoids;
    ``sort_flops`` models the per-coordinate worker-axis sort (W log2 W
    compares); ``reduce_flops`` the estimator-specific combine (mid-select,
    kept-order-stat mean, or distance pass + filtered mean). The byz bench
    suite gates these exactly, like the wire models of the other strategies.
    """
    d = float(n_buckets * bucket_size)
    sort = d * world * math.log2(world) if world > 1 else 0.0
    if kind == "ef_coord_median":
        reduce_flops = d
    elif kind == "ef_trimmed_mean":
        reduce_flops = d * (world - 2 * byz_f)
    elif kind == "ef_norm_filter":
        # distance-to-median pass (3 flops/coord/worker) + filtered mean
        reduce_flops = d * (3 * world + (world - byz_f))
    else:
        raise ValueError(f"unknown robust kind {kind!r}")
    return {
        "stack_hbm_bytes": 4.0 * world * d,
        "sort_flops": float(sort),
        "reduce_flops": float(reduce_flops),
        "total_flops": float(sort + reduce_flops),
    }


def ring_latency_model(
    n_buckets: int, bucket_size: int, world: int, *, bytes_per_us: float
) -> dict:
    """Analytic latency of the ring exchange on a ``bytes_per_us`` wire.

    Returns ``{"steps", "per_step_bytes", "per_step_us", "total_us"}`` — the
    per-step term is what the overlap scheduler hides behind backward
    compute; the bench overlap suite gates these against its baseline just
    like the wire-byte models of the existing strategies.
    """
    steps = max(0, world - 1)
    per_step = bucketed_sign_ring_per_step_bytes(n_buckets, bucket_size)
    per_step_us = per_step / bytes_per_us
    return {
        "steps": steps,
        "per_step_bytes": per_step,
        "per_step_us": per_step_us,
        "total_us": steps * per_step_us,
    }


# reference single-wire bandwidth shared by the analytic latency models and
# the bench suites (src/repro/bench/suites/{overlap,backends}.py)
REF_WIRE_BYTES_PER_US = 1250.0

# per-hop issue overhead of an in-kernel remote DMA: semaphore signal/wait +
# descriptor setup, no XLA collective dispatch on the critical path
DMA_HOP_LAUNCH_US = 1.0
# one-shot XLA collective: runtime dispatch + scheduler fence ahead of the wire
COLLECTIVE_LAUNCH_US = 10.0


def dma_ring_latency_model(
    n_buckets: int,
    bucket_size: int,
    world: int,
    *,
    bytes_per_us: float = REF_WIRE_BYTES_PER_US,
    hop_launch_us: float = DMA_HOP_LAUNCH_US,
    collective_launch_us: float = COLLECTIVE_LAUNCH_US,
) -> dict:
    """Analytic latency of the ``pallas_dma`` backend vs the one-shot
    all-gather — the accept/reject oracle behind ``backend="auto"`` promotion
    (``repro.comm.backends.recommend_backend``) and the ``backends`` bench
    suite's gate.

    Both transports move the identical (W−1)·nb sign payloads, so the
    comparison is pure launch structure: the DMA ring pays ``hop_launch_us``
    per hop (in-kernel semaphore + descriptor issue; the fused
    decompress-accumulate rides the DMA wait, adding nothing to the critical
    path), the all-gather pays one ``collective_launch_us`` dispatch up
    front. ``accept`` is True when the ring's total does not exceed the
    all-gather's — with the defaults that holds up to W−1 ≤ 10 hops, past
    which per-hop overhead has eaten the dispatch saving.
    """
    steps = max(0, world - 1)
    per_hop_bytes = bucketed_sign_ring_per_step_bytes(n_buckets, bucket_size)
    per_hop_us = hop_launch_us + per_hop_bytes / bytes_per_us
    dma_total_us = steps * per_hop_us
    allgather_bytes = bucketed_sign_allgather_wire_bytes(n_buckets, bucket_size, world)
    allgather_us = (collective_launch_us if steps else 0.0) + allgather_bytes / bytes_per_us
    return {
        "steps": steps,
        "per_hop_bytes": per_hop_bytes,
        "per_hop_us": per_hop_us,
        "dma_total_us": dma_total_us,
        "allgather_us": allgather_us,
        "accept": bool(dma_total_us <= allgather_us),
    }


class AggState(NamedTuple):
    worker_error: Any  # per-worker EF residual (pytree like params) or ()
    server_error: Any  # sharded server-side residual for double compression or ()
    key: jax.Array
    steps: jax.Array


def _axis_size(axis_names: AxisNames) -> int:
    w = 1
    for a in axis_names:
        w = w * lax.axis_size(a)
    return w


def _chunk_last(n_last: int, w: int) -> int:
    """Per-worker chunk of the last axis, padded so w·chunk ≥ n_last, %32==0."""
    per = (n_last + w - 1) // w
    return ((per + 31) // 32) * 32


def init_agg_state(
    strategy: str,
    params,
    *,
    world: int = 1,
    seed: int = 0,
    error_dtype=jnp.float32,
    bucket_size: int | None = None,
) -> AggState:
    """Build the aggregation state matching ``strategy``.

    ``world`` is the EF world size. With ``bucket_size`` set (the default
    training path, :mod:`repro.comm`) residuals are held per BUCKET — fp32
    ``(n_buckets, bucket_size)`` stacks per dtype group — and the
    double-compression server error is one bucket shard per worker. With
    ``bucket_size=None`` (per-leaf fallback) residuals mirror the param tree
    and the server error is sharded by last-axis chunk.
    """
    if bucket_size is not None:
        # local import: repro.comm depends on this module for AggInfo
        from repro.comm import bucketize, compressed

        from repro.comm.robust import ROBUST_STRATEGIES

        layout = bucketize.build_layout(params, bucket_size)
        worker_error = (
            compressed.init_error_buckets(layout)
            if strategy in ("ef_allgather", "ef_ring", "ef_alltoall") + ROBUST_STRATEGIES
            else ()
        )
        server_error = (
            compressed.init_server_buckets(layout, world)
            if strategy == "ef_alltoall"
            else ()
        )
        return AggState(
            worker_error=worker_error,
            server_error=server_error,
            key=jax.random.PRNGKey(seed),
            steps=jnp.int32(0),
        )

    zeros = lambda x: jnp.zeros(x.shape, error_dtype)
    worker_error: Any = ()
    server_error: Any = ()
    if strategy == "ef_ring":
        raise ValueError(
            "ef_ring is bucketed-only (repro.overlap.ring): the per-leaf "
            "fallback has no ring implementation — set a bucket_size"
        )
    if strategy in ("ef_coord_median", "ef_trimmed_mean", "ef_norm_filter"):
        raise ValueError(
            f"{strategy} is bucketed-only (repro.comm.robust): the per-leaf "
            "fallback has no robust decode path — set a bucket_size"
        )
    if strategy in ("ef_allgather", "ef_alltoall"):
        worker_error = jax.tree.map(zeros, params)
    if strategy == "ef_alltoall":
        def _server_chunk(x):
            c = _chunk_last(x.shape[-1], world)
            return jnp.zeros(x.shape[:-1] + (c,), error_dtype)

        server_error = jax.tree.map(_server_chunk, params)
    return AggState(
        worker_error=worker_error,
        server_error=server_error,
        key=jax.random.PRNGKey(seed),
        steps=jnp.int32(0),
    )


# ---------------------------------------------------------------------------
# dense baseline
# ---------------------------------------------------------------------------


def dense_mean(updates, state: AggState, axis_names: AxisNames, comp=None):
    out = jax.tree.map(lambda u: lax.pmean(u, axis_names), updates)
    nbytes = 2 * 4 * sum(x.size for x in jax.tree.leaves(updates))  # ring AR ≈ 2·d·4B
    info = AggInfo(
        wire_bytes_per_device=jnp.float32(nbytes),
        mean_density=jnp.float32(1.0),
    )
    return out, state._replace(steps=state.steps + 1), info


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def _decode_mean_fori(gathered: SignPayload, shape, w: int) -> jax.Array:
    """mean_w scale_w·signs_w with two live buffers (no (W, leaf) blowup)."""
    last = shape[-1]

    def body(i, acc):
        words = lax.dynamic_index_in_dim(gathered.words, i, axis=0, keepdims=False)
        scale = lax.dynamic_index_in_dim(gathered.scale, i, axis=0, keepdims=False)
        return acc + scale * unpack_signs_last(words, last).reshape(shape)

    acc = lax.fori_loop(0, w, body, jnp.zeros(shape, jnp.float32))
    return acc / w


def _generic_roundtrip(comp, p, key):
    flat = p.reshape(-1)
    payload = comp.compress(flat, key=key)
    return payload, comp.decompress(payload, flat.shape[0]).reshape(p.shape)


# ---------------------------------------------------------------------------
# paper-faithful multi-worker EF: compress → all-gather → decompress → mean
# ---------------------------------------------------------------------------


def ef_allgather(
    updates,
    state: AggState,
    axis_names: AxisNames,
    comp: Compressor | None = None,
):
    comp = comp or ScaledSignCompressor()
    is_sign = isinstance(comp, _SIGN_TYPES)
    key, sub = jax.random.split(state.key)
    leaves, treedef = jax.tree.flatten(updates)
    errs = jax.tree.leaves(state.worker_error)
    keys = (
        list(jax.random.split(sub, len(leaves)))
        if not comp.deterministic
        else [None] * len(leaves)
    )
    w = _axis_size(axis_names)

    outs, new_errs, dens, bits = [], [], [], 0
    for u, e, k in zip(leaves, errs, keys):
        p = u.astype(e.dtype) + e
        dens.append(density(p))
        if is_sign:
            payload = sign_encode(p, scaled=isinstance(comp, ScaledSignCompressor))
            delta_local = sign_decode(payload, p.shape)
            gathered = lax.all_gather(payload, axis_names, tiled=False)
            mean = _decode_mean_fori(gathered, p.shape, w)
        else:
            payload, delta_local = _generic_roundtrip(comp, p, k)
            gathered = lax.all_gather(payload, axis_names, tiled=False)
            n = u.size
            delta_all = jax.vmap(lambda pl: comp.decompress(pl, n))(gathered)
            mean = jnp.mean(delta_all, axis=0).reshape(p.shape)
        new_errs.append((p - delta_local).astype(e.dtype))
        outs.append(mean.astype(u.dtype))
        bits += comp.wire_bits(u.size)

    info = AggInfo(
        wire_bytes_per_device=jnp.float32((w - 1) * bits / 8.0),
        mean_density=lax.pmean(jnp.mean(jnp.stack(dens)), axis_names),
    )
    new_state = AggState(
        worker_error=jax.tree.unflatten(treedef, new_errs),
        server_error=state.server_error,
        key=key,
        steps=state.steps + 1,
    )
    return jax.tree.unflatten(treedef, outs), new_state, info


# ---------------------------------------------------------------------------
# beyond paper: all-to-all double compression (W-independent 32×)
# ---------------------------------------------------------------------------


def ef_alltoall(
    updates,
    state: AggState,
    axis_names: AxisNames,
    comp: Compressor | None = None,
):
    comp = comp or ScaledSignCompressor()
    if not isinstance(comp, _SIGN_TYPES):
        raise ValueError("ef_alltoall supports sign compressors (wire format)")
    scaled = isinstance(comp, ScaledSignCompressor)
    w = _axis_size(axis_names)
    leaves, treedef = jax.tree.flatten(updates)
    errs = jax.tree.leaves(state.worker_error)
    srv = jax.tree.leaves(state.server_error)

    outs, new_errs, new_srv, dens, bits = [], [], [], [], 0
    for u, e, se in zip(leaves, errs, srv):
        p = u.astype(e.dtype) + e
        dens.append(density(p))
        last = p.shape[-1]
        c = _chunk_last(last, w)  # == se.shape[-1]
        pp = jnp.pad(p, [(0, 0)] * (p.ndim - 1) + [(0, w * c - last)])
        # chunks on a leading axis: (w, ..., c)
        chunks = jnp.moveaxis(pp.reshape(*p.shape[:-1], w, c), -2, 0)

        # 1) per-chunk compression at the worker
        def enc(x):
            return sign_encode(x, scaled=scaled)

        payload = jax.vmap(enc)(chunks)  # words (w, ..., m), scale (w,)
        delta_chunks = jax.vmap(lambda pl: sign_decode(pl, chunks.shape[1:]))(payload)
        delta_local = jnp.moveaxis(delta_chunks, 0, -2).reshape(*p.shape[:-1], w * c)
        delta_local = delta_local[..., :last]
        new_errs.append((p - delta_local).astype(e.dtype))

        # 2) all-to-all: worker j receives chunk j from every worker
        routed = jax.tree.map(
            lambda x: lax.all_to_all(x, axis_names, split_axis=0, concat_axis=0, tiled=True),
            payload,
        )
        s_j = _decode_mean_fori(routed, chunks.shape[1:], w)  # mean over workers

        # 3) server-side EF re-compression of the mean
        q_in = s_j + se
        q_payload = sign_encode(q_in, scaled=scaled)
        q_delta = sign_decode(q_payload, q_in.shape)
        new_srv.append((q_in - q_delta).astype(se.dtype))

        # 4) all-gather the re-compressed chunk payloads; decode locally
        gathered = lax.all_gather(q_payload, axis_names, tiled=False)  # (w, ..., m)

        def body(i, acc):
            words = lax.dynamic_index_in_dim(gathered.words, i, axis=0, keepdims=False)
            scale = lax.dynamic_index_in_dim(gathered.scale, i, axis=0, keepdims=False)
            chunk = scale * unpack_signs_last(words, c).reshape(q_in.shape)
            return lax.dynamic_update_index_in_dim(acc, chunk, i, axis=0)

        full = lax.fori_loop(0, w, body, jnp.zeros((w,) + q_in.shape, jnp.float32))
        out = jnp.moveaxis(full, 0, -2).reshape(*p.shape[:-1], w * c)[..., :last]
        outs.append(out.astype(u.dtype))

        leaf_rows = math.prod(p.shape[:-1]) if p.ndim > 1 else 1
        chunk_bits = leaf_rows * (packed_len(c) * 32) + 32
        # a2a: recv (w−1) chunks; ag: recv (w−1) chunks
        bits += 2 * (w - 1) * chunk_bits

    info = AggInfo(
        wire_bytes_per_device=jnp.float32(bits / 8.0),
        mean_density=lax.pmean(jnp.mean(jnp.stack(dens)), axis_names),
    )
    new_state = AggState(
        worker_error=jax.tree.unflatten(treedef, new_errs),
        server_error=jax.tree.unflatten(treedef, new_srv),
        key=state.key,
        steps=state.steps + 1,
    )
    return jax.tree.unflatten(treedef, outs), new_state, info


# ---------------------------------------------------------------------------
# majority vote (no EF) — the brittle baseline
# ---------------------------------------------------------------------------


def majority_vote(updates, state: AggState, axis_names: AxisNames, comp=None):
    """x ← x − γ·sign(Σᵢ sign(gᵢ)) — signSGD with majority vote."""

    def _vote(u):
        s = jnp.where(u >= 0, 1.0, -1.0).astype(jnp.float32)
        tot = lax.psum(s, axis_names)
        return jnp.where(tot >= 0, 1.0, -1.0).astype(u.dtype)

    out = jax.tree.map(_vote, updates)
    d = sum(x.size for x in jax.tree.leaves(updates))
    w = _axis_size(axis_names)
    # in practice: all-gather of d-bit payloads + local vote
    info = AggInfo(
        wire_bytes_per_device=jnp.float32((w - 1) * d / 8.0),
        mean_density=jnp.float32(1.0),
    )
    return out, state._replace(steps=state.steps + 1), info


STRATEGIES = {
    "dense": dense_mean,
    "ef_allgather": ef_allgather,
    "ef_alltoall": ef_alltoall,
    "majority_vote": majority_vote,
}


def aggregate(strategy: str, updates, state: AggState, axis_names: AxisNames, comp=None):
    fn = STRATEGIES.get(strategy)
    if fn is None:
        raise ValueError(f"unknown aggregation strategy {strategy!r}")
    return fn(updates, state, axis_names, comp)
