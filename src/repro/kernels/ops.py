"""Jit'd public wrappers around the EF-sign kernels.

``ef_sign_step(g, e, gamma)`` runs the full fused pipeline on an arbitrary
flat tensor:

    scale  = ‖γg+e‖₁ / d        (pass 1: blocked partial-L1 + tiny host sum)
    words  = bitpack(sign(γg+e))
    e_new  = (γg+e) − scale·sign(γg+e)
    Δ      = scale·sign(γg+e)   (reconstructable from words+scale — not returned)

Implementation selection: the Pallas path runs on TPU (or anywhere with
``interpret=True``); the jnp reference path is the default elsewhere so that
the 512-device dry-run never traces a Pallas call. ``force`` overrides for
tests and for the on-chip comparison against the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ef_sign, ref

LANE = ref.LANE


def _backend() -> str:
    return jax.default_backend()


def _use_pallas(force: str | None) -> tuple[bool, bool]:
    """→ (use_pallas, interpret)."""
    if force == "pallas":
        return True, _backend() != "tpu"
    if force == "ref":
        return False, False
    return (_backend() == "tpu"), False


def pad_to_rows(x: jax.Array) -> tuple[jax.Array, int]:
    """Flatten and zero-pad to a (rows, LANE) view; returns (view, orig_n)."""
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    rows = max(1, (n + LANE - 1) // LANE)
    flat = jnp.pad(flat, (0, rows * LANE - n))
    return flat.reshape(rows, LANE), n


@functools.partial(jax.jit, static_argnames=("force",))
def ef_sign_step(g: jax.Array, e: jax.Array, gamma: jax.Array, *, force: str | None = None):
    """Fused EF-SIGNSGD compression of one tensor.

    Returns ``(words, scale, e_new)`` with shapes ``((rows,32) u32, () f32,
    g.shape f32)``. Note the padded tail influences the L1 sum by 0 (zeros) —
    the scale divides by the *true* n, matching Alg. 1 exactly.
    """
    use_pallas, interpret = _use_pallas(force)
    gv, n = pad_to_rows(g)
    ev, _ = pad_to_rows(e)
    gamma = jnp.asarray(gamma, jnp.float32)

    if use_pallas:
        partial = ef_sign.l1_partial(gv, ev, gamma, interpret=interpret)
    else:
        partial = ref.l1_partial_ref(gv, ev, gamma)
    scale = jnp.sum(partial) / float(n)

    if use_pallas:
        words, e_new = ef_sign.ef_sign_compress(gv, ev, gamma, scale, interpret=interpret)
    else:
        words, e_new = ref.ef_sign_compress_ref(gv, ev, gamma, scale)
    e_new = e_new.reshape(-1)[:n].reshape(g.shape)
    return words, scale, e_new


@functools.partial(jax.jit, static_argnames=("force",))
def decompress_mean(words: jax.Array, scales: jax.Array, *, force: str | None = None):
    """Mean of W sign payloads: (W,rows,32) u32 + (W,) f32 → (rows,LANE) f32."""
    use_pallas, interpret = _use_pallas(force)
    if use_pallas:
        return ef_sign.sign_decompress_mean(words, scales, interpret=interpret)
    return ref.sign_decompress_mean_ref(words, scales)


BUCKET_PALLAS_MULTIPLE = ef_sign.BUCKET_COLS  # one 128-lane row of words per block


@functools.partial(jax.jit, static_argnames=("fixed_scale", "force"))
def ef_sign_bucket_step(
    g: jax.Array,
    e: jax.Array,
    *,
    fixed_scale: float | None = None,
    force: str | None = None,
):
    """Fused EF sign compression of a whole bucket stack (repro.comm path).

    ``g``/``e`` are (n_buckets, bucket_size) f32 (update and EF residual);
    returns ``(words (nb, bs/32) u32, scales (nb,) f32, e_new (nb, bs) f32,
    dens (nb,) f32)``. The stats pass emits per-bucket (L1, L2²) from ONE read
    of (g, e), so the scale AND the density metric φ = ‖p‖₁²/(bs·‖p‖₂²) come
    for free — no second pass over p as the old ``vmap(density)`` cost.
    Scaled sign uses the per-bucket L1 mean ‖p_b‖₁/bs (the padded tail of the
    last bucket is zero, deflating its scale slightly — EF absorbs the
    difference and the unflatten slice discards the tail); ``fixed_scale``
    selects the unscaled-sign wire format instead (scale is fixed but the
    stats pass still supplies the density).
    """
    nb, bs = g.shape
    if bs % 32 != 0:
        raise ValueError(f"bucket_size must be a multiple of 32, got {bs}")
    use_pallas, interpret = _use_pallas(force)
    if use_pallas:
        l1, l2sq = ef_sign.bucket_stats(g, e, interpret=interpret)
    else:
        l1, l2sq = ref.bucket_stats_ref(g, e)
    dens = jnp.where(l2sq > 0, l1 * l1 / (float(bs) * l2sq), jnp.float32(1.0))
    if fixed_scale is not None:
        scales = jnp.full((nb,), fixed_scale, jnp.float32)
    else:
        scales = l1 / float(bs)
    if use_pallas:
        words, e_new = ef_sign.bucket_ef_sign_compress(g, e, scales, interpret=interpret)
    else:
        words, e_new = ref.bucket_ef_sign_compress_ref(g, e, scales)
    return words, scales, e_new, dens


@functools.partial(jax.jit, static_argnames=("force",))
def bucket_decompress_mean(words: jax.Array, scales: jax.Array, *, force: str | None = None):
    """Mean of W bucket payload stacks: (W, nb, bs/32) + (W, nb) → (nb, bs)."""
    use_pallas, interpret = _use_pallas(force)
    if use_pallas:
        return ef_sign.bucket_sign_decompress_mean(words, scales, interpret=interpret)
    return ref.bucket_decompress_mean_ref(words, scales)


@functools.partial(jax.jit, static_argnames=("force",))
def bucket_sign_accumulate(
    acc: jax.Array, words: jax.Array, scales: jax.Array, *, force: str | None = None
):
    """Fused decompress-accumulate (ring hop): acc + scaleᵦ·unpack(wordsᵦ).

    (nb, bs) f32 + (nb, bs/32) u32 + (nb,) f32 → (nb, bs) f32.
    """
    use_pallas, interpret = _use_pallas(force)
    if use_pallas:
        return ef_sign.bucket_sign_accumulate(acc, words, scales, interpret=interpret)
    return ref.bucket_sign_accumulate_ref(acc, words, scales)


def bucket_sign_decode(words: jax.Array, scales: jax.Array, bucket_size: int) -> jax.Array:
    """Single payload stack decode: (nb, bs/32) + (nb,) → (nb, bs)."""
    del bucket_size  # implied by the word count; kept for call-site clarity
    return ref.bucket_sign_decode_ref(words, scales)


def delta_from(words: jax.Array, scale: jax.Array, n: int, shape) -> jax.Array:
    """Reconstruct Δ = scale·sign(p) from a payload (for single-worker EF)."""
    out = ref.sign_decompress_ref(words, scale)
    return out.reshape(-1)[:n].reshape(shape)


def modeled_hbm_bytes_per_elem(fused: bool) -> float:
    """TPU-side HBM-traffic model for the EF-sign compression stage.

    Fused Pallas kernel (two passes sharing reads):
      L1 pass: read g + read e (8 B);  compress pass: read g + read e,
      write e' (12 B), write words (4/32 B) → 20.125 B/elem.
    Unfused XLA pipeline (each stage materializes):
      p = γg+e (r8, w4); scale = Σ|p| (r4); words = pack(sign p) (r4, w1/8);
      Δ = scale·unpack (r1/8, w4); e' = p−Δ (r8, w4) → 36.25 B/elem.

    The ratio (~1.8×) is the roofline bound on the compression stage; the
    kernels suite records both terms so the model is pinned by the baseline
    gate and any change to it is an explicit diff.
    """
    if fused:
        return 8.0 + 12.0 + 4.0 / 32.0
    return (8 + 4) + 4 + (4 + 4 / 32) + (4 / 32 + 4) + (8 + 4)
