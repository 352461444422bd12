"""Pallas TPU kernels for fused error-feedback sign compression.

The compression path (Alg. 1 lines 4-7) is purely memory-bound: every byte of
gradient is read, signed, packed, and a residual written back. Composed from
stock XLA ops this costs ≥4 HBM round-trips of the tensor (p = γg+e; |p| sum;
sign+pack; e' = p−Δ). The kernels below fuse each stage into a single
HBM→VMEM→HBM pass:

  * ``l1_partial``          — per-row |γg+e| partial sums (reduction pass 1)
  * ``ef_sign_compress``    — γg+e → packed sign words + new residual, fused
  * ``sign_decompress_mean``— unpack W gathered payloads and average them

Layout: flat tensors are viewed as (rows, 1024) f32; rows are tiled into
VMEM blocks of BLOCK_ROWS×1024 (512 KiB per operand — three operands resident
≈ 1.5 MiB, comfortably inside the ~16 MiB VMEM budget with double buffering).
1024 lanes = 8×128 VPU tiles; the pack's reduction axis (32) stays in-register.

Validated in ``interpret=True`` mode on CPU against ``ref.py`` (tests sweep
rows/dtypes); TPU (v5e) is the compile target.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 1024
WORDS_PER_ROW = LANE // 32
BLOCK_ROWS = 128  # 128×1024 f32 = 512 KiB per operand block


def _grid(rows: int, block_rows: int) -> int:
    assert rows % block_rows == 0, (rows, block_rows)
    return rows // block_rows


# ---------------------------------------------------------------------------
# pass 1: per-row L1 of p = γg + e
# ---------------------------------------------------------------------------


def _l1_partial_kernel(gamma_ref, g_ref, e_ref, out_ref):
    gamma = gamma_ref[0]
    p = gamma * g_ref[...].astype(jnp.float32) + e_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.sum(jnp.abs(p), axis=-1)


def l1_partial(g, e, gamma, *, block_rows: int = BLOCK_ROWS, interpret: bool = False):
    rows = g.shape[0]
    block_rows = min(block_rows, rows)
    grid = (_grid(rows, block_rows),)
    return pl.pallas_call(
        _l1_partial_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),  # γ broadcast to every block
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((rows,), jnp.float32),
        interpret=interpret,
    )(gamma.reshape(1), g, e)


# ---------------------------------------------------------------------------
# pass 2: fused sign + bitpack + residual update
# ---------------------------------------------------------------------------


def _ef_sign_kernel(gamma_ref, scale_ref, g_ref, e_ref, words_ref, e_new_ref):
    gamma = gamma_ref[0]
    scale = scale_ref[0]
    p = gamma * g_ref[...].astype(jnp.float32) + e_ref[...].astype(jnp.float32)
    bits = (p >= 0).astype(jnp.uint32)  # (block_rows, LANE)
    br = bits.shape[0]
    b = bits.reshape(br, WORDS_PER_ROW, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    words_ref[...] = jnp.sum(b << shifts, axis=-1, dtype=jnp.uint32)
    delta = scale * (2.0 * bits.astype(jnp.float32) - 1.0)
    e_new_ref[...] = p - delta


def ef_sign_compress(
    g, e, gamma, scale, *, block_rows: int = BLOCK_ROWS, interpret: bool = False
):
    """(rows,1024) γg+e → ((rows,32) uint32 packed signs, (rows,1024) residual)."""
    rows = g.shape[0]
    block_rows = min(block_rows, rows)
    grid = (_grid(rows, block_rows),)
    return pl.pallas_call(
        _ef_sign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, WORDS_PER_ROW), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, WORDS_PER_ROW), jnp.uint32),
            jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        ],
        interpret=interpret,
    )(gamma.reshape(1), scale.reshape(1), g, e)


# ---------------------------------------------------------------------------
# whole-bucket variants (repro.comm): per-bucket scale over a (nb, bs) stack.
# A bucket is a (bucket_size,) slice of the flattened grad stream; γ is folded
# into the update by the optimizer chain before bucketing, so p = g + e here.
#
# Tiling: a grid step holds BUCKET_ROWS buckets × BUCKET_COLS elements, so
# both block dims are whole (8, 128) f32 tiles (or the full bucket axis when
# nb < BUCKET_ROWS); the last row-block may be partial. BUCKET_COLS elements
# pack into exactly one 128-lane row of words, which is why bucket_size must
# be a multiple of BUCKET_COLS. Per-bucket scalars travel as (nb, 1) columns.
#
# The bit-pack runs on the MXU: word c of a block row is Σ_j bit(32c+j)·2^j,
# split into two 16-bit halves so every product and partial sum is an exact
# bf16×bf16→f32 integer (< 2^16). The unpack shifts each word against a
# 32-lane iota. Mosaic has no uint32 arithmetic, so words are int32 inside the
# kernels and bitcast at the refs; the bit pattern is ref.py's.
# ---------------------------------------------------------------------------

BUCKET_ROWS = 32
BUCKET_COLS = 4096
PACK_WORDS = BUCKET_COLS // 32  # 128: one lane row of words per block row


def _bucket_tiling(nb: int, bs: int) -> tuple[int, tuple[int, int]]:
    """→ (rows per block, grid) for a (nb, bs) bucket stack."""
    if bs % BUCKET_COLS:
        raise ValueError(f"bucket kernels need bucket_size % {BUCKET_COLS} == 0, got {bs}")
    rows = min(nb, BUCKET_ROWS)
    return rows, (pl.cdiv(nb, rows), bs // BUCKET_COLS)


def _params(*semantics: str, interpret):
    return None if interpret else pltpu.CompilerParams(dimension_semantics=semantics)


def pack_matrix() -> jax.Array:
    """(BUCKET_COLS, 2·PACK_WORDS) bf16 bit-pack weights: column c < 128 sums
    bits 0-15 of word c, column 128 + c bits 16-31, each weighted 2^(bit % 16)."""
    x = jnp.arange(BUCKET_COLS)[:, None]
    c = jnp.arange(2 * PACK_WORDS)[None, :]
    bit = x % 32
    hit = (x // 32 == c % PACK_WORDS) & (bit // 16 == c // PACK_WORDS)
    return jnp.where(hit, jnp.left_shift(1, bit % 16), 0).astype(jnp.bfloat16)


def _pack_signs(nonneg, pack):
    """(r, BUCKET_COLS) bool → (r, PACK_WORDS) uint32 words, bit j of word c = element 32c+j."""
    halves = jnp.dot(nonneg.astype(jnp.bfloat16), pack, preferred_element_type=jnp.float32)
    lo = halves[:, :PACK_WORDS].astype(jnp.int32)
    hi = halves[:, PACK_WORDS:].astype(jnp.int32)
    return pltpu.bitcast(lo | (hi << 16), jnp.uint32)


def _unpack_signs(words):
    """(r, PACK_WORDS) uint32 → (r, BUCKET_COLS) f32 of ±1."""
    wd = pltpu.bitcast(words, jnp.int32)
    r, m = wd.shape
    shifts = lax.broadcasted_iota(jnp.int32, (r, m, 32), 2)
    bits = (wd[:, :, None] >> shifts) & 1
    return (2 * bits - 1).reshape(r, m * 32).astype(jnp.float32)


def _bucket_stats_kernel(g_ref, e_ref, l1_ref, l2_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        l1_ref[...] = jnp.zeros(l1_ref.shape, jnp.float32)
        l2_ref[...] = jnp.zeros(l2_ref.shape, jnp.float32)

    p = g_ref[...].astype(jnp.float32) + e_ref[...].astype(jnp.float32)
    l1_ref[...] += jnp.sum(jnp.abs(p), axis=-1, keepdims=True)
    l2_ref[...] += jnp.sum(p * p, axis=-1, keepdims=True)


def bucket_stats(g, e, *, interpret: bool = False):
    """Per-bucket (L1, L2²) of p = g + e in one fused pass: (nb, bs) → 2×(nb,).

    The same HBM read of (g, e) feeds the scale and the density metric, so
    the metric costs no second pass over the bucket stack.
    """
    nb, bs = g.shape
    rows, grid = _bucket_tiling(nb, bs)
    block = pl.BlockSpec((rows, BUCKET_COLS), lambda i, j: (i, j))
    col = pl.BlockSpec((rows, 1), lambda i, j: (i, 0))
    l1, l2 = pl.pallas_call(
        _bucket_stats_kernel,
        grid=grid,
        in_specs=[block, block],
        out_specs=[col, col],
        out_shape=[jax.ShapeDtypeStruct((nb, 1), jnp.float32)] * 2,
        compiler_params=_params("parallel", "arbitrary", interpret=interpret),
        interpret=interpret,
    )(g, e)
    return l1[:, 0], l2[:, 0]


def bucket_l1(g, e, *, interpret: bool = False):
    """Per-bucket L1 of p = g + e: (nb, bs) → (nb,)."""
    return bucket_stats(g, e, interpret=interpret)[0]


def _bucket_ef_sign_kernel(scale_ref, pack_ref, g_ref, e_ref, words_ref, e_new_ref):
    p = g_ref[...].astype(jnp.float32) + e_ref[...].astype(jnp.float32)
    nonneg = p >= 0
    words_ref[...] = _pack_signs(nonneg, pack_ref[...])
    e_new_ref[...] = p - scale_ref[...] * jnp.where(nonneg, 1.0, -1.0)


def bucket_ef_sign_compress(g, e, scales, *, interpret: bool = False):
    """(nb, bs) p = g+e → ((nb, bs/32) u32 packed signs, (nb, bs) residual)."""
    nb, bs = g.shape
    rows, grid = _bucket_tiling(nb, bs)
    block = pl.BlockSpec((rows, BUCKET_COLS), lambda i, j: (i, j))
    return pl.pallas_call(
        _bucket_ef_sign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, 1), lambda i, j: (i, 0)),  # per-bucket scale
            pl.BlockSpec((BUCKET_COLS, 2 * PACK_WORDS), lambda i, j: (0, 0)),
            block,
            block,
        ],
        out_specs=[pl.BlockSpec((rows, PACK_WORDS), lambda i, j: (i, j)), block],
        out_shape=[
            jax.ShapeDtypeStruct((nb, bs // 32), jnp.uint32),
            jax.ShapeDtypeStruct((nb, bs), jnp.float32),
        ],
        compiler_params=_params("parallel", "parallel", interpret=interpret),
        interpret=interpret,
    )(scales.astype(jnp.float32).reshape(nb, 1), pack_matrix(), g, e)


def _bucket_accumulate_kernel(scale_ref, acc_ref, words_ref, out_ref):
    # one VMEM-resident decode fused with the add: no ±scale tensor hits HBM
    out_ref[...] = acc_ref[...] + scale_ref[...] * _unpack_signs(words_ref[...])


def bucket_sign_accumulate(acc, words, scales, *, interpret: bool = False):
    """Fused decompress-accumulate: acc + scaleᵦ·unpack(wordsᵦ) per bucket.

    acc (nb, bs) f32, words (nb, bs/32) u32, scales (nb,) f32 → (nb, bs) f32.
    The per-hop accumulation of the double-buffered ring aggregator: each
    arriving payload folds into the fp32 accumulator in a single
    HBM→VMEM→HBM pass (read acc + words, write acc'), so the ring's decode
    cost is spread across the W−1 hops instead of piling up after the last.
    """
    nb, bs = acc.shape
    rows, grid = _bucket_tiling(nb, bs)
    block = pl.BlockSpec((rows, BUCKET_COLS), lambda i, j: (i, j))
    return pl.pallas_call(
        _bucket_accumulate_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, 1), lambda i, j: (i, 0)),
            block,
            pl.BlockSpec((rows, PACK_WORDS), lambda i, j: (i, j)),
        ],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((nb, bs), jnp.float32),
        compiler_params=_params("parallel", "parallel", interpret=interpret),
        interpret=interpret,
    )(scales.astype(jnp.float32).reshape(nb, 1), acc, words)


def _bucket_decompress_mean_kernel(scales_ref, words_ref, out_ref, *, w: int):
    # words block (w, rows, PACK_WORDS); scales (w, rows, 1); out (rows, BUCKET_COLS)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for i in range(w):  # w is static; the same order as the ref's loop
        acc = acc + scales_ref[i] * _unpack_signs(words_ref[i])
    out_ref[...] = acc / w


def bucket_sign_decompress_mean(words, scales, *, interpret: bool = False):
    """(W, nb, bs/32) u32 + (W, nb) scales → (nb, bs) mean of ±scaleᵢᵦ."""
    w, nb, m = words.shape
    rows, grid = _bucket_tiling(nb, m * 32)
    return pl.pallas_call(
        functools.partial(_bucket_decompress_mean_kernel, w=w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((w, rows, 1), lambda i, j: (0, i, 0)),
            pl.BlockSpec((w, rows, PACK_WORDS), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((rows, BUCKET_COLS), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, m * 32), jnp.float32),
        compiler_params=_params("parallel", "parallel", interpret=interpret),
        interpret=interpret,
    )(scales.astype(jnp.float32).reshape(w, nb, 1), words)


# ---------------------------------------------------------------------------
# decompress-and-mean over W gathered payloads
# ---------------------------------------------------------------------------


def _decompress_mean_kernel(scales_ref, words_ref, out_ref, *, w: int):
    # words block: (w, block_rows, WORDS_PER_ROW); scales: (w,)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for i in range(w):  # w is static (16/32); unrolled vector loop
        wd = words_ref[i]  # (block_rows, WORDS_PER_ROW)
        bits = (wd[..., None] >> shifts) & jnp.uint32(1)
        signs = 2.0 * bits.reshape(out_ref.shape).astype(jnp.float32) - 1.0
        acc = acc + scales_ref[i] * signs
    out_ref[...] = acc / w


def sign_decompress_mean(
    words, scales, *, block_rows: int = BLOCK_ROWS, interpret: bool = False
):
    """(W,rows,32) uint32 + (W,) scales → (rows,1024) mean of ±scaleᵢ."""
    w, rows, _ = words.shape
    block_rows = min(block_rows, rows)
    grid = (_grid(rows, block_rows),)
    return pl.pallas_call(
        functools.partial(_decompress_mean_kernel, w=w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((w,), lambda i: (0,)),
            pl.BlockSpec((w, block_rows, WORDS_PER_ROW), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        interpret=interpret,
    )(scales, words)
