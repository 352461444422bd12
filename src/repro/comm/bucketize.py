"""Static bucket layout: flatten a gradient pytree into fixed-size buckets.

A :class:`BucketLayout` is computed ONCE per parameter spec (shapes + dtypes
only — works on ``jax.eval_shape`` results, no device data needed) and then
drives jit-compatible flatten/unflatten executors. Leaves are grouped by
dtype (dtype-homogeneous buckets: a real wire format ships bf16 and fp32
payloads separately), concatenated in tree-flatten order, zero-padded to a
whole number of ``bucket_size``-element buckets, and viewed as
``(n_buckets, bucket_size)``.

That view is only ever built row by row: :func:`build_layout` maps each leaf
to its own runs of the bucket array (whole rows that hold this leaf alone,
and at most one partial row at each end, shared with its neighbours or the
padding), and :func:`flatten_buckets` / :func:`unflatten_buckets` move each
leaf straight to and from those rows. No flat span of a whole group is ever
formed, so the compiler has no group-wide array to relayout.

Padding rules:
  * ``bucket_size`` must be a multiple of 32 so packed-sign payloads have no
    intra-bucket ragged words;
  * only the LAST bucket of each group carries padding; ``group.valid`` is
    the true element count and :func:`valid_mask` the static mask used to
    keep error-feedback residuals out of the padded tail.

Flattening deliberately trades GSPMD leaf-sharding preservation for a
realistic wire path (fixed-size payloads, one collective per bucket stream) —
the per-leaf strategies in ``repro.core.aggregation`` remain available for
giant fsdp-sharded models via ``bucket_size=None``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

DEFAULT_BUCKET_SIZE = 1 << 16  # 65536 elems = 256 KiB fp32 — DDP-scale buckets


@dataclasses.dataclass(frozen=True)
class Run:
    """``size`` consecutive elements of one leaf, from leaf element ``start``,
    at bucket ``row``, column ``col`` of its group's bucket array. A run with
    ``col == 0`` and ``size`` a whole number of buckets is :attr:`whole`: those
    rows hold this leaf alone. Any other run lies inside one row."""

    start: int
    row: int
    col: int
    size: int
    whole: bool


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one pytree leaf lives inside its dtype group's flat span."""

    group: int  # index into BucketLayout.groups
    offset: int  # element offset into the group's (unpadded) flat span
    size: int
    shape: tuple[int, ...]
    dtype: Any
    runs: tuple[Run, ...]  # the leaf's rows of the bucket array, in order


@dataclasses.dataclass(frozen=True)
class BucketGroup:
    """One dtype-homogeneous run of buckets."""

    dtype: Any
    valid: int  # true element count (before padding)
    n_buckets: int
    rows_one_leaf: int  # rows that hold one leaf alone (the direct path)

    @property
    def rows_shared(self) -> int:
        """Boundary rows, assembled from pieces: rows holding elements of
        more than one leaf, or a leaf's end and the padding."""
        return self.n_buckets - self.rows_one_leaf


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """Static flatten/unflatten plan for one pytree structure."""

    bucket_size: int
    treedef: Any
    slots: tuple[LeafSlot, ...]  # one per leaf, tree-flatten order
    groups: tuple[BucketGroup, ...]

    @property
    def n_buckets(self) -> int:
        return sum(g.n_buckets for g in self.groups)

    @property
    def n_elements(self) -> int:
        return sum(g.valid for g in self.groups)

    @property
    def rows_one_leaf(self) -> int:
        return sum(g.rows_one_leaf for g in self.groups)

    @property
    def rows_shared(self) -> int:
        return sum(g.rows_shared for g in self.groups)

    @property
    def padded_elements(self) -> int:
        return self.n_buckets * self.bucket_size

    @property
    def padding_overhead(self) -> float:
        """Fraction of transmitted elements that are padding."""
        pad = self.padded_elements - self.n_elements
        return pad / self.padded_elements if self.padded_elements else 0.0

    def wire_bits(self, comp) -> int:
        """Exact per-step bits on the wire: every bucket is one fixed-size
        payload of ``comp.wire_bits(bucket_size)`` bits."""
        return self.n_buckets * comp.wire_bits(self.bucket_size)


def _leaf_runs(offset: int, size: int, bucket_size: int) -> tuple[Run, ...]:
    """The runs of a leaf at group ``offset``: a partial head row, the whole
    rows it holds alone, and a partial tail row, each where present."""
    runs = []
    pos, end = offset, offset + size
    while pos < end:
        row, col = divmod(pos, bucket_size)
        if col == 0 and end - pos >= bucket_size:
            n = (end - pos) // bucket_size * bucket_size
            runs.append(Run(pos - offset, row, 0, n, whole=True))
        else:
            n = min(bucket_size - col, end - pos)
            runs.append(Run(pos - offset, row, col, n, whole=False))
        pos += n
    return tuple(runs)


def build_layout(tree, bucket_size: int = DEFAULT_BUCKET_SIZE) -> BucketLayout:
    """Compute the static bucket layout of ``tree`` (arrays or ShapeDtypeStructs)."""
    if bucket_size <= 0 or bucket_size % 32 != 0:
        raise ValueError(f"bucket_size must be a positive multiple of 32, got {bucket_size}")
    leaves, treedef = jax.tree.flatten(tree)
    group_order: list[Any] = []  # dtype, in first-appearance order
    group_sizes: dict[Any, int] = {}
    group_whole_rows: dict[Any, int] = {}
    slots = []
    for leaf in leaves:
        dt = jnp.dtype(leaf.dtype)
        if dt not in group_sizes:
            group_order.append(dt)
            group_sizes[dt] = 0
            group_whole_rows[dt] = 0
        runs = _leaf_runs(group_sizes[dt], int(leaf.size), bucket_size)
        slots.append(
            LeafSlot(
                group=group_order.index(dt),
                offset=group_sizes[dt],
                size=int(leaf.size),
                shape=tuple(leaf.shape),
                dtype=dt,
                runs=runs,
            )
        )
        group_sizes[dt] += int(leaf.size)
        group_whole_rows[dt] += sum(r.size // bucket_size for r in runs if r.whole)
    groups = tuple(
        BucketGroup(
            dtype=dt,
            valid=group_sizes[dt],
            n_buckets=max(1, -(-group_sizes[dt] // bucket_size)),
            rows_one_leaf=group_whole_rows[dt],
        )
        for dt in group_order
    )
    return BucketLayout(
        bucket_size=bucket_size,
        treedef=treedef,
        slots=tuple(slots),
        groups=groups,
    )


def _width(slot: LeafSlot, bucket_size: int) -> int:
    """Width of the rows a leaf of two or more dims is moved in: its last dim
    where that tiles the bucket rows and each of its runs, else the widest
    width that tiles all of them.

    A narrower view is materialized (``lax.optimization_barrier``, which the
    compiler may not fold into the relayout next to it): a relayout straight
    between leaf rows and bucket rows that do not tile each other compiles
    to megabytes of code per leaf; two relayouts through rows that tile both
    compile to a few hundred kilobytes, for one more pass over the leaf.
    """
    return math.gcd(slot.shape[-1], bucket_size, *(run.size for run in slot.runs))


def flatten_buckets(layout: BucketLayout, tree) -> tuple[jax.Array, ...]:
    """Pytree → one ``(n_buckets, bucket_size)`` fp32 array per dtype group.

    Each group's array is one concatenation of row blocks, in row order: a
    leaf's whole rows as ``(k, bucket_size)`` from the leaf's own rows (of its
    width, :func:`_width`), and each boundary row assembled from its small
    pieces and the padding.

    All compression/EF math runs in fp32 regardless of the group dtype; the
    group dtype drives the cast back in :func:`unflatten_buckets` (and the
    wire-byte model of a mixed-precision transport).
    """
    leaves = jax.tree.leaves(tree)
    if len(leaves) != len(layout.slots):
        raise ValueError(f"tree has {len(leaves)} leaves, layout expects {len(layout.slots)}")
    bs = layout.bucket_size
    blocks: list[list[jax.Array]] = [[] for _ in layout.groups]
    pieces: list[list[jax.Array]] = [[] for _ in layout.groups]  # the open boundary row

    def close_row(gi: int) -> None:
        if pieces[gi]:
            used = sum(p.shape[0] for p in pieces[gi])
            if used < bs:
                pieces[gi].append(jnp.zeros((bs - used,), jnp.float32))
            blocks[gi].append(jnp.concatenate(pieces[gi])[None])
            pieces[gi] = []

    for slot, leaf in zip(layout.slots, leaves):
        if tuple(leaf.shape) != slot.shape:
            raise ValueError(f"leaf shape {leaf.shape} != layout shape {slot.shape}")
        if leaf.ndim >= 2:
            width = _width(slot, bs)
            rows = leaf.reshape(-1, width)
            if width != leaf.shape[-1]:
                rows = lax.optimization_barrier(rows)
        else:
            width, rows = 1, leaf.reshape(-1)
        for run in slot.runs:
            span = rows[run.start // width : (run.start + run.size) // width]
            if run.whole:
                close_row(slot.group)
                blocks[slot.group].append(span.reshape(-1, bs).astype(jnp.float32))
            else:
                if run.col == 0:
                    close_row(slot.group)
                pieces[slot.group].append(span.reshape(-1).astype(jnp.float32))
    out = []
    for gi, group in enumerate(layout.groups):
        close_row(gi)
        if not blocks[gi]:  # a group of empty leaves: one bucket of padding
            blocks[gi].append(jnp.zeros((group.n_buckets, bs), jnp.float32))
        out.append(jnp.concatenate(blocks[gi]) if len(blocks[gi]) > 1 else blocks[gi][0])
    return tuple(out)


def _cut(b: jax.Array, row: int, col: int, size: tuple[int, int]) -> jax.Array:
    """``b[row:, col:]`` of ``size``, at a start the compiler cannot fold.

    Static slices that cover most of the bucket array lead XLA to cast the
    whole array to the leaves' dtype once and slice that: a pass over the
    whole group. Each leaf's slice, cast with it, is cheaper.
    """
    start = lax.optimization_barrier((jnp.int32(row), jnp.int32(col)))
    return lax.dynamic_slice(b, start, size)


def _leaf_view(slot: LeafSlot, b: jax.Array) -> jax.Array:
    """One leaf from its rows of its group's bucket array, in its dtype.

    Each run is cut from the bucket array and cast on its own; the runs are
    joined as rows of the leaf's width (:func:`_width`), so a leaf that holds
    its rows alone is one row slice, reshaped.
    """
    if not slot.runs:
        return jnp.zeros(slot.shape, slot.dtype)
    bs = b.shape[1]
    width = _width(slot, bs) if len(slot.shape) >= 2 else None
    parts = []
    for run in slot.runs:
        if run.whole:
            part = _cut(b, run.row, 0, (run.size // bs, bs))
        else:
            part = _cut(b, run.row, run.col, (1, run.size))
        part = part.astype(slot.dtype)
        parts.append(part.reshape(-1) if width is None else part.reshape(-1, width))
    joined = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    if width is not None and width != slot.shape[-1]:
        joined = lax.optimization_barrier(joined)
    return joined.reshape(slot.shape)


def unflatten_buckets(layout: BucketLayout, buckets: tuple[jax.Array, ...]):
    """Inverse of :func:`flatten_buckets`; leaves are cast back to group dtype."""
    if len(buckets) != len(layout.groups):
        raise ValueError(f"got {len(buckets)} bucket arrays, layout has {len(layout.groups)}")
    for group, b in zip(layout.groups, buckets):
        if b.shape != (group.n_buckets, layout.bucket_size):
            raise ValueError(f"bucket array {b.shape} != ({group.n_buckets}, {layout.bucket_size})")
    leaves = [_leaf_view(slot, buckets[slot.group]) for slot in layout.slots]
    return jax.tree.unflatten(layout.treedef, leaves)


def valid_mask(layout: BucketLayout, group_index: int) -> jax.Array:
    """(n_buckets, bucket_size) f32 mask: 1 on real elements, 0 on padding.

    Error-feedback residuals are multiplied by this so the padded tail never
    accumulates phantom error (sign-decode emits ±scale even where p == 0).
    """
    group = layout.groups[group_index]
    idx = jnp.arange(group.n_buckets * layout.bucket_size)
    mask = (idx < group.valid).astype(jnp.float32)
    return mask.reshape(group.n_buckets, layout.bucket_size)
