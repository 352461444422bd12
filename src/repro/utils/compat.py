"""``shard_map`` with the repo's defaults."""

from __future__ import annotations

from typing import Callable

import jax


def shard_map(f: Callable, *, mesh, in_specs, out_specs, manual_axes=None):
    """``jax.shard_map`` with replication checking off (the strategies' RNG-key
    plumbing defeats the inferencer). ``manual_axes``: mesh axes the body
    references collectively; the rest stay GSPMD-auto. ``None`` means fully
    manual."""
    kw = {} if manual_axes is None else {"axis_names": frozenset(manual_axes)}
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False, **kw
    )
