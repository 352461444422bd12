"""Activation-sharding constraints for model internals.

GSPMD propagation from parameter/batch shardings is usually enough, but under
fsdp the weight contractions make it profitable-looking for XLA to replicate
activations over the ``data`` axis inside the mamba/attention scans — on the
398B config that materialized ~34 GiB f32 scan tensors (batch unsharded).
These helpers pin the batch dim of key activations.

The context records which mesh axes are *available* (GSPMD-auto, visible to
``with_sharding_constraint``). Inside a ``shard_map`` the manual axes must not
be referenced, so the step builders set the context accordingly.
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()


def _current_mesh():
    """The ambient mesh (set by ``use_mesh``)."""
    return jax.sharding.get_abstract_mesh()


def _ctx():
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def activation_sharding(batch_axes: tuple[str, ...] | None, model_axis: str | None):
    prev = _ctx()
    _state.ctx = {"batch": batch_axes or None, "model": model_axis}
    try:
        yield
    finally:
        _state.ctx = prev


def constrain(x: jax.Array, dims: str) -> jax.Array:
    """Constrain by a dim-role string: 'b'=batch, 'm'=model-sharded, '.'=open.

    e.g. residual (B,S,D) → 'b..'; mamba scan elem (C,B,di,st) → '.bm.'.
    No-op outside an activation_sharding context.
    """
    ctx = _ctx()
    if ctx is None or (ctx["batch"] is None and ctx["model"] is None):
        return x
    mesh = _current_mesh()
    if mesh is None or mesh.empty:
        return x  # no mesh in context (single-device paths)
    spec = []
    for i, role in enumerate(dims):
        if role == "b" and ctx["batch"] and x.shape[i] % _axes_size(ctx["batch"]) == 0:
            spec.append(ctx["batch"])
        elif role == "m" and ctx["model"] and x.shape[i] % _axes_size((ctx["model"],)) == 0:
            spec.append(ctx["model"])
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _axes_size(axes) -> int:
    mesh = _current_mesh()
    if mesh is None or mesh.empty:
        return 1 << 30  # no mesh → make divisibility fail → no constraint
    n = 1
    for a in axes:
        if a not in mesh.shape:
            return 1 << 30
        n *= mesh.shape[a]
    return n
