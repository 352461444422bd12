"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benches see the real single CPU device.

Topology (TPU v5e target):
  single-pod: 16×16 = 256 chips, axes (data, model)
  multi-pod:  2×16×16 = 512 chips, axes (pod, data, model) — the ``pod`` axis
  is pure data-parallel and is where EF-compressed gradient aggregation runs
  (DESIGN.md §5).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def use_mesh(mesh):
    """``jax.set_mesh``: ``mesh`` is the ambient mesh for jit/shard_map inside
    the ``with`` block."""
    return jax.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over however many (fake) devices the host exposes."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


def dp_axis_names(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def ef_axis_names(mesh, policy: str) -> tuple[str, ...]:
    """Mesh axes treated as EF 'workers' (manual in shard_map).

    Multi-pod: the pod axis — compression rides the expensive inter-pod hop
    and params may still be fsdp-sharded intra-pod. Single-pod: the data axis,
    valid only when params are not data-sharded (dp/tp policies); fsdp runs
    single-worker EF (the paper's Alg. 2 per shard) instead.
    """
    if "pod" in mesh.axis_names:
        return ("pod",)
    if policy in ("dp", "tp") and "data" in mesh.axis_names:
        return ("data",)
    return ()
