"""The comparison that decides ``correct``.

Six numbers, each against its limit in ``workloads/<cell>.json``:

``loss``    the largest relative gap between the program's loss and the
            reference's over the first three steps;
``grad``    each worker's first gradient, as the optimizer holds it after
            one step (the momentum, which is the gradient then), per leaf:
            the gap between the program's norm and the reference's, over
            the larger of the reference's norm of that leaf and of the
            median leaf; the worst leaf of the worst worker;
``update``  the same for the parameters' change over three steps;
``grad_diff`` worker 0's first gradient: per leaf, the norm of the
            difference between the program's and the reference's, over the
            same denominator; the median leaf. (The worst leaf swings with
            the tokens that change experts on rounding: it read up to 0.12
            on sound granite runs, within 3x of the control.)
``grad_sign`` worker 0's first gradient: of the elements whose reference
            magnitude is at least ``SIGN_FLOOR`` times their leaf's root mean
            square, pooled over the leaves, the share whose sign differs
            from the reference's. Rounding moves an element by a small
            share of its leaf's scale, so it flips hardly any sign there; a
            lower precision moves more elements by more;
``update_sign`` the same for the parameters' change over three steps: a
            decode that reads the right scales with signs in the wrong
            places changes no norm, and flips half of these signs.

Gaps of norms for the update, not norms of differences: a scaled-sign update
flips the sign of elements near zero on rounding, which a difference would
count and which changes no norm. The first gradient has no such flips, and
random rounding moves its norm only at second order, so a lower precision
shows in the norm of its difference (``grad_diff``) and hardly in ``grad``. Leaves whose reference gradient is under a thousandth
of the median leaf's are left out of both gradient and change numbers: their
change comes from round-off alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

TINY = 1e-3
SIGN_FLOOR = 1.0


def _worst_gap(got, want, keep) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    floor = np.median(want[keep])
    gaps = np.abs(got - want) / np.maximum(np.maximum(want, floor), 1e-30)
    return float(np.max(gaps[keep]))


def compare(program, ref) -> dict:
    ref_g = np.asarray(ref.grad_norms, np.float64)  # (W, leaves)
    keep = ref_g[0] >= TINY * np.median(ref_g[0])
    loss = max(abs(a - b) / abs(b) for a, b in zip(program.losses, ref.losses))
    grad = max(_worst_gap(g, r, keep) for g, r in zip(program.grad_norms, ref_g))
    update = _worst_gap(program.change_norms, ref.change_norms, keep)
    return {"loss": loss, "grad": grad, "update": update,
            "grad_diff": _median_diff(program.first_grad, ref.first_grad, keep),
            "grad_sign": sign_share(program.first_grad, ref.first_grad, keep),
            "update_sign": sign_share(program.change, ref.change, keep)}


@jax.jit
def _sign_counts(a, b, floors):
    """(elements of ``b`` at least floor x its rms, those whose sign differs in ``a``), per floor."""
    b = b.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(jnp.square(b)))
    counted = jnp.abs(b)[None] >= (floors * rms).reshape((-1,) + (1,) * b.ndim)
    differ = jnp.sign(a.astype(jnp.float32)) != jnp.sign(b)
    axes = tuple(range(1, b.ndim + 1))
    return jnp.sum(counted, axis=axes), jnp.sum(counted & differ[None], axis=axes)


def sign_counts(got, want, floors=(SIGN_FLOOR,)) -> tuple[np.ndarray, np.ndarray]:
    """Per leaf and floor (leaves, floors): elements counted, and how many
    of them have another sign in ``got`` than in ``want``."""
    floors = jnp.asarray(floors, jnp.float32)
    counted, bad = [], []
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        b = jnp.asarray(b)
        n, k = _sign_counts(jax.device_put(a, b.sharding), b, floors)
        counted.append(np.asarray(n, np.float64))
        bad.append(np.asarray(k, np.float64))
    return np.stack(counted), np.stack(bad)


def sign_share(got, want, keep) -> float:
    counted, bad = sign_counts(got, want)
    return float(bad[keep].sum() / max(counted[keep].sum(), 1))


def leaf_diffs(got, want) -> tuple[np.ndarray, np.ndarray]:
    """Per leaf: (norm of got - want, norm of want)."""
    diffs, norms = [], []
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        b32 = jnp.asarray(b).astype(jnp.float32)
        a32 = jax.device_put(a, b32.sharding).astype(jnp.float32)
        diffs.append(float(jnp.sqrt(jnp.sum(jnp.square(a32 - b32)))))
        norms.append(float(jnp.sqrt(jnp.sum(jnp.square(b32)))))
        del a32, b32
    return np.asarray(diffs), np.asarray(norms)


def _median_diff(got, want, keep) -> float:
    diffs, norms = leaf_diffs(got, want)
    floor = np.median(norms[keep])
    return float(np.median((diffs / np.maximum(np.maximum(norms, floor), 1e-30))[keep]))


def with_limits(compared: dict, limits: dict) -> dict:
    return {k: {"value": v, "limit": limits.get(k)} for k, v in compared.items()}


def judge(compared: dict, limits: dict, not_compared=()) -> bool:
    """Correct only where every number keeps to its limit; a number without
    one must be named as not compared (with its reason, in the cell's file)."""
    return all(
        k in not_compared if limits.get(k) is None else v <= limits[k]
        for k, v in compared.items()
    )
