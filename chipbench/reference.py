"""The plain float32 reference of a training step, and its lower-precision control.

Written from the published layer equations and the program's documented
training semantics, importing nothing of the program. The model is the
configuration's family (``families/<family>.py``: weights by the program's
key schedule, the loss of one row); what is here holds for every family:

* the local optimizer is SGD with heavy-ball momentum and a constant step
  size, and the exchange is scaled-sign error feedback per bucket of the
  flattened update, averaged over the data-parallel workers in worker order;
* parameters, gradients and the momentum's output are stored in the
  configuration's parameter dtype, as the program stores them.

Every matrix product runs at ``Precision.HIGHEST``. ``precision="fp8"`` is
the control: where the configurations state bfloat16 in the forward pass
(every matrix product's operands and result, the embedding output, the
residual stream and the norm outputs), it rounds to float8 e4m3 with a
per-tensor scale; the gradient passes the rounding straight.
``precision="bf16"`` rounds the same places to bfloat16: the stated
precision, computed by the reference (a witness, not a control). ``fault``
plants a known fault in place of the program: ``half_batch`` (each worker's
mean taken over the first half of its rows), ``no_exchange`` (each worker
applies its own update, as if the exchange were left out; worker 0's copy is
read) or ``bit_order`` (the decoded mean's signs read in reverse order within
each 32-element word, as a pack and unpack that disagree on bit order).

Gradients are taken one batch row at a time and averaged, which is exact:
every family's loss is a mean over whole rows.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


# ---------------------------------------------------------------------------
# pieces of a family's forward pass
# ---------------------------------------------------------------------------


def _fp8(x):
    """Per-tensor scaled float8 e4m3 rounding; the gradient passes straight."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    q = (x / s).astype(FP8).astype(jnp.float32) * s
    return x + lax.stop_gradient(q - x)


def _bf16(x):
    """bfloat16 rounding; the gradient passes straight."""
    return x + lax.stop_gradient(x.astype(jnp.bfloat16).astype(jnp.float32) - x)


ROUND = {"fp8": _fp8, "bf16": _bf16}


def _matmul(precision: str):
    if precision in ROUND:
        r = ROUND[precision]
        return lambda eq, a, b: r(jnp.einsum(eq, r(a), r(b), precision=HIGHEST))
    return lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)


def _activation(precision: str):
    """Where the program holds activations in bfloat16 (embedding output,
    residual stream, norm outputs), the control rounds them to float8 and
    the witness to bfloat16."""
    return ROUND.get(precision, lambda x: x)


def _rms(x, scale):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * scale


def _rope(x, theta):
    s, hd = x.shape[-3], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def loss_and_grad(family, m, precision: str = "f32"):
    """jit of ``(params, tokens (b, S), labels) -> (mean loss, mean grads)``
    by the family's ``row_loss``, one row at a time; grads are cast to the
    parameters' dtype."""
    vg = jax.value_and_grad(lambda p, t, lab: family.row_loss(p, m, t, lab, precision))

    @jax.jit
    def run(params, tokens, labels):
        def body(acc, row):
            loss, g = vg(params, *row)
            return jax.tree.map(jnp.add, acc, (loss, g)), None

        zero = (jnp.float32(0.0), jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params))
        (loss, g), _ = lax.scan(body, zero, (tokens, labels))
        n = tokens.shape[0]
        return loss / n, jax.tree.map(lambda a, p: (a / n).astype(p.dtype), g, params)

    return run


# ---------------------------------------------------------------------------
# optimizer and error-feedback exchange
# ---------------------------------------------------------------------------


def bucket_layout(params, bucket_size: int) -> tuple[int, int]:
    """(elements, buckets) of the flattened parameters (one dtype group)."""
    leaves = jax.tree.leaves(params)
    if len({jnp.dtype(x.dtype) for x in leaves}) != 1:
        raise ValueError("the reference exchange expects parameters of one dtype")
    n = sum(int(x.size) for x in leaves)
    return n, -(-n // bucket_size)


def flatten(tree, nb: int, bs: int):
    flat = jnp.concatenate([x.reshape(-1).astype(jnp.float32) for x in jax.tree.leaves(tree)])
    return jnp.pad(flat, (0, nb * bs - flat.shape[0])).reshape(nb, bs)


def unflatten(buckets, like):
    leaves, treedef = jax.tree.flatten(like)
    flat, out, at = buckets.reshape(-1), [], 0
    for x in leaves:
        out.append(flat[at : at + x.size].reshape(x.shape).astype(x.dtype))
        at += x.size
    return jax.tree.unflatten(treedef, out)


@functools.partial(jax.jit, donate_argnums=0)
def _momentum(m, g):
    return jax.tree.map(lambda mm, gg: 0.9 * mm + gg.astype(jnp.float32), m, g)


def _ef_encode(update, err, n_valid):
    """Scaled sign with error feedback per bucket: (delta, new residual)."""
    p = update + err
    scale = jnp.sum(jnp.abs(p), axis=-1, keepdims=True) / p.shape[1]
    delta = scale * jnp.where(p >= 0, 1.0, -1.0)
    valid = jnp.arange(p.size).reshape(p.shape) < n_valid
    return delta, (p - delta) * valid


@dataclasses.dataclass
class Readings:
    """What the comparison reads from a run of three steps."""

    losses: list  # per step, mean over workers
    grad_norms: Any  # (W, leaves): each worker's first gradient, per leaf
    change_norms: Any  # (leaves,): the parameters' change after three steps
    first_grad: Any = None  # worker 0's first gradient itself (a pytree)
    change: Any = None  # the change itself, or its signs (a pytree)


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in jax.tree.leaves(tree)])


def run_steps(
    ref,
    weight_key,
    batches: list[dict],
    *,
    workers: int,
    strategy: str,
    lr: float,
    bucket_size: int,
    steps: int = 3,
    precision: str = "f32",
    fault: str | None = None,
    devices=None,
) -> Readings:
    """Follow the program's first ``steps`` steps from the weights drawn
    from ``weight_key``.

    ``ref`` is ``(family, model)`` as ``cells.reference_model`` gives it:
    the family module's ``init_params`` and ``row_loss`` at the model's
    sizes.

    ``batches[i]`` is step i's global batch; worker w takes its w-th block of
    rows. Worker w's gradient, momentum and residual live on ``devices[w]``
    (default: all on the first device), so the four-chip cell's reference
    fits.
    """
    family, m = ref
    devices = devices or [jax.devices()[0]] * workers
    params = jax.device_put(jax.jit(family.init_params, static_argnums=0)(m, weight_key), devices[0])
    start = params
    grad_fn = loss_and_grad(family, m, precision)
    n_valid, nb = bucket_layout(params, bucket_size)
    bs = bucket_size
    mom = [None] * workers
    err = [None] * workers
    losses, grad_norms = [], None
    ef = strategy != "dense"
    neg_lr = -jnp.float32(lr)
    # the momentum's output in the parameters' dtype, times -lr (an f32 scalar)
    local_update = jax.jit(
        lambda mm, p: jax.tree.map(lambda a, b: neg_lr * a.astype(b.dtype), mm, p)
    )
    bucketed = jax.jit(lambda mm, p: flatten(local_update(mm, p), nb, bs))
    encode = jax.jit(_ef_encode, static_argnums=2, donate_argnums=1)
    for step in range(steps):
        tokens, labels = batches[step]["tokens"], batches[step]["labels"]
        rows = tokens.shape[0] // workers
        step_loss, deltas, firsts = 0.0, [], []
        for w in range(workers):
            lo, hi = w * rows, (w + 1) * rows
            tw, lw = tokens[lo:hi], labels[lo:hi]
            if fault == "half_batch":
                if rows > 1:
                    tw, lw = tw[: rows // 2], lw[: rows // 2]
                else:
                    tw, lw = tw[:, : tw.shape[1] // 2], lw[:, : lw.shape[1] // 2]
            pw = jax.device_put(params, devices[w])
            loss, g = grad_fn(pw, jax.device_put(tw, devices[w]), jax.device_put(lw, devices[w]))
            step_loss += float(loss) / workers
            if step == 0:
                firsts.append(leaf_norms(g))
                if w == 0:
                    first_grad = g
                mom[w] = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), g)
                if ef:
                    err[w] = jax.device_put(jnp.zeros((nb, bs), jnp.float32), devices[w])
            mom[w] = _momentum(mom[w], g)
            del g
            if ef:
                delta, err[w] = encode(bucketed(mom[w], pw), err[w], n_valid)
                deltas.append(delta)
            else:
                deltas.append(local_update(mom[w], pw))
            del pw
        if fault == "no_exchange":
            mean = deltas[0]
        else:
            mean = jax.device_put(deltas[0], devices[0])
            for d in deltas[1:]:
                mean = mean + jax.device_put(d, devices[0])
            mean = mean / workers if workers > 1 else mean
        if fault == "bit_order" and ef:
            mean = mean.reshape(nb, bs // 32, 32)[..., ::-1].reshape(nb, bs)
        del deltas
        upd = unflatten(mean, params) if ef else mean
        params = jax.tree.map(lambda x, u: x + u.astype(x.dtype), params, upd)
        del mean, upd
        losses.append(step_loss)
        if step == 0:
            grad_norms = jnp.stack([jax.device_put(f, devices[0]) for f in firsts])
    change = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), params, start)
    del params, start
    return Readings(losses, jax.device_get(grad_norms), jax.device_get(leaf_norms(change)), first_grad,
                    change)
