"""A run with the timed path broken underneath comes out not correct: a step
that returns its state unchanged, half of each batch left out (the mean over
the rest), the decoded mean's signs in the wrong places, and the exchange
between workers left out."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import cells
import run
import tiny


def _broken(kind):
    real_build = run.build

    def build(cell, seed):
        mesh, pool, prep = real_build(cell, seed)
        step = prep.step_fn
        if kind == "unchanged":
            def broken(state, batch):
                _, out = step(jax.tree.map(jnp.copy, state), batch)
                return state, out
        else:
            def broken(state, batch):
                return step(state, jax.tree.map(lambda x: x[: x.shape[0] // 2], batch))
        prep.step_fn = broken
        return mesh, pool, prep

    return build


@pytest.mark.parametrize("kind", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(kind, monkeypatch):
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    monkeypatch.setattr(run, "build", _broken(kind))
    out = run.run_cell(tiny.cell(), 2**32 + 3, 0.3, False, jax.devices())
    assert out["correct"] is False, out["compared"]


def test_scrambled_decode_is_not_correct(monkeypatch):
    """The decode reads each 32-element word's signs in reverse order: every
    norm stays, and the change's signs catch it."""
    from repro.comm import exchange

    real = exchange.PayloadStack.mean

    def reversed_words(self):
        m = real(self)
        nb, bs = m.shape
        return m.reshape(nb, bs // 32, 32)[..., ::-1].reshape(nb, bs)

    monkeypatch.setattr(run, "enable_cache", lambda: None)
    monkeypatch.setattr(exchange.PayloadStack, "mean", reversed_words)
    out = run.run_cell(tiny.cell(), 2**32 + 3, 0.3, False, jax.devices())
    assert out["correct"] is False
    sign = out["compared"]["update_sign"]
    assert sign["value"] > sign["limit"], out["compared"]


EXCHANGE = """
import sys
sys.path.insert(0, 'chipbench'); sys.path.insert(0, 'chipbench/tests')
import jax, jax.numpy as jnp
import run, tiny
from repro.comm import exchange
from repro.comm.backends import xla
import dataclasses
run.enable_cache = lambda: None
# two workers' mean sign turns a rounding flip into a zero, so the change
# after three steps reads up to 0.04 on sound runs at this size (0.46 with
# the exchange left out)
cell = tiny.cell(workers=2)
cell = dataclasses.replace(cell, limits={**cell.limits, "update": 0.15})
print('sound', run.run_cell(cell, 41, 0.3, False, jax.devices())['correct'])

def own_only(self, comp, payload, bucket_size, ef_axes, world):
    slots = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (world,) + x.shape), payload)
    return exchange.PayloadStack(comp, bucket_size, world, slots=slots)

xla.XlaBackend.exchange = own_only
print('no_exchange', run.run_cell(cell, 41, 0.3, False, jax.devices())['correct'])
"""


def test_exchange_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=2")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", EXCHANGE], cwd=cells.CHECKOUT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith(("sound", "no_exchange"))]
    assert lines == ["sound True", "no_exchange False"], out.stdout[-3000:]
