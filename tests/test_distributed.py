"""Multi-device tests, run in subprocesses so the main pytest session keeps a
single CPU device (the brief forbids a global device-count override).

The EF strategies run through the bucketed comm layer (``repro.comm``) by
default — per-worker grads via vmap + fully-manual shard_map collectives.
The per-leaf ``bucket_size=None`` fallback keeps its own coverage below.
"""

import json
import os
import subprocess
import sys

import pytest


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DRIVER = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(%(repo)r, "src"))
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduced
from repro.core import optim
from repro.core.compressors import ScaledSignCompressor
from repro.launch.mesh import make_host_mesh, ef_axis_names, use_mesh
from repro.sharding.rules import ShardingRules
from repro.train.state import init_train_state
from repro.train import steps as ST

strategy, policy, use_pod, bucket = %(strategy)r, %(policy)r, %(pod)r, %(bucket)r
mesh = make_host_mesh(pod=2, data=2, model=2) if use_pod else make_host_mesh(data=4, model=2)
cfg = reduced(get_config("llama3_2_1b"))
key = jax.random.PRNGKey(0)
rules = ShardingRules(cfg, mesh, policy)
ef_axes = (("pod",) if use_pod else ef_axis_names(mesh, policy)) if strategy != "dense" else ()
chain = optim.sgd(0.02)
with use_mesh(mesh):
    state = init_train_state(cfg, key, chain, strategy, mesh, ef_axes, bucket_size=bucket)
    batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)}
    bundle = ST.make_train_step(cfg, mesh, rules, strategy=strategy,
        comp=ScaledSignCompressor(), local_chain=chain, ef_axes=ef_axes,
        batch_example=batch, state_example=state, bucket_size=bucket)
    state = jax.device_put(state, bundle.in_shardings[0])
    batch = jax.device_put(batch, bundle.in_shardings[1])
    fn = bundle.jit()
    losses = []
    for i in range(6):
        state, (loss, m) = fn(state, batch)
        losses.append(float(loss))
    # params identical across devices (aggregated update consistency)
    leaf = jax.device_get(jax.tree.leaves(state.params)[0])
    print(json.dumps({"losses": losses, "wire": float(m["wire_bytes"]),
                      "density": float(m["density"])}))
"""

# fixed-size comm buckets for the reduced config: small enough that every
# strategy sees a multi-bucket stream (boundary splits, a2a bucket shards)
BUCKET = 4096


def _run(strategy, policy, pod, bucket=BUCKET):
    code = DRIVER % {
        "repo": REPO, "strategy": strategy, "policy": policy, "pod": pod,
        "bucket": bucket if strategy != "dense" else None,
    }
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize(
    "strategy,policy,pod",
    [
        ("dense", "tp", False),
        ("ef_allgather", "tp", False),
        ("ef_alltoall", "tp", False),
        # EF over the pod axis, fsdp inside
        ("ef_allgather", "fsdp", True),
        ("ef_alltoall", "fsdp", True),
    ],
)
def test_train_step_strategies(strategy, policy, pod):
    out = _run(strategy, policy, pod)
    losses = out["losses"]
    assert losses[-1] < losses[0], losses
    if strategy != "dense":
        assert 0.0 < out["density"] <= 1.0
        # compressed exchange must move far fewer bytes than dense fp32
        dense_bytes = 8.0 * 1.0e6  # order-of-magnitude guard
        assert out["wire"] < dense_bytes


@pytest.mark.slow
def test_wire_bytes_ratio_signsgd_vs_dense():
    dense = _run("dense", "tp", False)
    ef = _run("ef_allgather", "tp", False)
    a2a = _run("ef_alltoall", "tp", False)
    # paper's headline: sign compression cuts wire bytes by ~running factor;
    # bucketed all-gather: ~(64/W)×(1 − scale overhead) (W=4 here → ~16×);
    # bucketed all-to-all double compression: ~32×, W-independent
    assert dense["wire"] / ef["wire"] > 10, (dense["wire"], ef["wire"])
    assert dense["wire"] / a2a["wire"] > 20, (dense["wire"], a2a["wire"])


# ---------------------------------------------------------------------------
# per-leaf fallback (bucket_size=None): partial-manual shard_map over the EF
# axes, with the layer scan and the collectives inside it
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("strategy", ["ef_allgather", "ef_alltoall"])
def test_train_step_per_leaf_fallback(strategy):
    out = _run(strategy, "tp", False, bucket=None)
    assert out["losses"][-1] < out["losses"][0], out["losses"]
    assert out["wire"] < 8.0e6
