"""Substrate tests: data generators, sharding rules, checkpointing, optimizer
schedules, and the 1-device training loop."""

import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, reduced
from repro.core import optim
from repro.data import synthetic
from repro.launch.mesh import make_host_mesh, use_mesh
from repro.models import transformer as T
from repro.sharding.rules import ShardingRules, default_policy
from repro.train import checkpoint as ckpt


# ------------------------------- data ------------------------------------ #


def test_wilson_data_matches_a6_spec():
    d = synthetic.wilson_least_squares(seed=3)
    a = np.vstack([d.a_train, d.a_test])
    y = np.concatenate([d.y_train, d.y_test])
    n = len(y)
    assert a.shape == (200, 1200) and n == 200
    assert set(np.unique(y)) == {-1.0, 1.0}
    # per-row structure: col0 = y, col1..2 = 1, then 1 or 3 slots of 1s
    for i in np.random.default_rng(0).choice(200, 20, replace=False):
        # rows were shuffled; identify by the unique block position instead
        row = a[i]
        assert row[1] == 1.0 and row[2] == 1.0
        width = int(row[3:].sum())
        # A.6: slots 4+5(i−1) … 4+5(i−1)+2(1−yᵢ) → 1 slot (y=+1) or 5 (y=−1)
        assert width in (1, 5)
        assert row[0] == (1.0 if width == 1 else -1.0)


def test_token_batches_deterministic_and_learnable():
    it1 = synthetic.token_batches(0, 4, 32, 128)
    it2 = synthetic.token_batches(0, 4, 32, 128)
    b1, b2 = next(it1), next(it2)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    # labels are next-token aligned
    np.testing.assert_array_equal(
        np.asarray(b1["tokens"][:, 1:]), np.asarray(b1["labels"][:, :-1])
    )


def test_proxy_classification_separates_noise():
    (xtr, ytr), (xte, yte) = synthetic.proxy_classification(seed=0)
    assert xtr.shape[0] == 4096 and xte.shape[0] == 1024
    assert 0 <= ytr.min() and ytr.max() < 10


# ----------------------------- sharding ---------------------------------- #


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("policy", ["tp", "fsdp"])
def test_param_specs_divisible(arch, policy):
    """Every spec axis must evenly divide its dim on the production mesh
    shape (checked abstractly against 16×16 sizes)."""
    cfg = get_config(arch)
    mesh = make_host_mesh(data=1, model=1)  # host mesh; sizes faked below
    rules = ShardingRules(cfg, mesh, policy)
    rules.model_size, rules.data_size = 16, 16  # production sizes

    params = jax.eval_shape(lambda k: T.init_params(cfg, k), jax.random.PRNGKey(0))
    specs = rules.param_specs(params)

    def check(path, leaf, spec):
        for dim, entry in zip(leaf.shape, tuple(spec)):
            axes = entry if isinstance(entry, tuple) else ((entry,) if entry else ())
            size = 1
            for a in axes:
                size *= {"model": 16, "data": 16, None: 1}.get(a, 1)
            assert dim % size == 0, (arch, policy, path, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), params, specs
    )


def test_vocab_padding():
    cfg = get_config("granite_moe_1b_a400m")
    assert cfg.vocab_size == 49155
    assert cfg.padded_vocab % 256 == 0
    assert cfg.padded_vocab >= cfg.vocab_size


def test_default_policy_by_size():
    assert default_policy(get_config("llama3_2_1b")) == "tp"
    assert default_policy(get_config("jamba_1_5_large_398b")) == "fsdp"


# ---------------------------- checkpoint --------------------------------- #


def test_checkpoint_roundtrip_and_latest():
    state = {
        "params": {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones((4,))},
        "step": jnp.int32(7),
    }
    with tempfile.TemporaryDirectory() as d:
        ckpt.save_checkpoint(d, state, 10)
        ckpt.save_checkpoint(d, jax.tree.map(lambda x: x * 2, state), 20)
        assert ckpt.latest_step(d) == 20
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        r10 = ckpt.restore_checkpoint(d, like, step=10)
        r20 = ckpt.restore_checkpoint(d, like)
        np.testing.assert_allclose(np.asarray(r10["params"]["w"]) * 2,
                                   np.asarray(r20["params"]["w"]))


# ---------------------------- schedules ---------------------------------- #


def test_step_decay_schedule_decimates():
    sched = optim.step_decay_schedule(1.0, 200)
    assert float(sched(jnp.int32(0))) == 1.0
    assert abs(float(sched(jnp.int32(120))) - 0.1) < 1e-6
    assert abs(float(sched(jnp.int32(180))) - 0.01) < 1e-7


def test_signum_matches_paper_recursion():
    """m_{t+1} = g_t + β m_t (NOT an EMA) — check two steps by hand."""
    opt = optim.signum(1.0, beta=0.5)
    p = {"x": jnp.zeros((2,))}
    st = opt.init(p)
    u1, st = opt.update({"x": jnp.array([1.0, -2.0])}, st, p)
    np.testing.assert_allclose(np.asarray(u1["x"]), [-1.0, 1.0])
    # m = [1,-2]; next g=[0.4,3] → m = [0.9, 2.0] → update = −sign = [-1,-1]
    u2, st = opt.update({"x": jnp.array([0.4, 3.0])}, st, p)
    np.testing.assert_allclose(np.asarray(u2["x"]), [-1.0, -1.0])


# ------------------------- 1-device training loop ------------------------ #


def test_training_loop_reduces_loss_and_checkpoints():
    from repro.train.loop import TrainJob, run_training

    cfg = reduced(get_config("llama3_2_1b"))
    mesh = make_host_mesh(data=1, model=1)
    with tempfile.TemporaryDirectory() as d:
        job = TrainJob(
            cfg=cfg, mesh=mesh, steps=25, batch=4, seq=48, lr=0.08,
            optimizer="ef_signsgd", strategy="dense", log_every=5,
            ckpt_dir=d, ckpt_every=20,
        )
        state, hist = run_training(job)
        assert hist[-1]["loss"] < hist[0]["loss"]
        assert ckpt.latest_step(d) == 20


def test_microbatch_gradient_accumulation_exact():
    """M-way gradient accumulation ≡ single full-batch step (fp32)."""
    import dataclasses

    from repro.train import steps as ST
    from repro.train.state import init_train_state

    cfg = dataclasses.replace(
        reduced(get_config("llama3_2_1b")), param_dtype="float32", compute_dtype="float32"
    )
    mesh = make_host_mesh(data=1, model=1)
    rules = ShardingRules(cfg, mesh, "dp")
    chain = optim.sgd(0.05)
    key = jax.random.PRNGKey(0)
    batch = {
        "tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size),
    }
    outs = {}
    with use_mesh(mesh):
        for m in (1, 4):
            state = init_train_state(cfg, key, chain, "dense", mesh, ())
            b = ST.make_train_step(
                cfg, mesh, rules, strategy="dense", local_chain=chain, ef_axes=(),
                batch_example=batch, state_example=state, microbatches=m,
            )
            st2, (loss, _) = b.jit()(state, batch)
            outs[m] = (float(loss), np.asarray(jax.tree.leaves(st2.params)[0]))
    np.testing.assert_allclose(outs[1][0], outs[4][0], rtol=1e-5)
    np.testing.assert_allclose(outs[1][1], outs[4][1], rtol=1e-4, atol=1e-6)
