"""The benchmark's own FLOP and byte counts, against hand counts and against
the FLOPs the compiled program executes."""

import dataclasses
import json

import jax
import pytest

import cells
import counts
import tiny

GRANITE = json.loads((cells.HERE / "configs" / "granite_moe_1b_a400m-6l.json").read_text())
DEEPSEEK = json.loads((cells.HERE / "configs" / "deepseek_7b-2l-v8.json").read_text())


def test_granite_forward_flops_by_hand():
    # per layer: q,o 1024x1024, k,v 1024x512 -> 2*3M; causal scores at 1024:
    # 2*2*16*64*512; router 2*1024*32; 8 experts x 3 matmuls 1024x512
    layer = 2 * 3 * 1024 * 1024 + 2 * 2 * 16 * 64 * 512 + 2 * 1024 * 32 + 8 * 3 * 2 * 1024 * 512
    head = 2 * 1024 * 49155
    assert counts.forward_flops_per_token(GRANITE, 1024) == 6 * layer + head
    assert counts.train_flops_per_step(GRANITE, 4096, 1024) == pytest.approx(3.7158e12, rel=1e-4)


def test_deepseek_forward_flops_by_hand():
    layer = 2 * 4 * 4096 * 4096 + 2 * 2 * 32 * 128 * 2048 + 3 * 2 * 4096 * 11008
    head = 2 * 4096 * 12800
    assert counts.forward_flops_per_token(DEEPSEEK, 4096) == 2 * layer + head
    assert counts.train_flops_per_step(DEEPSEEK, 4096, 4096) == pytest.approx(12.060e12, rel=1e-3)


def test_param_counts_and_buckets():
    # the program's param_counts() leaves out the final norm (d elements)
    assert counts.param_count(GRANITE) == 371_666_944 + 1024
    assert counts.param_count(DEEPSEEK) == 509_624_320 + 4096
    assert counts.n_buckets(GRANITE, 65536) == 5672
    assert counts.n_buckets(DEEPSEEK, 65536) == 7777


def test_param_count_matches_the_program_tree():
    from repro.models import transformer

    for moe in (True, False):
        c = tiny.config(moe)
        shapes = jax.eval_shape(
            lambda k, c=c: transformer.init_params(cells.program_config(c), k), jax.random.PRNGKey(0)
        )
        assert counts.param_count(c) == sum(x.size for x in jax.tree.leaves(shapes))


def test_kernel_bytes_by_hand():
    nb, bs = 5672, 65536
    n = nb * bs
    assert counts.stats_bytes(nb, bs) == 8 * n + 8 * nb
    assert counts.compress_bytes(nb, bs) == 12 * n + n / 8 + 4 * nb
    assert counts.decompress_mean_bytes(nb, bs, 4) == 4 * (n / 8 + 4 * nb) + 4 * n


@pytest.mark.parametrize("strategy", ["dense", "ef_allgather"])
@pytest.mark.parametrize("moe", [True, False])
def test_required_flops_at_most_executed(moe, strategy):
    """What the step requires is no more than what its compiled program
    executes (which adds remat recompute, one-hot dispatch and full-square
    attention scores)."""
    from repro.launch.mesh import use_mesh
    from repro.utils import hlo

    import run

    c = tiny.cell(strategy, moe=moe)
    c = dataclasses.replace(c, config={**c.config, "num_hidden_layers": 2})
    mesh, pool, prep = run.build(c, seed=5)
    with use_mesh(mesh):
        text = prep.step_fn.lower(prep.state, pool[0]).compile().as_text()
    executed = hlo.analyze(text)["dot_flops"]
    required = counts.train_flops_per_step(c.config, c.tokens_per_step, c.seq)
    assert 0 < required <= executed
