"""Distributed train / prefill / decode step builders.

Three gradient-exchange paths share the loss code (DESIGN.md §5):

``dense``
    one ``jax.jit``; GSPMD inserts the fp32 gradient all-reduce/reduce-scatter
    — the SGD communication baseline. (An EF *optimizer* may still be used —
    that is the paper's single-worker Algorithm 2 applied per param shard.)

Bucketed EF strategies (the default wire path, ``bucket_size`` set)
    Per-worker grads come from a ``vmap`` over an explicit leading EF-worker
    axis (batch reshaped ``(W, B/W, ...)``) inside the ordinary GSPMD-auto
    world — no ``shard_map`` around the model, so tensor/expert/fsdp
    parallelism, remat, and the layer-stack ``lax.scan`` all compose
    untouched. Updates are flattened into fixed-size buckets
    (:mod:`repro.comm.bucketize`) and exchanged by the fully-manual
    collective in :mod:`repro.comm.collective` — the only ``shard_map`` in
    the step, with every mesh axis manual.

Per-leaf EF strategies (``bucket_size=None`` fallback)
    The original ``shard_map``-around-the-model path: manual over the EF
    worker axes with every other mesh axis GSPMD-auto, compressing leaf by
    leaf (:mod:`repro.core.aggregation`). Preserves intra-leaf shardings (no
    flatten), so it remains the choice for the giant-model dry-run.

Worker-local state (EF residuals, momentum traces) is stacked on a leading
EF-world axis and sharded over the EF axes; see ``state_specs``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm import adversary as comm_adversary
from repro.comm import api as comm_api
from repro.comm import bucketize as comm_bucketize
from repro.comm import collective as comm_collective
from repro.configs.base import ByzConfig, OverlapConfig
from repro.core import aggregation, optim
from repro.core.compressors import Compressor
from repro.models import layers, transformer
from repro.obs import trace as obs_trace
from repro.obs import telemetry as obs_telemetry
from repro.utils import compat
from repro.models.act_sharding import activation_sharding
from repro.models.config import ModelConfig
from repro.sharding.rules import ShardingRules
from repro.train.state import TrainState


def _prepend(spec: P, *axes) -> P:
    return P(*axes, *tuple(spec))


def _filter_manual_spec(spec: P, manual: frozenset) -> P:
    """shard_map in/out_specs may only mention manual axes; auto-axis
    shardings ride along implicitly. Drop non-manual names from the spec."""
    out = []
    for entry in tuple(spec):
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in manual)
            out.append(kept if kept else None)
        else:
            out.append(entry if entry in manual else None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def _filter_manual(tree_specs, manual):
    manual = frozenset(manual)
    return jax.tree.map(
        lambda s: _filter_manual_spec(s, manual), tree_specs,
        is_leaf=lambda s: isinstance(s, P),
    )


def _worker_state_specs(tree_specs, ef_axes):
    """Worker-local pytrees get a leading EF-world dim sharded over ef_axes."""
    ef = ef_axes if len(ef_axes) != 1 else ef_axes[0]
    return jax.tree.map(lambda s: _prepend(s, ef), tree_specs)


class StepBundle:
    """A compiled-step description: fn + in/out shardings, ready to lower."""

    def __init__(self, fn, in_shardings, out_shardings, donate_argnums=()):
        self.fn = fn
        self.in_shardings = in_shardings
        self.out_shardings = out_shardings
        self.donate_argnums = donate_argnums

    def jit(self):
        return jax.jit(
            self.fn,
            in_shardings=self.in_shardings,
            out_shardings=self.out_shardings,
            donate_argnums=self.donate_argnums,
        )


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def _make_grad_fn(cfg: ModelConfig, microbatches: int, act_ctx):
    """value_and_grad of the mean loss, optionally accumulated over
    microbatches (batch dim split M-ways, lax.scan accumulation — constant
    activation memory at the cost of M sequential passes)."""

    def single(params, batch):
        def lf(p):
            with act_ctx():
                return transformer.loss_fn(p, cfg, batch)

        return jax.value_and_grad(lf, has_aux=True)(params)

    if microbatches <= 1:
        return single

    def accumulated(params, batch):
        def split(x):
            b = x.shape[0]
            assert b % microbatches == 0, (b, microbatches)
            return x.reshape(microbatches, b // microbatches, *x.shape[1:])

        mb = jax.tree.map(split, batch)

        def body(carry, mb_batch):
            (loss, metrics), grads = single(params, mb_batch)
            acc_g, acc_l, acc_m = carry
            acc_g = jax.tree.map(jnp.add, acc_g, grads)
            acc_m = {k: acc_m[k] + metrics[k] for k in acc_m}
            return (acc_g, acc_l + loss, acc_m), None

        zeros_g = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
        # first microbatch runs unrolled to seed the metric structure
        (l0, m0), g0 = single(params, jax.tree.map(lambda x: x[0], mb))
        zero_m = {k: jnp.zeros_like(v) for k, v in m0.items()}
        (grads, loss, metrics), _ = jax.lax.scan(
            body, (zeros_g, jnp.float32(0.0), zero_m), jax.tree.map(lambda x: x[1:], mb)
        )
        grads = jax.tree.map(lambda a, g: (a + g.astype(jnp.float32)) / microbatches, grads, g0)
        loss = (loss + l0) / microbatches
        metrics = {k: (metrics[k] + m0[k]) / microbatches for k in metrics}
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
        return (loss, metrics), grads

    return accumulated


def stageable(cfg: ModelConfig, microbatches: int) -> bool:
    """True when the loss decomposes into embed | block-stack | head ``vjp``
    stages. The block stack itself is a ``lax.scan``, so per-LAYER grads are
    never splittable here — three stages is the finest checkpoint-boundary
    chunking this model family admits; models that fail even this gate fall
    back to post-hoc pipelining of compress/collective (the overlap executor
    works either way)."""
    return microbatches <= 1 and not cfg.encoder_layers and not cfg.num_patch_tokens


def _make_staged_grad_fn(cfg: ModelConfig, act_ctx):
    """value_and_grad chunked at the embed | stack | head reverse-AD
    boundaries via per-stage ``jax.vjp``.

    Numerically this is the same chain rule over the same primitives as
    ``jax.value_and_grad`` of the fused loss (tests pin bitwise equality);
    what changes is the *dependency structure* of the jit graph: head and
    final-norm gradients are produced by ``vjp_head`` before the stack's
    backward scan runs, and the embedding gradient only at the very end — so
    the overlap executor's first bucket groups (rank 0 = head/final-norm, see
    :mod:`repro.overlap.schedule`) can compress and issue their collectives
    while the backward is still inside the scan.
    """
    tied = cfg.tie_embeddings

    def staged(params, batch):
        p_embed = params["embed"]
        p_head = {"final_norm": params["final_norm"]}
        if not tied:
            p_head["head"] = params["head"]

        def f_embed(pe):
            with act_ctx():
                x, _ = transformer.embed_inputs({"embed": pe}, cfg, batch)
            return x

        def f_stack(pb, x):
            with act_ctx():
                positions = 0 + jnp.arange(x.shape[1])
                x1, _, aux = transformer._run_stack(pb, cfg, x, positions, None, 0, None)
            return x1, aux

        def f_head(ph, pe, x1):
            with act_ctx():
                x = layers.apply_norm(ph["final_norm"], x1, cfg.norm_type)
                if tied:
                    logits = x @ pe["table"].astype(x.dtype).T
                else:
                    logits = layers.apply_linear(ph["head"], x)
                logits = logits.astype(jnp.float32)
                logp = jax.nn.log_softmax(logits, axis=-1)
                labels = batch["labels"]
                nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
                mask = batch.get("loss_mask", jnp.ones_like(nll))
                return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

        x0, vjp_embed = jax.vjp(f_embed, p_embed)
        (x1, aux), vjp_stack = jax.vjp(f_stack, params["blocks"], x0)
        ce, vjp_head = jax.vjp(f_head, p_head, p_embed, x1)
        total = ce
        if cfg.is_moe:
            total = (
                total
                + cfg.aux_loss_coef * aux["moe_aux_loss"]
                + cfg.router_z_coef * aux["moe_z_loss"]
            )

        # reverse-AD in stage order: head grads first, embedding last
        g_head, g_embed_head, dx1 = vjp_head(jnp.ones_like(ce))
        daux = {
            "moe_aux_loss": jnp.float32(cfg.aux_loss_coef if cfg.is_moe else 0.0),
            "moe_z_loss": jnp.float32(cfg.router_z_coef if cfg.is_moe else 0.0),
        }
        g_blocks, dx0 = vjp_stack((dx1, daux))
        (g_embed,) = vjp_embed(dx0)
        if tied:  # the head's contribution to the shared table accumulates
            g_embed = jax.tree.map(jnp.add, g_embed, g_embed_head)

        grads = {"blocks": g_blocks, "embed": g_embed, "final_norm": g_head["final_norm"]}
        if not tied:
            grads["head"] = g_head["head"]
        metrics = {"loss": ce, **aux}
        return (total, metrics), grads

    return staged


def make_train_step(
    cfg: ModelConfig,
    mesh,
    rules: ShardingRules,
    *,
    spec: comm_api.CommSpec | None = None,
    strategy: str = "dense",
    comp: Compressor | None = None,
    local_chain: optim.Transform,
    ef_axes: tuple[str, ...] = (),
    batch_example: Any,
    state_example: TrainState,
    microbatches: int = 1,
    bucket_size: int | None = None,
    overlap_groups: int | None = None,
    byz: ByzConfig | None = None,
) -> StepBundle:
    """Build the train step for one :class:`~repro.comm.api.CommSpec`.

    ``spec`` is the one description of the gradient exchange (strategy,
    compressor, bucket size, collective backend, overlap/byz riders); the
    individual keyword knobs remain accepted as the legacy spelling and are
    folded into a spec when ``spec`` is not given (``spec`` wins otherwise).
    All path validation happens in ``CommSpec.validate`` — structural checks
    here, the world-dependent tolerance check at aggregator build time.
    """
    if spec is None:
        spec = comm_api.CommSpec(
            strategy=strategy,
            compressor=comp,
            bucket_size=bucket_size,
            overlap=OverlapConfig(n_groups=overlap_groups) if overlap_groups is not None else None,
            byz=byz,
        )
    spec.validate()
    strategy, comp, bucket_size = spec.strategy, spec.resolved_compressor, spec.bucket_size
    param_specs = rules.param_specs(state_example.params)
    opt_specs_base = jax.tree.map(
        lambda _: P(), state_example.opt_state
    ) if rules.policy == "dp" else _opt_specs(rules, state_example)
    batch_specs = rules.batch_specs(batch_example)

    if strategy == "dense":
        assert not ef_axes

        dp_axes = rules.dp_axes

        grad_fn = _make_grad_fn(
            cfg, microbatches, lambda: activation_sharding(dp_axes, "model")
        )

        def train_step(state: TrainState, batch):
            (loss, metrics), grads = grad_fn(state.params, batch)
            updates, opt_state = local_chain.update(grads, state.opt_state, state.params)
            params = optim.apply_updates(state.params, updates)
            new_state = TrainState(params, opt_state, state.agg_state, state.step + 1)
            d = sum(x.size for x in jax.tree.leaves(grads))
            metrics = dict(metrics, wire_bytes=jnp.float32(8.0 * d), density=jnp.float32(1.0))
            return new_state, (loss, metrics)

        state_specs = TrainState(
            params=param_specs,
            opt_state=opt_specs_base,
            agg_state=jax.tree.map(lambda _: P(), state_example.agg_state),
            step=P(),
        )
        in_sh = (rules.named(state_specs), rules.named(batch_specs))
        out_sh = (rules.named(state_specs), rules.named((P(), {
            k: P() for k in ("loss", "moe_aux_loss", "moe_z_loss", "wire_bytes", "density")
        })))
        return StepBundle(train_step, in_sh, out_sh, donate_argnums=(0,))

    # ---------------- EF strategies: bucketed comm layer (default) --------
    assert ef_axes, "EF strategies need at least one manual worker axis"
    if bucket_size is not None:
        return _make_bucketed_ef_step(
            cfg, mesh, rules, spec=spec, local_chain=local_chain,
            ef_axes=ef_axes, batch_example=batch_example, state_example=state_example,
            microbatches=microbatches,
            param_specs=param_specs, opt_specs_base=opt_specs_base,
            batch_specs=batch_specs,
        )

    # ---------------- per-leaf fallback: shard_map over the EF worker axes
    ef = ef_axes if len(ef_axes) != 1 else ef_axes[0]

    has_worker_err = bool(jax.tree.leaves(state_example.agg_state.worker_error))
    agg_specs = aggregation.AggState(
        worker_error=_worker_state_specs(param_specs, ef_axes) if has_worker_err else (),
        server_error=jax.tree.map(lambda _: P(ef), state_example.agg_state.server_error),
        key=P(),
        steps=P(),
    )
    opt_specs = _worker_state_specs(opt_specs_base, ef_axes)
    state_specs = TrainState(params=param_specs, opt_state=opt_specs, agg_state=agg_specs, step=P())
    metric_keys = ("loss", "moe_aux_loss", "moe_z_loss", "wire_bytes", "density")

    def _strip(tree):  # drop the local leading EF-world dim (size 1)
        return jax.tree.map(lambda x: x[0], tree)

    def _lift(tree):
        return jax.tree.map(lambda x: x[None], tree)

    auto_dp = tuple(a for a in rules.dp_axes if a not in ef_axes)
    grad_fn = _make_grad_fn(
        cfg, microbatches, lambda: activation_sharding(auto_dp or None, "model")
    )

    def worker_body(params, batch, opt_state, agg_state):
        (loss, metrics), grads = grad_fn(params, batch)
        opt_local = _strip(opt_state)
        agg_local = agg_state._replace(
            worker_error=_strip(agg_state.worker_error),
            server_error=_strip(agg_state.server_error),
        )
        updates, opt_local = local_chain.update(grads, opt_local, params)
        updates, agg_local, info = aggregation.aggregate(
            strategy, updates, agg_local, ef_axes, comp
        )
        loss = lax.pmean(loss, ef_axes)
        metrics = {k: lax.pmean(v, ef_axes) for k, v in metrics.items()}
        metrics["wire_bytes"] = info.wire_bytes_per_device
        metrics["density"] = info.mean_density
        new_agg = agg_state._replace(
            worker_error=_lift(agg_local.worker_error),
            server_error=_lift(agg_local.server_error),
            key=agg_local.key,
            steps=agg_local.steps,
        )
        return updates, _lift(opt_local), new_agg, loss, metrics

    manual = frozenset(ef_axes)
    sharded_body = compat.shard_map(
        worker_body,
        mesh=mesh,
        in_specs=_filter_manual((param_specs, batch_specs, opt_specs, agg_specs), manual),
        out_specs=_filter_manual(
            (param_specs, opt_specs, agg_specs, P(), {k: P() for k in metric_keys}),
            manual,
        ),
        manual_axes=manual,
    )

    def train_step(state: TrainState, batch):
        updates, opt_state, agg_state, loss, metrics = sharded_body(
            state.params, batch, state.opt_state, state.agg_state
        )
        params = optim.apply_updates(state.params, updates)
        new_state = TrainState(params, opt_state, agg_state, state.step + 1)
        return new_state, (loss, metrics)

    in_sh = (rules.named(state_specs), rules.named(batch_specs))
    out_sh = (rules.named(state_specs), rules.named((P(), {k: P() for k in metric_keys})))
    return StepBundle(train_step, in_sh, out_sh, donate_argnums=(0,))


def _make_bucketed_ef_step(
    cfg: ModelConfig,
    mesh,
    rules: ShardingRules,
    *,
    spec: comm_api.CommSpec,
    local_chain: optim.Transform,
    ef_axes: tuple[str, ...],
    batch_example: Any,
    state_example: TrainState,
    microbatches: int,
    param_specs,
    opt_specs_base,
    batch_specs,
) -> StepBundle:
    """EF train step through the bucketed comm layer (see module docstring).

    The aggregator comes from the one construction path,
    :func:`repro.comm.api.make_aggregator`: it validates ``spec`` against the
    mesh, resolves the collective backend, and — with ``spec.overlap`` set —
    builds the overlap pipeline (a static
    :class:`~repro.overlap.schedule.OverlapSchedule` groups the buckets by
    reverse-AD availability and per-group collectives issue as independent
    dataflow chains). When the model admits it, the overlapped grad fn is the
    staged-``vjp`` variant so the head-stage groups' collectives are
    data-ready before the backward scan finishes. The trajectory is bitwise
    identical to the one-shot step.
    """
    strategy, comp, byz = spec.strategy, spec.resolved_compressor, spec.byz
    ef = ef_axes if len(ef_axes) != 1 else ef_axes[0]
    w = comm_collective.world_size(mesh, ef_axes)
    layout = comm_bucketize.build_layout(state_example.params, spec.bucket_size)
    # a 1-worker world has no collective latency to hide — pipelining would
    # be pure dispatch overhead, so make_aggregator degenerates overlap to
    # the one-shot path there
    overlap = spec.overlap is not None and w > 1
    agg_fn = comm_api.make_aggregator(
        spec, layout, mesh, ef_axes, params=state_example.params
    )
    attackers = comm_adversary.n_attackers(byz.fraction, w) if byz is not None else 0

    auto_dp = tuple(a for a in rules.dp_axes if a not in ef_axes)
    act_ctx = lambda: activation_sharding(auto_dp or None, "model")
    if overlap and stageable(cfg, microbatches):
        grad_fn = _make_staged_grad_fn(cfg, act_ctx)
    else:
        grad_fn = _make_grad_fn(cfg, microbatches, act_ctx)

    def _split_workers(x):
        b = x.shape[0]
        assert b % w == 0, f"batch dim {b} not divisible by EF world {w}"
        return x.reshape(w, b // w, *x.shape[1:])

    auto_dp_size = comm_collective.world_size(mesh, auto_dp)

    def _worker_sharding(leaf):
        inner = auto_dp if (auto_dp and leaf.shape[1] % auto_dp_size == 0) else None
        return NamedSharding(mesh, P(ef, inner, *([None] * (leaf.ndim - 2))))

    grad_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, _prepend(s, ef)), param_specs,
        is_leaf=lambda s: isinstance(s, P),
    )

    def train_step(state: TrainState, batch):
        wb = jax.tree.map(_split_workers, batch)
        wb = jax.tree.map(
            lambda x: lax.with_sharding_constraint(x, _worker_sharding(x)), wb
        )
        # per-worker grads: vmap over the leading EF-worker axis, params
        # broadcast — pure GSPMD-auto, composes with tp/fsdp/remat/scan
        with obs_trace.span(obs_trace.SPAN_BACKWARD):
            (loss_w, metrics_w), grads_w = jax.vmap(
                lambda b: grad_fn(state.params, b)
            )(wb)
        grads_w = lax.with_sharding_constraint(grads_w, grad_shardings)
        if attackers:
            # fault injection on the worker lanes; the attack key is folded
            # off the carried agg key so the honest RNG stream (split below)
            # is untouched and attackers=0 stays bitwise-identical
            grads_w = comm_adversary.corrupt_worker_tree(
                byz, grads_w, jax.random.fold_in(state.agg_state.key, 0x5A1), world=w
            )
        updates_w, opt_state = jax.vmap(
            lambda g, o: local_chain.update(g, o, state.params)
        )(grads_w, state.opt_state)
        with obs_trace.span(obs_trace.SPAN_BUCKETIZE):
            buckets_w = jax.vmap(lambda u: comm_bucketize.flatten_buckets(layout, u))(
                updates_w
            )
        key, sub = jax.random.split(state.agg_state.key)
        agg_buckets, new_err, new_srv, info = agg_fn(
            buckets_w,
            state.agg_state.worker_error,
            state.agg_state.server_error,
            sub,
        )
        with obs_trace.span(obs_trace.SPAN_APPLY):
            updates = comm_bucketize.unflatten_buckets(layout, agg_buckets)
            params = optim.apply_updates(state.params, updates)
        new_agg = aggregation.AggState(
            worker_error=new_err,
            server_error=new_srv,
            key=key,
            steps=state.agg_state.steps + 1,
        )
        loss = jnp.mean(loss_w)
        metrics = {k: jnp.mean(v) for k, v in metrics_w.items()}
        metrics["wire_bytes"] = info.wire_bytes_per_device
        metrics["density"] = info.mean_density
        if info.telemetry is not None:
            metrics["obs"] = info.telemetry
        new_state = TrainState(params, opt_state, new_agg, state.step + 1)
        return new_state, (loss, metrics)

    agg_specs = aggregation.AggState(
        worker_error=jax.tree.map(lambda _: P(ef), state_example.agg_state.worker_error),
        server_error=jax.tree.map(lambda _: P(ef), state_example.agg_state.server_error),
        key=P(),
        steps=P(),
    )
    opt_specs = _worker_state_specs(opt_specs_base, ef_axes)
    state_specs = TrainState(
        params=param_specs, opt_state=opt_specs, agg_state=agg_specs, step=P()
    )
    metric_keys = ("loss", "moe_aux_loss", "moe_z_loss", "wire_bytes", "density")
    metrics_sp = {k: P() for k in metric_keys}
    if spec.telemetry != "off":
        metrics_sp["obs"] = obs_telemetry.replicated_specs()
    in_sh = (rules.named(state_specs), rules.named(batch_specs))
    out_sh = (rules.named(state_specs), rules.named((P(), metrics_sp)))
    return StepBundle(train_step, in_sh, out_sh, donate_argnums=(0,))


def _opt_specs(rules: ShardingRules, state_example: TrainState):
    """Momentum traces etc. mirror param sharding; scalar states replicated."""
    param_specs = rules.param_specs(state_example.params)

    def rule(path, leaf):
        # TraceState/AdamState leaves mirror params by shape; counters scalar
        if leaf.ndim == 0:
            return P()
        # find a param leaf with identical path suffix via shape match
        return _match_param_spec(leaf, param_specs, state_example.params)

    return jax.tree_util.tree_map_with_path(rule, state_example.opt_state)


def _match_param_spec(leaf, param_specs, params):
    specs = jax.tree.leaves(param_specs, is_leaf=lambda s: isinstance(s, P))
    shapes = [p.shape for p in jax.tree.leaves(params)]
    for sp, sh in zip(specs, shapes):
        if sh == leaf.shape:
            return sp
    return P()


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def make_prefill_step(cfg: ModelConfig, mesh, rules: ShardingRules, *, batch_example, cache_example, params_example) -> StepBundle:
    param_specs = rules.param_specs(params_example)
    batch_specs = rules.batch_specs(batch_example)
    cache_specs = rules.cache_specs(cache_example)

    def prefill(params, batch, cache):
        with activation_sharding(rules.dp_axes, "model"):
            logits, cache, _ = transformer.forward(params, cfg, batch, cache=cache, pos=0)
        return logits[:, -1:, :], cache

    logit_spec = P(tuple(a for a in ("pod", "data") if a in mesh.axis_names) or None)
    in_sh = (rules.named(param_specs), rules.named(batch_specs), rules.named(cache_specs))
    out_sh = (NamedSharding(mesh, logit_spec), rules.named(cache_specs))
    return StepBundle(prefill, in_sh, out_sh, donate_argnums=(2,))


def make_decode_step(cfg: ModelConfig, mesh, rules: ShardingRules, *, cache_example, params_example) -> StepBundle:
    param_specs = rules.param_specs(params_example)
    cache_specs = rules.cache_specs(cache_example)
    b = jax.tree.leaves(cache_example)[0].shape[1]
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    tok_spec = P(dp) if b % dp_size == 0 and dp_size > 1 else P()

    def decode(params, cache, tokens, pos):
        with activation_sharding(rules.dp_axes, "model"):
            return transformer.decode_step(params, cfg, cache, tokens, pos)

    in_sh = (
        rules.named(param_specs),
        rules.named(cache_specs),
        NamedSharding(mesh, tok_spec),
        NamedSharding(mesh, P()),
    )
    out_sh = (NamedSharding(mesh, tok_spec), rules.named(cache_specs))
    return StepBundle(decode, in_sh, out_sh, donate_argnums=(1,))
