"""Training loop: builds the step bundle, streams batches, logs, checkpoints."""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Iterator

import jax

from repro.comm import bucketize as comm_bucketize
from repro.comm import collective as comm_collective
from repro.comm.api import CommSpec
from repro.comm.bucketize import DEFAULT_BUCKET_SIZE
from repro.obs import sink as obs_sink
from repro.obs import telemetry as obs_telemetry
from repro.obs import trace as obs_trace
from repro.configs.base import ByzConfig, OverlapConfig
from repro.core import optim
from repro.core.compressors import get_compressor
from repro.data import synthetic
from repro.launch.mesh import ef_axis_names, use_mesh
from repro.models.config import ModelConfig
from repro.sharding.rules import ShardingRules, default_policy
from repro.train import checkpoint as ckpt
from repro.train import steps as steps_lib
from repro.train.state import init_train_state


@dataclasses.dataclass
class TrainJob:
    cfg: ModelConfig
    mesh: Any
    steps: int = 100
    batch: int = 8
    seq: int = 128
    lr: float = 0.02
    momentum: float = 0.0
    weight_decay: float = 0.0
    optimizer: str = "sgd"  # local per-worker chain: sgd | ef_sgd | adam | ...
    # dense | ef_allgather | ef_ring | ef_alltoall | majority_vote |
    # ef_coord_median | ef_trimmed_mean | ef_norm_filter
    strategy: str = "dense"
    compressor: str = "scaled_sign"
    policy: str | None = None
    seed: int = 0
    log_every: int = 10
    ckpt_every: int = 0
    ckpt_dir: str = ""
    lr_schedule: str = "step_decay"  # the paper's /10-decimation schedule
    microbatches: int = 1  # gradient accumulation (M sequential passes)
    # gradient-exchange granularity: fixed-size buckets through repro.comm
    # (the default wire path); None falls back to per-leaf aggregation
    bucket_size: int | None = DEFAULT_BUCKET_SIZE
    # async overlap: pipeline per-group compression + collectives with the
    # backward (repro.overlap); None = one aggregator call after full grad
    overlap: OverlapConfig | None = None
    # Byzantine knobs: fault-injected worker lanes + declared robust
    # tolerance (repro.comm.adversary / repro.comm.robust); None = honest
    byz: ByzConfig | None = None
    # federated rider (repro.fed): run rounds over a simulated client
    # population instead of data-parallel steps; steps count ROUNDS and
    # batch is the PER-CLIENT batch (see repro.fed.loop)
    fed: Any = None  # FedSpec | None
    # the one spec describing the whole gradient exchange; None folds the
    # individual legacy fields above into a CommSpec (comm_spec()), set it
    # to override them wholesale (e.g. to pick a collective backend)
    comm: CommSpec | None = None
    # in-graph telemetry level ("off" | "full") — repro.obs run records
    telemetry: str = "off"
    # directory for the schema-versioned run.jsonl (repro.obs.sink); empty
    # disables the file sink (log_fn / history still work as before)
    log_dir: str = ""

    def comm_spec(self) -> CommSpec:
        """The job's gradient-exchange spec (``comm`` or the legacy fields)."""
        if self.comm is not None:
            if self.telemetry != "off" and self.comm.telemetry == "off":
                return dataclasses.replace(self.comm, telemetry=self.telemetry)
            return self.comm
        return CommSpec(
            strategy=self.strategy,
            compressor=self.compressor,
            bucket_size=self.bucket_size,
            overlap=self.overlap,
            byz=self.byz,
            telemetry=self.telemetry,
            fed=self.fed,
        )


def _local_chain(job: TrainJob) -> optim.Transform:
    sched = {
        "constant": optim.constant_schedule(job.lr),
        "step_decay": optim.step_decay_schedule(job.lr, job.steps),
        "cosine": optim.cosine_schedule(job.lr, job.steps),
    }[job.lr_schedule]
    kw = dict(weight_decay=job.weight_decay)
    if job.optimizer in ("sgd", "sgdm"):
        return optim.sgd(sched, momentum=job.momentum or (0.9 if job.optimizer == "sgdm" else 0.0), **kw)
    if job.optimizer in ("ef_sgd", "ef_signsgd"):
        return optim.ef_sgd(sched, compressor=get_compressor(job.compressor), momentum=job.momentum, **kw)
    if job.optimizer == "signsgd":
        return optim.signsgd(sched, **kw)
    if job.optimizer == "signum":
        return optim.signum(sched, **kw)
    if job.optimizer == "adam":
        return optim.adam(sched, **kw)
    raise ValueError(job.optimizer)


@dataclasses.dataclass
class PreparedRun:
    """A job's initial state and step, built but not yet run (see
    :func:`prepare_training`)."""

    state: Any
    bundle: steps_lib.StepBundle
    step_fn: Callable
    example: dict
    batches: Iterator[dict]
    spec: CommSpec
    policy: str
    ef_axes: tuple[str, ...]


def prepare_training(job: TrainJob, batches: Iterator[dict] | None = None) -> PreparedRun:
    """Initialise the state and build the jitted train step of a
    data-parallel ``job``. Call under ``use_mesh(job.mesh)``."""
    cfg, mesh = job.cfg, job.mesh
    spec = job.comm_spec()
    policy = job.policy or default_policy(cfg)
    rules = ShardingRules(cfg, mesh, policy)
    ef_axes = ef_axis_names(mesh, policy) if spec.strategy != "dense" else ()
    chain = _local_chain(job)
    key = jax.random.PRNGKey(job.seed)

    if batches is None:
        batches = synthetic.token_batches(job.seed, job.batch, job.seq, cfg.vocab_size)

    bucket_size = spec.bucket_size if spec.strategy != "dense" else None
    state = init_train_state(
        cfg, key, chain, spec.strategy, mesh, ef_axes, bucket_size=bucket_size
    )
    example = next(batches)
    bundle = steps_lib.make_train_step(
        cfg, mesh, rules,
        spec=spec, local_chain=chain, ef_axes=ef_axes,
        batch_example=example, state_example=state, microbatches=job.microbatches,
    )
    state = jax.device_put(state, bundle.in_shardings[0])
    return PreparedRun(state, bundle, bundle.jit(), example, batches, spec, policy, ef_axes)


def run_training(
    job: TrainJob, batches: Iterator[dict] | None = None, log_fn: Callable | None = None
):
    mesh = job.mesh
    spec = job.comm_spec()
    if spec.fed is not None:
        from repro.fed import loop as fed_loop  # lazy: keeps fed out of DP runs

        return fed_loop.run_fed_training(job, spec, log_fn=log_fn)
    with use_mesh(mesh):
        prep = prepare_training(job, batches)
        state, bundle, step_fn = prep.state, prep.bundle, prep.step_fn
        example, batches, policy, ef_axes = prep.example, prep.batches, prep.policy, prep.ef_axes

        writer = None
        if job.log_dir:
            writer = obs_sink.RunRecordWriter(os.path.join(job.log_dir, "run.jsonl"))
            modeled = None
            if spec.strategy != "dense" and spec.bucket_size is not None:
                layout = comm_bucketize.build_layout(state.params, spec.bucket_size)
                w = comm_collective.world_size(mesh, ef_axes)
                modeled = obs_telemetry.modeled_wire_bytes(
                    spec.strategy, layout, w, spec.resolved_compressor
                )
            writer.write(
                obs_sink.run_meta(
                    config={
                        "strategy": spec.strategy,
                        "backend": spec.backend,
                        "steps": job.steps,
                        "batch": job.batch,
                        "seq": job.seq,
                        "optimizer": job.optimizer,
                        "policy": policy,
                        "bucket_size": spec.bucket_size,
                    },
                    telemetry=spec.telemetry,
                    modeled_wire_bytes=modeled,
                )
            )

        history = []
        timers = obs_trace.WallTimers()
        t0 = time.time()
        try:
            for i in range(job.steps):
                batch = example if i == 0 else next(batches)
                batch = jax.device_put(batch, bundle.in_shardings[1])
                logged = i % job.log_every == 0 or i == job.steps - 1
                with obs_trace.step_span(i), timers.region("step"):
                    state, (loss, metrics) = step_fn(state, batch)
                    if logged:
                        jax.block_until_ready(loss)
                walls = timers.drain()
                if logged:
                    rec = obs_sink.step_record(i, {"loss": loss, **metrics}, walls=walls)
                    rec["wall_s"] = time.time() - t0
                    history.append(rec)
                    if log_fn:
                        log_fn(rec)
                    if writer:
                        writer.write(rec)
                if job.ckpt_every and job.ckpt_dir and (i + 1) % job.ckpt_every == 0:
                    ckpt.save_checkpoint(job.ckpt_dir, jax.device_get(state), i + 1)
        finally:
            # the epilogue record is unconditional — a zero-step run (or a
            # crashed one) still closes with a parseable "final" line
            if writer:
                writer.write(
                    obs_sink.final_record(history, steps=job.steps, wall_s=time.time() - t0)
                )
                writer.close()
        return state, history
