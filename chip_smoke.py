"""Smoke test of EF data-parallel training on TPU chips.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip exchange only

One process drives every chip it uses. It trains granite-moe-1b-a400m at its
published widths (d_model 1024, 16/8 heads of 64, all 32 experts of width 512,
8 per token, vocabulary 49155), cut to 4 of its 24 layers, from random
weights and synthetic tokens made from ``--seed``, through the normal entry
points: ``TrainJob`` → ``make_train_step`` → the bucketed EF exchange.

One chip, in order:

* a few steps of ``dense``, then of ``ef_allgather`` (W = 1, so ``auto``
  resolves to ``xla``), each with its compile time, median step time and
  the device's ``peak_bytes_in_use``;
* the compiled EF step must contain ``tpu_custom_call`` (the Pallas kernels
  ran, not the jnp reference);
* on one real bucket stack, ``ops.ef_sign_bucket_step`` must match its
  ``force="ref"`` reference: words bitwise, scales and residual within 1e-6
  relative; the decompress-mean kernel likewise at W = 1 and W = 4;
* every loss must be finite.

``--chips 4``: ``ef_allgather`` on the ``xla``, ``pallas_dma`` and ``auto``
backends of a ``data=4`` mesh. The aggregator alone, on one set of inputs,
must give bitwise-equal updates and residuals on all three; 3 training steps
from one seed on each backend report how far the final params are apart, and
the ``auto`` run must be bitwise equal to the run of the backend it resolved
to (see ``four_chips``).

Earlier lines of stdout are JSON records of each phase. The last line is one
JSON object, ``{"ok": ..., "device": {"platform", "kind", "count"}}``. The
exit code is 0 only when every check passed. Where JAX finds no TPU the
script exits with code 2 before any training.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.comm import api as comm_api, backends, bucketize  # noqa: E402
from repro.comm.api import CommSpec  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.mesh import make_host_mesh, use_mesh  # noqa: E402
from repro.train import loop  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "granite_moe_1b_a400m"
LAYERS = 4
BATCH_PER_CHIP = 4
SEQ = 1024
RTOL = 1e-6


def smoke_config():
    return dataclasses.replace(get_config(ARCH), num_layers=LAYERS)


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def train(cfg, mesh, strategy: str, *, backend="auto", steps: int, batch: int, seq: int, seed: int):
    """Run ``steps`` compiled steps of one data-parallel job; return the
    final state and what the smoke reports about the run."""
    job = loop.TrainJob(
        cfg=cfg, mesh=mesh, steps=steps, batch=batch, seq=seq, lr=1e-3,
        optimizer="sgd", seed=seed, comm=CommSpec(strategy=strategy, backend=backend),
    )
    with use_mesh(mesh):
        prep = loop.prepare_training(job)
        batch_sharding = prep.bundle.in_shardings[1]
        first = jax.device_put(prep.example, batch_sharding)
        t0 = time.perf_counter()
        step = prep.step_fn.lower(prep.state, first).compile()
        compile_s = time.perf_counter() - t0
        state, losses, step_s = prep.state, [], []
        for i in range(steps):
            b = first if i == 0 else jax.device_put(next(prep.batches), batch_sharding)
            t = time.perf_counter()
            state, (loss, _) = step(state, b)
            jax.block_until_ready((state, loss))
            step_s.append(time.perf_counter() - t)
            losses.append(float(loss))
    resolved = None
    if strategy != "dense":
        layout = bucketize.build_layout(state.params, prep.spec.bucket_size)
        resolved = backends.resolve(prep.spec, mesh, prep.ef_axes, layout=layout).name
    info = {
        "strategy": strategy,
        "backend": resolved,
        "compile_s": compile_s,
        # the first call pays one-time dispatch set-up; the median skips it
        "median_step_s": statistics.median(step_s[1:] or step_s),
        "step_s": step_s,
        "losses": losses,
        "tpu_custom_calls": step.as_text().count("tpu_custom_call"),
        "ef_axes": list(prep.ef_axes),
    }
    return state, info


def _max_rel(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def kernel_check(n_buckets: int, bucket_size: int, seed: int) -> dict:
    """Fused EF-sign kernels against the jnp reference on one bucket stack."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    g = jax.random.normal(k1, (n_buckets, bucket_size), jnp.float32)
    e = 0.1 * jax.random.normal(k2, (n_buckets, bucket_size), jnp.float32)
    words, scales, resid, _ = ops.ef_sign_bucket_step(g, e)
    r_words, r_scales, r_resid, _ = ops.ef_sign_bucket_step(g, e, force="ref")
    out = {
        "n_buckets": n_buckets,
        "bucket_size": bucket_size,
        "words_bitwise_equal": bool(jnp.array_equal(words, r_words)),
        "scale_max_rel": float(jnp.max(jnp.abs(scales - r_scales) / jnp.abs(r_scales))),
        "residual_max_rel": _max_rel(resid, r_resid),
    }
    del g, e, resid, r_resid
    for w in (1, 4):
        ws = jnp.stack([jnp.roll(words, i, axis=0) for i in range(w)])
        ss = jnp.stack([scales * (i + 1) for i in range(w)])
        got = ops.bucket_decompress_mean(ws, ss)
        want = ops.bucket_decompress_mean(ws, ss, force="ref")
        out[f"mean_w{w}_bitwise_equal"] = bool(jnp.array_equal(got, want))
        out[f"mean_w{w}_max_rel"] = _max_rel(got, want)
        del got, want
    out["ok"] = (
        out["words_bitwise_equal"]
        and out["scale_max_rel"] <= RTOL
        and out["residual_max_rel"] <= RTOL
        and out["mean_w1_max_rel"] <= RTOL
        and out["mean_w4_max_rel"] <= RTOL
    )
    return out


def one_chip(cfg, seed: int) -> bool:
    mesh = make_host_mesh(data=1, model=1)
    checks = {}
    state, dense = train(cfg, mesh, "dense", steps=3, batch=BATCH_PER_CHIP, seq=SEQ, seed=seed)
    del state
    report("dense", peak_bytes_in_use=peak_bytes(), **dense)

    state, ef = train(cfg, mesh, "ef_allgather", steps=5, batch=BATCH_PER_CHIP, seq=SEQ, seed=seed)
    n_buckets = state.agg_state.worker_error[0].shape[1]
    del state
    report("ef_allgather", peak_bytes_in_use=peak_bytes(), n_buckets=n_buckets, **ef)
    checks["ef_step_has_pallas_kernels"] = ef["tpu_custom_calls"] > 0
    checks["losses_finite"] = all(math.isfinite(x) for x in dense["losses"] + ef["losses"])

    kern = kernel_check(n_buckets, bucketize.DEFAULT_BUCKET_SIZE, seed)
    report("kernels_vs_ref", **kern)
    checks["pallas_matches_ref"] = kern["ok"]
    report("checks", **checks)
    return all(checks.values())


def compare(a, b) -> dict:
    """How far two pytrees of arrays are apart, element by element."""
    pairs = list(zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    mismatched = sum(int(jnp.sum(x != y)) for x, y in pairs)
    return {
        "bitwise_equal": mismatched == 0,
        "mismatched": mismatched,
        "of": sum(x.size for x, _ in pairs),
        "max_abs_diff": max(float(jnp.max(jnp.abs(x - y))) for x, y in pairs),
        "max_abs": max(float(jnp.max(jnp.abs(x))) for x, _ in pairs),
    }


def exchange_check(mesh, params, err, srv, key, ef_axes, seed: int) -> dict:
    """``ef_allgather``'s aggregator alone on each backend, on one set of
    inputs: the aggregated update and the new EF residual must be bitwise
    equal across transports."""
    layout = bucketize.build_layout(params, bucketize.DEFAULT_BUCKET_SIZE)
    shardings = [x.sharding for x in err]
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(err))
    buckets = tuple(
        jax.jit(lambda k, s=x.shape: jax.random.normal(k, s, jnp.float32), out_shardings=sh)(k)
        for k, x, sh in zip(keys, err, shardings)
    )
    out, ref = {}, None
    with use_mesh(mesh):
        for backend in ("xla", "pallas_dma", "auto"):
            spec = CommSpec(strategy="ef_allgather", backend=backend)
            agg = comm_api.make_aggregator(spec, layout, mesh, ef_axes)
            mean, new_err, _, _ = jax.jit(agg)(buckets, err, srv, key)
            got = (mean, new_err)
            name = backends.resolve(spec, mesh, ef_axes, layout=layout).name
            out[f"resolved[{backend}]"] = name
            if ref is None:
                ref = got
            else:
                out[f"bitwise_xla_vs_{backend}"] = compare(ref, got)["bitwise_equal"]
            del mean, new_err, got
    return out


def four_chips(cfg, seed: int) -> bool:
    """``ef_allgather`` on the three backends of a ``data=4`` mesh.

    The transports are compared where they are the only difference: the
    aggregator alone on one set of inputs must give bitwise-equal updates and
    residuals. Whole training steps are separate programs per backend, and
    the TPU compiler may tile the same matmul differently in each (which
    changes its f32 summation order), so their params are reported, not
    required to be bitwise equal. ``auto`` resolves to one of the other two
    and runs that same program again: its trajectory must be bitwise equal
    to that run's.
    """
    mesh = make_host_mesh(data=4, model=1)
    kw = dict(steps=3, batch=4 * BATCH_PER_CHIP, seq=SEQ, seed=seed)
    checks, finals, losses = {}, {}, []
    for backend in ("xla", "pallas_dma", "auto"):
        state, info = train(cfg, mesh, "ef_allgather", backend=backend, **kw)
        losses += info["losses"]
        report(f"ef_allgather[{backend}]", peak_bytes_in_use=peak_bytes(), **info)
        finals[backend] = (state.params, state.agg_state.worker_error)
        if backend == "xla":
            agg = state.agg_state
            ex = exchange_check(
                mesh, state.params, agg.worker_error, agg.server_error, agg.key,
                tuple(info["ef_axes"]), seed,
            )
            report("exchange_alone", **ex)
            checks["exchange_bitwise_across_backends"] = all(
                v for k, v in ex.items() if k.startswith("bitwise_")
            )
        else:
            ref_params, ref_err = finals["xla"]
            report(
                f"xla_vs_{backend}",
                params=compare(ref_params, state.params),
                residual=compare(ref_err, state.agg_state.worker_error),
            )
        if backend == "auto":
            twin = finals.get(info["backend"])  # the run of the same program
            checks["auto_run_bitwise_equal_to_its_backend"] = (
                twin is not None and compare(twin, finals["auto"])["bitwise_equal"]
            )
        del state
    checks["losses_finite"] = all(math.isfinite(x) for x in losses)
    report("checks", **checks)
    return all(checks.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)}", file=sys.stderr)
        return 2
    report("setup", compile_cache=enable_compile_cache(), jax=jax.__version__)

    run = four_chips if args.chips == 4 else one_chip
    ok = run(smoke_config(), args.seed)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
