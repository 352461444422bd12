"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the program's own training job for the cell (``TrainJob`` ->
``prepare_training``: ``make_train_step`` and the bucketed EF exchange) on a
``data=<chips>`` mesh, draws every batch from ``--seed``, and drives the
compiled step through its first three steps, which also compiles it (from
JAX's persistent cache in ``<checkout>/.jax_cache`` after a cell's first
run). The window then drives the same step for ``--seconds`` seconds with one
step in flight: step i+1 is dispatched before step i's loss is waited on.
With ``--trace 1`` a profiler trace of a few steps takes the window's place
and the per-layer metrics are read from it.

Once the window has closed and the peak memory has been read, the program's
state is freed and the float32 reference (``reference.py``, with the
configuration's family from ``families/``) follows the same three first
steps from the seed; ``check.py`` compares them.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``,
and last ``compared``: each number compared with its limit. Exits with code
2 and prints no result where JAX finds no TPU or fewer chips than the cell
needs.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import cells  # noqa: E402
import check  # noqa: E402
import reference  # noqa: E402
import tokens  # noqa: E402

WARM_STEPS = 3  # set-up steps: the ones the reference follows
TRACE_STEPS = 10  # steps in a traced window
POOL = 64  # distinct batches a run cycles through


def _log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def _annotate(name: str):
    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


def _momentum(opt_state):
    """The heavy-ball momentum in the local optimizer's chain state."""
    for part in opt_state:
        if hasattr(part, "momentum"):
            return part.momentum
    raise ValueError("the local optimizer keeps no momentum to read the first gradient from")


def _worker_leaf_norms(tree, workers: int, worker_axis: bool):
    """(W, leaves) norms; a tree without a worker axis is worker 0's."""
    rows = []
    for w in range(workers):
        pick = (lambda x, w=w: x[w]) if worker_axis else (lambda x: x)
        rows.append(jax.device_get(reference.leaf_norms(jax.tree.map(pick, tree))))
    return rows


def _change(params, start_host):
    """Per leaf, params minus the host copy of the start: (norms, signs as
    int8 on the host), leaf by leaf."""
    norms, signs = [], []
    for x, x0 in zip(jax.tree.leaves(params), jax.tree.leaves(start_host)):
        d = x.astype(jnp.float32) - jax.device_put(x0, x.sharding).astype(jnp.float32)
        norms.append(float(jnp.sqrt(jnp.sum(jnp.square(d)))))
        signs.append(jax.device_get(jnp.sign(d).astype(jnp.int8)))
        del d
    return norms, signs


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)


def enable_cache() -> None:
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def build(cell: cells.Cell, seed: int):
    """The program's job for the cell from the seed: (mesh, batches, prepared
    run), built through ``TrainJob`` -> ``prepare_training``."""
    from repro.comm.api import CommSpec
    from repro.launch.mesh import make_host_mesh
    from repro.train import loop

    traffic = cell.traffic
    mesh = make_host_mesh(data=cell.chips, model=1)
    pool = tokens.batches(seed, POOL, cell.global_rows, cell.seq, cell.config["vocab_size"])
    job = loop.TrainJob(
        cfg=cells.program_config(cell.config), mesh=mesh, steps=1 << 30, batch=cell.global_rows,
        seq=cell.seq, lr=traffic["lr"], optimizer=traffic["optimizer"], lr_schedule="constant",
        seed=tokens.program_seed(seed),
        comm=CommSpec(strategy=traffic["strategy"], backend=traffic["backend"],
                      bucket_size=traffic["bucket_size"]),
    )
    return mesh, pool, loop.prepare_training(job, batches=iter(pool))


def first_steps(cell: cells.Cell, prep, pool):
    """Drive the compiled step through its first steps with the window's own
    call and feed; return the state and what the comparison reads."""
    feed = prep.bundle.in_shardings[1]
    start = jax.device_get(prep.state.params)  # on the host, for the change
    state, losses, grads = prep.state, [], None
    for i in range(WARM_STEPS):
        state, (loss, _) = prep.step_fn(state, jax.device_put(pool[i], feed))
        losses.append(float(loss))
        if i == 0:
            momentum = _momentum(state.opt_state)
            grads = _worker_leaf_norms(momentum, cell.workers, cell.ef)
            # worker 0's first gradient, on the host, for its difference
            first = jax.device_get(jax.tree.map(lambda x: x[0], momentum) if cell.ef else momentum)
    norms, signs = _change(state.params, start)
    return state, reference.Readings(losses, grads, norms, first, signs)


def reference_readings(cell: cells.Cell, seed: int, pool, devices, **variant):
    """The reference's readings of the same first steps from the seed."""
    traffic = cell.traffic
    return reference.run_steps(
        cells.reference_model(cell.config), jax.random.PRNGKey(tokens.program_seed(seed)),
        pool[:WARM_STEPS], workers=cell.workers, strategy=traffic["strategy"], lr=traffic["lr"],
        bucket_size=traffic["bucket_size"], steps=WARM_STEPS, devices=devices[: cell.workers],
        **variant,
    )


def resolved_backend(cell: cells.Cell, prep, mesh) -> str:
    if not cell.ef:
        return "none"
    from repro.comm import backends, bucketize

    layout = bucketize.build_layout(prep.state.params, prep.spec.bucket_size)
    return backends.resolve(prep.spec, mesh, prep.ef_axes, layout=layout).name


def window(step, state, pool, feed, seconds: float, trace: bool):
    """Drive the step with one in flight; return (state, completion times
    from the window's start, losses, steps attempted)."""
    done_at, losses, attempted, i = [], [], 0, WARM_STEPS
    t0 = time.perf_counter()
    with _annotate("dispatch"):
        state, (loss, _) = step(state, jax.device_put(pool[i % POOL], feed))
    attempted += 1
    while True:
        i += 1
        more = attempted < TRACE_STEPS if trace else time.perf_counter() - t0 < seconds
        pending = loss
        if more:
            with _annotate("input"):
                batch = jax.device_put(pool[i % POOL], feed)
            with _annotate("dispatch"):
                state, (loss, _) = step(state, batch)
            attempted += 1
        with _annotate("wait"):
            pending.block_until_ready()
        done_at.append(time.perf_counter() - t0)
        losses.append(pending)
        if not more:
            return state, done_at, losses, attempted


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool, devices) -> dict:
    from repro.launch.mesh import use_mesh

    enable_cache()
    mesh, pool, prep = build(cell, seed)
    chips = list(mesh.devices.flat)
    info = {"cell": cell.name, "seed": seed, "backend": resolved_backend(cell, prep, mesh)}
    with use_mesh(mesh):
        jax.block_until_ready(prep.state)
        info["prepared_s"] = time.perf_counter() - T_PROCESS
        feed, step = prep.bundle.in_shardings[1], prep.step_fn
        state, program = first_steps(cell, prep, pool)
        hlo_text = ""
        if trace:  # op metadata of the compiled step, to key the trace's ops by scope
            hlo_text = step.lower(state, jax.device_put(pool[0], feed)).compile().as_text()
        setup_s = time.perf_counter() - T_PROCESS
        if trace:
            import tracing

            profile_dir = tracing.start()
        state, done_at, step_losses, attempted = window(step, state, pool, feed, seconds, trace)
        peak = peak_bytes(chips)
        if trace:
            traced = tracing.stop(profile_dir, hlo_text)
        del state, prep, step
        gc.collect()
    failed = sum(not math.isfinite(float(x)) for x in step_losses)
    times = [b - a for a, b in zip([0.0] + done_at[:-1], done_at)]
    info.update(steps=len(times), window_s=done_at[-1], setup_s=setup_s)
    print(json.dumps({"info": info}), flush=True)

    if trace:
        traced["device_kind"] = devices[0].device_kind
        metrics, breakdown, busy = tracing.per_layer_metrics(traced, cell, len(times), cells.benchmark())
    else:
        metrics = {
            "tokens_per_s": {"value": len(times) * cell.tokens_per_step / done_at[-1], "unit": "tokens/s"},
            "step_s.p90": {"value": statistics.quantiles(times, n=10)[-1], "unit": "s"},
            "peak_hbm_gib": {"value": peak / 2**30, "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }

    t = time.perf_counter()
    ref = reference_readings(cell, seed, pool, chips)
    _log(f"reference took {time.perf_counter() - t:.1f} s")
    compared = check.compare(program, ref)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": cell.chips,
              "memory_peak_bytes": peak}
    out = {"correct": check.judge(compared, cell.limits, cell.not_compared),
           "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        device.update(busy)
        out["breakdown"] = breakdown
    out["compared"] = check.with_limits(compared, cell.limits)
    for name, entry in out["compared"].items():
        _log(f"compared {name} = {entry['value']!r} (limit {entry['limit']!r})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _log(f"no TPU found (JAX platform {devices[0].platform!r}); the benchmark runs on the chip only")
        return 2
    if len(devices) < cell.chips:
        _log(f"cell {cell.name} needs {cell.chips} chips, JAX sees {len(devices)}")
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
