"""Observability benches: telemetry overhead gate + run-record invariants.

``python -m repro.bench run --suite obs`` → BENCH_obs.json. The headline
metric is the acceptance gate: a W=4 subprocess compiles the same bucketed
``ef_allgather`` train step with ``telemetry="off"`` vs ``"full"`` and
telemetry must add ≤ 2% overhead. The gate compares the two compiled
programs' trip-count-aware HLO costs (dot flops / HBM bytes via
``repro.utils.hlo`` — deterministic and run-to-run stable); a CPU clock is
no source of a time. The deterministic rest pins the run-record contract:
schema field count, in-graph wire bytes equal to the analytic model, density
inside the unit interval, and the report CLI seeing no wire mismatch.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax

from repro.bench.artifact import Metric
from repro.bench.registry import SkipBench, register_bench

BUCKET_SIZE = 1 << 12
WORLD = 4
OVERHEAD_GATE = 1.02  # telemetry-on compiled cost ≤ 2% over telemetry-off

_DRIVER = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(world)d"
import sys
sys.path.insert(0, %(src)r)
import jax, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.core import optim
from repro.core.compressors import ScaledSignCompressor
from repro.launch.mesh import make_host_mesh, ef_axis_names, use_mesh
from repro.sharding.rules import ShardingRules
from repro.train.state import init_train_state
from repro.train import steps as ST
from repro.comm import CommSpec, bucketize
from repro.obs.telemetry import modeled_wire_bytes
from repro.utils import hlo as hlo_util

BUCKET, WORLD = %(bucket)d, %(world)d
cfg = reduced(get_config("llama3_2_1b"))
mesh = make_host_mesh(data=WORLD, model=1)
rules = ShardingRules(cfg, mesh, "tp")
ef_axes = ef_axis_names(mesh, "tp")
chain = optim.sgd(0.02)
comp = ScaledSignCompressor()
key = jax.random.PRNGKey(0)
# a realistic training shape (batch 8 x seq 256): the gate is telemetry
# overhead relative to a REALISTIC step — a toy batch would shrink the
# denominator and overstate the fixed per-step telemetry reductions
batch = {"tokens": jax.random.randint(key, (8, 256), 0, cfg.vocab_size),
         "labels": jax.random.randint(jax.random.PRNGKey(1), (8, 256), 0, cfg.vocab_size)}

out = {}
with use_mesh(mesh):
    state0 = init_train_state(cfg, key, chain, "ef_allgather", mesh, ef_axes, bucket_size=BUCKET)
    layout = bucketize.build_layout(state0.params, BUCKET)
    out["modeled_wire_bytes"] = modeled_wire_bytes("ef_allgather", layout, WORLD, comp)
    for level in ("off", "full"):
        spec = CommSpec(strategy="ef_allgather", compressor=comp, bucket_size=BUCKET,
                        telemetry=level)
        bundle = ST.make_train_step(cfg, mesh, rules, spec=spec,
            local_chain=chain, ef_axes=ef_axes, batch_example=batch,
            state_example=state0)
        state = jax.device_put(state0, bundle.in_shardings[0])
        b = jax.device_put(batch, bundle.in_shardings[1])
        # no donation: the full-telemetry step runs again below on this state
        fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                     out_shardings=bundle.out_shardings)
        # trip-count-aware accounting (repro.utils.hlo): XLA's cost_analysis
        # counts the scan-over-layers body ONCE, underreporting the step ~12x
        # and inflating telemetry's relative share by the same factor
        parsed = hlo_util.analyze(fn.lower(state, b).compile().as_text())
        out["cost_" + level] = {"flops": float(parsed["dot_flops"]),
                                "bytes": float(parsed["hbm_bytes"])}
    # fn, state and b are the full-telemetry step's, built last
    _, (_, metrics) = fn(state, b)
    t = metrics["obs"]
    out["telemetry"] = {
        "wire_bytes": float(t.wire_bytes),
        "density": [float(x) for x in t.density],
        "err_l2": [float(x) for x in t.err_l2],
        "group_bytes_sum": float(jnp.sum(t.group_bytes)),
    }
print(json.dumps(out))
"""


@register_bench("obs_telemetry_overhead", suites=("obs",))
def obs_telemetry_overhead(ctx):
    """Telemetry-on vs -off bucketed EF step at W=4 (subprocess, 4 fake
    devices): the ≤2%% compiled-cost overhead gate and the in-graph-vs-model
    invariants measured on the same step."""
    if jax.default_backend() != "cpu":
        raise SkipBench("subprocess driver assumes CPU fake devices")
    repo_src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
    code = _DRIVER % {"src": repo_src, "bucket": BUCKET_SIZE, "world": WORLD}
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=1200,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if proc.returncode != 0:
        raise RuntimeError(f"obs driver failed: {proc.stderr[-2000:]}")
    out = json.loads(proc.stdout.strip().splitlines()[-1])

    cfg_d = {"world": WORLD, "bucket_size": BUCKET_SIZE, "arch": "llama3_2_1b"}
    # deterministic overhead: what telemetry ADDS to the compiled step, per
    # the trip-count-aware HLO cost model
    cost_ratio = max(
        out["cost_full"]["flops"] / max(out["cost_off"]["flops"], 1.0),
        out["cost_full"]["bytes"] / max(out["cost_off"]["bytes"], 1.0),
    )
    tele = out["telemetry"]
    modeled = out["modeled_wire_bytes"]
    return [
        Metric(
            name="obs_telemetry_cost_ratio", value=round(cost_ratio, 6),
            metric="ratio", unit="x", config=cfg_d,
            direction="lower", tolerance=0.0, abs_tolerance=0.02,
        ),
        Metric(
            # THE acceptance gate: telemetry adds ≤2% to the compiled step's
            # flops and bytes-accessed (deterministic, run-to-run stable)
            name="obs_overhead_within_2pct", value=float(cost_ratio <= OVERHEAD_GATE),
            metric="gate", unit="bool", config=dict(cfg_d, gate=OVERHEAD_GATE),
            direction="match", tolerance=0.0,
        ),
        Metric(
            # in-graph accounting equals the analytic model EXACTLY
            name="obs_wire_model_match",
            value=float(tele["wire_bytes"] == modeled == tele["group_bytes_sum"]),
            metric="invariant", unit="bool", config=dict(cfg_d, modeled=modeled),
            direction="match", tolerance=0.0,
        ),
        Metric(
            name="obs_density_in_unit",
            value=float(all(0.0 <= d <= 1.0 for d in tele["density"])),
            metric="invariant", unit="bool", config=cfg_d,
            direction="match", tolerance=0.0,
        ),
        Metric(
            name="obs_residual_finite",
            value=float(all(e == e and abs(e) != float("inf") for e in tele["err_l2"])),
            metric="invariant", unit="bool", config=cfg_d,
            direction="match", tolerance=0.0,
        ),
    ]


@register_bench("obs_record_contract", suites=("obs",))
def obs_record_contract(ctx):
    """Run-record contract, no subprocess: schema shape, writer/reader
    round-trip, and the report CLI's wire-model cross-check on a synthetic
    in-spec run."""
    import tempfile

    from repro.obs import report as obs_report
    from repro.obs import sink as obs_sink
    from repro.obs.telemetry import telemetry_schema

    fields = telemetry_schema()
    meta = obs_sink.run_meta(
        config={"strategy": "ef_allgather", "world": 4},
        telemetry="full",
        modeled_wire_bytes=1024.0,
    )
    steps = [
        obs_sink.step_record(
            i,
            {"loss": 2.0 - 0.1 * i, "wire_bytes": 1024.0, "density": 0.5},
            walls={"step": 0.01},
        )
        for i in range(5)
    ]
    final = obs_sink.final_record(steps, steps=5, wall_s=0.05)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "run.jsonl")
        with obs_sink.RunRecordWriter(path) as wr:
            for rec in [meta, *steps, final]:
                wr.write(rec)
        records = obs_sink.read_run(path)
        summary = obs_report.summarize(records)
    cfg_d = {"records": len(records)}
    return [
        Metric(
            name="obs_schema_n_fields", value=float(len(fields)),
            metric="schema", unit="fields", config={"schema": obs_sink.SCHEMA_VERSION},
            direction="match", tolerance=0.0,
        ),
        Metric(
            name="obs_roundtrip_records", value=float(len(records)),
            metric="schema", unit="records", config=cfg_d,
            direction="match", tolerance=0.0,
        ),
        Metric(
            name="obs_report_no_anomalies", value=float(not summary["anomalies"]),
            metric="invariant", unit="bool", config=cfg_d,
            direction="match", tolerance=0.0,
        ),
        Metric(
            name="obs_final_loss_present", value=float(summary["final_loss"] is not None),
            metric="invariant", unit="bool", config=cfg_d,
            direction="match", tolerance=0.0,
        ),
    ]

