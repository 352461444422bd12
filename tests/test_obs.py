"""Observability layer (repro.obs): the Telemetry pytree and its wire models,
trace spans surviving into compiled HLO, the schema-versioned JSONL sink and
report CLI, loop integration through TrainJob, and (slow) subprocess proofs
that ``telemetry="full"`` leaves the training trajectory bitwise identical to
``"off"`` at W ∈ {2, 4} across strategies and collective backends.
"""

import json
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.comm import CommSpec, bucketize, make_aggregator
from repro.comm import collective as comm_collective
from repro.comm.errors import PathConfigError
from repro.core import aggregation
from repro.core import compressors as C
from repro.launch.mesh import make_host_mesh, use_mesh
from repro.obs import report as obs_report
from repro.obs import sink as obs_sink
from repro.obs import telemetry as obs_telemetry
from repro.obs import trace as obs_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree():
    key = jax.random.PRNGKey(7)
    return {
        "w": jax.random.normal(key, (5, 130)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (40,)),
    }


# ---------------------------------------------------------------------------
# telemetry schema + wire models
# ---------------------------------------------------------------------------


def test_telemetry_schema_matches_pytree():
    fields = obs_telemetry.telemetry_schema()
    assert tuple(f["name"] for f in fields) == obs_telemetry.Telemetry._fields
    for f in fields:
        assert set(f) == {"name", "shape", "unit", "doc"}


def test_replicated_specs_is_all_replicated():
    specs = obs_telemetry.replicated_specs()
    assert isinstance(specs, obs_telemetry.Telemetry)
    assert all(s == P() for s in specs)


def test_residual_l2_matches_numpy_norm():
    x = np.linspace(-3.0, 5.0, 64, dtype=np.float32).reshape(4, 16)
    got = float(obs_telemetry.residual_l2(jnp.asarray(x)))
    np.testing.assert_allclose(got, np.linalg.norm(x), rtol=1e-6)
    assert float(obs_telemetry.residual_l2(jnp.zeros((3, 8), jnp.bfloat16))) == 0.0


def test_modeled_wire_bytes_matches_closed_forms():
    layout = bucketize.build_layout(_tree(), 128)
    nb, bs = layout.n_buckets, layout.bucket_size
    comp = C.ScaledSignCompressor()
    for world in (1, 2, 4, 16):
        ag = obs_telemetry.modeled_wire_bytes("ef_allgather", layout, world, comp)
        assert ag == aggregation.bucketed_sign_allgather_wire_bytes(nb, bs, world)
        assert obs_telemetry.modeled_wire_bytes("ef_ring", layout, world, comp) == ag
        for robust in ("ef_coord_median", "ef_trimmed_mean", "ef_norm_filter"):
            # the robust strategies decode the same stack: identical wire bill
            assert obs_telemetry.modeled_wire_bytes(robust, layout, world, comp) == ag
        mv = obs_telemetry.modeled_wire_bytes("majority_vote", layout, world, comp)
        assert mv == (world - 1) * nb * bs / 8.0
    assert obs_telemetry.modeled_wire_bytes("dense", layout, 4, comp) == 8.0 * nb * bs


def test_modeled_alltoall_sums_per_group_ceils():
    # two dtype groups: the server shard is ceil-divided per group, so the
    # model must be the SUM of per-group ceils, not the ceil of the sum
    tree = {"a": jnp.zeros((130,), jnp.float32), "b": jnp.zeros((40,), jnp.bfloat16)}
    layout = bucketize.build_layout(tree, 32)
    assert len(layout.groups) == 2
    comp = C.ScaledSignCompressor()
    world = 4
    from repro.comm import compressed

    expect = sum(
        2 * (world - 1) * compressed.server_shard_buckets(g.n_buckets, world) * comp.wire_bits(32)
        for g in layout.groups
    ) / 8.0
    assert obs_telemetry.modeled_wire_bytes("ef_alltoall", layout, world, comp) == expect


def test_strategy_wire_models_covers_every_strategy():
    layout = bucketize.build_layout(_tree(), 128)
    models = obs_telemetry.strategy_wire_models(layout, 4)
    assert set(models) == set(comm_collective.STRATEGIES)
    assert all(v >= 0.0 for v in models.values())
    with pytest.raises(ValueError, match="unknown bucketed strategy"):
        obs_telemetry.modeled_wire_bytes("nope", layout, 4)


# ---------------------------------------------------------------------------
# CommSpec validation
# ---------------------------------------------------------------------------


def test_commspec_rejects_unknown_telemetry_level():
    with pytest.raises(PathConfigError, match="unknown telemetry level"):
        CommSpec(strategy="ef_allgather", telemetry="verbose").validate()


def test_commspec_rejects_telemetry_off_graph_paths():
    # dense never reaches the bucketed aggregator (own GSPMD path) and the
    # per-leaf fallback has no bucketed intermediates to read
    with pytest.raises(PathConfigError, match="telemetry"):
        CommSpec(strategy="dense", telemetry="full").validate()
    with pytest.raises(PathConfigError, match="telemetry"):
        CommSpec(strategy="ef_allgather", bucket_size=None, telemetry="full").validate()


def test_commspec_accepts_bucketed_telemetry():
    for level in obs_telemetry.TELEMETRY_CHOICES:
        CommSpec(strategy="ef_allgather", telemetry=level).validate()


# ---------------------------------------------------------------------------
# aggregator telemetry (W=1 fast path; multi-worker in the slow tests below)
# ---------------------------------------------------------------------------


def _run_w1_aggregator(telemetry):
    mesh = make_host_mesh(data=1, model=1)
    tree = _tree()
    layout = bucketize.build_layout(tree, 128)
    buckets = bucketize.flatten_buckets(layout, tree)
    buckets_w = tuple(b[None] for b in buckets)
    err = tuple(jnp.zeros_like(b) for b in buckets_w)
    with use_mesh(mesh):
        spec = CommSpec(
            strategy="ef_allgather", compressor=C.ScaledSignCompressor(),
            bucket_size=128, telemetry=telemetry,
        )
        agg = make_aggregator(spec, layout, mesh, ("data",))
        jagg = jax.jit(agg)
        out = jagg(buckets_w, err, (), jax.random.PRNGKey(0))
        hlo = jagg.lower(buckets_w, err, (), jax.random.PRNGKey(0)).compile().as_text()
    return layout, out, hlo


def test_aggregator_telemetry_off_is_none():
    _, (_, _, _, info), _ = _run_w1_aggregator("off")
    assert info.telemetry is None


def test_aggregator_telemetry_full_invariants():
    layout, (_, _, _, info), _ = _run_w1_aggregator("full")
    t = info.telemetry
    assert isinstance(t, obs_telemetry.Telemetry)
    n_groups = len(layout.groups)
    assert t.err_l2.shape == (n_groups,)
    assert t.density.shape == (n_groups,)
    dens = np.asarray(t.density)
    assert np.all((dens >= 0.0) & (dens <= 1.0))
    errs = np.asarray(t.err_l2)
    assert np.all(np.isfinite(errs)) and np.all(errs >= 0.0)
    # W=1: nothing crosses the wire, and the split must still sum exactly
    assert float(t.wire_bytes) == obs_telemetry.modeled_wire_bytes("ef_allgather", layout, 1)
    assert float(np.asarray(t.group_bytes).sum()) == float(t.wire_bytes)
    np.testing.assert_array_equal(np.asarray(t.filtered_lanes), np.zeros((1,), np.float32))


def test_spans_survive_into_compiled_hlo():
    # named_scope is metadata-only: it must show up in the COMPILED program's
    # op_name metadata, which the device-trace reduction keys on
    _, _, hlo = _run_w1_aggregator("off")
    assert obs_trace.SPAN_COMPRESS in hlo
    assert obs_trace.SPAN_DECODE in hlo


def test_span_helpers():
    assert all(n.startswith("obs.") for n in obs_trace.SPAN_NAMES)
    with obs_trace.span("compress"):  # prefixes "obs." when missing
        pass
    with obs_trace.span(obs_trace.SPAN_DECODE):
        pass
    with obs_trace.host_span("host-side"):
        pass
    with obs_trace.step_span(3):
        pass


def test_wall_timers_accumulate_and_drain():
    timers = obs_trace.WallTimers()
    with timers.region("step"):
        pass
    with timers.region("step"):
        pass
    walls = timers.drain()
    assert set(walls) == {"step"} and walls["step"] >= 0.0
    assert timers.drain() == {}


# ---------------------------------------------------------------------------
# JSONL sink
# ---------------------------------------------------------------------------


def test_sink_roundtrip():
    meta = obs_sink.run_meta(
        config={"strategy": "ef_allgather", "world": 2},
        telemetry="full",
        modeled_wire_bytes=64.0,
        wire_models={"ef_allgather": 64.0},
    )
    assert meta["telemetry_fields"] == list(obs_telemetry.telemetry_schema())
    step = obs_sink.step_record(
        0,
        {
            "loss": jnp.float32(2.5),
            "wire_bytes": 64.0,
            "density": 0.5,
            "obs": obs_telemetry.Telemetry(
                err_l2=jnp.ones((2,)),
                density=jnp.full((2,), 0.5),
                wire_bytes=jnp.float32(64.0),
                group_bytes=jnp.array([48.0, 16.0]),
                filtered_lanes=jnp.zeros((2,)),
            ),
        },
        walls={"step": 0.25},
    )
    assert step["loss"] == 2.5 and step["wall_step_s"] == 0.25
    assert step["err_l2"] == [1.0, 1.0]
    assert step["group_bytes"] == [48.0, 16.0]
    assert step["telemetry_wire_bytes"] == 64.0
    final = obs_sink.final_record([step], steps=1, wall_s=0.3)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "run.jsonl")
        with obs_sink.RunRecordWriter(path) as wr:
            for rec in (meta, step, final):
                wr.write(rec)
        back = obs_sink.read_run(path)
    assert [r["kind"] for r in back] == ["run_meta", "step", "final"]
    assert back[1] == json.loads(json.dumps(step))


def test_sink_run_meta_off_has_no_field_table():
    assert "telemetry_fields" not in obs_sink.run_meta(config={}, telemetry="off")


def test_final_record_zero_step_run():
    # the launch/train.py epilogue regression: no history must NOT raise
    final = obs_sink.final_record([], steps=0)
    assert final["final_loss"] is None
    assert "last_logged_step" not in final


def test_sink_rejects_unknown_schema():
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "run.jsonl")
        with open(path, "w") as fh:
            fh.write(json.dumps({"schema": 999, "kind": "step"}) + "\n")
        with pytest.raises(ValueError, match="schema 999"):
            obs_sink.read_run(path)


def test_sink_writer_closed_raises():
    with tempfile.TemporaryDirectory() as d:
        wr = obs_sink.RunRecordWriter(os.path.join(d, "run.jsonl"))
        wr.close()
        with pytest.raises(ValueError, match="closed"):
            wr.write({"schema": 1})


# ---------------------------------------------------------------------------
# report CLI
# ---------------------------------------------------------------------------


def _synthetic_records(wire=64.0, modeled=64.0, density=0.5, err=0.01, lanes=None):
    meta = obs_sink.run_meta(
        config={"strategy": "ef_allgather"}, telemetry="full", modeled_wire_bytes=modeled
    )
    steps = []
    for i in range(6):
        rec = {
            "schema": 1, "kind": "step", "step": i, "loss": 2.0 - 0.1 * i,
            "wire_bytes": wire, "density": density, "err_l2": [err],
        }
        if lanes is not None:
            rec["filtered_lanes"] = lanes
        steps.append(rec)
    final = obs_sink.final_record(steps, steps=6)
    return [meta, *steps, final]


def test_report_clean_run():
    summary = obs_report.summarize(_synthetic_records())
    assert summary["anomalies"] == []
    assert summary["final_loss"] == pytest.approx(1.5)
    text = obs_report.format_summary(summary)
    assert "match" in text and "anomalies: none" in text


def test_report_flags_wire_model_mismatch():
    summary = obs_report.summarize(_synthetic_records(wire=60.0, modeled=64.0))
    assert "wire_model_mismatch" in summary["anomalies"]
    assert "MISMATCH" in obs_report.format_summary(summary)


def test_report_flags_density_out_of_unit():
    summary = obs_report.summarize(_synthetic_records(density=1.5))
    assert "density_out_of_unit" in summary["anomalies"]


def test_report_flags_residual_blowup():
    records = _synthetic_records()
    for i, rec in enumerate(r for r in records if r["kind"] == "step"):
        rec["err_l2"] = [0.01 * (100.0 if i >= 3 else 1.0)]
    summary = obs_report.summarize(records)
    assert "residual_blowup" in summary["anomalies"]


def test_report_flags_suspect_lanes():
    summary = obs_report.summarize(_synthetic_records(lanes=[0.0, 3.0, 0.0, 0.5]))
    assert summary["suspect_lanes"] == [1]
    assert "suspect_lanes" in summary["anomalies"]


def test_report_cli_json(capsys):
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "run.jsonl")
        with obs_sink.RunRecordWriter(path) as wr:
            for rec in _synthetic_records():
                wr.write(rec)
        assert obs_report.main([path, "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["n_step_records"] == 6 and summary["anomalies"] == []


# ---------------------------------------------------------------------------
# loop integration (W=1; the real launcher path runs in CI's obs smoke step)
# ---------------------------------------------------------------------------


def test_training_loop_writes_schema_valid_records():
    from repro.configs import get_config, reduced
    from repro.train.loop import TrainJob, run_training

    cfg = reduced(get_config("llama3_2_1b"))
    mesh = make_host_mesh(data=1, model=1)
    with tempfile.TemporaryDirectory() as d:
        job = TrainJob(
            cfg=cfg, mesh=mesh, steps=3, batch=2, seq=32, lr=0.02,
            optimizer="sgd", strategy="ef_allgather", log_every=2,
            telemetry="full", log_dir=d,
        )
        _, hist = run_training(job)
        records = obs_sink.read_run(os.path.join(d, "run.jsonl"))
    kinds = [r["kind"] for r in records]
    assert kinds[0] == "run_meta" and kinds[-1] == "final"
    assert kinds.count("step") == len(hist) == 2
    meta = records[0]
    assert meta["telemetry"] == "full" and "modeled_wire_bytes" in meta
    for rec in records[1:-1]:
        assert rec["telemetry_wire_bytes"] == meta["modeled_wire_bytes"]
        assert rec["wire_bytes"] == meta["modeled_wire_bytes"]
        assert len(rec["err_l2"]) == len(rec["group_density"]) >= 1
        assert rec["wall_step_s"] > 0.0
    summary = obs_report.summarize(records)
    assert "wire_model_mismatch" not in summary["anomalies"]
    assert records[-1]["final_loss"] == pytest.approx(hist[-1]["loss"])


# ---------------------------------------------------------------------------
# multi-worker bitwise invariance (subprocess, fake devices)
# ---------------------------------------------------------------------------

_BITWISE_DRIVER = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(world)d"
import sys
sys.path.insert(0, os.path.join(%(repo)r, "src"))
import jax, numpy as np
from repro.configs import get_config, reduced
from repro.core import optim
from repro.core.compressors import ScaledSignCompressor
from repro.launch.mesh import make_host_mesh, ef_axis_names, use_mesh
from repro.sharding.rules import ShardingRules
from repro.train.state import init_train_state
from repro.train import steps as ST
from repro.comm import CommSpec, bucketize
from repro.obs.telemetry import modeled_wire_bytes

W, STRATEGY, BACKEND = %(world)d, %(strategy)r, %(backend)r
cfg = reduced(get_config("llama3_2_1b"))
mesh = make_host_mesh(data=W, model=1)
key = jax.random.PRNGKey(0)
rules = ShardingRules(cfg, mesh, "tp")
ef_axes = ef_axis_names(mesh, "tp")
chain = optim.sgd(0.02)
comp = ScaledSignCompressor()
batch = {"tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab_size),
         "labels": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)}

with use_mesh(mesh):
    layout = bucketize.build_layout(
        init_train_state(cfg, key, chain, STRATEGY, mesh, ef_axes, bucket_size=4096).params, 4096
    )

def run(level):
    with use_mesh(mesh):
        # fresh (identical) state per run: bundle.jit() donates its input
        state = init_train_state(cfg, key, chain, STRATEGY, mesh, ef_axes, bucket_size=4096)
        spec = CommSpec(strategy=STRATEGY, compressor=comp, bucket_size=4096,
                        backend=BACKEND, telemetry=level)
        bundle = ST.make_train_step(cfg, mesh, rules, spec=spec, local_chain=chain,
                                    ef_axes=ef_axes, batch_example=batch, state_example=state)
        state = jax.device_put(state, bundle.in_shardings[0])
        b = jax.device_put(batch, bundle.in_shardings[1])
        fn = bundle.jit()
        traj = []
        for _ in range(5):
            state, (loss, m) = fn(state, b)
            traj.append(float(loss))
        tele = None
        if "obs" in m:
            t = m["obs"]
            tele = {"wire": float(t.wire_bytes),
                    "density": [float(x) for x in np.asarray(t.density)],
                    "err_l2": [float(x) for x in np.asarray(t.err_l2)],
                    "group_sum": float(np.asarray(t.group_bytes).sum()),
                    "lanes": [float(x) for x in np.asarray(t.filtered_lanes)]}
        return traj, jax.device_get(jax.tree.leaves(state.params)), float(m["wire_bytes"]), tele

t_off, p_off, w_off, none_tele = run("off")
t_full, p_full, w_full, tele = run("full")
bitwise = (t_off == t_full) and all(np.array_equal(a, b) for a, b in zip(p_off, p_full))
print(json.dumps({"bitwise": bool(bitwise), "traj": t_off,
                  "wire_off": w_off, "wire_full": w_full,
                  "modeled": modeled_wire_bytes(STRATEGY, layout, W, comp),
                  "off_has_tele": none_tele is not None, "tele": tele}))
"""


@pytest.mark.slow
@pytest.mark.parametrize("world", [2, 4])
@pytest.mark.parametrize(
    "strategy,backend",
    [("ef_allgather", "auto"), ("ef_ring", "auto"), ("ef_allgather", "pallas_dma")],
)
def test_telemetry_full_vs_off_bitwise(world, strategy, backend):
    code = _BITWISE_DRIVER % {
        "repo": REPO, "world": world, "strategy": strategy, "backend": backend
    }
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=1200,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    # telemetry is a pure read: the 5-step trajectory and final params are
    # bitwise identical with it on or off
    assert out["bitwise"], f"telemetry changed the trajectory: {out['traj']}"
    assert not out["off_has_tele"]
    # the billed wire equals the analytic model EXACTLY, both levels
    assert out["wire_off"] == out["wire_full"] == out["modeled"]
    tele = out["tele"]
    assert tele is not None
    assert tele["wire"] == out["modeled"]
    assert tele["group_sum"] == tele["wire"]
    assert all(0.0 <= d <= 1.0 for d in tele["density"])
    assert all(np.isfinite(e) and e >= 0.0 for e in tele["err_l2"])
    assert len(tele["lanes"]) == world and all(x == 0.0 for x in tele["lanes"])
