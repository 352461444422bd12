"""BENCHMARK.json and the files it names: every cell resolves its config,
traffic, workload and metric files, names and units keep to the allowed
characters, and a new cell, configuration or metric needs only new files
and new entries."""

import json
import re
import shutil
import subprocess
import sys

import pytest

import cells
import counts
import families
import tiny
import tracing

BENCH = cells.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FAMILY_API = ("KEYS", "PROGRAM_ONLY_KEYS", "model", "init_params", "row_loss",
              "forward_flops_per_token", "param_count")
WIDTH = re.compile(r"hidden_size|intermediate_size|latent|state_size|proj|head_size|_dim$|_rank$|experts_per_tok|expan")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for e in BENCH[k]]
    names += [w[k] for w in BENCH["workloads"] for k in ("config", "traffic")]
    names += [r for c in BENCH["configs"] for r in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        assert len({e["name"] for e in BENCH[k]}) == len(BENCH[k])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") for m in metrics)


def test_configs():
    for c in BENCH["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        data = json.loads((cells.CHECKOUT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        assert not any(WIDTH.search(k) for k in c["reduced"]), c["reduced"]
        assert c["source"].startswith("https://")
        family = families.load(data)
        assert all(hasattr(family, k) for k in FAMILY_API), family.__name__


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    c = cells.load(cell)
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert c.chips == entry["chips"] in (1, 4)
    assert c.tokens_per_step > 0 and len(entry["why"]) <= 200
    numbers = {"loss", "grad", "update", "grad_diff", "grad_sign", "update_sign"}
    assert set(c.limits) | set(c.not_compared) == numbers
    assert not set(c.limits) & set(c.not_compared)
    assert all(isinstance(v, float) and v > 0 for v in c.limits.values())
    assert all(isinstance(v, str) and v for v in c.not_compared.values())
    reported = [m for m in BENCH["per_layer"] if cell in m.get("workloads", [cell])]
    assert reported


def test_pairs_once_and_four_chip_share():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    fours = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert fours <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_exists(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert callable(tracing.load_reader(metric).read)
    assert entry["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    cell_names = {w["name"] for w in BENCH["workloads"]}
    assert set(entry.get("workloads", cell_names)) <= cell_names


def test_a_new_cell_config_and_metric_are_files_only(tmp_path):
    """Copy the benchmark, add a configuration, a traffic mix, a cell and a
    metric as new files plus entries, and load them with the harness as it
    stands."""
    root = tmp_path / "checkout"
    shutil.copytree(cells.HERE, root / "chipbench", ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((cells.CHECKOUT / "BENCHMARK.json").read_text())
    src = root / "chipbench"
    cfg = json.loads((src / "configs" / "granite_moe_1b_a400m-6l.json").read_text())
    cfg.update(name="granite_moe-12l", num_hidden_layers=12)
    (src / "configs" / "granite_moe-12l.json").write_text(json.dumps(cfg))
    (src / "traffic" / "ef.w1.b2s2048.json").write_text(json.dumps(
        {"strategy": "ef_allgather", "backend": "auto", "bucket_size": 65536, "workers": 1,
         "rows_per_worker": 2, "seq": 2048, "optimizer": "sgdm", "lr": 0.01}))
    (src / "workloads" / "granite_moe12.ef.w1.json").write_text(
        json.dumps({"limits": {"loss": 1, "grad": 1, "update": 1, "grad_diff": 1, "grad_sign": 1,
                               "update_sign": 1}}))
    (src / "metrics" / "steps_traced.py").write_text("def read(trace, cell, steps):\n    return float(steps)\n")
    bench["configs"].append({"name": "granite_moe-12l", "source": cfg["source"],
                             "file": "chipbench/configs/granite_moe-12l.json",
                             "reduced": ["num_hidden_layers"], "why": "deeper"})
    bench["workloads"].append({"name": "granite_moe12.ef.w1", "config": "granite_moe-12l",
                               "traffic": "ef.w1.b2s2048", "chips": 1, "why": "longer rows"})
    bench["per_layer"].append({"name": "steps_traced", "unit": "steps", "better": "higher",
                               "source": "device_trace", "layer": "device (v5e)", "moves": "tokens_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    probe = (
        "import sys; sys.path.insert(0, 'chipbench'); import cells, tracing\n"
        "c = cells.load('granite_moe12.ef.w1')\n"
        "print(c.config['num_hidden_layers'], c.tokens_per_step, tracing.load_reader('steps_traced').read({}, c, 7))\n"
    )
    out = subprocess.run([sys.executable, "-c", probe], cwd=root, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["12", "4096", "7.0"]


RELABEL = """\"\"\"The decoder under other published key names.\"\"\"
from families import decoder
from families.decoder import PROGRAM_ONLY_KEYS, init_params, row_loss  # noqa: F401

NAMES = {"n_embd": "hidden_size", "n_layer": "num_hidden_layers", "n_head": "num_attention_heads"}
OURS = {old: new for new, old in NAMES.items()}
KEYS = {OURS.get(k, k): f for k, f in decoder.KEYS.items()}


def _as_decoder(config):
    return {NAMES.get(k, k): v for k, v in config.items()}


def model(config):
    return decoder.model(_as_decoder(config))


def forward_flops_per_token(config, seq):
    return decoder.forward_flops_per_token(_as_decoder(config), seq)


def param_count(config):
    return decoder.param_count(_as_decoder(config))
"""

FAMILY_PROBE = """
import sys
sys.path[:0] = ["chipbench", "chipbench/tests", {src!r}]
import jax, numpy as np
import cells, counts, run, tiny
c = cells.load("tiny_gpt.ef.w1")
base = tiny.cell()
family, model = cells.reference_model(c.config)
print(family.__name__, model == cells.reference_model(base.config)[1])
print(cells.program_config(c.config) == cells.program_config(base.config))
print(counts.train_flops_per_step(c.config, c.tokens_per_step, c.seq)
      == counts.train_flops_per_step(base.config, base.tokens_per_step, base.seq),
      counts.param_count(c.config) == counts.param_count(base.config),
      counts.n_buckets(c.config, 1024))
pool = run.tokens.batches(7, 3, c.global_rows, c.seq, c.config["vocab_size"])
got, want = (run.reference_readings(x, 7, pool, jax.devices()) for x in (c, base))
print(got.losses == want.losses, np.array_equal(got.grad_norms, want.grad_norms),
      np.array_equal(got.change_norms, want.change_norms))
"""


def test_a_new_family_is_files_only(tmp_path):
    """A configuration names a family the harness has never seen, a relabelling
    of ``decoder`` under other published key names: loading the cell, the
    program's config, the reference's model, the counts and the reference's
    three steps resolve through it with no edit to an existing file, and
    read as the decoder under its own names does."""
    root = tmp_path / "checkout"
    shutil.copytree(cells.HERE, root / "chipbench", ignore=shutil.ignore_patterns("__pycache__"))
    src = root / "chipbench"
    (src / "families" / "gpt_relabel.py").write_text(RELABEL)
    names = {"hidden_size": "n_embd", "num_hidden_layers": "n_layer", "num_attention_heads": "n_head"}
    cfg = {names.get(k, k): v for k, v in tiny.config(moe=True).items()}
    cfg.update(name="tiny_gpt", family="gpt_relabel", source="https://example.org/tiny")
    (src / "configs" / "tiny_gpt.json").write_text(json.dumps(cfg))
    (src / "traffic" / "ef.w1.tiny.json").write_text(json.dumps(tiny.cell().traffic))
    (src / "workloads" / "tiny_gpt.ef.w1.json").write_text(json.dumps({"limits": tiny.LIMITS[True]}))
    bench = json.loads((cells.CHECKOUT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_gpt", "source": cfg["source"], "file": "chipbench/configs/tiny_gpt.json",
                             "reduced": [], "why": "a family under other key names"})
    bench["workloads"].append({"name": "tiny_gpt.ef.w1", "config": "tiny_gpt", "traffic": "ef.w1.tiny",
                               "chips": 1, "why": "small"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    probe = FAMILY_PROBE.format(src=str(cells.CHECKOUT / "src"))
    out = subprocess.run([sys.executable, "-c", probe], cwd=root, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split() == ["families.gpt_relabel", "True", "True", "True", "True",
                                  str(counts.n_buckets(tiny.config(moe=True), 1024)), "True", "True", "True"]
