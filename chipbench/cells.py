"""Find a cell's files by name and build what the program and the reference run.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its files:

* ``chipbench/workloads/<cell>.json``: the comparison's limits, and the
  numbers not compared with the reason for each;
* ``chipbench/configs/<config>.json``: the model, under the published
  config's key names, with ``reduced``/``assumed``/``deployment``;
* ``chipbench/traffic/<traffic>.json``: the job (strategy, backend, bucket
  size, workers, rows per worker, sequence length, optimizer, step size).

Nothing here names a cell: a new cell is new files and a new entry.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent

# published config key -> the program's ModelConfig field (and the reference's)
MODEL_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "num_layers",
    "num_local_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "param_dtype": "param_dtype",
    "moe_capacity_factor": "capacity_factor",
    "moe_aux_loss_coef": "aux_loss_coef",
    "moe_z_loss_coef": "router_z_coef",
}
PROGRAM_ONLY_KEYS = {"compute_dtype": "compute_dtype"}


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    not_compared: dict = dataclasses.field(default_factory=dict)

    @property
    def workers(self) -> int:
        return self.traffic["workers"]

    @property
    def global_rows(self) -> int:
        return self.traffic["rows_per_worker"] * self.workers

    @property
    def seq(self) -> int:
        return self.traffic["seq"]

    @property
    def tokens_per_step(self) -> int:
        return self.global_rows * self.seq

    @property
    def ef(self) -> bool:
        return self.traffic["strategy"] != "dense"


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _read(CHECKOUT / "BENCHMARK.json")


def load(name: str, bench: dict | None = None) -> Cell:
    bench = bench or benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: {sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read(CHECKOUT / configs[entry["config"]]["file"])
    traffic = _read(HERE / "traffic" / f"{entry['traffic']}.json")
    workload = _read(HERE / "workloads" / f"{name}.json")
    if traffic["workers"] != entry["chips"]:
        raise ValueError(f"{name}: traffic {entry['traffic']} has {traffic['workers']} workers, cell has {entry['chips']} chips")
    return Cell(name, entry["chips"], config, traffic, workload.get("limits", {}),
                workload.get("not_compared", {}))


def program_config(config: dict):
    """The program's ModelConfig of the named model with every key of the
    file applied by ``dataclasses.replace``: the configuration as it is run."""
    from repro.configs import get_config

    fields = {f: config[k] for k, f in {**MODEL_KEYS, **PROGRAM_ONLY_KEYS}.items() if k in config}
    return dataclasses.replace(get_config(config["program_config"]), **fields)


def reference_model(config: dict):
    from reference import Model

    return Model(**{f: config[k] for k, f in MODEL_KEYS.items() if k in config})
