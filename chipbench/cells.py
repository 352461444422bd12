"""Find a cell's files by name and build what the program and the reference run.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its files:

* ``chipbench/workloads/<cell>.json``: the comparison's limits, and the
  numbers not compared with the reason for each;
* ``chipbench/configs/<config>.json``: the model, under the published
  config's key names, with ``reduced``/``assumed``/``deployment``, and
  ``family``, the module under ``chipbench/families/`` that knows its keys,
  its reference and its counts (``decoder`` where the file names none);
* ``chipbench/traffic/<traffic>.json``: the job (strategy, backend, bucket
  size, workers, rows per worker, sequence length, optimizer, step size).

Nothing here names a cell: a new cell is new files and a new entry.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import families

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


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
    file that its family maps applied: the configuration as it is run."""
    from repro.configs import get_config

    family = families.load(config)
    fields = {f: config[k] for k, f in {**family.KEYS, **family.PROGRAM_ONLY_KEYS}.items() if k in config}
    return dataclasses.replace(get_config(config["program_config"]), **fields)


def reference_model(config: dict):
    """``(family, model)``: what ``reference.run_steps`` follows."""
    family = families.load(config)
    return family, family.model(config)
