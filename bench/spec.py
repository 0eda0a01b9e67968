"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

A cell (``workloads`` entry) names a configuration and a traffic mix.
The configuration is ``bench/configs/<config>.json``: the model's public
``config.json`` numbers under their own keys, with the keys that were
changed listed in ``reduced``; its ``model_type`` names the family
(``bench/families``) that builds, checks and counts the model. The
traffic mix is ``bench/traffic/<traffic>.json``: the arrival process, lengths and
sampling (read by ``loadgen``), the engine deployment (``engine``) and
the limits of the correctness check (``limits``).
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

from bench import families

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict                   # the configuration file
    traffic_name: str
    traffic: dict                  # the traffic file
    end_to_end: list               # metric entries that this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT,
              bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    cfile = root / confs[w["config"]]["file"]
    tfile = BENCH_DIR / "traffic" / f"{w['traffic']}.json"
    config = json.loads(cfile.read_text())
    families.of(config)       # refuses a model_type that no family serves
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"], config=config,
        traffic_name=w["traffic"], traffic=json.loads(tfile.read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file, by its
    family."""
    return families.of(conf).model_config(conf)
