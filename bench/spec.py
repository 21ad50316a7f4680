"""Find a cell's configuration, traffic mix and metrics by name.

Everything that belongs to one configuration, one traffic mix or one
metric sits in a file of its own, found from the names in
``BENCHMARK.json``:

  * the configuration's ``file`` (``bench/configs/<config>.json``);
  * the traffic mix ``bench/traffic/<traffic>.json``;
  * each metric's reader ``bench/metrics/<metric>.py``, a module with
    ``read(ctx) -> float | None``.

A metric belongs to a cell when its ``workloads`` list names the cell,
or, without that list, when the cell reports the end-to-end metric it
``moves`` (end-to-end metrics without the list belong to every cell).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
METRICS_DIR = os.path.join(BENCH_DIR, "metrics")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def cell(bench: Dict, name: str, root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} (known: {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(m):
        return name in m["workloads"] if "workloads" in m else None

    e2e = [m for m in bench["end_to_end"] if mine(m) is not False]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if mine(m) or (mine(m) is None and m["moves"] in e2e_names)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load_json(os.path.join(root, cfg["file"])),
        traffic=_load_json(os.path.join(BENCH_DIR, "traffic",
                                        w["traffic"] + ".json")),
        end_to_end=e2e, per_layer=per_layer)


def reader(metric: str) -> Callable:
    """The ``read`` function of ``<METRICS_DIR>/<metric>.py``."""
    path = os.path.join(METRICS_DIR, metric + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"metric {metric!r} has no reader at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
