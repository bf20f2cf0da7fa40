"""A cell, as ``BENCHMARK.json`` and the files it names describe it.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by name:

* ``BENCHMARK.json`` ``configs[].file``: the configuration's sizes, and the
  name of its plain reference under ``perfbench/references/``;
* ``perfbench/traffic/<traffic>.json``: the traffic's parameters;
* ``perfbench/metrics/<metric>.py``: the reader of a per-layer metric.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration's file
    traffic: dict         # the traffic's file
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list

    @property
    def model(self) -> dict:
        return self.config["model"]

    def reference(self):
        """The configuration's plain reference module."""
        return importlib.import_module(
            f"references.{self.config['reference']}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(workload: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "perfbench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(workload, w["chips"], config, traffic,
                [m for m in bench["end_to_end"] if _applies(m, workload)],
                [m for m in bench["per_layer"] if _applies(m, workload)])


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """``read(ctx)`` of ``perfbench/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
