"""Finds a cell's parts by the names ``BENCHMARK.json`` gives them:

* the configuration: the file its ``configs`` entry names;
* the traffic mix: ``chipbench/traffic/<traffic>.json``, whose ``step``
  names the step kind ``chipbench/steps/<step>.py`` and whose ``metric``
  (or list ``metrics``) names the end-to-end metrics its window measures;
* the correctness check's limits and sample sizes:
  ``chipbench/checks/<cell>.json``;
* each per-layer metric: ``chipbench/metrics/<metric>.py``, a ``read(r)``
  that returns a number, or None where it finds nothing to read; a metric
  ``<stem>.<part>`` without a file of its own is read by
  ``chipbench/metrics/<stem>.py`` (one quantity split by what it moves).

A new configuration, traffic mix, cell or metric is new files and new
entries in ``BENCHMARK.json``; no file here names one.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    checks: dict              # {"limits": {...}, "sample": {...}}
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list

    @property
    def step_module(self):
        return importlib.import_module(f"chipbench.steps.{self.traffic['step']}")

    def step(self, seed: int, **over):
        """The cell's step for ``seed``; ``over`` as the step kind takes."""
        return self.step_module.Step(self.cfg, self.traffic, seed,
                                     self.checks["sample"], **over)

    @property
    def timed_metrics(self) -> list:
        """The end-to-end metrics the window measures (all but setup_s)."""
        return timed_metrics(self.traffic)


def timed_metrics(traffic: dict) -> list:
    return list(traffic["metrics"]) if "metrics" in traffic else [traffic["metric"]]


def _for_cell(entries, cell, reported=None):
    out = []
    for m in entries:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif reported is None or m["moves"] in reported:
            out.append(m)
    return out


def load(name: str, bench_path: pathlib.Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_path.name}; "
                       f"have {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg = json.loads((bench_path.parent / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    checks = json.loads((HERE / "checks" / f"{name}.json").read_text())
    e2e = _for_cell(bench["end_to_end"], name)
    names = {m["name"] for m in e2e}
    timed = timed_metrics(traffic)
    if not set(timed) | {"setup_s"} <= names:
        raise ValueError(f"{name}: BENCHMARK.json lists {sorted(names)}, the "
                         f"traffic measures {timed} and setup_s")
    return Cell(name, w["chips"], cfg, traffic, checks, e2e,
                _for_cell(bench["per_layer"], name, names))


def reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{metric.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench.metrics." + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
