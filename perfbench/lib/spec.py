"""What one cell is, read from BENCHMARK.json and the files it names.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by its name:

    configs/<config>.json      sizes, optimizer, reference, departures
    traffic/<traffic>.json     plan, batch, sequence length, tokens
    limits/<workload>.json     the limit of each number ``correct`` compares
    metrics/<metric>.py        ``read(ctx)``: the metric, or None
    reference/<name>.py        the plain reference a configuration names
"""

from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _limits(workload: str) -> dict:
    path = HERE / "limits" / f"{workload}.json"
    return json.loads(path.read_text()) if path.exists() else {}


def load(workload: str, bench: dict | None = None) -> Cell:
    bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload) and m["moves"] in e2e_names]
    return Cell(
        name=workload, chips=w["chips"],
        config=json.loads((ROOT / conf["file"]).read_text()),
        traffic=json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=_limits(workload),
        end_to_end=e2e, per_layer=per_layer)


def reader(metric: str):
    """The ``read`` function of metrics/<metric>.py."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference(name: str):
    return importlib.import_module(f"perfbench.reference.{name}")
