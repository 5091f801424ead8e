"""Finding a cell's files by name, reading its per-layer metrics, and the
result line.

Everything that belongs to one configuration, traffic mix or per-layer metric
lives in a file of its own, found by the name ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json     sizes, neuron model, engine settings,
                                    simulated cell, comparison limits
    bench/traffic/<traffic>.json    {"driver": <kind>, ...parameters}
    bench/drivers/<kind>.py         one driver per traffic kind
    bench/metrics/<metric>.py       read(record) -> number or None

A later change adds a configuration, a mix, a driver or a metric by adding
files and entries; no file here names any of them.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path under a private module name."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    driver_path: str
    end_to_end: list       # metric entries this cell reports (trace 0)
    per_layer: list        # metric entries this cell reports (trace 1)
    metric_paths: dict     # per-layer metric name -> reader file


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: str = REPO_DIR) -> Cell:
    """Find every file of ``workload`` by the names ``BENCHMARK.json``
    gives.  Raises ``KeyError`` for an unknown cell and ``FileNotFoundError``
    for a name without its file."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench = os.path.join(root, "bench")
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(bench, "traffic", w["traffic"] + ".json"))
    driver = os.path.join(bench, "drivers", traffic["driver"] + ".py")
    if not os.path.exists(driver):
        raise FileNotFoundError(driver)
    e2e = [m for m in spec["end_to_end"] if _reports(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if ("workloads" in m and workload in m["workloads"])
             or ("workloads" not in m and m["moves"] in e2e_names)]
    paths = {}
    for m in layer:
        p = os.path.join(bench, "metrics", m["name"] + ".py")
        if not os.path.exists(p):
            raise FileNotFoundError(p)
        paths[m["name"]] = p
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, driver_path=driver, end_to_end=e2e,
                per_layer=layer, metric_paths=paths)


def read_per_layer(cell: Cell, record: dict) -> dict:
    """Run each per-layer metric's reader on the run's record.  A reader
    that finds nothing to read returns None, and the metric is left out.  A
    roofline's reader returns (share, bound), the bound being ``"compute"``
    or ``"memory"``; the bound goes beside the value."""
    out = {}
    for m in cell.per_layer:
        mod = load_module(cell.metric_paths[m["name"]],
                          "bench_metric_" + m["name"].replace(".", "_"))
        v = mod.read(record)
        if v is None:
            continue
        v, bound = v if isinstance(v, tuple) else (v, None)
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if bound is not None:
            out[m["name"]]["bound"] = bound
    return out


@dataclasses.dataclass
class Check:
    """One number of the correctness comparison beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: list,
                breakdown: Optional[dict] = None) -> str:
    """The last line of standard output; the checks come last."""
    out: dict = {"correct": bool(correct), "attempted": int(attempted),
                 "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return json.dumps(out)


def print_checks(checks: list, stream=sys.stderr) -> None:
    """Each compared number beside its limit, one line each."""
    for c in checks:
        print(f"check {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=stream, flush=True)
