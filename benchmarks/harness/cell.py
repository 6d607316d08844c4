"""``BENCHMARK.json`` and the files it names, found by name.

- a configuration: ``configs/<config>.json``, with its plain reference
  ``reference/<strategy>.py``;
- a traffic mix: ``traffic/<traffic>.json``;
- a metric: ``metrics/<name>.py``, a reader with ``read(run)`` that
  returns the value, or None when there is nothing to read.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def workload(name: str) -> tuple[dict, dict, dict]:
    """``(cell, configuration, traffic)`` of the workload ``name``."""
    cells = {w["name"]: w for w in benchmark()["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    return files(cells[name])


def files(cell: dict) -> tuple[dict, dict, dict]:
    """``(cell, configuration, traffic)`` of the cell entry ``cell``."""
    config = load_json(os.path.join(BENCH_DIR, "configs",
                                    cell["config"] + ".json"))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def reference(strategy: str):
    return importlib.import_module(f"reference.{strategy}")


def metric_specs(cell_name: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell_name`` reports: its end-to-end metrics
    with ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    b = benchmark()
    group = b["per_layer"] if trace else b["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]


def reader(name: str):
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
