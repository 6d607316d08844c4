"""``BENCHMARK.json`` names only files that exist, and every cell reports
what it must: ``setup_s``, another end-to-end metric and a per-layer
metric."""

import os
import re

import pytest

from harness import cell as cell_mod

B = cell_mod.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_are_valid_and_unique():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in B[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_cell_files_exist_and_load(w):
    cell, config, tr = cell_mod.workload(w["name"])
    assert config["name"] == w["config"]
    assert tr["loop"] in ("closed", "open")
    cell_mod.reference(config["strategy"])
    e2e = cell_mod.metric_specs(w["name"], trace=False)
    per_layer = cell_mod.metric_specs(w["name"], trace=True)
    assert "setup_s" in {m["name"] for m in e2e}
    assert len(e2e) >= 2 and per_layer
    moved = {m["name"] for m in e2e}
    assert all(m["moves"] in moved for m in per_layer)


# Readers of the served cell, held out of BENCHMARK.json while its stall
# is open (PERF.md, Open questions).
HELD_OUT = ["job_p95_s", "queue_wait_p95_s.served", "device_idle_share.served"]


@pytest.mark.parametrize(
    "name", [m["name"] for m in B["end_to_end"] + B["per_layer"]] + HELD_OUT)
def test_every_metric_has_a_reader(name):
    assert callable(cell_mod.reader(name))


def test_config_files_are_under_paths():
    for c in B["configs"]:
        assert os.path.exists(os.path.join(cell_mod.ROOT, c["file"]))
        assert any(c["file"].startswith(p + "/") for p in B["paths"])
