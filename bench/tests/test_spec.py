"""BENCHMARK.json and the files it names: every entry resolves, every
name and unit keeps the benchmark's format, every metric has a reader."""

from __future__ import annotations

import json
import os
import re

import pytest

from bench import harness

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_to_its_files(workload):
    cell = harness.Cell(SPEC, workload)
    assert cell.config["precision"] == "float32"
    assert callable(cell.generator.make) and cell.generator.PROGRAMS
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    # Every per-layer metric the cell reports moves an end-to-end metric
    # the cell reports too.
    assert {m["moves"] for m in cell.per_layer} <= e2e


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_every_metric_has_a_reader_and_keeps_the_format(metric):
    m = {x["name"]: x for x in METRICS}[metric]
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert callable(harness.metric_reader(metric))
    if "bound" in m:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    else:
        assert m["moves"] in {x["name"] for x in SPEC["end_to_end"]}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_names_are_unique_and_files_stay_under_paths():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in SPEC[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    assert all(f.startswith("bench/") and os.path.exists(
        os.path.join(harness.ROOT, f)) for f in files)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}


def test_every_traffic_file_names_a_generator():
    folder = os.path.join(harness.BENCH, "traffic")
    for f in sorted(os.listdir(folder)):
        if f.endswith(".json"):
            with open(os.path.join(folder, f)) as fh:
                traffic = json.load(fh)
            assert os.path.exists(os.path.join(
                harness.BENCH, "generators", traffic["generator"] + ".py"))
