"""The metric arithmetic and the comparison with the reference, on
records and rows written out by hand."""

from __future__ import annotations

import math

import pytest

from bench import compare, harness, readings


def record(**over):
    r = {"setup_s": 12.5,
         "window": {"queries": 4, "rows": 252, "wall_s": 6.0},
         "counters": {"rounds_max": [100, 300], "sweep_calls": [9, 10]},
         "setup_compile": {"trace_s": 3.0, "lower_s": 1.5, "backend_s": 2.0},
         "window_compile": {}, "numbers": {},
         "trace": {"devices": [{"busy_s": 0.75, "program_s": 0.5},
                               {"busy_s": 0.25, "program_s": 0.3}],
                   "window_s": 1.0, "queries": 2}}
    r.update(over)
    return r


@pytest.mark.parametrize("name, want", [
    ("setup_s", 12.5),
    ("sweep_rows_per_s", 42.0),
    ("capacity_query_s", 1.5),
    ("sweep_calls.capacity", 9.5),
    ("trace_lower_s", 4.5),
    ("backend_compile_s", 2.0),
    ("rounds_max.sweep", 200.0),
    ("loop_device_s.sweep", 0.2),
    ("loop_device_s.capacity", 0.2),
    ("device_idle.sweep", 50.0),
    ("device_idle.capacity", 50.0),
])
def test_readers(name, want):
    assert harness.metric_reader(name)(record()) == pytest.approx(want)


@pytest.mark.parametrize("name", ["loop_device_s.sweep", "device_idle.sweep",
                                  "sweep_calls.capacity",
                                  "rounds_max.sweep"])
def test_readers_return_nothing_without_something_to_read(name):
    empty = record(trace=None, counters={"rounds_max": [],
                                         "sweep_calls": []})
    assert harness.metric_reader(name)(empty) is None


def test_rel_gap():
    assert compare.rel_gap(1.0, 1.0) == 0.0
    assert compare.rel_gap(0.0, 0.0) == 0.0
    assert compare.rel_gap(1.5, 1.0) == 0.5
    assert compare.rel_gap(float("nan"), 1.0) == math.inf


def row(**over):
    r = {"completed_jobs": 10, "avg_execution": 500.0,
         "node_hours": 1000.0, "peak_nodes": 64}
    r.update(over)
    return r


def test_compare_rows_counts_and_gaps():
    ref = row()
    pairs = [("fb", row(completed_jobs=12, avg_execution=505.0), ref),
             ("flb_nub", row(node_hours=990.0, peak_nodes=66), ref),
             ("ec2", row(avg_execution=500.0 * (1 + 1e-6)), ref)]
    out = compare.compare_rows(pairs)
    # FB completions and FLB-NUB node-hours and peak are held within a
    # gap of their own; EC2's execution mean is held exact.
    assert out["rows_off"] == 0 and out["rows_compared"] == 3
    assert out["float_gap"] == pytest.approx(1e-6)
    assert out["fb_completed_gap"] == 2
    assert out["fb_execution_gap"] == pytest.approx(0.01)
    assert out["flb_node_hours_gap"] == pytest.approx(0.01)
    assert out["flb_peak_gap"] == pytest.approx(2 / 64)
    out = compare.compare_rows([("fb", row(peak_nodes=65), ref),
                                ("dcs", None, ref)])
    assert out["rows_off"] == 2 and out["float_gap"] == compare.NO_NUMBER


def test_a_missing_row_fails_every_gap_of_its_kind():
    out = compare.compare_rows([("fb", None, row()),
                                ("flb_nub", None, row())])
    assert all(out[k] == compare.NO_NUMBER for k in compare.GAPS)
    assert not compare.verdict(out)[0]


def test_verdict_holds_each_number_to_its_limit():
    ok, checks = compare.verdict({"rows_off": 0, "float_gap": 1e-15,
                                  "fb_completed_gap": 1,
                                  "rows_compared": 5})
    assert ok and set(checks) == {"rows_off", "float_gap",
                                  "fb_completed_gap"}
    ok, _ = compare.verdict({"rows_off": 1, "float_gap": 0.0})
    assert not ok
    assert not compare.verdict({})[0]


def test_metrics_of_the_gaps_left_out_are_held_exact():
    ref = row()
    out = compare.compare_rows(
        [("fb", row(completed_jobs=11, avg_execution=500.5), ref)], gaps=())
    assert set(out) == {"rows_off", "float_gap", "rows_compared"}
    assert out["rows_off"] == 1 and out["float_gap"] == pytest.approx(1e-3)
