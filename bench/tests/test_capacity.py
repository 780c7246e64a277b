"""The capacity cell at one simulated day on the CPU: the window's
queries keep their shapes, a sound run is correct, and the bfloat16
control and each planted fault come out not correct."""

from __future__ import annotations

import dataclasses

from bench.tests import tiny

CELL = "paper.capacity"


def test_window_queries_keep_the_warmed_shapes():
    clock = tiny.window_compiles(tiny.cell(CELL))
    assert clock.backend_compiles == 0 and clock.lower_s == 0


def test_sound_run_is_correct():
    res = tiny.run(tiny.cell(CELL))
    assert res["correct"], res["checks"]
    assert res["attempted"] == 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"capacity_query_s", "setup_s"}


def test_bfloat16_control_is_not_correct():
    res = tiny.run(tiny.cell(CELL, precision="bfloat16", days=0.25))
    assert not res["correct"]


def test_altered_answer_is_not_correct(monkeypatch):
    from repro.sim import capacity
    query = capacity.min_capacity

    class Altered:
        def __init__(self, report):
            self.report = report
            self.iterations = report.iterations

        def result(self, *args):
            r = self.report.result(*args)
            return dataclasses.replace(r, capacity=r.capacity + 1)

    monkeypatch.setattr(capacity, "min_capacity",
                        lambda *a, **k: Altered(query(*a, **k)))
    res = tiny.run(tiny.cell(CELL))
    assert not res["correct"] and res["checks"]["answers_off"]["value"] > 0


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    tiny.drop_half(monkeypatch)
    assert not tiny.run(tiny.cell(CELL))["correct"]
