"""The paper-grid cell at one simulated day on the CPU: the window's
queries keep their shapes, a sound run is correct, and the bfloat16
control and each planted fault come out not correct."""

from __future__ import annotations

from bench.tests import tiny

CELL = "paper.grid"


def test_window_queries_keep_the_warmed_shapes():
    clock = tiny.window_compiles(tiny.cell(CELL))
    assert clock.backend_compiles == 0 and clock.lower_s == 0


def test_sound_run_is_correct():
    res = tiny.run(tiny.cell(CELL))
    assert res["correct"], res["checks"]
    assert res["attempted"] == 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"sweep_rows_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


def test_bfloat16_control_is_not_correct():
    res = tiny.run(tiny.cell(CELL, precision="bfloat16", days=0.25))
    assert not res["correct"]


def test_altered_answers_are_not_correct(monkeypatch):
    tiny.alter_answers(monkeypatch)
    assert not tiny.run(tiny.cell(CELL))["correct"]


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    tiny.drop_half(monkeypatch)
    res = tiny.run(tiny.cell(CELL))
    assert not res["correct"] and res["checks"]["rows_off"]["value"] > 0


def test_trace_run_reports_the_per_layer_metrics_it_can_read():
    res = tiny.run(tiny.cell(CELL), trace=True)
    assert res["correct"]
    # The CPU has no device plane: the trace-read metrics stay silent.
    assert set(res["metrics"]) == {"trace_lower_s", "backend_compile_s",
                                   "rounds_max.sweep"}
    assert res["device"]["window_s"] > 0 and "breakdown" in res
