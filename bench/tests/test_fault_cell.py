"""The fault cell at one simulated day on the CPU: the window's queries
keep the warmed shapes, a sound run is correct, and the bfloat16
control and each planted fault in the reference come out not correct."""

from __future__ import annotations

import pytest

from bench import compare_faults
from bench.tests import tiny

CELL = "paper.faults"


def test_window_queries_keep_the_warmed_shapes():
    """Fresh schedules every query, one compiled program: the fault
    tables and the round budget take the traffic's ``fault_stops``."""
    clock = tiny.window_compiles(tiny.cell(CELL))
    assert clock.backend_compiles == 0 and clock.lower_s == 0


def test_sound_run_is_correct():
    res = tiny.run(tiny.cell(CELL))
    assert res["correct"], res["checks"]
    assert res["attempted"] == 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"sweep_rows_per_s", "setup_s"}
    assert res["numbers"]["rows_compared"] == 60
    assert set(res["checks"]) == {"rows_off"}
    assert all(res["numbers"][k] <= limit
               for k, limit in compare_faults.LIMITS.items())


def test_a_query_is_120_fb_rows_under_fresh_schedules():
    cell = tiny.cell(CELL)
    with tiny.precision(cell):
        sut = cell.generator.make(cell.config, cell.traffic, 2**33 + 1)
        q = sut.query(0)
        answer = sut.run(q)
    rows = answer["rows"]
    assert len(rows) == 24 and all(len(r) == 5 for r in rows)
    assert 128 <= q["capacity"] <= 256
    s = sut.summary(q, answer)
    assert s["rows"] == 120 and not s["failed"] and len(s["rounds_max"]) == 3
    # Every schedule is its own draw, and the 64x ones hold the most.
    lens = [len(f) for f in answer["faults"]]
    assert len(set(lens)) > 12
    assert min(lens[6::8] + lens[7::8]) > max(lens[0::8] + lens[1::8])
    assert sum(r["fault_rounds"] for rs in rows for r in rs) > 0


def test_bfloat16_control_is_not_correct():
    res = tiny.run(tiny.cell(CELL, precision="bfloat16", days=0.25))
    assert not res["correct"]


def test_a_reference_whose_failures_take_no_nodes_is_not_correct(
        monkeypatch):
    from bench import reference_faults
    monkeypatch.setattr(reference_faults.FaultFB, "on_fail",
                        lambda self, t, k: None)
    monkeypatch.setattr(reference_faults.FaultFB, "on_repair",
                        lambda self, t, k: None)
    res = tiny.run(tiny.cell(CELL))
    assert not res["correct"] and res["checks"]["rows_off"]["value"] > 0
    assert res["numbers"]["fault_node_hours_gap"] > \
        compare_faults.LIMITS["fault_node_hours_gap"]


def test_a_reference_killing_the_largest_first_is_not_correct(monkeypatch):
    from bench import reference

    def largest_first(self, t, n):
        short = n - self.free
        size, start = self.sim.size, self.sim.start_t
        for j in sorted(self.running, key=lambda j: (-size[j], start[j])):
            if short <= 0:
                break
            del self.running[j]
            self.sim.kill(j)
            self.enqueue(j)
            short -= size[j]
        self.owned -= n
        self.first_fit(t)

    monkeypatch.setattr(reference.Batch, "give_back", largest_first)
    # Which jobs die shows where they are not all done at the horizon:
    # the lowest capacities, over three days.
    cell = tiny.cell(CELL, days=3.0)
    cell.traffic["fb_capacity"] = {"strata": 1, "low": 128, "high": 136}
    res = tiny.run(cell)
    assert not res["correct"] and res["checks"]["rows_off"]["value"] > 0
    assert any(res["numbers"][k] > compare_faults.LIMITS[k]
               for k in ("fault_completed_gap", "fault_execution_gap"))


def test_trace_run_reports_the_per_layer_metrics_it_can_read():
    res = tiny.run(tiny.cell(CELL), trace=True)
    assert res["correct"]
    # The CPU has no device plane: the trace-read metrics stay silent.
    assert set(res["metrics"]) == {"trace_lower_s", "backend_compile_s",
                                   "rounds_max.sweep"}


def test_a_planner_without_fault_schedules_is_refused_at_set_up(
        monkeypatch):
    from repro.sim import sweep
    plain = sweep.run_sweep_workloads
    monkeypatch.setattr(
        sweep, "run_sweep_workloads",
        lambda points, workloads, duration=None, **kw: plain(
            points, workloads, duration, **kw))
    cell = tiny.cell(CELL)
    with pytest.raises(TypeError, match="no fault schedules"):
        cell.generator.make(cell.config, cell.traffic, 5)
