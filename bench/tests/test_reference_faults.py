"""The fault reference on workloads small enough to work out by hand
from the planner's stated FB failure semantics, and as a second witness
the planner's own event engine on random small rows."""

from __future__ import annotations

import numpy as np
import pytest

from bench import reference_faults

HOUR = 3600.0


def run(capacity, jobs, ws, faults, duration, lease=10 * HOUR):
    """``jobs`` as (submit, size, runtime) triples."""
    submit, size, runtime = zip(*jobs) if jobs else ((), (), ())
    return reference_faults.row(
        {"system": "fb", "capacity": capacity, "lease_seconds": lease},
        submit, size, runtime, ws, faults, duration)


def test_a_failure_takes_idle_nodes_before_any_job():
    # Capacity 4, web 2 of it until 100, then 0: the two freed nodes sit
    # idle until the boundary at 1000. A failure of 2 at 500 takes them,
    # and the 2-node job runs on.
    r = run(4, [(0.0, 2, 2000.0)], [(0.0, 2), (100.0, 0)],
            [(500.0, 2), (1500.0, -2)], 3000.0, lease=1000.0)
    assert r["completed_jobs"] == 1
    assert r["avg_execution"] == pytest.approx(2000.0)
    # Held: 4 to 100, 2 to the boundary at 1000, 2 to the one at 2000
    # (the repaired nodes idle until it), 4 after.
    assert r["node_hours"] == pytest.approx(
        (4 * 100 + 2 * 900 + 2 * 1000 + 4 * 1000) / HOUR)
    assert r["peak_nodes"] == 4


def test_a_failure_kills_the_smallest_latest_started_job_and_it_starts_over():
    # Capacity 4, no web demand: jobs of 2 (from 0), 1 (from 0) and 1
    # (from 50) fill the site. A failure of 1 at 100 kills the 1-node
    # job started last; the node returns at 300 and idles until the
    # boundary at 500, where the job starts over.
    r = run(4, [(0.0, 2, 1000.0), (0.0, 1, 1000.0), (50.0, 1, 400.0)],
            [(0.0, 0)], [(100.0, 1), (300.0, -1)], 2000.0, lease=250.0)
    assert r["completed_jobs"] == 3
    # The killed job runs 500..900 whole.
    assert r["avg_turnaround"] == pytest.approx((1000 + 1000 + 850) / 3)
    assert r["avg_execution"] == pytest.approx((1000 + 1000 + 400) / 3)
    # One node held by no one from the failure to the boundary.
    assert r["node_hours"] == pytest.approx((4 * 2000 - 400) / HOUR)


def test_a_failure_past_the_batch_side_sheds_web_replicas_until_a_repair():
    # Capacity 4, web demand 3, one 1-node job. A failure of 3 at 100
    # kills the job and sheds 2 replicas; a repair of 3 at 200 refills
    # the web side first (to 3) and leaves the last node idle until the
    # boundary at 1000, where the queued job takes it again.
    r = run(4, [(0.0, 1, 2000.0)], [(0.0, 3)],
            [(100.0, 3), (200.0, -3)], 4000.0, lease=1000.0)
    assert r["completed_jobs"] == 1
    assert r["avg_execution"] == pytest.approx(2000.0)
    assert r["avg_turnaround"] == pytest.approx(3000.0)
    assert r["node_hours"] == pytest.approx(
        (4 * 100 + 1 * 100 + 3 * 800 + 4 * 3000) / HOUR)
    assert r["peak_nodes"] == 4


def test_web_demand_while_nodes_are_down_gets_only_the_surviving_nodes():
    # Capacity 4 with 2 down from 10; web demand 4 at 20 gets 2 nodes;
    # the repair at 50 raises it to the 4 it asked for.
    r = run(4, [], [(0.0, 0), (20.0, 4)], [(10.0, 2), (50.0, -2)], 100.0)
    assert r["peak_nodes"] == 4
    assert r["node_hours"] == pytest.approx(
        (4 * 10 + 2 * 40 + 4 * 50) / HOUR)


def test_at_one_instant_a_job_ends_and_a_repair_comes_before_a_failure():
    # The 2-node job ends at 100, when 4 of the 4 nodes fail and 0 are
    # repaired: the job has ended. The other job, started at 0, is
    # killed.
    r = run(4, [(0.0, 2, 100.0), (0.0, 2, 500.0)], [(0.0, 0)],
            [(100.0, 4), (200.0, -4)], 1000.0, lease=1000.0)
    assert r["completed_jobs"] == 1
    assert r["avg_execution"] == pytest.approx(100.0)
    # Down from 100 to 200; all 4 idle from 200 to the boundary at 1000.
    assert r["node_hours"] == pytest.approx(4 * 100 / HOUR)


def test_no_more_than_capacity_nodes_go_down_and_repairs_return_only_those():
    # Failures of 3 and 3 on a 4-node site take 4; a repair of 6 brings
    # back 4.
    r = run(4, [], [(0.0, 4)], [(10.0, 3), (20.0, 3), (30.0, -6)], 100.0)
    assert r["node_hours"] == pytest.approx(
        (4 * 10 + 1 * 10 + 0 * 10 + 4 * 70) / HOUR)


def test_other_systems_are_refused():
    with pytest.raises(ValueError, match="FB points only"):
        reference_faults.row({"system": "flb_nub", "lb_pbj": 2,
                              "lb_ws": 1}, [], [], [], [(0.0, 0)], [],
                             100.0)


def test_agrees_with_the_planner_event_engine_on_random_rows():
    """A second witness: the planner's event engine (``run_sim`` with
    ``faults=``, plain kill mode) on random small workloads and
    schedules, every metric to round-off."""
    from bench.harness import add_program_path
    add_program_path()
    from repro.core.jobs import Job
    from repro.sim.engine import build_fb, run_sim
    from repro.sim.faults import (burst_schedule, merge_schedules,
                                  weibull_schedule)
    day = 24 * HOUR
    for seed in range(40):
        rng = np.random.default_rng(seed)
        cap = int(rng.integers(4, 20))
        jobs = [(float(rng.uniform(0, 0.7 * day)),
                 int(rng.integers(1, max(2, cap // 3))),
                 float(rng.uniform(600.0, day / 6))) for _ in range(30)]
        ws = [(float(t), int(rng.integers(0, cap // 2 + 2)))
              for t in np.sort(rng.uniform(0, day, 10))]
        sched = merge_schedules(
            weibull_schedule(seed, cap, 6 * HOUR, 1800.0, day, shape=0.7,
                             repair="lognormal"),
            burst_schedule(seed + 1000, max(1, cap // 3), 10 * HOUR, HOUR,
                           day, repair="lognormal"))
        for lease in (900.0, HOUR):
            ev = run_sim(build_fb(cap, lease),
                         [Job(jid=i, submit=s, size=z, runtime=r)
                          for i, (s, z, r) in enumerate(jobs)],
                         ws, day, faults=sched).row()
            ref = run(cap, jobs, ws, list(sched.events()), day, lease)
            for k in ("completed_jobs", "peak_nodes"):
                assert ev[k] == ref[k]
            for k in ("avg_execution", "avg_turnaround", "node_hours"):
                assert ev[k] == pytest.approx(ref[k], rel=1e-12)
