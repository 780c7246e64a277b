"""The plain reference on workloads small enough to work out by hand
from the paper's definitions."""

from __future__ import annotations

import pytest

from bench import reference

HOUR = 3600.0


def run(point, jobs, ws, duration):
    """``jobs`` as (submit, size, runtime) triples."""
    submit, size, runtime = zip(*jobs) if jobs else ((), (), ())
    return reference.row(point, submit, size, runtime, ws, duration)


def test_dcs_holds_its_partition_and_never_starts_a_job_too_large():
    r = run({"system": "dcs", "prc_pbj": 4, "prc_ws": 2},
            [(0.0, 4, 100.0), (10.0, 5, 100.0), (20.0, 2, 50.0)],
            [(0.0, 1), (500.0, 2)], 2 * HOUR)
    # The 4-node job runs 0..100, the 2-node one waits for it, 100..150;
    # the 5-node job never fits the 4-node partition.
    assert r["completed_jobs"] == 2
    assert r["avg_execution"] == pytest.approx(75.0)
    assert r["avg_turnaround"] == pytest.approx((100.0 + 130.0) / 2)
    assert r["node_hours"] == pytest.approx(6 * 2.0)
    assert r["peak_nodes"] == 6


def test_fb_kills_the_smallest_latest_started_job_and_restarts_it():
    # Capacity 4: the batch side owns all 4 nodes at start. Jobs of 2
    # (from t=0) and 1 (from t=50) nodes run; web demand of 2 at t=100
    # takes the batch side's unused node, then kills the 1-node job (the
    # smallest), which restarts at the lease boundary after demand falls.
    r = run({"system": "fb", "capacity": 4, "lease_seconds": 1000.0},
            [(0.0, 2, 3000.0), (50.0, 1, 400.0)],
            [(0.0, 0), (100.0, 2), (600.0, 0)], 4000.0)
    assert r["completed_jobs"] == 2
    # The 1-node job restarts at t=1000 (the boundary after the fall)
    # and runs its whole 400 s again: executions 3000 and 400.
    assert r["avg_execution"] == pytest.approx(1700.0)
    assert r["avg_turnaround"] == pytest.approx((3000.0 + 1350.0) / 2)
    # Held: 4 nodes, less 2 idle from the fall at 600 to the boundary.
    assert r["node_hours"] == pytest.approx((4 * 4000.0 - 2 * 400.0) / HOUR)
    assert r["peak_nodes"] == 4


def test_fb_on_equal_sizes_kills_the_latest_started():
    r = run({"system": "fb", "capacity": 2, "lease_seconds": 10 * HOUR},
            [(0.0, 1, 1000.0), (10.0, 1, 1000.0)],
            [(0.0, 0), (20.0, 1)], 2 * HOUR)
    # The job started at 10 is killed; the one from 0 ends at 1000 and
    # frees the node the killed job restarts on, to end at 2000.
    assert r["completed_jobs"] == 2
    assert r["avg_turnaround"] == pytest.approx((1000.0 + 1990.0) / 2)


def test_flb_nub_leases_for_the_queue_and_gives_back_at_low_demand():
    # Pool of 2 + 1. A 4-node job queues at t=10. At the boundary of
    # 1000 the idle web pool node goes to the batch side (3 owned), the
    # queue's demand over owned nodes, 4 / 3, passes U = 1.2, so 1 node is
    # leased and the job runs 1000..1500. At 2000 nothing is queued
    # (ratio 0 < V) and half of the 4 free nodes, 2, go back: the leased
    # node first, then a pool node.
    r = run({"system": "flb_nub", "lb_pbj": 2, "lb_ws": 1,
             "lease_seconds": 1000.0},
            [(10.0, 4, 500.0)], [(0.0, 0)], 3000.0)
    assert r["completed_jobs"] == 1
    assert r["avg_turnaround"] == pytest.approx(1490.0)
    assert r["node_hours"] == pytest.approx((3 * 3000.0 + 1000.0) / HOUR)
    assert r["peak_nodes"] == 4


def test_flb_nub_web_demand_takes_its_pool_share_then_leases():
    r = run({"system": "flb_nub", "lb_pbj": 2, "lb_ws": 1,
             "lease_seconds": 10 * HOUR},
            [], [(0.0, 3), (1800.0, 1)], HOUR)
    # Demand 3: 1 from the pool, 2 leased, until 1800.
    assert r["node_hours"] == pytest.approx((3 * HOUR + 2 * 1800.0) / HOUR)
    assert r["peak_nodes"] == 5
    assert r["completed_jobs"] == 0 and r["avg_execution"] == 0.0


def test_ec2_leases_per_job_until_the_boundary_after_it_ends():
    r = run({"system": "ec2", "lease_seconds": HOUR},
            [(100.0, 2, 1000.0), (200.0, 3, HOUR - 200.0)],
            [(0.0, 1)], 3 * HOUR)
    # Job 1 holds 2 nodes 100..3600; job 2 ends on the boundary at 3600,
    # after that boundary's release, and holds 3 nodes 200..7200.
    assert r["completed_jobs"] == 2
    assert r["avg_execution"] == pytest.approx((1000.0 + HOUR - 200.0) / 2)
    assert r["node_hours"] == pytest.approx(
        (1 * 3 * HOUR + 2 * 3500.0 + 3 * 7000.0) / HOUR)
    assert r["peak_nodes"] == 6


def test_a_web_fall_on_a_boundary_comes_before_the_boundary():
    # The fall at 1000 frees 2 nodes before the boundary at 1000 hands
    # the idle nodes to the batch side: no idle time, and the waiting
    # 4-node job starts at 1000.
    r = run({"system": "fb", "capacity": 4, "lease_seconds": 1000.0},
            [(10.0, 4, 100.0)], [(0.0, 2), (1000.0, 0)], 2000.0)
    assert r["node_hours"] == pytest.approx(4 * 2000.0 / HOUR)
    assert r["avg_turnaround"] == pytest.approx(1090.0)


def test_jobs_that_end_after_the_period_do_not_count():
    r = run({"system": "ec2", "lease_seconds": HOUR},
            [(0.0, 1, 100.0), (50.0, 1, 5000.0)], [(0.0, 0)], HOUR)
    assert r["completed_jobs"] == 1
    assert r["node_hours"] == pytest.approx((3600.0 + 3550.0) / HOUR)
