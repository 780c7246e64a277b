"""FB kill semantics and accounting invariants of the batched rounds path.

The lane encoding (repro.sim.lanes) replaces the event engine's Python
queue/kill machinery with status lanes over a fixed job window: a kill
is a masked flag flip and the killed lane *derives* back into the queue.
These tests pin that encoding down through ``mode="rounds"``, in the
spirit of tests/test_pool_accounting.py:

  * randomized (jobs, WS) workloads cross-checked against the event
    engine — kill activity, completed jobs and node-hours must agree;
  * capacity / pool invariants readable from the rows: an FB site never
    allocates beyond C, an FLB-NUB site never drops the rigid pool B
    (§5.2 — it is paid for whether idle or not).

The designed §5.1 demand-spike scenarios (kills, re-entry, a partial
kill) live in tests/test_rounds.py.
"""

import random

import pytest

pytestmark = pytest.mark.tier1

from repro.core.jobs import Job
from repro.sim.engine import build_fb, build_flb_nub, clone_jobs, run_sim
from repro.sim.sweep import ScanOptions, SweepPoint, run_sweep

DAY = 24 * 3600.0
OPTS = ScanOptions(window=32)   # tiny window: these workloads are small


def rounds_row(point, jobs, ws, duration):
    return run_sweep([point], jobs, ws, duration, mode="rounds",
                     scan_options=OPTS)[0]


# ------------------------------------------------------ designed kill spike

def spike_workload():
    """C=10: three jobs fill the site, then a WS spike to 8 leaves a
    budget of 2 nodes — both size-4 jobs MUST be killed (§5.1 rule 2)
    and can only finish by re-entering the queue and restarting after
    the demand recedes and the lease tick re-provisions the idle pool."""
    jobs = [Job(0, 0.0, size=4, runtime=2 * 3600.0),
            Job(1, 0.0, size=4, runtime=2 * 3600.0),
            Job(2, 0.0, size=2, runtime=1200.0)]
    ws = [(0.0, 0), (1800.0, 8), (2 * 3600.0, 0)]
    return jobs, ws


# ------------------------------------------------- randomized cross-checks

def random_workload(seed):
    rng = random.Random(seed)
    jobs = [Job(i, rng.uniform(0.0, 12 * 3600.0),
                size=2 ** rng.randrange(0, 4),
                runtime=rng.uniform(900.0, 2 * 3600.0))
            for i in range(30)]
    # WS change points on a 900 s grid.
    ws = [(k * 900.0, rng.randrange(0, 13)) for k in range(0, 96, 2)]
    return jobs, ws


@pytest.mark.parametrize("seed", range(6))
def test_fb_rounds_matches_event_on_random_traces(seed):
    jobs, ws = random_workload(seed)
    C = 12
    row = rounds_row(SweepPoint("fb", capacity=C), jobs, ws, DAY)
    ref = run_sim(build_fb(C), clone_jobs(jobs), ws, DAY)
    assert row["window_overflow"] == 0
    # Kill activity agrees (node-weighted timing differences allowed).
    assert (row["kills"] > 0) == (ref.kills > 0)
    assert abs(row["kills"] - ref.kills) <= max(2, 0.5 * ref.kills)
    # Jobs are conserved: killed jobs re-enter, nothing is lost. The
    # rounds contract's exact count does not hold here: the size-class
    # kill order breaks §5.1's ties differently (an open fault), and a
    # different victim can finish on either side of the horizon.
    assert abs(row["completed_jobs"] - ref.completed_jobs) <= 2
    assert row["node_hours"] == pytest.approx(ref.node_hours, rel=0.05)
    # Capacity invariant: an FB site can never allocate beyond C (§5.1).
    assert row["peak_nodes"] <= C
    assert row["node_hours"] <= C * DAY / 3600.0 + 1e-6


# --------------------------------------------- FLB-NUB kill-path exemption

@pytest.mark.parametrize("seed", (0, 1, 2))
def test_flb_nub_never_kills(seed):
    """The §5.2 policy has no force-release path: WS demand is satisfied
    elastically (never by taking PBJ nodes back) and the RSS release
    only ever returns *free* nodes — so FLB-NUB cannot kill, even on the
    kill-provoking workloads that make FB kill. This is why the rounds
    path's checkpoint_preempt guard (repro.sim.sweep) rejects only FB
    points: for FLB-NUB the preemption mode is provably a no-op."""
    from repro.core.pbj_manager import PBJPolicyParams

    jobs, ws = random_workload(seed) if seed else spike_workload()
    for preempt in (False, True):
        params = PBJPolicyParams(checkpoint_preempt=preempt)
        ref = run_sim(build_flb_nub(13, 12, params=params),
                      clone_jobs(jobs), ws, DAY)
        assert ref.kills == 0, (seed, preempt)
    # ... and the same workload genuinely kills under FB, so the zero
    # above is the policy's doing, not a tame workload.
    assert run_sim(build_fb(10 if seed == 0 else 12), clone_jobs(jobs),
                   ws, DAY).kills > 0, seed


def test_flb_nub_rounds_accepts_checkpoint_preempt():
    """mode="rounds" accepts FLB-NUB points with checkpoint_preempt=True
    (deliberate exemption — see test_flb_nub_never_kills) and returns
    the same rows as without the flag, since nothing is ever killed."""
    from repro.core.pbj_manager import PBJPolicyParams

    jobs, ws = random_workload(7)
    rows = [rounds_row(SweepPoint("flb_nub", lb_pbj=13, lb_ws=12,
                                params=PBJPolicyParams(
                                    checkpoint_preempt=preempt)),
                     jobs, ws, DAY)
            for preempt in (False, True)]
    assert rows[0]["kills"] == rows[1]["kills"] == 0
    assert rows[0] == rows[1]


def test_flb_rounds_peak_contract_on_beyond_paper_grid():
    """Regression for the long-lease peak overshoot: on L = 2 h with a
    2×-scaled World Cup profile (a beyond-paper combo), evaluating the
    U/V/G rules on *pre-start* demand lets one tick absorb a whole
    submit burst as a single DR1 request — 57 % peak drift vs the event
    engine. With the event-faithful tick ordering of ``flb_actions``
    (grant → first-fit → adjust → first-fit) the rounds contract
    holds: completed jobs exact, node-hours and peak within 5 %."""
    from repro.core.profiles import scale_profile
    from repro.sim import traces

    T = traces.TWO_WEEKS
    jobs = traces.nasa_ipsc(seed=1)
    ws = scale_profile(traces.worldcup98(seed=0, peak_vms=128), 2.0)
    pts = [SweepPoint("flb_nub", lb_pbj=13, lb_ws=12, lease_seconds=L,
                      label=f"FLB-NUB(L={L:g}s)")
           for L in (7200.0, 14400.0)]
    rounds = run_sweep(pts, jobs, ws, T, mode="rounds")
    event = run_sweep(pts, jobs, ws, T, mode="event")
    for p, s, e in zip(pts, rounds, event):
        assert s["window_overflow"] == 0, p
        assert s["peak_nodes"] == pytest.approx(e["peak_nodes"],
                                                rel=0.05), p
        assert s["node_hours"] == pytest.approx(e["node_hours"],
                                                rel=0.05), p
        assert s["completed_jobs"] == e["completed_jobs"], p


@pytest.mark.parametrize("seed", (0, 1, 2))
def test_flb_rounds_pool_invariants_on_random_traces(seed):
    jobs, ws = random_workload(100 + seed)
    lb_pbj, lb_ws = 13, 12
    B = lb_pbj + lb_ws
    row = rounds_row(SweepPoint("flb_nub", lb_pbj=lb_pbj, lb_ws=lb_ws),
                   jobs, ws, DAY)
    ref = run_sim(build_flb_nub(lb_pbj, lb_ws), clone_jobs(jobs), ws, DAY)
    assert row["window_overflow"] == 0
    assert row["kills"] == 0                    # FLB-NUB never kills (§5.2)
    assert row["completed_jobs"] == ref.completed_jobs
    assert row["node_hours"] == pytest.approx(ref.node_hours, rel=0.05)
    # Pool invariants (the lane analog of test_pool_accounting P5): the
    # rigid pool B is held for the whole trace, so consumption is at
    # least B node-hours per hour and the peak is at least B.
    assert row["node_hours"] >= B * DAY / 3600.0 - 1e-6
    assert B <= row["peak_nodes"]
