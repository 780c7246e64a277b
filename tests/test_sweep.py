"""Vectorized sweep engine vs the discrete-event engine (§6 methodology).

The acceptance bar for ``repro.sim.sweep``: one ``run_sweep`` call over
20+ (system, parameter) points, with the vectorized DCS/EC2 fast path
agreeing with per-point event-engine runs on every point — integer
metrics (peak nodes, completed jobs, adjust events) exactly, node-hours
to float64 round-off (< 1e-9 relative; the two paths sum the same
piecewise-constant integral in different association orders).
"""

import pytest

pytestmark = pytest.mark.tier1

from repro.sim import traces
from repro.sim.contracts import CONTRACTS
from repro.sim.engine import run_sim
from repro.sim.sweep import SweepPoint, _build, paper_grid, run_sweep

# Small trace grid: the first two simulated days of the moment-matched
# NASA-iPSC + WorldCup pair, including jobs that straddle the horizon.
T = 2 * 24 * 3600.0


@pytest.fixture(scope="module")
def workload():
    jobs = [j for j in traces.nasa_ipsc(seed=3) if j.submit < T]
    ws = [(t, d) for t, d in traces.worldcup98(seed=3, peak_vms=64)
          if t < T]
    return jobs, ws


@pytest.fixture(scope="module")
def grid():
    dcs = [SweepPoint("dcs", prc_pbj=p, prc_ws=w)
           for p, w in ((128, 64), (96, 32), (64, 64), (128, 128),
                        (32, 16), (64, 0), (48, 96), (200, 100))]
    ec2 = [SweepPoint("ec2", lease_seconds=s)
           for s in (450.0, 900.0, 1800.0, 2700.0, 3600.0, 5400.0,
                     7200.0, 10800.0, 14400.0, 28800.0)]
    phoenix = [SweepPoint("fb", capacity=160),
               SweepPoint("flb_nub", lb_pbj=13, lb_ws=12)]
    return dcs + ec2 + phoenix          # 20 vectorized + 2 event points


def test_vectorized_matches_event_engine_exactly(workload, grid):
    jobs, ws = workload
    assert len(grid) >= 20              # one call sweeps the whole grid
    vec = run_sweep(grid, jobs, ws, T, vectorize=True)
    ref = run_sweep(grid, jobs, ws, T, vectorize=False)
    assert [r["system"] for r in vec] == [p.name() for p in grid]
    for point, v, r in zip(grid, vec, ref):
        assert r["engine"] == "event"
        if point.system in ("dcs", "ec2"):
            assert v["engine"] == "vectorized", point
            # Exact integer agreement.
            assert v["peak_nodes"] == r["peak_nodes"], point
            assert v["adjust_events"] == r["adjust_events"], point
            assert v["pbj_adjust_events"] == r["pbj_adjust_events"], point
            assert v["kills"] == r["kills"], point
            if "completed_jobs" in v and "completed_jobs" in r:
                assert v["completed_jobs"] == r["completed_jobs"], point
                assert v["avg_turnaround"] == pytest.approx(
                    r["avg_turnaround"], rel=1e-9)
            # Node-hours to float64 round-off.
            assert v["node_hours"] == pytest.approx(r["node_hours"],
                                                    rel=1e-9,
                                                    abs=1e-9), point
        else:
            # The stateful policies ride the event-round engine in auto
            # mode (the default scan-family fast path): completed jobs
            # are exact by construction, node-hours and peak within its
            # 5 % contract.
            assert v["engine"] == "rounds", point
            assert v["completed_jobs"] == r["completed_jobs"], point
            assert v["node_hours"] == pytest.approx(r["node_hours"],
                                                    rel=0.05), point
            assert v["peak_nodes"] == pytest.approx(r["peak_nodes"],
                                                    rel=0.05), point
            assert v["window_overflow"] == 0 and v["truncated"] == 0


def test_vectorized_ec2_against_direct_run_sim(workload):
    """Belt and braces: the fast path also matches a hand-driven
    ``run_sim`` (not just ``run_sweep``'s own fallback)."""
    jobs, ws = workload
    from repro.sim.engine import build_ec2_rightscale, clone_jobs
    point = SweepPoint("ec2", lease_seconds=1800.0)
    row = run_sweep([point], jobs, ws, T)[0]
    r = run_sim(build_ec2_rightscale(1800.0), clone_jobs(jobs), ws, T)
    assert row["peak_nodes"] == r.peak_nodes
    assert row["completed_jobs"] == r.completed_jobs
    assert row["node_hours"] == pytest.approx(r.node_hours, rel=1e-9)
    assert row["avg_turnaround"] == pytest.approx(r.avg_turnaround, rel=1e-9)
    # EC2 never queues: turnaround == execution (§6.6.1).
    assert row["avg_turnaround"] == row["avg_execution"]


def test_paper_grid_shape_and_fallback_routing(workload):
    jobs, ws = workload
    pts = paper_grid(prc_pbj=64, prc_ws=64,
                     capacity_fracs=(0.6, 1.0), B_values=(13, 25),
                     lease_minutes=(30, 60), fig18_B=25)
    assert len(pts) == 1 + 2 + 2 + 2 * 2
    rows = run_sweep(pts, jobs, ws, T)
    by_kind = {r["system_kind"]: r["engine"] for r in rows}
    assert by_kind["dcs"] == "vectorized"
    assert by_kind["ec2"] == "vectorized"
    # The event-round engine is the default scan-family mode for the
    # stateful policies since this PR.
    assert by_kind["fb"] == "rounds"
    assert by_kind["flb_nub"] == "rounds"
    # Every builder constructs a ProvisioningSystem with the right lease.
    for p in pts:
        assert _build(p).lease_seconds == p.lease_seconds


# --------------------------------------------------- mode="rounds" fast path

def test_sweep_point_rejects_unknown_system():
    with pytest.raises(ValueError, match="unknown system"):
        SweepPoint("ec3")
    with pytest.raises(ValueError, match="lease_seconds"):
        SweepPoint("fb", capacity=10, lease_seconds=0.0)
    with pytest.raises(ValueError, match="unknown mode"):
        run_sweep([SweepPoint("dcs", prc_pbj=1)], [], [(0.0, 0)], 10.0,
                  mode="warp")
    # "scan" names no engine: an unknown mode like any other.
    removed = "scan"
    with pytest.raises(ValueError, match="unknown mode"):
        run_sweep([SweepPoint("fb", capacity=8)], [], [(0.0, 0)], 10.0,
                  mode=removed)
    # The lane kill encoding always restarts from scratch — the beyond-
    # paper checkpoint-preempt mode must be rejected, not silently run.
    from repro.core.pbj_manager import PBJPolicyParams
    ckpt = SweepPoint("fb", capacity=8,
                      params=PBJPolicyParams(checkpoint_preempt=True))
    with pytest.raises(ValueError, match="checkpoint_preempt"):
        run_sweep([ckpt], [], [(0.0, 0)], 7200.0, mode="rounds")


@pytest.fixture(scope="module")
def full_workload():
    return traces.nasa_ipsc(seed=0), traces.worldcup98(seed=0, peak_vms=128)


@pytest.fixture(scope="module")
def scan_grid():
    """Fig. 13 capacities + Fig. 14 pool sizes + Fig. 18 leases — the
    coordinated-policy points of the paper grids."""
    return (
        [SweepPoint("fb", capacity=c) for c in (128, 154, 192, 256)]
        + [SweepPoint("flb_nub", lb_pbj=B - 12, lb_ws=12)
           for B in (13, 25, 51, 154)]
        + [SweepPoint("flb_nub", lb_pbj=13, lb_ws=12, lease_seconds=L,
                      label=f"FLB-NUB(L={L:g}s)")
           for L in (900.0, 3600.0, 14400.0)])


def test_scan_mode_fidelity_contract(full_workload, scan_grid):
    """The rounds contract (``CONTRACTS["rounds"]``) of the batched
    event-round path vs the event engine on two-week paper workloads:
    completed jobs exact, node-hours and peak within 5 %."""
    jobs, ws = full_workload
    T_full = traces.TWO_WEEKS
    contract = CONTRACTS["rounds"]
    rounds_rows = run_sweep(scan_grid, jobs, ws, T_full, mode="rounds")
    event_rows = run_sweep(scan_grid, jobs, ws, T_full, mode="event")
    for p, s, e in zip(scan_grid, rounds_rows, event_rows):
        assert s["engine"] == "rounds" and e["engine"] == "event"
        assert s["window_overflow"] == 0, p
        assert s["truncated"] == 0, p
        assert contract.check_row(s, e) == [], p


def test_scan_mode_preserves_sweep_orderings(full_workload, scan_grid):
    """J1/J2 acceptance: the rounds path ranks parameter-sweep points the
    same way the event engine does (Fig. 13 capacity → cost, Fig. 14
    B → cost and turnaround, Fig. 18 L → adjust events)."""
    jobs, ws = full_workload
    T_full = traces.TWO_WEEKS
    scan_rows = run_sweep(scan_grid, jobs, ws, T_full, mode="rounds")
    event_rows = run_sweep(scan_grid, jobs, ws, T_full, mode="event")

    def order(rows, idx, metric):
        vals = [rows[i][metric] for i in idx]
        return sorted(range(len(vals)), key=vals.__getitem__)

    fb_idx, b_idx, l_idx = range(0, 4), range(4, 8), range(8, 11)
    # Fig. 13: node-hours grow with capacity C.
    assert order(scan_rows, fb_idx, "node_hours") \
        == order(event_rows, fb_idx, "node_hours") == [0, 1, 2, 3]
    # J1 (Fig. 14): consumption grows with B, turnaround falls with B.
    assert order(scan_rows, b_idx, "node_hours") \
        == order(event_rows, b_idx, "node_hours") == [0, 1, 2, 3]
    assert scan_rows[4]["avg_turnaround"] > scan_rows[7]["avg_turnaround"]
    assert event_rows[4]["avg_turnaround"] > event_rows[7]["avg_turnaround"]
    # Fig. 18: PBJ adjust events fall as the lease unit grows.
    assert order(scan_rows, l_idx, "pbj_adjust_events") \
        == order(event_rows, l_idx, "pbj_adjust_events") == [2, 1, 0]
