"""Fault schedules on the normal sweep path:
``run_sweep_workloads(..., faults=[schedule or None per workload])``.

What is pinned here:

* stacked faulted rows equal one-point ``run_sweep_workloads`` rows bit
  for bit, with workloads with and without a schedule in one device
  call;
* they hold ``CONTRACTS["faults"]`` against the event engine, and the
  event mode of the same call IS ``run_sim(faults=)``;
* ``faults=None`` (or no schedule with an event) leaves the pack, the
  lowered program and the rows as they are without the argument;
* DCS, EC2 and FLB-NUB points refuse a schedule, and ``"scan"`` is an
  unknown mode;
* the root span's counters and the ``rounds.fault_tables`` spans;
* ``fault_stops`` fixes the program's shapes across fresh draws.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.tier1

from repro import spans
from repro.core.jobs import Job
from repro.sim import rounds as roundslib
from repro.sim.contracts import CONTRACTS
from repro.sim.engine import build_fb, clone_jobs, run_sim
from repro.sim.faults import (FaultSchedule, burst_schedule,
                              merge_schedules, weibull_schedule)
from repro.sim.sweep import (ScanOptions, SweepPoint, _pack_rounds,
                             run_sweep_workloads)

DAY = 24 * 3600.0
REMOVED_MODE = "scan"
CAP = 12
LEASES = (900.0, 3600.0, 7200.0)


def _workload(seed, n=30):
    rng = np.random.Generator(np.random.PCG64(seed))
    jobs = [Job(jid=i, submit=float(rng.uniform(0, DAY * 0.7)),
                size=int(rng.integers(1, CAP // 3)),
                runtime=float(rng.uniform(600.0, DAY / 6)))
            for i in range(n)]
    ws = [(float(t), int(rng.integers(0, CAP // 2 + 2)))
          for t in np.sort(rng.uniform(0, DAY, 10))]
    return jobs, ws


def _schedule(seed):
    return merge_schedules(
        weibull_schedule(seed, CAP, 8 * 3600.0, 1800.0, DAY, shape=0.7,
                         repair="lognormal"),
        burst_schedule(seed + 1, 4, 12 * 3600.0, 3600.0, DAY,
                       repair="lognormal"))


WORKLOADS = [_workload(s) for s in range(3)]
SCHEDULES = [_schedule(10), None, _schedule(20)]
POINTS = [SweepPoint("fb", capacity=CAP, lease_seconds=L) for L in LEASES]


def _instants(schedule):
    """Distinct fault instants inside the horizon, after the clamp."""
    t = schedule.clamp(CAP).times
    return len(np.unique(t[t < DAY]))


def _fault_root():
    rows = run_sweep_workloads(POINTS, WORKLOADS, DAY, mode="rounds",
                               faults=SCHEDULES)
    return rows, spans.roots("sweep")[-1]


def test_stacked_fault_rows_equal_one_point_rows():
    """W = 3 workloads, one without a schedule, in ONE device call: every
    row equals the one-point call of its (workload, lease, schedule) bit
    for bit. The unfaulted workload's rows add only the
    fault counters, both 0."""
    stacked, root = _fault_root()
    dispatches = [r for r in spans.RECORDER.records
                  if r.root_id == root["id"] and r.name == "sweep.dispatch"]
    assert len(dispatches) == 1
    for w, (jobs, ws) in enumerate(WORKLOADS):
        for i, p in enumerate(POINTS):
            one = run_sweep_workloads([p], [(jobs, ws)], DAY,
                                      mode="rounds",
                                      faults=[SCHEDULES[w]])[0][0]
            got = dict(stacked[w][i])
            if SCHEDULES[w] is None:
                assert got.pop("fault_rounds") == 0
                assert got.pop("fault_kills") == 0
            assert got == one
    assert all(r["fault_rounds"] > 0 for r in stacked[0] + stacked[2])


def test_stacked_fault_rows_hold_the_fault_contract():
    """The stacked rows against the event engine under
    ``CONTRACTS["faults"]``; the event mode of the same call is
    ``run_sim(faults=)`` row for row."""
    stacked, _ = _fault_root()
    event = run_sweep_workloads(POINTS, WORKLOADS, DAY, mode="event",
                                faults=SCHEDULES)
    for w, (jobs, ws) in enumerate(WORKLOADS):
        for i, p in enumerate(POINTS):
            ev = run_sim(build_fb(CAP, p.lease_seconds), clone_jobs(jobs),
                         ws, DAY, name=p.name(), faults=SCHEDULES[w]).row()
            assert event[w][i]["engine"] == "event"
            assert {k: event[w][i][k] for k in ev} == ev
            assert CONTRACTS["faults"].check_row(stacked[w][i], ev) == []


def test_faults_none_leaves_the_pack_program_and_rows_unchanged():
    """``faults=None``, a list of ``None`` and a list of empty schedules
    are all the call without the argument: the same packed pytree, the
    same lowered program, the same rows."""
    import jax
    pts = POINTS + [SweepPoint("flb_nub", lb_pbj=6, lb_ws=4)]
    empty = FaultSchedule(np.zeros(0), np.zeros(0, dtype=np.int64))
    opts = ScanOptions()
    base = _pack_rounds(pts, WORKLOADS, DAY, opts)
    for faults in (None, [None] * 3, [empty] * 3):
        packed = _pack_rounds(pts, WORKLOADS, DAY, opts, faults)
        assert packed[6] == base[6] and packed[7] == base[7]   # specs
        a, b = base[4], packed[4]
        assert b.fault_times is None
        assert (jax.tree_util.tree_structure(a)
                == jax.tree_util.tree_structure(b))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def lowered(packs):
        _, _, fb, flb, fbp, flbp, fbs, flbs = packs
        return roundslib._rounds_grids_single.lower(
            fb, flb, fbp, flbp, fb_spec=fbs, flb_spec=flbs).as_text()

    assert lowered(_pack_rounds(pts, WORKLOADS, DAY, opts, [None] * 3)) \
        == lowered(base)
    plain = run_sweep_workloads(pts, WORKLOADS, DAY, mode="rounds")
    for faults in (None, [None] * 3, [empty] * 3):
        assert run_sweep_workloads(pts, WORKLOADS, DAY, mode="rounds",
                                   faults=faults) == plain


@pytest.mark.parametrize("point", [
    SweepPoint("flb_nub", lb_pbj=6, lb_ws=4),
    SweepPoint("dcs", prc_pbj=6, prc_ws=6),
    SweepPoint("ec2", lease_seconds=3600.0),
])
def test_a_schedule_beside_another_system_raises(point):
    with pytest.raises(ValueError, match="FB points only"):
        run_sweep_workloads(POINTS + [point], WORKLOADS, DAY,
                            mode="rounds", faults=SCHEDULES)


def test_a_schedule_in_scan_mode_or_misaligned_raises():
    # "scan" names no engine: an unknown mode like any other.
    with pytest.raises(ValueError, match="unknown mode"):
        run_sweep_workloads(POINTS, WORKLOADS, DAY, mode=REMOVED_MODE,
                            faults=SCHEDULES)
    with pytest.raises(ValueError, match="must align"):
        run_sweep_workloads(POINTS, WORKLOADS, DAY, mode="rounds",
                            faults=SCHEDULES[:2])


def test_fault_counters_and_spans_on_the_root():
    """``faults.events`` counts the fault instants inside the horizon,
    ``rounds.fault_rounds`` and ``faults.kills`` sum the rows' fault
    counters; one ``rounds.fault_tables`` span per faulted workload and
    one for the padded tables, all inside ``sweep.pack``."""
    stacked, root = _fault_root()
    c = root["counters"]
    assert c["faults.events"] == sum(_instants(s) for s in SCHEDULES
                                     if s is not None)
    flat = [r for rows in stacked for r in rows]
    assert c["rounds.fault_rounds"] == sum(r["fault_rounds"] for r in flat)
    assert c["faults.kills"] == sum(r["fault_kills"] for r in flat)
    assert 0 < c["faults.kills"] <= sum(r["kills"] for r in flat)
    # Every lane of a faulted workload stops at each of its instants.
    for w, s in enumerate(SCHEDULES):
        if s is not None:
            assert all(r["fault_rounds"] == _instants(s)
                       for r in stacked[w])
    recs = [r for r in spans.RECORDER.records if r.root_id == root["id"]]
    pack = [r for r in recs if r.name == "sweep.pack"]
    tables = [r for r in recs if r.name == "rounds.fault_tables"]
    assert len(pack) == 1 and len(tables) == 2 + 1
    assert all(t.parent_id == pack[0].id for t in tables)


def test_fault_stops_fixes_the_shapes_across_draws():
    """With ``fault_stops`` two draws of different lengths pack to the
    same shapes and round budget (one compiled program), with the rows
    of the call without it; a schedule longer than it raises."""
    opts = ScanOptions(fault_stops=128)
    packs = []
    for seed in (30, 40):
        faults = [_schedule(seed), _schedule(seed + 1), None]
        packs.append(_pack_rounds(POINTS, WORKLOADS, DAY, opts, faults))
        fixed = run_sweep_workloads(POINTS, WORKLOADS, DAY, mode="rounds",
                                    scan_options=opts, faults=faults)
        assert fixed == run_sweep_workloads(POINTS, WORKLOADS, DAY,
                                            mode="rounds", faults=faults)
    assert packs[0][6] == packs[1][6]
    assert packs[0][4].fault_times.shape == packs[1][4].fault_times.shape \
        == (3, 129)
    with pytest.raises(ValueError, match="fault_stops"):
        run_sweep_workloads(POINTS, WORKLOADS, DAY, mode="rounds",
                            scan_options=ScanOptions(fault_stops=4),
                            faults=SCHEDULES)
