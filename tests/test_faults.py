"""The chaos tier (repro.sim.faults + FAIL/REPAIR on the shared pump).

What is pinned here:

* the pump's simultaneity order with the new kinds — FINISH beats
  REPAIR beats FAIL at one timestamp, so a job finishing exactly when
  its node dies still completes;
* deterministic schedule generation (PRNG-keyed, replayable) and the
  site ledger's capacity clamp;
* the FB/FLB-NUB failure semantics (absorption order, shed accounting,
  pool bookkeeping) and the §5.1 checkpoint-restart recovery path;
* the three-path differential: event vs rounds under
  ``CONTRACTS["faults"]``, event vs LiveCloud trace replay with exact
  ledger identity;
* the no-lost-jobs invariant and monotone checkpointed progress, as a
  hypothesis property test when hypothesis is installed and over fixed
  seeds otherwise;
* the serving-layer degradation machinery: ``GrantBackoff`` and the
  admission throttle, plus torn-checkpoint skip-and-restore.
"""

import math
import os

import numpy as np
import pytest

pytestmark = pytest.mark.tier1

from repro.core.jobs import Job
from repro.sim.contracts import CONTRACTS, FAULT_CONTRACT, no_lost_jobs
from repro.sim.engine import (build_fb, build_flb_nub, clone_jobs,
                              run_sim)
from repro.sim.faults import (FaultSchedule, burst_schedule,
                              exponential_schedule, merge_schedules,
                              weibull_schedule)
from repro.sim.pump import (CALL, FAIL, FINISH, REPAIR, SUBMIT, TICK,
                            WS, DecisionLedger)

DAY = 24 * 3600.0


# ------------------------------------------------------------ tie order

def test_event_kind_ordinals_pinned():
    """The packed fold tables and the heap tie-break both encode these
    ordinals — changing one silently reorders simultaneous events."""
    assert (WS, CALL, TICK, SUBMIT, FINISH, REPAIR, FAIL) == \
        (0, 1, 2, 3, 4, 5, 6)


def test_same_timestamp_tie_order_with_fault_kinds():
    """At one timestamp: ws < tick < submit < finish < repair < fail.
    The finish-before-fail leg IS the no-lost-jobs convention: a job
    completing at the exact instant its node dies has completed."""
    jobs = [Job(jid=0, submit=0.0, size=2, runtime=1800.0),
            Job(jid=1, submit=1800.0, size=2, runtime=600.0)]
    ws = [(0.0, 0), (1800.0, 1)]
    sched = FaultSchedule(np.array([600.0, 1800.0, 1800.0]),
                          np.array([1, -1, 2]))
    led = DecisionLedger()
    sys_ = build_fb(4, lease_seconds=1800.0)
    run_sim(sys_, jobs, ws, duration=3600.0, ledger=led, faults=sched)
    at = [e.kind for e in led.entries if e.t == 1800.0]
    assert at == ["ws", "tick", "submit", "finish", "repair", "fail"]
    # Job 0 finished at 1800.0 even though 2 nodes failed at 1800.0.
    assert jobs[0].completed
    # The same-instant failure killed the just-started job 1 instead —
    # recorded as a failure kill on the "fail" row, and the job is
    # requeued, not lost.
    assert led.kills("fail") == 1
    assert not jobs[1].completed
    assert no_lost_jobs(jobs, sys_) == []


# ----------------------------------------------------------- schedules

def test_schedule_validation():
    with pytest.raises(ValueError):        # unsorted
        FaultSchedule(np.array([2.0, 1.0]), np.array([1, -1]))
    with pytest.raises(ValueError):        # t <= 0
        FaultSchedule(np.array([0.0]), np.array([1]))
    with pytest.raises(ValueError):        # zero delta
        FaultSchedule(np.array([1.0]), np.array([0]))
    with pytest.raises(ValueError):        # repair before any failure
        FaultSchedule(np.array([1.0, 2.0]), np.array([1, -2]))
    with pytest.raises(ValueError):        # shape mismatch
        FaultSchedule(np.array([1.0, 2.0]), np.array([1]))


def test_generators_deterministic_and_replayable():
    kw = dict(n_nodes=8, mtbf=6 * 3600.0, mttr=1800.0, duration=DAY)
    a = exponential_schedule(seed=3, **kw)
    b = exponential_schedule(seed=3, **kw)
    assert np.array_equal(a.times, b.times)
    assert np.array_equal(a.deltas, b.deltas)
    c = exponential_schedule(seed=4, **kw)
    assert len(a) and (len(a) != len(c)
                       or not np.array_equal(a.times, c.times))
    w = weibull_schedule(seed=3, n_nodes=8, mtbf=6 * 3600.0,
                         mttr=1800.0, duration=DAY, shape=1.5)
    assert len(w) and int(np.sum(w.deltas == 1)) >= 1
    bu = burst_schedule(seed=3, k=4, mtbf=8 * 3600.0, mttr=3600.0,
                        duration=DAY)
    assert set(np.unique(np.abs(bu.deltas))) <= {4}
    assert bu.max_concurrent() in (0, 4)   # bursts never overlap
    m = merge_schedules(a, bu, None)
    assert len(m) == len(a) + len(bu)
    assert np.all(np.diff(m.times) >= 0)


def test_lognormal_repairs_keep_the_mean_asked_for():
    """The lognormal repair draw (log-sigma given) has the mean repair
    time asked for, in both generators that take it."""
    mttr = 6 * 3600.0
    bu = burst_schedule(seed=5, k=3, mtbf=3600.0, mttr=mttr,
                        duration=20000 * 3600.0, repair="lognormal",
                        repair_sigma=1.0)
    down = bu.times[1::2] - bu.times[0::2]
    assert len(down) > 2000
    assert np.mean(down) == pytest.approx(mttr, rel=0.05)
    # Lognormal, not exponential: the log-times' spread is sigma.
    assert np.std(np.log(down)) == pytest.approx(1.0, rel=0.05)
    w = weibull_schedule(seed=6, n_nodes=1, mtbf=3600.0, mttr=mttr,
                         duration=20000 * 3600.0, shape=0.7,
                         repair="lognormal", repair_sigma=0.5)
    down = w.times[1::2] - w.times[0::2]
    assert len(down) > 2000
    assert np.mean(down) == pytest.approx(mttr, rel=0.05)
    with pytest.raises(ValueError, match="repair distribution"):
        burst_schedule(seed=5, k=3, mtbf=3600.0, mttr=mttr, duration=DAY,
                       repair="gamma")


def test_default_repairs_replay_the_exponential_draws():
    """The default repair stays exponential: schedules replay the same
    draws, in the same order, as the renewal written out here."""
    mtbf, mttr, shape = 6 * 3600.0, 1800.0, 1.5
    rng = np.random.Generator(np.random.PCG64(3))
    scale = mtbf / math.gamma(1.0 + 1.0 / shape)
    events = []
    for _ in range(8):
        t = scale * rng.weibull(shape)
        while t < DAY:
            events.append((t, 1))
            r = t + max(rng.exponential(mttr), 1e-6)
            events.append((r, -1))
            t = r + max(scale * rng.weibull(shape), 1e-6)
    events.sort(key=lambda e: e[0])
    w = weibull_schedule(seed=3, n_nodes=8, mtbf=mtbf, mttr=mttr,
                         duration=DAY, shape=shape)
    assert np.array_equal(w.times, [t for t, _ in events])
    assert np.array_equal(w.deltas, [d for _, d in events])


def test_schedule_clamp_matches_ledger():
    """clamp(C) must reproduce the Cluster.fail_nodes/repair_nodes
    recurrence: at most C down at once, repairs revive only
    actually-failed nodes."""
    s = FaultSchedule(np.array([1.0, 2.0, 3.0, 4.0, 5.0]),
                      np.array([6, 6, -6, -6, 2]))
    c = s.clamp(9)
    # +6 -> 6 down; +6 clamps to +3 (9 cap); -6 -> 3 down; -6 clamps
    # to -3; +2 -> 2 down.
    assert list(c.deltas) == [6, 3, -6, -3, 2]
    assert c.max_concurrent() == 9
    # A clamp that never binds is the identity.
    i = s.clamp(100)
    assert np.array_equal(i.times, s.times)
    assert np.array_equal(i.deltas, s.deltas)


# ------------------------------------------------- FB failure semantics

def test_fb_fail_absorption_order_and_shed():
    """Absorption order idle -> PBJ kill -> WS shed, and the §5.1
    priority invariant after every fault event:
    ws_alloc == min(raw demand, C - failed)."""
    sys_ = build_fb(4, lease_seconds=3600.0)
    jobs = [Job(jid=0, submit=0.0, size=2, runtime=DAY)]
    ws = [(0.0, 0), (100.0, 6)]
    sched = FaultSchedule(np.array([200.0, 300.0]), np.array([2, -2]))
    led = DecisionLedger()
    run_sim(sys_, jobs, ws, duration=1000.0, ledger=led, faults=sched)
    # t=100: demand 6 > C=4 -> 4 granted (killing the PBJ job's nodes
    # as needed), 2 shed. t=200: 2 nodes fail -> WS drained to 2, 2
    # more shed. t=300: repair -> WS refilled to 4 from idle.
    assert sys_.shed_count == 4
    assert led.sheds() == 4
    by_t = {e.t: e for e in led.entries if e.kind in ("ws", "fail",
                                                      "repair")}
    assert by_t[100.0].ws_nodes == 4 and by_t[100.0].shed == 2
    assert by_t[200.0].ws_nodes == 2 and by_t[200.0].shed == 2
    assert by_t[300.0].ws_nodes == 4 and by_t[300.0].shed == 0
    assert no_lost_jobs(jobs, sys_) == []


def test_fb_fail_uses_idle_before_killing():
    sys_ = build_fb(8, lease_seconds=3600.0)
    jobs = [Job(jid=0, submit=0.0, size=2, runtime=DAY)]
    sched = FaultSchedule(np.array([100.0]), np.array([4]))
    led = DecisionLedger()
    run_sim(sys_, jobs, [(0.0, 0)], duration=1000.0, ledger=led,
            faults=sched)
    # PBJ owns all 8 but only uses 2: the 4 dead nodes come from its
    # idle share — no kill.
    assert led.kills() == 0
    assert sys_.cluster.allocated("PBJ") == 4
    assert jobs[0].jid in sys_.pbj.running


def test_fb_checkpoint_restart_recovers_progress():
    """§5.1 kill path in checkpoint-preempt mode: a failure-killed job
    restarts from its checkpointed progress, so it still completes
    within a horizon that a from-scratch restart would overrun."""
    from repro.core.pbj_manager import PBJPolicyParams
    ckpt = PBJPolicyParams(checkpoint_preempt=True)
    jobs_k = [Job(jid=0, submit=0.0, size=4, runtime=6000.0)]
    jobs_c = clone_jobs(jobs_k)
    # Down in [4000, 7000); PBJ re-leases at the 7200 tick (repairs
    # refill WS immediately but PBJ regains nodes on lease boundaries).
    # From scratch that restart needs 6000s (ends 13200, past the
    # horizon); from the 4000s checkpoint it needs 2000s (ends 9200).
    sched = FaultSchedule(np.array([4000.0, 7000.0]), np.array([4, -4]))
    run_sim(build_fb(4, 3600.0), jobs_k, [(0.0, 0)], duration=12000.0,
            faults=sched)
    run_sim(build_fb(4, 3600.0, params=ckpt), jobs_c, [(0.0, 0)],
            duration=12000.0, faults=sched)
    assert not jobs_k[0].completed       # from-scratch restart too slow
    assert jobs_c[0].completed           # checkpointed remainder fits
    assert jobs_c[0].kills == 1


# -------------------------------------------- FLB-NUB failure semantics

def test_flb_pool_accounting_under_fail_and_repair():
    sys_ = build_flb_nub(4, 2, lease_seconds=3600.0)
    jobs = [Job(jid=0, submit=0.0, size=2, runtime=5 * 3600.0)]
    ws = [(0.0, 0), (100.0, 2)]
    sched = FaultSchedule(np.array([200.0, 400.0]), np.array([5, -5]))
    led = DecisionLedger()
    run_sim(sys_, jobs, ws, duration=DAY, ledger=led, faults=sched)
    ev = {e.t: e for e in led.entries}
    # t=200: 5 of the 6 pool nodes die. Absorption: pool idle (0),
    # then pool-PBJ (kills the job, 4 nodes), then the WS pool share —
    # which is immediately replaced by an elastic lease: WS never
    # sheds under FLB-NUB.
    assert ev[200.0].killed == 1 and ev[200.0].kind == "fail"
    assert ev[200.0].ws_nodes == 1        # 1 elastic beyond the pool
    assert ev[200.0].total_nodes == 1 + 1  # surviving pool + elastic
    assert led.sheds() == 0
    # t=400: repair. WS moves back onto pool nodes, elastic released.
    assert ev[400.0].ws_nodes == 0
    assert ev[400.0].total_nodes == 6     # full pool held again
    # The killed job re-leases via U/V/G at the next tick and finishes.
    assert jobs[0].completed
    assert no_lost_jobs(jobs, sys_) == []


# ------------------------------------------------ three-path differential

def _chaos_workload(seed=0, n=24, capacity=12, horizon=DAY):
    rng = np.random.Generator(np.random.PCG64(seed))
    jobs = [Job(jid=i, submit=float(rng.uniform(0, horizon * 0.7)),
                size=int(rng.integers(1, max(2, capacity // 3))),
                runtime=float(rng.uniform(600.0, horizon / 6)))
            for i in range(n)]
    ws = [(float(t), int(rng.integers(0, capacity // 2 + 2)))
          for t in np.sort(rng.uniform(0, horizon, 10))]
    return jobs, ws


def _chaos_schedule(capacity, horizon):
    return merge_schedules(
        exponential_schedule(seed=7, n_nodes=capacity // 2,
                             mtbf=5 * 3600.0, mttr=1800.0,
                             duration=horizon),
        burst_schedule(seed=11, k=max(1, capacity // 4),
                       mtbf=10 * 3600.0, mttr=3600.0,
                       duration=horizon))


def test_event_vs_rounds_fault_differential():
    """One schedule through both engines: node-hours/peak in the 2 %
    band, completions within ±2 jobs (CONTRACTS['faults'] — the same
    table the bench gate reads)."""
    from repro.sim.sweep import SweepPoint, run_sweep_workloads
    capacity, lease, horizon = 12, 3600.0, DAY
    jobs, ws = _chaos_workload(capacity=capacity, horizon=horizon)

    def rounds_row(faults=None):
        point = SweepPoint("fb", capacity=capacity, lease_seconds=lease)
        return run_sweep_workloads([point], [(jobs, ws)], horizon,
                                   mode="rounds", faults=[faults])[0][0]

    sched = _chaos_schedule(capacity, horizon)
    assert len(sched) > 4
    sys_ = build_fb(capacity, lease)
    ev_jobs = clone_jobs(jobs)
    ev = run_sim(sys_, ev_jobs, ws, duration=horizon, name="event",
                 faults=sched)
    rr = rounds_row(sched)
    assert rr["engine"] == "rounds"
    violations = FAULT_CONTRACT.check_row(rr, ev.row())
    assert violations == [], violations
    assert CONTRACTS["faults"] is FAULT_CONTRACT  # bench gate coupling
    assert no_lost_jobs(ev_jobs, sys_) == []
    # Degenerate schedule: faults=None must agree with the event engine
    # under the ordinary exact rounds semantics.
    ev0 = run_sim(build_fb(capacity, lease), clone_jobs(jobs), ws,
                  duration=horizon, name="event")
    rr0 = rounds_row()
    assert rr0["completed_jobs"] == ev0.completed_jobs
    # (float32 accumulation in the rounds kernel — not the fault band)
    assert rr0["node_hours"] == pytest.approx(ev0.node_hours, rel=1e-5)
    assert rr0["peak_nodes"] == ev0.peak_nodes


def test_live_vs_event_fault_ledger_identity():
    """The LiveCloud trace replay and the simulator share the pump: one
    fault schedule, two paths, identical ledgers entry for entry (the
    'completions exact event-vs-live' half of the chaos contract)."""
    from repro.core.pbj_manager import PBJPolicyParams
    from repro.core.runtime_bridge import LiveCloud
    capacity, lease, horizon = 12, 3600.0, DAY
    jobs, ws = _chaos_workload(seed=1, capacity=capacity,
                               horizon=horizon)
    sched = _chaos_schedule(capacity, horizon)
    sim_led = DecisionLedger()
    sim_jobs = clone_jobs(jobs)
    run_sim(build_fb(capacity, lease,
                     params=PBJPolicyParams(checkpoint_preempt=True)),
            sim_jobs, ws, duration=horizon, ledger=sim_led, faults=sched)
    d0 = max((int(d) for t, d in ws if t <= 0), default=0)
    cloud = LiveCloud(capacity, lease_seconds=lease, duration=horizon,
                      ws_initial=d0)
    live_jobs = clone_jobs(jobs)
    cloud.load_trace(live_jobs, ws_trace=ws, lease_ticks=True)
    cloud.inject_faults(sched)
    cloud.run_until(horizon)
    assert cloud.ledger.entries == sim_led.entries
    assert sum(j.completed for j in live_jobs) == \
        sum(j.completed for j in sim_jobs)
    assert cloud.ledger.kills("fail") > 0   # chaos actually engaged


# ------------------------------------- property: nothing is ever lost

def _run_invariant_case(seed):
    """No lost jobs + monotone checkpointed progress, FB and FLB-NUB."""
    from repro.core.pbj_manager import PBJPolicyParams
    capacity, horizon = 10, DAY
    jobs, ws = _chaos_workload(seed=seed, n=16, capacity=capacity,
                               horizon=horizon)
    rng = np.random.Generator(np.random.PCG64(seed + 99))
    sched = merge_schedules(
        exponential_schedule(seed=seed, n_nodes=capacity,
                             mtbf=float(rng.uniform(2, 8)) * 3600.0,
                             mttr=float(rng.uniform(0.2, 2)) * 3600.0,
                             duration=horizon),
        burst_schedule(seed=seed + 1, k=int(rng.integers(1, capacity)),
                       mtbf=8 * 3600.0, mttr=3600.0, duration=horizon))
    for build in (
            lambda: build_fb(capacity, 3600.0),
            lambda: build_fb(capacity, 3600.0, params=PBJPolicyParams(
                checkpoint_preempt=True)),
            lambda: build_flb_nub(capacity // 2, capacity // 2, 3600.0)):
        sys_ = build()
        progress = {}

        def watch(t, job, progress=progress):
            progress.setdefault(job.jid, []).append(job.progress)

        sys_.pbj.preempt_hooks.append(watch)
        run_jobs = clone_jobs(jobs)
        run_sim(sys_, run_jobs, ws, duration=horizon, faults=sched)
        assert no_lost_jobs(run_jobs, sys_) == [], (seed, type(sys_))
        ckpt = sys_.pbj.params.checkpoint_preempt
        for jid, seq in progress.items():
            if ckpt:
                # Checkpointed progress only ever accumulates across
                # restarts — a failure can never roll a job backwards.
                assert all(b >= a for a, b in zip(seq, seq[1:])), (
                    seed, jid, seq)
            else:
                assert all(p == 0.0 for p in seq), (seed, jid, seq)


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2 ** 31 - 1))
    def test_no_lost_jobs_property(seed):
        _run_invariant_case(seed)
else:                                                  # pragma: no cover
    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
    def test_no_lost_jobs_property(seed):
        _run_invariant_case(seed)


# ------------------------------------------- serving-layer degradation

def test_grant_backoff_deterministic_and_bounded():
    from repro.serving.autoscaler import GrantBackoff
    a = GrantBackoff(base=30.0, max_delay=240.0, max_retries=5, seed=7)
    b = GrantBackoff(base=30.0, max_delay=240.0, max_retries=5, seed=7)
    da = [a.next_delay() for _ in range(7)]
    assert da == [b.next_delay() for _ in range(7)]
    # Exactly max_retries delays, then None (give up until demand moves).
    assert sum(d is not None for d in da) == 5
    assert da[5] is None and da[6] is None
    for i, d in enumerate(da[:5]):
        cap = min(30.0 * 2 ** i, 240.0)
        assert cap / 2 < d <= cap        # equal-jitter window, capped
    a.reset()
    assert a.next_delay() is not None
    with pytest.raises(ValueError):
        GrantBackoff(base=0.0)
    with pytest.raises(ValueError):
        GrantBackoff(base=10.0, max_delay=5.0)


def test_admission_throttle_sheds_and_counts():
    from repro.serving.autoscaler import AutoscaledService
    from repro.serving.engine import Request, VirtualReplica
    from repro.core.ws_manager import InstanceAdjustmentPolicy
    svc = AutoscaledService(
        policy=InstanceAdjustmentPolicy(initial_instances=1,
                                        min_instances=1,
                                        nodes_per_instance=1),
        slots_per_replica=2, max_queue=2,
        replica_factory=lambda: VirtualReplica(2))
    admitted = sum(
        svc.submit(Request(rid=i, prompt=np.zeros(2, np.int32),
                           max_new_tokens=2), now=0.0)
        for i in range(5))
    assert admitted == 2
    assert svc.shed_requests == 3
    assert len(svc.queue) == 2


def test_replay_with_faults_backs_off_and_recovers():
    """A full-capacity outage mid-replay: the autoscaler's grants come
    back short, the driver retries on the bounded backoff instead of
    every serve tick, and service recovers after the repair."""
    from repro.serving.replay import replay
    horizon = 6 * 3600.0
    ws = [(0.0, 2), (600.0, 4)]
    sched = FaultSchedule(np.array([3600.0, 3600.0 + 1800.0]),
                          np.array([6, -6]))
    res = replay([], ws, capacity=6, duration=horizon, serve_dt=60.0,
                 lease_seconds=1800.0, faults=sched, max_queue=512)
    assert res.grant_retries >= 1
    assert res.ledger.sheds() > 0          # the outage shed WS demand
    assert res.requests_completed > 0      # ...and service recovered
    # After the repair the provision service can satisfy the trace
    # demand again: the last derived-demand grant is fully allocated.
    assert res.row.peak_nodes <= 6


# --------------------------------------------------- torn checkpoints

def test_torn_checkpoint_skip_and_restore(tmp_path):
    from repro.train.checkpoint import Checkpointer, TornCheckpointError
    tree = {"w": np.arange(6, dtype=np.float32),
            "b": np.ones(3, dtype=np.float32)}
    ck = Checkpointer(str(tmp_path), keep=5)
    ck.save(1, tree, metadata={"step": 1})
    ck.save(2, {"w": tree["w"] * 2, "b": tree["b"] * 2},
            metadata={"step": 2})
    # Tear step 2: flip a leaf's bytes (CRC mismatch).
    leaf = os.path.join(str(tmp_path), "step_2", "leaf_0.npy")
    arr = np.load(leaf)
    np.save(leaf, arr + 1.0)
    with pytest.raises(TornCheckpointError):
        ck.restore(2, tree)
    # restore_latest skips the torn step and lands on the intact one.
    with pytest.warns(UserWarning, match="torn checkpoint"):
        restored, meta, step = ck.restore_latest(tree)
    assert step == 1 and meta["step"] == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]), tree["w"])
    # Tear step 1's manifest too: nothing restorable left.
    with open(os.path.join(str(tmp_path), "step_1",
                           "manifest.msgpack"), "wb") as f:
        f.write(b"\xc1garbage")
    with pytest.warns(UserWarning, match="torn checkpoint"):
        assert ck.restore_latest(tree) is None
    # verify=False still refuses structurally torn steps (missing blob).
    os.remove(leaf)
    with pytest.raises(TornCheckpointError):
        ck.restore(2, tree, verify=False)
