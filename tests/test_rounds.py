"""Event-round engine (repro.sim.rounds) vs the discrete-event engine.

Jumping straight to event times makes completions exact (no substep
rounding), so on any workload the completed-job count must match the
event engine exactly and — with enough first-fit passes for the queue
to resolve the way the engine's sequential scan does — the completion
*times* must match too, not just within a tolerance. These tests pin
that, the §5.1 kill semantics on the designed spike scenario and the
window-overflow diagnostic (surfaced as a RuntimeWarning).
"""

import random
import warnings

import numpy as np
import pytest

pytestmark = pytest.mark.tier1

from repro.core.jobs import Job
from repro.sim.engine import build_fb, build_flb_nub, clone_jobs, run_sim
from repro.sim.sweep import ScanOptions, SweepPoint, run_sweep

DAY = 24 * 3600.0


def rounds_row(point, jobs, ws, duration, **opts):
    return run_sweep([point], jobs, ws, duration, mode="rounds",
                     scan_options=ScanOptions(**opts))[0]


def random_workload(seed, n_jobs=40, ws_level=2):
    """Queue-provoking random trace: bursty arrivals, constant low WS
    demand (no demand rises, so FB never kills and the §5.1 tie-order
    caveat cannot blur the exactness assertion)."""
    rng = random.Random(seed)
    jobs = [Job(i, rng.uniform(0.0, 16 * 3600.0),
                size=2 ** rng.randrange(0, 4),
                runtime=rng.uniform(600.0, 3 * 3600.0))
            for i in range(n_jobs)]
    ws = [(0.0, ws_level)]
    return jobs, ws


# ------------------------------------------------ exact completion times

@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("system", ["fb", "flb_nub"])
def test_rounds_completion_times_match_event_exactly(seed, system):
    """The event-round property: in float64, start times are event
    times and end times are the same float sum the engine computes, so
    completed jobs, turnaround and execution agree to round-off — not
    to a discretization tolerance. (ff_passes is raised so the
    vectorized first-fit provably converges to the engine's sequential
    scan on every round.)"""
    import jax
    jobs, ws = random_workload(seed)
    if system == "fb":
        point = SweepPoint("fb", capacity=12)
        ref_sys = build_fb(12)
    else:
        point = SweepPoint("flb_nub", lb_pbj=6, lb_ws=4)
        ref_sys = build_flb_nub(6, 4)
    ref = run_sim(ref_sys, clone_jobs(jobs), ws, DAY)
    with jax.enable_x64(True):
        row = rounds_row(point, jobs, ws, DAY, ff_passes=8,
                         dtype=np.float64)
    assert row["window_overflow"] == 0 and row["truncated"] == 0
    assert row["completed_jobs"] == ref.completed_jobs, (seed, system)
    assert row["avg_turnaround"] == pytest.approx(ref.avg_turnaround,
                                                  rel=1e-9), (seed, system)
    assert row["avg_execution"] == pytest.approx(ref.avg_execution,
                                                 rel=1e-9), (seed, system)
    assert row["kills"] == ref.kills == 0
    assert row["peak_nodes"] == ref.peak_nodes


@pytest.mark.parametrize("seed", range(4))
def test_rounds_fidelity_contract_on_random_traces(seed):
    """At the default (float32) settings the contract is: completed
    jobs exact, node-hours and peak within 5 % of the event engine."""
    rng = random.Random(100 + seed)
    jobs = [Job(i, rng.uniform(0.0, 12 * 3600.0),
                size=2 ** rng.randrange(0, 4),
                runtime=rng.uniform(900.0, 2 * 3600.0))
            for i in range(30)]
    ws = [(k * 900.0, rng.randrange(0, 13)) for k in range(0, 96, 2)]
    for point, ref_sys in (
            (SweepPoint("fb", capacity=16), build_fb(16)),
            (SweepPoint("flb_nub", lb_pbj=13, lb_ws=12),
             build_flb_nub(13, 12))):
        row = rounds_row(point, jobs, ws, DAY, window=32)
        ref = run_sim(ref_sys, clone_jobs(jobs), ws, DAY)
        assert row["window_overflow"] == 0 and row["truncated"] == 0
        assert row["completed_jobs"] == ref.completed_jobs, (seed, point)
        assert row["node_hours"] == pytest.approx(ref.node_hours,
                                                  rel=0.05), (seed, point)
        if point.system == "fb":
            # FB peak is exact by construction (the §5.1 ratchet makes
            # each lease window's max analytic). FLB-NUB peak carries
            # the shared U/V/G *policy* approximation on adversarial
            # small traces, so only the paper-grid contract (<= 5 %,
            # gated in the sweep benchmark) applies to it.
            assert row["peak_nodes"] == ref.peak_nodes, (seed, point)


# ------------------------------------------------------ §5.1 kill spike

def spike_workload():
    jobs = [Job(0, 0.0, size=4, runtime=2 * 3600.0),
            Job(1, 0.0, size=4, runtime=2 * 3600.0),
            Job(2, 0.0, size=2, runtime=1200.0)]
    ws = [(0.0, 0), (1800.0, 8), (2 * 3600.0, 0)]
    return jobs, ws


def test_rounds_fb_killed_jobs_reenter_and_finish():
    """The §5.1 demand spike: both size-4 jobs die and can only finish
    by re-queueing — the rounds engine reproduces kills, restarts and
    the exact completion count, with exact node-hours (the spike's
    reclaim happens at a demand-rise stop, not a rounded substep)."""
    jobs, ws = spike_workload()
    row = rounds_row(SweepPoint("fb", capacity=10), jobs, ws, 8 * 3600.0,
                     window=16)
    ref = run_sim(build_fb(10), clone_jobs(jobs), ws, 8 * 3600.0)
    assert ref.kills == 2
    assert row["kills"] == ref.kills
    assert row["completed_jobs"] == ref.completed_jobs == 3
    assert row["peak_nodes"] == ref.peak_nodes == 10
    assert row["node_hours"] == pytest.approx(ref.node_hours, rel=1e-5)


def test_rounds_killed_job_restarts_at_the_freeing_completion():
    """Regression: a §5.1 kill re-queues its job, and the very next
    completion that frees enough capacity must restart it AT that
    completion time (the event engine's behavior) — the queue flag and
    the usage carried between rounds must reflect the post-kill state,
    or the restart slips to the next tick."""
    jobs = [Job(0, 0.0, size=4, runtime=1200.0),       # killed at 500
            Job(1, 0.0, size=6, runtime=1000.0)]       # frees 6 at 1000
    ws = [(0.0, 0), (500.0, 4)]
    T = 3000.0      # next lease tick (3600) is beyond the horizon
    row = rounds_row(SweepPoint("fb", capacity=10), jobs, ws, T, window=8)
    ref = run_sim(build_fb(10), clone_jobs(jobs), ws, T)
    assert ref.kills == 1
    assert ref.completed_jobs == 2   # restart at 1000 + 1200 s < 3000 s
    assert row["kills"] == 1
    # Job 0 completes (at exactly 2200 s) only if it restarted at the
    # t=1000 completion; a restart deferred to the next stop would
    # leave it running at the horizon.
    assert row["completed_jobs"] == 2
    assert row["avg_turnaround"] == pytest.approx(ref.avg_turnaround,
                                                  rel=1e-5)
    assert row["node_hours"] == pytest.approx(ref.node_hours, rel=1e-5)
    assert row["peak_nodes"] == ref.peak_nodes


def test_rounds_fb_partial_kill():
    jobs, ws = spike_workload()
    ws = [(0.0, 0), (1800.0, 5), (2 * 3600.0, 0)]
    row = rounds_row(SweepPoint("fb", capacity=10), jobs, ws, 8 * 3600.0,
                     window=16)
    ref = run_sim(build_fb(10), clone_jobs(jobs), ws, 8 * 3600.0)
    assert ref.kills == 1
    assert row["kills"] == 1
    assert row["completed_jobs"] == ref.completed_jobs == 3


# ------------------------------------------------- diagnostics surface

def test_rounds_window_overflow_warns():
    """A window too small for the backlog must not fail silently: the
    rows carry ``window_overflow`` and run_sweep emits a
    RuntimeWarning."""
    rng = random.Random(7)
    jobs = [Job(i, float(i), size=8, runtime=9 * 3600.0)
            for i in range(24)]          # 24 jobs, site fits 1 at a time
    ws = [(0.0, 0)]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        row = rounds_row(SweepPoint("fb", capacity=8), jobs, ws, DAY,
                         window=8)
    assert row["window_overflow"] > 0
    messages = [str(w.message) for w in caught
                if issubclass(w.category, RuntimeWarning)]
    assert any("backlog outgrew" in m for m in messages), messages


def test_rounds_rejects_checkpoint_preempt_and_auto_falls_back():
    from repro.core.pbj_manager import PBJPolicyParams

    jobs, ws = spike_workload()
    ckpt = SweepPoint("fb", capacity=8,
                      params=PBJPolicyParams(checkpoint_preempt=True))
    with pytest.raises(ValueError, match="checkpoint_preempt"):
        run_sweep([ckpt], jobs, ws, 7200.0, mode="rounds")
    # auto: the rejected point quietly takes the event engine, the rest
    # still batch through rounds.
    rows = run_sweep([ckpt, SweepPoint("fb", capacity=8)], jobs, ws,
                     7200.0, mode="auto")
    assert rows[0]["engine"] == "event"
    assert rows[1]["engine"] == "rounds"


def test_rounds_batches_trace_axis():
    """run_sweep_workloads in rounds mode: per-workload rows reflect
    their own trace (the workload axis is a vmap axis of one device
    call)."""
    from repro.sim.sweep import run_sweep_workloads

    jobs1, ws1 = random_workload(11)
    jobs2, ws2 = random_workload(12, n_jobs=25, ws_level=5)
    pts = [SweepPoint("fb", capacity=12),
           SweepPoint("flb_nub", lb_pbj=6, lb_ws=4)]
    rows = run_sweep_workloads(pts, [(jobs1, ws1), (jobs2, ws2)], DAY,
                               mode="rounds")
    assert len(rows) == 2 and all(len(r) == 2 for r in rows)
    for w, (jobs, ws) in enumerate([(jobs1, ws1), (jobs2, ws2)]):
        for i, (pt, ref_sys) in enumerate((
                (pts[0], build_fb(12)), (pts[1], build_flb_nub(6, 4)))):
            ref = run_sim(ref_sys if w + i else build_fb(12),
                          clone_jobs(jobs), ws, DAY)
            assert rows[w][i]["engine"] == "rounds"
            if i == 0 and w == 0:
                assert rows[w][i]["completed_jobs"] == ref.completed_jobs
    # The traces differ (40 vs 25 jobs), so per-workload job metrics
    # must too. (FB node-hours would NOT discriminate here: with flat
    # WS demand the §5.1 allocation is exactly C around the clock for
    # any job trace.)
    assert rows[0][0]["completed_jobs"] != rows[1][0]["completed_jobs"]
    assert rows[0][0]["avg_turnaround"] != rows[1][0]["avg_turnaround"]


def test_stacked_workloads_match_single_workload_calls():
    """Three workloads of different lengths run as ONE device call whose
    rows equal three single-workload calls, rounds included: lanes that
    finish early idle in lockstep and change nothing. (The workloads
    share their job and demand-point counts, so the three single calls
    share one compiled program.)"""
    from repro import spans
    from repro.sim.sweep import run_sweep_workloads

    def workload(seed, hours, peak):
        rng = random.Random(seed)
        jobs = [Job(i, rng.uniform(0.0, hours * 3600.0),
                    size=2 ** rng.randrange(0, 4),
                    runtime=rng.uniform(600.0, 3 * 3600.0))
                for i in range(24)]
        ws = [(0.0, 1), (0.3 * hours * 3600.0, peak),
              (0.6 * hours * 3600.0, 2)]
        return jobs, ws

    workloads = [workload(21, 16, 6), workload(22, 4, 3),
                 workload(23, 9, 5)]
    pts = [SweepPoint("fb", capacity=10), SweepPoint("fb", capacity=16),
           SweepPoint("flb_nub", lb_pbj=6, lb_ws=4),
           SweepPoint("flb_nub", lb_pbj=3, lb_ws=2)]
    stacked = run_sweep_workloads(pts, workloads, DAY, mode="rounds")
    root = spans.roots("sweep")[-1]
    dispatches = [r for r in spans.RECORDER.records
                  if r.root_id == root["id"] and r.name == "sweep.dispatch"]
    assert len(dispatches) == 1
    single = [run_sweep(pts, jobs, ws, DAY, mode="rounds")
              for jobs, ws in workloads]
    assert stacked == single
    # The workloads end at different depths, so some lanes idled.
    depths = {max(r["rounds"] for r in rows) for rows in stacked}
    assert len(depths) == 3


def test_round_budget_scales_with_inputs():
    from repro.sim.rounds import round_budget

    base = round_budget(100, 50, DAY, 3600.0)
    assert base > 100 + 50 + 24
    assert round_budget(200, 50, DAY, 3600.0) > base
    assert round_budget(100, 50, DAY, 900.0) > base   # more ticks


def test_compile_cache_honours_env_dir(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, the entry-point helper returns
    it and sets no cache directory in code."""
    import jax
    from repro import compat

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/env/cache")
    assert compat.enable_compile_cache() == "/env/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    """Without the variable the cache sits at one fixed, gitignored path
    inside the checkout — never a temp name, pid or time."""
    import os

    import jax
    from repro import compat

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compat.COMPILE_CACHE_DIR == os.path.join(root, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compat.enable_compile_cache() == compat.COMPILE_CACHE_DIR
        assert (jax.config.jax_compilation_cache_dir
                == compat.COMPILE_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
