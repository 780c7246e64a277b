"""Cross-engine differential harness (in the spirit of the paper's
EC2+RightScale comparison methodology and the earlier PhoenixCloud
consolidation study, arXiv:0906.1346).

One shared scenario generator drives random PBJ/WS traces and sweep
points through the per-point discrete-event reference and the batched
event-round engine, and asserts the rounds fidelity contract from
``repro.sim.contracts`` (the same table the CI bench gate imports, so
the gate and these tests cannot drift apart).

Layout:

* seeded random differentials always run (the container has no
  mandatory hypothesis dependency);
* a hypothesis-driven differential runs when hypothesis is installed,
  reusing the identical checker;
* the all-contended drain pins a crafted trace where the queue drains
  one generation of simultaneous completions at a time: completion
  times bit-exact, one round per generation;
* a unit test pins that the bench gate (`benchmarks.run
  .rounds_contract_ok`) actually reads the contract table.

Scenario shapes are FIXED per-axis (job count, WS change count,
horizon, windows) so every seed reuses one compiled program.
"""

import random

import numpy as np
import pytest

from repro.core.jobs import Job
from repro.sim.contracts import (CONTRACTS, FAULT_CONTRACT,
                                 LIVE_CONTRACT, ROUNDS_CONTRACT,
                                 check_fidelity)
from repro.sim.sweep import ScanOptions, SweepPoint, run_sweep

pytestmark = pytest.mark.tier1

DAY = 24 * 3600.0
N_JOBS = 36          # fixed -> shared RoundsSpec.max_rounds -> one compile
N_WS_STEPS = 24      # fixed -> shared round budget across seeds
WINDOW = 48          # >= N_JOBS: no backlog can outgrow the lanes

POINTS = [SweepPoint("fb", capacity=16),
          SweepPoint("fb", capacity=24),
          SweepPoint("flb_nub", lb_pbj=6, lb_ws=4),
          SweepPoint("flb_nub", lb_pbj=13, lb_ws=12)]


def scenario(seed: int):
    """Random queue-provoking workload of a FIXED shape: bursty
    arrivals against small capacities, a stepping WS demand trace
    (rises included, so FB reclaims and kills are exercised)."""
    rng = random.Random(seed)
    jobs = [Job(i, rng.uniform(0.0, 16 * 3600.0),
                size=2 ** rng.randrange(0, 4),
                runtime=rng.uniform(600.0, 3 * 3600.0))
            for i in range(N_JOBS)]
    ws = [(k * 3600.0, rng.randrange(0, 9)) for k in range(N_WS_STEPS)]
    return jobs, ws


def run_engines(jobs, ws):
    """The shared fixture core: one scenario through the event reference
    and the event-round engine. Returns ``{engine_name: rows}`` aligned
    with POINTS."""
    return {
        "event": run_sweep(POINTS, jobs, ws, DAY, mode="event"),
        "rounds": run_sweep(POINTS, jobs, ws, DAY, mode="rounds",
                            scan_options=ScanOptions(window=WINDOW)),
    }


def assert_contracts(engines: dict, label) -> None:
    """The rounds fidelity contract against the event reference — the
    assertions AND the bench gate read repro.sim.contracts.

    One carve-out, inherited from tests/test_rounds.py: the FLB-NUB
    bands are paper-grid contracts (gated for real by the sweep
    benchmark's --check-fidelity on the Fig. 13/14/18 grids). On
    adversarial random microtraces — WS demand repeatedly crossing a
    tiny lb_ws — the U/V/G feedback's policy approximation can
    overshoot them, so the random differential holds FLB-NUB to DOUBLE
    the node-hours band (still a tight differential against real
    divergence) and leaves its peak to the paper grids, while FB stays
    at the full contract (its peak is exact by construction) and
    completed-job exactness stays absolute everywhere."""
    import dataclasses

    ev = engines["event"]
    rows = engines["rounds"]
    for r in rows:
        assert r["window_overflow"] == 0, (label, r["system"])
        assert r.get("truncated", 0) == 0, (label, r["system"])
    violations = []
    for r, e in zip(rows, ev):
        c = CONTRACTS[r["engine"]]
        if r["system"].startswith("FLB-NUB"):
            c = dataclasses.replace(
                c, node_hours_rel=2 * c.node_hours_rel,
                peak_rel=float("inf"))
        violations += [f"{r['system']}: {v}" for v in c.check_row(r, e)]
    assert not violations, (label, violations)
    # The rounds engine promises exact completion counts — assert the
    # integer equality directly (not via the drift machinery).
    for r, e in zip(rows, ev):
        assert r["completed_jobs"] == e["completed_jobs"], (
            label, r["system"])


@pytest.mark.parametrize("seed", range(4))
def test_differential_random_traces(seed):
    jobs, ws = scenario(seed)
    assert_contracts(run_engines(jobs, ws), seed)


def test_differential_completion_times_bit_match_in_float64():
    """The rounds engine's stronger promise: with float64 lanes and
    enough first-fit passes the completion *times* (through the
    turnaround/execution sums) match the event engine to round-off.
    The WS trace is flat: demand rises trigger §5.1 kills, whose
    size-class tie-breaking is the one documented divergence from the
    engine's latest-start order (the same precondition as
    tests/test_rounds.py's exactness property)."""
    import jax

    jobs, _ = scenario(97)
    ws = [(0.0, 3)]
    ev = run_sweep(POINTS, jobs, ws, DAY, mode="event")
    with jax.enable_x64(True):
        rows = run_sweep(
            POINTS, jobs, ws, DAY, mode="rounds",
            scan_options=ScanOptions(window=WINDOW, ff_passes=8,
                                     dtype=np.float64))
    for r, e in zip(rows, ev):
        assert r["completed_jobs"] == e["completed_jobs"], r["system"]
        assert r["avg_turnaround"] == pytest.approx(
            e["avg_turnaround"], rel=1e-9), r["system"]
        assert r["avg_execution"] == pytest.approx(
            e["avg_execution"], rel=1e-9), r["system"]


try:
    import hypothesis
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2 ** 31 - 1))
    def test_differential_hypothesis(seed):
        """Hypothesis drives the same differential checker over the
        seeded generator (the scenario shape stays fixed, so every
        example reuses the compiled engines)."""
        jobs, ws = scenario(seed)
        assert_contracts(run_engines(jobs, ws), seed)


# ------------------------------------------------ all-contended drain

def crafted_all_contended():
    """The crafted all-contended trace: C nodes, >C equal unit jobs all
    submitted at t=0, flat WS, a lease longer than the horizon — the
    queue drains one GENERATION of C simultaneous completions at a time
    with no reactable WS change, submit or lease boundary in between."""
    C, gens, rt = 16, 6, 1000.0
    jobs = [Job(i, 0.0, size=1, runtime=rt) for i in range(C * gens)]
    ws = [(0.0, 0)]
    duration = gens * rt + 500.0
    point = SweepPoint("fb", capacity=C, lease_seconds=10 * duration)
    return jobs, ws, duration, point, C, gens, rt


def test_all_contended_drain_completes_exactly():
    """Each round of the drain completes a whole generation and starts
    the next one from the queue head: per-job completion times
    reproduce the event engine bit-exactly (generation k completes at
    exactly k*rt), and the drain takes one round per generation: the
    last folds its completions on the way to the horizon."""
    from repro.sim.engine import build_fb, clone_jobs, run_sim

    jobs, ws, duration, point, C, gens, rt = crafted_all_contended()
    ref = run_sim(build_fb(C, point.lease_seconds), clone_jobs(jobs), ws,
                  duration)
    assert ref.completed_jobs == C * gens
    assert ref.avg_execution == rt          # every generation runs rt

    row = run_sweep([point], jobs, ws, duration, mode="rounds",
                    scan_options=ScanOptions(window=128))[0]
    assert row["window_overflow"] == 0 and row["truncated"] == 0
    assert row["completed_jobs"] == C * gens
    # Bit-exact per-job times: generation k completes at k*rt, so the
    # turnaround mean is exactly rt * (1 + ... + gens) / gens.
    assert row["avg_turnaround"] == rt * (gens + 1) / 2.0
    assert row["avg_execution"] == rt
    # The time integrals accumulate in the lane dtype (float32 by
    # default) — equality up to its round-off, not bit-for-bit.
    assert row["node_hours"] == pytest.approx(ref.node_hours, rel=1e-6)
    assert row["peak_nodes"] == ref.peak_nodes == C
    assert row["rounds"] == gens


# --------------------------------------- bench gate <-> contract table

def test_bench_gate_uses_the_contract_table():
    """The CI gate in benchmarks/run.py must read its thresholds from
    repro.sim.contracts: the gate flips exactly at the table's
    node-hours and peak bounds, and hard-fails on inexact job counts,
    truncation, donation warnings and sharded mismatches."""
    from benchmarks.run import rounds_contract_ok

    def fid(**kw):
        base = dict(completed_jobs_exact=True,
                    max_drift_node_hours=0.0, max_drift_peak=0.0,
                    truncated_lanes=0)
        base.update(kw)
        return base

    assert rounds_contract_ok(fid(), [], True)
    # Flips exactly at the table's thresholds (no hardcoded copies).
    nh = ROUNDS_CONTRACT.node_hours_rel
    pk = ROUNDS_CONTRACT.peak_rel
    assert rounds_contract_ok(fid(max_drift_node_hours=nh), [], True)
    assert not rounds_contract_ok(
        fid(max_drift_node_hours=nh + 1e-9), [], True)
    assert rounds_contract_ok(fid(max_drift_peak=pk), [], True)
    assert not rounds_contract_ok(fid(max_drift_peak=pk + 1e-9), [],
                                  True)
    assert not rounds_contract_ok(fid(completed_jobs_exact=False), [],
                                  True)
    assert not rounds_contract_ok(fid(truncated_lanes=1), [], True)
    assert not rounds_contract_ok(fid(), ["donated buffer reused"], True)
    assert not rounds_contract_ok(fid(), [], False)


def test_contract_table_values():
    """The documented bands: rounds exact/5 %/5 %,
    live exact/10 %/10 % plus the 25 % demand-drift bounds, faults
    ±2-jobs-or-2 %/2 %/2 %, queries' §6 headline bands 40–55 %/28–45 %
    (pinned value-by-value in test_capacity.py). A change here is a
    contract change — update README and the bench note in the same
    commit."""
    assert ROUNDS_CONTRACT.completed_exact
    assert ROUNDS_CONTRACT.node_hours_rel == 0.05
    assert ROUNDS_CONTRACT.peak_rel == 0.05
    assert LIVE_CONTRACT.completed_exact
    assert LIVE_CONTRACT.node_hours_rel == 0.10
    assert LIVE_CONTRACT.peak_rel == 0.10
    assert LIVE_CONTRACT.demand_mae_rel == 0.25
    assert LIVE_CONTRACT.demand_peak_rel == 0.25
    assert not FAULT_CONTRACT.completed_exact
    assert FAULT_CONTRACT.completed_abs == 2
    assert FAULT_CONTRACT.completed_rel == 0.02
    assert FAULT_CONTRACT.node_hours_rel == 0.02
    assert FAULT_CONTRACT.peak_rel == 0.02
    assert set(CONTRACTS) == {"rounds", "vectorized", "live", "faults",
                              "queries"}


def test_check_fidelity_flags_violations():
    ev = [{"system": "FB(C=1)", "engine": "event", "completed_jobs": 100,
           "node_hours": 100.0, "peak_nodes": 10}]
    good = [dict(ev[0], engine="rounds")]
    assert check_fidelity(good, ev) == []
    bad = [dict(ev[0], engine="rounds", completed_jobs=99)]
    assert any("completed_jobs" in v for v in check_fidelity(bad, ev))
    drifted = [dict(ev[0], engine="rounds", node_hours=106.0)]
    assert any("node_hours" in v for v in check_fidelity(drifted, ev))
    ok_rounds = [dict(ev[0], engine="rounds", node_hours=104.0)]
    assert check_fidelity(ok_rounds, ev) == []
