"""On-device scenario synthesis (repro.sim.scenarios) + the batched WS
fold tables (repro.sim.rounds.ws_fold_tables_batch).

The generator family's contract has three legs, each pinned here:

* determinism & batching — every lane is a pure function of its PRNG
  key, and K vmapped lanes bit-match K single-key calls. The bit-match
  holds between JITTED programs (the vmapped batch is always jitted);
  an eager single call may reassociate float ops and drift by an ulp,
  which is exactly why the property is stated under jit.
* moments — the paper-trace parameter points reproduce the TraceSpec
  targets: utilization pinned exactly by the rescale, job counts exact,
  runtime means inside the bands the numpy generators realize, WS peak
  exactly the spec's integer peak.
* fold tables — the batched (W, P) build is elementwise EQUAL to the
  per-point reference loop, and the per-workload lru cache in front of
  ``pack_event_workloads`` serves repeated packs from memory.
"""

import functools

import numpy as np
import pytest

pytestmark = pytest.mark.tier1

import jax

from repro.sim import scenarios as sc
from repro.sim.rounds import (_ws_fold_tables_ref, fold_table_cache_clear,
                              fold_table_cache_info, pack_event_workloads,
                              ws_fold_tables_batch)

DAY = 24 * 3600.0


def small_grid(n=4, duration=2 * DAY, max_jobs=200):
    return sc.ScenarioGrid(
        seeds=tuple(range(n)),
        pbj=sc.PBJParams(nodes=64.0, utilization=0.5,
                         n_jobs=float(max_jobs - 50)),
        ws=sc.WSParams(peak=32.0),
        duration=duration, max_jobs=max_jobs)


# ------------------------------------------------- determinism & batching

def test_synthesize_deterministic_per_key():
    grid = small_grid()
    a, b = sc.synthesize(grid), sc.synthesize(grid)
    for f in ("submit", "size", "runtime", "n_jobs", "ws_values"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    # A different seed tuple is a different batch.
    c = sc.synthesize(sc.ScenarioGrid(
        seeds=(7, 8, 9, 10), pbj=grid.pbj, ws=grid.ws,
        duration=grid.duration, max_jobs=grid.max_jobs))
    assert not np.array_equal(a.submit, c.submit)


def test_vmapped_lanes_bitmatch_single_key_calls():
    """K vmapped lanes == K jitted single-key generator calls, bit for
    bit — the property that makes wide generated grids trustworthy
    stand-ins for one-at-a-time synthesis. Both sides must see the same
    program: jitted, with float32 param ARRAYS (the production
    ``_synth_batch`` configuration) — closing over Python-float params
    instead lets XLA constant-fold the arrival CDF into a different op
    order and drift submit times by an ulp."""
    import dataclasses

    seeds = (3, 11, 42)
    keys = sc.lane_keys(seeds)
    params = sc._broadcast_params(
        sc.PBJParams(nodes=64.0, utilization=0.5, n_jobs=150.0), 3)
    wsp = sc._broadcast_params(sc.WSParams(peak=32.0), 3)
    kw = dict(max_jobs=200, duration=2 * DAY)
    batch = jax.jit(jax.vmap(lambda k, p: sc.synth_pbj(k, p, **kw)))(
        keys[:, 0], params)
    single = jax.jit(lambda k, p: sc.synth_pbj(k, p, **kw))
    ws_batch = jax.jit(jax.vmap(lambda k, p: sc.synth_ws(k, p,
                                                         n_steps=96)))(
        keys[:, 1], wsp)
    ws_single = jax.jit(lambda k, p: sc.synth_ws(k, p, n_steps=96))

    def lane(pytree, w):
        return type(pytree)(**{
            f.name: np.asarray(getattr(pytree, f.name))[w]
            for f in dataclasses.fields(pytree)})

    for w, s in enumerate(seeds):
        k0, k1 = jax.random.split(jax.random.PRNGKey(s))
        sub, size, rt, nj = single(k0, lane(params, w))
        assert np.array_equal(np.asarray(batch[0][w]), np.asarray(sub))
        assert np.array_equal(np.asarray(batch[1][w]), np.asarray(size))
        assert np.array_equal(np.asarray(batch[2][w]), np.asarray(rt))
        assert int(batch[3][w]) == int(nj)
        assert np.array_equal(np.asarray(ws_batch[w]),
                              np.asarray(ws_single(k1, lane(wsp, w))))


def test_param_broadcast_and_per_lane_axes():
    grid = sc.ScenarioGrid(
        seeds=(0, 1, 2),
        pbj=sc.PBJParams(nodes=64.0, n_jobs=100.0,
                         utilization=np.array([0.3, 0.5, 0.7])),
        ws=sc.WSParams(peak=np.array([16.0, 32.0, 64.0])),
        duration=2 * DAY, max_jobs=150)
    s = sc.synthesize(grid)
    util = np.array([(s.size[w] * s.runtime[w]).sum()
                     for w in range(3)]) / (64.0 * 2 * DAY)
    assert np.allclose(util, [0.3, 0.5, 0.7], atol=1e-3)
    assert list(s.ws_values.max(axis=1)) == [16.0, 32.0, 64.0]
    with pytest.raises(ValueError, match="leading dim"):
        sc.synthesize(sc.ScenarioGrid(
            seeds=(0, 1, 2),
            pbj=sc.PBJParams(utilization=np.array([0.3, 0.5])),
            duration=2 * DAY, max_jobs=150))


# ------------------------------------------------------ moment matching

@pytest.mark.parametrize("point,nodes,util,n_jobs,rt_band", [
    (sc.NASA_IPSC_PBJ, 128, 0.466, 2603, (400.0, 700.0)),
    (sc.SDSC_BLUE_PBJ, 144, 0.762, 2657, (1500.0, 2500.0)),
])
def test_pbj_paper_points_match_trace_moments(point, nodes, util, n_jobs,
                                              rt_band):
    grid = sc.ScenarioGrid(seeds=(0,), pbj=point)
    s = sc.synthesize(grid)
    n = int(s.n_jobs[0])
    assert n == n_jobs                                  # count exact
    size, rt = s.size[0][:n], s.runtime[0][:n]
    sub = s.submit[0]
    assert np.all(np.diff(sub[:n]) >= 0)                # arrival sorted
    assert np.all(np.isinf(sub[n:]))                    # pad convention
    assert np.all((size >= 1) & (size <= nodes))
    assert np.all(np.log2(size) == np.round(np.log2(size)))
    realized = float((size * rt).sum()) / (nodes * sc.TWO_WEEKS)
    assert realized == pytest.approx(util, abs=1e-3)    # pinned by rescale
    assert rt_band[0] < rt.mean() < rt_band[1]
    assert rt.min() >= 1.0


def test_ws_paper_point_matches_worldcup_moments():
    s = sc.synthesize(sc.ScenarioGrid(seeds=(0, 1), ws=sc.WORLDCUP_WS,
                                      max_jobs=100,
                                      pbj=sc.PBJParams(n_jobs=50.0)))
    v = s.ws_values
    assert np.all(v.max(axis=1) == 64.0)                # peak exact
    assert v.min() >= 1.0                               # 1-VM floor
    assert np.all(v == np.round(v))                     # integer demands
    changes = (v[:, 1:] != v[:, :-1]).sum(axis=1)
    assert np.all(changes > 500)                        # a live series


# ------------------------------------------------------ fold-table batch

def _random_fold_case(rng, W):
    n = rng.integers(5, 60)
    times = np.concatenate([[0.0], np.sort(rng.uniform(
        0.0, 4000.0, n - 1))])
    values = rng.integers(0, 30, (W, n)).astype(np.float64)
    duration = float(rng.uniform(3000.0, 5000.0))
    P = int(rng.integers(1, 5))
    leases = rng.uniform(200.0, 2000.0, P)
    levels = rng.integers(1, 25, P).astype(np.float64)
    return times[times < duration], values, duration, leases, levels


def test_fold_batch_equals_reference_loop():
    """The batched (W, P) build is elementwise EQUAL (not close) to the
    per-point reference loop — integral, window max and boundary gather
    alike — across random lease/level grids and both policies."""
    rng = np.random.default_rng(0)
    for trial in range(25):
        times, values, duration, leases, levels = _random_fold_case(
            rng, W=1)
        values = values[:, :len(times)]
        for policy in ("fb", "flb_nub"):
            ref = _ws_fold_tables_ref(times, values[0], duration, policy,
                                      leases, levels)
            got = ws_fold_tables_batch(times, values[0], duration, policy,
                                       leases, levels)
            for r, g, name in zip(ref, got, ("integral", "winmax",
                                             "at_tick")):
                assert np.array_equal(r, g[0]), (trial, policy, name)


def test_fold_batch_multi_lane_equals_per_lane():
    rng = np.random.default_rng(7)
    times, values, duration, leases, levels = _random_fold_case(rng, W=6)
    values = values[:, :len(times)]
    for policy in ("fb", "flb_nub"):
        integral, winmax, at_tick = ws_fold_tables_batch(
            times, values, duration, policy, leases, levels)
        for w in range(6):
            ref = _ws_fold_tables_ref(times, values[w], duration, policy,
                                      leases, levels)
            assert np.array_equal(ref[0], integral[w])
            assert np.array_equal(ref[1], winmax[w])
            assert np.array_equal(ref[2], at_tick[w])


def test_fold_table_cache_hits_on_repeat_pack():
    """Re-packing the same workload (what the differential harness and
    the multi-engine benchmark do once per engine column) must serve
    the fold tables from the lru cache."""
    s = sc.synthesize(small_grid(n=2))
    workloads = sc.sample_workloads(s, [0, 1])
    fold_table_cache_clear()
    pack_event_workloads(workloads, s.duration, 16, "fb",
                         [3600.0], [48.0])
    info = fold_table_cache_info()
    assert info.misses == 2 and info.hits == 0
    pack_event_workloads(workloads, s.duration, 16, "fb",
                         [3600.0], [48.0])
    info = fold_table_cache_info()
    assert info.misses == 2 and info.hits == 2
    # A different level grid is a different key, not a stale hit.
    pack_event_workloads(workloads, s.duration, 16, "fb",
                         [3600.0], [64.0])
    assert fold_table_cache_info().misses == 4


# ------------------------------------------------------- batch plumbing

def test_sample_workloads_round_trips_the_batch():
    s = sc.synthesize(small_grid(n=3))
    for w, (jobs, trace) in enumerate(sc.sample_workloads(s, [0, 1, 2])):
        assert len(jobs) == int(s.n_jobs[w])
        assert jobs[0].submit == float(s.submit[w, 0])
        assert [j.size for j in jobs[:5]] == list(s.size[w, :5])
        # The step trace re-realizes the dense series exactly.
        t = np.asarray([p[0] for p in trace])
        v = np.asarray([p[1] for p in trace], np.float64)
        idx = np.searchsorted(t, s.ws_times, "right") - 1
        assert np.array_equal(v[idx], s.ws_values[w])


def test_pack_scenarios_matches_pack_event_workloads():
    """The all-array pack path produces the same fold tables and rise
    stops as the host-loop pack of the sampled lanes."""
    s = sc.synthesize(small_grid(n=3))
    workloads = sc.sample_workloads(s, [0, 1, 2])
    a = sc.pack_scenarios(s, window=16, policy="fb", leases=[3600.0],
                          levels=[48.0])
    b = pack_event_workloads(workloads, s.duration, 16, "fb",
                             [3600.0], [48.0])
    assert np.array_equal(np.asarray(a.ws_integral),
                          np.asarray(b.ws_integral))
    assert np.array_equal(np.asarray(a.ws_winmax),
                          np.asarray(b.ws_winmax))
    assert np.array_equal(np.asarray(a.ws_at_tick),
                          np.asarray(b.ws_at_tick))
    assert np.array_equal(np.asarray(a.ws0), np.asarray(b.ws0))
    assert np.array_equal(np.asarray(a.ws_adjusts),
                          np.asarray(b.ws_adjusts))
    assert np.array_equal(np.asarray(a.n_jobs), np.asarray(b.n_jobs))
    # Rise stops agree once both are filtered to the real (finite) ones.
    ra = np.asarray(a.rise_times)
    rb = np.asarray(b.rise_times)
    for w in range(3):
        assert np.array_equal(ra[w][np.isfinite(ra[w])],
                              rb[w][np.isfinite(rb[w])])


def test_traces_module_forwards_scenario_names():
    from repro.sim import traces
    assert traces.synth_pbj is sc.synth_pbj
    assert traces.NASA_IPSC_PBJ is sc.NASA_IPSC_PBJ
    with pytest.raises(AttributeError):
        traces.not_a_scenario_name


# ------------------------------------------- end-to-end generated sweep

@pytest.mark.slow
def test_generated_sweep_matches_event_engine_on_sampled_lanes():
    """A generated ScenarioGrid through ``run_sweep_workloads`` on the
    rounds engine, with sampled lanes re-run on the event engine and
    held to the rounds contract (completed exact, node-hours/peak
    within 5 %) — the PR 5 differential harness over generated lanes."""
    from repro.sim.contracts import CONTRACTS
    from repro.sim.sweep import SweepPoint, run_sweep_workloads

    grid = sc.ScenarioGrid(
        seeds=tuple(range(6)),
        pbj=sc.PBJParams(nodes=64.0, utilization=0.5, n_jobs=350.0),
        ws=sc.WSParams(peak=32.0),
        duration=2 * DAY, max_jobs=400)
    points = [SweepPoint("fb", capacity=48),
              SweepPoint("fb", capacity=64),
              SweepPoint("flb_nub", lb_pbj=6, lb_ws=4),
              SweepPoint("flb_nub", lb_pbj=13, lb_ws=12,
                         lease_seconds=1800.0)]
    rows = run_sweep_workloads(points, grid, mode="rounds")
    assert len(rows) == 6 and all(len(r) == len(points) for r in rows)

    sample = [0, 3, 5]
    synth = sc.synthesize(grid)
    ev_rows = run_sweep_workloads(points, sc.sample_workloads(
        synth, sample), grid.duration, mode="event")
    for j, w in enumerate(sample):
        for i in range(len(points)):
            violations = CONTRACTS["rounds"].check_row(rows[w][i],
                                                       ev_rows[j][i])
            assert not violations, (w, points[i].name(), violations)


def test_generated_sweep_rejects_bad_modes_and_duration():
    from repro.sim.sweep import SweepPoint, run_sweep_workloads
    grid = small_grid(n=2)
    points = [SweepPoint("fb", capacity=48)]
    with pytest.raises(ValueError, match="duration is fixed"):
        run_sweep_workloads(points, grid, 3 * DAY, mode="rounds")
    # "scan" names no engine: an unknown mode like any other.
    removed = "scan"
    with pytest.raises(ValueError, match="unknown mode"):
        run_sweep_workloads(points, grid, mode=removed)
    with pytest.raises(ValueError):
        run_sweep_workloads([SweepPoint("dcs", prc_pbj=32, prc_ws=32)],
                            grid, mode="rounds")
