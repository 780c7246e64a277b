"""One outer step of the rounds loop (``rounds._chunk_core``).

An outer step compacts the window (``lanes.stable_compact``), admits
the next job-table rows into the freed tail and runs ``compact_every``
event rounds. These tests drive that step directly from crafted loop
states — ``random`` mid-simulation, ``all_full`` (every slot running,
nothing done), ``all_empty`` (every slot a pad row) and
``overflow_edge`` (admission cursor at the table end, the
dynamic-slice clamp path) — for both policies and check:

* jobs are conserved: compaction keeps exactly the unfinished slots, in
  order, and admission appends exactly the next table rows; every slot
  the rounds finish is counted once; ``used`` is the size of the
  running slots; ``window_overflow`` grows exactly when a submitted job
  is still outside the window;
* lanes vmapped together give each lane's own result, as the engine
  batches them.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.sim import rounds as roundslib
from repro.sim import traces
from repro.sim.rounds import ACC_KEYS, RoundsSpec
from repro.sim.sweep import ScanOptions, SweepPoint

pytestmark = pytest.mark.tier1

DAY = 24 * 3600.0
K = 16          # small window → real compaction and admission
KINDS = ["random", "all_full", "all_empty", "overflow_edge"]
SPEC = RoundsSpec(duration=2 * DAY, max_rounds=4096, window=K)
LANES = 4
INTEGRALS = ("turn_sum", "exec_sum", "node_seconds")

# Loop-state slots (the 17-tuple ``_chunk_core`` carries).
T, USED, NEXT_ROW = 0, 3, 8
W_SUB, W_SZ, W_RT, RUN, DONE, START_T, END_T, ACC = range(9, 17)


def _lane(policy, seed=0):
    """One real packed lane of a two-day trace and its round inputs."""
    horizon = 2 * DAY
    jobs = [j for j in traces.nasa_ipsc(seed=seed) if j.submit < horizon]
    ws = [(t, d) for t, d in traces.worldcup98(seed=seed, peak_vms=64)
          if t < horizon]
    if policy == "fb":
        leases, levels = [3600.0], [24]
        prm = {"lease": 3600.0, "capacity": 24.0}
    else:
        leases, levels = [3600.0], [12]
        prm = {"lease": 3600.0, "B": 25.0, "lb_ws": 12.0, "U": 0.25,
               "V": 0.5, "G": 2.0}
    pk = jax.tree_util.tree_map(
        lambda a: a[0], roundslib.pack_event_workloads(
            [(jobs, ws)], horizon, K, policy, leases=leases,
            levels=levels))
    prm = {k: jnp.asarray(v, pk.submit.dtype) for k, v in prm.items()}
    prm["p_idx"] = jnp.asarray(0, jnp.int32)
    return pk, roundslib._lane_ctx(policy, prm, pk)


def _core(pk, kind, rng):
    """A loop state of the requested shape."""
    f = pk.submit.dtype
    zero = jnp.zeros((), f)
    Jp = int(pk.submit.shape[0])
    # Whole-number accumulators: a step's increments read back exactly.
    acc = {k: jnp.asarray(float(rng.integers(0, 50)), f)
           for k in ACC_KEYS}
    t = jnp.asarray(rng.uniform(0, DAY), f)
    w_sub = pk.submit[:K]
    w_sz, w_rt = pk.size[:K], pk.runtime[:K]
    if kind == "random":
        run = jnp.asarray(rng.random(K) < 0.4)
        done = jnp.asarray(rng.random(K) < 0.2) & ~run
        next_row = jnp.asarray(K + 7, jnp.int32)
    elif kind == "all_full":
        run = jnp.ones(K, bool)
        done = jnp.zeros(K, bool)
        next_row = jnp.asarray(K, jnp.int32)
    elif kind == "all_empty":
        run = jnp.zeros(K, bool)
        done = jnp.ones(K, bool)      # whole window compacts away
        next_row = jnp.asarray(Jp, jnp.int32)
        w_sub = jnp.full(K, jnp.inf, f)
        w_sz = jnp.zeros(K, f)
        w_rt = jnp.zeros(K, f)
    else:                              # overflow_edge
        run = jnp.asarray(rng.random(K) < 0.5)
        done = ~run                    # max churn at the table end
        next_row = jnp.asarray(Jp, jnp.int32)
    start_t = jnp.where(run | done, jnp.maximum(w_sub, 0.0), zero)
    end_t = jnp.where(run | done, start_t + w_rt, zero)
    return (t, jnp.asarray(24.0, f), jnp.asarray(4.0, f),
            jnp.sum(jnp.where(run, w_sz, zero)),
            jnp.asarray(bool(rng.random() < 0.5)),
            pk.ws0, jnp.asarray(20.0, f), jnp.asarray(0, jnp.int32),
            next_row, w_sub, w_sz, w_rt, run, done, start_t, end_t, acc)


def _step(policy, ctx):
    return jax.jit(lambda core: roundslib._chunk_core(policy, ctx, SPEC,
                                                      core))


def _np(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


@pytest.mark.parametrize("policy", ["fb", "flb_nub"])
@pytest.mark.parametrize("kind", KINDS)
def test_chunk_step_conserves_jobs(policy, kind):
    pk, ctx = _lane(policy)
    core = _core(pk, kind, np.random.default_rng(3))
    before, after = _np(core), _np(_step(policy, ctx)(core))
    tr = {k: np.asarray(getattr(pk, k)) for k in ("submit", "size",
                                                  "runtime")}
    Jp = tr["submit"].shape[0]

    # Compaction keeps the unfinished slots in order; admission appends
    # the next table rows (the slice clamps into the +inf pad block).
    kept = ~before[DONE]
    n_keep = int(kept.sum())
    start = min(int(before[NEXT_ROW]), Jp - (K - n_keep)) \
        if n_keep < K else 0
    for slot, col in ((W_SUB, "submit"), (W_SZ, "size"),
                      (W_RT, "runtime")):
        want = np.concatenate([before[slot][kept],
                               tr[col][start:start + K - n_keep]])
        np.testing.assert_array_equal(after[slot], want, err_msg=col)
    next_row = min(int(before[NEXT_ROW]) + K - n_keep, Jp)
    assert int(after[NEXT_ROW]) == next_row
    # A pad row never runs, and a running slot is never also done.
    assert not (after[RUN] & after[DONE]).any()
    assert not (after[RUN] & np.isinf(after[W_SUB])).any()
    # Every slot the rounds finished is counted once, and no earlier
    # than its end time.
    acc0, acc1 = before[ACC], after[ACC]
    assert acc1["completed"] - acc0["completed"] == after[DONE].sum()
    assert (after[END_T][after[DONE]] <= after[T]).all()
    # ``used`` is the size of the running slots.
    assert after[USED] == np.sum(np.where(after[RUN], after[W_SZ], 0.0))
    # Overflow grows exactly when the next unadmitted row has submitted
    # by the step's end (the state starts inside the horizon).
    assert before[T] < SPEC.duration
    row_sub = tr["submit"][min(next_row, Jp - 1)]
    grew = acc1["window_overflow"] > acc0["window_overflow"]
    assert grew == (row_sub <= after[T]), (row_sub, after[T])
    if kind in ("all_empty", "overflow_edge"):
        assert not grew                # the table is exhausted


@pytest.mark.parametrize("policy", ["fb", "flb_nub"])
@pytest.mark.parametrize("kind", KINDS)
def test_vmapped_lanes_equal_each_lane_alone(policy, kind):
    """The lane axis the sweep batches over: each lane's step under
    ``vmap`` equals that lane's step run alone. Every slot, flag, time
    and count is bit-identical; the three time integrals (turnaround,
    execution and node-seconds sums) may round a ULP apart, since a
    batched reduction may sum in another order than a single lane's."""
    pk, ctx = _lane(policy)
    rng = np.random.default_rng(11)
    cores = [_core(pk, kind, rng) for _ in range(LANES)]
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *cores)
    batched = _np(jax.jit(jax.vmap(
        lambda c: roundslib._chunk_core(policy, ctx, SPEC, c)))(stacked))
    step = _step(policy, ctx)
    for i, core in enumerate(cores):
        alone = _np(step(core))
        lane = jax.tree_util.tree_map(lambda a: a[i], batched)
        for slot in range(ACC):
            assert alone[slot].dtype == lane[slot].dtype
            np.testing.assert_array_equal(alone[slot], lane[slot],
                                          err_msg=f"lane {i} slot {slot}")
        for k in ACC_KEYS:
            a, b = alone[ACC][k], lane[ACC][k]
            if k in INTEGRALS:
                np.testing.assert_allclose(a, b, rtol=1e-6, err_msg=k)
            else:
                np.testing.assert_array_equal(a, b, err_msg=k)


def test_warmup_sweep_is_clear_caches_safe():
    """The bench's compile-measurement helper: warming, clearing and
    re-warming returns identical rows (nothing stale survives a
    jax.clear_caches), and the warmed steady-state call still works."""
    from repro.sim.sweep import run_sweep_workloads, warmup_sweep

    horizon = 12 * 3600.0
    jobs = [j for j in traces.nasa_ipsc(seed=2) if j.submit < horizon]
    ws = [(t, d) for t, d in traces.worldcup98(seed=2, peak_vms=64)
          if t < horizon]
    pts = [SweepPoint("fb", capacity=24)]
    wls = [(jobs, ws)]
    opts = ScanOptions()
    wall = warmup_sweep(pts, wls, horizon, mode="rounds",
                        scan_options=opts)
    assert wall > 0
    rows1 = run_sweep_workloads(pts, wls, horizon, mode="rounds",
                                scan_options=opts)
    jax.clear_caches()
    warmup_sweep(pts, wls, horizon, mode="rounds", scan_options=opts)
    rows2 = run_sweep_workloads(pts, wls, horizon, mode="rounds",
                                scan_options=opts)
    assert rows1 == rows2
