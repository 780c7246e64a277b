"""Fused Pallas round-step kernel: interpret-mode bit-equality.

The kernel (``repro.kernels.round_step``) packs the rounds-engine loop
state into a scalar vector + window matrix and runs the shared
``rounds._chunk_core`` — compaction (``stable_compact``), job-table
admission, size classes and the unrolled event rounds built on
``fb_actions`` / ``flb_actions`` — as ONE ``pallas_call``. These tests
pin the two promises the ``kernel="pallas"`` backend rests on:

* the state pack round-trips EXACTLY (bools, int cursors, times,
  accumulators — no field loses a bit);
* a fused step equals the unfused reference step bit-for-bit on random,
  all-full, all-empty and overflow-edge windows, for both policies,
  with coalescing off and on, in f32 and f64 — and whole-sweep rows
  through ``ScanOptions(kernel="pallas")`` equal the ``"xla"`` rows.

Everything runs in interpret mode (CPU CI); on TPU the same tests
exercise the compiled kernel via ``ops._default_interpret``.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import round_step as rsk
from repro.sim import rounds as roundslib
from repro.sim import traces
from repro.sim.rounds import ACC_KEYS, RoundsSpec
from repro.sim.sweep import ScanOptions, SweepPoint, run_sweep

pytestmark = pytest.mark.tier1

DAY = 24 * 3600.0
K = 16          # small window → fast interpret steps, real compaction


def _spec(**kw):
    base = dict(duration=2 * DAY, max_rounds=4096, window=K,
                kernel="pallas")
    base.update(kw)
    return RoundsSpec(**base)


def _lane(policy, seed=0):
    """One real packed lane + its ctx dict and kernel input stack."""
    rng = np.random.default_rng(seed)
    horizon = 2 * DAY
    jobs = [j for j in traces.nasa_ipsc(seed=seed) if j.submit < horizon]
    ws = [(t, d) for t, d in traces.worldcup98(seed=seed, peak_vms=64)
          if t < horizon]
    if policy == "fb":
        leases, levels = [3600.0], [24]
        prm = {"lease": jnp.asarray(3600.0), "capacity": jnp.asarray(24.0),
               "p_idx": jnp.asarray(0, jnp.int32)}
    else:
        leases, levels = [3600.0], [12]
        prm = {"lease": jnp.asarray(3600.0), "B": jnp.asarray(25.0),
               "lb_ws": jnp.asarray(12.0), "U": jnp.asarray(0.25),
               "V": jnp.asarray(0.5), "G": jnp.asarray(2.0),
               "p_idx": jnp.asarray(0, jnp.int32)}
    pk = jax.tree_util.tree_map(
        lambda a: a[0], roundslib.pack_event_workloads(
            [(jobs, ws)], horizon, K, policy, leases=leases,
            levels=levels))
    prm = {k: v.astype(pk.submit.dtype) if k != "p_idx" else v
           for k, v in prm.items()}
    ctx = roundslib._lane_ctx(policy, prm, pk)
    return pk, ctx, rsk.lane_inputs(policy, ctx), rng


def _core(pk, kind, rng):
    """A loop state of the requested shape: ``random`` mid-simulation,
    ``all_full`` (every lane running, nothing done), ``all_empty``
    (every lane a pad row), ``overflow_edge`` (admission cursor at the
    table end — the dynamic-slice clamp path)."""
    f = pk.submit.dtype
    zero = jnp.zeros((), f)
    Jp = int(pk.submit.shape[0])
    acc = {k: jnp.asarray(rng.uniform(0, 50), f) for k in ACC_KEYS}
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


def _assert_trees_equal(a, b, label):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        assert x.dtype == y.dtype, (label, x.dtype, y.dtype)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=str(label))


@pytest.mark.parametrize("policy", ["fb", "flb_nub"])
@pytest.mark.parametrize("kind", ["random", "all_full", "all_empty",
                                  "overflow_edge"])
def test_pack_carry_roundtrip_is_exact(policy, kind):
    pk, _, _, rng = _lane(policy)
    core = _core(pk, kind, rng)
    back = rsk.unpack_carry(*rsk.pack_carry(core))
    _assert_trees_equal(core, back, (policy, kind))
    # Bool/int fields come back with their exact types, not floats.
    assert back[4].dtype == jnp.bool_          # has_queue
    assert back[7].dtype == jnp.int32          # rise_i
    assert back[8].dtype == jnp.int32          # next_row
    assert back[12].dtype == back[13].dtype == jnp.bool_   # run, done


@pytest.mark.parametrize("policy", ["fb", "flb_nub"])
def test_ctx_roundtrip_through_kernel_inputs(policy):
    """lane_inputs → _ctx_from_inputs reproduces the _lane_ctx dict
    value-for-value — the precondition for shared-_chunk_core
    equality."""
    _, ctx, inputs, _ = _lane(policy)
    back = rsk._ctx_from_inputs(policy, *inputs)
    assert set(back) == set(ctx)
    for k in ctx:
        np.testing.assert_array_equal(np.asarray(ctx[k]),
                                      np.asarray(back[k]), err_msg=k)


@pytest.mark.parametrize("policy", ["fb", "flb_nub"])
@pytest.mark.parametrize("kind", ["random", "all_full", "all_empty",
                                  "overflow_edge"])
@pytest.mark.parametrize("batch", [1, 8])
def test_fused_step_bit_equals_reference(policy, kind, batch):
    """One fused pallas_call == one unfused traced step, bit-for-bit,
    on every window shape × policy × coalesce setting. Both sides run
    under jit — the only way the engines ever call them (an EAGER
    op-by-op reference can drift a ULP on the float accumulators, as
    eager dispatch rounds each mul/add separately)."""
    pk, _, inputs, rng = _lane(policy)
    spec = _spec(batch=batch)
    sc, win = rsk.pack_carry(_core(pk, kind, rng))

    def call(fn):
        return jax.jit(lambda s, w: fn(*inputs, s, w, policy=policy,
                                       spec=spec, interpret=True))(sc, win)

    _assert_trees_equal(call(rsk.chunk_step), call(rsk.chunk_step_ref),
                        (policy, kind, batch))


@pytest.mark.parametrize("policy", ["fb", "flb_nub"])
def test_fused_step_equals_reference_vmapped(policy):
    """Under vmap (the lane axis the sweep engines batch over): every
    DISCRETE outcome — the window matrix (starts, completions, kills,
    queue state, times) and the event-exact scalars — matches the
    vmapped reference bit-for-bit. The three float TIME-INTEGRAL
    accumulators (turn_sum, exec_sum, node_seconds) are compared to
    1e-6 relative instead: a batched reduction may round a ULP apart
    from a per-lane one in EITHER backend (vmapping the pure-jnp
    reference shifts them the same way), so cross-batching bit-equality
    is not a property any backend has. The bit-identity contract that
    matters — fused vs unfused rows under the SAME engine batching —
    is pinned end-to-end by test_sweep_rows_match_xla_backend and the
    differential harness."""
    pk, _, inputs, rng = _lane(policy)
    spec = _spec()
    cores = [rsk.pack_carry(_core(pk, "random", rng)) for _ in range(5)]
    sc = jnp.stack([c[0] for c in cores])
    win = jnp.stack([c[1] for c in cores])

    def call(fn):
        return jax.jit(jax.vmap(
            lambda s, w: fn(*inputs, s, w, policy=policy, spec=spec,
                            interpret=True), in_axes=(0, 0)))(sc, win)

    fused, ref = call(rsk.chunk_step), call(rsk.chunk_step_ref)
    np.testing.assert_array_equal(np.asarray(fused[1]),
                                  np.asarray(ref[1]), err_msg=policy)
    integral = [rsk.SC_ACC0 + ACC_KEYS.index(k)
                for k in ("turn_sum", "exec_sum", "node_seconds")]
    exact = [i for i in range(rsk.SC_SIZE) if i not in integral]
    sf, sr = np.asarray(fused[0]), np.asarray(ref[0])
    np.testing.assert_array_equal(sf[:, exact], sr[:, exact],
                                  err_msg=policy)
    np.testing.assert_allclose(sf[:, integral], sr[:, integral],
                               rtol=1e-6, err_msg=policy)


def test_fused_step_bit_equals_reference_float64():
    """f64 lanes (the bit-match-vs-event precision) through the fused
    kernel — the pack dtype follows the lane dtype."""
    with jax.enable_x64(True):
        pk, _, inputs, rng = _lane("fb")
        assert pk.submit.dtype == jnp.float64
        spec = _spec()
        sc, win = rsk.pack_carry(_core(pk, "random", rng))
        assert sc.dtype == win.dtype == jnp.float64

        def call(fn):
            return jax.jit(lambda s, w: fn(*inputs, s, w, policy="fb",
                                           spec=spec, interpret=True)
                           )(sc, win)

        _assert_trees_equal(call(rsk.chunk_step),
                            call(rsk.chunk_step_ref), "f64")


def test_sweep_rows_match_xla_backend():
    """End to end: ScanOptions(kernel="pallas") rows == kernel="xla"
    rows on a queue-provoking trace, for both policies, plain and
    coalesced."""
    horizon = 2 * DAY
    jobs = [j for j in traces.nasa_ipsc(seed=11) if j.submit < horizon]
    ws = [(t, d) for t, d in traces.worldcup98(seed=11, peak_vms=64)
          if t < horizon]
    pts = [SweepPoint("fb", capacity=24),
           SweepPoint("flb_nub", lb_pbj=13, lb_ws=12)]
    for co in (None, 8):
        xla = run_sweep(pts, jobs, ws, horizon, mode="rounds",
                        scan_options=ScanOptions(coalesce=co))
        pallas = run_sweep(pts, jobs, ws, horizon, mode="rounds",
                           scan_options=ScanOptions(coalesce=co,
                                                    kernel="pallas"))
        assert pallas == xla, (co, [(i, a, b) for i, (a, b) in
                                    enumerate(zip(xla, pallas))
                                    if a != b][:2])


def test_kernel_field_is_validated_and_cached_separately():
    """Unknown kernels fail fast; the jit-cache key (policy, spec)
    distinguishes backends, so switching can never reuse a stale
    program."""
    with pytest.raises(ValueError, match="unknown rounds kernel"):
        _spec(kernel="triton")
    with pytest.raises(ValueError, match="unknown rounds kernel"):
        dataclasses.replace(_spec(), kernel="")
    s = _spec()
    assert roundslib._rounds_lane("fb", s) is roundslib._rounds_lane(
        "fb", _spec())
    assert roundslib._rounds_lane("fb", s) is not roundslib._rounds_lane(
        "fb", dataclasses.replace(s, kernel="xla"))


def test_warmup_sweep_is_clear_caches_safe():
    """The bench's compile-measurement helper: warming, clearing and
    re-warming returns identical rows (nothing stale survives a
    jax.clear_caches), and the warmed steady-state call still works."""
    from repro.sim.sweep import warmup_sweep
    from repro.sim.sweep import run_sweep_workloads

    horizon = 12 * 3600.0
    jobs = [j for j in traces.nasa_ipsc(seed=2) if j.submit < horizon]
    ws = [(t, d) for t, d in traces.worldcup98(seed=2, peak_vms=64)
          if t < horizon]
    pts = [SweepPoint("fb", capacity=24)]
    wls = [(jobs, ws)]
    opts = ScanOptions(kernel="pallas")
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
