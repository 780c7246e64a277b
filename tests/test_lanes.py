"""The lane primitives of ``repro.sim.lanes``, on cases worked by hand.

Each case is small enough to follow the paper's rules step by step:

* §6.5.2 first-fit scans the queue in arrival order and starts every
  job that fits the free nodes left by the jobs started before it;
* §5.1 (FB): a WS demand rise first takes idle PBJ nodes, then kills
  running jobs smallest first; at each lease tick all idle nodes go to
  the PBJ TRE;
* §5.2 (FLB-NUB): at each lease tick the PBJ TRE requests DR1 = demand
  − owned when demand/owned > U, else DR2 = biggest − free when the
  biggest queued job exceeds what it owns, and releases ⌊G · free⌋ when
  demand/owned < V. It never takes nodes back from a running job.

The kill cases are ones where the size-class order and §5.1's order
agree. Where they differ (a tie inside one power-of-two class) is the
open kill-victim fault, and is pinned neither way here.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.sim import lanes

pytestmark = pytest.mark.tier1

F = jnp.float32


def _f(*v):
    return jnp.asarray(v, F)


def _b(*v):
    return jnp.asarray(v, bool)


def _lanes(mask):
    return [int(i) for i in np.flatnonzero(np.asarray(mask))]


# ----------------------------------------------------------------- first-fit

@pytest.mark.parametrize("free, queued, size, passes, started, left", [
    # Sequential first-fit on 10 free nodes: 4 starts (6 left), 8 does
    # not fit, 2 starts (4 left), 3 starts (1 left). One filtered-prefix
    # pass is conservative and starts only the head.
    (10.0, (1, 1, 1, 1), (4, 8, 2, 3), 1, [0], 6.0),
    (10.0, (1, 1, 1, 1), (4, 8, 2, 3), 2, [0, 2, 3], 1.0),
    # A job exactly the size of the free nodes starts; the one behind
    # it no longer fits; a lane that is not queued never starts.
    (5.0, (0, 1, 1), (1, 5, 1), 1, [1], 0.0),
    (5.0, (0, 1, 1), (1, 5, 1), 2, [1], 0.0),
])
def test_first_fit(free, queued, size, passes, started, left):
    free_out, mask = lanes.first_fit(jnp.asarray(free, F), _b(*queued),
                                     _f(*size), passes)
    assert _lanes(mask) == started
    assert float(free_out) == left


# -------------------------------------------------------------- size classes

@pytest.mark.parametrize("size, cls", [
    (1, 0), (2, 1), (3, 2), (4, 2), (5, 3), (256, 8), (257, 9),
    (2 ** 15, 15), (2 ** 20, 15),
])
def test_size_classes_at_power_of_two_edges(size, cls):
    """Class ``ceil(log2(size))``, clipped to the last class: a power
    of two closes its class, one more opens the next."""
    got, masks = lanes.size_classes(_f(size))
    assert int(got[0]) == cls
    assert masks.shape == (lanes.KILL_CLASSES, 1)
    assert _lanes(masks[:, 0]) == [cls]


# ------------------------------------------------------------ kill selection

@pytest.mark.parametrize("running, size, need, killed", [
    # Smallest first: 1, then 2 covers a need of 3.
    ((1, 1, 1, 1), (1, 2, 4, 8), 3.0, [0, 1]),
    # A need of 4 takes 1 and 2 (3 nodes), then the 4.
    ((1, 1, 1, 1), (1, 2, 4, 8), 4.0, [0, 1, 2]),
    # No need, no kill.
    ((1, 1, 1, 1), (1, 2, 4, 8), 0.0, []),
    # A lane that is not running is never killed: 1, then 4.
    ((1, 0, 1, 1), (1, 1, 4, 8), 2.0, [0, 2]),
    # Equal sizes started in arrival order: the latest started (the
    # newest arrival) goes first, then the next latest.
    ((1, 1, 1), (2, 2, 2), 3.0, [1, 2]),
])
def test_kill_selection_smallest_first(running, size, need, killed):
    sz = _f(*size)
    cls, masks = lanes.size_classes(sz)
    got = lanes.kill_selection(_b(*running), sz, cls, masks,
                               jnp.asarray(need, F))
    assert _lanes(got) == killed


# ------------------------------------------------------------ FB, §5.1

def _fb(wsv, is_tick, *, owned, run, size, queued=None):
    sz = _f(*size)
    run = _b(*run)
    queued = _b(*(queued or (0,) * len(size)))
    used = jnp.sum(jnp.where(run, sz, 0.0))
    return lanes.fb_actions(
        jnp.asarray(10.0, F), jnp.asarray(owned, F), run, used, queued,
        jnp.asarray(wsv, F), sz, *lanes.size_classes(sz),
        jnp.asarray(is_tick), 2)


@pytest.mark.parametrize("wsv, killed, owned", [
    # C = 10, PBJ owns 8 and runs 4 + 2 (2 idle). Demand 3 needs one
    # node back: the idle ones cover it, nothing dies.
    (3.0, [], 7.0),
    # Demand 5 needs 3 back: 2 idle, then the size-2 job dies.
    (5.0, [1], 5.0),
])
def test_fb_ws_rise_takes_idle_nodes_before_it_kills(wsv, killed, owned):
    owned_out, run, starts, kill, alloc, _ = _fb(
        wsv, False, owned=8.0, run=(1, 1), size=(4, 2))
    assert _lanes(kill) == killed
    assert _lanes(run) == [i for i in (0, 1) if i not in killed]
    assert float(owned_out) == owned
    assert float(alloc) == owned + wsv
    assert _lanes(starts) == []


@pytest.mark.parametrize("is_tick, owned, started, events", [
    # Off a tick the 4 idle nodes stay with the provider: the queued
    # size-6 job does not fit in the 4 PBJ owns.
    (False, 4.0, [], 0.0),
    # At the tick PBJ is granted all of them (10 − 2 − 4) and the job
    # starts.
    (True, 8.0, [0], 1.0),
])
def test_fb_lease_tick_grants_idle_nodes(is_tick, owned, started, events):
    owned_out, _, starts, kill, alloc, pbj_ev = _fb(
        2.0, is_tick, owned=4.0, run=(0,), size=(6,), queued=(1,))
    assert float(owned_out) == owned
    assert _lanes(starts) == started
    assert _lanes(kill) == []
    assert float(alloc) == owned + 2.0
    assert float(pbj_ev) == events


# ------------------------------------------------------------ FLB-NUB, §5.2

def _flb(*, owned, run, size, queued, U=1.2, V=0.2, G=0.5, is_tick=True,
         wsv=2.0):
    """B = 10 with lb_ws = 2 and the pool already fully leased to PBJ
    (pool_pbj = 8), so the tick's pool grant is zero and only the U/V/G
    rules move ``owned``."""
    sz = _f(*size)
    run = _b(*run)
    used = jnp.sum(jnp.where(run, sz, 0.0))
    return lanes.flb_actions(
        jnp.asarray(10.0, F), jnp.asarray(2.0, F), jnp.asarray(U, F),
        jnp.asarray(V, F), jnp.asarray(G, F), jnp.asarray(owned, F),
        jnp.asarray(8.0, F), run, used, _b(*queued), jnp.asarray(wsv, F),
        sz, jnp.asarray(is_tick), 2)


@pytest.mark.parametrize("wsv", [0.0, 2.0, 50.0])
def test_flb_nub_never_kills(wsv):
    """Whatever the web demand, every running job still runs after the
    instant: FLB-NUB serves WS elastically and releases only free
    nodes."""
    _, _, run, _, alloc, _ = _flb(owned=8.0, run=(1, 1, 0),
                                  size=(4, 4, 2), queued=(0, 0, 1),
                                  V=0.9, G=1.0, wsv=wsv)
    assert _lanes(run)[:2] == [0, 1]
    # The web share above lb_ws is leased on top of the pool.
    assert float(alloc) >= 10.0 + max(wsv - 2.0, 0.0)


@pytest.mark.parametrize("case, kw, owned, pool, started, events", [
    # demand 10 / owned 8 = 1.25 > U = 1.2: request DR1 = 10 − 8.
    ("grow_dr1", dict(run=(1, 0, 0), size=(8, 4, 6), queued=(0, 1, 1)),
     10.0, 8.0, [], 1.0),
    # 10 / 8 = 1.25 < U = 1.5, but the queued 10 exceeds the 8 owned:
    # request DR2 = 10 − 8 free, and the job starts on the grant.
    ("grow_dr2", dict(run=(0,), size=(10,), queued=(1,), U=1.5),
     10.0, 8.0, [0], 1.0),
    # No queue: 0 / 8 < V = 0.2, release ⌊0.5 · 6 free⌋ = 3; the leased
    # pool shrinks with it.
    ("shrink", dict(run=(1,), size=(2,), queued=(0,)),
     5.0, 5.0, [], 1.0),
    # Queued 6 does not fit the 4 free; 6 / 8 lies between V and U and
    # 6 <= 8 owned: no request, no release.
    ("hold", dict(run=(1, 0), size=(4, 6), queued=(0, 1)),
     8.0, 8.0, [], 0.0),
    # The DR1 case off a tick: the rules fire only at lease ticks.
    ("off_tick", dict(run=(1, 0, 0), size=(8, 4, 6), queued=(0, 1, 1),
                      is_tick=False),
     8.0, 8.0, [], 0.0),
])
def test_flb_nub_uvg_thresholds(case, kw, owned, pool, started, events):
    owned_out, pool_out, _, starts, alloc, pbj_ev = _flb(owned=8.0, **kw)
    assert float(owned_out) == owned, case
    assert float(pool_out) == pool, case
    assert _lanes(starts) == started, case
    assert float(pbj_ev) == events, case
    # Allocation: the pool B, plus what PBJ leases beyond it, plus the
    # web share above lb_ws (none here).
    assert float(alloc) == 10.0 + max(owned - pool, 0.0), case
