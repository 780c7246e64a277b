"""JAX-native FLB-NUB tick simulator — the paper's policy as a
``lax.scan``, ``vmap``-able over policy parameters.

The event simulator (repro.sim) is the reproduction workhorse; this
module re-expresses the FLB-NUB dynamics (§5.2) as a pure, jittable
program over fixed-size arrays so that the paper's §6.6.4 parameter
study — B × U × V × G, 20+ configurations, each a full two-week trace —
runs as ONE batched XLA program instead of 20 sequential event-driven
simulations. This is the paper's contribution as a *composable JAX
module* (DESIGN.md §3).

The sweep engine's event-round path (``repro.sim.rounds``, exposed as
``run_sweep(..., mode="rounds")``) replaces the fixed tick with exact
event times over a sliding job window, with the FB kill path and a
traced lease axis. This module remains the minimal, fixed-lease
B × U × V × G study (§6.6.4) in its simplest vmappable form.

Approximations vs the event simulator (both documented and measured in
tests): time is discretized to the lease tick L (job completions round up
to tick boundaries), and the WS demand is sampled per tick. Fidelity is
cross-validated in tests/test_jaxsim.py: completed-jobs within ~2 %,
node-hours within ~15 %, and all parameter-sweep TRENDS (J1/J2, Fig 18)
match the event simulator.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core.jobs import Job
from repro.core.profiles import per_tick_profile


@dataclasses.dataclass(frozen=True)
class FLBNUBParams:
    """The §5.2 knobs, as a vmap-able pytree of scalars."""

    B: jnp.ndarray          # coordinated pool size (lower bounds sum)
    U: jnp.ndarray          # threshold ratio of requesting
    V: jnp.ndarray          # threshold ratio of releasing
    G: jnp.ndarray          # elastic factor


jax.tree_util.register_dataclass(
    FLBNUBParams, data_fields=["B", "U", "V", "G"], meta_fields=[])


SUBSTEPS = 12    # job dynamics advance at L/12 (300 s at L=1h); policy
#                  actions (provision / U-V-G adjust) fire on tick
#                  boundaries only, exactly like the event simulator.


def pack_trace(jobs: Sequence[Job], ws_trace: Sequence[Tuple[float, int]],
               duration: float, lease_seconds: float,
               substeps: int = SUBSTEPS, dtype=None):
    """Fixed-size arrays: job table + per-substep WS demand.

    ``dtype`` defaults to the active x64 setting — float64 inside the
    ``enable_x64`` scope the sweep engine (``repro.sim.sweep``) runs its
    exact paths under, float32 otherwise — so scan-vs-event comparisons
    are never limited by the packing precision.
    """
    dtype = compat.resolve_pack_dtype(dtype)
    dt = lease_seconds / substeps
    n_steps = int(np.ceil(duration / dt))
    submit = np.array([j.submit for j in jobs], dtype)
    size = np.array([j.size for j in jobs], dtype)
    runtime = np.array([j.runtime for j in jobs], dtype)
    ws = per_tick_profile(ws_trace, duration, dt)[:n_steps].astype(dtype)
    return (jnp.asarray(submit), jnp.asarray(size), jnp.asarray(runtime),
            jnp.asarray(ws), n_steps)


@functools.partial(jax.jit, static_argnames=("n_steps", "lease_seconds",
                                             "lb_ws", "substeps"))
def simulate(params: FLBNUBParams, submit, size, runtime, ws_demand,
             n_steps: int, lease_seconds: float, lb_ws: int = 12,
             substeps: int = SUBSTEPS) -> Dict:
    """One FLB-NUB run; vmap over ``params`` for parameter sweeps."""
    n_jobs = submit.shape[0]
    lb_pbj = jnp.maximum(params.B - lb_ws, 1.0)
    dt = lease_seconds / substeps

    def step(state, s_ws):
        s_idx, ws = s_ws
        t = (s_idx + 1.0) * dt
        is_tick = (s_idx.astype(jnp.int32) % substeps) == (substeps - 1)
        owned, pool_pbj, remaining, running, done, finish_t = state

        # 1. Advance running jobs one substep.
        remaining = jnp.where(running, remaining - dt, remaining)
        completing = running & (remaining <= 0)
        finish_t = jnp.where(completing, t, finish_t)
        done = done | completing
        running = running & ~completing

        queued = (submit <= t) & ~running & ~done
        demand = jnp.sum(jnp.where(queued, size, 0.0))
        used = jnp.sum(jnp.where(running, size, 0.0))

        # 2+3. On tick boundaries: pool flow + the §5.2 U/V/G adjust.
        pool_ws = jnp.minimum(ws, float(lb_ws))
        pool_idle = jnp.maximum(params.B - pool_ws - pool_pbj, 0.0)
        grant = jnp.where(is_tick, pool_idle, 0.0)
        owned = owned + grant
        pool_pbj = pool_pbj + grant
        ratio = jnp.where(owned > 0, demand / jnp.maximum(owned, 1.0),
                          jnp.where(demand > 0, jnp.inf, 0.0))
        biggest = jnp.max(jnp.where(queued, size, 0.0))
        free = owned - used
        dr1 = jnp.maximum(demand - owned, 0.0)
        dr2 = jnp.maximum(biggest - free, 0.0)
        req = jnp.where(is_tick & (ratio > params.U), dr1,
                        jnp.where(is_tick & (biggest > owned), dr2, 0.0))
        rss = jnp.where(is_tick & (ratio < params.V) & (req == 0.0),
                        jnp.floor(params.G * jnp.maximum(free, 0.0)), 0.0)
        owned = owned + req - rss
        pool_pbj = jnp.minimum(pool_pbj, owned)   # leased-first release

        # 4. First-fit in arrival order (sequential scan over the table);
        # runs every substep, like submit/finish events in the event sim.
        free = owned - used

        def ff(carry, inp):
            fr = carry
            is_q, sz = inp
            start = is_q & (sz <= fr)
            return fr - jnp.where(start, sz, 0.0), start

        _, starts = jax.lax.scan(ff, free, (queued, size))
        running = running | starts

        # 5. Accounting: consumption = B pool + leased + WS-beyond-lb.
        leased = jnp.maximum(owned - pool_pbj, 0.0)
        ws_beyond = jnp.maximum(ws - pool_ws, 0.0)
        alloc = params.B + leased + ws_beyond
        events = (req > 0).astype(jnp.float32) + (rss > 0).astype(jnp.float32)
        state = (owned, pool_pbj, remaining, running, done, finish_t)
        return state, (alloc, events)

    state0 = (lb_pbj, lb_pbj, runtime, jnp.zeros(n_jobs, bool),
              jnp.zeros(n_jobs, bool), jnp.zeros(n_jobs, submit.dtype))
    steps = (jnp.arange(n_steps, dtype=submit.dtype), ws_demand)
    state, (alloc, events) = jax.lax.scan(step, state0, steps)
    _, _, _, running, done, finish_t = state
    turnaround = jnp.where(done, finish_t - submit, 0.0)
    return {
        "completed_jobs": jnp.sum(done),
        "avg_turnaround": jnp.sum(turnaround) / jnp.maximum(
            jnp.sum(done), 1),
        "node_hours": jnp.sum(alloc) * dt / 3600.0,
        "peak_nodes": jnp.max(alloc),
        "adjust_events": jnp.sum(events),
    }


def sweep(param_grid: List[Dict[str, float]], jobs, ws_trace, duration,
          lease_seconds: float = 3600.0, lb_ws: int = 12,
          substeps: int = SUBSTEPS) -> List[Dict]:
    """The §6.6.4 study as one vmapped program."""
    packed = pack_trace(jobs, ws_trace, duration, lease_seconds, substeps)
    submit, size, runtime, ws, n_steps = packed
    params = FLBNUBParams(
        B=jnp.array([p["B"] for p in param_grid], jnp.float32),
        U=jnp.array([p["U"] for p in param_grid], jnp.float32),
        V=jnp.array([p["V"] for p in param_grid], jnp.float32),
        G=jnp.array([p["G"] for p in param_grid], jnp.float32))
    fn = jax.vmap(lambda pr: simulate(pr, submit, size, runtime, ws,
                                      n_steps=n_steps,
                                      lease_seconds=lease_seconds,
                                      lb_ws=lb_ws, substeps=substeps))
    out = fn(params)
    return [{**param_grid[i],
             **{k: float(v[i]) for k, v in out.items()}}
            for i in range(len(param_grid))]
