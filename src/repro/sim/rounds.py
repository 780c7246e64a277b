"""Event-round fast path: jump-to-next-event steps for the stateful
PhoenixCloud policies.

Each step of the jitted loop computes the next event horizon per lane —

    ``b = min(next submit, earliest completion among running lanes,
              next WS change the policy can react to,
              next lease boundary L·(⌊t/L⌋+1))``

— advances straight to it, and fires the policy tick only when the step
lands on a lease boundary (the lease axis L stays *traced*, so Fig. 18
sweeps it inside the batch). Completions happen at their exact times
(``start + runtime``) and every allocation interval integrates exactly,
so the only residue against the event engine is the policy
approximation (first-fit pass convergence and FB kill tie-breaking):
completed jobs match the event engine *exactly* and node-hours/peak
stay within 5 % on the paper grids.

What counts as an event (the step-count economics)
--------------------------------------------------

A naive event list (every submit, completion and WS change) is dense on
the paper traces — the World Cup demand profile alone changes ~2.8k
times in two weeks. The engine therefore jumps over every event whose
effect is computable without stopping:

* **WS demand changes** never stop a lane. The WS share of the
  allocation is policy-independent, so its node-hour integral and its
  per-lease-window maxima are precomputed host-side per sweep point
  (``∫min(ws, C)`` for FB, ``∫max(ws − lb_ws, 0)`` and per-tick-window
  maxima for FLB-NUB's peak), and the loop samples the instantaneous
  demand with one binary search when a round needs it (FB reclaim, the
  FLB pool flow at ticks). Only FB demand *rises* remain stops — §5.1
  rule 3 reclaims (and kills) the moment demand grows — which also
  keeps the between-stops demand monotone falling, making the per-stop
  peak probe exact.
* **Submits** skip whenever they provably start on time: if the queue
  is empty and the summed size of every submit in the horizon fits in
  the currently free capacity (a conservative bound — completions
  inside the horizon only add slack), each submitting lane starts
  *retroactively* at its exact submit time. Contended submits fall back
  to one round per event.
* **Completions** stop a lane only while the queue is non-empty (a
  finish can then start queued jobs); with an empty queue they fold
  retroactively at the next round, at their exact times.

What remains is one round per lease tick plus the contended stretches.

The queue/kill machinery lives in ``repro.sim.lanes``: the fixed-size
job window with status lanes, vectorized first-fit and §5.1 size-class
kill selection (``fb_actions`` / ``flb_actions``). Lanes carry an
absolute ``end_t``, which makes completions exact and FB kill-restarts
trivially correct (a restart rewrites ``end_t``).

Loop structure: an outer ``while_loop`` step compacts the window (one
stacked lane gather — the only data-movement op, amortized) and admits
fresh job-table rows as contiguous ``dynamic_slice`` reads; an inner
unrolled block runs ``compact_every`` event rounds of pure elementwise/
reduction work. Lanes that reach the horizon self-mask (``b = t``) and
the outer loop exits once every lane is done.

Tie order at one timestamp replays the event engine's kinds (WS demand
→ lease tick → submit → finish) except for exact-float coincidences of
a completion or a skipped submit with a tick, which fold before the
tick's policy actions instead of around them — a measure-zero
coincidence on real-valued traces.

With ``devices`` set, the flattened (point × trace) lane axis shards
across host devices (``lanes.sharded_grid_map``); each lane runs the
identical per-lane program, so sharded rows are bit-identical to
single-device rows.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat, spans
from repro.core.jobs import Job
from repro.core.profiles import step_points
from repro.sim.lanes import (FBGrid, FLBGrid, fb_actions, flb_actions,
                             pack_job_table, prm_tree, resolve_pack_dtype,
                             sharded_grid_map, size_classes, stable_compact)

__all__ = [
    "PackedEventWorkloads", "RoundsSpec", "pack_event_workloads",
    "rounds_grids", "round_budget", "ws_fold_tables_batch",
    "fold_table_cache_info", "fold_table_cache_clear",
    "FB_ROUNDS_WINDOW", "FLB_ROUNDS_WINDOW", "ROUNDS_FF_PASSES",
    "COMPACT_EVERY",
]

# Windows are sized to the measured unfinished-job backlog on the §6.2
# traces (FB is capacity-bound — ≤ 158 unfinished at the Fig-13
# capacities on SDSC BLUE; FLB-NUB leases elastically — ≤ 55) plus
# slack: between compactions completed lanes linger and freshly
# submitting jobs must already be admitted.
FB_ROUNDS_WINDOW = 192
FLB_ROUNDS_WINDOW = 96
# Filtered-prefix first-fit passes per round. A pass-convergence miss
# at an exact event time is a start-time error; the paper-grid contract
# was measured at two passes (completed jobs exact on all 45 evals,
# node-hours <= 3.8 %, peak <= 1.3 % — identical to three passes) and
# the random-trace contract tests hold.
ROUNDS_FF_PASSES = 2
# Rounds between window compactions. Compaction is the one data-movement
# op of the loop (a stacked lane gather); amortizing it every few rounds
# keeps the per-round cost at reduction-dispatch level. The inner block
# is unrolled, so this also bounds the compiled body size.
COMPACT_EVERY = 8


@dataclasses.dataclass(frozen=True)
class RoundsSpec:
    """Static (hashable) execution parameters of one policy's
    event-round program: the measurement horizon, the safety cap on
    rounds (the loop exits when every lane reaches the horizon — the
    cap only stops a runaway lane, see :func:`round_budget`), the job
    window, the first-fit passes per round and the compaction cadence.
    The jit caches key on ``(policy, spec)``."""

    duration: float
    max_rounds: int
    window: int
    ff_passes: int = ROUNDS_FF_PASSES
    compact_every: int = COMPACT_EVERY


@dataclasses.dataclass(frozen=True)
class PackedEventWorkloads:
    """Fixed-size event arrays for W workloads and one policy's P sweep
    points: the arrival-sorted job tables (``lanes.pack_job_table``),
    the WS demand change points (value changes only, +inf sentinel
    padding) and the host-precomputed WS fold tables (see the module
    docstring — the loop never stops at a WS change, it reads these
    instead)."""

    submit: jnp.ndarray       # (W, J + K) — padded past the table end
    size: jnp.ndarray         # (W, J + K)
    runtime: jnp.ndarray      # (W, J + K)
    ws0: jnp.ndarray          # (W,) demand at t = 0
    ws_adjusts: jnp.ndarray   # (W,) ledgered WS events (startup + changes)
    rise_times: jnp.ndarray   # (W, NR) demand-rise times (FB stops), +inf
    rise_vals: jnp.ndarray    # (W, NR) demand value after each rise
    ws_integral: jnp.ndarray  # (W, P) ∫ policy's WS allocation share
    ws_winmax: jnp.ndarray    # (W, P, NT) per-lease-window max of the
    #                           policy's WS share (peak folding)
    ws_at_tick: jnp.ndarray   # (W, P, NT) demand at each lease boundary
    n_jobs: jnp.ndarray       # (W,) real (unpadded) job counts
    # Chaos tier (repro.sim.faults), FB only. None (the default) leaves
    # the pack structurally identical to the pre-fault format: a None
    # data field flattens to an empty pytree, so vmap axes and every
    # existing construction site are untouched.
    fault_times: Optional[jnp.ndarray] = None   # (W, NF) stop times, +inf
    fault_failed: Optional[jnp.ndarray] = None  # (W, NF) failed count
    #                                             in effect AFTER each stop
    fault_wsv: Optional[jnp.ndarray] = None     # (W, NF) raw WS demand at
    #                                             each stop (reclaim level)


jax.tree_util.register_dataclass(
    PackedEventWorkloads,
    data_fields=["submit", "size", "runtime", "ws0", "ws_adjusts",
                 "rise_times", "rise_vals", "ws_integral", "ws_winmax",
                 "ws_at_tick", "n_jobs", "fault_times", "fault_failed",
                 "fault_wsv"],
    meta_fields=[])


# ------------------------------------------------------------------ packing

def _ws_fold_tables_ref(times: np.ndarray, values: np.ndarray,
                        duration: float, policy: str, leases: np.ndarray,
                        levels: np.ndarray
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Reference fold-table build: the original per-point Python loop
    (``np.union1d`` + ``searchsorted`` + grouped max per lease window).
    Kept as the correctness oracle for :func:`ws_fold_tables_batch`
    (tests pin exact equality) and as the host-loop baseline the
    ``benchmarks.run scenarios`` setup comparison measures against —
    NOT called on any production path.
    """
    edges = np.minimum(np.append(times[1:], duration), duration)
    widths = np.maximum(edges - np.minimum(times, duration), 0.0)
    P = len(leases)
    if policy == "fb":
        share = np.minimum(values[None, :], levels[:, None])   # (P, NWS)
    else:
        share = np.maximum(values[None, :] - levels[:, None], 0.0)
    integral = share @ widths
    # One entry past the last full window: when the horizon is an exact
    # lease multiple a tick fires AT the horizon and probes the
    # degenerate window starting there — it must read the horizon-time
    # demand, not zero padding.
    nt = max(int(np.ceil(duration / leases.min())), 1) + 1
    winmax = np.zeros((P, nt))
    at_tick = np.zeros((P, nt))
    for p in range(P):
        n_win = max(int(np.ceil(duration / leases[p])), 1)
        # Merge the demand change points with the window edges, so each
        # merged cell lies in exactly one window and carries one share
        # value; a grouped max per window then covers segments that
        # span window boundaries.
        win_edges = np.arange(n_win) * leases[p]
        merged = np.union1d(times, win_edges)
        merged = merged[merged < duration]
        vals = share[p][np.searchsorted(times, merged, "right") - 1]
        starts = np.searchsorted(merged, win_edges, "left")
        winmax[p, :n_win] = np.maximum.reduceat(vals, starts)
        at_tick[p, :n_win] = values[
            np.searchsorted(times, win_edges, "right") - 1]
        end_idx = np.searchsorted(times, n_win * leases[p], "right") - 1
        winmax[p, n_win] = share[p][end_idx]
        at_tick[p, n_win] = values[end_idx]
    return integral, winmax, at_tick


def ws_fold_tables_batch(times: np.ndarray, values: np.ndarray,
                         duration: float, policy: str, leases: np.ndarray,
                         levels: np.ndarray,
                         failed: Optional[np.ndarray] = None
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized WS fold tables over all (W, P) lanes at once.

    ``times`` is ONE sorted change-point axis (N,) shared by every lane
    (each entry < ``duration``; generated scenario batches share a
    dense grid, single-trace callers pass that trace's points), and
    ``values`` the per-lane demand rows (W, N) — a 1-D ``values`` is
    treated as one lane. Returns ``(integral (W, P), winmax (W, P, NT),
    at_tick (W, P, NT))``, elementwise equal to the reference per-point
    loop (:func:`_ws_fold_tables_ref`, pinned by tests):

    * ``integral`` — exact node-second integral of the policy's WS
      allocation share (``min(ws, C)`` for FB, ``max(ws − lb_ws, 0)``
      for FLB-NUB), one stacked GEMV over the segment widths;
    * ``winmax`` — the share's max over every lease window
      ``[kL, (k+1)L)``: the max of the *boundary* value (the segment
      covering ``kL``, one batched ``searchsorted`` gather) and a
      segment-max of the change points grouped by window index. The
      groups are contiguous runs of the sorted time axis, so ONE
      flattened ``maximum.reduceat`` over the (P·N) composite grouping
      covers every point at once — no per-point loop;
    * ``at_tick`` — the demand at every lease boundary (same gather).

    Windows past a point's horizon (``k > ceil(duration / L_p)``) are
    zero, exactly like the reference.

    ``failed``, when given, is the concurrently-failed node count as a
    step series on the SAME time axis (N,), shared by every lane — the
    chaos tier's time-varying capacity. The FB share line becomes
    ``min(ws, max(C - failed, 0))`` (the §5.1 WS-priority invariant the
    event engine's ``on_fail`` maintains), which keeps the integral and
    the window maxima exact under failures. FLB-NUB satisfies WS
    elastically regardless of pool failures, so ``failed`` is rejected
    there.
    """
    times = np.asarray(times, np.float64)
    values = np.asarray(values, np.float64)
    if values.ndim == 1:
        values = values[None]
    leases = np.asarray(leases, np.float64)
    levels = np.asarray(levels, np.float64)
    W, N = values.shape
    P = len(leases)
    edges = np.minimum(np.append(times[1:], duration), duration)
    widths = np.maximum(edges - np.minimum(times, duration), 0.0)   # (N,)
    if failed is not None and policy != "fb":
        raise ValueError("time-varying failed capacity is FB-only "
                         "(FLB-NUB's WS share is elastic)")
    if policy == "fb":
        cap = levels[None, :, None]
        if failed is not None:
            failed = np.asarray(failed, np.float64)
            cap = np.maximum(cap - failed[None, None, :], 0.0)
        share = np.minimum(values[:, None, :], cap)
    else:
        share = np.maximum(values[:, None, :] - levels[None, :, None],
                           0.0)                                 # (W, P, N)
    # (W, P, N) @ (N,) runs the same (P, N) GEMV per lane as the
    # reference loop, keeping the integral bit-identical for every W.
    integral = share @ widths
    nt = max(int(np.ceil(duration / leases.min())), 1) + 1
    n_win = np.maximum(np.ceil(duration / leases).astype(np.int64), 1)
    win_edges = np.arange(nt)[None, :] * leases[:, None]        # (P, NT)
    # The segment covering each window boundary (right-continuous).
    bidx = (np.searchsorted(times, win_edges.ravel(), "right")
            .reshape(P, nt) - 1)
    at_tick = values[:, bidx]                                   # (W, P, NT)
    winmax = np.take_along_axis(
        share, np.broadcast_to(bidx, (W, P, nt)), axis=2).copy()
    # Segment max of the interior change points, grouped by window
    # index. For a fixed p the groups are contiguous runs of the sorted
    # time axis; flattening (p, window) into one composite, strictly
    # sorted grouping makes them contiguous runs of the (P·N) axis too,
    # so one reduceat covers all points. reduceat's empty-segment quirk
    # (it returns the start element) is masked off via the run lengths.
    interior = times < duration
    ii = np.nonzero(interior)[0]
    if ii.size:
        M = ii.size
        widx = np.minimum((times[ii][None, :]
                           // leases[:, None]).astype(np.int64),
                          nt - 1)                               # (P, M)
        flat_groups = (np.arange(P)[:, None] * nt + widx).ravel()
        starts = np.searchsorted(flat_groups, np.arange(P * nt), "left")
        counts = np.append(np.diff(starts), P * M - starts[-1])
        # A trailing -inf sentinel keeps every start index valid
        # (trailing empty groups have starts == P*M; clipping instead
        # would truncate the last non-empty group's segment end).
        share_flat = np.concatenate(
            [share[:, :, ii].reshape(W, P * M),
             np.full((W, 1), -np.inf)], axis=1)
        seg = np.maximum.reduceat(share_flat, starts, axis=1)
        seg = np.where(counts[None, :] > 0, seg, -np.inf)
        winmax = np.maximum(winmax, seg.reshape(W, P, nt))
    # A point's windows end at n_win = ceil(duration / L): entry n_win
    # is the degenerate horizon-boundary probe (boundary value only —
    # every interior point lies strictly below duration <= n_win·L),
    # entries past it stay zero like the reference's.
    live = np.arange(nt)[None, :] <= n_win[:, None]             # (P, NT)
    winmax = np.where(live[None], winmax, 0.0)
    at_tick = np.where(live[None], at_tick, 0.0)
    return integral, winmax, at_tick


@functools.lru_cache(maxsize=256)
def _fold_tables_cached(times_b: bytes, values_b: bytes, duration: float,
                        policy: str, leases_b: bytes, levels_b: bytes,
                        failed_b: bytes = b""
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One workload's fold tables, memoized on the trace identity (the
    raw change-point bytes), the policy and the grid's (leases, levels)
    — the differential harness and the multi-engine benchmark re-pack
    identical workloads once per engine column, and the tables are the
    dominant pack cost. Cached arrays are marked read-only; consumers
    copy via ``astype`` before mutating/stacking."""
    times = np.frombuffer(times_b, np.float64)
    values = np.frombuffer(values_b, np.float64)
    leases = np.frombuffer(leases_b, np.float64)
    levels = np.frombuffer(levels_b, np.float64)
    failed = np.frombuffer(failed_b, np.float64) if failed_b else None
    integral, winmax, at_tick = ws_fold_tables_batch(
        times, values, duration, policy, leases, levels, failed)
    out = (integral[0], winmax[0], at_tick[0])
    for a in out:
        a.flags.writeable = False
    return out


def fold_table_cache_info():
    """``lru_cache`` statistics of the per-workload fold-table cache —
    the ``benchmarks.run scenarios`` CI leg gates on the hit count."""
    return _fold_tables_cached.cache_info()


def fold_table_cache_clear() -> None:
    _fold_tables_cached.cache_clear()


def _fault_table(fs, levels: np.ndarray, times: np.ndarray,
                 values: np.ndarray, duration: float):
    """One workload's fault stops and its fold axis. Returns the
    distinct fault instants inside the horizon ``u_t``, the failed
    count in effect after all of an instant's events ``u_n``, the raw
    demand at each (the loop's reclaim level) ``u_w``, and the fold
    axis: the union of demand and fault change points with the demand
    and failed count resampled onto it; ``None`` when the clamp leaves
    no event."""
    # Mirror the site ledger's clamp (at most C nodes down at once;
    # repairs revive only actually-failed nodes). The clamp recurrence
    # depends on C, so a multi-level grid can only share one fault
    # table when the clamp never binds.
    if np.unique(levels).size == 1:
        fs = fs.clamp(int(levels[0]))
    elif fs.max_concurrent() > int(np.min(levels)):
        raise ValueError(
            "fault schedule's concurrent failures exceed the smallest "
            "capacity level; the ledger clamp is per-capacity — pack one "
            "level at a time")
    if not len(fs):
        return None
    f_t, f_n = fs.failed_series()
    u_t = np.unique(f_t[f_t < duration])
    u_n = np.concatenate([[0], f_n])[
        np.searchsorted(f_t, u_t, "right")].astype(np.float64)
    u_w = values[np.searchsorted(times, u_t, "right") - 1]
    m_t = np.union1d(times, u_t)
    m_v = values[np.searchsorted(times, m_t, "right") - 1]
    m_f = np.concatenate([[0.0], u_n])[np.searchsorted(u_t, m_t, "right")]
    return (u_t, u_n, u_w, m_t, m_v,
            np.ascontiguousarray(m_f, np.float64))


def pack_event_workloads(workloads: Sequence[Tuple[Sequence[Job],
                                                   Sequence[Tuple[float,
                                                                  int]]]],
                         duration: float, window: int, policy: str,
                         leases: Sequence[float], levels: Sequence[float],
                         dtype: Optional[np.dtype] = None, faults=None,
                         fault_stops: Optional[int] = None):
    """Pack ``(jobs, ws_trace)`` workloads into event-round arrays for
    one policy's sweep points.

    ``levels`` is the per-point WS fold level — the capacity C for FB,
    the WS lower bound for FLB-NUB (integers; the fold tables are exact
    for the values given). WS change points collapse to actual value
    changes within the horizon (the event engine ledgers nothing for a
    no-op demand event); a trailing ``+inf`` sentinel keeps gathers in
    range after the last real change.

    ``faults``, when given, is a per-workload sequence of
    :class:`repro.sim.faults.FaultSchedule` (or ``None`` entries) —
    FB only. Fault instants become loop stops (``fault_times`` /
    ``fault_failed`` / ``fault_wsv``), and the fold tables are rebuilt
    on the union of demand and fault change points with the FB share
    line ``min(ws, max(C - failed(t), 0))``, so the WS integral and the
    window maxima stay exact under failures. Demand-rise stops keep
    coming from the original demand points. The fault tables are padded
    to the most instants over the workloads, or to ``fault_stops`` when
    given (a schedule with more instants raises).
    """
    dtype = resolve_pack_dtype(dtype)
    if faults is not None and any(f is not None and len(f) for f in faults):
        if policy != "fb":
            raise ValueError(
                "fault schedules are FB-only in the rounds engine; run "
                "FLB-NUB faults through the event engine")
        if len(faults) != len(workloads):
            raise ValueError(
                f"faults ({len(faults)}) must align with workloads "
                f"({len(workloads)})")
    else:
        faults = None
    submit, size, runtime, n_jobs = pack_job_table(workloads, window, dtype)
    W = len(workloads)
    leases = np.asarray(leases, np.float64)
    levels = np.asarray(levels, np.float64)
    rises: List[Tuple[np.ndarray, np.ndarray]] = []
    fault_tabs: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
    integrals, winmaxes, at_ticks = [], [], []
    ws0 = np.zeros(W, dtype)
    ws_adjusts = np.zeros(W, dtype)
    for w, (_, ws_trace) in enumerate(workloads):
        times, values = step_points(ws_trace, duration)
        keep = np.ones(len(times), bool)
        keep[1:] = values[1:] != values[:-1]   # drop no-op change points
        times, values = times[keep], values[keep]
        ws0[w] = values[0]
        ws_adjusts[w] = (len(times) - 1) + float(values[0] > 0)
        up = values[1:] > values[:-1]
        rises.append((times[1:][up], values[1:][up]))
        fs = faults[w] if faults is not None else None
        tab = None
        if fs is not None and len(fs):
            with spans.span("rounds.fault_tables"):
                tab = _fault_table(fs, levels, times, values, duration)
        if tab is not None:
            u_t, u_n, u_w, fold_t, fold_v, failed = tab
            spans.count("faults.events", len(u_t))
            fault_tabs.append((u_t, u_n, u_w))
            failed_b = failed.tobytes()
        else:
            fault_tabs.append((np.zeros(0), np.zeros(0), np.zeros(0)))
            fold_t, fold_v, failed_b = times, values, b""
        before = _fold_tables_cached.cache_info()
        with spans.span("rounds.fold_tables"):
            integral, winmax, at_tick = _fold_tables_cached(
                np.ascontiguousarray(fold_t, np.float64).tobytes(),
                np.ascontiguousarray(fold_v, np.float64).tobytes(),
                float(duration), policy, leases.tobytes(),
                levels.tobytes(), failed_b)
        after = _fold_tables_cached.cache_info()
        spans.count("fold_tables.hits", after.hits - before.hits)
        spans.count("fold_tables.misses", after.misses - before.misses)
        integrals.append(integral)
        winmaxes.append(winmax)
        at_ticks.append(at_tick)
    nr = max((len(r) for r, _ in rises), default=0) + 1   # +inf sentinel
    rise_times = np.full((W, nr), np.inf, dtype)
    rise_vals = np.zeros((W, nr), dtype)
    for w, (r_t, r_v) in enumerate(rises):
        rise_times[w, :len(r_t)] = r_t
        rise_vals[w, :len(r_v)] = r_v
    arrays = dict(
        submit=submit, size=size, runtime=runtime, ws0=ws0,
        ws_adjusts=ws_adjusts, rise_times=rise_times,
        rise_vals=rise_vals,
        ws_integral=np.stack(integrals).astype(dtype),
        ws_winmax=np.stack(winmaxes).astype(dtype),
        ws_at_tick=np.stack(at_ticks).astype(dtype), n_jobs=n_jobs)
    if faults is not None:
        # (W, NF) tables padded to the most instants over the stacked
        # workloads; a workload without a schedule reads only the
        # sentinel, so its lanes never stop at a fault.
        with spans.span("rounds.fault_tables"):
            most = max(len(ft) for ft, _, _ in fault_tabs)
            if fault_stops is not None and most > fault_stops:
                raise ValueError(
                    f"a fault schedule holds {most} instants inside the "
                    f"horizon, more than fault_stops={fault_stops}")
            nf = (most if fault_stops is None else fault_stops) + 1  # +inf
            fault_times = np.full((W, nf), np.inf, dtype)
            fault_failed = np.zeros((W, nf), dtype)
            fault_wsv = np.zeros((W, nf), dtype)
            for w, (f_t, f_n, f_w) in enumerate(fault_tabs):
                fault_times[w, :len(f_t)] = f_t
                fault_failed[w, :len(f_n)] = f_n
                fault_wsv[w, :len(f_w)] = f_w
        arrays.update(fault_times=fault_times, fault_failed=fault_failed,
                      fault_wsv=fault_wsv)
    return PackedEventWorkloads(
        **{k: jnp.asarray(v) for k, v in arrays.items()})


def round_budget(max_jobs: int, n_ws: int, duration: float,
                 min_lease: float) -> int:
    """Safety cap on rounds per lane: every submit, one completion per
    job plus generous kill-restart slack (FB restarts re-enter the
    completion stream), every demand rise and every lease tick of the
    *shortest* lease in the grid. The loop exits as soon as every lane
    reaches the horizon, so the cap is free unless a lane runs away; a
    lane that exhausts it reports ``truncated`` and the sweep layer
    warns.
    """
    ticks = int(np.ceil(duration / max(min_lease, 1.0)))
    return int(n_ws + 4 * max_jobs + ticks + 64)


# ------------------------------------------------------------- the rounds core

# The loop's metric accumulators.
ACC_KEYS = ("completed", "turn_sum", "exec_sum", "kills", "node_seconds",
            "peak", "pbj_adjusts", "adjusts", "window_overflow", "rounds")


def _fault_acc_keys(ctx: Dict) -> Tuple[str, ...]:
    """The accumulators a faulted lane adds beside ``ACC_KEYS``: the
    rounds that stopped at a fault instant and the jobs killed in them.
    A pack without fault tables adds none, so its program is unchanged."""
    return ("fault_rounds", "fault_kills") if "fault_times" in ctx else ()


def _lane_ctx(policy: str, prm: Dict, pk: PackedEventWorkloads) -> Dict:
    """One lane's traced round-body inputs as a flat dict — the job
    table, the FB demand-rise stops, the per-point WS fold tables and
    the policy scalars, built from the packed pytree."""
    f = pk.submit.dtype
    p_idx = prm["p_idx"]
    ctx = {
        "L": prm["lease"].astype(f),
        "tr_submit": pk.submit, "tr_size": pk.size,
        "tr_runtime": pk.runtime,
        "rise_times": pk.rise_times, "rise_vals": pk.rise_vals,
        "ws_winmax": pk.ws_winmax[p_idx],    # (NT,) WS-share window max
        "ws_at_tick": pk.ws_at_tick[p_idx],  # (NT,) demand at boundaries
    }
    if policy == "fb":
        ctx["C"] = prm["capacity"].astype(f)
        if pk.fault_times is not None:
            # Chaos tier: fault stop instants, the failed count after
            # each, and the raw demand at each (pack enforces FB-only).
            ctx["fault_times"] = pk.fault_times
            ctx["fault_failed"] = pk.fault_failed
            ctx["fault_wsv"] = pk.fault_wsv
    else:
        ctx["B"] = prm["B"].astype(f)
        ctx["lb_ws"] = prm["lb_ws"].astype(f)
        ctx["U"], ctx["V"], ctx["G"] = (prm[k].astype(f)
                                        for k in ("U", "V", "G"))
    return ctx


def _actions(policy: str, ctx: Dict, ff_passes: int, owned, pool_pbj,
             run, used, queued, wsv, is_tick, win, w_sz, szcls, acc):
    """The §5 policy step at one instant (``repro.sim.lanes``). The
    integrand it returns covers only the policy-owned share — the
    WS share integrates host-side (``ws_integral``) — and peaks
    fold per lease window: the policy share is constant inside one
    (FB reclaims only at demand-rise stops, which ratchet it down
    monotonically after the window's grant; FLB adjusts only at
    ticks), so combining it with the precomputed WS-share window
    max is exact without stopping at demand changes."""
    ws_winmax = ctx["ws_winmax"]
    if policy == "fb":
        C = ctx["C"]
        owned, run, starts, killed, alloc, pbj_ev = fb_actions(
            C, owned, run, used, queued, wsv, w_sz,
            *szcls, is_tick, ff_passes)
        acc["kills"] += jnp.sum(killed)
        # Window peak: owned is maximal right after the window's
        # grant, and the §5.1 ratchet owned(τ) = C − runmax(ws)
        # makes the in-window alloc max exactly min(owned + M, C).
        peak_cand = jnp.minimum(owned + ws_winmax[win], C)
        integrand = owned
    else:
        owned, pool_pbj, run, starts, alloc, pbj_ev = flb_actions(
            ctx["B"], ctx["lb_ws"], ctx["U"], ctx["V"], ctx["G"],
            owned, pool_pbj, run, used, queued, wsv, w_sz, is_tick,
            ff_passes)
        leased = ctx["B"] + jnp.maximum(owned - pool_pbj, 0.0)
        peak_cand = leased + ws_winmax[win]
        integrand = leased
    acc["peak"] = jnp.maximum(acc["peak"],
                              jnp.where(is_tick, peak_cand, -jnp.inf))
    acc["pbj_adjusts"] += pbj_ev
    acc["adjusts"] += pbj_ev
    return owned, pool_pbj, run, starts, integrand, acc


def _round_body(policy: str, ctx: Dict, spec: RoundsSpec, carry, szcls):
    """One event round over the window lanes — pure jnp on the carry
    (see the module docstring for the event semantics)."""
    (t, owned, pool_pbj, used, has_queue, wsv, alloc_prev, rise_i,
     row_sub, w_sub, w_sz, w_rt, run, done, start_t, end_t, acc) = carry
    duration = spec.duration
    f = w_sub.dtype
    inf = jnp.asarray(jnp.inf, f)
    zero = jnp.zeros((), f)
    one = jnp.ones((), f)
    dur = jnp.asarray(duration, f)
    L = ctx["L"]
    rise_times, rise_vals = ctx["rise_times"], ctx["rise_vals"]
    ws_at_tick = ctx["ws_at_tick"]
    NT = ctx["ws_winmax"].shape[0]
    active = t < duration
    # --- the next event horizon. Every candidate is strictly > t,
    # so the loop always progresses; a finished lane pins b = t and
    # becomes a no-op. Completions bound the horizon only while the
    # queue is non-empty (they can then start queued work);
    # otherwise they fold retroactively below, at exact times.
    mins = jnp.min(jnp.stack([jnp.where(w_sub > t, w_sub, inf),
                              jnp.where(run, end_t, inf)]),
                   axis=-1)                      # one packed reduction
    next_sub = jnp.minimum(mins[0],
                           jnp.where(row_sub > t, row_sub, inf))
    # The next lease boundary k·L strictly after t, with (k-1)·L <= t.
    # A division that is not correctly rounded (TPU f32) can put
    # floor(t / L) one off at a boundary; the exact products correct it.
    k_next = jnp.floor(t / L) + 1.0
    k_next = jnp.where(k_next * L <= t, k_next + 1.0, k_next)
    k_next = jnp.where((k_next - 1.0) * L > t, k_next - 1.0, k_next)
    t_tick = k_next * L
    b0 = jnp.minimum(t_tick,
                     jnp.minimum(jnp.where(row_sub > t, row_sub, inf),
                                 dur))
    if policy == "fb":
        b0 = jnp.minimum(b0, rise_times[rise_i])
    faulted = "fault_times" in ctx
    if faulted:
        # Chaos tier: every fault instant is a stop (capacity changes
        # there — kills and WS drains must replay at the exact time).
        # Between stops the failed count, and therefore the effective
        # capacity, is constant, which keeps the interval integration
        # and the policy share exact.
        ft = ctx["fault_times"]
        fi = jnp.searchsorted(ft, t, side="right")
        b0 = jnp.minimum(b0, ft[jnp.minimum(fi, ft.shape[0] - 1)])
    # --- submit skipping and the contended horizon. Empty queue:
    # if every submit in (t, b0] fits the currently-free capacity
    # in aggregate (free only grows inside the horizon; the
    # row_sub cap keeps every such submit inside the window), each
    # starts exactly on time — retroactively, below; otherwise
    # stop at the next submit. Non-empty queue: stop at the
    # earliest running-lane completion, and silently enqueue
    # arrivals that cannot fit the (then constant) free capacity.
    b0 = jnp.minimum(b0, jnp.where(has_queue, mins[1], inf))
    fresh = (w_sub > t) & (w_sub <= b0)
    sum_new = jnp.sum(jnp.where(fresh, w_sz, zero))
    free = owned - used
    skip_ok = ~has_queue & (sum_new <= free)
    min_new = jnp.min(jnp.where(fresh, w_sz, inf))
    unbounded = skip_ok | (has_queue & (min_new > free))
    b = jnp.where(unbounded, b0, jnp.minimum(b0, next_sub))
    b = jnp.where(active, b, t)
    # --- exact interval integration: the policy-owned share is
    # constant on (t, b] — it only ever changes at policy actions,
    # which happen at rounds (ticks, rises), never at completions or
    # starts.
    acc["node_seconds"] += alloc_prev * jnp.maximum(b - t, 0.0)
    # --- retroactive starts at exact submit times.
    starting = (w_sub > t) & (w_sub <= b) & ~run & ~done & skip_ok
    run = run | starting
    start_t = jnp.where(starting, w_sub, start_t)
    end_t = jnp.where(starting, w_sub + w_rt, end_t)
    # --- exact completions (including flash jobs that started and
    # finished inside this very horizon).
    completing = run & (end_t <= b)
    run = run & ~completing
    done = done | completing
    cmp_f = completing.astype(f)
    folds = jnp.sum(jnp.stack([cmp_f, cmp_f * (end_t - w_sub),
                               cmp_f * (end_t - start_t),
                               jnp.where(run, w_sz, zero)]),
                    axis=-1)                     # one packed reduction
    acc["completed"] += folds[0]
    acc["turn_sum"] += folds[1]
    acc["exec_sum"] += folds[2]
    used = folds[3]
    # --- policy actions at b. The tick fires only on a lease
    # boundary and reads the boundary-time demand from the host
    # table; between stops the carried demand only matters to FB,
    # whose reclaim level it tracks exactly (rises are FB stops).
    queued = (w_sub <= b) & ~run & ~done
    is_tick = t_tick <= b
    win = jnp.minimum(k_next, NT - 1.0).astype(jnp.int32)
    if policy == "fb":
        rised = rise_times[rise_i] <= b
        wsv = jnp.where(rised, rise_vals[rise_i], wsv)
        rise_i = rise_i + rised.astype(jnp.int32)
    if faulted:
        # Effective capacity at b: failed count after the last fault
        # event <= b. When the stop IS a fault instant, also sync the
        # carried demand to its packed raw value — the event engine's
        # on_fail sees the *current* demand (falls released WS nodes as
        # they happened), while the carried wsv only tracks rises and
        # ticks; without the sync a stale-high wsv would over-kill PBJ.
        ffl, fwv = ctx["fault_failed"], ctx["fault_wsv"]
        fib = jnp.searchsorted(ft, b, side="right")
        fprev = jnp.maximum(fib - 1, 0)
        failed_b = jnp.where(fib > 0, ffl[fprev], zero)
        at_fault = active & (fib > 0) & (ft[fprev] == b)
        wsv = jnp.where(at_fault, fwv[fprev], wsv)
        ctx = dict(ctx, C=jnp.maximum(ctx["C"] - failed_b, zero))
        kills_before = acc["kills"]
    wsv = jnp.where(is_tick, ws_at_tick[win], wsv)
    owned, pool_pbj, run, starts, integrand, acc = _actions(
        policy, ctx, spec.ff_passes, owned, pool_pbj, run, used, queued,
        wsv, is_tick, win, w_sz, szcls, acc)
    if faulted:
        # Rounds that stopped at a fault instant, and the jobs killed
        # in them (a web rise or tick at the same instant included).
        acc["fault_rounds"] += at_fault.astype(f)
        acc["fault_kills"] += jnp.where(at_fault, acc["kills"] - kills_before,
                                        zero)
    start_t = jnp.where(starts, b, start_t)
    end_t = jnp.where(starts, b + w_rt, end_t)
    # Recompute the queue and usage from the POST-action lane state:
    # fb_actions may have killed running lanes, which re-queue
    # (run cleared, not done) and release their nodes — deriving
    # from the pre-action masks would hide a killed job from the
    # next round's completion horizon and overstate ``used`` in its
    # skip/enqueue tests.
    post = jnp.sum(jnp.stack([
        jnp.where((w_sub <= b) & ~run & ~done, one, zero),
        jnp.where(run, w_sz, zero)]),
        axis=-1)                                 # one packed reduction
    has_queue = post[0] > 0
    used = post[1]
    acc["window_overflow"] += (active & (row_sub <= b)).astype(f)
    acc["rounds"] += active.astype(f)
    return (b, owned, pool_pbj, used, has_queue, wsv, integrand,
            rise_i, row_sub, w_sub, w_sz, w_rt, run, done, start_t,
            end_t, acc)


def _chunk_core(policy: str, ctx: Dict, spec: RoundsSpec, core):
    """One outer step of the loop: window compaction, job-table
    admission, the per-chunk size classes and ``compact_every`` unrolled
    event rounds. ``core`` is the 17-tuple loop state with ``next_row``
    (the admission cursor) in the slot the inner rounds carry
    ``row_sub`` in."""
    (t, owned, pool_pbj, used, has_queue, wsv, alloc_prev, rise_i,
     next_row, w_sub, w_sz, w_rt, run, done, start_t, end_t, acc) = core
    tr_submit = ctx["tr_submit"]
    tr_size, tr_runtime = ctx["tr_size"], ctx["tr_runtime"]
    K = w_sub.shape[0]
    Jp = tr_submit.shape[0]        # includes >= K pad rows (submit = +inf)
    f = w_sub.dtype
    inf = jnp.asarray(jnp.inf, f)
    zero = jnp.zeros((), f)
    lanes = jnp.arange(K)
    # --- compact done lanes out of the window (stacked gather) and
    # admit the next table rows into the freed tail as contiguous
    # dynamic-slice reads. When the table is exhausted the slice
    # start clamps into the +inf padding block, so admitted lanes
    # read pad rows — never a duplicate of a live row.
    (run_c, start_t, end_t, w_sub, w_sz, w_rt), n_keep = \
        stable_compact(~done, [run, start_t, end_t, w_sub, w_sz, w_rt],
                       [False, zero, zero, inf, zero, zero])
    run = run_c
    done = jnp.zeros(K, bool)
    adm_start = next_row - n_keep
    tail = lanes >= n_keep
    w_sub = jnp.where(tail, jax.lax.dynamic_slice(tr_submit,
                                                  (adm_start,), (K,)),
                      w_sub)
    w_sz = jnp.where(tail, jax.lax.dynamic_slice(tr_size,
                                                 (adm_start,), (K,)),
                     w_sz)
    w_rt = jnp.where(tail, jax.lax.dynamic_slice(tr_runtime,
                                                 (adm_start,), (K,)),
                     w_rt)
    next_row = jnp.minimum(next_row + (K - n_keep),
                           Jp).astype(jnp.int32)
    row_sub = tr_submit[jnp.minimum(next_row, Jp - 1)]
    inner = (t, owned, pool_pbj, used, has_queue, wsv, alloc_prev,
             rise_i, row_sub, w_sub, w_sz, w_rt, run, done, start_t,
             end_t, acc)
    # The FB kill size classes depend only on the window contents,
    # which change at compactions — computed once per chunk, not
    # once per round.
    szcls = size_classes(w_sz)
    for _ in range(spec.compact_every):  # unrolled: XLA fuses the rounds
        inner = _round_body(policy, ctx, spec, inner, szcls)
    (t, owned, pool_pbj, used, has_queue, wsv, alloc_prev, rise_i,
     row_sub, w_sub, w_sz, w_rt, run, done, start_t, end_t,
     acc) = inner
    return (t, owned, pool_pbj, used, has_queue, wsv, alloc_prev,
            rise_i, next_row, w_sub, w_sz, w_rt, run, done, start_t,
            end_t, acc)


def _simulate_rounds(policy: str, prm: Dict, pk: PackedEventWorkloads,
                     spec: RoundsSpec) -> Dict[str, jnp.ndarray]:
    """One (point, workload) lane; vmapped over both axes by the caller.

    ``pk`` holds a single workload's rows; ``prm`` one sweep point's
    scalars plus its index ``p_idx`` into the packed WS fold tables;
    ``policy`` is static ("fb" | "flb_nub").
    """
    duration = spec.duration
    K = spec.window
    R = spec.compact_every
    ctx = _lane_ctx(policy, prm, pk)
    tr_submit = ctx["tr_submit"]
    tr_size, tr_runtime = ctx["tr_size"], ctx["tr_runtime"]
    ws0 = pk.ws0
    f = tr_submit.dtype
    zero = jnp.zeros((), f)
    ws_integral = pk.ws_integral[prm["p_idx"]]   # exact ∫ WS share
    ws_winmax = ctx["ws_winmax"]
    if policy == "fb":
        C = ctx["C"]
        owned0 = C - jnp.minimum(ws0, C)     # startup: all idle → PBJ (§5.1)
        pool0 = zero
    else:
        owned0 = jnp.maximum(ctx["B"] - ctx["lb_ws"], 1.0)  # §5.2 bound
        pool0 = owned0

    # ---- startup round at t = 0: the engine's startup() allocation
    # followed by the t = 0 submit events (no tick fires at 0), plus
    # the first lease window's peak probe (the tick-gated probe in
    # _actions starts at window 1).
    acc = {k: zero for k in ACC_KEYS + _fault_acc_keys(ctx)}
    w_sub = tr_submit[:K]
    w_sz = tr_size[:K]
    w_rt = tr_runtime[:K]
    queued0 = w_sub <= 0.0
    owned, pool_pbj, run, starts0, alloc0, acc = _actions(
        policy, ctx, spec.ff_passes, owned0, pool0, jnp.zeros(K, bool),
        zero, queued0, ws0, jnp.asarray(False), jnp.asarray(0, jnp.int32),
        w_sz, size_classes(w_sz), acc)
    if policy == "fb":
        acc["peak"] = jnp.maximum(acc["peak"],
                                  jnp.minimum(owned + ws_winmax[0], C))
    else:
        acc["peak"] = jnp.maximum(
            acc["peak"], ctx["B"] + jnp.maximum(owned - pool_pbj, 0.0)
            + ws_winmax[0])
    start_t = jnp.zeros(K, f)
    end_t = jnp.where(starts0, w_rt, jnp.zeros(K, f))
    used0 = jnp.sum(jnp.where(run, w_sz, zero))
    has_queue0 = jnp.sum(jnp.where(queued0 & ~run, 1.0, 0.0)) > 0

    outer_max = -(-spec.max_rounds // R)
    core0 = (zero, owned, pool_pbj, used0, has_queue0, ws0, alloc0,
             jnp.asarray(0, jnp.int32), jnp.asarray(K, jnp.int32),
             w_sub, w_sz, w_rt, run, jnp.zeros(K, bool), start_t, end_t,
             acc)

    def cond(carry):
        i, t = carry[0], carry[1]
        return (i < outer_max) & (t < duration)

    def chunk(carry):
        return (carry[0] + 1,) + _chunk_core(policy, ctx, spec, carry[1:])

    carry = jax.lax.while_loop(
        cond, chunk, (jnp.asarray(0, jnp.int32),) + core0)
    t_end, acc = carry[1], carry[-1]

    n_done = jnp.maximum(acc["completed"], 1.0)
    return {
        "completed_jobs": acc["completed"],
        "avg_turnaround": acc["turn_sum"] / n_done,
        "avg_execution": acc["exec_sum"] / n_done,
        "node_hours": (acc["node_seconds"] + ws_integral) / 3600.0,
        "peak_nodes": acc["peak"],
        "adjust_events": acc["adjusts"] + pk.ws_adjusts,
        "pbj_adjust_events": acc["pbj_adjusts"],
        "kills": acc["kills"],
        "window_overflow": acc["window_overflow"],
        "rounds": acc["rounds"],
        "truncated": (t_end < duration).astype(f),
        **{k: acc[k] for k in _fault_acc_keys(ctx)},
    }


def _rounds_prm_tree(policy: str, grid) -> Dict[str, jnp.ndarray]:
    """The grid's parameter tree plus each point's index into the packed
    WS fold tables (``ws_integral`` / ``ws_winmax``)."""
    prm = dict(prm_tree(policy, grid))
    prm["p_idx"] = jnp.arange(int(grid.lease.shape[0]), dtype=jnp.int32)
    return prm


@functools.lru_cache(maxsize=None)
def _rounds_lane(policy: str, spec: RoundsSpec):
    """Per-lane event-round program as a stable ``(prm, packed_row)``
    closure — the cache keys the jit caches of the batched runners."""
    def lane(prm, pk: PackedEventWorkloads):
        return _simulate_rounds(policy, prm, pk, spec)
    return lane


@functools.partial(jax.jit, static_argnames=("fb_spec", "flb_spec"))
def _rounds_grids_single(fb: Optional[FBGrid], flb: Optional[FLBGrid],
                         fb_packed: Optional[PackedEventWorkloads],
                         flb_packed: Optional[PackedEventWorkloads], *,
                         fb_spec: Optional[RoundsSpec] = None,
                         flb_spec: Optional[RoundsSpec] = None
                         ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Single-device execution: the (trace, point) grid as ONE vmap over
    the flattened W·P lanes, each lane gathering its point's parameters
    and its trace's pack row (as the sharded path does per device).
    Nested vmaps would lay the lanes out as W blocks of P rows, each
    padded to the device's tile on its own: on a TPU v5e the paper
    grid's rounds loop then took 1.35 s against 0.77 s flattened, with
    bit-identical rows. Nothing is donated: the outputs are per-lane
    metrics, so no packed buffer could be reused for them."""
    def run(policy, prm_tree, packed, spec):
        W = packed.submit.shape[0]
        P = prm_tree["p_idx"].shape[0]
        w_idx = jnp.repeat(jnp.arange(W), P)
        p_idx = jnp.tile(jnp.arange(P), W)
        prm_l = jax.tree_util.tree_map(lambda a: a[p_idx], prm_tree)
        pk_l = jax.tree_util.tree_map(lambda a: a[w_idx], packed)
        out = jax.vmap(_rounds_lane(policy, spec))(prm_l, pk_l)
        return jax.tree_util.tree_map(lambda a: a.reshape(W, P), out)

    out: Dict[str, Dict[str, jnp.ndarray]] = {}
    if fb_spec is not None:
        out["fb"] = run("fb", _rounds_prm_tree("fb", fb), fb_packed,
                        fb_spec)
    if flb_spec is not None:
        out["flb_nub"] = run("flb_nub", _rounds_prm_tree("flb_nub", flb),
                             flb_packed, flb_spec)
    return out


def rounds_grids(fb: Optional[FBGrid], flb: Optional[FLBGrid],
                 fb_packed: Optional[PackedEventWorkloads],
                 flb_packed: Optional[PackedEventWorkloads], *,
                 fb_spec: Optional[RoundsSpec] = None,
                 flb_spec: Optional[RoundsSpec] = None,
                 devices: compat.Devices = None
                 ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Evaluate FB and FLB-NUB sweep grids through the event-round
    engine. Returns ``{"fb": metrics, "flb_nub": metrics}`` with
    ``(W, P_policy)`` metric arrays; a policy is skipped when its spec
    is ``None``.

    ``devices`` (``None`` | device count | device sequence, see
    ``repro.compat.resolve_devices``) selects the backend: ``None`` /
    one device runs the flattened (trace × point) lanes as one vmapped
    program, two or more shard those lanes via
    ``lanes.sharded_grid_map`` — bit-identical rows either way, since
    every lane runs the identical per-lane program.
    """
    devs = compat.resolve_devices(devices)
    if devs is None:
        return _rounds_grids_single(fb, flb, fb_packed, flb_packed,
                                    fb_spec=fb_spec, flb_spec=flb_spec)
    if ((fb_packed is not None and fb_packed.fault_times is not None)
            or (flb_packed is not None
                and flb_packed.fault_times is not None)):
        raise NotImplementedError(
            "fault-injected packs run single-device; the sharded lane "
            "splitter predates the optional fault tables")
    out: Dict[str, Dict[str, jnp.ndarray]] = {}
    if fb_spec is not None:
        out["fb"] = sharded_grid_map(
            _rounds_lane("fb", fb_spec), _rounds_prm_tree("fb", fb),
            fb_packed, int(fb_packed.submit.shape[0]),
            int(fb.lease.shape[0]), devs)
    if flb_spec is not None:
        out["flb_nub"] = sharded_grid_map(
            _rounds_lane("flb_nub", flb_spec),
            _rounds_prm_tree("flb_nub", flb), flb_packed,
            int(flb_packed.submit.shape[0]), int(flb.lease.shape[0]), devs)
    return out
