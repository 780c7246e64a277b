"""Batched ``lax.scan`` fast path for the stateful PhoenixCloud policies.

The sweep engine (``repro.sim.sweep``) batches the *stateless* baselines
(DCS, EC2+RightScale) as exact vectorized jnp programs, but the paper's
headline grids sweep the two *stateful* coordinated policies — FB
capacity C for Fig. 13 and the FLB-NUB lease unit L for Fig. 18 — and
those used to fall back to one Python event simulation per point. This
module re-expresses both policies as one jitted, twice-vmapped
``lax.scan`` so a whole (system, parameter, trace) grid runs as a single
XLA program: axis 0 batches packed workload traces, axis 1 batches sweep
points. With ``devices`` set, ``scan_grids`` flattens the two batch axes
into one lane axis and ``shard_map``s it across host devices (padding
lanes to a device multiple, dropping the padding from the results), so
the grid's throughput scales with the machine instead of one core's
SIMD width — on CPU-only hosts, split the cores into XLA devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

Design (the scan-friendly queue/kill encoding)
----------------------------------------------

* **Job table with status lanes.** Jobs live in a fixed-size *window* of
  ``K`` lanes over the arrival-sorted job table: per lane a ``running``
  and a ``done`` flag, a remaining-runtime value and a start time.
  "Queued" is *derived* (submitted ∧ ¬running ∧ ¬done), so an FB kill is
  a masked flag flip — the killed lane is instantly queued again at its
  arrival-order position, and its runtime is re-read from the job table
  on the next start (kills need no list surgery).
* **Sliding window.** The window only ever needs to span the oldest
  unfinished job to the newest submitted one; the head advances past
  completed lanes once per chunk (one lease tick), when the next ``K``
  table rows are re-gathered. Completions fold into scalar accumulators
  (completed count, turnaround/execution sums) the substep they happen,
  so nothing outside the window is carried. A diagnostic counts the
  steps on which the backlog outgrew the window (``window_overflow``;
  0 on the paper workloads at the default ``K``).
* **Vectorized first-fit.** The §6.5.2 first-fit queue scan is a few
  *filtered-prefix* passes instead of a sequential per-job scan: each
  pass starts every candidate (queued, fits in free) whose exclusive
  prefix-sum of candidate sizes still fits. A pass never overcommits
  (the prefix bound is conservative) and each pass starts at least the
  first schedulable job, so a small fixed number of passes converges to
  the event engine's first-fit up to rare one-substep start delays.
* **FB kills as a size threshold.** §5.1 rule 2 kills smallest-size
  first. The scan encodes the kill order as power-of-two size classes:
  class sums pick the threshold class, classes strictly below it are
  killed outright, and the remainder is taken from the threshold class
  newest-arrival-first via a reversed prefix sum. This matches the event
  engine's ordering exactly up to ties inside one size class (which the
  event engine breaks by latest *start*, not latest arrival).
* **Time discretization.** Like ``repro.core.jaxsim``: job dynamics
  advance on substeps of ``dt``; policy actions (pool flow, U/V/G
  adjust, FB tick grants) fire when a substep crosses a lease boundary,
  detected per point as a ``floor(t/L)`` increment so the lease axis L
  is *traced* (Fig. 18 sweeps it inside the batch). Completions round to
  the *nearest* substep (unbiased), and each policy runs at its own
  granularity: FB's allocation hugs C between WS moves so ``FB_DT``
  is coarse; the FLB-NUB U/V/G feedback needs ``FLB_DT`` (both
  validated against the event engine at these settings).
* **Event-faithful tick ordering.** Within an FLB-NUB tick substep the
  event engine's sequence is pool grant → first-fit → U/V/G adjust →
  first-fit again on the request grant, and the scan replays exactly
  that: the adjustment reads *post-start* demand and free. Evaluating
  U/V/G on pre-start state looks harmless but lets one tick absorb a
  whole submit burst as a single DR1 request the event engine would
  have started incrementally — >50 % peak overshoot on long-lease
  (L ≥ 2 h) grids under scaled WS demand.

Fidelity contract (cross-validated in tests/test_sweep.py): completed
jobs within 2 %, node-hours within 15 %, peak within 15 % of the event
engine, and identical parameter-sweep orderings (J1/J2 trends). Adjust-
event counts are trend-faithful approximations of the event ledger.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro import compat
from repro.core.jobs import Job
from repro.core.pbj_manager import PBJPolicyParams
from repro.core.profiles import sample_steps, step_points

# PBJPolicyParams is defined jax-free in core (the event engine imports
# with numpy alone); its pytree registration lives here with the other
# scan pytrees.
jax.tree_util.register_dataclass(
    PBJPolicyParams,
    data_fields=["request_threshold", "release_threshold", "elastic_factor"],
    meta_fields=["checkpoint_preempt"])

__all__ = [
    "FBGrid", "FLBGrid", "PackedWorkloads", "ScanSpec", "pack_workloads",
    "pack_job_table", "resolve_pack_dtype", "scan_grids", "pick_dt",
    "fb_actions", "flb_actions", "compact_window", "sharded_grid_map",
    "DEFAULT_WINDOW", "DEFAULT_SUBSTEPS", "DEFAULT_FF_PASSES",
    "FB_DT", "FLB_DT", "FLB_MIN_DT",
]

DEFAULT_WINDOW = 192       # job-table lanes carried through the scan
FB_WINDOW = 192            # FB backlog is capacity-bound (≤ 158 unfinished
#                            jobs on the §6.2 traces at the Fig-13
#                            capacities — SDSC BLUE at C=128) and the
#                            window additionally buffers a whole chunk of
#                            arrivals; 160 overflowed there, which the
#                            window_overflow warning now surfaces
FLB_WINDOW = 128           # FLB-NUB leases elastically, so its backlog is
#                            small; the window mostly buffers fresh arrivals
DEFAULT_SUBSTEPS = 12      # substeps per base lease (dt = base_lease / 12)
DEFAULT_FF_PASSES = 2      # filtered-prefix first-fit passes per substep
FB_DT = 900.0              # default FB substep: alloc ≈ C between WS moves,
#                            so FB tolerates a coarse grid (nh < 1 %)
FLB_DT = 300.0             # default FLB-NUB substep: the U/V/G feedback
#                            needs fine demand sampling (validated bound)
FLB_MIN_DT = 60.0          # floor of the WS-spacing cap in pick_dt — a
#                            pathological 1 s demand trace must not explode
#                            the substep count by four orders of magnitude
_KILL_CLASSES = 16         # power-of-two size classes for the FB kill order


@dataclasses.dataclass(frozen=True)
class ScanSpec:
    """Static (hashable) execution parameters of one policy's scan: the
    substep ``dt``, the horizon in substeps, the job-window size and the
    re-gather cadence. One spec per policy, so FB can run its coarse
    grid while FLB-NUB runs the fine one in the same jitted call."""

    n_steps: int
    dt: float
    window: int = DEFAULT_WINDOW
    chunk_len: int = DEFAULT_SUBSTEPS
    ff_passes: int = DEFAULT_FF_PASSES


# ------------------------------------------------------------------ pytrees

@dataclasses.dataclass(frozen=True)
class FBGrid:
    """FB sweep points (§5.1): per-point capacity C and lease unit L."""

    capacity: jnp.ndarray     # (P,)
    lease: jnp.ndarray        # (P,)


@dataclasses.dataclass(frozen=True)
class FLBGrid:
    """FLB-NUB sweep points (§5.2): B, lb_ws, U, V, G and lease L."""

    B: jnp.ndarray            # (P,)
    lb_ws: jnp.ndarray        # (P,)
    U: jnp.ndarray            # (P,)
    V: jnp.ndarray            # (P,)
    G: jnp.ndarray            # (P,)
    lease: jnp.ndarray        # (P,)


@dataclasses.dataclass(frozen=True)
class PackedWorkloads:
    """Fixed-size arrays for W workloads: arrival-sorted job tables padded
    to a common length (padding rows have ``submit = +inf``, size 0) plus
    the per-substep WS demand profile and per-chunk submit frontiers."""

    submit: jnp.ndarray       # (W, J + K) — padded past the table end too
    size: jnp.ndarray         # (W, J + K)
    runtime: jnp.ndarray      # (W, J + K)
    ws: jnp.ndarray           # (W, S) demand sampled at each substep END —
    #                           a change landing exactly on a tick applies
    #                           before the tick, like the event engine
    ws0: jnp.ndarray          # (W,) demand at t = 0 (startup allocation)
    ws_changed: jnp.ndarray   # (W, S) bool: demand differs from prev substep
    hi_chunk: jnp.ndarray     # (W, n_chunks) jobs submitted by chunk end
    n_jobs: jnp.ndarray       # (W,) real (unpadded) job counts


for _cls, _fields in ((FBGrid, ["capacity", "lease"]),
                      (FLBGrid, ["B", "lb_ws", "U", "V", "G", "lease"]),
                      (PackedWorkloads, ["submit", "size", "runtime", "ws",
                                        "ws0", "ws_changed", "hi_chunk",
                                        "n_jobs"])):
    jax.tree_util.register_dataclass(_cls, data_fields=_fields,
                                     meta_fields=[])


# ------------------------------------------------------------------ packing

# Canonical copy lives in repro.compat; re-exported here because every
# pack caller historically imports it from the scan module.
resolve_pack_dtype = compat.resolve_pack_dtype


def pack_job_table(workloads: Sequence[Tuple[Sequence[Job],
                                             Sequence[Tuple[float, int]]]],
                   window: int, dtype: np.dtype
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                              np.ndarray]:
    """Arrival-sorted job tables padded to a common length plus a full
    window of trailing padding rows (``submit = +inf``, size 0) so the
    window can slide past the table end. Shared by the fixed-dt scan
    pack and the event-round pack (``repro.sim.rounds``). Returns
    ``(submit, size, runtime, n_jobs)`` as numpy arrays of shape
    ``(W, max_jobs + window)`` / ``(W,)``.
    """
    max_jobs = max(len(jobs) for jobs, _ in workloads)
    J = max_jobs + window                      # window can slide past the end
    submit = np.full((len(workloads), J), np.inf, dtype)
    size = np.zeros((len(workloads), J), dtype)
    runtime = np.zeros((len(workloads), J), dtype)
    n_jobs = np.zeros(len(workloads), np.int32)
    for w, (jobs, _) in enumerate(workloads):
        order = sorted(jobs, key=lambda j: j.submit)
        n_jobs[w] = len(order)
        submit[w, :len(order)] = [j.submit for j in order]
        size[w, :len(order)] = [j.size for j in order]
        runtime[w, :len(order)] = [j.runtime for j in order]
    return submit, size, runtime, n_jobs


def pack_workloads(workloads: Sequence[Tuple[Sequence[Job],
                                             Sequence[Tuple[float, int]]]],
                   duration: float, dt: float,
                   window: int = DEFAULT_WINDOW,
                   chunk_len: int = DEFAULT_SUBSTEPS,
                   dtype: Optional[np.dtype] = None
                   ) -> Tuple[PackedWorkloads, int]:
    """Pack ``(jobs, ws_trace)`` workloads into stacked scan arrays.

    Returns ``(packed, n_steps)`` where ``n_steps = ceil(duration / dt)``
    (the scan itself runs ``n_chunks * chunk_len >= n_steps`` substeps;
    the overhang is masked out). ``dtype`` defaults to the active jax
    x64 setting, like :func:`repro.core.jaxsim.pack_trace`.
    """
    dtype = resolve_pack_dtype(dtype)
    n_steps = int(np.ceil(duration / dt))
    n_chunks = -(-n_steps // chunk_len)
    s_pad = n_chunks * chunk_len
    submit, size, runtime, n_jobs = pack_job_table(workloads, window, dtype)
    ws = np.zeros((len(workloads), s_pad), dtype)
    ws0 = np.zeros(len(workloads), dtype)
    hi_chunk = np.zeros((len(workloads), n_chunks), np.int32)
    for w, (jobs, ws_trace) in enumerate(workloads):
        times, values = step_points(ws_trace, duration)
        prof = sample_steps(times, values, np.arange(1, n_steps + 1) * dt)
        ws[w, :n_steps] = prof.astype(dtype)
        ws0[w] = values[0]
        chunk_end_t = (np.arange(1, n_chunks + 1) * chunk_len) * dt
        hi_chunk[w] = np.searchsorted(submit[w, :n_jobs[w]], chunk_end_t,
                                      side="right")
    ws_changed = np.zeros(ws.shape, bool)
    ws_changed[:, 1:] = ws[:, 1:] != ws[:, :-1]
    ws_changed[:, 0] = ws[:, 0] != ws0
    return PackedWorkloads(
        submit=jnp.asarray(submit), size=jnp.asarray(size),
        runtime=jnp.asarray(runtime), ws=jnp.asarray(ws),
        ws0=jnp.asarray(ws0), ws_changed=jnp.asarray(ws_changed),
        hi_chunk=jnp.asarray(hi_chunk), n_jobs=jnp.asarray(n_jobs)), n_steps


# ---------------------------------------------------------- scan primitives

def _first_fit(free, queued, size, passes: int):
    """Vectorized §6.5.2 first-fit: ``passes`` filtered-prefix rounds.

    Each round admits every candidate whose exclusive prefix sum of
    *candidate* sizes still fits — a conservative bound (candidates it
    counts are a superset of what actually starts), so the admitted set
    never overcommits, and the earliest schedulable job always starts.
    """
    started = jnp.zeros_like(queued)
    for _ in range(passes):
        cand = queued & ~started & (size <= free)
        sz = jnp.where(cand, size, jnp.zeros_like(size))
        prefix = jnp.cumsum(sz) - sz
        start = cand & (prefix + size <= free)
        free = free - jnp.sum(jnp.where(start, size, jnp.zeros_like(size)))
        started = started | start
    return free, started


def _size_classes(size):
    """Power-of-two size classes encoding the §5.1 kill priority (small
    first). Returns ``(cls, class_masks)`` where ``class_masks`` is the
    ``(_KILL_CLASSES, K)`` membership mask — the per-class sums reduce
    over a masked stack, which XLA:CPU executes an order of magnitude
    faster inside a loop body than the equivalent (K, C) matmul.

    The class is ``ceil(log2(size))`` clipped to the class range, counted
    by exact comparisons with the powers of two: a transcendental
    ``log2`` is not exact at powers of two on every backend (TPU), and a
    class off by one reorders the kills."""
    cls = sum((size > float(2 ** c)).astype(jnp.int32)
              for c in range(_KILL_CLASSES - 1))
    class_masks = cls[None, :] == jnp.arange(_KILL_CLASSES)[:, None]
    return cls, class_masks


def _kill_selection(running, size, cls, class_masks, kill_need):
    """§5.1 rule 2 kill set: smallest size class first, newest-arrival
    first inside the threshold class, until ``kill_need`` nodes free."""
    run_sz = jnp.where(running, size, jnp.zeros_like(size))
    class_sum = jnp.sum(jnp.where(class_masks, run_sz[None, :],
                                  jnp.zeros_like(size)[None, :]),
                        axis=-1)                            # (_KILL_CLASSES,)
    below = jnp.concatenate([jnp.zeros(1, size.dtype),
                             jnp.cumsum(class_sum)[:-1]])  # freed below class c
    # Threshold class: first class whose cumulative sum covers the need.
    covered = below + class_sum >= kill_need
    thresh = jnp.argmax(covered)          # all-False → 0, but then need == 0
    kill_all = running & (cls < thresh)
    # Partial kills inside the threshold class, newest arrival first.
    rem_need = jnp.maximum(kill_need - below[thresh], 0.0)
    in_thr = running & (cls == thresh)
    thr_sz = jnp.where(in_thr, size, jnp.zeros_like(size))
    rev_prefix = jnp.cumsum(thr_sz[::-1])[::-1] - thr_sz
    kill_thr = in_thr & (rev_prefix < rem_need)
    killed = jnp.where(kill_need > 0, kill_all | kill_thr,
                       jnp.zeros_like(running))
    return killed


# ------------------------------------------------ shared policy-step helpers
#
# One instant of each policy's §5 rules, expressed over the window lanes.
# Both time discretizations drive these: the fixed-dt substep below feeds
# them its substep state, and the event-round engine (repro.sim.rounds)
# feeds them exact event times. Runtime bookkeeping (remaining-time vs
# absolute end-time) stays with the caller, which applies ``starts`` /
# ``killed`` to its own encoding.

def fb_actions(C, owned, run, used, queued, wsv, w_sz, w_cls, w_cls_masks,
               is_tick, ff_passes: int):
    """§5.1 rules 2–4 at one instant: WS reclaim (killing smallest-first
    when idle nodes don't cover the demand rise), the on-tick grant of
    all idle resources to the PBJ TRE, and the arrival-order first-fit.

    Returns ``(owned, run, starts, killed, alloc, pbj_ev)``; ``run`` in
    the result excludes ``killed`` and includes ``starts``.
    """
    ws_t = jnp.minimum(wsv, C)
    need = jnp.maximum(owned - (C - ws_t), 0.0)
    free = owned - used
    kill_need = jnp.minimum(jnp.maximum(need - free, 0.0), used)
    killed = _kill_selection(run, w_sz, w_cls, w_cls_masks, kill_need)
    run = run & ~killed          # killed lanes re-queue derived
    used = used - jnp.sum(jnp.where(killed, w_sz, jnp.zeros_like(w_sz)))
    owned = owned - need
    idle = jnp.maximum(C - ws_t - owned, 0.0)
    grant = jnp.where(is_tick, idle, 0.0)
    owned = owned + grant
    f = w_sz.dtype
    pbj_ev = (grant > 0).astype(f) + (need > 0).astype(f)
    alloc = owned + ws_t
    free = owned - used
    _, starts = _first_fit(free, queued, w_sz, ff_passes)
    run = run | starts
    return owned, run, starts, killed, alloc, pbj_ev


def flb_actions(B, lb_ws, U, V, G, owned, pool_pbj, run, used, queued,
                wsv, w_sz, is_tick, ff_passes: int):
    """§5.2 rules 2–4 at one instant, in the event engine's tick order:
    pool grant → first-fit → U/V/G adjust on *post-start* demand and
    free → second first-fit on the request grant (evaluating the rules
    on pre-start state lets one tick absorb a whole submit burst as a
    single DR1 request — the long-lease peak overshoot fixed in PR 3).

    Returns ``(owned, pool_pbj, run, starts, alloc, pbj_ev)`` where
    ``starts`` is the union of both first-fit passes (same instant, so
    the caller's start-time bookkeeping is identical for both).
    """
    pool_ws = jnp.minimum(wsv, lb_ws)
    pool_idle = jnp.maximum(B - pool_ws - pool_pbj, 0.0)
    grant = jnp.where(is_tick, pool_idle, 0.0)
    owned = owned + grant
    pool_pbj = pool_pbj + grant
    free = owned - used
    _, starts = _first_fit(free, queued, w_sz, ff_passes)
    run = run | starts
    queued = queued & ~starts
    used = used + jnp.sum(jnp.where(starts, w_sz, jnp.zeros_like(w_sz)))
    demand = jnp.sum(jnp.where(queued, w_sz, jnp.zeros_like(w_sz)))
    ratio = jnp.where(owned > 0, demand / jnp.maximum(owned, 1.0),
                      jnp.where(demand > 0, jnp.inf, 0.0))
    biggest = jnp.max(jnp.where(queued, w_sz, jnp.zeros_like(w_sz)))
    free = owned - used
    dr1 = jnp.maximum(demand - owned, 0.0)
    dr2 = jnp.maximum(biggest - free, 0.0)
    req = jnp.where(is_tick & (ratio > U), dr1,
                    jnp.where(is_tick & (biggest > owned), dr2, 0.0))
    rss = jnp.where(is_tick & (ratio < V) & (req == 0.0),
                    jnp.floor(G * jnp.maximum(free, 0.0)), 0.0)
    owned = owned + req - rss
    pool_pbj = jnp.minimum(pool_pbj, owned)       # leased released first
    f = w_sz.dtype
    pbj_ev = (req > 0).astype(f) + (rss > 0).astype(f)
    alloc = B + jnp.maximum(owned - pool_pbj, 0.0) \
        + jnp.maximum(wsv - lb_ws, 0.0)
    free = owned - used
    _, starts2 = _first_fit(free, queued, w_sz, ff_passes)
    run = run | starts2
    return owned, pool_pbj, run, starts | starts2, alloc, pbj_ev


def rank_by_search(cs, q):
    """``searchsorted(cs, q, "left")`` as a binary search: one gather of
    ``cs`` per level, ⌈log2(K + 1)⌉ levels."""
    return jnp.searchsorted(cs, q)


def rank_by_compare(cs, q):
    """``searchsorted(cs, q, "left")`` as ``Σ_j [cs[j] < q]``: K × len(q)
    compares summed in one fusion, no gather."""
    return jnp.searchsorted(cs, q, method="compare_all")


def stable_compact(keep, arrays, fills):
    """Stable partition: kept lanes move to the head in lane order, the
    tail reads ``fills``. One stacked *gather* moves every array at once
    — XLA:CPU runs the equivalent scatter an order of magnitude slower
    inside a loop body, and this compaction sits on the hot path of the
    event-round engine (every few rounds) as well as the scan's chunk
    boundary. Arrays are cast through the float dtype of the first
    array (lane payloads are flags, times and small ints — all exact in
    it). Returns ``(compacted_arrays, n_keep)``.
    """
    K = keep.shape[0]
    f = next((a.dtype for a in arrays if a.dtype.kind == "f"),
             arrays[0].dtype)
    cs = jnp.cumsum(keep)
    n_keep = cs[-1]
    # src[i] = index of the (i+1)-th kept lane (searchsorted over the
    # monotone keep-prefix), valid for lanes < n_keep.
    # arange(K) + 1 (not arange(1, K + 1)): the latter lowers to a
    # captured numpy constant under Pallas tracing; the former is a
    # staged iota, identical values either way.
    # On the TPU each level of the binary search is a batched gather, and
    # those gathers dominate the rounds loop; the compare-and-count is
    # one fusion there. The CPU runs the search an order of magnitude
    # faster. The branch is chosen for the platform being lowered for,
    # so an ahead-of-time compile for a TPU takes the TPU branch.
    src = jnp.minimum(
        jax.lax.platform_dependent(cs, jnp.arange(K) + 1,
                                   tpu=rank_by_compare,
                                   default=rank_by_search), K - 1)
    valid = jnp.arange(K) < n_keep
    stacked = jnp.stack([a.astype(f) for a in arrays])
    moved = stacked[:, src]
    fill_col = jnp.stack([jnp.asarray(fill, f).reshape(())
                          for fill in fills])[:, None]
    out = jnp.where(valid[None, :], moved, fill_col)
    return [out[i].astype(a.dtype) for i, a in enumerate(arrays)], n_keep


def compact_window(keep, jidx, next_row, Jp: int, fields):
    """Compact kept lanes to the window head (stable, so lane order
    stays arrival order) and admit the next job-table rows into the
    freed tail. ``fields`` is a sequence of ``(array, fill)`` pairs
    compacted alongside ``jidx``; admitted lanes read ``fill`` until
    their table row is gathered. Returns ``(jidx, next_row, compacted)``.
    """
    K = jidx.shape[0]
    lanes = jnp.arange(K, dtype=jnp.int32)
    arrays, n_keep = stable_compact(
        keep, [jidx] + [a for a, _ in fields],
        [0] + [fill for _, fill in fields])
    fresh = jnp.minimum(next_row + lanes - n_keep, Jp - 1)
    jidx = jnp.where(lanes >= n_keep, fresh, arrays[0])
    next_row = jnp.minimum(next_row + (K - n_keep), Jp - 1)
    return jidx, next_row, arrays[1:]


# ------------------------------------------------------------- the scan core

def _simulate(policy: str, prm: Dict, tr_submit, tr_size, tr_runtime,
              tr_ws, tr_ws0, tr_ws_changed, tr_hi, spec: ScanSpec) -> Dict:
    """One (point, workload) pair; vmapped over both axes by the caller.

    All array args are a single workload's lanes; ``prm`` holds one sweep
    point's scalars. ``policy`` is static ("fb" | "flb_nub").
    """
    n_steps, dt = spec.n_steps, spec.dt
    chunk_len, ff_passes = spec.chunk_len, spec.ff_passes
    K = spec.window
    n_chunks = tr_ws.shape[0] // chunk_len
    Jp = tr_submit.shape[0]        # includes >= K pad rows (submit = +inf)
    f = tr_ws.dtype
    L = prm["lease"].astype(f)
    ws0 = tr_ws0
    if policy == "fb":
        C = prm["capacity"].astype(f)
        owned0 = C - jnp.minimum(ws0, C)     # startup: all idle → PBJ (§5.1)
        pool0 = jnp.zeros((), f)
    else:
        B = prm["B"].astype(f)
        lb_ws = prm["lb_ws"].astype(f)
        U, V, G = (prm[k].astype(f) for k in ("U", "V", "G"))
        owned0 = jnp.maximum(B - lb_ws, 1.0)  # startup lower bound (§5.2)
        pool0 = owned0

    def make_substep(w_sub, w_sz, w_rt, w_cls, w_onehot):
      def substep(carry, xs):
        s_idx, wsv, ws_chg = xs
        (owned, pool_pbj, run, done, rem, start_t, acc) = carry
        t = (s_idx + 1.0) * dt
        active = s_idx < n_steps
        is_tick = active & (jnp.floor(t / L) > jnp.floor(s_idx * dt / L))

        # 1. Advance running jobs one substep; fold completions into the
        # scalar accumulators the moment they happen.
        rem = jnp.where(run & active, rem - dt, rem)
        completing = run & (rem <= 0.5 * dt) & active
        run = run & ~completing
        done = done | completing
        acc["completed"] += jnp.sum(completing)
        acc["turn_sum"] += jnp.sum(jnp.where(completing, t - w_sub, 0.0))
        acc["exec_sum"] += jnp.sum(jnp.where(completing, t - start_t, 0.0))

        queued = active & (w_sub <= t) & ~run & ~done
        used = jnp.sum(jnp.where(run, w_sz, 0.0))

        if policy == "fb":
            # 2-4. §5.1 WS reclaim (kills) → tick grant → first-fit; the
            # event engine applies WS changes before tick grants, and
            # fb_actions replays that order.
            owned, run, starts, killed, alloc, pbj_ev = fb_actions(
                C, owned, run, used, queued, wsv, w_sz, w_cls, w_onehot,
                is_tick, ff_passes)
            acc["kills"] += jnp.sum(killed)
        else:
            # 2-4. §5.2 pool grant → first-fit → U/V/G on post-start
            # state → first-fit (the event engine's tick order).
            owned, pool_pbj, run, starts, alloc, pbj_ev = flb_actions(
                B, lb_ws, U, V, G, owned, pool_pbj, run, used, queued,
                wsv, w_sz, is_tick, ff_passes)
        rem = jnp.where(starts, w_rt, rem)       # runtime read on start —
        start_t = jnp.where(starts, t, start_t)  # kills reset lazily

        # 6. Accounting (§6.1 metrics).
        alloc = jnp.where(active, alloc, 0.0)
        acc["node_seconds"] += alloc * dt
        acc["peak"] = jnp.maximum(acc["peak"], alloc)
        acc["pbj_adjusts"] += jnp.where(active, pbj_ev, 0.0)
        acc["adjusts"] += jnp.where(active, pbj_ev + ws_chg.astype(f), 0.0)
        return (owned, pool_pbj, run, done, rem, start_t, acc), None
      return substep

    lanes = jnp.arange(K, dtype=jnp.int32)

    def chunk(carry, xs):
        chunk_i, ws_c, ws_chg_c, hi_end = xs
        jidx, next_row, owned, pool_pbj, run, rem, start_t, acc = carry
        w_sub = tr_submit[jidx]
        w_sz = tr_size[jidx]
        w_rt = tr_runtime[jidx]
        substep = make_substep(w_sub, w_sz, w_rt, *_size_classes(w_sz))
        s0 = (chunk_i * chunk_len).astype(f)
        steps = (s0 + jnp.arange(chunk_len, dtype=f), ws_c, ws_chg_c)
        done = jnp.zeros(K, bool)
        (owned, pool_pbj, run, done, rem, start_t, acc), _ = jax.lax.scan(
            substep, (owned, pool_pbj, run, done, rem, start_t, acc), steps)
        # Compact finished lanes out of the window and admit the next
        # job-table rows into the freed tail. Rows are admitted ahead of
        # their submit time, so mid-chunk arrivals are already on a lane
        # when they submit.
        jidx, next_row, (run, rem, start_t) = compact_window(
            ~done, jidx, next_row, Jp,
            ((run, False), (rem, jnp.zeros((), f)),
             (start_t, jnp.zeros((), f))))
        acc["window_overflow"] += (hi_end > next_row).astype(f)
        return (jidx, next_row, owned, pool_pbj, run, rem, start_t, acc), None

    acc0 = {k: jnp.zeros((), f) for k in
            ("completed", "turn_sum", "exec_sum", "kills", "node_seconds",
             "peak", "pbj_adjusts", "adjusts", "window_overflow")}
    acc0["adjusts"] = (ws0 > 0).astype(f)   # startup WS allocation event
    carry0 = (lanes, jnp.asarray(K, jnp.int32), owned0, pool0,
              jnp.zeros(K, bool), jnp.zeros(K, f), jnp.zeros(K, f), acc0)
    xs = (jnp.arange(n_chunks, dtype=f),
          tr_ws.reshape(n_chunks, chunk_len),
          tr_ws_changed.reshape(n_chunks, chunk_len),
          tr_hi)
    carry, _ = jax.lax.scan(chunk, carry0, xs)
    acc = carry[-1]
    n_done = jnp.maximum(acc["completed"], 1.0)
    return {
        "completed_jobs": acc["completed"],
        "avg_turnaround": acc["turn_sum"] / n_done,
        "avg_execution": acc["exec_sum"] / n_done,
        "node_hours": acc["node_seconds"] / 3600.0,
        "peak_nodes": acc["peak"],
        "adjust_events": acc["adjusts"],
        "pbj_adjust_events": acc["pbj_adjusts"],
        "kills": acc["kills"],
        "window_overflow": acc["window_overflow"],
    }


@functools.lru_cache(maxsize=None)
def _scan_lane(policy: str, spec: ScanSpec):
    """The per-lane scan program as a ``(prm, packed_row) -> metrics``
    closure. Cached per (policy, spec) so the function object is stable
    across calls — it keys the jit caches of the batched runners."""
    def lane(prm, pk: PackedWorkloads):
        return _simulate(policy, prm, pk.submit, pk.size, pk.runtime,
                         pk.ws, pk.ws0, pk.ws_changed, pk.hi_chunk, spec)
    return lane


@functools.partial(jax.jit, static_argnames=("fb_spec", "flb_spec"))
def _scan_grids_single(fb: Optional[FBGrid], flb: Optional[FLBGrid],
                       fb_packed: Optional[PackedWorkloads],
                       flb_packed: Optional[PackedWorkloads], *,
                       fb_spec: Optional[ScanSpec] = None,
                       flb_spec: Optional[ScanSpec] = None
                       ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Single-device execution: the (trace, point) grid as nested vmaps."""
    def run(policy, prm_tree, packed, spec):
        lane = _scan_lane(policy, spec)
        over_points = jax.vmap(lane, in_axes=(0, None))
        over_traces = jax.vmap(over_points, in_axes=(None, 0))
        return over_traces(prm_tree, packed)

    out: Dict[str, Dict[str, jnp.ndarray]] = {}
    if fb_spec is not None:
        out["fb"] = run("fb", _prm_tree("fb", fb), fb_packed, fb_spec)
    if flb_spec is not None:
        out["flb_nub"] = run("flb_nub", _prm_tree("flb_nub", flb),
                             flb_packed, flb_spec)
    return out


def _prm_tree(policy: str, grid) -> Dict[str, jnp.ndarray]:
    if policy == "fb":
        return {"capacity": grid.capacity, "lease": grid.lease}
    return {"B": grid.B, "lb_ws": grid.lb_ws, "U": grid.U, "V": grid.V,
            "G": grid.G, "lease": grid.lease}


@functools.partial(jax.jit, static_argnames=("lane_fn", "mesh"))
def _sharded_lanes(prm_tree, packed, w_idx, p_idx, *, lane_fn, mesh):
    """Flattened (trace, point) lanes split across ``mesh``, for any
    per-lane program ``lane_fn(prm, packed_row) -> metrics``.

    ``w_idx`` / ``p_idx`` map each lane to its workload row and sweep
    point; they are sharded over the mesh's ``lanes`` axis while the
    grid and the packed workloads stay replicated, so each device
    gathers just its own lane slice and runs the plain vmapped program
    on it — no collectives, the lanes are embarrassingly parallel.
    """
    def lanes(w_l, p_l, prm, pk):
        prm_l = jax.tree_util.tree_map(lambda a: a[p_l], prm)
        pk_l = jax.tree_util.tree_map(lambda a: a[w_l], pk)
        return jax.vmap(lane_fn)(prm_l, pk_l)

    lane = PartitionSpec("lanes")
    rep = PartitionSpec()
    fn = jax.shard_map(lanes, mesh=mesh, in_specs=(lane, lane, rep, rep),
                       out_specs=lane, check_vma=False)
    return fn(w_idx, p_idx, prm_tree, packed)


def sharded_grid_map(lane_fn, prm_tree, packed, n_workloads: int,
                     n_points: int, devices) -> Dict[str, jnp.ndarray]:
    """Run ``lane_fn`` over the flattened (trace × point) lanes sharded
    across ``devices`` and reshape the metrics back to ``(W, P)``.

    Lanes are padded up to a multiple of the device count with copies of
    lane 0 (every device needs an equal shard); the padding is dropped
    before the metrics are reshaped, so padded lanes never reach a
    reported metric. Each lane runs the identical per-lane program the
    single-device path vmaps, so per-lane results do not depend on the
    device split. Shared by the fixed-dt scan and the event-round engine
    (``repro.sim.rounds``); ``lane_fn`` must be a stable (cached) object
    — it keys the jit cache.
    """
    mesh = Mesh(np.asarray(devices), ("lanes",))
    d = len(devices)
    w, p = n_workloads, n_points
    n = w * p
    pad = -n % d
    w_idx = np.concatenate([np.repeat(np.arange(w), p),
                            np.zeros(pad, np.int64)]).astype(np.int32)
    p_idx = np.concatenate([np.tile(np.arange(p), w),
                            np.zeros(pad, np.int64)]).astype(np.int32)
    flat = _sharded_lanes(prm_tree, packed, jnp.asarray(w_idx),
                          jnp.asarray(p_idx), lane_fn=lane_fn, mesh=mesh)
    # Gather host-side: a device-side slice/reshape of a lanes-sharded
    # array compiles a tiny cross-module all-gather, and XLA:CPU's
    # rendezvous can deadlock it against the still-executing sharded
    # program (observed with the long interpret-mode fused round-step
    # executable: rank 0 never reaches the rendezvous and every thread
    # parks at 0% CPU). block_until_ready serializes the two, and
    # np.asarray assembles the shards with no collective at all.
    flat = jax.block_until_ready(flat)
    return {k: np.asarray(v)[:n].reshape(w, p) for k, v in flat.items()}


def _scan_grids_sharded(fb, flb, fb_packed, flb_packed, fb_spec, flb_spec,
                        devices) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Shard each policy's (trace × point) lanes across ``devices``
    (see :func:`sharded_grid_map`)."""
    out: Dict[str, Dict[str, jnp.ndarray]] = {}
    if fb_spec is not None:
        out["fb"] = sharded_grid_map(
            _scan_lane("fb", fb_spec), _prm_tree("fb", fb), fb_packed,
            int(fb_packed.submit.shape[0]), int(fb.lease.shape[0]), devices)
    if flb_spec is not None:
        out["flb_nub"] = sharded_grid_map(
            _scan_lane("flb_nub", flb_spec), _prm_tree("flb_nub", flb),
            flb_packed, int(flb_packed.submit.shape[0]),
            int(flb.lease.shape[0]), devices)
    return out


def scan_grids(fb: Optional[FBGrid], flb: Optional[FLBGrid],
               fb_packed: Optional[PackedWorkloads],
               flb_packed: Optional[PackedWorkloads], *,
               fb_spec: Optional[ScanSpec] = None,
               flb_spec: Optional[ScanSpec] = None,
               devices: compat.Devices = None
               ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Evaluate FB and FLB-NUB sweep grids over all packed workloads in
    one jitted program. Returns ``{"fb": metrics, "flb_nub": metrics}``
    where each metric array has shape ``(W, P_policy)``; a policy is
    skipped when its spec is ``None``. Each policy runs at its own
    (static) :class:`ScanSpec` — the packs may use different substeps.

    ``devices`` (``None`` | device count | device sequence, see
    ``repro.compat.resolve_devices``) selects the execution backend:
    ``None`` / one device runs the nested-vmap program on the default
    device; two or more shard the flattened (trace × point) lane axis
    across the devices with ``shard_map``, padding the lane count up to
    a device multiple and dropping the padding from the results. The
    sharded path computes the identical per-lane program, only placed
    differently, so its rows are bit-identical to the single-device
    path's (tests/test_sweep_sharded.py pins this).
    """
    devs = compat.resolve_devices(devices)
    if devs is None:
        return _scan_grids_single(fb, flb, fb_packed, flb_packed,
                                  fb_spec=fb_spec, flb_spec=flb_spec)
    return _scan_grids_sharded(fb, flb, fb_packed, flb_packed,
                               fb_spec, flb_spec, devs)


def pick_dt(policy: str, leases: Sequence[float],
            ws_traces: Optional[Sequence[Sequence[Tuple[float, int]]]] = None,
            duration: Optional[float] = None) -> float:
    """Default substep for a policy's grid: the validated granularity
    (``FB_DT`` / ``FLB_DT``), never coarser than the shortest lease in
    the grid (so every lease gets at least one policy substep).

    For FLB-NUB the substep is additionally capped by the shortest WS
    change-point spacing across ``ws_traces`` (floored at
    ``FLB_MIN_DT``): the scan samples WS demand once per substep, and a
    demand trace finer than the substep would alias the U/V/G feedback
    the §5.2 policy runs on. Change points at or beyond ``duration`` are
    ignored — the scan never simulates them, so they must not shrink the
    substep. The paper's World Cup profile steps every 300 s — exactly
    ``FLB_DT`` — so the cap only bites on finer traces.
    """
    base = FB_DT if policy == "fb" else FLB_DT
    dt = min(base, min(leases))
    if policy == "flb_nub" and ws_traces:
        horizon = duration if duration is not None else np.inf
        spacing = min((b - a for trace in ws_traces
                       for (a, _), (b, _) in zip(trace, trace[1:])
                       if b > a and a < horizon), default=dt)
        dt = min(dt, max(spacing, FLB_MIN_DT))
    return dt
