"""Lane primitives of the batched rounds engine (``repro.sim.rounds``).

The rounds engine runs each (sweep point × workload) pair as one *lane*
over a fixed-size window of ``K`` job slots in arrival order: per slot a
``running`` and a ``done`` flag, a start and an end time. "Queued" is
derived (submitted ∧ ¬running ∧ ¬done), so an FB kill is a masked flag
flip — the killed slot is queued again at its arrival-order position
and restarts from scratch on its next start.

This module holds what the engine does to one window at one instant,
independent of how it advances time:

* **Sweep grids** — :class:`FBGrid` and :class:`FLBGrid`, the traced
  per-point parameters of §5.1 and §5.2, and :func:`prm_tree`.
* **Vectorized first-fit** — the §6.5.2 arrival-order queue scan as a
  few *filtered-prefix* passes (:func:`first_fit`): each pass starts
  every candidate whose exclusive prefix sum of candidate sizes still
  fits. A pass never overcommits, and each pass starts at least the
  first schedulable job.
* **FB kills as a size threshold** — §5.1 rule 2 kills smallest size
  first. :func:`size_classes` buckets sizes into power-of-two classes
  and :func:`kill_selection` kills every class below the threshold
  class outright and the rest of the threshold class newest arrival
  first. This matches §5.1's order except for ties inside one class,
  which §5.1 breaks by latest *start*.
* **One instant of each policy** — :func:`fb_actions` (WS reclaim and
  kills, the tick grant, first-fit) and :func:`flb_actions` (pool grant,
  first-fit, U/V/G adjust on post-start state, first-fit again).
* **Window compaction** — :func:`stable_compact`, a stable partition
  that moves the kept slots to the window head in one stacked gather.
* **Packing and placement** — :func:`pack_job_table` (arrival-sorted
  job tables padded past their end) and :func:`sharded_grid_map` (the
  flattened lanes split over several devices with ``shard_map``).

Importing this module registers :class:`PBJPolicyParams` as a pytree.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.compat import resolve_pack_dtype
from repro.core.jobs import Job
from repro.core.pbj_manager import PBJPolicyParams

# PBJPolicyParams is defined jax-free in core (the event engine imports
# with numpy alone); its pytree registration lives here with the other
# lane pytrees.
jax.tree_util.register_dataclass(
    PBJPolicyParams,
    data_fields=["request_threshold", "release_threshold", "elastic_factor"],
    meta_fields=["checkpoint_preempt"])

__all__ = [
    "FBGrid", "FLBGrid", "prm_tree", "first_fit", "size_classes",
    "kill_selection", "fb_actions", "flb_actions", "rank_by_search",
    "rank_by_compare", "stable_compact", "pack_job_table",
    "resolve_pack_dtype", "sharded_grid_map", "KILL_CLASSES",
]

KILL_CLASSES = 16          # power-of-two size classes for the FB kill order


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


for _cls, _fields in ((FBGrid, ["capacity", "lease"]),
                      (FLBGrid, ["B", "lb_ws", "U", "V", "G", "lease"])):
    jax.tree_util.register_dataclass(_cls, data_fields=_fields,
                                     meta_fields=[])


def prm_tree(policy: str, grid) -> Dict[str, jnp.ndarray]:
    """A grid's per-point parameters as a flat dict of ``(P,)`` arrays."""
    if policy == "fb":
        return {"capacity": grid.capacity, "lease": grid.lease}
    return {"B": grid.B, "lb_ws": grid.lb_ws, "U": grid.U, "V": grid.V,
            "G": grid.G, "lease": grid.lease}


# ------------------------------------------------------------------ packing

def pack_job_table(workloads: Sequence[Tuple[Sequence[Job],
                                             Sequence[Tuple[float, int]]]],
                   window: int, dtype: np.dtype
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                              np.ndarray]:
    """Arrival-sorted job tables padded to a common length plus a full
    window of trailing padding rows (``submit = +inf``, size 0) so the
    window can slide past the table end. Returns ``(submit, size,
    runtime, n_jobs)`` as numpy arrays of shape ``(W, max_jobs +
    window)`` / ``(W,)``.
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


# ------------------------------------------------------ first-fit and kills

def first_fit(free, queued, size, passes: int):
    """Vectorized §6.5.2 first-fit: ``passes`` filtered-prefix rounds.

    Each round admits every candidate whose exclusive prefix sum of
    *candidate* sizes still fits — a conservative bound (candidates it
    counts are a superset of what actually starts), so the admitted set
    never overcommits, and the earliest schedulable job always starts.
    Returns ``(free, started)``.
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


def size_classes(size):
    """Power-of-two size classes encoding the §5.1 kill priority (small
    first). Returns ``(cls, class_masks)`` where ``class_masks`` is the
    ``(KILL_CLASSES, K)`` membership mask — the per-class sums reduce
    over a masked stack, which XLA:CPU executes an order of magnitude
    faster inside a loop body than the equivalent (K, C) matmul.

    The class is ``ceil(log2(size))`` clipped to the class range, counted
    by exact comparisons with the powers of two: a transcendental
    ``log2`` is not exact at powers of two on every backend (TPU), and a
    class off by one reorders the kills."""
    cls = sum((size > float(2 ** c)).astype(jnp.int32)
              for c in range(KILL_CLASSES - 1))
    class_masks = cls[None, :] == jnp.arange(KILL_CLASSES)[:, None]
    return cls, class_masks


def kill_selection(running, size, cls, class_masks, kill_need):
    """§5.1 rule 2 kill set: smallest size class first, newest-arrival
    first inside the threshold class, until ``kill_need`` nodes free."""
    run_sz = jnp.where(running, size, jnp.zeros_like(size))
    class_sum = jnp.sum(jnp.where(class_masks, run_sz[None, :],
                                  jnp.zeros_like(size)[None, :]),
                        axis=-1)                            # (KILL_CLASSES,)
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


# ------------------------------------------------------- one policy instant
#
# One instant of each policy's §5 rules, expressed over the window slots.
# The rounds engine feeds them exact event times. Start and end times
# stay with the caller, which applies ``starts`` / ``killed`` to its own
# lane state.

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
    killed = kill_selection(run, w_sz, w_cls, w_cls_masks, kill_need)
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
    _, starts = first_fit(free, queued, w_sz, ff_passes)
    run = run | starts
    return owned, run, starts, killed, alloc, pbj_ev


def flb_actions(B, lb_ws, U, V, G, owned, pool_pbj, run, used, queued,
                wsv, w_sz, is_tick, ff_passes: int):
    """§5.2 rules 2–4 at one instant, in the event engine's tick order:
    pool grant → first-fit → U/V/G adjust on *post-start* demand and
    free → second first-fit on the request grant (evaluating the rules
    on pre-start state lets one tick absorb a whole submit burst as a
    single DR1 request, and overshoots the peak on long leases).

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
    _, starts = first_fit(free, queued, w_sz, ff_passes)
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
    _, starts2 = first_fit(free, queued, w_sz, ff_passes)
    run = run | starts2
    return owned, pool_pbj, run, starts | starts2, alloc, pbj_ev


# --------------------------------------------------------- window compaction

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
    rounds loop (every few rounds). Arrays are cast through the float
    dtype of the first float array (lane payloads are flags, times and
    small ints — all exact in it). Returns ``(compacted_arrays,
    n_keep)``.
    """
    K = keep.shape[0]
    f = next((a.dtype for a in arrays if a.dtype.kind == "f"),
             arrays[0].dtype)
    cs = jnp.cumsum(keep)
    n_keep = cs[-1]
    # src[i] = index of the (i+1)-th kept lane (searchsorted over the
    # monotone keep-prefix), valid for lanes < n_keep.
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


# ------------------------------------------------------------ sharded lanes

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
    device split. ``lane_fn`` must be a stable (cached) object — it
    keys the jit cache.
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
    # program (observed with a long-running sharded executable: rank 0
    # never reaches the rendezvous and every thread parks at 0% CPU).
    # block_until_ready serializes the two, and np.asarray assembles the
    # shards with no collective at all.
    flat = jax.block_until_ready(flat)
    return {k: np.asarray(v)[:n].reshape(w, p) for k, v in flat.items()}
