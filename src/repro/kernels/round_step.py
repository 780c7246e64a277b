"""Fused Pallas round-step kernel for the event-rounds sweep engine.

One outer step of ``repro.sim.rounds`` — masked window compaction,
dynamic-slice job admission, the per-chunk size classes and the
``compact_every`` unrolled event rounds (multi-pass first-fit, size-class
kill selection, prefix-sum queue admission, the contended-stretch
coalescer) — executes as ONE ``pl.pallas_call`` per lane instead of the
few hundred XLA ops the traced body dispatches. The per-op dispatch
overhead at (P, K) lane sizes is the measured cost floor of the rounds
engine (see the README perf ledger); fusing the whole body into a single
kernel program attacks exactly that floor. Lanes stay ordinary vmap
axes, so the (point × trace) grid AND the ``sharded_grid_map`` backend
compose unchanged — under vmap the kernel's batch axis becomes the
Pallas grid.

Bit-equality by construction
----------------------------
The kernel body does not reimplement the round math: it reads its refs
into plain jnp values, rebuilds the same ``ctx`` dict the XLA path uses
(:func:`_ctx_from_inputs` mirrors ``rounds._lane_ctx``) and calls the
SAME :func:`repro.sim.rounds._chunk_core`. The loop state round-trips
through a float pack (:func:`pack_carry` / :func:`unpack_carry`) that is
exact for every field — bools are 0/1, the int cursors stay far below
2**24, times and node counts are already the pack dtype — so the fused
backend is bit-identical to ``kernel="xla"`` on both f32 and f64
(tests/test_round_step_kernel.py asserts equality on the packed state
after every chunk, not just on the final rows).

State layout
------------
``sc`` (``SC_SIZE``,) scalar vector: the nine loop scalars followed by
the eleven metric accumulators in ``rounds.ACC_KEYS`` order. ``win``
(``WIN_ROWS``, K) window matrix: submit / size / runtime / run / done /
start / end per lane. Inputs per lane: ``jobs`` (3, Jp) job table,
``rises`` (2, NR) FB demand-rise stops, ``wstab`` (2, NT) WS fold
tables, ``prm`` policy scalars ((2,) fb: lease, C; (6,) flb_nub: lease,
B, lb_ws, U, V, G).

Mosaic does not lower the kernel
-------------------------------
Compiled for a TPU (``interpret=False``), the body is refused: it is
``_chunk_core`` unchanged, and Pallas TPU has no lowering rule for the
``cumsum``, ``dynamic_slice`` and ``rev`` it uses (prefix sums,
admission slices, reversed class sums) nor for ``searchsorted``'s
``le_to`` (window compaction), on top of gathers over 1-D lane vectors.
A port means rewriting the round math for Mosaic's 2-D tiles, a second
implementation beside the XLA one. So the kernel runs in interpret mode
only: ``interpret`` defaults to True off-TPU, and on a TPU the compiled
kernel raises :class:`NotImplementedError` instead of interpreting
silently — use ``kernel="xla"`` there.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.sim import rounds as _rounds
from repro.sim.rounds import ACC_KEYS, RoundsSpec

# ----------------------------------------------------------- state layout

SC_T = 0            # current time (the while_loop exit test reads this)
SC_OWNED = 1
SC_POOL = 2
SC_USED = 3
SC_HAS_QUEUE = 4    # bool as 0/1
SC_WSV = 5
SC_ALLOC_PREV = 6
SC_RISE_I = 7       # int cursor as float (exact < 2**24)
SC_NEXT_ROW = 8     # int cursor as float (exact < 2**24)
SC_ACC0 = 9         # first of the len(ACC_KEYS) accumulators
SC_SIZE = SC_ACC0 + len(ACC_KEYS)

WIN_SUB, WIN_SZ, WIN_RT, WIN_RUN, WIN_DONE, WIN_START, WIN_END = range(7)
WIN_ROWS = 7


def pack_carry(core) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """17-tuple loop state → ``(sc (SC_SIZE,), win (WIN_ROWS, K))``."""
    (t, owned, pool_pbj, used, has_queue, wsv, alloc_prev, rise_i,
     next_row, w_sub, w_sz, w_rt, run, done, start_t, end_t, acc) = core
    f = w_sub.dtype
    sc = jnp.stack([jnp.asarray(v, f) for v in
                    (t, owned, pool_pbj, used, has_queue, wsv, alloc_prev,
                     rise_i, next_row)]
                   + [jnp.asarray(acc[k], f) for k in ACC_KEYS])
    win = jnp.stack([w_sub, w_sz, w_rt, run.astype(f), done.astype(f),
                     start_t, end_t])
    return sc, win


def unpack_carry(sc: jnp.ndarray, win: jnp.ndarray):
    """Inverse of :func:`pack_carry` — exact for every field."""
    acc = {k: sc[SC_ACC0 + i] for i, k in enumerate(ACC_KEYS)}
    return (sc[SC_T], sc[SC_OWNED], sc[SC_POOL], sc[SC_USED],
            sc[SC_HAS_QUEUE] > 0, sc[SC_WSV], sc[SC_ALLOC_PREV],
            sc[SC_RISE_I].astype(jnp.int32),
            sc[SC_NEXT_ROW].astype(jnp.int32),
            win[WIN_SUB], win[WIN_SZ], win[WIN_RT],
            win[WIN_RUN] > 0, win[WIN_DONE] > 0,
            win[WIN_START], win[WIN_END], acc)


def lane_inputs(policy: str, ctx: Dict) -> Tuple[jnp.ndarray, jnp.ndarray,
                                                 jnp.ndarray, jnp.ndarray]:
    """One lane's ``rounds._lane_ctx`` dict → the kernel's four stacked
    input arrays ``(jobs, rises, wstab, prm)``."""
    jobs = jnp.stack([ctx["tr_submit"], ctx["tr_size"], ctx["tr_runtime"]])
    rises = jnp.stack([ctx["rise_times"], ctx["rise_vals"]])
    wstab = jnp.stack([ctx["ws_winmax"], ctx["ws_at_tick"]])
    f = jobs.dtype
    if policy == "fb":
        prm = jnp.stack([ctx["L"].astype(f), ctx["C"].astype(f)])
    else:
        prm = jnp.stack([ctx[k].astype(f)
                         for k in ("L", "B", "lb_ws", "U", "V", "G")])
    return jobs, rises, wstab, prm


def _ctx_from_inputs(policy: str, jobs, rises, wstab, prm) -> Dict:
    """Rebuild the ``rounds._lane_ctx`` dict from the stacked kernel
    inputs — the exact inverse of :func:`lane_inputs`, so the kernel
    body feeds ``_chunk_core`` the same values the XLA path does."""
    ctx = {
        "L": prm[0],
        "tr_submit": jobs[0], "tr_size": jobs[1], "tr_runtime": jobs[2],
        "rise_times": rises[0], "rise_vals": rises[1],
        "ws_winmax": wstab[0], "ws_at_tick": wstab[1],
    }
    if policy == "fb":
        ctx["C"] = prm[1]
    else:
        ctx["B"], ctx["lb_ws"], ctx["U"], ctx["V"], ctx["G"] = (
            prm[1], prm[2], prm[3], prm[4], prm[5])
    return ctx


# ------------------------------------------------------------- the kernel

@functools.lru_cache(maxsize=None)
def _chunk_kernel(policy: str, spec: RoundsSpec):
    """The fused kernel body for one (policy, spec): read refs, rebuild
    ctx, run the shared ``_chunk_core``, write the packed state back.
    Cached so repeated traces reuse one function object (the jit caches
    above this key on (policy, spec) too — see ``rounds._rounds_lane``)."""

    def kernel(jobs_ref, rises_ref, wstab_ref, prm_ref, sc_ref, win_ref,
               sc_out_ref, win_out_ref):
        ctx = _ctx_from_inputs(policy, jobs_ref[...], rises_ref[...],
                               wstab_ref[...], prm_ref[...])
        core = unpack_carry(sc_ref[...], win_ref[...])
        core = _rounds._chunk_core(policy, ctx, spec, core)
        sc, win = pack_carry(core)
        sc_out_ref[...] = sc
        win_out_ref[...] = win

    return kernel


def chunk_step(jobs, rises, wstab, prm, sc, win, *, policy: str,
               spec: RoundsSpec, interpret: Optional[bool] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One fused outer step: compaction + admission + size classes +
    ``spec.compact_every`` rounds, as a single ``pallas_call``. Under
    vmap the lane axis becomes the Pallas grid. The compiled kernel
    (``interpret=False``, the default on a TPU) is refused, see the
    module docstring."""
    if interpret is None:
        from repro.kernels.ops import _default_interpret
        interpret = _default_interpret()
    if not interpret:
        raise NotImplementedError(
            "the fused Pallas round step does not compile for TPU: Mosaic "
            "has no lowering for cumsum, dynamic_slice, rev and "
            "searchsorted (le_to) in rounds._chunk_core; run the rounds "
            "engine with kernel=\"xla\"")
    return pl.pallas_call(
        _chunk_kernel(policy, spec),
        out_shape=[jax.ShapeDtypeStruct(sc.shape, sc.dtype),
                   jax.ShapeDtypeStruct(win.shape, win.dtype)],
        interpret=interpret,
    )(jobs, rises, wstab, prm, sc, win)


def chunk_step_ref(jobs, rises, wstab, prm, sc, win, *, policy: str,
                   spec: RoundsSpec, interpret: Optional[bool] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Unfused reference with the kernel's exact signature: the same
    pack → ``_chunk_core`` → unpack round-trip as plain traced jnp ops
    (a few hundred XLA dispatches). The bit-equality tests and the
    ``roundstep`` microbenchmark diff :func:`chunk_step` against this."""
    del interpret
    ctx = _ctx_from_inputs(policy, jobs, rises, wstab, prm)
    core = unpack_carry(sc, win)
    return pack_carry(_rounds._chunk_core(policy, ctx, spec, core))
