"""Parameter-sweep engine — the paper's evaluation methodology at scale.

The headline results of the paper are *sweeps*: Fig. 13 sweeps the
private-cloud capacity C to find the ~40 % configuration-size reduction,
Fig. 14 sweeps the coordinated-pool size B, and Fig. 18 sweeps the lease
time unit L against EC2+RightScale. ``run_sweep`` evaluates a whole grid
of :class:`SweepPoint`s — mixing all four systems — in one call, and
``run_sweep_workloads`` adds a second batch axis over workload traces.

Three execution paths, selected by ``mode``:

  * **Vectorized fast path** (DCS and EC2+RightScale; every mode except
    ``"event"``). Both baselines are *stateless* given the trace —
    DCS is a static partition (its cost/peak curve is closed-form
    arithmetic over the grid) and the EC2 allocation curve is a pure
    function of (submit, runtime, L) evaluated for ALL sweep points at
    once as batched host numpy arrays: the trace's WS demand change
    points are extracted and integrated once (``core.profiles``), job
    release ticks for every lease value are a broadcasted rounding to
    lease boundaries, node-hours is the WS integral plus each job's
    size·(release − submit) span, and peak consumption is a
    cumulative-max over the merged, time-sorted event deltas. The
    arithmetic runs in float64 so results agree with the event engine
    to round-off — node-hours match to < 1e-9 relative and every
    integer metric (peak nodes, completed jobs, adjust events) matches
    exactly (tests/test_sweep.py).

  * **Event-round fast path** (PhoenixCloud FB and FLB-NUB; modes
    ``"rounds"`` and ``"auto"``). The coordinated policies are
    stateful — kills, queue contents and U/V/G adjustments feed back
    into the allocation — so they cannot be closed-form;
    ``repro.sim.rounds`` batches them as a jitted ``lax.while_loop``
    whose every step jumps straight to the next event (submit /
    completion / WS change / lease boundary) per lane, ``vmap``-ed over
    sweep points AND packed workload traces. Completions and the
    allocation integral are *exact*: completed jobs match the event
    engine exactly and node-hours/peak stay within 5 % (the residue is
    first-fit pass convergence and kill tie-breaking, not time
    discretization). ``mode="auto"`` routes FB / FLB-NUB points through
    this engine, except beyond-paper ``checkpoint_preempt`` FB points
    which quietly fall back to the event engine (the status-lane kill
    encoding always restarts from scratch).

  * **Event-engine path** (mode ``"event"``, and the fallback for
    points no fast path accepts). Each point runs through
    ``repro.sim.engine.run_sim`` on its own clone of the trace — the
    per-point reference every fast path is validated against.

The vectorized path replicates the event engine's semantics exactly,
including its tie-breaking: at a shared timestamp, WS demand changes
apply before lease-tick releases, and releases before submits. A job
finishing precisely on a tick boundary is therefore released one full
lease later (the tick event sorts before the finish event).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat, spans
from repro.core.jobs import Job
from repro.core.pbj_manager import PBJPolicyParams
from repro.core.profiles import step_integral, step_points
from repro.sim import lanes
from repro.sim import rounds as roundslib
from repro.sim.engine import (_SUBMIT, _TICK, _WS, SYSTEMS, build_dcs,
                              build_ec2_rightscale, build_fb, build_flb_nub,
                              clone_jobs, default_duration, run_sim)

__all__ = ["SweepPoint", "ScanOptions", "run_sweep", "run_sweep_workloads",
           "paper_grid"]

MODES = ("auto", "event", "rounds")

# Systems with a stateless closed-form fast path vs the stateful
# coordinated policies that take the batched rounds path.
_VECTORIZED = ("dcs", "ec2")
_BATCHED = ("fb", "flb_nub")


@dataclasses.dataclass(frozen=True)
class SweepPoint:
    """One (system, parameter) point of a sweep grid.

    ``system`` selects the provisioning system; the remaining fields are
    that system's knobs (unused ones are ignored): ``capacity`` is the
    Fig.-13 sweep variable C, ``lb_pbj + lb_ws`` the Fig.-14 pool size
    B, and ``lease_seconds`` the Fig.-18 lease unit L.
    """

    system: str                       # "dcs" | "fb" | "flb_nub" | "ec2"
    prc_pbj: int = 0                  # dcs: static PBJ partition
    prc_ws: int = 0                   # dcs: static WS partition
    capacity: int = 0                 # fb: private-cloud capacity C
    lb_pbj: int = 0                   # flb_nub: PBJ lower bound
    lb_ws: int = 0                    # flb_nub: WS lower bound
    lease_seconds: float = 3600.0     # all: lease time unit L
    params: PBJPolicyParams = PBJPolicyParams()
    label: str = ""

    def __post_init__(self):
        if self.system not in SYSTEMS:
            raise ValueError(
                f"unknown system {self.system!r}; expected one of "
                f"{sorted(SYSTEMS)}")
        if self.lease_seconds <= 0:
            raise ValueError(
                f"lease_seconds must be > 0, got {self.lease_seconds}")

    def name(self) -> str:
        if self.label:
            return self.label
        return {
            "dcs": f"DCS({self.prc_pbj}+{self.prc_ws})",
            "fb": f"FB(C={self.capacity})",
            "flb_nub": f"FLB-NUB(B={self.lb_pbj + self.lb_ws})",
            "ec2": f"EC2+RightScale(L={self.lease_seconds:g}s)",
        }[self.system]


@dataclasses.dataclass(frozen=True)
class ScanOptions:
    """Tuning knobs of the batched rounds path (``mode="rounds"`` and
    ``"auto"``, see ``repro.sim.rounds``). The defaults are the
    settings the fidelity contracts are validated at. ``window`` is the
    job window per lane (``None``: ``FB_ROUNDS_WINDOW`` /
    ``FLB_ROUNDS_WINDOW``), ``ff_passes`` the first-fit passes per
    round (``None``: ``ROUNDS_FF_PASSES``) and ``dtype`` the pack dtype
    (``None``: the active jax x64 setting).
    ``devices`` selects the execution backend
    (``repro.compat.resolve_devices``): ``None`` runs the whole
    grid on one device, a count or device sequence shards the
    (point × trace) lanes across host devices via ``shard_map``.
    ``fault_stops`` fixes how many fault instants one workload's
    schedule may hold inside the horizon (FB under ``faults=``; more
    raise). Left ``None``, the fault tables and the round budget size to
    the call's schedules, so each new draw of schedules compiles the
    rounds program again; a caller asking many questions of fresh draws
    sets it once and compiles once."""

    window: Optional[int] = None
    ff_passes: Optional[int] = None
    dtype: Optional[np.dtype] = None
    devices: compat.Devices = None
    fault_stops: Optional[int] = None

    def resolve_rounds(self, policy: str, leases: Sequence[float],
                       duration: float, max_jobs: int,
                       n_ws: int) -> roundslib.RoundsSpec:
        window = (self.window if self.window is not None else
                  (roundslib.FB_ROUNDS_WINDOW if policy == "fb"
                   else roundslib.FLB_ROUNDS_WINDOW))
        ff = (self.ff_passes if self.ff_passes is not None
              else roundslib.ROUNDS_FF_PASSES)
        return roundslib.RoundsSpec(
            duration=duration,
            max_rounds=roundslib.round_budget(max_jobs, n_ws, duration,
                                              min(leases)),
            window=window, ff_passes=ff)


def _build(p: SweepPoint):
    if p.system == "dcs":
        return build_dcs(p.prc_pbj, p.prc_ws, p.lease_seconds)
    if p.system == "fb":
        return build_fb(p.capacity, p.lease_seconds, p.params)
    if p.system == "flb_nub":
        return build_flb_nub(p.lb_pbj, p.lb_ws, p.lease_seconds, p.params)
    if p.system == "ec2":
        return build_ec2_rightscale(p.lease_seconds)
    raise ValueError(f"unknown system {p.system!r}")


# ------------------------------------------------------- vectorized baselines

def _sweep_dcs(points: List[SweepPoint], duration: float) -> List[Dict]:
    """All DCS points at once: the partition is static, so the cost curve
    is an affine function of the configuration size.

    Vectorized DCS rows carry the cost/peak metrics only — job metrics
    (completed jobs, turnaround) depend on the first-fit queue dynamics
    and need the event engine (``run_sweep(..., mode="event")``).
    """
    rows = []
    for p in points:
        size = p.prc_pbj + p.prc_ws
        rows.append({
            "system": p.name(), "system_kind": "dcs", "engine": "vectorized",
            "lease_seconds": p.lease_seconds,
            "node_hours": size * duration / 3600.0,
            "peak_nodes": size,
            "adjust_events": int(p.prc_ws > 0) + int(p.prc_pbj > 0),
            "pbj_adjust_events": int(p.prc_pbj > 0),
            "kills": 0,
        })
    return rows


def _sweep_ec2(points: List[SweepPoint], jobs: Sequence[Job],
               ws_trace: Sequence[Tuple[float, int]],
               duration: float) -> List[Dict]:
    """All EC2+RightScale points (one per lease value) as batched float64
    numpy arrays on the host.

    Per job j and lease L: the job allocates ``size_j`` on
    ``[submit_j, rel_j)`` where ``rel_j`` is the first lease tick
    *strictly after* its completion (§6.6.2 whole-hour billing plus the
    engine's tick-before-finish tie order), clipped to the trace
    duration when the tick never fires. The WS curve replays the demand
    trace verbatim and is lease-independent.

    The host is the one placement: the closed form is a few thousand
    elements per lease, and XLA:TPU takes minutes to compile its
    float64 sort for each trace shape (168 s for a v5e).
    """
    ws_t64, ws_v64 = step_points(ws_trace, duration)
    ws_node_seconds = step_integral(ws_t64, ws_v64, duration)
    ws_deltas64 = np.concatenate([ws_v64[:1], np.diff(ws_v64)])
    ws_adjusts = int(np.count_nonzero(ws_deltas64))

    submit = np.asarray([j.submit for j in jobs], np.float64)
    size = np.asarray([j.size for j in jobs], np.float64)
    runtime = np.asarray([j.runtime for j in jobs], np.float64)
    end = submit + runtime
    in_trace = submit <= duration + 1e-9         # engine drops later submits
    finishes = in_trace & (end <= duration + 1e-9)

    L = np.asarray([p.lease_seconds for p in points],
                   np.float64)[:, None]                        # (P, 1)
    # First tick strictly after the finish event (see module doc).
    # A tick exists only while k·L <= duration — the engine's strict
    # scheduling comparison, mirrored here without tolerance.
    rel = (np.floor(end / L) + 1.0) * L                        # (P, J)
    fired = in_trace & (rel <= duration)
    rel_eff = np.where(fired, rel, duration)
    pbj_ns = np.sum(np.where(in_trace, size * (rel_eff - submit), 0.0),
                    axis=1)
    node_hours = (pbj_ns + ws_node_seconds) / 3600.0

    # Peak: merge WS steps, submits (+size) and releases (−size) and
    # take the cumulative max of the running total. Tie order at one
    # timestamp follows the engine's event kinds (releases happen
    # inside tick events).
    P, n_ws, n_j = len(points), len(ws_t64), len(submit)
    ev_t = np.concatenate([np.broadcast_to(ws_t64, (P, n_ws)),
                           np.broadcast_to(submit, (P, n_j)), rel], axis=1)
    ev_kind = np.broadcast_to(np.concatenate(
        [np.full(n_ws, float(_WS)), np.full(n_j, float(_SUBMIT)),
         np.full(n_j, float(_TICK))]), ev_t.shape)
    delta = np.concatenate(
        [np.broadcast_to(ws_deltas64, (P, n_ws)),
         np.broadcast_to(np.where(in_trace, size, 0.0), (P, n_j)),
         np.where(fired, -size, 0.0)], axis=1)
    order = np.lexsort((ev_kind, ev_t), axis=-1)
    running = np.cumsum(np.take_along_axis(delta, order, axis=1), axis=1)
    peak = np.maximum(np.max(running, axis=1), 0.0)

    completed = np.sum(finishes)
    sum_rt = np.sum(np.where(finishes, runtime, 0.0))
    n_released = np.sum(fired, axis=1)
    n_submitted = np.sum(in_trace)

    n_completed = int(completed)
    avg_rt = float(sum_rt) / n_completed if n_completed else 0.0
    rows = []
    for i, p in enumerate(points):
        pbj_adjusts = int(n_submitted) + int(n_released[i])
        rows.append({
            "system": p.name(), "system_kind": "ec2", "engine": "vectorized",
            "lease_seconds": p.lease_seconds,
            "node_hours": float(node_hours[i]),
            "peak_nodes": int(round(float(peak[i]))),
            "completed_jobs": n_completed,
            "avg_turnaround": avg_rt,        # EC2 never queues (§6.6.1)
            "avg_execution": avg_rt,
            "adjust_events": pbj_adjusts + ws_adjusts,
            "pbj_adjust_events": pbj_adjusts,
            "kills": 0,
        })
    return rows


# ------------------------------------------------------ batched rounds path

def _reject_preempt(points: List[SweepPoint], mode: str) -> None:
    for p in points:
        # The status-lane kill encoding resets a killed lane to its full
        # runtime (repro.sim.lanes); the beyond-paper checkpoint-preempt
        # mode only exists on the event engine — fail loudly rather than
        # silently report full-restart metrics for a preemption study. The guard is FB-only on purpose: FLB-NUB
        # never force-releases (§5.2 satisfies WS elastically and only
        # ever releases *free* nodes), so it has no kills for the
        # preemption mode to change —
        # tests/test_lane_policies.py::test_flb_nub_never_kills pins
        # that invariant, making the exemption safe.
        if p.system == "fb" and p.params.checkpoint_preempt:
            raise ValueError(
                f"{p.name()}: checkpoint_preempt is not supported by "
                f"mode=\"{mode}\"; run this point with mode=\"auto\" or "
                f"mode=\"event\"")


def _fb_grid(points: List[SweepPoint], idxs: List[int],
             f) -> lanes.FBGrid:
    return lanes.FBGrid(
        capacity=jnp.asarray([float(points[i].capacity) for i in idxs], f),
        lease=jnp.asarray([points[i].lease_seconds for i in idxs], f))


def _flb_grid(points: List[SweepPoint], idxs: List[int],
              f) -> lanes.FLBGrid:
    return lanes.FLBGrid(
        B=jnp.asarray([float(points[i].lb_pbj + points[i].lb_ws)
                       for i in idxs], f),
        lb_ws=jnp.asarray([float(points[i].lb_ws) for i in idxs], f),
        U=jnp.asarray([points[i].params.request_threshold
                       for i in idxs], f),
        V=jnp.asarray([points[i].params.release_threshold
                       for i in idxs], f),
        G=jnp.asarray([points[i].params.elastic_factor for i in idxs], f),
        lease=jnp.asarray([points[i].lease_seconds for i in idxs], f))


_DIAG_KEYS = ("window_overflow", "truncated", "rounds", "fault_rounds",
              "fault_kills")


def _assemble_rows(points: List[SweepPoint], fb_idx: List[int],
                   flb_idx: List[int], out: Dict, n_workloads: int,
                   engine: str) -> List[List[Dict]]:
    """Metric arrays → one row list per workload, aligned with
    ``points``; diagnostics (window overflow, round truncation) ride
    along per row so callers can see them."""
    per_workload: List[List[Dict]] = []
    for w in range(n_workloads):
        rows: List[Optional[Dict]] = [None] * len(points)
        for kind, idxs in (("fb", fb_idx), ("flb_nub", flb_idx)):
            for j, i in enumerate(idxs):
                m = {k: v[w][j] for k, v in out[kind].items()}
                p = points[i]
                rows[i] = {
                    "system": p.name(), "system_kind": p.system,
                    "engine": engine, "lease_seconds": p.lease_seconds,
                    "completed_jobs": int(round(float(m["completed_jobs"]))),
                    "avg_turnaround": float(m["avg_turnaround"]),
                    "avg_execution": float(m["avg_execution"]),
                    "node_hours": float(m["node_hours"]),
                    "peak_nodes": int(round(float(m["peak_nodes"]))),
                    "adjust_events": int(round(float(m["adjust_events"]))),
                    "pbj_adjust_events": int(round(float(
                        m["pbj_adjust_events"]))),
                    "kills": int(round(float(m["kills"]))),
                    "window_overflow": int(round(float(
                        m["window_overflow"]))),
                }
                for k in _DIAG_KEYS[1:]:
                    if k in m:
                        rows[i][k] = int(round(float(m[k])))
        per_workload.append(rows)                 # type: ignore[arg-type]
    return per_workload                           # type: ignore[return-value]


def _warn_diagnostics(per_workload: List[List[Dict]], engine: str,
                      stacklevel: int = 3) -> None:
    """Surface lane diagnostics: a backlog that outgrew the job window
    (results silently degrade — jobs start late or never) or a lane
    that exhausted its round budget. Callers also get both per row.

    ``stacklevel`` must resolve to the frame OUTSIDE the sweep library —
    the entry points thread the extra wrapper depth through
    ``warn_stacklevel`` / ``_stack_offset`` so ``-W error`` reports and
    warning filters name the caller's file, not this module."""
    overflowed = [r["system"] for rows in per_workload for r in rows
                  if r is not None and r.get("window_overflow", 0) > 0]
    if overflowed:
        warnings.warn(
            f"{engine} sweep: job backlog outgrew the lane window on "
            f"{len(overflowed)} row(s) ({', '.join(sorted(set(overflowed)))}"
            f"); metrics under-report queued work — raise "
            f"ScanOptions.window", RuntimeWarning, stacklevel=stacklevel)
    truncated = [r["system"] for rows in per_workload for r in rows
                 if r is not None and r.get("truncated", 0) > 0]
    if truncated:
        warnings.warn(
            f"{engine} sweep: round budget exhausted before the horizon "
            f"on {len(truncated)} row(s) "
            f"({', '.join(sorted(set(truncated)))})", RuntimeWarning,
            stacklevel=stacklevel)


def _pack_rounds(points: List[SweepPoint],
                 workloads: Sequence[Tuple[Sequence[Job],
                                           Sequence[Tuple[float, int]]]],
                 duration: float, options: ScanOptions, faults=None):
    """Host-side setup stage of the rounds path: event packing + fold
    tables + grid construction, apart from the device call so callers
    can time setup on its own. Each policy's
    pack holds every workload, stacked on a leading trace axis. FB's
    pack carries ``faults`` (one schedule or ``None`` per workload), and
    its round budget the slack of the call's largest schedule."""
    with spans.span("sweep.pack"):
        fb_idx = [i for i, p in enumerate(points) if p.system == "fb"]
        flb_idx = [i for i, p in enumerate(points) if p.system == "flb_nub"]
        max_jobs = max(len(jobs) for jobs, _ in workloads)
        n_ws = max(len(ws) for _, ws in workloads)

        fb = flb = fb_packed = flb_packed = fb_spec = flb_spec = None
        if fb_idx:
            leases = [points[i].lease_seconds for i in fb_idx]
            fb_spec = options.resolve_rounds("fb", leases, duration,
                                             max_jobs, n_ws)
            n_faults = max((len(f) for f in faults or () if f is not None),
                           default=0)
            if n_faults and options.fault_stops is not None:
                n_faults = options.fault_stops
            if n_faults:
                # Each fault stop may kill and restart jobs.
                fb_spec = dataclasses.replace(
                    fb_spec, max_rounds=fb_spec.max_rounds + 8 * n_faults)
            fb_packed = roundslib.pack_event_workloads(
                workloads, duration, fb_spec.window, "fb", leases,
                [float(points[i].capacity) for i in fb_idx],
                dtype=options.dtype, faults=faults,
                fault_stops=options.fault_stops)
            fb = _fb_grid(points, fb_idx, fb_packed.submit.dtype)
        if flb_idx:
            leases = [points[i].lease_seconds for i in flb_idx]
            flb_spec = options.resolve_rounds("flb_nub", leases, duration,
                                              max_jobs, n_ws)
            flb_packed = roundslib.pack_event_workloads(
                workloads, duration, flb_spec.window, "flb_nub", leases,
                [float(points[i].lb_ws) for i in flb_idx],
                dtype=options.dtype)
            flb = _flb_grid(points, flb_idx, flb_packed.submit.dtype)
        return (fb_idx, flb_idx, fb, flb, fb_packed, flb_packed, fb_spec,
                flb_spec)


def _sweep_rounds(points: List[SweepPoint],
                  workloads: Sequence[Tuple[Sequence[Job],
                                            Sequence[Tuple[float, int]]]],
                  duration: float,
                  options: ScanOptions,
                  warn_stacklevel: int = 3, faults=None) -> List[List[Dict]]:
    """FB and FLB-NUB points through the event-round fast path
    (``repro.sim.rounds``): adaptive jump-to-next-event steps with
    exact completions, batched over sweep points and workloads.

    All workloads run in ONE device call: the packs stack the traces
    and the program runs every (trace × point) lane in lockstep, so the
    call lasts as long as each policy's slowest lane over all
    workloads, where one call per workload would last the sum of every
    workload's slowest lane (0.36× the serial rounds on the paper
    grid). A round costs more at more lanes, but on a TPU v5e by less
    than the depth falls: the paper grid's loop takes 0.77 s in one
    call against 1.32 s in three. On a 2-core CPU, where a round's cost
    grows in proportion to the lanes, the one call is ~12 % slower on
    the paper grid. With ``devices`` set, the call shards the lanes
    across the devices. ``faults`` (FB only, one schedule or ``None`` per
    workload) rides in the same call; the root span then counts the
    rounds that stopped at a fault instant (``rounds.fault_rounds``)
    and the jobs killed in them (``faults.kills``).
    """
    assert all(p.system in _BATCHED for p in points)
    _reject_preempt(points, "rounds")
    (fb_idx, flb_idx, fb, flb, fb_packed, flb_packed,
     fb_spec, flb_spec) = _pack_rounds(points, workloads, duration, options,
                                       faults)

    with spans.span("sweep.dispatch"):
        out = roundslib.rounds_grids(
            fb, flb, fb_packed, flb_packed,
            fb_spec=fb_spec, flb_spec=flb_spec, devices=options.devices)
    with spans.span("sweep.wait"):
        out = jax.tree_util.tree_map(np.asarray, out)
    # Lockstep efficiency: each policy's lanes in the device call run
    # until its slowest lane is done.
    for metrics in out.values():
        r = np.rint(metrics["rounds"]).astype(np.int64)
        spans.count("rounds.lane_rounds", int(r.sum()))
        spans.count("rounds.lane_slots", int(r.size * r.max()))
    if "fault_rounds" in out.get("fb", {}):
        spans.count("rounds.fault_rounds",
                    int(np.rint(out["fb"]["fault_rounds"]).sum()))
        spans.count("faults.kills",
                    int(np.rint(out["fb"]["fault_kills"]).sum()))
    rows = _assemble_rows(points, fb_idx, flb_idx, out, len(workloads),
                          "rounds")
    _warn_diagnostics(rows, "rounds", stacklevel=warn_stacklevel)
    return rows


def _pack_scenarios_grids(points: List[SweepPoint], grid,
                          synth, options: ScanOptions):
    """Setup stage of the generated-scenario path: one
    :func:`repro.sim.scenarios.pack_scenarios` per policy (job tables,
    rise compression and the batched (W, P) fold tables are all array
    ops — no per-lane host loop)."""
    from repro.sim import scenarios as scenarioslib
    fb_idx = [i for i, p in enumerate(points) if p.system == "fb"]
    flb_idx = [i for i, p in enumerate(points) if p.system == "flb_nub"]
    duration = float(grid.duration)
    changes = synth.ws_values[:, 1:] != synth.ws_values[:, :-1]
    n_ws = int(changes.sum(axis=1).max()) + 1

    fb = flb = fb_packed = flb_packed = fb_spec = flb_spec = None
    if fb_idx:
        leases = [points[i].lease_seconds for i in fb_idx]
        fb_spec = options.resolve_rounds("fb", leases, duration,
                                         grid.max_jobs, n_ws)
        fb_packed = scenarioslib.pack_scenarios(
            synth, fb_spec.window, "fb", leases,
            [float(points[i].capacity) for i in fb_idx],
            dtype=options.dtype)
        fb = _fb_grid(points, fb_idx, fb_packed.submit.dtype)
    if flb_idx:
        leases = [points[i].lease_seconds for i in flb_idx]
        flb_spec = options.resolve_rounds("flb_nub", leases, duration,
                                          grid.max_jobs, n_ws)
        flb_packed = scenarioslib.pack_scenarios(
            synth, flb_spec.window, "flb_nub", leases,
            [float(points[i].lb_ws) for i in flb_idx],
            dtype=options.dtype)
        flb = _flb_grid(points, flb_idx, flb_packed.submit.dtype)
    return (fb_idx, flb_idx, fb, flb, fb_packed, flb_packed, fb_spec,
            flb_spec)


def _sweep_rounds_generated(points: List[SweepPoint], grid,
                            options: ScanOptions,
                            synth=None,
                            warn_stacklevel: int = 3) -> List[List[Dict]]:
    """FB / FLB-NUB points over a generated scenario batch
    (:class:`repro.sim.scenarios.ScenarioGrid`) through the event-round
    engine. Like :func:`_sweep_rounds`, the whole (W × P) batch runs as
    ONE program — one vmap over the lanes on a single device,
    ``sharded_grid_map`` across ``options.devices``; generated lanes
    share one dense WS grid and one job-table height.
    """
    from repro.sim import scenarios as scenarioslib
    assert all(p.system in _BATCHED for p in points)
    _reject_preempt(points, "rounds")
    if synth is None:
        synth = scenarioslib.synthesize(grid)
    (fb_idx, flb_idx, fb, flb, fb_packed, flb_packed, fb_spec,
     flb_spec) = _pack_scenarios_grids(points, grid, synth, options)
    out = roundslib.rounds_grids(fb, flb, fb_packed, flb_packed,
                                 fb_spec=fb_spec, flb_spec=flb_spec,
                                 devices=options.devices)
    out = jax.tree_util.tree_map(np.asarray, out)
    rows = _assemble_rows(points, fb_idx, flb_idx, out, grid.n_lanes,
                          "rounds")
    _warn_diagnostics(rows, "rounds", stacklevel=warn_stacklevel)
    return rows


# --------------------------------------------------------------- the sweep

def _check_faults(points: Sequence[SweepPoint], workloads, faults,
                  mode: str) -> Optional[List]:
    """``faults`` as a list aligned with ``workloads``, or ``None`` when
    no schedule holds an event; raises where a schedule cannot run."""
    if faults is None:
        return None
    faults = list(faults)
    if not any(f is not None and len(f) for f in faults):
        return None
    from repro.sim import scenarios as scenarioslib
    if isinstance(workloads, scenarioslib.ScenarioGrid):
        raise ValueError("fault schedules run over listed workloads, not a "
                         "generated ScenarioGrid")
    if len(faults) != len(workloads):
        raise ValueError(f"faults ({len(faults)}) must align with workloads "
                         f"({len(workloads)})")
    other = sorted({p.system for p in points if p.system != "fb"})
    if other:
        raise ValueError(
            f"fault schedules run FB points only (the rounds engine folds "
            f"failures for FB alone), got {other}; run those points "
            f"without faults or one at a time through run_sim(faults=)")
    return faults


def _resolve_mode(mode: Optional[str], vectorize: bool) -> str:
    if mode is None:
        return "auto" if vectorize else "event"
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; expected one of {MODES}")
    return mode


def run_sweep(points: Sequence[SweepPoint], jobs: Sequence[Job],
              ws_trace: Sequence[Tuple[float, int]],
              duration: Optional[float] = None,
              vectorize: bool = True,
              mode: Optional[str] = None,
              scan_options: ScanOptions = ScanOptions(),
              devices: compat.Devices = None) -> List[Dict]:
    """Evaluate every sweep point on the same (jobs, ws_trace) workload.

    Returns one row dict per point, in input order, each tagged with
    ``engine`` = ``"vectorized"`` (exact batched jnp fast path),
    ``"rounds"`` (event-round fast path for FB / FLB-NUB) or
    ``"event"`` (per-point discrete-event run).

    ``mode`` selects the execution paths (see module docstring):
    ``"auto"`` (default) vectorizes DCS/EC2 and batches FB / FLB-NUB
    through the event-round engine (``repro.sim.rounds`` — completed
    jobs exact, node-hours/peak within 5 %), falling back to the event
    engine for points the fast path rejects (FB with
    ``checkpoint_preempt``); ``"rounds"`` is the same but *fails* on
    such points; ``"event"`` runs everything on the
    event engine — the cross-validation reference used by
    tests/test_sweep.py. The legacy ``vectorize=False`` flag is
    equivalent to ``mode="event"``.

    ``devices`` (shorthand for ``scan_options.devices``) shards the
    fast path's (point × trace) lanes across that many host devices —
    see :class:`ScanOptions`. It affects modes ``"auto"`` and
    ``"rounds"``.

    Vectorized DCS rows carry cost/peak metrics only (use ``.get`` or
    ``mode="event"`` when job metrics are needed for a DCS point);
    rounds rows carry the full metric set plus lane diagnostics
    (``window_overflow``, ``truncated`` and ``rounds``) — a nonzero
    diagnostic also raises a ``RuntimeWarning``.
    """
    return run_sweep_workloads(points, [(jobs, ws_trace)], duration,
                               vectorize=vectorize, mode=mode,
                               scan_options=scan_options,
                               devices=devices, _stack_offset=1)[0]


def run_sweep_workloads(points: Sequence[SweepPoint],
                        workloads: Sequence[Tuple[Sequence[Job],
                                                  Sequence[Tuple[float, int]]]],
                        duration: Optional[float] = None,
                        vectorize: bool = True,
                        mode: Optional[str] = None,
                        scan_options: ScanOptions = ScanOptions(),
                        devices: compat.Devices = None,
                        faults: Optional[Sequence] = None,
                        _stack_offset: int = 0
                        ) -> List[List[Dict]]:
    """Evaluate a sweep grid over SEVERAL workload traces at once.

    Returns ``rows[w][i]`` — one row list per workload, aligned with
    ``points``. In the batched modes the FB / FLB-NUB points of ALL
    workloads batch through a single jitted program (the trace axis is
    a second ``vmap`` axis); DCS / EC2 points run the exact vectorized
    path per workload, and the event fallback runs per (point, workload)
    pair. All workloads share one measurement horizon ``duration``
    (§6.1) — the default is the latest horizon any workload implies.
    ``devices`` overrides ``scan_options.devices`` (see
    :class:`ScanOptions`).

    ``workloads`` may instead be a
    :class:`repro.sim.scenarios.ScenarioGrid` — a generated scenario
    batch (per-lane PRNG seeds + parameter grids). The lanes then
    synthesize on device, pack as ONE batch and run the event-round
    engine as a single (W × P) program (sharded across
    ``devices`` when set); only FB / FLB-NUB points are supported and
    the grid fixes the horizon (``duration`` must stay ``None``).

    ``faults``, when given, holds one
    :class:`repro.sim.faults.FaultSchedule` or ``None`` per workload:
    node failures and repairs on that workload's site (the chaos tier).
    They run FB points only, through the rounds engine (one device call
    with the other workloads, fault instants as loop stops and the
    capacity ``max(C - failed(t), 0)``) or, in ``mode="event"`` and for
    the points ``"auto"`` sends there, through ``run_sim(faults=)``. A
    schedule beside a DCS, EC2 or FLB-NUB point raises ``ValueError``.
    ``None``, or no schedule with an event, leaves every path as it is
    without faults.

    ``_stack_offset`` (private) is the number of wrapper frames between
    the user's call site and this function; diagnostic
    ``RuntimeWarning``\\ s use it to attribute the warning to the
    caller's file instead of the sweep internals. Wrappers that forward
    here (``run_sweep``, ``warmup_sweep``, the capacity query layer)
    each add their own frame count.
    """
    with spans.span("sweep"):
        mode = _resolve_mode(mode, vectorize)
        # warnings.warn stack depth from inside _warn_diagnostics:
        # 1 = _warn_diagnostics, 2 = _sweep_*, 3 = this function,
        # 4 = our caller — plus any wrapper frames above us.
        warn_stacklevel = 4 + _stack_offset
        if devices is not None:
            scan_options = dataclasses.replace(scan_options, devices=devices)
        from repro.sim import scenarios as scenarioslib
        faults = _check_faults(points, workloads, faults, mode)
        if isinstance(workloads, scenarioslib.ScenarioGrid):
            # Generated scenario batches (keys + param grids, not
            # List[Job]) flow the event-round engine only: the lanes share
            # one dense WS grid and job-table height, so the whole (W × P)
            # batch is one program. The grid carries its own horizon.
            if mode not in ("auto", "rounds"):
                raise ValueError(
                    f"generated scenario batches run the rounds engine only "
                    f"(mode 'auto'/'rounds', got {mode!r})")
            if duration is not None and duration != workloads.duration:
                raise ValueError(
                    "duration is fixed by ScenarioGrid.duration — pass None")
            bad = sorted({p.system for p in points
                          if p.system not in _BATCHED})
            if bad:
                raise ValueError(
                    f"generated scenario batches support FB / FLB-NUB points "
                    f"only, got {bad}; evaluate DCS/EC2 baselines on "
                    f"sampled lanes (repro.sim.scenarios.sample_workloads)")
            return _sweep_rounds_generated(list(points), workloads,
                                           scan_options,
                                           warn_stacklevel=warn_stacklevel)
        if duration is None:
            duration = max(default_duration(jobs, ws)
                           for jobs, ws in workloads)
        rows: List[List[Optional[Dict]]] = [
            [None] * len(points) for _ in workloads]

        dcs_idx = [i for i, p in enumerate(points) if p.system == "dcs"]
        ec2_idx = [i for i, p in enumerate(points) if p.system == "ec2"]
        if mode != "event" and (dcs_idx or ec2_idx):
            with spans.span("sweep.closed_forms"):
                dcs = [points[i] for i in dcs_idx]
                ec2 = [points[i] for i in ec2_idx]
                for w, (jobs, ws_trace) in enumerate(workloads):
                    if dcs:
                        for i, row in zip(dcs_idx,
                                          _sweep_dcs(dcs, duration)):
                            rows[w][i] = row
                    if ec2:
                        for i, row in zip(ec2_idx,
                                          _sweep_ec2(ec2, jobs, ws_trace,
                                                     duration)):
                            rows[w][i] = row

        if mode in ("auto", "rounds"):
            batch_idx = [i for i, p in enumerate(points)
                         if p.system in _BATCHED]
            if mode == "auto":
                # Points the event-round engine rejects (FB
                # checkpoint_preempt) quietly take the per-point event
                # path below instead of failing.
                batch_idx = [i for i in batch_idx
                             if not (points[i].system == "fb"
                                     and points[i].params.checkpoint_preempt)]
            if batch_idx:
                batch = [points[i] for i in batch_idx]
                fast_rows = _sweep_rounds(batch, workloads, duration,
                                          scan_options,
                                          warn_stacklevel=warn_stacklevel,
                                          faults=faults)
                for w in range(len(workloads)):
                    for j, i in enumerate(batch_idx):
                        rows[w][i] = fast_rows[w][j]

        for w, (jobs, ws_trace) in enumerate(workloads):
            for i, p in enumerate(points):
                if rows[w][i] is not None:
                    continue
                r = run_sim(_build(p), clone_jobs(jobs), ws_trace, duration,
                            name=p.name(),
                            faults=faults[w] if faults is not None else None)
                row = r.row()
                row.update(system_kind=p.system, engine="event",
                           lease_seconds=p.lease_seconds)
                rows[w][i] = row
        return rows                               # type: ignore[return-value]


def warmup_sweep(points: Sequence[SweepPoint],
                 workloads: Sequence[Tuple[Sequence[Job],
                                           Sequence[Tuple[float, int]]]],
                 duration: Optional[float] = None, *, mode: str = "rounds",
                 scan_options: ScanOptions = ScanOptions(),
                 devices: compat.Devices = None) -> float:
    """Prime every jit cache one (grid, workloads, mode, options)
    configuration touches and return the priming call's seconds, as its
    ``sweep`` root span recorded them (so call it outside any open
    :mod:`repro.spans` span) — the compile cost the steady-state path
    then never pays again.

    The rounds programs are cached on ``(policy, spec)`` keys and, for
    the sharded backend, the device mesh (``rounds._rounds_lane`` /
    ``lanes._sharded_lanes``),
    so warming one configuration never evicts or aliases another. The
    helper is ``jax.clear_caches()``-safe: nothing is memoized on wall
    time or call order, so after a cache clear the next call simply
    recompiles and re-primes — callers that need a cold-compile
    measurement (``benchmarks/run.py sweep``'s ``compile_s`` column)
    call ``jax.clear_caches()`` first and take this helper's return
    value; live paths call it once at startup and pay ~0 afterwards.
    """
    run_sweep_workloads(points, workloads, duration, mode=mode,
                        scan_options=scan_options, devices=devices,
                        _stack_offset=1)
    return spans.roots("sweep")[-1]["s"]


# ------------------------------------------------------------- paper grids

def paper_grid(prc_pbj: int, prc_ws: int = 128,
               capacity_fracs: Sequence[float] = (0.5, 0.6, 0.75, 0.9, 1.0),
               B_values: Sequence[int] = (13, 25, 51, 102, 154),
               lease_minutes: Sequence[int] = (15, 30, 60, 120, 240),
               fig18_B: int = 25, lb_ws: int = 12,
               params: PBJPolicyParams = PBJPolicyParams()
               ) -> List[SweepPoint]:
    """The Fig. 13 + Fig. 14 + Fig. 18 grids as one sweep (21 points).

    Fig. 13: FB capacity C as a fraction of the DCS configuration size
    (plus the DCS reference). Fig. 14: FLB-NUB pool size B. Fig. 18:
    lease unit L for both FLB-NUB and the EC2+RightScale baseline.
    """
    dcs_size = prc_pbj + prc_ws
    pts = [SweepPoint("dcs", prc_pbj=prc_pbj, prc_ws=prc_ws,
                      label=f"DCS({dcs_size})")]
    for f in capacity_fracs:
        c = int(round(dcs_size * f))
        pts.append(SweepPoint("fb", capacity=c, params=params,
                              label=f"FB(C={c})"))
    for B in B_values:
        w = min(lb_ws, B - 1)
        pts.append(SweepPoint("flb_nub", lb_pbj=B - w, lb_ws=w,
                              params=params, label=f"FLB-NUB(B={B})"))
    for m in lease_minutes:
        w = min(lb_ws, fig18_B - 1)
        pts.append(SweepPoint("flb_nub", lb_pbj=fig18_B - w, lb_ws=w,
                              lease_seconds=60.0 * m, params=params,
                              label=f"FLB-NUB(L={m}min)"))
        pts.append(SweepPoint("ec2", lease_seconds=60.0 * m,
                              label=f"EC2(L={m}min)"))
    return pts
