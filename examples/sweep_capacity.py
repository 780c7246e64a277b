"""Paper-scale parameter study in one call (Figs. 13/14/18, §6.5–§6.6).

Sweeps the whole comparison grid — private-cloud capacity C for the FB
policy (Fig. 13, the ~40 % configuration-size headline), coordinated
pool size B for FLB-NUB (Fig. 14), and the lease unit L for both
PhoenixCloud and EC2+RightScale (Fig. 18) — through
``repro.sim.sweep.run_sweep``. DCS and EC2 points are evaluated on the
exact vectorized host fast path in every mode; ``--mode`` picks how the
stateful PhoenixCloud policies run:

  auto   (default) FB / FLB-NUB on the event-round engine — same as
         rounds, with an event-engine fallback for points the fast
         path rejects
  rounds FB / FLB-NUB batched through the jump-to-next-event engine
         (completed jobs exact, node-hours/peak within 5 %)
  event  everything on the event engine (the cross-validation reference)

``--devices N`` shards the batched path's point lanes across N host
devices (forcing N XLA CPU devices when needed) — the multi-core
backend of the sweep engine.

``--queries`` additionally runs the capacity query layer
(``repro.sim.capacity``) on top of the same grid: the §6.5.3 headline
re-derived as a batched min-C bisection against the DCS throughput
(instead of eyeballing the swept rows), a Pareto frontier over the
evaluated grid, and the multi-cloud cost lens answering "cheapest
provider for this frontier".

Run:  PYTHONPATH=src python examples/sweep_capacity.py [--mode rounds]
      [--devices 2] [--queries]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ap = argparse.ArgumentParser()
ap.add_argument("--mode", default="auto",
                help="execution path for the FB / FLB-NUB points")
ap.add_argument("--devices", type=int, default=0,
                help="shard the batched-path lanes across N host devices "
                "(requires a batched mode: auto or rounds)")
ap.add_argument("--queries", action="store_true",
                help="also run the capacity query layer: min-C "
                "bisection, Pareto frontier and the cost lens")
args = ap.parse_args()

if args.devices >= 2:
    if args.mode not in ("auto", "rounds"):
        # Only the batched path consumes the devices option — anything
        # else would silently run unsharded.
        ap.error("--devices requires a batched mode (auto, rounds)")
    from repro.hostdev import force_host_device_count
    force_host_device_count(args.devices)

from repro.compat import enable_compile_cache

enable_compile_cache()

import numpy as np

from repro.core.profiles import job_demand_profile
from repro.sim import traces
from repro.sim.sweep import MODES, paper_grid, run_sweep

if args.mode not in MODES:
    ap.error(f"--mode must be one of {MODES}")

T = traces.TWO_WEEKS
jobs = traces.nasa_ipsc(seed=0)
ws = traces.worldcup98(seed=0, peak_vms=128)

# The precomputed per-lease-window PBJ demand profile the sweep engine
# batches over — also a quick feasibility read on any capacity C.
profile = job_demand_profile(np.array([j.submit for j in jobs]),
                             np.array([j.size for j in jobs]), T, 3600.0)
print(f"PBJ demand profile: peak {profile.max():.0f} nodes/h, "
      f"mean {profile.mean():.1f} nodes/h over {len(profile)} lease windows\n")

PRC_PBJ, PRC_WS = 128, 128
rows = run_sweep(paper_grid(prc_pbj=PRC_PBJ, prc_ws=PRC_WS), jobs, ws, T,
                 mode=args.mode, devices=args.devices or None)

print(f"{'point':22s} {'engine':>10s} {'jobs':>5s} {'peak':>6s} "
      f"{'node-h':>9s} {'adjusts':>8s}")
for r in rows:
    jobs_s = str(r.get("completed_jobs", "-"))
    print(f"{r['system']:22s} {r['engine']:>10s} {jobs_s:>5s} "
          f"{r['peak_nodes']:6d} {r['node_hours']:9.0f} "
          f"{r['adjust_events']:8d}")

dcs_size = PRC_PBJ + PRC_WS
dcs = next(r for r in rows if r["system_kind"] == "dcs")
fb60 = next(r for r in rows
            if r["system"] == f"FB(C={int(round(dcs_size * 0.6))})")
fb100 = next(r for r in rows if r["system"] == f"FB(C={dcs_size})")
print(f"\n=> FB at 60% capacity completes {fb60['completed_jobs']} jobs — the "
      f"same throughput as the full-size FB(C={dcs_size}) "
      f"({fb100['completed_jobs']}) on a site 40% smaller than the "
      f"{dcs['peak_nodes']}-node DCS (Fig. 13).")

if args.queries:
    import warnings

    from repro.sim.capacity import (CapacitySLO, CostModel, SweepPoint,
                                    min_capacity, pareto_front)

    # The §6.5.3 claim as a QUERY: minimum FB capacity matching the DCS
    # throughput, found by batched bisection instead of grid eyeballing.
    dcs_jobs = next(r for r in run_sweep(
        [SweepPoint("dcs", prc_pbj=PRC_PBJ, prc_ws=PRC_WS)], jobs, ws, T,
        mode="event"))["completed_jobs"]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rep = min_capacity(SweepPoint("fb"), (jobs, ws),
                           CapacitySLO(min_completed=dcs_jobs),
                           lo=1, hi=dcs_size, duration=T, mode="rounds",
                           devices=args.devices or None)
    r = rep.results[0]
    print(f"\n=> min_capacity: FB needs C={r.capacity} to match DCS's "
          f"{dcs_jobs} completed jobs — a "
          f"{1 - r.capacity / dcs_size:.1%} smaller configuration, "
          f"found in {rep.rows_evaluated} sweep rows vs "
          f"{rep.brute_force_rows} for a brute-force scan.")

    # The non-dominated policies of the grid just swept (minus the
    # vectorized DCS row, which carries no completed_jobs), and what
    # the cheapest provider would charge for them.
    front = pareto_front(rows=[r for r in rows if "completed_jobs" in r])
    cm = CostModel()
    est = cm.cheapest(front.frontier_rows())
    print(f"=> Pareto frontier (node-hours, peak, throughput): "
          f"{[front.points[i].row['system'] for i in front.frontier]}")
    print(f"=> cheapest provider for the frontier mix: {est.provider} "
          f"(${est.total_usd:,.0f} = {est.node_hours:,.0f} node-h + "
          f"{est.requests} API requests)")
