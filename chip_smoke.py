#!/usr/bin/env python3
"""Smoke run of the provisioning planner's main path on a TPU.

    python chip_smoke.py              # phases a-c on one chip
    python chip_smoke.py --chips 4    # phase b sharded over 4 chips vs one

Every phase goes through the public sweep API (``run_sweep_workloads``,
``run_sweep``, ``min_capacity``) with ``mode="rounds"``, at the paper's
size: a 256-node site and two-week traces generated from seeds.

  a. Paper grid: the three two-week workloads of ``benchmarks.run
     sweep`` times the 15 FB / FLB-NUB points of Figs. 13/14/18 (45
     evaluations), plus the DCS and EC2 points of ``paper_grid(128,
     128)``. Two sampled FB / FLB-NUB lanes are re-run on the event
     engine and held to ``CONTRACTS["rounds"]``; every EC2 row is held
     to ``CONTRACTS["vectorized"]``.
  b. Generated scenarios: a 1,025-lane ``ScenarioGrid`` (205 traces x 5
     points). Two sampled traces are re-run on the event engine and held
     to ``CONTRACTS["rounds"]``.
  c. One capacity query: ``min_capacity`` for FB at L=3600 s on NASA
     iPSC + World Cup, checked feasible with result-1 infeasible.

Each phase is called twice: the first call pays compilation, the second
is the warm ``run_s``, and both must return the same rows. Each phase
prints one JSON line; the last line is the device record. A contract
miss, a truncated lane, a window overflow or a ``RuntimeWarning`` exits
non-zero, and so does a run without a TPU, before any phase.

``--chips 4`` runs phase b only: once with ``devices=4`` and once on one
device, and requires bit-identical rows.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

DAY = 24 * 3600.0
# Job window of phase b. The grid's FB(C=96) lanes above ~0.6
# utilization hold backlogs deeper than the default 192 lanes (30 of the
# 205 traces overflow it); 384 holds them all, 512 leaves margin.
SCENARIO_WINDOW = 512


class CompileClock:
    """Seconds XLA spent compiling (persistent-cache reads included) and
    persistent-cache hits, while the context is open."""

    def __enter__(self):
        import jax
        self.backend_compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def _timed_twice(fn):
    """Run ``fn`` cold, then warm. Returns (rows, timing dict); the two
    calls must agree."""
    with CompileClock() as clock:
        t0 = time.perf_counter()
        first = fn()
        cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    second = fn()
    warm = time.perf_counter() - t0
    if first != second:
        raise AssertionError("the warm call returned different rows")
    return second, {"first_call_s": cold, "run_s": warm,
                    "compile_s": cold - warm,
                    "backend_compile_s": clock.backend_compile_s,
                    "cache_hits": clock.cache_hits}


def _lane_stats(rows):
    """Rows of the rounds engine: count, rounds per lane, diagnostics."""
    fast = [r for rs in rows for r in rs if r["engine"] == "rounds"]
    bad = [f"{r['system']}: truncated={r['truncated']} "
           f"window_overflow={r['window_overflow']}" for r in fast
           if r["truncated"] or r["window_overflow"]]
    rounds = [r["rounds"] for r in fast]
    return {"rows": sum(len(rs) for rs in rows), "rounds_lanes": len(fast),
            "rounds_max": max(rounds),
            "rounds_mean": sum(rounds) / len(rounds)}, bad


def _check(contract_name, fast_rows, event_rows):
    from repro.sim.contracts import CONTRACTS
    contract = CONTRACTS[contract_name]
    return [f"{f['system']}: {v}" for f, e in zip(fast_rows, event_rows)
            for v in contract.check_row(f, e)]


def paper_workloads(horizon: float):
    """The three workloads of the paper-grid benchmark, cut to
    ``horizon`` when it is shorter than the two-week traces."""
    from repro.core.profiles import scale_profile
    from repro.sim import traces
    ws_nasa = traces.worldcup98(seed=0, peak_vms=128)
    wls = [(traces.nasa_ipsc(seed=0), ws_nasa),
           (traces.sdsc_blue(seed=0), traces.worldcup98(seed=1,
                                                        peak_vms=128)),
           (traces.nasa_ipsc(seed=1), scale_profile(ws_nasa, 2.0))]
    return [([j for j in jobs if j.submit < horizon],
             [(t, d) for t, d in ws if t < horizon]) for jobs, ws in wls]


def paper_points():
    """The 15 FB / FLB-NUB points of Figs. 13/14/18 followed by the DCS
    and EC2 points of ``paper_grid(128, 128)``."""
    from repro.sim.sweep import SweepPoint, paper_grid
    dcs_size = 256
    fb = [SweepPoint("fb", capacity=int(round(dcs_size * f)),
                     label=f"FB(C={int(round(dcs_size * f))})")
          for f in (0.5, 0.6, 0.75, 0.9, 1.0)]
    flb = [SweepPoint("flb_nub", lb_pbj=B - min(12, B - 1),
                      lb_ws=min(12, B - 1), label=f"FLB-NUB(B={B})")
           for B in (13, 25, 51, 102, 154)]
    flb_l = [SweepPoint("flb_nub", lb_pbj=13, lb_ws=12,
                        lease_seconds=60.0 * m, label=f"FLB-NUB(L={m}min)")
             for m in (15, 30, 60, 120, 240)]
    base = [p for p in paper_grid(prc_pbj=128, prc_ws=128)
            if p.system in ("dcs", "ec2")]
    return fb + flb + flb_l + base


def phase_paper_grid(horizon: float = 14 * DAY, seed: int = 0) -> dict:
    import numpy as np
    from repro.sim.sweep import run_sweep_workloads

    workloads = paper_workloads(horizon)
    points = paper_points()
    rows, timing = _timed_twice(lambda: run_sweep_workloads(
        points, workloads, horizon, mode="rounds"))
    stats, bad = _lane_stats(rows)

    rng = np.random.default_rng(seed)
    fb_i = [i for i, p in enumerate(points) if p.system == "fb"]
    flb_i = [i for i, p in enumerate(points) if p.system == "flb_nub"]
    sampled = [(int(rng.integers(len(workloads))), int(rng.choice(idx)))
               for idx in (fb_i, flb_i)]
    for w, i in sampled:
        ev = run_sweep_workloads([points[i]], [workloads[w]], horizon,
                                 mode="event")[0]
        bad += _check("rounds", [rows[w][i]], ev)
    ec2_i = [i for i, p in enumerate(points) if p.system == "ec2"]
    ev = run_sweep_workloads([points[i] for i in ec2_i], workloads,
                             horizon, mode="event")
    for w in range(len(workloads)):
        bad += _check("vectorized", [rows[w][i] for i in ec2_i], ev[w])
    return {"phase": "a_paper_grid", **timing, **stats,
            "evals": len(points) * len(workloads),
            "sampled_lanes": [f"w{w}:{points[i].name()}"
                              for w, i in sampled],
            "ec2_rows_checked": len(ec2_i) * len(workloads),
            "violations": bad}


def scenario_grid(width: int = 205, duration: float = 14 * DAY,
                  max_jobs: int = 3000, n_jobs: float = 2603.0):
    """The README's generated-scenario batch: ``width`` traces sweeping
    utilization, runtime/size coupling and WS peak."""
    import numpy as np
    from repro.sim.scenarios import PBJParams, ScenarioGrid, WSParams
    return ScenarioGrid(
        seeds=tuple(range(width)),
        pbj=PBJParams(utilization=np.linspace(0.35, 0.8, width),
                      alpha=np.linspace(0.15, 0.7, width), n_jobs=n_jobs),
        ws=WSParams(peak=np.round(np.linspace(32, 128, width))),
        duration=duration, max_jobs=max_jobs)


def scenario_points():
    """FB C in {96, 128, 160} and FLB-NUB B=25 at L=3600 s and 1800 s."""
    from repro.sim.sweep import SweepPoint
    return ([SweepPoint("fb", capacity=c) for c in (96, 128, 160)]
            + [SweepPoint("flb_nub", lb_pbj=13, lb_ws=12,
                          lease_seconds=L) for L in (3600.0, 1800.0)])


def phase_scenarios(grid=None, seed: int = 0) -> dict:
    import numpy as np
    from repro.sim import scenarios
    from repro.sim.sweep import ScanOptions, run_sweep_workloads

    grid = grid if grid is not None else scenario_grid()
    points = scenario_points()
    opts = ScanOptions(window=SCENARIO_WINDOW)
    rows, timing = _timed_twice(lambda: run_sweep_workloads(
        points, grid, mode="rounds", scan_options=opts))
    stats, bad = _lane_stats(rows)

    rng = np.random.default_rng(seed)
    sample = sorted(int(w) for w in rng.choice(grid.n_lanes, 2,
                                               replace=False))
    synth = scenarios.synthesize(grid)
    ev = run_sweep_workloads(points, scenarios.sample_workloads(
        synth, sample), grid.duration, mode="event")
    for j, w in enumerate(sample):
        bad += _check("rounds", rows[w], ev[j])
    return {"phase": "b_scenarios", **timing, **stats,
            "lanes": grid.n_lanes * len(points), "sampled_traces": sample,
            "violations": bad}


def phase_capacity(horizon: float = 14 * DAY) -> dict:
    from repro.sim import traces
    from repro.sim.capacity import CapacitySLO, min_capacity
    from repro.sim.sweep import ScanOptions, SweepPoint, run_sweep

    jobs = [j for j in traces.nasa_ipsc(seed=0) if j.submit < horizon]
    ws = [(t, d) for t, d in traces.worldcup98(seed=0, peak_vms=128)
          if t < horizon]
    slo = CapacitySLO(min_completed_frac=0.95)
    template = SweepPoint("fb", lease_seconds=3600.0)
    # The bisection probes C=1, whose backlog is nearly the whole trace:
    # a window that holds every job keeps every probe exact.
    opts = ScanOptions(window=-(-len(jobs) // 128) * 128)

    def query():
        report = min_capacity(template, [(jobs, ws)], slo, lo=1, hi=256,
                              duration=horizon, mode="rounds",
                              scan_options=opts)
        r = report.result(0, 0)
        return [[r.row]], r.capacity, report.iterations

    (rows, cap, iters), timing = _timed_twice(query)
    bad = []
    if not slo.satisfied(rows[0][0], len(jobs)):
        bad.append(f"FB(C={cap}) does not meet {slo.describe(len(jobs))}")
    below = run_sweep([SweepPoint("fb", capacity=cap - 1,
                                  lease_seconds=3600.0)],
                      jobs, ws, horizon, mode="rounds", scan_options=opts)
    if slo.satisfied(below[0], len(jobs)):
        bad.append(f"FB(C={cap - 1}) already meets the SLO")
    stats, diag = _lane_stats(rows + [below])
    return {"phase": "c_capacity", **timing, **stats,
            "min_capacity": cap, "iterations": iters,
            "violations": bad + diag}


def phase_sharded(grid=None, devices: int = 4) -> dict:
    """Phase b with its lanes sharded over ``devices`` chips against the
    same batch on one device; rows must be bit-identical."""
    from repro.sim.sweep import ScanOptions, run_sweep_workloads

    grid = grid if grid is not None else scenario_grid()
    points = scenario_points()
    opts = ScanOptions(window=SCENARIO_WINDOW)
    sharded, t_sh = _timed_twice(lambda: run_sweep_workloads(
        points, grid, mode="rounds", scan_options=opts, devices=devices))
    single, t_one = _timed_twice(lambda: run_sweep_workloads(
        points, grid, mode="rounds", scan_options=opts))
    stats, bad = _lane_stats(sharded)
    diff = sum(a != b for ra, rb in zip(sharded, single)
               for a, b in zip(ra, rb))
    if diff:
        bad.append(f"{diff} sharded rows differ from single-device rows")
    return {"phase": "b_sharded", "devices": devices,
            "sharded": t_sh, "single": t_one, **stats,
            "lanes": grid.n_lanes * len(points), "rows_differing": diff,
            "violations": bad}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run phase b sharded over four chips against "
                         "one device, and nothing else")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the lanes re-run on the event engine")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (default device: "
              f"{devices[0].platform}); this script runs on the chip only",
              file=sys.stderr)
        return 1

    from repro.compat import enable_compile_cache
    cache = enable_compile_cache()
    print(json.dumps({"compile_cache": cache,
                      "device_kind": devices[0].device_kind,
                      "devices": len(devices)}), flush=True)

    warnings.simplefilter("error", RuntimeWarning)
    if args.chips == 4:
        phases = [lambda: phase_sharded(devices=4)]
    else:
        phases = [lambda: phase_paper_grid(seed=args.seed),
                  lambda: phase_scenarios(seed=args.seed),
                  phase_capacity]
    failed = False
    for phase in phases:
        out = phase()
        print(json.dumps(out), flush=True)
        failed |= bool(out["violations"])
    if failed:
        print("chip_smoke: contract violations (see above)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
