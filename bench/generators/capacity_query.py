"""Capacity queries: the smallest capacity that meets a completion SLO,
by ``min_capacity``'s batched bisection, one query per SLO.

Traffic keys: ``workload`` (index into the configuration's workloads),
``job_window`` (lanes, or ``"whole_trace"``),
``template`` (the point whose capacity knob the query owns), ``lo`` and
``hi`` (the capacity interval), ``slos`` (completion fractions, one
per query, dealt out in seeded orders) and ``check_answers`` (answers, sampled from the
window's queries, that the reference re-checks).
"""

from __future__ import annotations

import math
import time
from typing import Dict

import numpy as np

from bench import compare, reference
from bench.generators import common, site_traces

PROGRAMS = ("_rounds_grids_single",)


class CapacityQuery:
    def __init__(self, config: Dict, traffic: Dict, seed: int):
        from repro.sim.sweep import ScanOptions
        self.config, self.traffic, self.seed = config, traffic, seed
        self.horizon = float(config["horizon_s"])
        self.workload = site_traces.workload(
            config["workloads"][traffic["workload"]]).cut(self.horizon)
        w = self.workload
        self.jobs = common.program_jobs(w.submit, w.size, w.runtime)
        # Probes far below the answer hold nearly the whole trace in
        # their backlog: "whole_trace" sizes the job window to hold every
        # job (in blocks of 128), which keeps each probe exact.
        window = traffic["job_window"]
        if window == "whole_trace":
            window = -(-len(self.jobs) // 128) * 128
        self.options = ScanOptions(window=window, dtype=common.dtype(config))

    def query(self, k: int) -> float:
        """Query ``k``'s SLO: the list is dealt out in an order drawn
        from the seed, a fresh order every pass, so every run asks the
        same questions in another order."""
        slos = self.traffic["slos"]
        cycle, i = divmod(k, len(slos))
        return float(slos[common.rng(self.seed, cycle).permutation(len(slos))[i]])

    def run(self, slo: float):
        from repro.sim.capacity import CapacitySLO, min_capacity
        t = self.traffic
        report = min_capacity(
            common.sweep_point(t["template"]),
            [(self.jobs, list(self.workload.ws))],
            CapacitySLO(min_completed_frac=slo), lo=int(t["lo"]),
            hi=int(t["hi"]), duration=self.horizon, mode="rounds",
            scan_options=self.options)
        res = report.result(0, 0)
        return {"capacity": res.capacity, "row": res.row,
                "iterations": report.iterations}

    def summary(self, slo, answer) -> Dict:
        return {"rows": 1, "failed": common.bad_row(answer["row"]),
                "sweep_calls": answer["iterations"]}

    def check(self, done, r: np.random.Generator) -> Dict:
        """For each sampled answer C: the reference must meet the SLO at
        C and miss it at C - 1 (unless C is ``lo``), and the program's
        row at C must equal the reference's."""
        t, w = self.traffic, self.workload
        answered = [(k, slo, a) for k, slo, a in done if a is not None]
        picked = common.sample(r, answered, int(t["check_answers"]))
        rows: Dict[int, Dict] = {}

        def ref(c: int) -> Dict:
            if c not in rows:
                point = dict(t["template"], capacity=int(c))
                rows[c] = reference.row(point, w.submit, w.size, w.runtime,
                                        w.ws, self.horizon)
            return rows[c]

        t0 = time.perf_counter()
        n_jobs = len(w.submit)
        wrong, pairs = 0, []
        for _, slo, a in picked:
            need = int(math.ceil(slo * n_jobs))
            c = int(a["capacity"])
            if ref(c)["completed_jobs"] < need:
                wrong += 1
            elif c > int(t["lo"]) and ref(c - 1)["completed_jobs"] >= need:
                wrong += 1
            pairs.append((t["template"]["system"], a["row"], ref(c)))
        # Every metric of the answer's row is held exact: on every SLO of
        # the mix the planner's row agrees with the reference's to
        # round-off (PERF.md).
        out = compare.compare_rows(pairs, gaps=())
        out["answers_off"] = wrong
        out["answers_compared"] = len(picked)
        out["reference_s"] = time.perf_counter() - t0
        return out


def make(config: Dict, traffic: Dict, seed: int) -> CapacityQuery:
    return CapacityQuery(config, traffic, seed)
