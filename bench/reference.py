"""The benchmark's plain reference: the four provisioning systems of the
PhoenixCloud paper (arXiv:1006.1401), simulated one event at a time on
a heap, written from the paper's definitions and importing nothing of
the planner.

The site (§6.1). A ledger of the nodes the provider holds: node-hours
are the integral of that count over the measured period (the trace's
duration), the peak is its largest value, counted at every increase.

Events and their order at one instant: a change of web demand, then a
lease boundary (every ``L`` seconds, §4), then a job's arrival, then a
job's end; events of one kind keep the order they were made in.

Batch jobs (§6.5.2). A rigid job needs ``size`` nodes for ``runtime``
seconds. Queued jobs keep their order of arrival, and a killed job goes
back to its place in it. First fit: scan the queue in that order and
start every job that fits in the nodes the batch environment owns and
does not use.

- DCS (§6.5.1): a static partition, ``prc_pbj`` nodes for the batch
  queue and ``prc_ws`` for the web service, held all the time.
- FB (§5.1): a site of ``capacity`` nodes. At start the web service
  takes its demand and the batch queue the rest. A rise of web demand
  is met from idle nodes, then from the batch environment's unused
  nodes, then by killing running jobs, the smallest first and, among
  equal sizes, the latest started first; killed jobs are queued again
  and start over. A fall leaves nodes idle; at each lease boundary all
  idle nodes go to the batch environment.
- FLB-NUB (§5.2): ``lb_pbj + lb_ws`` nodes held from the start. The
  batch environment starts with ``lb_pbj`` of them; web demand takes up
  to ``lb_ws`` of the free pool nodes and leases the rest. At each lease
  boundary the idle pool nodes go to the batch environment, which then
  compares its queued demand D with the nodes it owns O: if D / O > U
  it leases D - O more; else if the largest queued job needs more than
  O it leases what that job lacks; else if D / O < V it gives back
  ``int(G * free)`` of its unused nodes, leased ones first
  (U = 1.2, V = 0.2, G = 0.5, §6.6.3).
- EC2+RightScale (§6.6.1, §6.6.2): web nodes follow demand; every job
  leases its own nodes on arrival, starts at once, and gives them back
  at the first lease boundary after it ends (a whole unit is paid).
"""

from __future__ import annotations

import bisect
import heapq
import itertools
import math
from typing import Dict, Sequence, Tuple

__all__ = ["row"]

WS, TICK, SUBMIT, FINISH = range(4)
EPS = 1e-9
U, V, G = 1.2, 0.2, 0.5


class Ledger:
    """Nodes the provider holds: their integral and their peak."""

    def __init__(self):
        self.held, self.t, self.node_s, self.peak = 0, 0.0, 0.0, 0

    def add(self, t: float, n: int) -> None:
        if n == 0:
            return
        self.node_s += (t - self.t) * self.held
        self.t = t
        self.held += n
        if n > 0:
            self.peak = max(self.peak, self.held)


class Batch:
    """The batch environment: nodes it owns, its queue and running jobs."""

    def __init__(self, sim: "Sim"):
        self.sim = sim
        self.owned = 0
        self.queue = []          # job ids in arrival order
        self.running = {}        # job id -> None, in order of start

    @property
    def used(self) -> int:
        return sum(self.sim.size[j] for j in self.running)

    @property
    def free(self) -> int:
        return self.owned - self.used

    def enqueue(self, j: int) -> None:
        bisect.insort_right(self.queue, j, key=self.sim.submit.__getitem__)

    def first_fit(self, t: float) -> None:
        free, keep = self.free, []
        for j in self.queue:
            if self.sim.size[j] <= free:
                free -= self.sim.size[j]
                self.running[j] = None
                self.sim.start(t, j)
            else:
                keep.append(j)
        self.queue = keep

    def grant(self, t: float, n: int) -> None:
        self.owned += n
        if n > 0:
            self.first_fit(t)

    def give_back(self, t: float, n: int) -> None:
        """Hand ``n`` owned nodes over, killing jobs as FB rule 2 says."""
        short = n - self.free
        size, start = self.sim.size, self.sim.start_t
        victims = sorted(self.running, key=lambda j: (size[j], -start[j]))
        for j in victims:
            if short <= 0:
                break
            del self.running[j]
            self.sim.kill(j)
            self.enqueue(j)
            short -= size[j]
        self.owned -= n
        self.first_fit(t)


class DCS:
    def __init__(self, sim, point):
        self.sim, self.pbj, self.ws = sim, point["prc_pbj"], point["prc_ws"]

    def startup(self, t, demand):
        self.sim.ledger.add(t, self.ws)
        self.sim.ledger.add(t, self.pbj)
        self.sim.batch.grant(t, self.pbj)

    def on_ws(self, t, demand):
        pass

    def on_tick(self, t):
        pass


class FB:
    def __init__(self, sim, point):
        self.sim, self.c, self.ws = sim, int(point["capacity"]), 0

    def idle(self):
        return self.c - self.sim.ledger.held

    def startup(self, t, demand):
        self.ws = min(demand, self.c)
        self.sim.ledger.add(t, self.ws)
        self.on_tick(t)

    def on_ws(self, t, demand):
        demand = min(demand, self.c)
        if demand > self.ws:
            from_idle = min(demand - self.ws, self.idle())
            self.sim.ledger.add(t, from_idle)
            from_batch = demand - self.ws - from_idle
            if from_batch:
                self.sim.batch.give_back(t, from_batch)
        else:
            self.sim.ledger.add(t, demand - self.ws)
        self.ws = demand

    def on_tick(self, t):
        idle = self.idle()
        self.sim.ledger.add(t, idle)
        self.sim.batch.grant(t, idle)


class FLBNUB:
    def __init__(self, sim, point):
        self.sim = sim
        self.lb_pbj, self.lb_ws = int(point["lb_pbj"]), int(point["lb_ws"])
        self.pool_pbj = self.pool_ws = 0     # pool nodes each side uses
        self.leased_pbj = self.leased_ws = 0

    def pool_idle(self):
        return self.lb_pbj + self.lb_ws - self.pool_pbj - self.pool_ws

    def startup(self, t, demand):
        self.sim.ledger.add(t, self.lb_pbj + self.lb_ws)
        self.pool_pbj = self.lb_pbj
        self.sim.batch.grant(t, self.lb_pbj)
        if demand:
            self.on_ws(t, demand)

    def on_ws(self, t, demand):
        self.pool_ws = min(demand, self.lb_ws, self.pool_ws + self.pool_idle())
        leased = max(0, demand - self.pool_ws)
        self.sim.ledger.add(t, leased - self.leased_ws)
        self.leased_ws = leased

    def on_tick(self, t):
        batch = self.sim.batch
        idle = self.pool_idle()
        if idle > 0:
            self.pool_pbj += idle
            batch.grant(t, idle)
        queued = sum(self.sim.size[j] for j in batch.queue)
        owned = batch.owned
        ratio = queued / owned if owned else (math.inf if queued else 0.0)
        largest = max((self.sim.size[j] for j in batch.queue), default=0)
        if ratio > U and queued > owned:
            self.lease(t, queued - owned)
        elif largest > owned and largest > batch.free:
            self.lease(t, largest - batch.free)
        elif ratio < V and int(G * batch.free) > 0:
            n = int(G * batch.free)
            from_lease = min(n, self.leased_pbj)
            batch.owned -= n
            self.sim.ledger.add(t, -from_lease)
            self.leased_pbj -= from_lease
            self.pool_pbj -= n - from_lease

    def lease(self, t, n):
        self.sim.ledger.add(t, n)
        self.leased_pbj += n
        self.sim.batch.grant(t, n)


class EC2:
    def __init__(self, sim, point):
        self.sim, self.ws, self.due = sim, 0, []

    def startup(self, t, demand):
        self.on_ws(t, demand)

    def on_ws(self, t, demand):
        self.sim.ledger.add(t, demand - self.ws)
        self.ws = demand

    def submit(self, t, j):
        self.sim.ledger.add(t, self.sim.size[j])
        self.sim.start(t, j)

    def finish(self, t, j):
        boundary = max(math.ceil((t - EPS) / self.sim.lease), 0) * self.sim.lease
        self.due.append((boundary, self.sim.size[j]))

    def on_tick(self, t):
        keep = []
        for boundary, n in self.due:
            if boundary <= t + 1e-6:
                self.sim.ledger.add(t, -n)
            else:
                keep.append((boundary, n))
        self.due = keep


POLICIES = {"dcs": DCS, "fb": FB, "flb_nub": FLBNUB, "ec2": EC2}


class Sim:
    """One system over one workload, to the end of ``duration``."""

    def __init__(self, point: Dict, submit, size, runtime, duration: float):
        self.submit = [float(s) for s in submit]
        self.size = [int(z) for z in size]
        self.runtime = [float(r) for r in runtime]
        self.duration = float(duration)
        self.lease = float(point.get("lease_seconds", 3600.0))
        n = len(self.submit)
        self.start_t = [-1.0] * n
        self.end_t = [-1.0] * n
        self.runs = [0] * n          # starts so far; a stale end is ignored
        self.done = [False] * n
        self.heap = []
        self.seq = itertools.count()
        self.ledger = Ledger()
        self.batch = Batch(self)
        self.policy = POLICIES[point["system"]](self, point)

    def push(self, t: float, kind: int, what) -> None:
        if t <= self.duration + EPS:
            heapq.heappush(self.heap, (t, kind, next(self.seq), what))

    def start(self, t: float, j: int) -> None:
        self.start_t[j] = t
        self.runs[j] += 1
        self.push(t + self.runtime[j], FINISH, (j, self.runs[j]))

    def kill(self, j: int) -> None:
        self.runs[j] += 1
        self.start_t[j] = -1.0

    def run(self, ws: Sequence[Tuple[float, int]]) -> Dict:
        for j, s in enumerate(self.submit):
            self.push(s, SUBMIT, j)
        demand0 = 0
        for t, d in ws:
            if t <= 0:
                demand0 = int(d)
            else:
                self.push(float(t), WS, int(d))
        k = 1
        while k * self.lease <= self.duration:
            self.push(k * self.lease, TICK, None)
            k += 1
        policy, batch = self.policy, self.batch
        policy.startup(0.0, demand0)
        while self.heap:
            t, kind, _, what = heapq.heappop(self.heap)
            if t > self.duration + EPS:
                break
            if kind == WS:
                policy.on_ws(t, what)
            elif kind == TICK:
                policy.on_tick(t)
            elif kind == SUBMIT:
                if isinstance(policy, EC2):
                    policy.submit(t, what)
                else:
                    batch.enqueue(what)
                    batch.first_fit(t)
            else:
                j, run = what
                if run != self.runs[j]:
                    continue
                self.end_t[j], self.done[j] = t, True
                if isinstance(policy, EC2):
                    policy.finish(t, j)
                else:
                    del batch.running[j]
                    batch.first_fit(t)
        return self.metrics()

    def metrics(self) -> Dict:
        done = [j for j, d in enumerate(self.done) if d]
        n = len(done)
        led = self.ledger
        node_s = led.node_s + (self.duration - led.t) * led.held
        return {
            "completed_jobs": n,
            "avg_turnaround": (sum(self.end_t[j] - self.submit[j]
                                   for j in done) / n) if n else 0.0,
            "avg_execution": (sum(self.end_t[j] - self.start_t[j]
                                  for j in done) / n) if n else 0.0,
            "node_hours": node_s / 3600.0,
            "peak_nodes": led.peak,
        }


def row(point: Dict, submit: Sequence[float], size: Sequence[int],
        runtime: Sequence[float], ws: Sequence[Tuple[float, int]],
        duration: float) -> Dict:
    """Metrics of one system ``point`` (a dict of its knobs) over one
    workload, measured over ``duration`` seconds."""
    return Sim(point, submit, size, runtime, duration).run(ws)
