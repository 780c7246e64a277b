"""The plain reference of the fault cell: the PhoenixCloud paper's FB
system (arXiv:1006.1401 §5.1) on a site whose nodes fail and come back,
one event at a time on a heap. It builds on ``bench.reference`` (the
site ledger, the batch environment and its §5.1 kill order) and, like
it, imports nothing of the planner.

Failures follow the planner's stated semantics for FB: a failure takes
idle nodes first, then nodes of the batch environment (its unused ones,
then running jobs killed in the §5.1 order, smallest first and, among
equal sizes, the latest started first; a killed job is queued again
and starts over, as in plain kill mode), then web replicas, which are
shed until a repair. While nodes are down, web demand is met up to the
surviving nodes. A repair refills the web shortfall first; the rest of
the returned nodes stay idle until the next lease boundary gives them
to the batch environment. At most ``capacity`` nodes are down at once,
and a repair returns only nodes that are down.

Node-hours integrate the nodes the provider holds for a tenant, as
``bench.reference`` does: a failed node is held by no one, so it counts
nothing from its failure to the lease boundary after its repair (or to
the web refill that takes it). The peak is the most nodes held at once.

Events at one instant come in this order: a change of web demand, a
lease boundary, a job's arrival, a job's end, a repair, a failure;
events of one kind keep the order they were made in.
"""

from __future__ import annotations

import heapq
from typing import Dict, Sequence, Tuple

from bench import reference

__all__ = ["row"]

WS, TICK, SUBMIT, FINISH = reference.WS, reference.TICK, \
    reference.SUBMIT, reference.FINISH
REPAIR, FAIL = FINISH + 1, FINISH + 2


class FaultFB(reference.FB):
    """FB (§5.1) with ``failed`` of its ``capacity`` nodes down."""

    def __init__(self, sim, point):
        super().__init__(sim, point)
        self.failed = 0
        self.demand = 0          # web demand as asked, before any shedding

    def up(self) -> int:
        return self.c - self.failed

    def idle(self) -> int:
        return self.up() - self.sim.ledger.held

    def startup(self, t, demand):
        self.demand = demand
        super().startup(t, demand)

    def on_ws(self, t, demand):
        self.demand = demand
        super().on_ws(t, min(demand, self.up()))

    def on_fail(self, t, k):
        k = min(k, self.up())
        if k <= 0:
            return
        self.failed += k
        over = self.sim.ledger.held - self.up()    # idle nodes went first
        if over <= 0:
            return
        batch = self.sim.batch
        give = min(over, batch.owned)
        if give:
            batch.give_back(t, give)
            self.sim.ledger.add(t, -give)
        shed = over - give
        if shed:
            self.ws -= shed
            self.sim.ledger.add(t, -shed)

    def on_repair(self, t, k):
        k = min(k, self.failed)
        if k <= 0:
            return
        self.failed -= k
        grow = min(min(self.demand, self.up()) - self.ws, self.idle())
        if grow > 0:
            self.ws += grow
            self.sim.ledger.add(t, grow)


class FaultSim(reference.Sim):
    """One FB point over one workload and one failure schedule."""

    def __init__(self, point: Dict, submit, size, runtime, duration: float):
        if point["system"] != "fb":
            raise ValueError(f"the fault reference runs FB points only, "
                             f"got {point['system']!r}")
        super().__init__(point, submit, size, runtime, duration)
        self.policy = FaultFB(self, point)

    def run(self, ws: Sequence[Tuple[float, int]],
            faults: Sequence[Tuple[float, int]] = ()) -> Dict:
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
        for t, d in faults:
            if t > 0:
                self.push(float(t), FAIL if d > 0 else REPAIR, abs(int(d)))
        policy, batch = self.policy, self.batch
        policy.startup(0.0, demand0)
        while self.heap:
            t, kind, _, what = heapq.heappop(self.heap)
            if t > self.duration + reference.EPS:
                break
            if kind == WS:
                policy.on_ws(t, what)
            elif kind == TICK:
                policy.on_tick(t)
            elif kind == SUBMIT:
                batch.enqueue(what)
                batch.first_fit(t)
            elif kind == FINISH:
                j, run = what
                if run != self.runs[j]:
                    continue
                self.end_t[j], self.done[j] = t, True
                del batch.running[j]
                batch.first_fit(t)
            elif kind == REPAIR:
                policy.on_repair(t, what)
            else:
                policy.on_fail(t, what)
        return self.metrics()


def row(point: Dict, submit: Sequence[float], size: Sequence[int],
        runtime: Sequence[float], ws: Sequence[Tuple[float, int]],
        faults: Sequence[Tuple[float, int]], duration: float) -> Dict:
    """Metrics of one FB ``point`` over one workload whose site loses and
    regains nodes as ``faults`` says: ``(t, k)`` pairs, ``k > 0`` nodes
    failing at ``t``, ``k < 0`` nodes repaired; measured over
    ``duration`` seconds."""
    return FaultSim(point, submit, size, runtime, duration).run(ws, faults)
