"""The readers of the program's own spans and counters, on tiny CPU
queries: which roots they read, what they compute from them, and when
they find nothing to read."""

from __future__ import annotations

import sys

import pytest

from bench import harness, program_spans
from bench.generators.common import WARMUP
from bench.tests import tiny

SWEEP = ("host_s.sweep", "pack_s.sweep", "fold_tables_s.sweep",
         "closed_forms_s.sweep", "dispatch_s.sweep", "device_wait_s.sweep",
         "lockstep_eff.sweep")
CAPACITY = ("host_s.capacity", "bisect_s.capacity", "pack_s.capacity",
            "device_wait_s.capacity", "fold_hits.capacity")


def queries(name: str, n: int):
    """The warm-up and ``n`` window queries of a one-day cell."""
    cell = tiny.cell(name)
    with tiny.precision(cell):
        sut = cell.generator.make(cell.config, cell.traffic, 2**31 + 9)
        for k in [WARMUP] + list(range(n)):
            sut.run(sut.query(k))


def record(queries: int, trace=None):
    return {"window": {"queries": queries}, "trace": trace}


DEVICE = {"devices": [{"busy_s": 1.0}]}      # a trace that saw the device


@pytest.fixture(scope="module")
def grid():
    queries("paper.grid", 3)
    from repro import spans
    return spans.roots("sweep")


@pytest.fixture(scope="module")
def capacity():
    queries("paper.capacity", 1)
    from repro import spans
    return spans.roots("capacity")


@pytest.mark.parametrize("n, trace, picked", [
    (3, None, [-3, -2, -1]),
    (3, DEVICE, [-3]),              # traced: the profiled query alone
    (1, None, [-1]),
    (1, DEVICE, [-1]),
    (3, {"devices": []}, None),     # the trace saw no device
])
def test_the_window_roots_are_the_last_ones(grid, n, trace, picked):
    got = program_spans.window_roots(record(n, trace), "sweep")
    assert (got and [r["id"] for r in got]) == (
        picked and [grid[i]["id"] for i in picked])


def test_sweep_readers_compute_from_the_window_roots(grid):
    rec = record(2, DEVICE)
    r = grid[-2]
    kids, c = r["children_s"], r["counters"]
    want = {"host_s.sweep": r["s"] - kids["sweep.wait"],
            "pack_s.sweep": kids["sweep.pack"],
            "fold_tables_s.sweep": kids["rounds.fold_tables"],
            "closed_forms_s.sweep": kids["sweep.closed_forms"],
            "dispatch_s.sweep": kids["sweep.dispatch"],
            "device_wait_s.sweep": kids["sweep.wait"],
            "lockstep_eff.sweep": 100.0 * c["rounds.lane_rounds"]
            / c["rounds.lane_slots"]}
    got = {m: harness.metric_reader(m)(rec) for m in SWEEP}
    assert got == pytest.approx(want)
    assert 0 < got["lockstep_eff.sweep"] <= 100
    assert 0 < got["host_s.sweep"] < r["s"]


def test_capacity_readers_compute_from_the_window_roots(capacity):
    rec = record(1)
    r = capacity[-1]
    kids, c = r["children_s"], r["counters"]
    hits, misses = c["fold_tables.hits"], c["fold_tables.misses"]
    want = {"host_s.capacity": r["s"] - kids["sweep.wait"],
            "bisect_s.capacity": r["s"] - kids["sweep"],
            "pack_s.capacity": kids["sweep.pack"],
            "device_wait_s.capacity": kids["sweep.wait"],
            "fold_hits.capacity": 100.0 * hits / (hits + misses)}
    got = {m: harness.metric_reader(m)(rec) for m in CAPACITY}
    assert got == pytest.approx(want)
    assert 0 < got["bisect_s.capacity"] < got["host_s.capacity"]


@pytest.mark.parametrize("cell, metrics", [("paper.grid", SWEEP),
                                           ("paper.capacity", CAPACITY)])
def test_readers_find_nothing_without_whole_roots(cell, metrics,
                                                  monkeypatch):
    from repro import spans
    # No roots at all.
    empty = spans.Recorder()
    monkeypatch.setattr(spans, "roots", empty.roots)
    assert all(harness.metric_reader(m)(record(1)) is None
               for m in metrics)
    # Roots whose spans the ring dropped, and fewer roots than queries.
    small = spans.Recorder(maxlen=8)
    monkeypatch.setattr(spans, "span", small.span)
    monkeypatch.setattr(spans, "count", small.count)
    monkeypatch.setattr(spans, "roots", small.roots)
    queries(cell, 1)
    assert small.dropped > 0
    for n in (1, 9):
        assert all(harness.metric_reader(m)(record(n)) is None
                   for m in metrics)
    # A program without the module.
    import repro
    monkeypatch.delattr(repro, "spans")
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert all(harness.metric_reader(m)(record(1)) is None
               for m in metrics)
