"""repro.spans: nesting, the bounded ring, the counters the sweep path
records, and the profiler annotations each span writes."""

from __future__ import annotations

import contextlib
import gc
import glob
import os

import jax
import pytest

from repro import spans
from repro.sim import rounds, traces
from repro.sim.sweep import SweepPoint, run_sweep_workloads, warmup_sweep

H = 12 * 3600.0


def points(fb_capacities):
    return ([SweepPoint("fb", capacity=c) for c in fb_capacities]
            + [SweepPoint("flb_nub", lb_pbj=20, lb_ws=12),
               SweepPoint("dcs", prc_pbj=64, prc_ws=64),
               SweepPoint("ec2", lease_seconds=3600.0)])


@pytest.fixture(scope="module")
def workloads():
    out = []
    for seed in (2, 3):
        jobs = [j for j in traces.nasa_ipsc(seed=seed) if j.submit < H]
        ws = [(t, d) for t, d in traces.worldcup98(seed=seed, peak_vms=64)
              if t < H]
        out.append((jobs, ws))
    return out


@pytest.fixture(scope="module")
def call(workloads):
    """One warm ``run_sweep_workloads`` call whose FB capacities are new
    (fold-table misses) and whose FLB-NUB point is not (hits): its rows,
    its root span and the fold-table cache statistics around it."""
    run_sweep_workloads(points([48, 96]), workloads, H, mode="rounds")
    before = rounds.fold_table_cache_info()
    rows = run_sweep_workloads(points([49, 97]), workloads, H,
                               mode="rounds")
    after = rounds.fold_table_cache_info()
    return rows, spans.roots("sweep")[-1], before, after


def test_spans_nest_and_share_the_root_id():
    rec = spans.Recorder()
    with rec.span("q"):
        with rec.span("a"):
            with rec.span("b"):
                pass
        with rec.span("a"):
            rec.count("n", 2)
        rec.count("n")
    with rec.span("q"):
        pass
    first = [r for r in rec.records if r.root_id == rec.records[3].id]
    assert [r.name for r in first] == ["b", "a", "a", "q"]
    b, a1, a2, q = first
    assert q.parent_id is None and a1.parent_id == a2.parent_id == q.id
    assert b.parent_id == a1.id
    assert q.start_ns <= a1.start_ns <= b.start_ns <= b.end_ns <= q.end_ns
    (got, second) = rec.roots("q")
    assert got["counters"] == {"n": 3} and second["counters"] == {}
    assert got["children_s"]["a"] == pytest.approx(
        (a1.end_ns - a1.start_ns + a2.end_ns - a2.start_ns) * 1e-9)
    assert got["complete"] and rec.roots("a") == []


def test_the_ring_keeps_the_newest_records_and_counts_those_dropped():
    rec = spans.Recorder(maxlen=4)
    for _ in range(2):
        with rec.span("q"):
            with rec.span("a"):
                pass
            with rec.span("a"):
                pass
    assert len(rec.records) == 4 and rec.dropped == 2
    assert [r.name for r in rec.records] == ["q", "a", "a", "q"]
    # The first root lost its spans, the second kept them.
    assert [r["complete"] for r in rec.roots("q")] == [False, True]


def test_counters_need_an_open_root_and_the_collector_counts_there():
    spans.count("test.outside", 1)          # no root: nothing happens
    gc.collect()
    with spans.span("test.gc"):
        gc.collect()
    root = spans.roots("test.gc")[-1]
    assert root["counters"]["gc.collections"] >= 1
    assert root["counters"]["gc.pause_ns"] > 0
    assert "test.outside" not in root["counters"]


def test_fold_counters_are_the_cache_statistics_over_the_call(call):
    _, root, before, after = call
    c = root["counters"]
    assert c["fold_tables.hits"] == after.hits - before.hits
    assert c["fold_tables.misses"] == after.misses - before.misses
    # Two workloads: FB's new capacities miss, FLB-NUB's point hits.
    assert c["fold_tables.hits"] == 2 and c["fold_tables.misses"] == 2


def test_lane_counters_are_the_rows_rounds(call):
    rows, root, _, _ = call
    # One device call per query: each policy's lanes over all workloads
    # wait for its slowest lane over all workloads.
    total = slots = 0
    for kind in ("fb", "flb_nub"):
        r = [row["rounds"] for rs in rows for row in rs
             if row["system_kind"] == kind]
        total += sum(r)
        slots += len(r) * max(r)
    assert root["counters"]["rounds.lane_rounds"] == total
    assert root["counters"]["rounds.lane_slots"] == slots
    assert set(root["children_s"]) == {
        "sweep.closed_forms", "sweep.pack", "rounds.fold_tables",
        "sweep.dispatch", "sweep.wait"}


def test_rows_do_not_depend_on_the_spans(call, workloads, monkeypatch):
    rows = call[0]
    monkeypatch.setattr(spans, "span", lambda name: contextlib.nullcontext())
    monkeypatch.setattr(spans, "count", lambda name, n=1: None)
    bare = run_sweep_workloads(points([49, 97]), workloads, H, mode="rounds")
    assert bare == rows


def test_warmup_sweep_returns_its_root_span(workloads):
    s = warmup_sweep(points([48, 96]), workloads, H)
    assert s == spans.roots("sweep")[-1]["s"] > 0


def test_profiler_annotations_match_the_spans(call, workloads, tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        run_sweep_workloads(points([49, 97]), workloads, H, mode="rounds")
    finally:
        jax.profiler.stop_trace()
    root = spans.roots("sweep")[-1]
    mine = sorted((r for r in spans.RECORDER.records
                   if r.root_id == root["id"]), key=lambda r: r.start_ns)
    by_id = {r.id: r for r in mine}

    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = [(e.name[len(spans.PREFIX):], e.start_ns,
               e.start_ns + e.duration_ns)
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if not plane.name.startswith("/device:")
              for line in plane.lines for e in line.events
              if e.name.startswith(spans.PREFIX)]
    events.sort(key=lambda e: (e[1], -e[2]))
    assert [e[0] for e in events] == [r.name for r in mine]

    def parent(i):
        """The innermost annotation that holds annotation ``i``."""
        _, s, e = events[i]
        held = [j for j in range(i) if events[j][1] <= s
                and e <= events[j][2]]
        return events[held[-1]][0] if held else None

    for i, (r, (name, s, e)) in enumerate(zip(mine, events)):
        assert abs((e - s) - (r.end_ns - r.start_ns)) < 1e6, name
        want = by_id[r.parent_id].name if r.parent_id else None
        assert parent(i) == want, name
