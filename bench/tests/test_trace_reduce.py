"""The reduction from a profiler trace to busy time, program time,
top operations and labelled idle gaps: on intervals written out by
hand, and on a small trace recorded on the CPU."""

from __future__ import annotations

import os

import pytest

from bench import trace_reduce as tr


def test_short_names_drop_the_signature():
    assert tr.short("%while.834 = (s32[1,5]) while(...)") == "%while.834"
    assert tr.short("jit_f(12)") == "jit_f(12)"


def test_merge_and_gaps():
    busy = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [[0, 3], [5, 8]]
    assert tr.gaps(busy, 0, 10) == [(3, 5), (8, 10)]
    assert tr.gaps(busy, -1, 6) == [(-1, 0), (3, 5)]
    assert tr.gaps([], 2, 4) == [(2, 4)]


def test_label_takes_the_innermost_open_span():
    spans = [("bench.query", 0, 100), ("bench.inner", 40, 60)]
    assert tr.label(50, spans) == "bench.inner"
    assert tr.label(10, spans) == "bench.query"
    assert tr.label(200, spans) == "outside benchmark spans"


def test_reduce_on_planes_written_out_by_hand():
    ns = 1e9
    planes = {
        "/device:TPU:0": {
            tr.OPS_LINE: [("fusion.1", 0, 2 * ns),
                          ("while.2", 2 * ns, 4 * ns),
                          ("fusion.3", 2.5 * ns, 3.5 * ns),
                          ("fusion.1", 6 * ns, 7 * ns)],
            tr.MODULES_LINE: [("jit__rounds_grids_single(1)", 0, 4 * ns),
                              ("jit_other(2)", 6 * ns, 7 * ns)]},
        "/host:CPU": {"python": [("bench.query", 0, 10 * ns),
                                 ("bench.inner", 3 * ns, 6 * ns)]},
    }
    r = tr.reduce(planes, programs=("_rounds_grids_single",))
    (dev,) = r["devices"]
    assert dev["busy_s"] == pytest.approx(5.0)
    assert dev["program_s"] == pytest.approx(4.0)
    # while.2 holds fusion.3: only the innermost operations rank.
    assert r["device_ops"] == [["fusion.1", pytest.approx(3.0)],
                               ["fusion.3", pytest.approx(1.0)]]
    assert r["idle_gaps"] == [
        ["bench.query (/device:TPU:0)", pytest.approx(3.0)],
        ["bench.inner (/device:TPU:0)", pytest.approx(2.0)]]
    assert r["span_s"] == pytest.approx(10.0)


def test_reduce_on_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    log_dir = os.fspath(tmp_path)
    with tr.record(log_dir):
        with jax.profiler.TraceAnnotation("bench.query"):
            for _ in range(3):
                f(x).block_until_ready()
    planes = tr.load(log_dir, everything=True)
    assert set(tr.load(log_dir)) == {p for p in planes
                                     if not p.startswith("/device:")}
    host = tr.spans(planes)
    assert [s[0] for s in host] == ["bench.query"]
    # The CPU has no device plane: hand the reduction the threads that
    # ran XLA's operations in its place.
    lines = [f"{p}|{line}" for p, ls in planes.items()
             for line, evs in ls.items()
             if any(not e[0].startswith(("$", "Thread", "bench."))
                    and e[2] > e[1] for e in evs) and "XLA" in line]
    assert not tr.device_planes(planes)
    r = tr.reduce(planes, devices=lines)
    span = host[0][2] - host[0][1]
    assert r["span_s"] == pytest.approx(span * 1e-9)
    for d in r["devices"]:
        assert 0.0 < d["busy_s"] <= r["span_s"]
    assert r["device_ops"] and r["idle_gaps"]
    assert all(g[0].startswith("bench.query") for g in r["idle_gaps"])
