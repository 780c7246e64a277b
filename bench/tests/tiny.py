"""Cells cut to a size the CPU test run holds: one simulated day and
small reference samples."""

from __future__ import annotations

import contextlib

from bench import harness

DAY = 86400.0


def cell(name: str, precision: str = "float32",
         days: float = 1.0) -> harness.Cell:
    harness.add_program_path()
    c = harness.Cell(harness.load_spec(), name)
    c.config["horizon_s"] = days * DAY
    c.config["precision"] = precision
    gen = c.traffic["generator"]
    if gen == "paper_sweep":
        c.traffic["check_rows"] = {"fb": 3, "flb_nub": 3, "ec2": 2, "dcs": 1}
    elif gen == "capacity_query":
        c.traffic.update(slos=[0.5, 0.6, 0.7], check_answers=2)
    return c


@contextlib.contextmanager
def precision(cell: harness.Cell):
    """x64 as the cell's configuration states it, for the enclosed block
    only: the test process is shared with other test files."""
    import jax
    with jax.enable_x64(cell.config["precision"] == "float64"):
        yield


def run(cell: harness.Cell, seed: int = 2**31 + 5, seconds: float = 0.0,
        trace: bool = False):
    """One run of ``cell`` on this process's default device, past the
    harness's look for a chip."""
    import io
    import jax
    with precision(cell):
        return harness.run_cell(cell, seed, seconds, trace,
                                jax.devices()[:1], log=io.StringIO())


def window_compiles(cell: harness.Cell, seed: int = 77, queries: int = 3):
    """The ``CompileClock`` of ``queries`` window queries run after one
    warm-up query, as the harness orders them."""
    from bench.compile_clock import CompileClock
    from bench.generators.common import WARMUP
    with precision(cell):
        sut = cell.generator.make(cell.config, cell.traffic, seed)
        sut.run(sut.query(WARMUP))
        with CompileClock() as clock:
            for k in range(queries):
                sut.run(sut.query(k))
    return clock


def alter_answers(monkeypatch):
    """Fault: every row's execution-time mean and node-hours altered by
    one part in a thousand where the rows are assembled."""
    from repro.sim import sweep
    assemble = sweep._assemble_rows

    def altered(*args, **kwargs):
        rows = assemble(*args, **kwargs)
        for rs in rows:
            for r in rs:
                r["avg_execution"] *= 1 + 1e-3
                r["node_hours"] *= 1 + 1e-3
        return rows

    monkeypatch.setattr(sweep, "_assemble_rows", altered)


def drop_half(monkeypatch):
    """Fault: the second half of every device batch's lanes left out,
    their metrics returned as zeros."""
    import numpy as np
    from repro.sim import rounds
    grids = rounds.rounds_grids

    def halved(*args, **kwargs):
        out = grids(*args, **kwargs)
        cut = {}
        for kind, metrics in out.items():
            cut[kind] = {}
            for k, v in metrics.items():
                a = np.array(v).reshape(-1)
                a[a.size // 2:] = 0
                cut[kind][k] = a.reshape(np.shape(v))
        return cut

    monkeypatch.setattr(rounds, "rounds_grids", halved)
