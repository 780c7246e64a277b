"""Compile the planner's main path for a TPU v5e chip, without the chip.

The TPU compiler is installed with jax, and it compiles for a v5e
topology that is described rather than attached. These tests hold the
programs the chip runs to that compiler at the paper's width: the
rounds engine for each policy over the 45-evaluation paper pack, and
FB under failures as the ``paper.faults`` cell stacks it.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and every test worker
imports this file.
"""

import importlib.util
import os

import pytest

import jax
from jax.sharding import SingleDeviceSharding

from repro.sim import rounds as roundslib
from repro.sim import traces
from repro.sim.sweep import ScanOptions, SweepPoint, _pack_rounds

DAY = 24 * 3600.0
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler or library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture(scope="module")
def paper_pack():
    """The rounds pack of the paper grid (3 two-week workloads x 15
    FB / FLB-NUB points), as ``chip_smoke.py`` phase a runs it."""
    smoke = _chip_smoke()
    points = [p for p in smoke.paper_points()
              if p.system in ("fb", "flb_nub")]
    return _pack_rounds(points, smoke.paper_workloads(traces.TWO_WEEKS),
                        traces.TWO_WEEKS, ScanOptions())


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("policy", ["fb", "flb_nub"])
def test_rounds_program_compiles_for_v5e(policy, paper_pack, one_chip,
                                         no_persistent_cache):
    """The whole per-policy rounds program — the while loop over
    compaction, admission and the unrolled event rounds, vmapped over
    the sweep points and the three workloads — compiles for one v5e
    chip at paper width."""
    (_, _, fb, flb, fb_packed, flb_packed, fb_spec, flb_spec) = paper_pack
    if policy == "fb":
        args = _shapes((fb, None, fb_packed, None), one_chip)
        spec = dict(fb_spec=fb_spec, flb_spec=None)
        n_points = fb.lease.shape[0]
    else:
        args = _shapes((None, flb, None, flb_packed), one_chip)
        spec = dict(fb_spec=None, flb_spec=flb_spec)
        n_points = flb.lease.shape[0]
    assert n_points == (5 if policy == "fb" else 10)
    compiled = roundslib._rounds_grids_single.lower(*args, **spec).compile()
    out = compiled.out_info[policy]
    assert out["completed_jobs"].shape == (3, n_points)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    assert mem.temp_size_in_bytes < 16 * 2 ** 30      # fits one chip
    # Compaction ranks kept lanes by compare-and-count on the TPU: no
    # binary-search loop (one gather per level) is left in the program.
    hlo = compiled.as_text()
    assert "op_name=" in hlo
    assert "jit(searchsorted)/vmap()/while" not in hlo


def test_faulted_fb_program_compiles_for_v5e(one_chip, no_persistent_cache):
    """FB under failures, as ``run_sweep_workloads(faults=)`` stacks it:
    the paper's three workloads, each under a node-and-rack schedule,
    with the fault tables at a fixed ``fault_stops`` — the program the
    ``paper.faults`` cell runs, at two-week depth."""
    from repro.sim.faults import (burst_schedule, merge_schedules,
                                  weibull_schedule)
    smoke = _chip_smoke()
    horizon = traces.TWO_WEEKS
    points = [SweepPoint("fb", capacity=192, lease_seconds=L)
              for L in (900.0, 3600.0, 14400.0)]
    faults = [merge_schedules(
        weibull_schedule(s, 192, 6 * DAY, 6 * 3600.0, horizon, shape=0.7,
                         repair="lognormal"),
        burst_schedule(s + 1, 32, 6 * DAY, 4 * 3600.0, horizon,
                       repair="lognormal")) for s in range(3)]
    (_, _, fb, _, fb_packed, _, fb_spec, _) = _pack_rounds(
        points, smoke.paper_workloads(horizon), horizon,
        ScanOptions(fault_stops=2048, window=384), faults)
    assert fb_packed.fault_times.shape == (3, 2049)
    compiled = roundslib._rounds_grids_single.lower(
        *_shapes((fb, None, fb_packed, None), one_chip),
        fb_spec=fb_spec, flb_spec=None).compile()
    out = compiled.out_info["fb"]
    assert out["fault_rounds"].shape == out["fault_kills"].shape == (3, 3)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 30
