"""Compile the planner's main path for a TPU v5e chip, without the chip.

The TPU compiler is installed with jax, and it compiles for a v5e
topology that is described rather than attached. These tests hold the
programs the chip runs to that compiler at the paper's width: the
rounds engine for each policy over the 45-evaluation paper pack. They
also pin the fused Pallas round step's status: Mosaic refuses it, and
``kernel="pallas"`` on a TPU backend raises instead of interpreting.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and every test worker
imports this file.
"""

import importlib.util
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import round_step as rsk
from repro.sim import rounds as roundslib
from repro.sim import traces
from repro.sim.sweep import ScanOptions, SweepPoint, _pack_rounds, run_sweep

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


def test_mosaic_refuses_the_fused_round_step(one_chip, no_persistent_cache):
    """Mosaic has no lowering for the round body's prefix sums: the
    compiled kernel (bypassing ``chunk_step``'s guard) is refused."""
    from jax.experimental import pallas as pl
    K = 16
    spec = roundslib.RoundsSpec(duration=2 * DAY, max_rounds=4096, window=K,
                                kernel="pallas")
    f = jnp.float32
    shapes = [((3, 64 + K), f), ((2, 8), f), ((2, 50), f), ((2,), f),
              ((rsk.SC_SIZE,), f), ((rsk.WIN_ROWS, K), f)]
    sc, win = (jax.ShapeDtypeStruct(s, d) for s, d in shapes[-2:])
    call = pl.pallas_call(rsk._chunk_kernel("fb", spec),
                          out_shape=[sc, win], interpret=False)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    with pytest.raises(Exception, match="cumsum"):
        jax.jit(call).lower(*args).compile()


def test_pallas_kernel_is_refused_on_a_tpu_backend(monkeypatch):
    """With the backend reported as TPU, ``kernel="pallas"`` raises a
    NotImplementedError that names the refusal — it never falls back to
    interpret mode."""
    horizon = DAY
    jobs = [j for j in traces.nasa_ipsc(seed=0) if j.submit < horizon]
    ws = [(t, d) for t, d in traces.worldcup98(seed=0, peak_vms=64)
          if t < horizon]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="Mosaic"):
        run_sweep([SweepPoint("fb", capacity=96)], jobs, ws, horizon,
                  mode="rounds", scan_options=ScanOptions(kernel="pallas",
                                                          window=40))
    spec = roundslib.RoundsSpec(duration=horizon, max_rounds=8, window=4,
                                kernel="pallas")
    with pytest.raises(NotImplementedError, match="kernel=\"xla\""):
        rsk.chunk_step(*[jnp.zeros(1)] * 6, policy="fb", spec=spec)
