"""JAX-native tick simulator: fidelity vs the event simulator + the
paper's parameter trends, as one vmapped program."""

import pytest

from repro.core import jaxsim
from repro.sim import traces
from repro.sim.engine import build_flb_nub, clone_jobs, run_sim


@pytest.fixture(scope="module")
def setup():
    jobs = traces.nasa_ipsc(seed=0)
    ws = traces.worldcup98(seed=0, peak_vms=128)
    return jobs, ws


def test_fidelity_vs_event_sim(setup):
    jobs, ws = setup
    T = traces.TWO_WEEKS
    ref = run_sim(build_flb_nub(13, 12), clone_jobs(jobs), ws, T)
    out = jaxsim.sweep([{"B": 25, "U": 1.2, "V": 0.2, "G": 0.5}],
                       jobs, ws, T)[0]
    assert abs(out["completed_jobs"] - ref.completed_jobs) <= 2
    assert abs(out["node_hours"] - ref.node_hours) / ref.node_hours < 0.15
    assert abs(out["peak_nodes"] - ref.peak_nodes) / ref.peak_nodes < 0.15


def test_pack_trace_dtype_follows_x64_setting(setup):
    """pack_trace defaults to the active x64 mode (the setting the sweep
    engine's exact paths run under), and takes an explicit dtype."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    jobs, ws = setup
    packed = jaxsim.pack_trace(jobs[:8], ws[:8], 7200.0, 3600.0)
    assert packed[0].dtype == jnp.float32
    with jax.enable_x64(True):
        packed64 = jaxsim.pack_trace(jobs[:8], ws[:8], 7200.0, 3600.0)
        assert packed64[0].dtype == jnp.float64
        assert packed64[3].dtype == jnp.float64
        forced = jaxsim.pack_trace(jobs[:8], ws[:8], 7200.0, 3600.0,
                                   dtype=np.float32)
        assert forced[0].dtype == jnp.float32
    # Explicit float64 without x64 would be silently downcast — refuse.
    with pytest.raises(ValueError, match="x64"):
        jaxsim.pack_trace(jobs[:8], ws[:8], 7200.0, 3600.0,
                          dtype=np.float64)


def test_vmapped_paper_trends(setup):
    """J1 (Fig 14): consumption grows and turnaround falls with B;
    §6.6.4: turnaround grows with G — in one batched program."""
    jobs, ws = setup
    grid = [{"B": b, "U": 1.2, "V": 0.2, "G": 0.5} for b in (13, 51, 154)] \
        + [{"B": 25, "U": 1.2, "V": 0.2, "G": g} for g in (0.25, 0.99)]
    out = jaxsim.sweep(grid, jobs, ws, traces.TWO_WEEKS)
    b_rows, g_rows = out[:3], out[3:]
    assert b_rows[0]["node_hours"] < b_rows[1]["node_hours"] \
        < b_rows[2]["node_hours"]                       # J1: nh grows w/ B
    assert b_rows[0]["avg_turnaround"] > b_rows[2]["avg_turnaround"]
    assert g_rows[0]["avg_turnaround"] < g_rows[1]["avg_turnaround"]  # G
    assert all(r["completed_jobs"] >= 2600 for r in out)
