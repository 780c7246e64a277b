"""Multi-device sweep backend: sharded vs single-device equality.

The ``devices`` option of ``run_sweep_workloads`` splits the rounds
path's flattened (point × trace) lane axis across host devices via
``shard_map`` (``repro.sim.lanes.sharded_grid_map``). Because every
lane runs the identical per-lane program, the sharded backend must
reproduce the single-device rows BIT-IDENTICALLY — including when the
lane count is not divisible by the device count, which exercises the
pad-and-drop path. The equality
test runs in a subprocess with two forced XLA host devices (the
test_distributed.py pattern), so it holds regardless of the machine CI
lands on.
"""

import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.tier1

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Every subprocess FIRST asserts the device count it was forced to —
# the resolved count, through the same repro.compat.resolve_devices the
# sweep backends use. If the XLA flag is ignored (a jax upgrade, a
# conflicting XLA_FLAGS from the outer environment, a platform that
# pins one device) the test FAILS with the resolution error instead of
# silently exercising the single-device path and reporting green.
_DEVICE_PREAMBLE = """
    import jax
    from repro.compat import resolve_devices
    devs = resolve_devices(2)
    assert devs is not None and len(devs) == 2, (
        "forced host device count not honored: resolved %r from %r"
        % (devs, jax.devices()))
    assert len(jax.devices()) == 2, jax.devices()
"""


def _run2(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"       # children never touch a chip
    out = subprocess.run(
        [sys.executable, "-c",
         textwrap.dedent(_DEVICE_PREAMBLE) + textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
@pytest.mark.sharded_subprocess
def test_forced_device_count_is_asserted_inside_the_subprocess():
    """The skip-surface fix: a subprocess whose device resolution falls
    back to 1 must FAIL (returncode != 0 with the resolution message),
    never skip — exercised by running the same preamble WITHOUT the
    XLA flag."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)          # no forced devices
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"       # children never touch a chip
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_DEVICE_PREAMBLE)],
        capture_output=True, text=True, env=env, timeout=420)
    if out.returncode == 0:             # multi-device host: flag moot
        import jax
        assert len(jax.devices()) >= 2
    else:
        assert ("xla_force_host_platform_device_count" in out.stderr
                or "not honored" in out.stderr), out.stderr[-2000:]


@pytest.mark.slow
@pytest.mark.sharded_subprocess
def test_sharded_matches_single_device_on_odd_lane_count():
    """3 workloads × 3 points per policy = 9 lanes — NOT divisible by 2
    devices, so both policies pad one lane and must drop it from the
    reported rows."""
    out = _run2("""
        from repro.sim import traces
        from repro.sim.sweep import SweepPoint, run_sweep_workloads

        T = 2 * 24 * 3600.0
        def cut(jobs):
            return [j for j in jobs if j.submit < T]
        def cutws(ws):
            return [(t, d) for t, d in ws if t < T]
        wls = [(cut(traces.nasa_ipsc(seed=3)),
                cutws(traces.worldcup98(seed=3, peak_vms=64))),
               (cut(traces.sdsc_blue(seed=3)),
                cutws(traces.worldcup98(seed=4, peak_vms=64))),
               (cut(traces.nasa_ipsc(seed=5)),
                cutws(traces.worldcup98(seed=5, peak_vms=64)))]
        pts = ([SweepPoint("fb", capacity=c) for c in (96, 128, 160)]
               + [SweepPoint("flb_nub", lb_pbj=B - 12, lb_ws=12)
                  for B in (25, 51, 102)]
               + [SweepPoint("ec2", lease_seconds=3600.0)])
        # Each policy's 9 lanes shard over the 2 devices: the odd-lane
        # pad-and-drop path.
        single_r = run_sweep_workloads(pts, wls, T, mode="rounds")
        sharded_r = run_sweep_workloads(pts, wls, T, mode="rounds",
                                        devices=2)
        assert sharded_r == single_r, [
            (w, i, a, b)
            for w, (ra, rb) in enumerate(zip(single_r, sharded_r))
            for i, (a, b) in enumerate(zip(ra, rb)) if a != b][:3]
        # The rows really took the rounds engine on both backends.
        assert all(r["engine"] == "rounds" for row in sharded_r
                   for r in row[:-1])
        print("OK")
    """)
    assert "OK" in out


def test_devices_request_beyond_visible_raises():
    """Asking for more devices than jax sees must fail with a message
    that names the XLA flag, not silently fall back to one device."""
    import jax
    import pytest
    from repro.sim import traces
    from repro.sim.sweep import SweepPoint, run_sweep

    T = 12 * 3600.0
    jobs = [j for j in traces.nasa_ipsc(seed=3) if j.submit < T]
    ws = [(t, d) for t, d in traces.worldcup98(seed=3, peak_vms=64)
          if t < T]
    too_many = len(jax.devices()) + 1
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        run_sweep([SweepPoint("fb", capacity=64)], jobs, ws, T,
                  mode="rounds", devices=too_many)


def test_devices_one_is_the_plain_single_device_path():
    """devices=1 collapses to the unsharded backend (resolve_devices
    returns None) — results are the plain path's, trivially
    bit-identical to not passing devices at all."""
    from repro.compat import resolve_devices
    from repro.sim import traces
    from repro.sim.sweep import SweepPoint, run_sweep

    import pytest

    assert resolve_devices(None) is None
    assert resolve_devices(1) is None
    with pytest.raises(ValueError, match="devices must be >= 1"):
        resolve_devices(0)
    with pytest.raises(ValueError, match="devices must be >= 1"):
        resolve_devices(-1)

    T = 12 * 3600.0
    jobs = [j for j in traces.nasa_ipsc(seed=3) if j.submit < T]
    ws = [(t, d) for t, d in traces.worldcup98(seed=3, peak_vms=64)
          if t < T]
    pts = [SweepPoint("fb", capacity=64),
           SweepPoint("flb_nub", lb_pbj=13, lb_ws=12)]
    assert run_sweep(pts, jobs, ws, T, mode="rounds", devices=1) \
        == run_sweep(pts, jobs, ws, T, mode="rounds")
