"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and
its phases hold their contracts when cut to a two-day horizon and a
few lanes — the rehearsal that keeps the chip run from rotting."""

import importlib.util
import json
import os
import subprocess
import sys
import warnings

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DAY = 24 * 3600.0


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300,
                         cwd=ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("phase", ["paper_grid", "scenarios", "capacity"])
def test_phases_meet_their_contracts_cut_to_two_days(smoke, phase):
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        if phase == "paper_grid":
            out = smoke.phase_paper_grid(horizon=2 * DAY)
            assert out["evals"] == 21 * 3
            assert out["rounds_lanes"] == 15 * 3
            assert out["ec2_rows_checked"] == 5 * 3
        elif phase == "scenarios":
            grid = smoke.scenario_grid(width=3, duration=2 * DAY,
                                       max_jobs=400, n_jobs=350.0)
            out = smoke.phase_scenarios(grid=grid)
            assert out["lanes"] == out["rows"] == 3 * 5
            assert len(out["sampled_traces"]) == 2
        else:
            out = smoke.phase_capacity(horizon=2 * DAY)
            assert 1 < out["min_capacity"] <= 256
    assert out["violations"] == []
    assert out["run_s"] > 0 and out["rounds_max"] >= out["rounds_mean"] > 0
    json.dumps(out)                      # one printable JSON line
