"""The harness refuses to measure without a TPU: a non-zero exit and no
result line, never a fall-back to the CPU."""

from __future__ import annotations

import os
import subprocess
import sys

from bench import harness


def test_run_exits_non_zero_and_prints_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"),
         "--workload", "paper.grid", "--seed", "3000000000",
         "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


def test_require_chips_refuses_the_cpu():
    import jax
    import pytest
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(harness.NoChip):
        harness.require_chips(1)
