"""Helpers the traffic generators share: seeded streams, the program's
input types, and the diagnostics every rounds row carries."""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

# Index of the warm-up query in the seeded stream; window queries count
# up from 0, so the warm-up asks a question no window query repeats.
WARMUP = 1 << 40


def rng(seed: int, *keys: int) -> np.random.Generator:
    """A stream drawn from ``(seed, *keys)``; any whole ``seed`` works."""
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) & ((1 << 64) - 1)] + [int(k) for k in keys]))


def dtype(config: Dict):
    """The pack precision the configuration states."""
    import jax.numpy as jnp
    return {"float64": np.float64, "float32": np.float32,
            "bfloat16": jnp.bfloat16}[config["precision"]]


def program_jobs(submit, size, runtime) -> List:
    """Batch jobs as the planner takes them."""
    from repro.core.jobs import Job
    return [Job(jid=i, submit=float(s), size=int(z), runtime=float(r))
            for i, (s, z, r) in enumerate(zip(submit, size, runtime))]


def sweep_point(point: Dict):
    """A point dict (``{"system": "fb", "capacity": 128, ...}``) as the
    planner's ``SweepPoint``."""
    from repro.sim.sweep import SweepPoint
    return SweepPoint(**point)


def bad_row(row: Dict) -> bool:
    """A rounds row that ran out of rounds or outgrew its job window."""
    return bool(row.get("truncated", 0) or row.get("window_overflow", 0))


def sample(r: np.random.Generator, items: Sequence, n: int) -> List:
    """Up to ``n`` of ``items`` without replacement, in drawn order."""
    if n >= len(items):
        return list(items)
    return [items[i] for i in r.choice(len(items), n, replace=False)]
