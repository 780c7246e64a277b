"""Two-week site traces shaped after the PhoenixCloud paper's inputs
(arXiv:1006.1401 §6.2): NASA iPSC and SDSC BLUE parallel-batch logs
(Parallel Workloads Archive) and World Cup '98 web demand.

A frozen copy of the planner's numpy trace generators, kept with the
benchmark so that the traffic it measures does not move when the
planner's own generators change. Each generator is deterministic given
its seed and returns plain arrays; ``workload`` bundles one batch trace
with one web-demand trace.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

TWO_WEEKS = 14 * 24 * 3600.0


@dataclasses.dataclass(frozen=True)
class TraceSpec:
    name: str
    nodes: int
    utilization: float
    n_jobs: int
    mean_runtime: float
    duration: float = TWO_WEEKS


NASA_IPSC = TraceSpec("nasa_ipsc", nodes=128, utilization=0.466,
                      n_jobs=2603, mean_runtime=573.0)
SDSC_BLUE = TraceSpec("sdsc_blue", nodes=144, utilization=0.762,
                      n_jobs=2657, mean_runtime=1975.0)
_SIZE_EXPS = np.arange(8)           # 1 .. 128, powers of two
_SIZE_PROBS = np.array([.20, .15, .13, .12, .12, .12, .13, .03])


@dataclasses.dataclass(frozen=True)
class Workload:
    """One batch trace (arrival-sorted arrays) and one web-demand trace
    of ``(time, VMs)`` change points starting at t=0."""

    submit: np.ndarray
    size: np.ndarray
    runtime: np.ndarray
    ws: Tuple[Tuple[float, int], ...]

    def cut(self, horizon: float) -> "Workload":
        keep = self.submit < horizon
        return Workload(self.submit[keep], self.size[keep],
                        self.runtime[keep],
                        tuple((t, d) for t, d in self.ws if t < horizon))


def _arrivals(rng: np.random.Generator, n: int, duration: float) -> np.ndarray:
    """Bursty diurnal arrivals with deep night and weekend troughs."""
    n_burst = int(0.12 * n)
    n_base = n - n_burst
    t = rng.uniform(0, duration, size=6 * n_base)
    day_phase = 2 * np.pi * ((t % 86400.0) / 86400.0 - 0.375)
    keep_p = (1 + 0.95 * np.sin(day_phase)) / 1.95
    weekend = ((t // 86400.0).astype(int) % 7) >= 5
    keep_p = np.where(weekend, keep_p * 0.35, keep_p)
    t = t[rng.uniform(size=t.shape) < keep_p][:n_base]
    episodes = int(rng.integers(24, 40))
    centers = rng.uniform(0, duration, size=3 * episodes)
    c_phase = 2 * np.pi * ((centers % 86400.0) / 86400.0 - 0.375)
    centers = centers[np.sin(c_phase) > -0.2][:episodes]
    weights = rng.dirichlet(np.ones(len(centers)))
    counts = rng.multinomial(n_burst, weights)
    bursts = [c + rng.exponential(180.0, size=k)
              for c, k in zip(centers, counts)]
    out = np.concatenate([t] + bursts)
    out = np.clip(out, 0, duration - 1.0)
    out.sort()
    return out[:n]


def _sample_jobs(spec: TraceSpec, alpha: float, sigma: float, seed: int):
    """Power-of-two sizes, lognormal runtimes with mean ∝ size^alpha,
    one global rescale that pins the utilization exactly, and
    full-machine jobs snapped to a nightly slot."""
    rng = np.random.default_rng(seed)
    submit = _arrivals(rng, spec.n_jobs, spec.duration)
    n = len(submit)
    sizes = 2 ** rng.choice(_SIZE_EXPS, size=n, p=_SIZE_PROBS)
    sizes = np.minimum(sizes, spec.nodes)
    mean_rt = sizes.astype(float) ** alpha
    mu = np.log(mean_rt) - sigma ** 2 / 2
    runtimes = rng.lognormal(mu, sigma)
    target = spec.utilization * spec.nodes * spec.duration
    runtimes *= target / float(np.sum(sizes * runtimes))
    runtimes = np.maximum(runtimes, 1.0)
    full = sizes >= spec.nodes
    if np.any(full):
        day = (submit[full] // 86400.0) * 86400.0
        submit = submit.copy()
        submit[full] = day + 2 * 3600.0 + rng.uniform(-7200, 7200,
                                                      size=int(full.sum()))
        submit = np.clip(submit, 0, spec.duration - 1.0)
        order = np.argsort(submit)
        submit, sizes, runtimes = submit[order], sizes[order], runtimes[order]
    return (submit.astype(np.float64), sizes.astype(np.int64),
            runtimes.astype(np.float64))


def nasa_ipsc(seed: int):
    """46.6 % utilization, mean runtime ≈ 573 s, 128 nodes."""
    return _sample_jobs(NASA_IPSC, alpha=0.68, sigma=1.0, seed=seed)


def sdsc_blue(seed: int):
    """76.2 % utilization, mean runtime ≈ 1975 s, 144 nodes."""
    return _sample_jobs(SDSC_BLUE, alpha=0.15, sigma=1.0, seed=seed)


def worldcup98(seed: int, peak_vms: int, step_seconds: float = 300.0,
               duration: float = TWO_WEEKS) -> List[Tuple[float, int]]:
    """Diurnal VM demand plus match-day surges; the peak is exactly
    ``peak_vms``. Returns change points starting at t=0."""
    rng = np.random.default_rng(seed + 7)
    t = np.arange(0.0, duration, step_seconds)
    day = (t % 86400.0) / 86400.0
    base = 10 + 6 * np.sin(2 * np.pi * (day - 0.3))
    base += rng.normal(0, 0.8, size=t.shape)
    surge = np.zeros_like(t)
    for d in rng.choice(np.arange(1, 14), size=12, replace=True):
        start = d * 86400.0 + rng.uniform(12, 20) * 3600.0
        length = rng.uniform(1.5, 3.5) * 3600.0
        amp = rng.uniform(22, 55)
        ramp = rng.uniform(0.15, 0.3) * length
        rel = t - start
        up = np.clip(rel / ramp, 0, 1)
        down = np.clip((length - rel) / ramp, 0, 1)
        surge += amp * np.clip(np.minimum(up, down), 0, 1)
    demand = np.maximum(base + surge, 1.0)
    demand *= peak_vms / demand.max()
    demand = np.maximum(np.round(demand).astype(int), 1)
    out: List[Tuple[float, int]] = [(0.0, int(demand[0]))]
    for i in range(1, len(t)):
        if demand[i] != out[-1][1]:
            out.append((float(t[i]), int(demand[i])))
    return out


def scale_demand(trace, factor: float) -> List[Tuple[float, int]]:
    """Scale demand values by ``factor``, rounding to whole VMs."""
    return [(t, max(0, int(round(v * factor)))) for t, v in trace]


BATCH_TRACES = {"nasa_ipsc": nasa_ipsc, "sdsc_blue": sdsc_blue}


def workload(spec: dict) -> Workload:
    """One workload from a configuration entry such as
    ``{"batch": "nasa_ipsc", "batch_seed": 0, "web_seed": 0,
    "web_peak_vms": 128, "web_scale": 1.0}``."""
    submit, size, runtime = BATCH_TRACES[spec["batch"]](spec["batch_seed"])
    ws = worldcup98(spec["web_seed"], peak_vms=spec["web_peak_vms"])
    if spec.get("web_scale", 1.0) != 1.0:
        ws = scale_demand(ws, spec["web_scale"])
    return Workload(submit, size, runtime, tuple(ws))
