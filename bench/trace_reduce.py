"""From a JAX profiler trace to the numbers the per-layer metrics read.

``record`` wraps a stretch of the run in a profiler session; ``load``
reads the ``.xplane.pb`` file it wrote into plain tuples; ``reduce``
turns those into device busy time, the time spent inside named device
programs, the operations that took most time and the longest idle gaps,
each gap labelled by the benchmark's own host spans (names starting
``bench.``) that were open at its midpoint.
"""

from __future__ import annotations

import contextlib
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

# (name, start_ns, end_ns)
Event = Tuple[str, float, float]
# plane name -> line name -> events
Planes = Dict[str, Dict[str, List[Event]]]


@contextlib.contextmanager
def record(log_dir: str):
    """Profile the enclosed block into ``log_dir``; Python's own
    function tracer stays off."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def short(name: str) -> str:
    """An operation's HLO instruction name, without its signature."""
    return name.split(" = ", 1)[0]


def load(log_dir: str, everything: bool = False) -> Planes:
    """The newest trace under ``log_dir``: the device planes' operation
    and module lines (operation names shortened), and the benchmark's
    own host spans; with ``everything``, every line of every plane."""
    import jax
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    planes: Planes = {}
    names: Dict[str, str] = {}
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if everything:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
            elif device and line.name in (OPS_LINE, MODULES_LINE):
                evs = []
                for e in line.events:
                    n = short(e.name)
                    evs.append((names.setdefault(n, n), e.start_ns,
                                e.start_ns + e.duration_ns))
            elif not device:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events
                       if e.name.startswith(SPAN_PREFIX)]
            else:
                continue
            if evs:
                planes.setdefault(plane.name, {}).setdefault(
                    line.name, []).extend(evs)
    return planes


def device_planes(planes: Planes) -> List[str]:
    """Names of the accelerator planes, in device order."""
    return sorted(p for p in planes
                  if p.startswith("/device:") and OPS_LINE in planes[p])


def merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Union of ``[start, end)`` intervals as sorted disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy: Sequence[Sequence[float]], start: float,
         end: float) -> List[Tuple[float, float]]:
    """The idle stretches of ``[start, end)`` outside the merged
    ``busy`` intervals."""
    out, t = [], start
    for s, e in busy:
        if s > t:
            out.append((t, min(s, end)))
        t = max(t, e)
        if t >= end:
            break
    if t < end:
        out.append((t, end))
    return [(s, e) for s, e in out if e > s]


def spans(planes: Planes, prefix: str = SPAN_PREFIX) -> List[Event]:
    """The benchmark's own host spans, from every host plane."""
    return sorted((ev for p, lines in planes.items()
                   if not p.startswith("/device:")
                   for evs in lines.values() for ev in evs
                   if ev[0].startswith(prefix)), key=lambda ev: ev[1])


def label(t: float, host_spans: Sequence[Event]) -> str:
    """The innermost (latest-starting) benchmark span open at ``t``."""
    best = None
    for name, s, e in host_spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "outside benchmark spans"


def reduce(planes: Planes, programs: Sequence[str] = (),
           devices: Sequence[str] = None, top: int = 10) -> Dict:
    """Per-device busy seconds (union of operation intervals), seconds
    inside device programs whose module name contains one of
    ``programs``, the ``top`` innermost operations by summed time and
    the ``top`` longest idle gaps within the benchmark's spans. Operation seconds
    are averaged over the devices.

    ``devices`` defaults to the accelerator planes; a test passes host
    thread lines here to exercise the arithmetic without a chip, as
    ``"plane|line"`` names."""
    devices = list(devices) if devices is not None else [
        f"{p}|{OPS_LINE}" for p in device_planes(planes)]
    host = spans(planes)
    t0 = min((s for _, s, _ in host), default=None)
    t1 = max((e for _, _, e in host), default=None)
    per_device, op_time, idle = [], {}, []
    for dev in devices:
        plane, line = dev.split("|", 1)
        ops = sorted(planes[plane][line], key=lambda ev: ev[1])
        busy = merge((s, e) for _, s, e in ops)
        # Time goes to the innermost operations: one that holds the next
        # (a while loop around its body) is left out of the ranking.
        for i, (name, s, e) in enumerate(ops):
            if i + 1 < len(ops) and ops[i + 1][1] < e:
                continue
            op_time[name] = op_time.get(name, 0.0) + (e - s) * 1e-9
        mods = planes[plane].get(MODULES_LINE, [])
        program_ns = sum(e - s for name, s, e in mods
                         if any(p in name for p in programs))
        per_device.append({
            "device": plane, "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "program_s": program_ns * 1e-9, "ops": len(ops)})
        if t0 is not None:
            for s, e in gaps(busy, t0, t1):
                idle.append((label((s + e) / 2, host), (e - s) * 1e-9,
                             plane))
    idle.sort(key=lambda g: -g[1])
    return {
        "devices": per_device,
        "device_ops": sorted(([n, s / max(1, len(devices))]
                              for n, s in op_time.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[f"{name} ({plane})", s]
                      for name, s, plane in idle[:top]],
        "span_s": (t1 - t0) * 1e-9 if t0 is not None else None,
    }
