"""One run of one benchmark cell: set-up, a timed window of back-to-back
queries, the reference check, and the result line.

Everything that belongs to one configuration, traffic mix or metric is
a file found by its name in ``BENCHMARK.json``:

- ``bench/configs/<config>.json`` (the entry's ``file``);
- ``bench/traffic/<traffic>.json``, whose ``generator`` names a module
  ``bench/generators/<generator>.py`` exposing ``make(config, traffic,
  seed)`` and ``PROGRAMS`` (device program names);
- ``bench/metrics/<metric>.py``, exposing ``read(record)``, which returns
  the metric's value or ``None`` when the run holds nothing to read.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


def process_age() -> float:
    """Seconds since this process started (Linux), from the kernel's
    start time of the process and the boot clock."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_spec(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(root: str, rel: str) -> Dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def metric_reader(name: str, root: str = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench.metrics." + name.replace(".", "__"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Cell:
    """A workload entry resolved to its configuration, traffic mix,
    generator module and the metrics it reports."""

    def __init__(self, spec: Dict, name: str, root: str = ROOT):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                           f"{sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = {c["name"]: c for c in spec["configs"]}[self.entry["config"]]
        self.config = _read_json(root, cfg["file"])
        self.traffic = _read_json(
            root, os.path.join("bench", "traffic",
                               self.entry["traffic"] + ".json"))
        self.generator = importlib.import_module(
            "bench.generators." + self.traffic["generator"])

        def mine(m):
            return name in m.get("workloads", [name])

        self.end_to_end = [m for m in spec["end_to_end"] if mine(m)]
        self.per_layer = [m for m in spec["per_layer"] if mine(m)]


def require_chips(chips: int) -> List:
    """The first ``chips`` TPU devices; raises :class:`NoChip` when JAX
    finds no TPU or fewer chips. Never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's default device is "
                     f"{devices[0].platform}; this benchmark measures the "
                     f"chip only")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices[:chips]


def add_program_path() -> None:
    """Make the planner (``src/``) importable."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def configure_jax(cell: Cell) -> str:
    """For a benchmark process: x64 on only where the configuration
    states float64, and JAX's persistent compile cache at a fixed path
    inside the checkout, whatever the environment names, so that two
    checkouts measured side by side share no compiled program; every
    program is cached whatever its compile time."""
    import jax
    add_program_path()
    jax.config.update("jax_enable_x64",
                      cell.config["precision"] == "float64")
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR


def _peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices: List, log=sys.stderr) -> Dict:
    """Set up, warm up, measure for ``seconds``, check against the
    reference and return the result object (without printing it)."""
    import jax
    from bench import compare, trace_reduce
    from bench.compile_clock import CompileClock
    from bench.generators.common import WARMUP, rng

    # 1. Set-up: inputs from the seed, then one warm-up query at the
    # window's shapes. Its tracing and compile-cache reads count.
    started = process_age()
    with CompileClock() as setup_clock:
        sut = cell.generator.make(cell.config, cell.traffic, seed)
        made = process_age()
        try:
            with jax.profiler.TraceAnnotation("bench.warmup"):
                sut.run(sut.query(WARMUP))
        except Exception:
            # The window's queries fail alike and are counted there.
            traceback.print_exc(file=log)
    setup_s = process_age()
    phases = {"start_s": started, "inputs_s": made - started,
              "warmup_s": setup_s - made, **setup_clock.as_dict()}
    print(json.dumps({"setup_phases": phases}), flush=True)

    # 2. Window: one caller, queries back to back; whole queries start
    # until ``seconds`` have elapsed. ``trace`` profiles the first query.
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    done, counters = [], {"rounds_max": [], "sweep_calls": []}
    query_s = []
    attempted = failed = rows = 0
    traced_s = None
    with CompileClock() as window_clock, contextlib.ExitStack() as tracing:
        first = time.perf_counter()
        if trace:
            tracing.enter_context(trace_reduce.record(trace_dir))
            t_trace = time.perf_counter()
        last = first
        k = 0
        while last - first < seconds or k == 0:
            q = sut.query(k)
            attempted += 1
            begun = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench.query"):
                    answer = sut.run(q)
            except Exception:
                traceback.print_exc(file=log)
                answer = None
            last = time.perf_counter()
            query_s.append(round(last - begun, 4))
            if trace and k == 0:
                traced_s = last - t_trace
                tracing.close()
            if answer is None:
                failed += 1
            else:
                s = sut.summary(q, answer)
                rows += s["rows"]
                failed += bool(s["failed"])
                for key in counters:
                    v = s.get(key)
                    counters[key] += v if isinstance(v, list) else (
                        [v] if v is not None else [])
            done.append((k, q, answer))
            k += 1
    window = {"queries": attempted, "rows": rows, "wall_s": last - first}
    compiles = window_clock.as_dict()
    line = {"window_compiles": compiles, "queries": attempted,
            "query_s": query_s}
    print(json.dumps(line), flush=True)
    if compiles["backend_compiles"]:
        print(f"bench: {compiles['backend_compiles']} backend compiles in "
              f"the window ({compiles['cache_hits']} from the persistent "
              f"cache)", file=log, flush=True)

    memory_peak = _peak_bytes(devices)

    reduced = None
    if trace:
        try:
            reduced = trace_reduce.reduce(
                trace_reduce.load(trace_dir),
                programs=cell.generator.PROGRAMS)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        reduced["window_s"] = traced_s
        reduced["queries"] = 1

    # 3. Reference check, once the window's device buffers are freed.
    gc.collect()
    numbers = sut.check(done, rng(seed, 1 << 41))
    ok, checks = compare.verdict(numbers)
    ok = ok and any(a is not None for _, _, a in done)

    record = {"setup_s": setup_s, "window": window, "counters": counters,
              "setup_compile": setup_clock.as_dict(),
              "window_compile": compiles, "trace": reduced,
              "numbers": numbers}
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = metric_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        busy = [d["busy_s"] for d in reduced["devices"]]
        device["busy_s"] = sum(busy) / len(busy) if busy else 0.0
        device["window_s"] = traced_s
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["numbers"] = numbers
    result["checks"] = checks
    return result


def print_result(result: Dict, out=sys.stdout, err=sys.stderr) -> None:
    """The compared numbers as the last lines of standard error, the
    result object as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
