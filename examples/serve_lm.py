"""Serving example: batched requests against the autoscaled WS TRE.

The §6.4 instance-adjustment policy (80% slot-utilization threshold)
scales replicas up under a request burst and back down as it drains —
the live version of the paper's World Cup experiment.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import os, sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compat import enable_compile_cache

enable_compile_cache()

import numpy as np
from repro.configs.base import get_config, reduced_config
from repro.launch.mesh import make_local_mesh
from repro.serving.autoscaler import AutoscaledService
from repro.serving.engine import Request

cfg = reduced_config(get_config("smollm_135m"))
svc = AutoscaledService(cfg, make_local_mesh(), slots_per_replica=4,
                        max_len=64)
rng = np.random.default_rng(0)
print("tick  queue  active  replicas  util")
trace = []
for tick in range(120):
    if tick < 30:                      # request burst
        for _ in range(rng.poisson(1.5)):
            svc.submit(Request(rid=tick * 100 + _, max_new_tokens=12,
                               prompt=rng.integers(0, cfg.vocab, 8)
                               .astype(np.int32)))
    svc.tick(now=float(tick))
    trace.append(len(svc.replicas))
    if tick % 10 == 0:
        active = sum(r.n_active for r in svc.replicas)
        print(f"{tick:4d} {len(svc.queue):6d} {active:7d} "
              f"{len(svc.replicas):9d} {svc.utilization:5.2f}")
    if tick > 60 and not svc.queue and \
            all(r.n_active == 0 for r in svc.replicas) and \
            len(svc.replicas) <= 2:
        break
lat = [r.completed - r.submitted for r in svc.completed]
print(f"\ncompleted={len(svc.completed)} max_replicas={max(trace)} "
      f"final_replicas={trace[-1]}")
print("scale-up under load and scale-down after drain = paper Fig 8/9 "
      "behaviour, live.")
