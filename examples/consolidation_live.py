"""LIVE PhoenixCloud on JAX: real training jobs + a serving spike.

A miniature FB-policy cloud (8 logical chips): a real smollm training job
holds 6 chips; a web-serving spike demands 5, force-preempting the job
via CHECKPOINT (the beyond-paper §5.1 adaptation); the spike recedes, the
next lease tick re-provisions, and the job resumes from its checkpoint —
no lost work.

Run:  PYTHONPATH=src python examples/consolidation_live.py
"""
import os, sys, tempfile
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compat import enable_compile_cache

enable_compile_cache()

from repro.core.runtime_bridge import LiveCloud
from repro.launch.mesh import make_local_mesh

root = tempfile.mkdtemp(prefix="phoenixcloud_")
cloud = LiveCloud(capacity=8, mesh=make_local_mesh(), checkpoint_root=root)
cloud.submit_training(jid=1, arch="smollm_135m", chips=6, steps=20)
print("job 1 scheduled on 6/8 chips; training...")
cloud.run_quantum(steps=6)
p = cloud._live[1].payload
print(f"  progressed to step {p.step}/20")

print("WS spike: demand=5 chips -> checkpoint-preempt the job")
cloud.preempt_for_ws(5)
print(f"  job running: {1 in cloud.pbj.running}; "
      f"WS holds {cloud.service.cluster.allocated('WS')} chips; "
      f"checkpoint at step {p.step}")

print("spike recedes; lease tick re-provisions idle chips")
cloud.set_ws_demand(1)
cloud.lease_tick()
print(f"  job running again: {1 in cloud.pbj.running}")
while 1 in cloud._live:
    cloud.run_quantum(steps=6)
print(f"job 1 completed at step {p.step}/20 — "
      f"preemption cost zero lost steps (kill-restart would have lost "
      f"{6} steps).")

# Every decision above went through the same event pump + ledger the
# simulator uses — a live run is diffable against a simulated one.
print("\ndecision ledger (t, kind, arg, started/killed, pbj+ws nodes):")
for e in cloud.ledger.entries:
    print(f"  t={e.t:6.0f} {e.kind:7s} arg={e.arg:4.0f} "
          f"+{e.started}/-{e.killed} pbj={e.pbj_nodes} ws={e.ws_nodes}")
