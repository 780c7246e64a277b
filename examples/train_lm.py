"""End-to-end training driver: train smollm-135m (the ~100M-class arch)
on the synthetic bigram-structured LM stream with checkpointing.

CPU container: defaults to the reduced config + 120 steps so the loss
curve is visible in ~a minute. The full 135M config and a few hundred
steps is the same command with --full --steps 300 (TPU-scale).

Run:  PYTHONPATH=src python examples/train_lm.py [--full] [--steps N]
"""
import argparse, os, sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compat import enable_compile_cache

enable_compile_cache()

from repro.configs.base import get_config, reduced_config
from repro.launch.mesh import make_local_mesh
from repro.train.trainer import TrainJob, TrainJobConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full 135M config (slow on CPU)")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--arch", default="smollm_135m")
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced_config(cfg)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"steps={args.steps}")
    job = TrainJob(cfg, TrainJobConfig(
        arch=args.arch, steps=args.steps, batch=8, seq_len=64, lr=3e-3,
        checkpoint_dir=args.ckpt, checkpoint_every=50), make_local_mesh())
    result = job.run()
    h = job.history
    print(f"loss: start {sum(h[:10])/10:.3f} -> end {sum(h[-10:])/10:.3f} "
          f"({result['wall_seconds']:.0f}s, ckpt at {args.ckpt})")
    assert sum(h[-10:]) < sum(h[:10]), "loss must decrease"
    print("resume check:", end=" ")
    job2 = TrainJob(cfg, TrainJobConfig(
        arch=args.arch, steps=args.steps, batch=8, seq_len=64,
        checkpoint_dir=args.ckpt), make_local_mesh())
    job2.initialize()
    print(f"restored at step {job2.step} OK")


if __name__ == "__main__":
    main()
