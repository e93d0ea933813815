"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \\
      --steps 50 --ckpt-dir /tmp/ckpt [--resume] [--compress-grads] \\
      [--microbatches 2] [--remat full] [--mesh host]

--smoke uses the reduced same-family config (CPU-runnable); the full config
is for real TPU slices.  --mesh host builds a mesh over the local devices;
the production meshes live in launch/mesh.py for the dry-run.

On TPU pods, launch with the standard JAX distributed bootstrap; the XLA
latency-hiding scheduler flags below enable compute/collective overlap
(distributed-optimization trick; no-ops on CPU).
"""

from __future__ import annotations

import argparse
import os

# compute/collective overlap on real hardware (harmless on CPU).  Appended:
# the TPU host may already set LIBTPU_INIT_ARGS, and its flags must stay
_OVERLAP_FLAGS = ("--xla_tpu_enable_async_collective_fusion=true "
                  "--xla_tpu_enable_async_collective_fusion_fuse_all_gather"
                  "=true")
os.environ["LIBTPU_INIT_ARGS"] = " ".join(
    filter(None, (os.environ.get("LIBTPU_INIT_ARGS"), _OVERLAP_FLAGS)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none",
                    choices=["none", "dots", "full"])
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", default="none", choices=["none", "host"])
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args()

    from repro.configs import get_config, reduce_for_smoke
    from repro.data.pipeline import DataConfig
    from repro.launch.mesh import make_host_mesh
    from repro.train.loop import TrainConfig, fit
    from repro.train.optimizer import OptConfig

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    dc = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                    global_batch=args.batch,
                    embed_dim=cfg.d_model if cfg.embed_inputs else 0)
    tc = TrainConfig(steps=args.steps, microbatches=args.microbatches,
                     remat=args.remat, compress_grads=args.compress_grads,
                     ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                     watchdog_secs=120.0)
    mesh = make_host_mesh(args.model_parallel) if args.mesh == "host" else None
    metrics = fit(cfg, dc, OptConfig(lr=args.lr, total_steps=args.steps),
                  tc, mesh=mesh, resume=args.resume)
    print("final:", metrics)


if __name__ == "__main__":
    main()
