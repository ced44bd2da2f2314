"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \
      --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
      --mesh host --steps 20     # sharded over local devices

Production notes (1000+ nodes): run under the cluster launcher with one
process per host; jax.distributed.initialize() picks up the coordinator;
the same code paths (mesh from launch.mesh, shardings from
distributed.sharding) then span pods. XLA flags for collective overlap:
  --xla_tpu_enable_async_collective_fusion=true
  --xla_tpu_enable_latency_hiding_scheduler=true
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import get_config, get_smoke_config, list_archs
from repro.data.pipeline import SyntheticCorpus
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.train.optimizer import OptConfig
from repro.train.trainer import Trainer


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="llama3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["none", "host"], default="none")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.replace(scan_layers=cfg.scan_layers and not args.smoke)
    corpus = SyntheticCorpus(cfg.vocab, seed=0)
    opt = OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                    total_steps=args.steps)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    mesh = make_host_mesh() if args.mesh == "host" else None
    trainer = Trainer(cfg, opt, corpus.batches(args.batch, args.seq),
                      ckpt=ckpt, ckpt_every=args.ckpt_every,
                      n_microbatches=args.microbatches,
                      compute_dtype=jnp.float32 if args.smoke else jnp.bfloat16,
                      mesh=mesh,
                      log_fn=lambda s, m: print(
                          f"step {s:5d}  loss {float(m['loss']):.4f}  "
                          f"lr {float(m['lr']):.2e}", flush=True)
                      if s % 10 == 0 else None)
    report = trainer.run(args.steps)
    print(f"\ndone: {report.steps_run} steps, final loss "
          f"{report.losses[-1]:.4f}, stragglers flagged: "
          f"{len(report.stragglers)}, preempted: {report.preempted}")


if __name__ == "__main__":
    main()
