"""Training launcher.

Local (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --steps 50 --batch 8 --seq 128

Production (TPU pod; mesh axes data×model from the device grid):
  python -m repro.launch.train --arch internvl2-76b --mesh 16,16 \
      --batch 256 --seq 4096 --steps 1000 --ckpt-dir gs://...
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, get_config
from ..compile_cache import enable_compile_cache
from ..data import DataConfig, lm_batches
from ..models import build_model
from ..sharding import MeshRules, use_rules
from ..training import AdamWConfig, Trainer, save_checkpoint
from .mesh import make_mesh


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default=None, help="e.g. 16,16 → (data, model)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    trainer = Trainer(
        model,
        AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                    total_steps=args.steps),
        loss_chunk=min(512, args.seq),
    )

    rules = None
    mesh_cm = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        axes = ("data", "model")[: len(shape)] if len(shape) == 2 else (
            "pod", "data", "model")
        mesh = make_mesh(shape, axes)
        rules = MeshRules.for_mesh(mesh, fsdp=cfg.fsdp)
        mesh_cm = mesh

    def run():
        params, opt = trainer.init_state(jax.random.key(0))
        step_fn = trainer.jit_train_step(donate=True)
        it = lm_batches(cfg, DataConfig(batch=args.batch, seq_len=args.seq))
        t0 = time.time()
        for i in range(args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(it).items()}
            params, opt, m = step_fn(params, opt, batch)
            if i % args.log_every == 0 or i == args.steps - 1:
                print(
                    f"step {i:5d} loss {float(m['loss']):.4f} "
                    f"ce {float(m['ce']):.4f} gnorm {float(m['grad_norm']):.2f} "
                    f"lr {float(m['lr']):.2e} {(time.time()-t0)/(i+1):.2f}s/step",
                    flush=True,
                )
        if args.ckpt_dir:
            save_checkpoint(args.ckpt_dir, params, step=args.steps)
            print(f"saved checkpoint to {args.ckpt_dir}")
        return params

    if mesh_cm is not None:
        with use_rules(rules), mesh_cm:
            run()
    else:
        run()


if __name__ == "__main__":
    main()
