"""Train any assigned --arch (reduced config) with the production trainer:
data pipeline -> sharded jit step -> async checkpoints -> resume.

  PYTHONPATH=src python examples/train_lm.py --arch jamba-v0.1-52b --steps 60
"""

import argparse

import jax.numpy as jnp

from repro import configs
from repro.configs.base import ShapeCell
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.train import train_loop
from repro.optim import AdamWConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=configs.ARCHS)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = configs.get_config(args.arch, smoke=True)
    shape = ShapeCell("example", "train", args.seq, args.batch)
    mesh = make_host_mesh(1, 1)
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                      total_steps=args.steps)
    _, _, losses = train_loop(cfg, shape, mesh, steps=args.steps,
                              opt_cfg=opt, ckpt_dir=args.ckpt_dir,
                              param_dtype=jnp.float32)
    print(f"[{args.arch}] loss {losses[0]:.3f} -> {losses[-1]:.3f} over "
          f"{args.steps} steps")
    assert losses[-1] < losses[0], "loss should decrease"


if __name__ == "__main__":
    enable_compile_cache()
    main()
