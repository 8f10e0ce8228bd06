"""End-to-end driver: train a deformable-conv classifier (~reduced VGG19-3)
for a few hundred steps on synthetic blob images, with checkpoints.

  PYTHONPATH=src python examples/train_dcn.py --steps 300

The deformable layers train their own offsets (stage-1 conv weights are
zero-initialized = regular grid, then learn to deform). Loss should fall
well below ln(4)=1.386 chance level.
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro import checkpoint as ckpt
from repro.data import DataConfig, image_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models.dcn_models import DcnNetConfig, dcn_net_apply, init_dcn_net
from repro.optim import AdamWConfig, adamw_update, init_opt_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--classes", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--variant", default="dcn2", choices=["dcn1", "dcn2"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_dcn_ckpt")
    args = ap.parse_args()

    cfg = DcnNetConfig(name="vgg19", n_deform=3, variant=args.variant,
                       img_size=32, width_mult=0.25,
                       num_classes=args.classes)
    params = init_dcn_net(jax.random.PRNGKey(0), cfg)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=20,
                          total_steps=args.steps, weight_decay=0.01)
    opt_state = init_opt_state(params, opt_cfg)
    dcfg = DataConfig(seed=0, global_batch=args.batch)

    @jax.jit
    def step(params, opt_state, images, labels):
        def loss_fn(p):
            logits = dcn_net_apply(p, cfg, images)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, labels[:, None], 1)[:, 0]
            return jnp.mean(lse - gold)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state, _ = adamw_update(params, grads, opt_state,
                                            opt_cfg)
        return params, opt_state, loss

    ckptr = ckpt.AsyncCheckpointer(args.ckpt_dir)
    t0 = time.time()
    first = None
    for s in range(args.steps):
        b = image_batch(dcfg, s, img=32, classes=args.classes)
        params, opt_state, loss = step(params, opt_state,
                                       jnp.asarray(b["images"]),
                                       jnp.asarray(b["labels"]))
        if first is None:
            first = float(loss)
        if s % 25 == 0 or s == args.steps - 1:
            print(f"step {s:4d} loss {float(loss):.4f} "
                  f"({time.time()-t0:.0f}s)", flush=True)
        if (s + 1) % 100 == 0:
            ckptr.save(s + 1, {"params": params, "opt": opt_state})
    ckptr.wait()
    print(f"done: loss {first:.3f} -> {float(loss):.3f} "
          f"(chance={jnp.log(args.classes):.3f}); "
          f"checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
