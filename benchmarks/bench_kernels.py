"""Kernel-level microbenchmarks + correctness gates.

CPU wall-times validate STRUCTURE (the matmul formulation beats the
gather formulation even on CPU because XLA vectorizes the contraction);
TPU performance claims come from the roofline analysis, not these timings.
Every timing row is preceded by an allclose gate vs the jnp oracle.
"""

from __future__ import annotations

import jax
import numpy as np

from repro.core.deform import deformable_conv2d, init_deformable_conv
from repro.kernels import ref
from repro.kernels.dcn_bli import bli_gather_reference, bli_tile_matmul
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import (coords_to_idx_coeff, deformable_conv2d_pallas,
                               resolve_interpret)
from repro.obs import Stopwatch


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    with Stopwatch() as sw:
        for _ in range(iters):
            jax.block_until_ready(fn(*args))
    return sw.dur / iters * 1e6


def run(csv=print):
    key = jax.random.PRNGKey(0)
    # --- BLI formulations on one 32x32x256 tile
    sh = sw = 32
    c, p = 256, 1024
    x_tile = jax.random.normal(key, (sh * sw, c))
    coords = jax.random.uniform(jax.random.fold_in(key, 1), (p, 2),
                                maxval=30.99)
    idx, coeff = coords_to_idx_coeff(coords, sh, sw)
    want = ref.bli_tile_ref(x_tile.reshape(sh, sw, c), coords)

    gather = jax.jit(bli_gather_reference)
    np.testing.assert_allclose(gather(x_tile, idx, coeff), want,
                               rtol=1e-5, atol=1e-5)
    t_gather = _time(gather, x_tile, idx, coeff)
    csv(f"kernel,bli_gather_xla,{t_gather:.0f},us_per_tile_allclose_ok")

    interp = resolve_interpret(None)
    t_matmul = _time(lambda x, i, cf: bli_tile_matmul(x, i, cf,
                                                      interpret=interp),
                     x_tile, idx, coeff)
    out = bli_tile_matmul(x_tile, idx, coeff, interpret=interp)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    mode = "interpret" if interp else jax.default_backend()
    csv(f"kernel,bli_matmul_pallas_{mode},{t_matmul:.0f},"
        f"us_per_tile_allclose_ok(device={mode})")

    # --- full deformable conv: XLA vs fused-Pallas paths
    params = init_deformable_conv(jax.random.fold_in(key, 2), 64, 64)
    params = params._replace(w_off=jax.random.normal(
        jax.random.fold_in(key, 3), params.w_off.shape) * 0.2)
    x = jax.random.normal(jax.random.fold_in(key, 4), (1, 32, 32, 64))
    y_ref = deformable_conv2d(x, params)
    y_pal = deformable_conv2d_pallas(x, params)
    np.testing.assert_allclose(y_pal, y_ref, rtol=2e-4, atol=2e-4)
    t_xla = _time(jax.jit(lambda x: deformable_conv2d(x, params)), x)
    csv(f"kernel,deform_conv_xla,{t_xla:.0f},us_per_img_allclose_ok")

    # --- flash attention vs reference
    ks = jax.random.split(jax.random.fold_in(key, 5), 3)
    q = jax.random.normal(ks[0], (1, 256, 8, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    out = flash_attention(q, k, v, interpret=interp)
    np.testing.assert_allclose(out, ref.attention_ref(q, k, v),
                               rtol=2e-4, atol=2e-4)
    t_ref = _time(jax.jit(lambda q, k, v: ref.attention_ref(q, k, v)),
                  q, k, v)
    csv(f"kernel,attention_xla_ref,{t_ref:.0f},us_allclose_ok")
    csv(f"kernel,flash_attention_pallas,validated,interpret={interp} "
        "vs oracle")


if __name__ == "__main__":
    run()
