"""Quickstart: the paper's deformable convolution, end to end.

  PYTHONPATH=src python examples/quickstart.py

1. builds a deformable conv (Eq. 1-3) and runs the XLA reference path,
2. runs the SAME layer through the fused Pallas kernel (BLI-as-matmul on
   the MXU; interpret mode on CPU) and checks they agree,
3. builds the Tile Dependency Table from the layer's real offsets, runs
   Algorithm 1, and prints the DRAM-traffic win over the naive order,
4. runs a small DCN network through the network-graph executor
   (backend="graph") and prints the per-group fused-vs-unfused DRAM
   bytes — the paper's Fig. 18 layer-fusion delta, executed.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (deformable_conv2d, init_deformable_conv,
                        make_square_grid, per_pixel_input_tiles,
                        schedule_tiles, simulate_network,
                        simulate_strategies, tdt_from_coords)
from repro.core.deform import conv2d, offsets_to_coords
from repro.kernels.ops import deformable_conv2d_pallas
from repro.launch.compile_cache import enable_compile_cache
from repro.models.dcn_models import DcnNetConfig, dcn_net_apply, init_dcn_net
from repro.runtime import GraphConfig, build_graph, run_graph
from repro.runtime.fused_exec import network_sim_specs


def main():
    key = jax.random.PRNGKey(0)
    c_in, c_out, hw = 32, 64, 24

    # 1. deformable conv, XLA reference path
    params = init_deformable_conv(key, c_in, c_out, variant="dcn2")
    params = params._replace(w_off=jax.random.normal(
        jax.random.fold_in(key, 1), params.w_off.shape) * 0.3)
    x = jax.random.normal(jax.random.fold_in(key, 2), (1, hw, hw, c_in))
    y_ref = deformable_conv2d(x, params)
    print(f"XLA path:    {x.shape} -> {y_ref.shape}")

    # 2. fused Pallas kernel (stages 2+3 in one VMEM-resident kernel)
    y_pal = deformable_conv2d_pallas(x, params)
    np.testing.assert_allclose(y_pal, y_ref, rtol=2e-4, atol=2e-4)
    print("Pallas path: matches XLA reference (rtol 2e-4)")

    # 3. TDT + Algorithm 1 over the layer's actual sampling pattern
    offsets = conv2d(x, params.w_off, params.b_off)
    coords = offsets_to_coords(offsets.astype(jnp.float32), 3, "dcn2")[0]
    grid = make_square_grid(hw, hw, 4)
    B = np.asarray(tdt_from_coords(coords, grid, grid))
    pp = np.asarray(per_pixel_input_tiles(coords, grid))
    rep = simulate_strategies(B, pp, grid, channels=c_in, c_out=c_out,
                              kernel_size=3, buffer_bytes=4096)
    sched = schedule_tiles(B, 4)
    print(f"TDT: {B.shape[0]} output tiles x {B.shape[1]} input tiles, "
          f"density {B.mean():.2f}")
    print(f"tile loads  naive={rep['naive'].tile_loads}  "
          f"bitvec={rep['bitvec'].tile_loads}  "
          f"Alg1={rep['scheduled'].tile_loads}")
    print(f"Alg 1 execution order (first 8 tiles): {sched.oid[:8]}")

    # 4. network-graph executor: cross-layer tile fusion (backend="graph")
    cfg = DcnNetConfig(name="vgg19", n_deform=2, img_size=16,
                       width_mult=0.125, num_classes=4)
    net_params = init_dcn_net(jax.random.fold_in(key, 3), cfg)
    imgs = jax.random.normal(jax.random.fold_in(key, 4), (1, 16, 16, 3))
    logits = dcn_net_apply(net_params, cfg, imgs, backend="graph",
                           graph=GraphConfig(tile=4))
    print(f"graph backend: {imgs.shape} -> logits {logits.shape}")

    graph = build_graph(cfg)
    _, trace = run_graph(net_params["convs"], graph, imgs,
                         config=GraphConfig(tile=4), return_trace=True)
    specs = network_sim_specs(trace)
    fused = simulate_network(specs, boundary_bytes=trace.boundary_bytes)
    unfused = simulate_network(specs, boundary_bytes=trace.boundary_bytes,
                               fused=False)
    for g_f, g_u in zip(fused.groups, unfused.groups):
        if g_f.n_layers > 1:
            print(f"  fused group ({g_f.n_layers} layers): "
                  f"{g_f.total_dram_bytes} B fused vs "
                  f"{g_u.total_dram_bytes} B per-layer")
    saved = 100 * (1 - fused.total_dram_bytes / unfused.total_dram_bytes)
    print(f"network DRAM: fused={fused.total_dram_bytes} B, "
          f"per-layer={unfused.total_dram_bytes} B ({saved:.1f}% saved)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
