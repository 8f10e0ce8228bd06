"""Paper Fig. 18 *executed*: cross-layer fused groups vs per-layer DRAM.

Runs a real VGG19-style DCN backbone through the network-graph executor
(``repro.runtime.fused_exec``) and cross-checks the executed trace against
the network-level traffic simulator (``repro.core.simulator``) with the
same FIFO-replay discipline as bench_scheduling:

  * per fused group, the executed group-input load sequence replayed
    through the FIFO buffer model must equal the simulator's fused
    prediction EXACTLY (same composite TDT, same Algorithm-1 schedule,
    same buffer model) — byte counts included;
  * the fused network DRAM total must be strictly below the per-layer
    (PR 1-style) execution of the same network — the Fig. 18 delta,
    reported per group and in aggregate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.deform import DeformableConvParams, randomize_offset_conv
from repro.obs import Stopwatch
from repro.core.simulator import simulate_network
from repro.models.dcn_models import DcnNetConfig, dcn_net_apply, init_dcn_net
from repro.runtime.fused_exec import (GraphConfig, network_sim_specs,
                                      run_graph, run_graph_dense)
from repro.runtime.graph import (FusedGroup, build_graph, group_weight_bytes,
                                 partition_graph)


def _case(img: int, n_deform: int, width_mult: float, seed: int,
          offset_scale: float = 2.0):
    cfg = DcnNetConfig(name="vgg19", n_deform=n_deform, img_size=img,
                       width_mult=width_mult, num_classes=4)
    key = jax.random.PRNGKey(seed)
    params = init_dcn_net(key, cfg)
    # Non-zero offset convs so the sampling pattern is genuinely irregular.
    convs = []
    for i, p in enumerate(params["convs"]):
        if isinstance(p, DeformableConvParams):
            p = randomize_offset_conv(p, jax.random.fold_in(key, 100 + i),
                                      offset_scale / p.w.shape[2])
        convs.append(p)
    params["convs"] = convs
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, img, img, 3))
    return cfg, params, x


def run(csv=print, img: int = 13, n_deform: int = 2,
        width_mult: float = 0.125, tile: int = 4,
        buffer_tiles: int | None = None, seed: int = 0):
    """Executor-vs-simulator cross-check + fused-vs-layerwise Fig. 18 delta."""
    cfg, params, x = _case(img, n_deform, width_mult, seed)
    gcfg = GraphConfig(tile=tile, buffer_tiles=buffer_tiles)

    graph = build_graph(cfg)
    y, trace = run_graph(params["convs"], graph, x, config=gcfg,
                         return_trace=True)
    y_ref = run_graph_dense(params["convs"], graph, x)
    err = float(jnp.max(jnp.abs(y.astype(jnp.float32)
                                - y_ref.astype(jnp.float32))))
    csv(f"graph_oracle,max_abs_err_vs_xla={err:.2e},"
        f"ok={'yes' if err < 1e-4 else 'NO'}")

    specs = network_sim_specs(trace)
    sim_fused = simulate_network(specs, boundary_bytes=trace.boundary_bytes,
                                 fused=True)
    sim_layer = simulate_network(specs, boundary_bytes=trace.boundary_bytes,
                                 fused=False)

    # Independent byte accounting straight from the graph IR, so a trace
    # bookkeeping bug cannot satisfy its own cross-check.
    itemsize = x.dtype.itemsize
    ir_groups = [s for s in partition_graph(graph, gcfg.onchip_budget_bytes,
                                            itemsize)
                 if isinstance(s, FusedGroup)]

    exact = True
    for gt, rep, seg in zip(trace.groups, sim_fused.groups, ir_groups):
        exec_loads = gt.fifo_replay().loads
        match = (exec_loads == rep.tile_loads
                 and gt.input_load_bytes == rep.input_read_bytes
                 and rep.output_write_bytes
                 == seg.h * seg.w * seg.c_out * itemsize
                 and rep.weight_read_bytes
                 == group_weight_bytes(seg, itemsize))
        exact &= match
        csv(f"graph_xcheck,group={gt.group},n_layers={rep.n_layers},"
            f"exec_fifo_loads={exec_loads},sim_loads={rep.tile_loads},"
            f"match={'yes' if match else 'NO'}")
    total_exact = (exact
                   and trace.total_dram_bytes == sim_fused.total_dram_bytes)
    csv(f"graph_xcheck_total,exec_dram_bytes={trace.total_dram_bytes},"
        f"sim_fused_bytes={sim_fused.total_dram_bytes},"
        f"exact={'yes' if total_exact else 'NO'}")

    for g_f, g_l in zip(sim_fused.groups, sim_layer.groups):
        if g_f.n_layers > 1:
            csv(f"fig18_group,n_layers={g_f.n_layers},"
                f"fused_bytes={g_f.total_dram_bytes},"
                f"layerwise_bytes={g_l.total_dram_bytes},"
                f"saved={g_l.total_dram_bytes - g_f.total_dram_bytes}")
    red = 1 - sim_fused.total_dram_bytes / sim_layer.total_dram_bytes
    below = sim_fused.total_dram_bytes < sim_layer.total_dram_bytes
    csv(f"fig18_network,fused_dram_bytes={sim_fused.total_dram_bytes},"
        f"layerwise_dram_bytes={sim_layer.total_dram_bytes},"
        f"reduction={100*red:.1f}%,"
        f"strictly_below={'yes' if below else 'NO'}")
    max_res = max((g.max_resident_bytes for g in trace.groups), default=0)
    csv(f"graph_buffers,recomputes={trace.total_recomputes},"
        f"max_resident_bytes={max_res},"
        f"schedule_cache_hits={trace.schedule_cache_hits},"
        f"misses={trace.schedule_cache_misses}")
    return trace, sim_fused, sim_layer


def run_dispatch(csv=print, img: int = 13, n_deform: int = 2,
                 width_mult: float = 0.125, tile: int = 4, batch: int = 2,
                 repeats: int = 3, seed: int = 0):
    """Per-tile loop vs whole-batch fused dispatch.

    Same network, same schedules (cache disabled for fair host-cost
    accounting); reports kernel-dispatch counts, end-to-end wall-clock
    (best of ``repeats`` after a compile warmup) and the host-prepass
    overlap fraction. The batch-fused count must be exactly one per
    layer segment PER BATCH, below both the per-tile count and the
    count of the same images run one at a time (the serving engine's
    degraded step: one per layer segment per image).
    """
    cfg, params, x = _case(img, n_deform, width_mult, seed)
    x = jnp.concatenate([x] * batch) if batch > 1 else x
    graph = build_graph(cfg)
    y_ref = run_graph_dense(params["convs"], graph, x)
    n_segments = sum(len(s.nodes) for s in
                     partition_graph(graph,
                                     GraphConfig().onchip_budget_bytes,
                                     x.dtype.itemsize)
                     if isinstance(s, FusedGroup))

    per_tile = GraphConfig(tile=tile, dispatch="per_tile", staging_depth=1,
                           use_schedule_cache=False)
    fused = GraphConfig(tile=tile, staging_depth=2,
                        use_schedule_cache=False)

    def run(gcfg, batches):
        """(outputs, traces) of ``x`` run as ``batches``."""
        outs = [run_graph(params["convs"], graph, xb, config=gcfg,
                          return_trace=True) for xb in batches]
        return jnp.concatenate([y for y, _ in outs]), [t for _, t in outs]

    whole = [x]
    alone = [x[i:i + 1] for i in range(x.shape[0])]
    variants = {"per_tile": (per_tile, whole),
                "batch_fused": (fused, whole),
                "one_at_a_time": (fused, alone)}
    results = {}
    for name, (gcfg, batches) in variants.items():
        y, traces = run(gcfg, batches)   # warmup: compiles kernels
        err = float(jnp.max(jnp.abs(y.astype(jnp.float32)
                                    - y_ref.astype(jnp.float32))))
        best = float("inf")
        for _ in range(repeats):
            with Stopwatch() as sw:
                y, traces = run(gcfg, batches)
                jax.block_until_ready(y)
            best = min(best, sw.dur)
        dispatches = sum(t.kernel_dispatches for t in traces)
        results[name] = (best, traces[0], dispatches)
        csv(f"dispatch_mode,mode={name},wall_ms={1e3 * best:.1f},"
            f"dispatches={dispatches},"
            f"max_abs_err_vs_xla={err:.2e},"
            f"ok={'yes' if err < 1e-4 else 'NO'}")

    t_p, tr_p, _ = results["per_tile"]
    t_f, tr_f, _ = results["batch_fused"]
    t_1, _, d_1 = results["one_at_a_time"]
    seg_bound = tr_f.kernel_dispatches <= n_segments
    csv(f"dispatch_bench,per_tile_ms={1e3 * t_p:.1f},"
        f"batch_fused_ms={1e3 * t_f:.1f},speedup={t_p / t_f:.2f}x,"
        f"per_tile_dispatches={tr_p.kernel_dispatches},"
        f"batch_fused_dispatches={tr_f.kernel_dispatches},"
        f"host_overlap_frac={tr_f.host_overlap_frac:.3f},"
        f"dispatches_le_segments={'yes' if seg_bound else 'NO'},"
        f"improved={'yes' if t_f < t_p else 'NO'}")
    # One dispatch per layer segment for the WHOLE batch.
    one_per_seg = tr_f.dispatches_per_batch == n_segments
    csv(f"batch_fused_bench,batch={batch},n_segments={n_segments},"
        f"dispatches_per_batch={tr_f.dispatches_per_batch},"
        f"one_at_a_time_dispatches={d_1},"
        f"batch_fused_ms={1e3 * t_f:.1f},one_at_a_time_ms={1e3 * t_1:.1f},"
        f"speedup_vs_one_at_a_time={t_1 / t_f:.2f}x,"
        f"one_dispatch_per_segment={'yes' if one_per_seg else 'NO'},"
        f"improved={'yes' if t_f < t_1 else 'NO'}")
    return results


def run_model_backend(csv=print, img: int = 16, n_deform: int = 2,
                      width_mult: float = 0.125, tile: int = 4,
                      seed: int = 0):
    """backend="graph" through the model entry point vs the XLA backend."""
    cfg, params, x = _case(img, n_deform, width_mult, seed)
    y_graph = dcn_net_apply(params, cfg, x, backend="graph",
                            graph=GraphConfig(tile=tile))
    y_xla = dcn_net_apply(params, cfg, x, backend="xla", fused=False)
    err = float(np.max(np.abs(np.asarray(y_graph) - np.asarray(y_xla))))
    csv(f"graph_model_backend,max_abs_err={err:.2e},"
        f"ok={'yes' if err < 5e-3 else 'NO'}")
    return err


if __name__ == "__main__":
    run()
    run_dispatch()
    run_model_backend()
