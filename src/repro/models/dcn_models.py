"""The paper's benchmark networks (Table III): VGG19 and SegNet with the
last-k convolution layers replaced by deformable convolutions.

Configurations follow §V-A: {VGG19, SegNet} x {-3, -8, -F} x {DCN-I, II}.
Replacement proceeds from the output layer toward the input layer ("we
have deformable convolution placed from the output layer to input layer
... to minimize the deformable convolution induced computation").

The forward pass selects an execution ``backend`` per deformable layer:

  * ``"xla"``      — reference path (repro.core.deform); differentiable.
  * ``"pallas"``   — whole-plane fused Pallas kernels (repro.kernels).
  * ``"pipeline"`` — each deformable layer alone through the graph
                     executor (``repro.runtime.dcn_pipeline``): TDT ->
                     Algorithm-1 schedule -> fused-kernel dispatch.
                     Forward only.
  * ``"graph"``    — the network-graph executor with cross-layer tile
                     fusion (repro.runtime.fused_exec): the backbone is
                     partitioned into fused groups whose boundary planes
                     never round-trip DRAM. Forward only.

The legacy ``use_pallas`` flag maps to ``backend="pallas"``.
``layer_shapes`` feeds the traffic simulator / fusion planner benchmarks.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.deform import (conv2d, deformable_conv2d,
                               fused_deformable_conv2d,
                               init_deformable_conv)
from repro.core.fusion import LayerShape
from repro.kernels.ops import deformable_conv2d_pallas
from repro.runtime.fused_exec import GraphConfig, dcn_pipeline, run_graph
from repro.runtime.graph import build_graph
from repro.runtime.staging import clamp_tile_config

# (channels, n_convs) per VGG19 stage; maxpool after each stage.
_VGG19_STAGES = ((64, 2), (128, 2), (256, 4), (512, 4), (512, 4))


@dataclasses.dataclass(frozen=True)
class DcnNetConfig:
    name: str                    # "vgg19" | "segnet"
    n_deform: int                # 3 | 8 | -1 (=F, all)
    variant: str = "dcn2"        # dcn1 | dcn2  (paper DCN-I / DCN-II)
    num_classes: int = 100
    in_channels: int = 3
    img_size: int = 64           # paper uses ImageNet 224; smoke uses 32/64
    width_mult: float = 1.0      # smoke-reduction knob
    max_displacement: float | None = None

    def stage_plan(self, decoder: bool = False):
        """[(c_in, c_out, deformable?)] conv list + pool markers."""
        stages = [(max(8, int(c * self.width_mult)), n)
                  for c, n in _VGG19_STAGES]
        convs: list[tuple[int, int]] = []
        c_prev = self.in_channels
        for c, n in stages:
            for _ in range(n):
                convs.append((c_prev, c))
                c_prev = c
        if decoder:  # SegNet decoder mirrors the encoder
            dec = []
            rev = list(reversed(convs))
            for i, (ci, co) in enumerate(rev):
                dec.append((co, ci if i < len(rev) - 1 else rev[-1][1]))
            convs = convs + dec
        n_def = len(convs) if self.n_deform < 0 else min(self.n_deform,
                                                         len(convs))
        flags = [i >= len(convs) - n_def for i in range(len(convs))]
        return [(ci, co, f) for (ci, co), f in zip(convs, flags)]


def init_dcn_net(key: jax.Array, cfg: DcnNetConfig, dtype=jnp.float32):
    decoder = cfg.name == "segnet"
    plan = cfg.stage_plan(decoder)
    params: dict[str, Any] = {"convs": []}
    for i, (ci, co, deform) in enumerate(plan):
        k = jax.random.fold_in(key, i)
        if deform:
            params["convs"].append(init_deformable_conv(
                k, ci, co, 3, cfg.variant, dtype))
        else:
            fan = 9 * ci
            params["convs"].append({
                "w": jax.random.normal(k, (3, 3, ci, co), dtype)
                * jnp.sqrt(2.0 / fan).astype(dtype),
                "b": jnp.zeros((co,), dtype),
            })
    if not decoder:
        k = jax.random.fold_in(key, 10_000)
        c_last = plan[-1][1]
        params["fc"] = {
            "w": jax.random.normal(k, (c_last, cfg.num_classes), dtype) * 0.02,
            "b": jnp.zeros((cfg.num_classes,), dtype),
        }
    else:
        k = jax.random.fold_in(key, 10_000)
        c_last = plan[-1][1]
        params["seg_head"] = {
            "w": jax.random.normal(k, (1, 1, c_last, cfg.num_classes), dtype)
            * 0.02,
            "b": jnp.zeros((cfg.num_classes,), dtype),
        }
    return params


def _pool_positions(cfg: DcnNetConfig) -> set[int]:
    """Conv indices after which a 2x2 maxpool (encoder) happens."""
    pos, i = set(), 0
    for _, n in _VGG19_STAGES:
        i += n
        pos.add(i - 1)
    return pos


def dcn_net_apply(params, cfg: DcnNetConfig, x, *, use_pallas: bool = False,
                  fused: bool = True, backend: str | None = None,
                  pipeline: GraphConfig | None = None,
                  graph: GraphConfig | None = None):
    """x: (N, H, W, C). Returns logits (N, classes) for vgg19 or per-pixel
    logits (N, H', W', classes) for segnet.

    backend: "xla" (default), "pallas", "pipeline" (each deformable
    layer alone through the graph executor, configured by
    ``pipeline``), or "graph" (the cross-layer fused network executor,
    configured by ``graph``); overrides ``use_pallas``.
    """
    if backend is None:
        backend = "pallas" if use_pallas else "xla"
    if backend not in ("xla", "pallas", "pipeline", "graph"):
        raise ValueError(f"unknown backend: {backend!r}")
    decoder = cfg.name == "segnet"

    if backend == "graph":
        net_graph = build_graph(cfg)
        gcfg = clamp_tile_config(graph or GraphConfig(), x.shape[1],
                                 x.shape[2])
        x = run_graph(params["convs"], net_graph, x, config=gcfg,
                      max_displacement=cfg.max_displacement)
        return _apply_head(params, cfg, x, decoder)

    plan = cfg.stage_plan(decoder)
    pools = _pool_positions(cfg)
    n_enc = sum(n for _, n in _VGG19_STAGES)

    def run_conv(p, x, deform):
        if deform:
            if backend == "pipeline":
                pcfg = pipeline or GraphConfig(
                    tile=max(2, min(8, x.shape[1] // 2, x.shape[2] // 2)))
                # The requested tile is an upper bound: deep-stage planes
                # shrink below it, so clamp per layer (the raw executor
                # rejects tile > plane).
                pcfg = clamp_tile_config(pcfg, x.shape[1], x.shape[2])
                return dcn_pipeline(x, p, variant=cfg.variant,
                                    max_displacement=cfg.max_displacement,
                                    config=pcfg)
            if backend == "pallas":
                return deformable_conv2d_pallas(
                    x, p, variant=cfg.variant,
                    max_displacement=cfg.max_displacement)
            fn = fused_deformable_conv2d if fused else deformable_conv2d
            return fn(x, p, variant=cfg.variant,
                      max_displacement=cfg.max_displacement)
        return conv2d(x, p["w"], p["b"])

    # Encoder pools are skipped once a plane side drops below 2; each
    # decoder upsample must mirror a pool that actually ran, or tiny
    # inputs inflate (img_size=8 used to yield 32x32 segnet logits).
    applied_pools: set[int] = set()
    for i, (ci, co, deform) in enumerate(plan):
        x = jax.nn.relu(run_conv(params["convs"][i], x, deform))
        if i < n_enc and i in pools and x.shape[1] >= 2 and x.shape[2] >= 2:
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            applied_pools.add(i)
        elif decoder and i >= n_enc and (2 * n_enc - 1 - i) in applied_pools:
            n, h, w, c = x.shape  # unpool by nearest-neighbour upsample
            x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)

    return _apply_head(params, cfg, x, decoder)


def _apply_head(params, cfg: DcnNetConfig, x, decoder: bool):
    if not decoder:
        x = x.mean(axis=(1, 2))
        return x @ params["fc"]["w"] + params["fc"]["b"]
    return conv2d(x, params["seg_head"]["w"], params["seg_head"]["b"])


def layer_shapes(cfg: DcnNetConfig) -> list[LayerShape]:
    """Deformable-layer shapes for the traffic/energy benchmarks, with the
    paper's 8-bit feature size (dtype_bytes=1)."""
    decoder = cfg.name == "segnet"
    plan = cfg.stage_plan(decoder)
    pools = _pool_positions(cfg)
    n_enc = sum(n for _, n in _VGG19_STAGES)
    hw = cfg.img_size
    applied_pools: set[int] = set()
    out = []
    for i, (ci, co, deform) in enumerate(plan):
        if deform:
            out.append(LayerShape(h=hw, w=hw, c_in=ci, c_out=co,
                                  kernel_size=3, dtype_bytes=1))
        if i < n_enc and i in pools and hw >= 2:
            hw = hw // 2
            applied_pools.add(i)
        elif decoder and i >= n_enc and (2 * n_enc - 1 - i) in applied_pools:
            hw *= 2
    return out
