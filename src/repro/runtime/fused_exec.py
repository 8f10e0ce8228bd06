"""Network executor with cross-layer tile fusion (paper §IV-D taken
network-wide).

Executes a :class:`~repro.runtime.graph.NetGraph` under the accelerator's
cross-layer dataflow: inside each
:class:`~repro.runtime.graph.FusedGroup`, boundary feature planes between
layers carry no *modeled* DRAM traffic — the
:class:`~repro.runtime.trace.NetworkTrace` prices exactly group-input
tile loads (under the FIFO buffer model), group outputs, weights and
pool/upsample boundary planes, matching
``core.simulator.simulate_network`` byte-for-byte:

  prepass   run the stage-1 chain densely (the paper's pre-scheduler
            runs ahead of the PE array) and build one TDT per layer —
            measured ``tdt_from_coords`` for DCN layers, analytic
            ``tdt_standard_conv`` halos for standard convs — then chain
            them (``compose_tdt``) into ONE Algorithm-1 schedule per
            fused group and image. Under ``batch_fused`` the prepass is
            per group for the whole batch, and its device work is one
            compiled program per group (``_group_prepass_program``);
            under ``per_tile`` it is per image. With ``staging_depth >
            1`` the next unit's prepass runs on a staging thread
            (``runtime.staging.run_staged``) while the current one
            executes.
  execute   two dispatch modes:
              * ``"batch_fused"`` (default) — the schedules of every
                image in the batch concatenate into ONE kernel grid per
                DCN layer (``kernels.dcn_fused.dcn_fused_batch``), with
                the scalar-prefetched dep table driving the input-tile
                DMA sequence; the standard convs, ReLUs, tile masks and
                the scatter around the kernels are compiled programs per
                group. Interior planes are whole device arrays between
                segments (recorded in ``LayerBufferStats``
                ``max_resident_bytes``). A single image is a batch of
                one: ``dcn_pipeline`` and the serving engine's degraded
                step run this path too.
              * ``"per_tile"`` — the reference model of the paper's
                bounded on-chip intermediate buffer: each group-output
                tile pulls its producer tiles recursively through a
                bounded recompute-on-evict :class:`TileBuffer` (eviction
                costs FLOPs, never modeled DRAM), one kernel dispatch
                per produced tile.

Both modes execute the same Algorithm-1 schedule, whose group-input load
order is what the trace records and the simulator prices — the batch
grid keeps each image's schedule order, so the cross-check stays exact.
benchmarks/bench_graph.py asserts it; tests/test_graph.py +
tests/test_batched_dispatch.py pin the numerics vs the XLA reference.
:func:`dcn_pipeline` runs one deformable layer as a one-node graph.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.deform import (DeformableConvParams, conv2d,
                               deformable_conv2d, offsets_to_coords)
from repro.core.scheduler import (DeviceSchedule, TileSchedule, pow2_pad,
                                  schedule_arrays_device, schedule_tiles,
                                  sequential_schedule)
from repro.core.tiles import (TileGrid, compose_tdt_chain,
                              compose_tdt_chain_device, tdt_from_coords,
                              tdt_standard_conv)
from repro.kernels.dcn_fused import (dcn_fused_batch,
                                     dcn_fused_batch_sharded,
                                     dcn_fused_tile)
from repro.kernels.dcn_schedule import (tdt_dispatch_arrays,
                                        tdt_from_coords_device)
from repro.kernels.ops import resolve_interpret, round_up
from repro.obs import Tracer, default_registry, get_tracer, use_tracer
from repro.runtime.cache import (ScheduleCache, chain_digest, conv_digest,
                                 coords_digest, default_schedule_cache,
                                 floors_digest)
from repro.runtime.graph import (DeformNode, FusedGroup, NetGraph, PoolNode,
                                 Segment, UpsampleNode, boundary_bytes,
                                 group_weight_bytes,
                                 partition_graph_cached)
from repro.runtime.packing import (build_neighbour_tables,
                                   narrow_dep_slots, pack_batch_schedules,
                                   pack_output_tile, pack_plane_operands,
                                   plane_to_tiles, tiles_to_plane)
from repro.runtime.staging import run_staged, validate_dispatch_config
from repro.runtime.shard import (ShardPlan, allgather_nbytes,
                                 plan_batch_shards, resolve_shard_mesh,
                                 shard_batch_schedules, stack_rows,
                                 unstack_rows)
from repro.runtime.trace import (GroupTrace, LayerBufferStats, NetworkTrace,
                                 TileRecord)

ONCHIP_BUDGET_BYTES = (128 + 256) * 1024   # paper Table I: input + output buf

# Process-wide like core.scheduler.host_schedule_builds: the serving
# engine keeps a construction-time baseline and reports its delta.
prepass_programs = default_registry().counter(
    "executor.prepass_programs",
    help="fused-group batch prepasses served by the compiled prepass "
         "program")
exec_programs = default_registry().counter(
    "executor.exec_programs",
    help="fused-group batch executes served by the compiled execute "
         "programs")
# Output tiles of the composite schedules built on cache misses (one
# Algorithm-1 run per group and image), counted while the executor's
# tracer is enabled, like the ``prepass.alg1`` span around each run.
alg1_tiles = default_registry().counter(
    "executor.alg1_tiles",
    help="output tiles scheduled by Algorithm 1 on schedule-cache misses "
         "(traced runs only)")

# Least dep-table width of a batch-fused dispatch. At 2-px offsets and
# 8x8 tiles an output tile reads 9-16 input tiles (SegNet-8 at 224²), so
# 32 slots hold every batch and one kernel compiles per layer; groups of
# at most 32 tiles keep their full width.
DEP_SLOTS_FLOOR = 32


@dataclasses.dataclass(frozen=True)
class GraphConfig:
    """Network-graph executor knobs."""

    tile: int | tuple[int, int] = 8       # tile side(s), shared per group
    buffer_tiles: int | None = None       # M for the composite schedule
    # Intermediate tile-buffer capacity per layer plane (per_tile dispatch).
    # None = derive from onchip_budget_bytes (budget split across the
    # group's layers); an int pins it, and undersizing only costs
    # recomputes, never correctness.
    inter_buffer_tiles: int | None = None
    schedule: str = "alg1"                # "alg1" | "sequential"
    block_p: int = 128                    # kernel pixel-block size
    interpret: bool | None = None         # None = auto (CPU -> interpret)
    onchip_budget_bytes: int = ONCHIP_BUDGET_BYTES  # drives group planning
    use_schedule_cache: bool = True
    # "batch_fused": the concatenated schedules of all batch images as one
    #   kernel grid per DCN layer, everything around the kernels compiled
    #   per group; with schedule_backend="device" the schedule arrays
    #   flow into the dispatch operands with zero host round trip.
    # "per_tile": one kernel dispatch per produced tile, intermediates in
    #   bounded recompute-on-evict TileBuffers — the reference model of
    #   the paper's on-chip intermediate buffer.
    dispatch: str = "batch_fused"
    # "host": TDT scatter + Algorithm-1 loop in host numpy/Python.
    # "device": both as Pallas kernels (kernels.dcn_schedule), bit-exact
    # vs the host path — the staging thread shrinks to packing only.
    schedule_backend: str = "host"
    # Images staged ahead of execution: 1 = serial, 2 = prepass image i+1
    # on a worker thread while image i executes (the default), >2 queues
    # deeper (rarely helps: prepass is single-threaded host work).
    staging_depth: int = 2
    # Staging-worker watchdog deadline (seconds); None = wait forever.
    # A staged prepass that misses it triggers failover to synchronous
    # prepass for the rest of the run (see staging.run_staged).
    watchdog_s: float | None = None
    # Batch-dimension scale-out (batch_fused only): an explicit
    # jax.sharding.Mesh with a "data" axis, or data_parallel=D (builds a
    # (D, 1) host mesh at run time). Each mesh device runs the
    # concatenated schedules of its local images; the only collective is
    # the all-gather at the logits.
    mesh: Any = None
    data_parallel: int | None = None
    # Simulator-guided plan autotuning (repro.tuning): "off" = greedy
    # partition at the default tile; "offline" = search (once per plan
    # key, cached) for the best cuts + per-group tile shapes;
    # "cached-only" = use a cached plan if present, never search (for
    # replicas that must not pay search latency).
    autotune: str = "off"
    # Directory for the persistent TunedPlan store; None = in-memory
    # only (the plan still survives across engines in this process).
    plan_cache_dir: str | None = None
    # Max simulator evaluations the search may pay per plan.
    autotune_budget: int = 128
    # Fault injector (repro.testing.faults.FaultInjector) — test/bench
    # only, excluded from config equality.
    faults: Any = dataclasses.field(default=None, compare=False)

    def __post_init__(self):
        validate_dispatch_config(self)

    @property
    def tile_hw(self) -> tuple[int, int]:
        t = self.tile
        th, tw = (t, t) if isinstance(t, int) else (int(t[0]), int(t[1]))
        if th < 1 or tw < 1:
            raise ValueError(f"tile sides must be >= 1, got {(th, tw)}")
        return th, tw


class TileBuffer:
    """Bounded on-chip store for one intermediate plane's output tiles.

    FIFO eviction like the paper's input buffer; a miss on a previously
    produced tile means recompute (fusion forbids the DRAM round trip).
    Used by the ``per_tile`` dispatch mode.
    """

    def __init__(self, capacity_tiles: int):
        if capacity_tiles < 1:
            raise ValueError("tile buffer capacity must be >= 1 tile")
        self.capacity = int(capacity_tiles)
        self._tiles: dict[int, Any] = {}
        self._queue: list[int] = []
        self._ever: set[int] = set()
        self.computes = 0
        self.recomputes = 0
        self.resident_bytes = 0
        self.max_resident_bytes = 0

    def get(self, tile: int):
        return self._tiles.get(tile)

    def put(self, tile: int, value, nbytes: int) -> None:
        self.computes += 1
        if tile in self._ever:
            self.recomputes += 1
        self._ever.add(tile)
        if tile not in self._tiles:
            self._queue.append(tile)
        self._tiles[tile] = value
        self.resident_bytes += nbytes
        while len(self._queue) > self.capacity:
            evicted = self._queue.pop(0)
            self._tiles.pop(evicted, None)
            self.resident_bytes -= nbytes  # uniform tile size per plane
        self.max_resident_bytes = max(self.max_resident_bytes,
                                      self.resident_bytes)


def apply_layer_dense(plane: jax.Array, node, p,
                      max_displacement: float | None = None) -> jax.Array:
    """XLA reference for one layer node on a (H, W, C) plane."""
    if isinstance(node, DeformNode):
        y = deformable_conv2d(plane[None], p, node.kernel_size, node.variant,
                              max_displacement)[0]
    else:
        y = conv2d(plane[None], p["w"], p["b"])[0]
    return jax.nn.relu(y) if node.relu else y


def apply_boundary_dense(plane: jax.Array, node: Segment) -> jax.Array:
    """Dense pool/upsample between groups (resolution boundary)."""
    if isinstance(node, PoolNode):
        k = node.window
        return jax.lax.reduce_window(plane[None], -jnp.inf, jax.lax.max,
                                     (1, k, k, 1), (1, k, k, 1), "VALID")[0]
    f = node.factor
    return jnp.repeat(jnp.repeat(plane, f, axis=0), f, axis=1)


def run_graph_dense(convs: list, graph: NetGraph, x: jax.Array,
                    max_displacement: float | None = None) -> jax.Array:
    """Dense XLA execution of the whole graph — the numerics oracle."""
    outs = []
    for i in range(x.shape[0]):
        plane = x[i]
        for node in graph.nodes:
            if isinstance(node, (PoolNode, UpsampleNode)):
                plane = apply_boundary_dense(plane, node)
            else:
                plane = apply_layer_dense(plane, node, convs[node.param_idx],
                                          max_displacement)
        outs.append(plane)
    return jnp.stack(outs)


def _segment_grid(seg: FusedGroup, th: int, tw: int) -> TileGrid:
    """Tile grid for one fused group: the group's autotuned tile shape
    when the plan set one, the config default otherwise — either way
    clamped to the group's plane (interior groups sit at lower
    resolution than the input)."""
    if seg.tile_hw is not None:
        th, tw = seg.tile_hw
    return TileGrid(seg.h, seg.w, min(th, seg.h), min(tw, seg.w))


def _inter_capacity(cfg: GraphConfig, group: FusedGroup, node,
                    tp: int, dtype_bytes: int) -> int:
    """Tile-buffer capacity for one layer plane: an even split of the
    on-chip budget across the group's layers, in that plane's tile size."""
    if cfg.inter_buffer_tiles is not None:
        return cfg.inter_buffer_tiles
    per_layer = cfg.onchip_budget_bytes // max(1, group.n_layers)
    return max(1, per_layer // (tp * node.c_out * dtype_bytes))


@functools.lru_cache(maxsize=None)
def _tile_valid_masks(grid: TileGrid) -> np.ndarray:
    """(T, tp, 1) float32 masks of every tile: 1 inside the real H x W
    plane, 0 on padding. They depend on the grid alone, so the stack is
    built once per grid (read-only: every caller shares it)."""
    r = np.arange(grid.rows * grid.th).reshape(grid.rows, 1, grid.th, 1)
    c = np.arange(grid.cols * grid.tw).reshape(1, grid.cols, 1, grid.tw)
    valid = (r < grid.h) & (c < grid.w)        # (rows, cols, th, tw)
    masks = valid.reshape(grid.num_tiles, grid.th * grid.tw, 1)
    masks = masks.astype(np.float32)
    masks.flags.writeable = False
    return masks


def _assemble_halo(dep_arrays: list, deps: np.ndarray, grid: TileGrid,
                   out_tile: int, r: int, c: int) -> jax.Array:
    """Paste dependent tiles into the (th+2r, tw+2r, C) halo window of
    ``out_tile``. Positions no tile covers stay zero — identical to the
    SAME-conv zero padding because produced tiles are masked beyond the
    real plane."""
    th, tw = grid.th, grid.tw
    tr, tc = divmod(out_tile, grid.cols)
    r_lo, c_lo = tr * th - r, tc * tw - r
    win = jnp.zeros((th + 2 * r, tw + 2 * r, c), dep_arrays[0].dtype)
    for d, arr in zip(deps, dep_arrays):
        dr, dc = divmod(int(d), grid.cols)
        a0, a1 = max(dr * th, r_lo), min((dr + 1) * th, r_lo + th + 2 * r)
        b0, b1 = max(dc * tw, c_lo), min((dc + 1) * tw, c_lo + tw + 2 * r)
        if a1 <= a0 or b1 <= b0:
            continue
        patch = arr.reshape(th, tw, c)[a0 - dr * th:a1 - dr * th,
                                       b0 - dc * tw:b1 - dc * tw]
        win = win.at[a0 - r_lo:a1 - r_lo, b0 - c_lo:b1 - c_lo].set(patch)
    return win


@dataclasses.dataclass
class _GroupArtifacts:
    """Prepass products for one fused group of one image (per_tile)."""

    grid: TileGrid
    m: int                                # schedule buffer capacity
    b_layers: list[np.ndarray]            # per-layer TDTs
    nbs: list                             # per-layer NeighbourTables | None
    sched: TileSchedule                   # composite Algorithm-1 schedule
    cache_hit: bool | None
    # TDT + schedule build wall time inside the prepass, and the portion
    # that ran through the device scheduling backend.
    schedule_s: float = 0.0
    schedule_device_s: float = 0.0


def _group_schedule_artifacts(
    x_g: jax.Array,
    group: FusedGroup,
    convs: list,
    grid: TileGrid,
    m: int,
    cfg: GraphConfig,
    max_displacement: float | None,
    cache: ScheduleCache | None,
    need_out_plane: bool,
    interp: bool = False,
    tracer: Tracer | None = None,
) -> tuple[_GroupArtifacts, jax.Array]:
    """Prepass for one group: per-layer TDTs + neighbour tables +
    composite schedule, plus the group's dense output plane when
    ``need_out_plane`` (a downstream group still holds a DeformNode whose
    offset conv consumes it — the stage-1 chain runs exactly as far ahead
    as the deformation reaches, no further).

    The (TDTs, schedule) pair is cached under the quantized-coords chain
    digest when a cache is given.
    """
    tr = tracer if tracer is not None else get_tracer()
    # Dense planes are consumed only by DeformNode offset convs; stop
    # advancing after the last consumer (monotone: deforms never reappear
    # past this point within the group when need_out_plane is False).
    needs_plane = [need_out_plane
                   or any(isinstance(nd, DeformNode)
                          for nd in group.nodes[j + 1:])
                   for j in range(group.n_layers)]
    plane = x_g
    nbs: list = []
    digests: list[str] = []
    dcn_coords: list = []
    for j, node in enumerate(group.nodes):
        p = convs[node.param_idx]
        if isinstance(node, DeformNode):
            offsets = conv2d(plane[None], p.w_off, p.b_off)
            coords = offsets_to_coords(offsets.astype(jnp.float32),
                                       node.kernel_size, node.variant,
                                       max_displacement)[0]
            nbs.append(build_neighbour_tables(coords, grid))
            digests.append(coords_digest(coords, grid))
            dcn_coords.append(coords)
        else:
            nbs.append(None)
            digests.append(conv_digest(node.kernel_size, grid))
            dcn_coords.append(None)
        if needs_plane[j]:
            plane = apply_layer_dense(plane, node, p, max_displacement)

    def build():
        device = cfg.schedule_backend == "device"
        b_layers = []
        with tr.span("prepass.tdt", backend=cfg.schedule_backend,
                     layers=group.n_layers):
            for node, coords in zip(group.nodes, dcn_coords):
                if coords is None:
                    # Standard-conv halos are static per grid — no offsets
                    # to decode, so the analytic host table stays.
                    b_layers.append(tdt_standard_conv(grid, grid,
                                                      node.kernel_size))
                elif device:
                    b_layers.append(np.asarray(tdt_from_coords_device(
                        coords, grid, grid, interpret=interp)))
                else:
                    b_layers.append(np.asarray(tdt_from_coords(coords,
                                                               grid,
                                                               grid)))
        comp = compose_tdt_chain(b_layers)
        if cfg.schedule == "alg1":
            sched = schedule_tiles(comp, m,
                                   backend=cfg.schedule_backend,
                                   interpret=interp)
        elif cfg.schedule == "sequential":
            sched = sequential_schedule(comp)
        else:
            raise ValueError(f"unknown schedule: {cfg.schedule!r}")
        return b_layers, sched

    with tr.timed("prepass.schedule",
                  backend=cfg.schedule_backend) as ssp:
        if cache is None:
            b_layers, sched = build()
            hit = None
        else:
            # Tile dims are hashed into every digest via the grid, but
            # stay an explicit key component too: same coords under a
            # different (tile_h, tile_w) must never collide.
            key = (chain_digest(digests, grid), grid.th, grid.tw, m,
                   cfg.schedule)
            if cfg.faults is not None:
                salt = cfg.faults.miss_salt()
                if salt is not None:
                    key = key + (salt,)
            (b_layers, sched), hit = cache.get_or_build(key, build)
        ssp.set(cached=hit)
    schedule_s = ssp.dur

    art = _GroupArtifacts(
        grid=grid, m=m, b_layers=list(b_layers), nbs=nbs, sched=sched,
        cache_hit=hit, schedule_s=schedule_s,
        schedule_device_s=(schedule_s
                           if cfg.schedule_backend == "device" else 0.0))
    return art, plane


def _image_prepass(
    x_i: jax.Array,
    segments: list[Segment],
    convs: list,
    cfg: GraphConfig,
    max_displacement: float | None,
    cache: ScheduleCache | None,
    interp: bool = False,
    tracer: Tracer | None = None,
) -> list[_GroupArtifacts | None]:
    """Host-side prepass of one whole image: the dense stage-1 chain runs
    ahead through the segments as far as the last DeformNode's offset
    conv needs it, emitting per-group schedule artifacts. Runs on the
    staging thread so it overlaps device execution of the previous
    image."""
    th, tw = cfg.tile_hw
    # deform_after[s]: a segment AFTER s still contains a DeformNode, so
    # segment s must keep advancing the dense plane for its prepass.
    deform_after = [False] * len(segments)
    seen = False
    for s in range(len(segments) - 1, -1, -1):
        deform_after[s] = seen
        if isinstance(segments[s], FusedGroup) and any(
                isinstance(nd, DeformNode) for nd in segments[s].nodes):
            seen = True

    arts: list[_GroupArtifacts | None] = []
    plane = x_i
    for s, seg in enumerate(segments):
        if isinstance(seg, (PoolNode, UpsampleNode)):
            if deform_after[s]:
                plane = apply_boundary_dense(plane, seg)
            arts.append(None)
        else:
            grid = _segment_grid(seg, th, tw)
            m = (grid.num_tiles if cfg.buffer_tiles is None
                 else cfg.buffer_tiles)
            art, plane = _group_schedule_artifacts(
                plane, seg, convs, grid, m, cfg, max_displacement, cache,
                need_out_plane=deform_after[s], interp=interp,
                tracer=tracer)
            arts.append(art)
    return arts


def _exec_group_per_tile(
    x_tiles: jax.Array,
    group: FusedGroup,
    convs: list,
    cfg: GraphConfig,
    interpret: bool,
    art: _GroupArtifacts,
    masks: list,
    dtype_bytes: int,
) -> tuple[jax.Array, list[LayerBufferStats], int]:
    """PR 2 demand-driven loop: one kernel dispatch per produced tile,
    intermediates in bounded recompute-on-evict TileBuffers."""
    grid, b_layers, nbs, sched = art.grid, art.b_layers, art.nbs, art.sched
    tp = grid.th * grid.tw
    bp = min(cfg.block_p, tp)
    p_pad = tp if tp % bp == 0 else round_up(tp, cfg.block_p)
    k_pad = [pow2_pad(int(b.sum(axis=1).max())) for b in b_layers]
    buffers = [TileBuffer(_inter_capacity(cfg, group, n, tp, dtype_bytes))
               for n in group.nodes]

    def produce(j: int, t: int) -> jax.Array:
        if j < 0:
            return x_tiles[t]
        cached = buffers[j].get(t)
        if cached is not None:
            return cached
        node = group.nodes[j]
        deps = np.flatnonzero(b_layers[j][t])
        dep_arrays = [produce(j - 1, int(d)) for d in deps]
        p = convs[node.param_idx]
        if isinstance(node, DeformNode):
            idx, coeff = pack_output_tile(nbs[j], grid, t, deps.tolist(),
                                          p_pad)
            x_packed = jnp.stack(dep_arrays)                  # (k, tp, C)
            if len(deps) < k_pad[j]:
                x_packed = jnp.pad(
                    x_packed, ((0, k_pad[j] - len(deps)), (0, 0), (0, 0)))
            kk = node.kernel_size ** 2
            w2 = p.w.reshape(kk, node.c_in, node.c_out)
            y = dcn_fused_tile(
                x_packed.reshape(k_pad[j] * tp, node.c_in),
                jnp.asarray(idx), jnp.asarray(coeff), w2, p.b,
                kernel_size=node.kernel_size, block_p=cfg.block_p,
                interpret=interpret)[:tp]
        else:
            r = (node.kernel_size - 1) // 2
            win = _assemble_halo(dep_arrays, deps, grid, t, r, node.c_in)
            y = conv2d(win[None], p["w"], p["b"], padding="VALID")[0]
            y = y.reshape(tp, node.c_out)
        if node.relu:
            y = jax.nn.relu(y)
        y = y * masks[t]    # zero padded-plane pixels: halo reads see zeros
        buffers[j].put(t, y, tp * node.c_out * dtype_bytes)
        return y

    last = group.n_layers - 1
    y_tiles: list = [None] * grid.num_tiles
    for out_tile in sched.oid:
        y_tiles[out_tile] = produce(last, out_tile)
    zero = jnp.zeros((tp, group.c_out), x_tiles.dtype)
    out = jnp.stack([t if t is not None else zero for t in y_tiles])

    stats = [LayerBufferStats(kind=n.kind, tiles_computed=b.computes,
                              recomputes=b.recomputes,
                              max_resident_bytes=b.max_resident_bytes)
             for n, b in zip(group.nodes, buffers)]
    dispatches = sum(b.computes for b in buffers)
    return out, stats, dispatches


def _run_group(
    x_g: jax.Array,
    group: FusedGroup,
    convs: list,
    cfg: GraphConfig,
    interpret: bool,
    art: _GroupArtifacts,
) -> tuple[jax.Array, GroupTrace]:
    h, w, c_in = x_g.shape
    grid, sched = art.grid, art.sched
    tp = grid.th * grid.tw
    dtype_bytes = x_g.dtype.itemsize

    x_tiles = plane_to_tiles(x_g, grid)
    masks = [jnp.asarray(m, x_g.dtype) for m in _tile_valid_masks(grid)]

    y_tiles, layer_stats, dispatches = _exec_group_per_tile(
        x_tiles, group, convs, cfg, interpret, art, masks, dtype_bytes)

    tile_bytes = tp * c_in * dtype_bytes
    trace = GroupTrace(
        grid=grid, tile_bytes=tile_bytes, buffer_tiles=art.m,
        schedule=cfg.schedule, schedule_cache_hit=art.cache_hit,
        schedule_backend=cfg.schedule_backend,
        dtype_bytes=dtype_bytes, layer_channels=group.layer_channels,
        output_bytes=h * w * group.c_out * dtype_bytes,
        weight_bytes=group_weight_bytes(group, dtype_bytes),
        b_layers=list(art.b_layers),
        kernel_dispatches=dispatches, dispatch=cfg.dispatch)
    trace.layer_stats = layer_stats
    for out_tile, loads in zip(sched.oid, sched.iid):
        trace.records.append(TileRecord(
            out_tile=out_tile,
            dep_tiles=tuple(loads),
            loaded_bytes=len(loads) * tile_bytes,
            buffer_bytes=len(loads) * tile_bytes))

    y = tiles_to_plane(y_tiles, grid, h, w)
    return y, trace


# ---------------------------------------------------------------------------
# Batch-fused dispatch: one kernel call per layer segment for the WHOLE batch.
# ---------------------------------------------------------------------------


def apply_boundary_batch(planes: jax.Array, node: Segment) -> jax.Array:
    """Batched :func:`apply_boundary_dense` — one op for all N images."""
    if isinstance(node, PoolNode):
        k = node.window
        return jax.lax.reduce_window(planes, -jnp.inf, jax.lax.max,
                                     (1, k, k, 1), (1, k, k, 1), "VALID")
    f = node.factor
    return jnp.repeat(jnp.repeat(planes, f, axis=1), f, axis=2)


def _advance_dense_batch(planes: jax.Array, node, p,
                         max_displacement: float | None) -> jax.Array:
    """Batched stage-1 chain advance (XLA, one dispatch for all images)."""
    if isinstance(node, DeformNode):
        y = deformable_conv2d(planes, p, node.kernel_size, node.variant,
                              max_displacement)
    else:
        y = conv2d(planes, p["w"], p["b"])
    return jax.nn.relu(y) if node.relu else y


@dataclasses.dataclass
class _ImageGroupSched:
    """One image's schedule bundle for one fused group, in dense
    dispatch form (the schedule-cache value for batch-fused mode)."""

    b_layers: list                        # per-layer TDTs (device or np)
    exec_scheds: list                     # per-layer DeviceSchedule | None:
    #   interior DCN layers dispatch in plane order over their own TDT
    #   rows; the LAST layer dispatches in the composite Algorithm-1
    #   order (its dep rows still come from its own TDT — the composite
    #   iid is the group-input load order the trace records).
    ds: DeviceSchedule                    # composite schedule (records)


@dataclasses.dataclass
class _BatchLayerOps:
    """One DCN layer's batch-fused operands (whole batch).

    Single-device: ``batch`` is a ``packing.BatchDispatch`` and
    idx/coeff are flat ``(N*T, p_pad, KK, 4)``. Sharded: ``shard`` is a
    ``shard.ShardedDispatch`` and idx/coeff carry a leading shard axis
    ``(D, n_max*T, p_pad, KK, 4)`` (shard-contiguous, zero-padded to the
    fullest shard).
    """

    batch: object                         # BatchDispatch | None
    idx: jax.Array
    coeff: jax.Array
    shard: object = None                  # ShardedDispatch | None


@dataclasses.dataclass
class _BatchGroupArtifacts:
    """Prepass products of one fused group for the WHOLE batch."""

    grid: TileGrid
    m: int
    bundles: list[_ImageGroupSched]
    cache_hits: list[bool | None]
    layer_ops: list[_BatchLayerOps | None]
    schedule_s: float = 0.0
    schedule_device_s: float = 0.0


class _DeformPrepass(NamedTuple):
    """One DCN layer's products of the compiled group prepass, whole
    batch (device arrays)."""

    coords: jax.Array                     # (N, H, W, KK, 2) f32, read
    #   only by the device scheduling backend's per-image TDT kernel
    r0: jax.Array                         # (N, H, W, KK) int32 clipped
    c0: jax.Array                         #   floors: the cache key's bytes
    tdt: jax.Array                        # (N, T, T) bool per-image TDTs
    idx: jax.Array                        # (N*T, p_pad, KK, 4) int32
    coeff: jax.Array                      # (N*T, p_pad, KK, 4) f32


def _program_nodes(nodes) -> tuple:
    """A group's nodes as a compiled program's static argument: without
    their param index, so groups of one structure share the program."""
    return tuple(dataclasses.replace(nd, param_idx=0) for nd in nodes)


@functools.partial(jax.jit, static_argnames=(
    "nodes", "grid", "p_pad", "needs_plane", "max_displacement"))
def _group_prepass_program(planes, params, *, nodes, grid, p_pad,
                           needs_plane, max_displacement):
    """A fused group's whole batch prepass as ONE program: the stage-1
    chain (offset conv, ``offsets_to_coords``, the dense advance) and,
    per DCN layer, the clipped floors ``coords_digest`` hashes, the
    per-image TDTs (``tdt_from_coords``) and the plane-order operands
    (``pack_plane_operands``). ``params`` are arguments, not constants,
    so no weights are baked into the program; ``nodes`` carry no param
    index, so groups of one structure share the compiled program.
    Returns (the advanced plane, or None when no layer advances it;
    per layer a :class:`_DeformPrepass` or None)."""
    n = planes.shape[0]
    plane = planes
    layers = []
    for node, p, need in zip(nodes, params, needs_plane):
        if isinstance(node, DeformNode):
            offsets = conv2d(plane, p.w_off, p.b_off)
            coords = offsets_to_coords(offsets.astype(jnp.float32),
                                       node.kernel_size, node.variant,
                                       max_displacement)
            r0 = jnp.clip(jnp.floor(coords[..., 0]), 0, grid.h - 1)
            c0 = jnp.clip(jnp.floor(coords[..., 1]), 0, grid.w - 1)
            tdt = jax.vmap(lambda c: tdt_from_coords(c, grid, grid))(coords)
            idx, coeff = jax.vmap(
                lambda c: pack_plane_operands(c, grid, p_pad))(coords)
            rows = (n * grid.num_tiles, p_pad, node.kernel_size ** 2, 4)
            layers.append(_DeformPrepass(
                coords, r0.astype(jnp.int32), c0.astype(jnp.int32), tdt,
                idx.reshape(rows), coeff.reshape(rows)))
        else:
            layers.append(None)
        if need:
            plane = _advance_dense_batch(plane, node, p, max_displacement)
    return (plane if any(needs_plane) else None), layers


def _group_batch_prepass(
    planes: jax.Array,                    # (N, H, W, C) dense chain state
    group: FusedGroup,
    convs: list,
    grid: TileGrid,
    m: int,
    cfg: GraphConfig,
    max_displacement: float | None,
    cache: ScheduleCache | None,
    need_out_plane: bool,
    interp: bool,
    tracer: Tracer | None = None,
    plan: ShardPlan | None = None,
    segment: int = 0,
) -> tuple[_BatchGroupArtifacts, jax.Array]:
    """Batch-level prepass for one group: ONE compiled program
    (:func:`_group_prepass_program`) runs the stage-1 chain and derives
    every DCN layer's floors, TDTs and plane-order operands; one fetch
    brings the TDTs and floors to the host; per-image composite
    schedules are built in dense form (cached under the floors' digest
    — partial batch hits skip scheduling for the hit images); the
    per-layer batch operands are concatenated with per-image base
    offsets in numpy and uploaded once per layer. With the device
    scheduling backend the TDT and greedy kernels run per image on the
    device and only the floors are fetched (for the cache key). With a
    shard ``plan`` the per-layer operands concatenate PER SHARD (each
    shard keeps its own ragged padding) — per-image schedules themselves
    are built identically either way, so traces never depend on
    placement. ``segment`` is the group's index in the partition (span
    attrs)."""
    tr = tracer if tracer is not None else get_tracer()
    n = planes.shape[0]
    device = cfg.schedule_backend == "device" and cfg.schedule == "alg1"
    t_out = grid.num_tiles
    k_pad = pow2_pad(t_out)
    tp = grid.th * grid.tw
    bp = min(cfg.block_p, tp)
    p_pad = tp if tp % bp == 0 else round_up(tp, cfg.block_p)
    last = group.n_layers - 1

    needs_plane = tuple(need_out_plane
                        or any(isinstance(nd, DeformNode)
                               for nd in group.nodes[j + 1:])
                        for j in range(group.n_layers))
    with tr.span("prepass.stage1", group=segment, layers=group.n_layers,
                 batch=n):
        plane, layers = _group_prepass_program(
            planes, [convs[nd.param_idx] for nd in group.nodes],
            nodes=_program_nodes(group.nodes),
            grid=grid, p_pad=p_pad, needs_plane=needs_plane,
            max_displacement=max_displacement)
    prepass_programs.inc()
    if plane is None:
        plane = planes

    def build_bundle(i: int) -> _ImageGroupSched:
        if device:
            b_layers = [
                jnp.asarray(tdt_standard_conv(grid, grid, nd.kernel_size))
                if lay is None else
                tdt_from_coords_device(lay.coords[i], grid, grid,
                                       interpret=interp)
                for nd, lay in zip(group.nodes, layers)]
        else:
            b_layers = [
                tdt_standard_conv(grid, grid, nd.kernel_size)
                if tdt is None else tdt[i]
                for nd, (tdt, _) in zip(group.nodes, fetched)]
        with tr.span("prepass.alg1", group=segment, image=i, tiles=t_out):
            if device:
                comp = compose_tdt_chain_device(b_layers)
                ds = schedule_arrays_device(comp, m, k_pad=k_pad,
                                            interpret=interp)
            else:
                comp = compose_tdt_chain(b_layers)
                if cfg.schedule == "alg1":
                    sched = schedule_tiles(comp, m)
                elif cfg.schedule == "sequential":
                    sched = sequential_schedule(comp)
                else:
                    raise ValueError(f"unknown schedule: {cfg.schedule!r}")
                ds = DeviceSchedule.from_host(sched, t_out)
        if tr.enabled:
            alg1_tiles.inc(t_out)
        xp = jnp if device else np
        exec_scheds: list = []
        for j, node in enumerate(group.nodes):
            if not isinstance(node, DeformNode):
                exec_scheds.append(None)
                continue
            dep_j, cnt_j = tdt_dispatch_arrays(b_layers[j], k_pad)
            if j == last:
                oid = xp.asarray(ds.oid).reshape(-1)
                sel = xp.maximum(oid, 0)
                exec_scheds.append(DeviceSchedule(
                    oid, dep_j[sel],
                    xp.where(oid >= 0, cnt_j[sel], 0),
                    xp.zeros_like(oid)))
            else:
                ar = xp.arange(t_out, dtype=xp.int32)
                exec_scheds.append(DeviceSchedule(
                    ar, dep_j, cnt_j, xp.zeros_like(ar)))
        return _ImageGroupSched(b_layers, exec_scheds, ds)

    bundles, hits = [], []
    with tr.timed("prepass.schedule", backend=cfg.schedule_backend,
                  batch=n) as ssp:
        with tr.span("prepass.tdt", backend=cfg.schedule_backend,
                     batch=n):
            # One fetch for the group: the TDTs Algorithm 1 reads on
            # the host, the floors the cache key hashes.
            fetched = jax.device_get([
                (None, None) if lay is None else
                (None if device else lay.tdt,
                 None if cache is None else (lay.r0, lay.c0))
                for lay in layers])
        for i in range(n):
            if cfg.faults is not None:
                cfg.faults.check("prepass", image=i)
            if cache is None:
                bundles.append(build_bundle(i))
                hits.append(None)
                continue
            digests = [
                conv_digest(nd.kernel_size, grid) if floors is None
                else floors_digest(floors[0][i], floors[1][i], grid)
                for nd, (_, floors) in zip(group.nodes, fetched)]
            key = (chain_digest(digests, grid), grid.th, grid.tw, m,
                   cfg.schedule, "dense")
            if cfg.faults is not None:
                salt = cfg.faults.miss_salt()
                if salt is not None:
                    key = key + (salt,)
            bundle, hit = cache.get_or_build(key,
                                             lambda i=i: build_bundle(i))
            bundles.append(bundle)
            hits.append(hit)
        ssp.set(hits=sum(bool(h) for h in hits))
    schedule_s = ssp.dur
    if cache is not None:
        cache.note_batch_assembly(sum(bool(h) for h in hits),
                                  images=len(hits))

    layer_ops: list[_BatchLayerOps | None] = []
    with tr.span("pack", dispatch="batch_fused", batch=n,
                 layers=group.n_layers):
        for j, lay in enumerate(layers):
            if lay is None:
                layer_ops.append(None)
                continue
            scheds = [bundles[i].exec_scheds[j] for i in range(n)]
            if plan is not None:
                layer_ops.append(_BatchLayerOps(
                    None,
                    stack_rows(lay.idx, plan, t_out),
                    stack_rows(lay.coeff, plan, t_out),
                    shard=shard_batch_schedules(scheds, t_out, t_out,
                                                plan)))
            else:
                layer_ops.append(_BatchLayerOps(
                    jax.device_put(narrow_dep_slots(
                        pack_batch_schedules(scheds, t_out, t_out),
                        DEP_SLOTS_FLOOR)),
                    lay.idx, lay.coeff))

    art = _BatchGroupArtifacts(
        grid=grid, m=m, bundles=bundles, cache_hits=hits,
        layer_ops=layer_ops, schedule_s=schedule_s,
        schedule_device_s=schedule_s if device else 0.0)
    return art, plane


def _conv_chain(planes, params, nodes):
    """Standard conv layers (+ ReLU) on the whole (N, H, W, C) plane."""
    for node, p in zip(nodes, params):
        planes = conv2d(planes, p["w"], p["b"])
        if node.relu:
            planes = jax.nn.relu(planes)
    return planes


def _plane_rows(planes, grid: TileGrid):
    """(N, H, W, C) -> the kernel's (N*T, tp, C) tile rows."""
    return jax.vmap(lambda pl: plane_to_tiles(pl, grid))(planes).reshape(
        planes.shape[0] * grid.num_tiles, grid.th * grid.tw, -1)


def _rows_plane(rows, grid: TileGrid):
    """(N*T, tp, C) tile rows in (image, tile) order -> (N, H, W, C)."""
    return jax.vmap(lambda ti: tiles_to_plane(ti, grid, grid.h, grid.w))(
        rows.reshape(-1, grid.num_tiles, grid.th * grid.tw, rows.shape[-1]))


@functools.partial(jax.jit, static_argnames=("nodes", "grid"))
def _group_lead_program(planes, params, *, nodes, grid):
    """A fused group's standard conv layers up to its first DCN layer,
    on the whole plane; with a ``grid``, the result as that layer's
    kernel rows. A conv-only group is this program alone (``grid``
    None): plane in, plane out, no tiles and so no tile masks. Weights
    are arguments and ``nodes`` carry no param index, as in
    :func:`_group_prepass_program`."""
    planes = _conv_chain(planes, params, nodes)
    return planes if grid is None else _plane_rows(planes, grid)


@functools.partial(jax.jit, static_argnames=(
    "nodes", "grid", "n", "relu", "scatter", "to_rows"))
def _group_post_program(y, oid, row_id, params, *, nodes, grid, n, relu,
                        scatter, to_rows):
    """What follows one DCN layer's kernel call in a fused group of
    ``n`` images: its ReLU; the tile-valid mask of each row's output
    tile, a constant of the program; for the group's last layer
    (``scatter``) the scheduled rows back in (image, tile) order; then
    the standard conv layers up to the group's next DCN layer, on the
    whole plane. Returns that layer's kernel rows (``to_rows``) or the
    group's output plane."""
    t, tp = grid.num_tiles, grid.th * grid.tw
    y = y[:, :tp]
    if relu:
        y = jax.nn.relu(y)
    masks = jnp.asarray(_tile_valid_masks(grid), y.dtype)    # (T, tp, 1)
    y = y * masks[jnp.maximum(oid, 0)]
    if scatter:
        # Ragged-padding rows fall into a dropped dump row.
        target = jnp.where(oid >= 0, row_id, n * t)
        y = jnp.zeros((n * t + 1, tp, y.shape[-1]),
                      y.dtype).at[target].set(y)[:-1]
    if to_rows and not nodes:
        return y             # rows already in (image, tile) order
    planes = _conv_chain(_rows_plane(y, grid), params, nodes)
    return _plane_rows(planes, grid) if to_rows else planes


def _exec_group_batch_fused(
    planes: jax.Array,                    # (N, H, W, C_in)
    group: FusedGroup,
    convs: list,
    cfg: GraphConfig,
    interpret: bool,
    art: _BatchGroupArtifacts,
    mesh=None,
    plan: ShardPlan | None = None,
) -> tuple[jax.Array, int]:
    """Execute one fused group for the whole batch: ONE kernel dispatch
    per DCN layer, everything around the kernels compiled. A conv-only
    group is one program (:func:`_group_lead_program`); a group with
    DCN layers is its lead program, then per DCN layer the batch-fused
    kernel and :func:`_group_post_program`. No tile mask is uploaded.
    With ``mesh``/``plan`` a group with DCN layers runs
    :func:`_exec_group_sharded`. Returns (the group's output plane,
    layer segments dispatched)."""
    n = planes.shape[0]
    if cfg.faults is not None:
        cfg.faults.check("dispatch", images=plan.n if plan else n)
    deform = [j for j, nd in enumerate(group.nodes)
              if isinstance(nd, DeformNode)]
    if plan is not None and deform:
        return (_exec_group_sharded(planes, group, convs, cfg, interpret,
                                    art, mesh, plan), group.n_layers)
    grid = art.grid
    nodes = _program_nodes(group.nodes)
    params = [convs[nd.param_idx] for nd in group.nodes]
    cuts = deform + [group.n_layers]
    x = _group_lead_program(planes, params[:cuts[0]], nodes=nodes[:cuts[0]],
                            grid=grid if deform else None)
    for j, nxt in zip(cuts, cuts[1:]):
        node, p, ops = group.nodes[j], params[j], art.layer_ops[j]
        kk = node.kernel_size ** 2
        y = dcn_fused_batch(
            x, ops.batch.row_id, ops.batch.dep_glb, ops.batch.dep_cnt,
            ops.idx, ops.coeff, p.w.reshape(kk, node.c_in, node.c_out), p.b,
            t_in=grid.num_tiles, kernel_size=node.kernel_size,
            block_p=cfg.block_p, interpret=interpret)
        x = _group_post_program(
            y, ops.batch.oid, ops.batch.row_id, params[j + 1:nxt],
            nodes=nodes[j + 1:nxt], grid=grid, n=n, relu=node.relu,
            scatter=j == group.n_layers - 1, to_rows=nxt < group.n_layers)
    exec_programs.inc()
    return x, group.n_layers


def _exec_group_sharded(
    planes: jax.Array,                    # (N, H, W, C_in)
    group: FusedGroup,
    convs: list,
    cfg: GraphConfig,
    interpret: bool,
    art: _BatchGroupArtifacts,
    mesh,
    plan: ShardPlan,
) -> jax.Array:
    """A fused group with DCN layers over a mesh: each DCN segment
    stacks its tile rows into per-shard slabs, dispatches the shard_map
    kernel, and unstacks the scattered result — everything else (conv
    segments, plane assembly) runs on the TRUE batch with exactly the
    single-device shapes, so sharded results are bit-equal to the
    unsharded run (XLA convs can change reduction order with batch
    size; never giving them a padded pseudo-batch avoids that)."""
    grid = art.grid
    tp = grid.th * grid.tw
    t = grid.num_tiles
    masks_arr = jnp.asarray(_tile_valid_masks(grid), planes.dtype)

    flat = _plane_rows(planes, grid)
    for j, node in enumerate(group.nodes):
        p = convs[node.param_idx]
        if isinstance(node, DeformNode):
            ops = art.layer_ops[j]
            kk = node.kernel_size ** 2
            w2 = p.w.reshape(kk, node.c_in, node.c_out)
            sh = ops.shard
            d = plan.n_shards
            slab = plan.n_max * t
            y = dcn_fused_batch_sharded(
                stack_rows(flat, plan, t), sh.row_id, sh.dep_glb,
                sh.dep_cnt, ops.idx, ops.coeff, w2, p.b, mesh=mesh,
                t_in=t, kernel_size=node.kernel_size,
                block_p=cfg.block_p, interpret=interpret)[:, :, :tp]
            if node.relu:
                y = jax.nn.relu(y)
            y = y * masks_arr[jnp.maximum(sh.oid, 0)]
            # Scatter each shard's scheduled rows back to shard-local
            # (image, tile) order — padding rows (ragged schedules or
            # shard-size fill) land in a dropped per-shard dump row —
            # then unstack to true batch rows.
            target = jnp.where(sh.oid >= 0, sh.row_id, slab)
            y_all = jnp.zeros((d, slab + 1, tp, node.c_out), y.dtype)
            y_all = jax.vmap(lambda ya, tg, yy: ya.at[tg].set(yy))(
                y_all, target, y)
            flat = unstack_rows(y_all[:, :-1], plan, t)
        else:
            flat = _plane_rows(_conv_chain(_rows_plane(flat, grid), [p],
                                           [node]), grid)
    return _rows_plane(flat, grid)


def _batch_fused_group_traces(
    group: FusedGroup,
    art: _BatchGroupArtifacts,
    cfg: GraphConfig,
    dtype_bytes: int,
    group_idx: int,
) -> list[GroupTrace]:
    """Per-image GroupTraces of one batch-fused group — lazy host
    assembly of the composite schedules, OFF the hot path."""
    grid = art.grid
    tp = grid.th * grid.tw
    t = grid.num_tiles
    tile_bytes = tp * group.c_in * dtype_bytes
    traces = []
    for i, bundle in enumerate(art.bundles):
        sched = bundle.ds.to_host()
        gt = GroupTrace(
            grid=grid, tile_bytes=tile_bytes, buffer_tiles=art.m,
            schedule=cfg.schedule, schedule_cache_hit=art.cache_hits[i],
            schedule_backend=cfg.schedule_backend,
            dispatch="batch_fused", batch_rows=(i * t, (i + 1) * t),
            dtype_bytes=dtype_bytes, layer_channels=group.layer_channels,
            output_bytes=grid.h * grid.w * group.c_out * dtype_bytes,
            weight_bytes=group_weight_bytes(group, dtype_bytes),
            b_layers=[np.asarray(b) for b in bundle.b_layers],
            kernel_dispatches=0)
        gt.image, gt.group = i, group_idx
        gt.layer_stats = [LayerBufferStats(
            kind=nd.kind,
            tiles_computed=(len(sched.oid) if j == group.n_layers - 1
                            and isinstance(nd, DeformNode) else t),
            recomputes=0,
            max_resident_bytes=t * tp * nd.c_out * dtype_bytes)
            for j, nd in enumerate(group.nodes)]
        for out_tile, loads in zip(sched.oid, sched.iid):
            gt.records.append(TileRecord(
                out_tile=out_tile, dep_tiles=tuple(loads),
                loaded_bytes=len(loads) * tile_bytes,
                buffer_bytes=len(loads) * tile_bytes))
        traces.append(gt)
    return traces


def _run_graph_batch_fused(
    convs: list,
    segments: list[Segment],
    x: jax.Array,
    cfg: GraphConfig,
    interpret: bool,
    cache: ScheduleCache | None,
    max_displacement: float | None,
    trace: NetworkTrace,
    return_trace: bool,
    tracer: Tracer | None = None,
    mesh=None,
    shard_sizes=None,
) -> jax.Array:
    """Batch-fused graph execution: the staging unit is a SEGMENT of the
    whole batch (not an image) — segment s+1's batch prepass overlaps
    segment s's execution on the staging thread.

    With a ``mesh`` every DCN segment dispatches through the shard_map
    kernel over per-shard row slabs (see ``_exec_group_batch_fused``);
    the prepass chain and all dense segments stay on the TRUE batch, so
    schedules, traces and numerics are identical to the single-device
    run. The modeled collective is the one logits all-gather."""
    tr = tracer if tracer is not None else get_tracer()
    n = x.shape[0]
    th, tw = cfg.tile_hw
    itemsize = x.dtype.itemsize
    plan = None
    if mesh is not None:
        d = dict(mesh.shape)["data"]
        plan = plan_batch_shards(n, d, shard_sizes)

    deform_after = [False] * len(segments)
    seen = False
    for s in range(len(segments) - 1, -1, -1):
        deform_after[s] = seen
        if isinstance(segments[s], FusedGroup) and any(
                isinstance(nd, DeformNode) for nd in segments[s].nodes):
            seen = True

    # The dense stage-1 chain state, advanced sequentially by the prepass
    # (run_staged's single worker preserves submission order). The epoch
    # guard exists for watchdog failover: after a stuck worker is
    # abandoned and the same segment re-runs synchronously, the worker
    # may still wake and finish — its read is rejected (epoch moved on)
    # or its commit is discarded, so the chain state can never regress
    # or double-advance.
    pre_lock = threading.Lock()
    pre_state = {"plane": x, "epoch": 0}

    def prepass(s: int):
        seg = segments[s]
        with pre_lock:
            if pre_state["epoch"] != s:
                return None        # stale duplicate from an abandoned worker
            plane_in = pre_state["plane"]
        if isinstance(seg, (PoolNode, UpsampleNode)):
            art = None
            plane = (apply_boundary_batch(plane_in, seg)
                     if deform_after[s] else plane_in)
        else:
            grid = _segment_grid(seg, th, tw)
            m = (grid.num_tiles if cfg.buffer_tiles is None
                 else cfg.buffer_tiles)
            art, plane = _group_batch_prepass(
                plane_in, seg, convs, grid, m, cfg, max_displacement,
                cache, need_out_plane=deform_after[s], interp=interpret,
                tracer=tr, plan=plan, segment=s)
        with pre_lock:
            if pre_state["epoch"] == s:
                pre_state["plane"] = plane
                pre_state["epoch"] = s + 1
        return art

    exec_state = {"plane": x, "group": 0}
    pending: list[GroupTrace] = []

    def execute(s: int, art):
        seg = segments[s]
        kind = ("pool" if isinstance(seg, PoolNode) else
                "upsample" if isinstance(seg, UpsampleNode) else "group")
        # Host time to enqueue the segment's programs (dispatch is
        # async: the device may run them later); trace assembly is not
        # part of it.
        with tr.span("exec.segment", segment=s, kind=kind):
            if art is None:
                exec_state["plane"] = apply_boundary_batch(
                    exec_state["plane"], seg)
            else:
                exec_state["plane"], dispatches = _exec_group_batch_fused(
                    exec_state["plane"], seg, convs, cfg, interpret, art,
                    mesh=mesh, plan=plan)
        if art is None:
            trace.boundary_bytes += n * boundary_bytes(seg, itemsize)
            return None
        trace.batch_dispatches += dispatches
        trace.overlap.schedule_s += art.schedule_s
        trace.overlap.schedule_device_s += art.schedule_device_s
        if return_trace:
            pending.extend(_batch_fused_group_traces(
                seg, art, cfg, itemsize, exec_state["group"]))
        exec_state["group"] += 1
        return None

    run_staged(len(segments), prepass, execute, cfg.staging_depth,
               trace.overlap, tracer=tr, watchdog_s=cfg.watchdog_s,
               faults=cfg.faults)
    # Keep trace.groups image-major like the per-image executors.
    pending.sort(key=lambda g: (g.image, g.group))
    trace.groups.extend(pending)
    out = exec_state["plane"]
    if plan is not None:
        # Modeled collective traffic: each replica keeps its local rows
        # until the logits, which cross once (the executor's per-layer
        # host gathers are simulation plumbing, not modeled DRAM).
        trace.shards = plan.n_shards
        trace.allgather_bytes += allgather_nbytes(out)
    return out


def run_graph(
    convs: list,
    graph: NetGraph,
    x: jax.Array,
    *,
    config: GraphConfig | None = None,
    max_displacement: float | None = None,
    return_trace: bool = False,
    schedule_cache: ScheduleCache | None = None,
    tracer: Tracer | None = None,
    shard_sizes=None,
    tuned_plan="auto",
):
    """Execute a backbone graph over a batch: (N,H,W,C) -> (N,H',W',C').

    ``convs`` is the per-node parameter list (``params["convs"]`` of the
    DCN models): ``DeformableConvParams`` for DeformNodes, ``{"w", "b"}``
    dicts for ConvNodes. Numerically matches :func:`run_graph_dense` (the
    XLA reference) to float tolerance; with ``return_trace`` additionally
    returns the :class:`NetworkTrace` of the executed DRAM traffic.

    With ``staging_depth > 1`` (the default) the next unit's host prepass
    — segment s+1 of the whole batch under ``batch_fused``, image i+1
    under ``per_tile`` — runs on a worker thread while the current
    one's kernels execute; the trace's ``host_overlap_frac`` reports
    how much host time was hidden.
    ``schedule_cache`` overrides the process-wide cache (serving engines
    pass their own). ``tracer`` routes span tracing (``prepass.*``,
    ``pack``, ``dispatch.*``) into an enabled :class:`~repro.obs.Tracer`;
    default is the current ``repro.obs.get_tracer()`` (a no-op unless
    enabled or overridden via ``use_tracer``).

    With ``config.mesh`` / ``config.data_parallel`` (batch_fused only)
    the batch dimension shards over the mesh's ``"data"`` axis;
    ``shard_sizes`` pins an explicit per-shard image count (the serving
    engine's replica placement — must sum to N, zeros allowed). Traces
    are placement-independent: per-image schedules and records are built
    exactly as on a single device.

    With ``config.autotune`` enabled the partition and per-group tile
    shapes come from the simulator-guided tuner (``repro.tuning``):
    ``tuned_plan="auto"`` resolves through the plan cache per the config
    knobs; pass a ``TunedPlan`` (or None for explicitly-greedy) to skip
    resolution — the serving engine resolves once at construction and
    replays the same plan on every step and replica. Executed traces
    stay exactly equal to the DRAM simulator under any tuned plan.
    """
    if isinstance(x, jax.core.Tracer):
        raise ValueError(
            "run_graph is a host-driven, forward-only executor: the "
            "cross-layer schedule is data-dependent, so it cannot run "
            "under jit/grad/vmap. Use backend='xla' for those paths.")
    cfg = config or GraphConfig()
    if tuple(x.shape[1:]) != (graph.in_h, graph.in_w, graph.in_c):
        raise ValueError(
            f"input {tuple(x.shape[1:])} does not match the graph's "
            f"({graph.in_h}, {graph.in_w}, {graph.in_c}) input plane — "
            f"rebuild the graph for this image size")
    th, tw = cfg.tile_hw
    if th > graph.in_h or tw > graph.in_w:
        raise ValueError(
            f"tile {th}x{tw} exceeds the {graph.in_h}x{graph.in_w} input "
            f"plane — a degenerate 1-tile grid; choose tile sides <= the "
            f"plane (interior groups at lower resolution are clamped "
            f"automatically)")
    interpret = resolve_interpret(cfg.interpret)
    tr = tracer if tracer is not None else get_tracer()
    if schedule_cache is not None:
        cache: ScheduleCache | None = schedule_cache
    else:
        cache = default_schedule_cache() if cfg.use_schedule_cache else None
    trace = NetworkTrace()
    n = x.shape[0]
    if n == 0:
        h, w, c = graph.out_shape
        y = jnp.zeros((0, h, w, c), x.dtype)
        return (y, trace) if return_trace else y

    # "auto": resolve per cfg.autotune (cache-through; "offline" may pay
    # a search on first use). Callers that already hold a plan — the
    # serving engine resolves once at construction — pass it (or None
    # for explicitly-greedy) so the hot path never re-resolves.
    if tuned_plan == "auto":
        tuned_plan = None
        if cfg.autotune != "off":
            from repro.tuning import resolve_tuned_plan
            tuned_plan = resolve_tuned_plan(
                convs, graph, autotune=cfg.autotune,
                onchip_budget_bytes=cfg.onchip_budget_bytes,
                dtype_bytes=x.dtype.itemsize, tile_hw=cfg.tile_hw,
                buffer_tiles=cfg.buffer_tiles, schedule=cfg.schedule,
                batch=n, budget=cfg.autotune_budget,
                plan_cache_dir=cfg.plan_cache_dir,
                max_displacement=max_displacement, tracer=tr)
    segments = partition_graph_cached(graph, cfg.onchip_budget_bytes,
                                      dtype_bytes=x.dtype.itemsize,
                                      autotune=cfg.autotune,
                                      tuned=tuned_plan)

    mesh = resolve_shard_mesh(cfg.mesh, cfg.data_parallel)
    if shard_sizes is not None and mesh is None:
        raise ValueError(
            "shard_sizes= requires a sharded config (mesh= or "
            "data_parallel= with a data axis > 1)")
    if cfg.dispatch == "batch_fused":
        with use_tracer(tr):
            y = _run_graph_batch_fused(convs, segments, x, cfg, interpret,
                                       cache, max_displacement, trace,
                                       return_trace, tracer=tr, mesh=mesh,
                                       shard_sizes=shard_sizes)
        return (y, trace) if return_trace else y

    def prepass(i: int):
        if cfg.faults is not None:
            cfg.faults.check("prepass", image=i)
        return _image_prepass(x[i], segments, convs, cfg, max_displacement,
                              cache, interp=interpret, tracer=tr)

    def execute_image(i: int, arts) -> jax.Array:
        if cfg.faults is not None:
            cfg.faults.check("dispatch", image=i)
        plane = x[i]
        g = 0
        for seg, art in zip(segments, arts):
            if art is None:
                plane = apply_boundary_dense(plane, seg)
                trace.boundary_bytes += boundary_bytes(seg,
                                                       x.dtype.itemsize)
            else:
                plane, gt = _run_group(plane, seg, convs, cfg, interpret,
                                       art)
                gt.image, gt.group = i, g
                g += 1
                trace.overlap.schedule_s += art.schedule_s
                trace.overlap.schedule_device_s += art.schedule_device_s
                trace.groups.append(gt)
        return plane

    with use_tracer(tr):
        outs = run_staged(n, prepass, execute_image, cfg.staging_depth,
                          trace.overlap, tracer=tr,
                          watchdog_s=cfg.watchdog_s, faults=cfg.faults)
    y = jnp.stack(outs)
    return (y, trace) if return_trace else y


def dcn_pipeline(
    x: jax.Array,
    params: DeformableConvParams,
    *,
    kernel_size: int = 3,
    variant: str = "dcn2",
    max_displacement: float | None = None,
    tile: int | tuple[int, int] = 8,
    buffer_tiles: int | None = None,
    schedule: str = "alg1",
    block_p: int = 128,
    interpret: bool | None = None,
    return_trace: bool = False,
    config: GraphConfig | None = None,
    tracer: Tracer | None = None,
):
    """One deformable convolution over a batch: (N,H,W,C) -> (N,H,W,O).

    The layer runs through :func:`run_graph` as a one-node graph (a
    :class:`DeformNode` without ReLU): stage-1 offsets -> TDT ->
    Algorithm-1 schedule -> fused-kernel dispatch. Numerically matches
    ``core.deform.deformable_conv2d`` (the XLA reference) to float
    tolerance; with ``return_trace`` also returns the
    :class:`NetworkTrace`, one :class:`GroupTrace` per image.

    ``config`` overrides the individual executor keywords when given;
    ``tracer`` is passed on to :func:`run_graph`.
    """
    cfg = config or GraphConfig(tile=tile, buffer_tiles=buffer_tiles,
                                schedule=schedule, block_p=block_p,
                                interpret=interpret)
    h, w, c_in = x.shape[1:]
    node = DeformNode(0, c_in, params.w.shape[-1], h, w, kernel_size,
                      variant, relu=False)
    return run_graph([params], NetGraph((node,), h, w, c_in), x,
                     config=cfg, max_displacement=max_displacement,
                     return_trace=return_trace, tracer=tracer)


def network_sim_specs(trace: NetworkTrace) -> list[dict]:
    """Rebuild ``core.simulator.simulate_network`` group specs from an
    executed trace — byte-identical TDT inputs, so the fused prediction
    must equal the executed FIFO replay exactly."""
    specs = []
    for gt in trace.groups:
        specs.append(dict(
            b_layers=gt.b_layers,
            grid=gt.grid,
            layer_channels=gt.layer_channels,
            weight_bytes=gt.weight_bytes,
            buffer_tiles=gt.buffer_tiles,
            dtype_bytes=gt.dtype_bytes,
            schedule=gt.schedule,
        ))
    return specs
