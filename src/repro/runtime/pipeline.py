"""The tile-pipeline executor: TDT -> schedule -> pack -> fused kernel.

``dcn_pipeline`` runs a full deformable convolution over a real
``(N, H, W, C)`` batch the way the paper's accelerator does (§IV-C/D):
the stage-1 offset conv runs dense (XLA), the resulting sampling
coordinates drive a per-image tile dependency table and Algorithm-1
schedule (host side, as the paper's scheduler is a dedicated hardware
block running ahead of the PE array), and the schedule executes through
the fused BLI(+)conv Pallas kernel.

Two dispatch modes (``PipelineConfig.dispatch``):

  * ``"batched"`` (default) — the whole schedule is ONE ``pallas_call``:
    the scheduled-tile index is the leading grid dimension and the
    scalar-prefetched dep table drives the input-tile DMA order
    (``kernels.dcn_fused.dcn_fused_schedule``); outputs scatter back in
    one op. One kernel dispatch per image.
  * ``"per_tile"`` — the PR 1 loop: one packed-buffer kernel dispatch per
    schedule entry.

Scheduling is data-dependent (it inspects the offsets), so the executor
is a host-driven loop rather than one jitted graph — the same structural
split as the hardware, where pre-scheduling runs concurrently with
execution. With ``staging_depth > 1`` the prepass (TDT + schedule +
packing) of image i+1 runs on a worker thread under image i's device
execution. Gradients do not flow through this path; training uses the
XLA ``fused_deformable_conv2d`` (checkpoint) formulation.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FutTimeout
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.deform import DeformableConvParams, conv2d, offsets_to_coords
from repro.core.scheduler import (DeviceSchedule, TileSchedule, pow2_pad,
                                  schedule_arrays_device, schedule_tiles,
                                  sequential_schedule)
from repro.core.tiles import TileGrid, tdt_from_coords
from repro.kernels.dcn_fused import (dcn_fused_batch,
                                     dcn_fused_batch_sharded,
                                     dcn_fused_schedule, dcn_fused_tile)
from repro.kernels.dcn_schedule import tdt_from_coords_device
from repro.kernels.ops import resolve_interpret, round_up
from repro.obs import Tracer, default_registry, get_tracer, use_tracer
from repro.runtime.cache import coords_digest, default_schedule_cache
from repro.runtime.packing import (NeighbourTables, build_neighbour_tables,
                                   pack_batch_schedules, pack_output_tile,
                                   pack_plane_operands, pack_schedule_tiles,
                                   plane_to_tiles, tiles_to_plane)
from repro.runtime.shard import (ShardPlan, allgather_nbytes,
                                 plan_batch_shards, resolve_shard_mesh,
                                 shard_batch_schedules, stack_rows,
                                 unstack_rows)
from repro.runtime.trace import ImageTrace, PipelineTrace, TileRecord


# Process-wide like core.scheduler.host_schedule_builds: callers that
# need a per-engine view keep a construction-time baseline and report
# their delta.
staging_watchdog_failovers = default_registry().counter(
    "staging.watchdog_failovers",
    help="staged prepasses that missed the watchdog deadline and were "
         "re-run synchronously on the driving thread")


def run_staged(n: int, prepass, execute, depth: int, overlap,
               tracer: Tracer | None = None,
               watchdog_s: float | None = None, faults=None) -> list:
    """The multi-image staging queue shared by both executors.

    ``prepass(i)`` builds image i's host-side artifacts, ``execute(i,
    art)`` dispatches its kernels. With ``depth > 1`` up to ``depth - 1``
    prepasses run ahead on a single worker thread while the main thread
    executes (jax dispatch is itself async, so the device stays busy
    under the host-side schedule build); ``overlap`` (an
    :class:`~repro.runtime.trace.OverlapSpans`) is re-derived from the
    ``prepass`` / ``prepass.wait`` spans this queue records through
    ``tracer`` (always measured; stored only when the tracer is
    enabled). Returns the per-image execute results.

    ``watchdog_s`` bounds each wait on the staging worker: a prepass
    that does not deliver within the deadline is treated as wedged — the
    queue fails over to synchronous prepass for the rest of the run
    (``staging.watchdog_failover`` instant marker + process counter),
    the stuck worker is abandoned (never joined), and batch-fused
    callers' sequential prepass state stays consistent because their
    epoch-guarded commit discards any late duplicate (see
    ``_run_graph_batch_fused``). ``faults`` is a test-only injector
    (``repro.testing.faults``) consulted for ``worker_stall`` sleeps.
    """
    tr = tracer if tracer is not None else get_tracer()

    def staged(i: int):
        # On the staging worker too, spans opened through get_tracer()
        # (packing, schedule kernels, lowerings) land in ``tr``.
        if faults is not None:
            faults.stall("worker_stall")
        with use_tracer(tr), tr.timed("prepass", unit=i) as sp:
            art = prepass(i)
        return art, sp

    outs = []
    if depth == 1 or n == 1:
        for i in range(n):
            # Serial mode: the execute loop blocks on the whole prepass,
            # so the wait span wraps it (host_overlap_frac == 0).
            with tr.timed("prepass.wait", unit=i) as wsp:
                art, sp = staged(i)
            overlap.add_span(sp)
            overlap.add_span(wsp)
            outs.append(execute(i, art))
        return outs
    pool = ThreadPoolExecutor(max_workers=1)
    failed_over = False
    try:
        futs: deque = deque()
        nxt = 0
        while nxt < n and len(futs) < depth - 1:
            futs.append(pool.submit(staged, nxt))
            nxt += 1
        for i in range(n):
            with tr.timed("prepass.wait", unit=i) as wsp:
                if failed_over or not futs:
                    art, sp = staged(i)
                else:
                    try:
                        art, sp = futs.popleft().result(
                            timeout=watchdog_s)
                    except _FutTimeout:
                        failed_over = True
                        staging_watchdog_failovers.bump()
                        tr.instant("staging.watchdog_failover", unit=i)
                        art, sp = staged(i)
            overlap.add_span(sp)
            overlap.add_span(wsp)
            if not failed_over and nxt < n:
                futs.append(pool.submit(staged, nxt))
                nxt += 1
            outs.append(execute(i, art))
    finally:
        # A wedged worker would hang the context-manager shutdown; after
        # a failover, abandon it (queued-but-unstarted work is
        # cancelled, the running thread exits on its own — injected
        # stalls are finite by contract).
        pool.shutdown(wait=not failed_over, cancel_futures=failed_over)
    return outs


def validate_dispatch_config(cfg) -> None:
    """Shared ``__post_init__`` checks of the executor configs: tile
    sides, dispatch mode, schedule backend and staging depth."""
    cfg.tile_hw                          # validates tile sides
    if cfg.dispatch not in ("batched", "per_tile", "batch_fused"):
        raise ValueError(f"unknown dispatch mode: {cfg.dispatch!r}")
    if cfg.schedule_backend not in ("host", "device"):
        raise ValueError(
            f"unknown schedule backend: {cfg.schedule_backend!r}")
    if cfg.staging_depth < 1:
        raise ValueError(
            f"staging_depth must be >= 1, got {cfg.staging_depth}")
    if cfg.watchdog_s is not None and cfg.watchdog_s <= 0:
        raise ValueError(
            f"watchdog_s must be > 0 (or None), got {cfg.watchdog_s}")
    dp = cfg.data_parallel
    if dp is not None and dp < 1:
        raise ValueError(f"data_parallel must be >= 1, got {dp}")
    if ((cfg.mesh is not None or (dp or 1) > 1)
            and cfg.dispatch != "batch_fused"):
        raise ValueError(
            "mesh=/data_parallel= sharding only applies to "
            f"dispatch='batch_fused', got dispatch={cfg.dispatch!r}")
    if cfg.autotune not in ("off", "offline", "cached-only"):
        raise ValueError(
            f"autotune must be 'off', 'offline' or 'cached-only', "
            f"got {cfg.autotune!r}")
    if cfg.autotune_budget < 1:
        raise ValueError(
            f"autotune_budget must be >= 1, got {cfg.autotune_budget}")


def clamp_tile_config(cfg, h: int, w: int):
    """Clamp a config's tile to an (h, w) input plane — the model and
    serving entry points accept any image size, while the raw executors
    reject tile > plane (a silent 1-tile grid otherwise). Works for both
    ``PipelineConfig`` and ``GraphConfig``."""
    th, tw = cfg.tile_hw
    if th <= h and tw <= w:
        return cfg
    return dataclasses.replace(cfg, tile=(min(th, h), min(tw, w)))


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Executor knobs (everything except the layer's own parameters)."""

    tile: int | tuple[int, int] = 8      # output/input tile side(s)
    buffer_tiles: int | None = None      # M for Algorithm 1; None = all
    schedule: str = "alg1"               # "alg1" | "sequential"
    block_p: int = 128                   # kernel pixel-block size
    interpret: bool | None = None        # Pallas interpret; None = auto
    use_schedule_cache: bool = True      # LRU-cache TDT+Algorithm-1 builds
    # "batched": the whole schedule as one pallas_call grid (per image).
    # "batch_fused": the concatenated schedules of ALL batch images as
    #   one pallas_call grid — one dispatch per layer segment per BATCH,
    #   and with schedule_backend="device" the schedule arrays feed the
    #   dispatch directly (no host TileSchedule on the hot path).
    # "per_tile": one kernel dispatch per schedule entry (PR 1).
    dispatch: str = "batched"
    # "host": TDT scatter + Algorithm-1 loop in host numpy/Python.
    # "device": both run as Pallas kernels (kernels.dcn_schedule) — the
    # paper's on-chip scheduler block; bit-exact vs the host path, and
    # the staging thread shrinks to packing only.
    schedule_backend: str = "host"
    # Images staged ahead: 1 = serial, 2 (default) = prepass image i+1 on
    # a worker thread while image i executes.
    staging_depth: int = 2
    # Staging-worker watchdog: None = wait forever (pre-resilience
    # behavior); a float bounds each wait on a staged prepass, after
    # which the run fails over to synchronous prepass.
    watchdog_s: float | None = None
    # Batch-dimension scale-out (batch_fused only): an explicit
    # jax.sharding.Mesh with a "data" axis, or data_parallel=D as the
    # convenience spelling (builds a (D, 1) host mesh at run time, so
    # device availability is checked at run, not config construction).
    # Each mesh device runs the concatenated schedules of its local
    # images; the only collective is the all-gather at the logits.
    mesh: Any = None
    data_parallel: int | None = None
    # Simulator-guided tile autotuning (repro.tuning): "off" = use the
    # configured tile; "offline" = search once per layer geometry for
    # the (tile_h, tile_w) with the least simulated DRAM traffic and
    # cache the winner; "cached-only" = use a cached winner, never
    # search. plan_cache_dir persists winners across processes.
    autotune: str = "off"
    plan_cache_dir: str | None = None
    autotune_budget: int = 128
    # Fault injector (repro.testing.faults.FaultInjector) — test/bench
    # only, excluded from config equality: two configs with the same
    # executor knobs are the same config.
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


@dataclasses.dataclass
class _ImageArtifacts:
    """Prepass products of one image: schedule + packed kernel operands."""

    sched: TileSchedule
    cache_hit: bool | None
    nb: NeighbourTables
    k_pad: int
    # TDT + schedule build wall time inside the prepass, and the portion
    # that ran through the device scheduling backend.
    schedule_s: float = 0.0
    schedule_device_s: float = 0.0
    # batched dispatch only: stacked kernel operands for the whole schedule
    dep_tbl: np.ndarray | None = None
    dep_cnt: np.ndarray | None = None
    idx: np.ndarray | None = None
    coeff: np.ndarray | None = None


def _pipeline_prepass(
    coords_i: jax.Array,      # (H, W, KK, 2)
    grid: TileGrid,
    m: int,
    p_pad: int,
    cfg: PipelineConfig,
    interp: bool,
    tracer: Tracer | None = None,
) -> _ImageArtifacts:
    """Host-side prepass of one image: TDT -> schedule (cached) ->
    neighbour tables -> (batched) group-level packed operands. With
    ``schedule_backend="device"`` the TDT scatter and the Algorithm-1
    selection run as Pallas kernels and the host only reassembles."""
    tr = tracer if tracer is not None else get_tracer()

    def build_schedule():
        with tr.span("prepass.tdt", backend=cfg.schedule_backend):
            if cfg.schedule_backend == "device":
                B = tdt_from_coords_device(coords_i, grid, grid,
                                           interpret=interp)
            else:
                B = tdt_from_coords(coords_i, grid, grid)
        if cfg.schedule == "alg1":
            return schedule_tiles(B, m, backend=cfg.schedule_backend,
                                  interpret=interp)
        if cfg.schedule == "sequential":
            return sequential_schedule(np.asarray(B))
        raise ValueError(f"unknown schedule: {cfg.schedule!r}")

    with tr.timed("prepass.schedule",
                  backend=cfg.schedule_backend) as ssp:
        if cfg.use_schedule_cache:
            # Tile dims are hashed inside coords_digest via the grid, but
            # stay an explicit key component too: two configs sharing
            # coords must never collide across (tile_h, tile_w).
            key = (coords_digest(coords_i, grid), grid.th, grid.tw, m,
                   cfg.schedule)
            if cfg.faults is not None:
                salt = cfg.faults.miss_salt()
                if salt is not None:
                    key = key + (salt,)
            sched, cache_hit = default_schedule_cache().get_or_build(
                key, build_schedule)
        else:
            sched, cache_hit = build_schedule(), None
        ssp.set(cached=cache_hit)
    schedule_s = ssp.dur

    with tr.span("pack", dispatch=cfg.dispatch):
        nb = build_neighbour_tables(coords_i, grid)
        # Uniform packed-buffer size across the image's dispatches (one
        # kernel compilation): dep-tile count padded to a power of two.
        oid, deps, counts = sched.dense()
        k_pad = deps.shape[1]
        art = _ImageArtifacts(
            sched=sched, cache_hit=cache_hit, nb=nb, k_pad=k_pad,
            schedule_s=schedule_s,
            schedule_device_s=(schedule_s
                               if cfg.schedule_backend == "device"
                               else 0.0))
        if cfg.dispatch == "batched":
            dep_lists = [d[:c] for d, c in zip(deps, counts)]
            (art.dep_tbl, art.dep_cnt, art.idx,
             art.coeff) = pack_schedule_tiles(
                nb, grid, oid, dep_lists, p_pad, k_pad)
    return art


def _pipeline_exec(
    x_i: jax.Array,           # (H, W, C_in)
    art: _ImageArtifacts,
    w2: jax.Array,            # (KK, C_in, C_out)
    b: jax.Array,             # (C_out,)
    kernel_size: int,
    cfg: PipelineConfig,
    grid: TileGrid,
    m: int,
    p_pad: int,
    interpret: bool,
) -> tuple[jax.Array, ImageTrace]:
    h, w, c = x_i.shape
    tp = grid.th * grid.tw
    sched, nb, k_pad = art.sched, art.nb, art.k_pad
    c_out = w2.shape[-1]

    tile_bytes = tp * c * x_i.dtype.itemsize
    trace = ImageTrace(grid=grid, tile_bytes=tile_bytes, buffer_tiles=m,
                       schedule=cfg.schedule,
                       schedule_cache_hit=art.cache_hit,
                       dispatch=cfg.dispatch,
                       schedule_backend=cfg.schedule_backend)

    x_tiles = plane_to_tiles(x_i, grid)               # (T, tp, C)
    buffer_bytes = k_pad * tp * c * x_i.dtype.itemsize

    if cfg.dispatch == "batched":
        y_sched = dcn_fused_schedule(
            x_tiles, jnp.asarray(art.dep_tbl), jnp.asarray(art.dep_cnt),
            jnp.asarray(art.idx), jnp.asarray(art.coeff), w2, b,
            kernel_size=kernel_size, block_p=cfg.block_p,
            interpret=interpret)[:, :tp]
        oid = np.asarray(sched.oid, np.int32)
        y_tiles = jnp.zeros((grid.num_tiles, tp, c_out), x_i.dtype)
        y_tiles = y_tiles.at[jnp.asarray(oid)].set(y_sched)
        trace.kernel_dispatches = 1
    else:
        tiles: list = [None] * grid.num_tiles
        for out_tile, deps in zip(sched.oid, sched.iid):
            idx, coeff = pack_output_tile(nb, grid, out_tile, deps, p_pad)
            x_packed = x_tiles[jnp.asarray(deps, jnp.int32)]  # (k, tp, C)
            if len(deps) < k_pad:
                x_packed = jnp.pad(
                    x_packed, ((0, k_pad - len(deps)), (0, 0), (0, 0)))
            y_t = dcn_fused_tile(
                x_packed.reshape(k_pad * tp, c),
                jnp.asarray(idx), jnp.asarray(coeff), w2, b,
                kernel_size=kernel_size, block_p=cfg.block_p,
                interpret=interpret)
            tiles[out_tile] = y_t[:tp]
            trace.kernel_dispatches += 1
        zero = jnp.zeros((tp, c_out), x_i.dtype)
        y_tiles = jnp.stack([t if t is not None else zero for t in tiles])

    for out_tile, deps in zip(sched.oid, sched.iid):
        trace.records.append(TileRecord(
            out_tile=out_tile,
            dep_tiles=tuple(deps),
            loaded_bytes=len(deps) * tile_bytes,
            buffer_bytes=buffer_bytes))

    y = tiles_to_plane(y_tiles, grid, h, w)
    return y, trace


# ---------------------------------------------------------------------------
# Batch-fused dispatch: ONE kernel call for the whole batch's schedules.
# ---------------------------------------------------------------------------


def build_dense_schedule(coords_i, grid: TileGrid, m: int, cfg, interp: bool,
                         cache) -> tuple[DeviceSchedule, bool | None]:
    """One image's schedule in dense dispatch form (cached).

    With ``schedule_backend="device"`` (and the default alg1 schedule)
    the TDT scatter, greedy selection, and the schedule->dispatch
    handoff all run on-device — the returned arrays are device arrays
    and NO host ``TileSchedule`` is built. The host backend (and the
    sequential ablation) builds the classic schedule and densifies it.
    """

    def build() -> DeviceSchedule:
        if cfg.schedule_backend == "device" and cfg.schedule == "alg1":
            B = tdt_from_coords_device(coords_i, grid, grid,
                                       interpret=interp)
            return schedule_arrays_device(B, m, interpret=interp)
        if cfg.schedule_backend == "device":
            B = np.asarray(tdt_from_coords_device(coords_i, grid, grid,
                                                  interpret=interp))
        else:
            B = np.asarray(tdt_from_coords(coords_i, grid, grid))
        if cfg.schedule == "alg1":
            sched = schedule_tiles(B, m)
        elif cfg.schedule == "sequential":
            sched = sequential_schedule(B)
        else:
            raise ValueError(f"unknown schedule: {cfg.schedule!r}")
        return DeviceSchedule.from_host(sched, grid.num_tiles)

    if cache is None:
        return build(), None
    # Same digest as the per-image paths plus a "dense" discriminator:
    # the cached artifact type differs from the TileSchedule entries.
    key = (coords_digest(coords_i, grid), grid.th, grid.tw, m,
           cfg.schedule, "dense")
    if cfg.faults is not None:
        salt = cfg.faults.miss_salt()
        if salt is not None:
            key = key + (salt,)
    return cache.get_or_build(key, build)


@dataclasses.dataclass
class _BatchArtifacts:
    """Prepass products of one whole batch (batch-fused dispatch)."""

    scheds: list[DeviceSchedule]
    cache_hits: list[bool | None]
    batch: object                 # packing.BatchDispatch (None if sharded)
    idx: jax.Array                # (N*T, p_pad, KK, 4) plane-global
    coeff: jax.Array              # (N*T, p_pad, KK, 4)
    schedule_s: float = 0.0
    schedule_device_s: float = 0.0
    shard: object = None          # shard.ShardedDispatch when sharded


def _pipeline_batch_prepass(
    coords: jax.Array,            # (N, H, W, KK, 2)
    grid: TileGrid,
    m: int,
    p_pad: int,
    cfg: PipelineConfig,
    interp: bool,
    tracer: Tracer | None = None,
    plan: ShardPlan | None = None,
) -> _BatchArtifacts:
    """Whole-batch prepass: per-image dense schedules (cached; partial
    batch hits skip scheduling for the hit images) concatenated into one
    batch grid, plus the plane-ordered packed operands — all jnp, so the
    device scheduling backend keeps the hot path host-free. With a
    shard ``plan`` the schedules concatenate PER SHARD instead (each
    shard keeps its own ragged padding)."""
    tr = tracer if tracer is not None else get_tracer()
    n = coords.shape[0]
    cache = default_schedule_cache() if cfg.use_schedule_cache else None
    with tr.timed("prepass.schedule", backend=cfg.schedule_backend,
                  batch=n) as ssp:
        scheds, hits = [], []
        for i in range(n):
            if cfg.faults is not None:
                cfg.faults.check("prepass", image=i)
            ds, hit = build_dense_schedule(coords[i], grid, m, cfg, interp,
                                           cache)
            scheds.append(ds)
            hits.append(hit)
        if plan is None:
            batch = jax.device_put(pack_batch_schedules(
                scheds, grid.num_tiles, grid.num_tiles))
            shard = None
        else:
            batch = None
            shard = shard_batch_schedules(scheds, grid.num_tiles,
                                          grid.num_tiles, plan)
    schedule_s = ssp.dur
    if cache is not None:
        cache.note_batch_assembly(sum(bool(h) for h in hits),
                                  images=len(hits))

    with tr.span("pack", dispatch="batch_fused", batch=n):
        idx, coeff = jax.vmap(
            lambda c: pack_plane_operands(c, grid, p_pad))(coords)
    kk = coords.shape[3]
    idx = idx.reshape(n * grid.num_tiles, p_pad, kk, 4)
    coeff = coeff.reshape(n * grid.num_tiles, p_pad, kk, 4)
    device = cfg.schedule_backend == "device" and cfg.schedule == "alg1"
    return _BatchArtifacts(
        scheds=scheds, cache_hits=hits, batch=batch, idx=idx, coeff=coeff,
        schedule_s=schedule_s,
        schedule_device_s=schedule_s if device else 0.0, shard=shard)


def _pipeline_batch_exec(
    x: jax.Array,                 # (N, H, W, C_in)
    art: _BatchArtifacts,
    w2: jax.Array,
    b: jax.Array,
    kernel_size: int,
    cfg: PipelineConfig,
    grid: TileGrid,
    m: int,
    interp: bool,
    trace: PipelineTrace,
    return_trace: bool,
    mesh=None,
    plan: ShardPlan | None = None,
) -> jax.Array:
    n, h, w = x.shape[0], x.shape[1], x.shape[2]
    c = x.shape[3]
    tp = grid.th * grid.tw
    t = grid.num_tiles
    c_out = w2.shape[-1]
    if cfg.faults is not None:
        cfg.faults.check("dispatch", images=n)

    x_tiles = jax.vmap(lambda p: plane_to_tiles(p, grid))(x)  # (N, T, tp, C)
    if plan is None:
        y_rows = dcn_fused_batch(
            x_tiles.reshape(n * t, tp, c), art.batch.row_id,
            art.batch.dep_glb, art.batch.dep_cnt, art.idx, art.coeff,
            w2, b, t_in=t, kernel_size=kernel_size, block_p=cfg.block_p,
            interpret=interp)[:, :tp]
        # Scatter valid rows back to (image, tile) order; ragged-padding
        # rows land in a dump row that is dropped.
        target = jnp.where(art.batch.oid >= 0, art.batch.row_id, n * t)
        y_all = jnp.zeros((n * t + 1, tp, c_out), x.dtype)
        y_all = y_all.at[target].set(y_rows.astype(x.dtype))
        y_tiles = y_all[:-1].reshape(n, t, tp, c_out)
    else:
        sh = art.shard
        y_rows = dcn_fused_batch_sharded(
            stack_rows(x_tiles.reshape(n * t, tp, c), plan, t),
            sh.row_id, sh.dep_glb, sh.dep_cnt,
            stack_rows(art.idx, plan, t), stack_rows(art.coeff, plan, t),
            w2, b, mesh=mesh, t_in=t, kernel_size=kernel_size,
            block_p=cfg.block_p, interpret=interp)[:, :, :tp]
        # Per-shard scatter (row ids are shard-local) stays on each
        # device; the unstack of the result is the run's ONE all-gather.
        slab = plan.n_max * t
        target = jnp.where(sh.oid >= 0, sh.row_id, slab)
        y_all = jnp.zeros((plan.n_shards, slab + 1, tp, c_out), x.dtype)
        y_all = jax.vmap(lambda ya, tg, yy: ya.at[tg].set(yy))(
            y_all, target, y_rows.astype(x.dtype))
        y_flat = unstack_rows(y_all[:, :-1], plan, t)
        trace.allgather_bytes += allgather_nbytes(y_flat)
        trace.shards = plan.n_shards
        y_tiles = y_flat.reshape(n, t, tp, c_out)
    y = jax.vmap(lambda yt: tiles_to_plane(yt, grid, h, w))(y_tiles)

    trace.batch_dispatches += 1
    tile_bytes = tp * c * x.dtype.itemsize
    for i in range(n):
        im = ImageTrace(grid=grid, tile_bytes=tile_bytes, buffer_tiles=m,
                        schedule=cfg.schedule,
                        schedule_cache_hit=art.cache_hits[i],
                        dispatch="batch_fused",
                        schedule_backend=cfg.schedule_backend,
                        batch_rows=(i * t, (i + 1) * t))
        if return_trace:
            # Lazy host assembly — traces/cross-checks only, never the
            # hot path (asserted by the prepass-instrumentation test).
            # buffer_bytes uses the schedule's own padded dep count (as
            # the per-image batched path does), NOT DeviceSchedule.k_pad
            # — the device handoff pads that to pow2_pad(num_tiles).
            sched = art.scheds[i].to_host()
            k_pad = pow2_pad(max((len(d) for d in sched.iid), default=1))
            buffer_bytes = k_pad * tp * c * x.dtype.itemsize
            for out_tile, deps in zip(sched.oid, sched.iid):
                im.records.append(TileRecord(
                    out_tile=out_tile, dep_tiles=tuple(deps),
                    loaded_bytes=len(deps) * tile_bytes,
                    buffer_bytes=buffer_bytes))
        trace.images.append(im)
    return y


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
    config: PipelineConfig | None = None,
    tracer: Tracer | None = None,
):
    """Scheduler-driven deformable conv over a batch: (N,H,W,C) -> (N,H,W,O).

    Per batch element: stage-1 offsets -> coords -> TDT -> Algorithm-1
    schedule -> fused-kernel execution (one batched grid dispatch per
    image by default; per-tile dispatches with ``dispatch="per_tile"``)
    -> scatter. Numerically matches ``core.deform.deformable_conv2d``
    (the XLA reference) to float tolerance; additionally returns a
    :class:`PipelineTrace` of the actual packed-tile traffic when
    ``return_trace`` is set.

    ``config`` overrides the individual executor keywords when given.
    ``tracer`` routes the call's telemetry spans (prepass/pack/dispatch)
    into a specific :class:`~repro.obs.Tracer`; default is the current
    ``repro.obs.get_tracer()`` (a no-op unless enabled).
    """
    if isinstance(x, jax.core.Tracer):
        raise ValueError(
            "dcn_pipeline is a host-driven, forward-only executor: the "
            "Algorithm-1 schedule is data-dependent, so it cannot run "
            "under jit/grad/vmap. Trace with backend='xla' "
            "(fused_deformable_conv2d) for differentiable/jitted paths.")
    cfg = config or PipelineConfig(tile=tile, buffer_tiles=buffer_tiles,
                                   schedule=schedule, block_p=block_p,
                                   interpret=interpret)
    tr = tracer if tracer is not None else get_tracer()
    n, h, w = x.shape[0], x.shape[1], x.shape[2]
    th, tw = cfg.tile_hw
    if th > h or tw > w:
        raise ValueError(
            f"tile {th}x{tw} exceeds the {h}x{w} feature plane — a "
            f"degenerate 1-tile grid; choose tile sides <= the plane")
    kk = kernel_size * kernel_size
    c_out = params.w.shape[-1]

    offsets = conv2d(x, params.w_off, params.b_off)               # Eq. 1
    coords = offsets_to_coords(offsets.astype(jnp.float32),
                               kernel_size, variant, max_displacement)
    w2 = params.w.reshape(kk, x.shape[-1], c_out)

    trace = PipelineTrace()
    if n == 0:
        y = jnp.zeros(x.shape[:3] + (c_out,), x.dtype)
        return (y, trace) if return_trace else y

    if cfg.autotune != "off":
        # Single layer, nothing to cut: the search degenerates to the
        # tile shape with the least simulated DRAM (first image's
        # coords as the representative input; winner cached per layer
        # geometry, so later batches skip straight to it).
        from repro.tuning import resolve_tuned_tile
        tt = resolve_tuned_tile(
            coords[0], h, w, c_in=int(x.shape[-1]), c_out=int(c_out),
            kernel_size=kernel_size, autotune=cfg.autotune,
            dtype_bytes=x.dtype.itemsize, tile_hw=(th, tw),
            buffer_tiles=cfg.buffer_tiles, schedule=cfg.schedule,
            budget=cfg.autotune_budget,
            plan_cache_dir=cfg.plan_cache_dir, tracer=tr)
        if tt is not None:
            th, tw = tt
    grid = TileGrid(h, w, th, tw)
    tp = grid.th * grid.tw
    m = grid.num_tiles if cfg.buffer_tiles is None else cfg.buffer_tiles
    bp = min(cfg.block_p, tp)
    p_pad = tp if tp % bp == 0 else round_up(tp, cfg.block_p)
    interp = resolve_interpret(cfg.interpret)

    if cfg.dispatch == "batch_fused":
        # Batch-level prepass replaces the per-image staging loop: the
        # whole batch's schedules concatenate into ONE kernel dispatch
        # (per shard, when a mesh shards the batch axis).
        mesh = resolve_shard_mesh(cfg.mesh, cfg.data_parallel)
        plan = (plan_batch_shards(n, dict(mesh.shape)["data"])
                if mesh is not None else None)
        with tr.timed("prepass", batch=n) as psp:
            art = _pipeline_batch_prepass(coords, grid, m, p_pad, cfg,
                                          interp, tracer=tr, plan=plan)
        trace.overlap.add_span(psp)
        trace.overlap.prepass_wait_s += psp.dur
        trace.overlap.schedule_s += art.schedule_s
        trace.overlap.schedule_device_s += art.schedule_device_s
        with use_tracer(tr):
            y = _pipeline_batch_exec(x, art, w2, params.b, kernel_size,
                                     cfg, grid, m, interp, trace,
                                     return_trace, mesh=mesh, plan=plan)
        return (y, trace) if return_trace else y

    def prepass(i: int) -> _ImageArtifacts:
        if cfg.faults is not None:
            cfg.faults.check("prepass", image=i)
        return _pipeline_prepass(coords[i], grid, m, p_pad, cfg, interp,
                                 tracer=tr)

    def execute(i: int, art: _ImageArtifacts) -> jax.Array:
        if cfg.faults is not None:
            cfg.faults.check("dispatch", image=i)
        with use_tracer(tr):
            y_i, im_tr = _pipeline_exec(x[i], art, w2, params.b,
                                        kernel_size, cfg, grid, m, p_pad,
                                        interp)
        trace.overlap.schedule_s += art.schedule_s
        trace.overlap.schedule_device_s += art.schedule_device_s
        trace.images.append(im_tr)
        return y_i

    outs = run_staged(n, prepass, execute, cfg.staging_depth,
                      trace.overlap, tracer=tr,
                      watchdog_s=cfg.watchdog_s, faults=cfg.faults)
    y = jnp.stack(outs)
    return (y, trace) if return_trace else y
