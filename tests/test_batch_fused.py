"""Whole-batch fused dispatch (ISSUE 5): oracle, ragged-padding,
zero-host-round-trip and partial-batch-cache tests.

``dispatch="batch_fused"`` concatenates the Algorithm-1 schedules of all
batch images into ONE ragged-padded kernel grid per layer segment, and
with ``schedule_backend="device"`` the device scheduler's arrays flow
directly into the dispatch operands — no host ``TileSchedule`` on the
hot path. These tests pin down that:

  * a batch == its images run one at a time (the serving engine's
    degraded step) == XLA reference numerics across rect tiles, ragged
    grids, and both schedule backends;
  * the per-image trace records (and therefore the executor-vs-simulator
    DRAM cross-check) are EXACTLY those of the image run alone — the
    concatenated grid order is the concatenated schedule order;
  * batches mixing empty and full schedules pad per image with elided
    slots and still compute correctly;
  * the device-backend hot path performs no host TileSchedule builds;
  * partial batch hits in the ScheduleCache skip scheduling only for the
    hit images and splice the misses into the batch grid.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scheduler
from repro.core.deform import (deformable_conv2d, init_deformable_conv,
                               randomize_offset_conv)
from repro.core.scheduler import (DeviceSchedule, schedule_arrays_device,
                                  schedule_tiles)
from repro.core.simulator import simulate_network, simulate_strategies
from repro.core.tiles import (TileGrid, per_pixel_input_tiles,
                              tdt_from_coords)
from repro.kernels import dcn_fused
from repro.kernels.dcn_fused import dcn_fused_batch, dcn_fused_tile
from repro.kernels.dcn_schedule import tdt_dispatch_arrays
from repro.models.dcn_models import DcnNetConfig, init_dcn_net
from repro.runtime import (GraphConfig, ScheduleCache, dcn_pipeline,
                           pack_batch_schedules, pack_output_tile,
                           pack_plane_operands, run_graph, run_graph_dense)
from repro.runtime.fused_exec import network_sim_specs
from repro.runtime.packing import build_neighbour_tables, narrow_dep_slots
from repro.serving import DcnServingEngine

from tests.test_graph import _acceptance_case


def _layer(key, c_in, c_out, variant="dcn2", offset_scale=0.7):
    p = init_deformable_conv(key, c_in, c_out, 3, variant)
    return randomize_offset_conv(p, jax.random.fold_in(key, 1), offset_scale)


def _one_at_a_time(run, x):
    """``run`` on each image of ``x`` alone, as the serving engine's
    degraded step does."""
    return [run(x[i:i + 1]) for i in range(x.shape[0])]


class TestBatchFusedPipelineOracle:
    @pytest.mark.parametrize("h,w,tile", [
        (16, 16, 8),        # divisible
        (13, 13, 4),        # non-divisible (ragged edge tiles)
        (12, 10, (3, 5)),   # rectangular plane AND rectangular tiles
        (9, 14, (4, 3)),    # both dims ragged
    ])
    @pytest.mark.parametrize("backend", ["host", "device"])
    def test_batch_fused_equals_batched_equals_xla(self, h, w, tile,
                                                   backend):
        key = jax.random.PRNGKey(h * 37 + w)
        params = _layer(key, 5, 7)
        x = jax.random.normal(jax.random.fold_in(key, 2), (3, h, w, 5))
        y_ref = deformable_conv2d(x, params)
        cfg = GraphConfig(tile=tile, schedule_backend=backend,
                          use_schedule_cache=False)
        y_f, tr_f = dcn_pipeline(x, params, return_trace=True, config=cfg)
        y_b = jnp.concatenate(_one_at_a_time(
            lambda xi: dcn_pipeline(xi, params, config=cfg), x))
        np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_b),
                                   rtol=1e-5, atol=1e-5)
        # ONE dispatch for the whole batch (vs one per image alone).
        assert tr_f.kernel_dispatches == 1
        assert tr_f.dispatches_per_batch == 1
        assert all(g.dispatch == "batch_fused" for g in tr_f.groups)

    def test_batch_of_one(self):
        key = jax.random.PRNGKey(9)
        params = _layer(key, 4, 6)
        x = jax.random.normal(jax.random.fold_in(key, 2), (1, 13, 13, 4))
        y_ref = deformable_conv2d(x, params)
        y = dcn_pipeline(x, params, config=GraphConfig(tile=4))
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_records_identical_to_batched(self):
        """The per-image spans of the fused dispatch preserve each
        image's schedule order, so records — and the FIFO replay the
        simulator cross-check consumes — are byte-identical to the
        images run one at a time."""
        key = jax.random.PRNGKey(3)
        params = _layer(key, 4, 6)
        x = jax.random.normal(jax.random.fold_in(key, 2), (3, 13, 13, 4))
        cfg = GraphConfig(tile=4, use_schedule_cache=False)
        alone = _one_at_a_time(
            lambda xi: dcn_pipeline(xi, params, return_trace=True,
                                    config=cfg), x)
        _, tr_f = dcn_pipeline(x, params, return_trace=True, config=cfg)
        ib_all = [tr.groups[0] for _, tr in alone]
        t = tr_f.groups[0].grid.num_tiles
        for i, (ib, im) in enumerate(zip(ib_all, tr_f.groups)):
            assert [r.out_tile for r in ib.records] == \
                [r.out_tile for r in im.records]
            assert [r.dep_tiles for r in ib.records] == \
                [r.dep_tiles for r in im.records]
            assert ib.batch_rows == (0, t)
            assert im.batch_rows == (i * t, (i + 1) * t)
        assert (sum(g.fifo_replay().loads for g in tr_f.groups)
                == sum(g.fifo_replay().loads for g in ib_all))

    def test_pipeline_fifo_equals_simulator(self):
        """Concatenated-schedule FIFO loads == sum of per-image simulator
        scheduled loads (the executor-vs-simulator invariant, batched
        across the fused grid)."""
        key = jax.random.PRNGKey(11)
        params = _layer(key, 4, 4, offset_scale=1.5)
        x = jax.random.normal(jax.random.fold_in(key, 2), (3, 16, 16, 4))
        m = 2
        _, tr = dcn_pipeline(
            x, params, return_trace=True,
            config=GraphConfig(tile=8, buffer_tiles=m,
                               use_schedule_cache=False))
        from repro.core.deform import conv2d, offsets_to_coords
        offsets = conv2d(x, params.w_off, params.b_off)
        coords = offsets_to_coords(offsets.astype(jnp.float32), 3, "dcn2")
        grid = TileGrid(16, 16, 8, 8)
        sim_total = 0
        for i in range(x.shape[0]):
            B = np.asarray(tdt_from_coords(coords[i], grid, grid))
            pp = np.asarray(per_pixel_input_tiles(coords[i], grid))
            rep = simulate_strategies(
                B, pp, grid, channels=4, c_out=4, kernel_size=3,
                buffer_bytes=m * grid.tile_bytes(4, 4), dtype_bytes=4)
            sim_total += rep["scheduled"].tile_loads
        assert sum(g.fifo_replay().loads for g in tr.groups) == sim_total


class TestBatchFusedGraphOracle:
    @pytest.mark.parametrize("backend", ["host", "device"])
    def test_matches_dense_and_batched(self, backend):
        convs, graph, x = _acceptance_case()
        y_ref = run_graph_dense(convs, graph, x)
        y_f = run_graph(convs, graph, x, config=GraphConfig(
            tile=4, dispatch="batch_fused", schedule_backend=backend,
            use_schedule_cache=False))
        y_b = jnp.concatenate(_one_at_a_time(
            lambda xi: run_graph(convs, graph, xi, config=GraphConfig(
                tile=4, schedule_backend=backend,
                use_schedule_cache=False)), x))
        np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_b),
                                   rtol=1e-5, atol=1e-5)

    def test_one_dispatch_per_segment_per_batch(self):
        """Kernel dispatches per layer segment == 1 for the WHOLE batch
        (down from one per image run alone)."""
        convs, graph, x = _acceptance_case()
        x4 = jnp.concatenate([x, x[::-1]])          # batch of 4
        cfg = GraphConfig(tile=4)
        alone = _one_at_a_time(
            lambda xi: run_graph(convs, graph, xi, config=cfg,
                                 return_trace=True), x4)
        _, tr_f = run_graph(convs, graph, x4, config=cfg,
                            return_trace=True)
        n_segments = sum(len(g.layer_stats) for g in alone[0][1].groups)
        assert tr_f.dispatches_per_batch == n_segments
        assert sum(tr.kernel_dispatches for _, tr in alone) == \
            4 * n_segments
        assert all(g.kernel_dispatches == 0 for g in tr_f.groups)

    def test_records_and_simulator_exact(self):
        """The executed trace of the fused batch grid must still equal
        the network DRAM simulator EXACTLY, per image."""
        convs, graph, x = _acceptance_case(seed=1)
        _, tr = run_graph(convs, graph, x,
                          config=GraphConfig(tile=4,
                                             dispatch="batch_fused",
                                             use_schedule_cache=False),
                          return_trace=True)
        sim = simulate_network(network_sim_specs(tr),
                               boundary_bytes=tr.boundary_bytes,
                               fused=True)
        for gt, rep in zip(tr.groups, sim.groups):
            assert gt.fifo_replay().loads == rep.tile_loads
            assert gt.input_load_bytes == rep.input_read_bytes
        assert tr.total_dram_bytes == sim.total_dram_bytes

    def test_records_identical_across_dispatch_modes(self):
        convs, graph, x = _acceptance_case(seed=2)
        cfg = GraphConfig(tile=4, use_schedule_cache=False)
        alone = _one_at_a_time(
            lambda xi: run_graph(convs, graph, xi, config=cfg,
                                 return_trace=True), x)
        _, tr = run_graph(convs, graph, x, config=cfg, return_trace=True)
        batch = {(g.image, g.group): g for g in tr.groups}
        single = {(i, g.group): g for i, (_, tr_i) in enumerate(alone)
                  for g in tr_i.groups}
        assert single.keys() == batch.keys()
        for k, gb in single.items():
            gf = batch[k]
            assert [r.out_tile for r in gb.records] == \
                [r.out_tile for r in gf.records]
            assert [r.dep_tiles for r in gb.records] == \
                [r.dep_tiles for r in gf.records]

    @pytest.mark.parametrize("depth", [1, 2, 3])
    def test_staging_depth_overlaps_whole_batches(self, depth):
        """staging_depth stages SEGMENTS of the whole batch; numerics
        must not depend on the queue depth."""
        convs, graph, x = _acceptance_case(seed=4)
        outs = run_graph(convs, graph, x, config=GraphConfig(
            tile=4, dispatch="batch_fused", staging_depth=depth))
        ref = run_graph(convs, graph, x, config=GraphConfig(
            tile=4, dispatch="batch_fused", staging_depth=1))
        np.testing.assert_allclose(np.asarray(outs), np.asarray(ref),
                                   rtol=0, atol=0)


class TestRaggedBatchPadding:
    """Satellite: ragged-batch padding semantics — images whose schedule
    lengths differ pad to the uniform per-image row count with elided
    slots; a batch mixing an EMPTY schedule (the empty-TDT quirk: one
    step, zero deps) with a full one must still compute correctly."""

    def _coords(self, key, grid, n_imgs):
        h, w = grid.h, grid.w
        return jnp.stack([
            jnp.clip(jax.random.uniform(
                jax.random.fold_in(key, i), (h, w, 9, 2)) *
                jnp.asarray([h - 1.0, w - 1.0]), 0.0, None)
            for i in range(n_imgs)])

    def test_mixed_empty_and_full_schedules(self):
        grid = TileGrid(8, 8, 4, 4)
        t = grid.num_tiles
        tp = 16
        key = jax.random.PRNGKey(0)
        coords = self._coords(key, grid, 2)
        x = jax.random.normal(jax.random.fold_in(key, 9), (2, t, tp, 3))
        w = jax.random.normal(jax.random.fold_in(key, 10), (9, 3, 5)) * 0.3
        b = jax.random.normal(jax.random.fold_in(key, 11), (5,)) * 0.1

        # Image 0: the empty-TDT quirk schedule (one step, zero deps).
        # Image 1: a real full schedule from its coords.
        empty = schedule_tiles(np.zeros((t, t), bool), t)
        assert empty.oid == [0] and empty.iid == [[]]
        B1 = np.asarray(tdt_from_coords(coords[1], grid, grid))
        full = schedule_tiles(B1, t)
        scheds = [DeviceSchedule.from_host(empty, t),
                  DeviceSchedule.from_host(full, t)]
        batch = pack_batch_schedules(scheds, t, t)

        # Ragged padding: image 0 contributes 1 valid row, image 1 len(oid).
        oid = np.asarray(batch.oid)
        assert (oid[:t] >= 0).sum() == 1
        assert (oid[t:] >= 0).sum() == len(full.oid)
        # Padded rows' dep entries repeat a real dep of the SAME image
        # (DMA elision across the image boundary).
        dep = np.asarray(batch.dep_glb)
        assert (dep[1:t] == dep[1, 0]).all()
        assert (dep[:t] < t).all() and (dep[t:] >= t).all()

        idx, coeff = jax.vmap(
            lambda c: pack_plane_operands(c, grid, tp))(coords)
        y = dcn_fused_batch(
            x.reshape(2 * t, tp, 3), batch.row_id, batch.dep_glb,
            batch.dep_cnt, idx.reshape(2 * t, tp, 9, 4),
            coeff.reshape(2 * t, tp, 9, 4), w, b, t_in=t, interpret=True)

        # Image 0's single zero-dep row: bias only (packed coeff zeroed).
        np.testing.assert_allclose(
            np.asarray(y[0]), np.broadcast_to(np.asarray(b), (tp, 5)),
            rtol=1e-6, atol=1e-6)
        # Image 1's rows match the per-tile kernel on the same schedule.
        nb = build_neighbour_tables(coords[1], grid)
        y1 = []
        for out_tile, deps in zip(full.oid, full.iid):
            i1, c1 = pack_output_tile(nb, grid, out_tile, deps, tp)
            y1.append(dcn_fused_tile(
                x[1][jnp.asarray(deps)].reshape(len(deps) * tp, 3),
                jnp.asarray(i1), jnp.asarray(c1), w, b, interpret=True))
        valid = np.asarray(batch.oid[t:]) >= 0
        np.testing.assert_allclose(np.asarray(y[t:][valid]),
                                   np.asarray(jnp.stack(y1)),
                                   rtol=1e-5, atol=1e-5)

    def test_pack_batch_schedules_rejects_mismatched_grids(self):
        s1 = DeviceSchedule(np.zeros(4, np.int32), np.zeros((4, 2), np.int32),
                            np.zeros(4, np.int32), np.zeros(4, np.int32))
        s2 = DeviceSchedule(np.zeros(6, np.int32), np.zeros((6, 2), np.int32),
                            np.zeros(6, np.int32), np.zeros(6, np.int32))
        with pytest.raises(ValueError, match="share the tile grid"):
            pack_batch_schedules([s1, s2], 4, 4)


class TestDeviceScheduleHandoff:
    @pytest.mark.parametrize("seed", range(4))
    def test_device_schedule_bit_exact_vs_host(self, seed):
        """The dense device handoff, lazily assembled, must be byte-equal
        to the host Algorithm-1 schedule (same oid/iid/load order)."""
        rng = np.random.default_rng(seed)
        n = int(rng.integers(4, 12))
        B = rng.random((n, n)) < 0.4
        m = int(rng.integers(1, n + 1))
        host = schedule_tiles(B, m)
        ds = schedule_arrays_device(jnp.asarray(B), m, interpret=True)
        assert ds.to_host() == host

    def test_from_host_round_trip(self):
        rng = np.random.default_rng(7)
        B = rng.random((6, 6)) < 0.5
        host = schedule_tiles(B, 3)
        ds = DeviceSchedule.from_host(host, 6)
        assert ds.to_host() is host      # memoized, no rebuild
        rebuilt = DeviceSchedule(ds.oid, ds.dep_tbl, ds.dep_cnt,
                                 ds.overlap)
        assert rebuilt.to_host() == host

    def test_device_hot_path_builds_no_host_schedule(self):
        """ISSUE 5 acceptance: with schedule_backend="device" and
        dispatch="batch_fused", the hot path (return_trace=False)
        performs NO host TileSchedule construction — pipeline AND graph."""
        key = jax.random.PRNGKey(5)
        params = _layer(key, 4, 4)
        x = jax.random.normal(jax.random.fold_in(key, 2), (2, 13, 13, 4))
        convs, graph, xg = _acceptance_case(seed=3)

        c0 = scheduler.host_schedule_builds.count
        y = dcn_pipeline(x, params, config=GraphConfig(
            tile=4, schedule_backend="device", use_schedule_cache=False))
        jax.block_until_ready(y)
        y = run_graph(convs, graph, xg, config=GraphConfig(
            tile=4, dispatch="batch_fused", schedule_backend="device",
            use_schedule_cache=False))
        jax.block_until_ready(y)
        assert scheduler.host_schedule_builds.count == c0

        # ... and the lazy trace path DOES assemble them (off hot path).
        _, tr = dcn_pipeline(x, params, return_trace=True,
                             config=GraphConfig(
                                 tile=4, schedule_backend="device",
                                 use_schedule_cache=False))
        assert scheduler.host_schedule_builds.count > c0
        assert all(g.records for g in tr.groups)


class TestPartialBatchCacheHits:
    def test_mixed_hit_miss_batch(self):
        """Satellite: cached images skip scheduling, misses are built and
        spliced into the batch grid; hit accounting splits into
        image_hits / batch_assemblies. Conv-only groups have
        data-independent digests, so they legitimately hit across
        images; deform groups are keyed per image."""
        from repro.runtime import DeformNode, FusedGroup, partition_graph
        convs, graph, x = _acceptance_case(seed=6)   # batch of 2
        cache = ScheduleCache(maxsize=64)
        cfg = GraphConfig(tile=4, dispatch="batch_fused")
        groups = [s for s in partition_graph(graph,
                                             cfg.onchip_budget_bytes, 4)
                  if isinstance(s, FusedGroup)]
        deform_groups = [gi for gi, g in enumerate(groups)
                         if any(isinstance(nd, DeformNode)
                                for nd in g.nodes)]
        n_groups, n_def = len(groups), len(deform_groups)
        assert n_def >= 1

        y1, tr1 = run_graph(convs, graph, x, config=cfg,
                            schedule_cache=cache, return_trace=True)
        info1 = cache.info()
        # Image 0 misses every group; image 1 misses the deform groups
        # and hits the static (conv-only) ones.
        assert info1["batch_assemblies"] == n_groups
        assert info1["misses"] == n_groups + n_def
        assert info1["image_hits"] == n_groups - n_def

        # Second batch: image 0 replayed (full hit), image 1 new (deform
        # groups miss and are spliced into the batch grid).
        x2 = jnp.concatenate([x[:1], x[1:] * 1.7])
        y2, tr2 = run_graph(convs, graph, x2, config=cfg,
                            schedule_cache=cache, return_trace=True)
        info2 = cache.info()
        assert info2["batch_assemblies"] == 2 * n_groups
        assert info2["misses"] == n_groups + 2 * n_def
        assert info2["image_hits"] == \
            info1["image_hits"] + 2 * n_groups - n_def
        hits = {(g.image, g.group): g.schedule_cache_hit
                for g in tr2.groups}
        assert all(hits[(0, g)] for g in range(n_groups))
        assert not any(hits[(1, g)] for g in deform_groups)

        # The mixed hit/miss batch must equal a cache-less run exactly.
        y_ref = run_graph(convs, graph, x2,
                          config=GraphConfig(tile=4,
                                             dispatch="batch_fused",
                                             use_schedule_cache=False))
        np.testing.assert_array_equal(np.asarray(y2), np.asarray(y_ref))
        # Image 0's rows are identical to the first batch's.
        np.testing.assert_array_equal(np.asarray(y2[0]),
                                      np.asarray(y1[0]))

    def test_serving_stats_expose_batch_counters(self):
        cfg = DcnNetConfig(name="vgg19", n_deform=2, img_size=16,
                           width_mult=0.125, num_classes=4)
        p = init_dcn_net(jax.random.PRNGKey(2), cfg)
        eng = DcnServingEngine(
            p, cfg, graph=GraphConfig(tile=4, dispatch="batch_fused"))
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 16, 3))
        eng.infer(x)
        eng.infer(x)
        s = eng.stats
        assert s["dispatch"] == "batch_fused"
        assert s["batch_assemblies"] > 0
        assert s["image_hits"] > 0                   # second request replays
        assert s["dispatches_per_batch"] == s["kernel_dispatches"] / 2
        assert s["kernel_dispatches"] > 0


class TestConfigValidation:
    def test_batch_fused_accepted_everywhere(self):
        assert GraphConfig().dispatch == "batch_fused"
        assert GraphConfig(dispatch="batch_fused").dispatch == "batch_fused"

    def test_unknown_dispatch_still_rejected(self):
        with pytest.raises(ValueError, match="dispatch"):
            GraphConfig(dispatch="fused_batch")
        with pytest.raises(ValueError, match="dispatch"):
            GraphConfig(dispatch="mega")


class TestLongBatchGrids:
    """Grids as long as SegNet-8's 224² layers at batch 8 (6,272 rows):
    the dep table is cut to the slots its rows use, and a grid whose
    scalar-prefetched tables outgrow SMEM runs as several equal calls.
    Both compute exactly what one call over the full table computes."""

    def _case(self, n_imgs=3):
        grid = TileGrid(24, 24, 4, 4)
        t, tp = grid.num_tiles, 16
        key = jax.random.PRNGKey(4)
        rows = jnp.arange(24.0)[:, None, None]
        cols = jnp.arange(24.0)[None, :, None]
        centre = jnp.stack(jnp.broadcast_arrays(rows, cols), -1)
        coords = jnp.stack([jnp.clip(
            centre + 1.5 * jax.random.normal(jax.random.fold_in(key, i),
                                             (24, 24, 9, 2)), 0.0, 23.0)
            for i in range(n_imgs)])
        # Plane-order rows over every tile, as the executor dispatches an
        # interior layer: the table is as wide as the plane has tiles.
        scheds = []
        for c in coords:
            dep, cnt = tdt_dispatch_arrays(
                np.asarray(tdt_from_coords(c, grid, grid)),
                scheduler.pow2_pad(t))
            ar = np.arange(t, dtype=np.int32)
            scheds.append(DeviceSchedule(ar, dep, cnt, np.zeros_like(ar)))
        batch = pack_batch_schedules(scheds, t, t)
        idx, coeff = jax.vmap(
            lambda c: pack_plane_operands(c, grid, tp))(coords)
        x = jax.random.normal(jax.random.fold_in(key, 9),
                              (n_imgs * t, tp, 3))
        w = jax.random.normal(jax.random.fold_in(key, 10), (9, 3, 5)) * 0.3
        b = jax.random.normal(jax.random.fold_in(key, 11), (5,)) * 0.1

        def run(bd):
            return np.asarray(dcn_fused_batch(
                x, jnp.asarray(bd.row_id), jnp.asarray(bd.dep_glb),
                jnp.asarray(bd.dep_cnt), idx.reshape(n_imgs * t, tp, 9, 4),
                coeff.reshape(n_imgs * t, tp, 9, 4), w, b, t_in=t,
                interpret=True))
        return batch, run

    def test_narrowed_dep_table_computes_the_same(self):
        batch, run = self._case()
        k_pad = batch.dep_glb.shape[1]
        need = int(batch.dep_cnt.max())
        assert need < k_pad // 2        # offsets of ~1.5 px: few deps
        narrow = narrow_dep_slots(batch, 1)
        k = narrow.dep_glb.shape[1]
        assert need <= k < 2 * need and k & (k - 1) == 0
        np.testing.assert_array_equal(run(narrow), run(batch))
        # The floor holds the width; it never widens the table.
        assert narrow_dep_slots(batch, k_pad).dep_glb.shape[1] == k_pad
        assert narrow_dep_slots(batch, 4 * k_pad) is batch
        # Device tables stay as they are (no wait for their counts).
        on_device = batch._replace(dep_cnt=jnp.asarray(batch.dep_cnt))
        assert narrow_dep_slots(on_device, 1) is on_device

    def test_calls_split_to_fit_smem_compute_the_same(self, monkeypatch):
        batch, run = self._case()
        g, k_pad = batch.dep_glb.shape
        whole = run(batch)
        # Room for 7 rows a call: 108 rows run as 16 calls of 7, the
        # last padded with skipped rows.
        monkeypatch.setattr(dcn_fused, "BATCH_PREFETCH_SMEM_BYTES",
                            7 * 4 * (k_pad + 2))
        assert dcn_fused.batch_grid_chunk(g, k_pad) == 7
        jax.clear_caches()
        try:
            np.testing.assert_array_equal(run(batch), whole)
        finally:
            jax.clear_caches()

    @pytest.mark.parametrize("g,k_pad,chunk", [
        (64, 16, 64),          # VGG19-8's 28² layers at batch 4: one call
        (1568, 32, 1568),      # SegNet-8's 112² layers at batch 8
        (6272, 32, 1568),      # its 224² layer: four calls
        (6272, 1024, 63),      # the full 784-slot table: 100 calls
    ])
    def test_grid_chunk(self, g, k_pad, chunk):
        got = dcn_fused.batch_grid_chunk(g, k_pad)
        assert got == chunk
        assert got * 4 * (k_pad + 2) <= dcn_fused.BATCH_PREFETCH_SMEM_BYTES
