"""On-device tile scheduler (kernels.dcn_schedule): bit-exactness vs the
host reference, executor integration, serving stats, and the
schedule-cache tile-shape regression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.deform import conv2d, deformable_conv2d, offsets_to_coords
from repro.core.scheduler import (schedule_tiles, schedule_tiles_device,
                                  sequential_schedule)
from repro.core.tiles import TileGrid, tdt_from_coords
from repro.kernels.dcn_schedule import (greedy_schedule_arrays,
                                        tdt_from_coords_device)
from repro.runtime import (GraphConfig, ScheduleCache, build_graph,
                           dcn_pipeline, run_graph, run_graph_dense)
from repro.runtime.cache import coords_digest


def random_coords(rng, h, w, kk=9, spread=4.0):
    """Absolute sampling coordinates incl. out-of-range values (the
    clipped-floor path must behave like the host's)."""
    return jnp.asarray(rng.uniform(-spread, h + spread,
                                   size=(h, w, kk, 2)).astype(np.float32))


def assert_schedules_equal(a, b):
    assert a.oid == b.oid
    assert a.iid == b.iid
    assert a.reuse_overlap == b.reuse_overlap


class TestTdtDeviceKernel:
    @pytest.mark.parametrize("h,w,th,tw", [
        (16, 16, 8, 8),      # even tiling
        (13, 11, 4, 5),      # ragged edges + rectangular tiles
        (8, 8, 8, 8),        # single tile
        (24, 24, 6, 8),      # rectangular, multi-row
    ])
    def test_matches_host_tdt(self, h, w, th, tw):
        rng = np.random.default_rng(h * 100 + w)
        grid = TileGrid(h, w, th, tw)
        coords = random_coords(rng, h, w)
        B_host = np.asarray(tdt_from_coords(coords, grid, grid))
        B_dev = np.asarray(tdt_from_coords_device(coords, grid, grid,
                                                  interpret=True))
        assert B_dev.dtype == bool
        assert np.array_equal(B_host, B_dev)

    def test_all_out_of_range_coords_clip_identically(self):
        grid = TileGrid(12, 12, 4, 4)
        coords = jnp.full((12, 12, 9, 2), 1e6, jnp.float32)
        B_host = np.asarray(tdt_from_coords(coords, grid, grid))
        B_dev = np.asarray(tdt_from_coords_device(coords, grid, grid,
                                                  interpret=True))
        assert np.array_equal(B_host, B_dev)


class TestGreedyDeviceKernel:
    @pytest.mark.parametrize("n,density,m", [
        (6, 0.2, 2), (9, 0.5, 3), (16, 0.35, 4), (16, 0.9, 1),
        (12, 0.6, 20),           # buffer larger than the table
    ])
    def test_matches_host_schedule(self, n, density, m):
        rng = np.random.default_rng(n * 7 + m)
        for trial in range(5):
            B = rng.random((n, n)) < density
            host = schedule_tiles(B, m)
            dev = schedule_tiles_device(B, m, interpret=True)
            assert_schedules_equal(host, dev)

    def test_empty_tdt(self):
        """All-False TDT: the host schedules its argmax pick (tile 0,
        empty load list) once — the device path must reproduce it."""
        B = np.zeros((5, 5), bool)
        host = schedule_tiles(B, 2)
        dev = schedule_tiles_device(B, 2, interpret=True)
        assert host.oid == [0] and host.iid == [[]]
        assert_schedules_equal(host, dev)

    def test_single_tile(self):
        B = np.ones((1, 1), bool)
        assert_schedules_equal(schedule_tiles(B, 1),
                               schedule_tiles_device(B, 1, interpret=True))

    def test_rows_without_deps_are_skipped(self):
        rng = np.random.default_rng(3)
        B = rng.random((10, 10)) < 0.4
        B[2] = False
        B[7] = False
        host = schedule_tiles(B, 3)
        dev = schedule_tiles_device(B, 3, interpret=True)
        assert 2 not in dev.oid and 7 not in dev.oid
        assert_schedules_equal(host, dev)

    def test_rectangular_tdt(self):
        """Composite (cross-layer) tables need not be square."""
        rng = np.random.default_rng(11)
        B = rng.random((6, 14)) < 0.3
        assert_schedules_equal(schedule_tiles(B, 4),
                               schedule_tiles_device(B, 4, interpret=True))

    def test_dense_arrays_shapes(self):
        rng = np.random.default_rng(5)
        B = rng.random((8, 8)) < 0.5
        oid, klass, ovl = greedy_schedule_arrays(jnp.asarray(B), 2,
                                                 interpret=True)
        assert oid.shape == (8, 1) and ovl.shape == (8, 1)
        assert klass.shape == (8, 8)

    def test_backend_dispatch_and_validation(self):
        B = np.ones((2, 2), bool)
        assert_schedules_equal(schedule_tiles(B, 1),
                               schedule_tiles(B, 1, backend="device",
                                              interpret=True))
        with pytest.raises(ValueError, match="backend"):
            schedule_tiles(B, 1, backend="gpu")


class TestMeasuredTdtBackends:
    def test_real_offsets_schedule_bit_exact(self):
        """Oracle configs: TDTs measured from a real stage-1 offset conv,
        across tile shapes and buffer sizes."""
        from benchmarks.workloads import executor_case
        params, x = executor_case(16, 16, 8, 8, 0)
        offsets = conv2d(x, params.w_off, params.b_off)
        coords = offsets_to_coords(offsets.astype(jnp.float32), 3, "dcn2")
        for tile in ((8, 8), (4, 4), (4, 8)):
            grid = TileGrid(16, 16, *tile)
            for m in (1, 2, grid.num_tiles):
                for i in range(x.shape[0]):
                    B_dev = tdt_from_coords_device(coords[i], grid, grid,
                                                   interpret=True)
                    host = schedule_tiles(
                        np.asarray(tdt_from_coords(coords[i], grid, grid)),
                        m)
                    dev = schedule_tiles_device(B_dev, m, interpret=True)
                    assert_schedules_equal(host, dev)


class TestDeviceBackendPipeline:
    def test_matches_xla_and_host_backend(self):
        from benchmarks.workloads import executor_case
        params, x = executor_case(16, 16, 4, 4, 1)
        ref = deformable_conv2d(x, params, 3, "dcn2")
        traces = {}
        for backend in ("host", "device"):
            cfg = GraphConfig(tile=8, buffer_tiles=2,
                              use_schedule_cache=False,
                              schedule_backend=backend)
            y, tr = dcn_pipeline(x, params, config=cfg, return_trace=True)
            np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                       rtol=1e-4, atol=1e-4)
            traces[backend] = tr
        # Bit-exact schedules -> identical executed tile records.
        for im_h, im_d in zip(traces["host"].groups,
                              traces["device"].groups):
            assert im_h.records == im_d.records
        assert traces["device"].groups[0].schedule_backend == "device"
        assert traces["device"].schedule_device_frac == 1.0
        assert traces["host"].schedule_device_frac == 0.0
        assert traces["device"].overlap.schedule_s > 0

    def test_per_tile_dispatch_with_device_schedule(self):
        from benchmarks.workloads import executor_case
        params, x = executor_case(16, 16, 4, 4, 2)
        ref = deformable_conv2d(x, params, 3, "dcn2")
        cfg = GraphConfig(tile=8, buffer_tiles=2, dispatch="per_tile",
                          use_schedule_cache=False,
                          schedule_backend="device")
        y = dcn_pipeline(x, params, config=cfg)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_invalid_backend_rejected(self):
        with pytest.raises(ValueError, match="schedule backend"):
            GraphConfig(dispatch="per_tile", schedule_backend="asic")
        with pytest.raises(ValueError, match="schedule backend"):
            GraphConfig(schedule_backend="asic")


class TestDeviceBackendGraph:
    @pytest.fixture(scope="class")
    def net(self):
        from repro.models.dcn_models import DcnNetConfig, init_dcn_net
        cfg = DcnNetConfig(name="vgg19", n_deform=2, variant="dcn2",
                           img_size=16, width_mult=0.125)
        params = init_dcn_net(jax.random.PRNGKey(0), cfg)
        graph = build_graph(cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
        return cfg, params, graph, x

    def test_matches_dense_and_host_trace(self, net):
        cfg, params, graph, x = net
        dense = run_graph_dense(params["convs"], graph, x,
                                cfg.max_displacement)
        traces = {}
        for backend in ("host", "device"):
            gc = GraphConfig(tile=4, use_schedule_cache=False,
                             schedule_backend=backend)
            y, tr = run_graph(params["convs"], graph, x, config=gc,
                              max_displacement=cfg.max_displacement,
                              return_trace=True)
            np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                                       rtol=1e-3, atol=1e-3)
            traces[backend] = tr
        for gh, gd in zip(traces["host"].groups, traces["device"].groups):
            assert gh.records == gd.records
            assert [b.tolist() for b in gh.b_layers] == \
                   [b.tolist() for b in gd.b_layers]
        assert traces["device"].groups[0].schedule_backend == "device"
        assert traces["device"].schedule_device_frac == 1.0
        # Identical schedules -> identical modeled DRAM traffic.
        assert (traces["host"].total_dram_bytes
                == traces["device"].total_dram_bytes)

    def test_serving_stats_expose_schedule_backend(self, net):
        from repro.serving.engine import DcnServingEngine
        cfg, params, graph, x = net
        eng = DcnServingEngine(
            params, cfg,
            graph=GraphConfig(tile=4, schedule_backend="device"))
        eng.infer(x)
        stats = eng.stats
        assert stats["schedule_backend"] == "device"
        assert stats["schedule_s"] > 0
        assert stats["schedule_device_frac"] == 1.0


class TestScheduleCacheTileShape:
    def test_digest_differs_across_tile_shapes(self):
        rng = np.random.default_rng(0)
        coords = random_coords(rng, 16, 16)
        d44 = coords_digest(coords, TileGrid(16, 16, 4, 4))
        d48 = coords_digest(coords, TileGrid(16, 16, 4, 8))
        d88 = coords_digest(coords, TileGrid(16, 16, 8, 8))
        assert len({d44, d48, d88}) == 3

    def test_same_coords_different_tiles_never_collide(self):
        """Regression: two configs sharing coords but differing in
        (tile_h, tile_w) must build two cache entries, not share one."""
        from benchmarks.workloads import executor_case
        from repro.runtime import default_schedule_cache
        params, x = executor_case(16, 16, 4, 4, 5)
        ref = deformable_conv2d(x, params, 3, "dcn2")
        cache = default_schedule_cache()
        cache.clear()
        for tile in ((4, 4), (4, 8), (8, 8)):
            y = dcn_pipeline(x, params,
                             config=GraphConfig(tile=tile,
                                                buffer_tiles=2))
            np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                       rtol=1e-4, atol=1e-4)
        # Same coords under three tile shapes (16x16 plane, nothing is
        # clamped): every schedule build must miss.
        assert cache.info()["hits"] == 0
        assert cache.info()["misses"] == 3 * x.shape[0]
        # ... while a genuine replay (same coords AND tile) hits.
        dcn_pipeline(x, params,
                     config=GraphConfig(tile=(4, 8), buffer_tiles=2))
        assert cache.info()["hits"] == x.shape[0]

    def test_graph_clamped_tiles_share_entries_legitimately(self):
        """Differently-configured tiles that clamp to the SAME effective
        grid on low-res interior groups may share entries (bit-identical
        schedules); only differing effective grids must miss."""
        from repro.models.dcn_models import DcnNetConfig, init_dcn_net
        cfg = DcnNetConfig(name="vgg19", n_deform=1, variant="dcn2",
                           img_size=16, width_mult=0.125)
        params = init_dcn_net(jax.random.PRNGKey(2), cfg)
        graph = build_graph(cfg)
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 16, 3))
        dense = run_graph_dense(params["convs"], graph, x,
                                cfg.max_displacement)
        cache = ScheduleCache(maxsize=32)
        for tile in (4, 8):
            y = run_graph(params["convs"], graph, x,
                          config=GraphConfig(tile=tile),
                          max_displacement=cfg.max_displacement,
                          schedule_cache=cache)
            np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                                       rtol=1e-3, atol=1e-3)
        # The full-res group's grid differs (4x4 vs 8x8): it must miss
        # on the second run — misses strictly exceed the first run's.
        info = cache.info()
        assert info["misses"] > 5  # first run builds 5 distinct entries

    def test_sequential_schedule_unaffected(self):
        """Backend plumbing must leave the ablation baseline alone."""
        rng = np.random.default_rng(1)
        B = rng.random((6, 6)) < 0.5
        s = sequential_schedule(B)
        assert s.oid == [o for o in range(6) if B[o].any()]
