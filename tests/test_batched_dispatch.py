"""Tile-grid dispatch: oracle, trace-invariant and config tests.

The default ``batch_fused`` dispatch replaces the per-tile Python
dispatch loop with ONE ``pallas_call`` grid per DCN layer — the
Algorithm-1 schedule becomes the grid order, the scalar-prefetched dep
table the DMA sequence. These tests pin down that:

  * grid == per-tile == XLA reference numerics (rectangular tiles,
    non-divisible shapes, multi-layer fused groups);
  * the executed trace still equals the DRAM simulator EXACTLY (the
    records are the schedule, which batching preserves);
  * the dispatch count drops from O(num_tiles) per segment to <= the
    number of layer segments per group;
  * empty schedules and degenerate tile configs are handled loudly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.deform import (deformable_conv2d, init_deformable_conv,
                               randomize_offset_conv)
from repro.core.scheduler import DeviceSchedule, TileSchedule, schedule_tiles
from repro.core.simulator import simulate_network
from repro.kernels.dcn_fused import dcn_fused_batch
from repro.models.dcn_models import DcnNetConfig, dcn_net_apply, init_dcn_net
from repro.runtime import (GraphConfig, dcn_pipeline, pack_batch_schedules,
                           run_graph, run_graph_dense)
from repro.runtime.fused_exec import network_sim_specs
from repro.serving import DcnServingEngine

from tests.test_graph import _acceptance_case


def _layer(key, c_in, c_out, variant="dcn2", offset_scale=0.7):
    p = init_deformable_conv(key, c_in, c_out, 3, variant)
    return randomize_offset_conv(p, jax.random.fold_in(key, 1), offset_scale)


class TestBatchedPipelineOracle:
    @pytest.mark.parametrize("h,w,tile", [
        (16, 16, 8),        # divisible
        (13, 13, 4),        # non-divisible (edge tiles)
        (12, 10, (3, 5)),   # rectangular plane AND rectangular tiles
        (9, 14, (4, 3)),    # both dims ragged
    ])
    def test_batched_equals_per_tile_equals_xla(self, h, w, tile):
        key = jax.random.PRNGKey(h * 37 + w)
        params = _layer(key, 5, 7)
        x = jax.random.normal(jax.random.fold_in(key, 2), (2, h, w, 5))
        y_ref = deformable_conv2d(x, params)
        y_b, tr_b = dcn_pipeline(
            x, params, return_trace=True,
            config=GraphConfig(tile=tile, use_schedule_cache=False))
        y_p, tr_p = dcn_pipeline(
            x, params, return_trace=True,
            config=GraphConfig(tile=tile, dispatch="per_tile",
                               staging_depth=1,
                               use_schedule_cache=False))
        np.testing.assert_allclose(np.asarray(y_b), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(y_b), np.asarray(y_p),
                                   rtol=1e-5, atol=1e-5)
        # One grid dispatch for the batch vs one per schedule entry.
        assert tr_b.kernel_dispatches == 1
        assert tr_p.kernel_dispatches == sum(
            len(g.records) for g in tr_p.groups)
        assert tr_b.kernel_dispatches < tr_p.kernel_dispatches

    def test_staging_depth_does_not_change_numerics(self):
        key = jax.random.PRNGKey(3)
        params = _layer(key, 4, 6)
        x = jax.random.normal(jax.random.fold_in(key, 2), (3, 13, 13, 4))
        outs = [dcn_pipeline(x, params,
                             config=GraphConfig(tile=4, staging_depth=d))
                for d in (1, 2, 3)]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                       rtol=0, atol=0)

    def test_overlap_spans_recorded(self):
        key = jax.random.PRNGKey(5)
        params = _layer(key, 4, 4)
        x = jax.random.normal(jax.random.fold_in(key, 2), (3, 16, 16, 4))
        _, tr = dcn_pipeline(
            x, params, return_trace=True,
            config=GraphConfig(tile=8, use_schedule_cache=False))
        assert tr.overlap.prepass_s > 0
        assert 0.0 <= tr.host_overlap_frac <= 1.0


class TestBatchedGraphOracle:
    @pytest.mark.parametrize("dispatch,depth", [
        ("batch_fused", 1), ("batch_fused", 2), ("per_tile", 2),
    ])
    def test_matches_dense_reference(self, dispatch, depth):
        convs, graph, x = _acceptance_case()
        y_ref = run_graph_dense(convs, graph, x)
        y = run_graph(convs, graph, x,
                      config=GraphConfig(tile=4, dispatch=dispatch,
                                         staging_depth=depth))
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_batched_equals_per_tile(self):
        convs, graph, x = _acceptance_case(seed=3)
        y_b = run_graph(convs, graph, x, config=GraphConfig(
            tile=4, use_schedule_cache=False))
        y_p = run_graph(convs, graph, x, config=GraphConfig(
            tile=4, dispatch="per_tile", use_schedule_cache=False))
        np.testing.assert_allclose(np.asarray(y_b), np.asarray(y_p),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("buffer_tiles", [None, 4, 2])
    def test_trace_matches_simulator_exactly(self, buffer_tiles):
        """The grid path's executed trace of one image agrees EXACTLY
        with the network simulator's FIFO replay — the schedule order
        became the grid order, so the records are byte-identical to the
        per-tile executor's."""
        convs, graph, x = _acceptance_case()
        _, trace = run_graph(
            convs, graph, x[:1],
            config=GraphConfig(tile=4, buffer_tiles=buffer_tiles),
            return_trace=True)
        sim = simulate_network(network_sim_specs(trace),
                               boundary_bytes=trace.boundary_bytes,
                               fused=True)
        for gt, rep in zip(trace.groups, sim.groups):
            assert gt.fifo_replay().loads == rep.tile_loads
            assert gt.input_load_bytes == rep.input_read_bytes
        assert trace.total_dram_bytes == sim.total_dram_bytes

    def test_records_identical_across_dispatch_modes(self):
        convs, graph, x = _acceptance_case(seed=1)
        traces = {}
        for disp in ("batch_fused", "per_tile"):
            _, tr = run_graph(convs, graph, x[:1],
                              config=GraphConfig(tile=4, dispatch=disp),
                              return_trace=True)
            traces[disp] = tr
        for gb, gp in zip(traces["batch_fused"].groups,
                          traces["per_tile"].groups):
            assert [r.out_tile for r in gb.records] == \
                [r.out_tile for r in gp.records]
            assert [r.dep_tiles for r in gb.records] == \
                [r.dep_tiles for r in gp.records]

    def test_dispatch_count_bounded_by_segments(self):
        """Kernel dispatches of one image == its number of layer
        segments (per_tile pays O(num_tiles x layers))."""
        convs, graph, x = _acceptance_case()
        _, tr_b = run_graph(convs, graph, x[:1],
                            config=GraphConfig(tile=4), return_trace=True)
        _, tr_p = run_graph(convs, graph, x[:1],
                            config=GraphConfig(tile=4, dispatch="per_tile"),
                            return_trace=True)
        assert tr_b.kernel_dispatches == sum(len(gt.layer_stats)
                                             for gt in tr_b.groups)
        assert tr_b.kernel_dispatches < tr_p.kernel_dispatches

    def test_batched_is_default(self):
        convs, graph, x = _acceptance_case()
        assert GraphConfig().dispatch == "batch_fused"
        with pytest.raises(ValueError, match="dispatch"):
            GraphConfig(dispatch="batched")
        _, tr = run_graph(convs, graph, x[:1],
                          config=GraphConfig(tile=4), return_trace=True)
        assert all(g.dispatch == "batch_fused" for g in tr.groups)


class TestEmptyScheduleAndPacking:
    @staticmethod
    def _run_batch(batch, t=4):
        """The batch kernel over ``batch`` on random 4x4-tile operands
        of one image with ``t`` tiles; returns (rows, bias)."""
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (t, 16, 3))
        idx = jax.random.randint(jax.random.fold_in(key, 1),
                                 (t, 16, 9, 4), 0, t * 16)
        coeff = jax.random.uniform(jax.random.fold_in(key, 2),
                                   (t, 16, 9, 4))
        w = jax.random.normal(jax.random.fold_in(key, 3), (9, 3, 5))
        b = jax.random.normal(jax.random.fold_in(key, 4), (5,))
        y = dcn_fused_batch(x, jnp.asarray(batch.row_id),
                            jnp.asarray(batch.dep_glb),
                            jnp.asarray(batch.dep_cnt), idx, coeff, w, b,
                            t_in=t, interpret=True)
        return np.asarray(y), np.asarray(b)

    def test_fused_batch_kernel_empty(self):
        """An empty batch grid dispatches nothing."""
        y = dcn_fused_batch(
            jnp.zeros((4, 16, 3)), jnp.zeros((0,), jnp.int32),
            jnp.zeros((0, 2), jnp.int32), jnp.zeros((0,), jnp.int32),
            jnp.zeros((4, 16, 9, 4), jnp.int32),
            jnp.zeros((4, 16, 9, 4), jnp.float32), jnp.zeros((9, 3, 5)),
            jnp.zeros((5,)), t_in=4, interpret=True)
        assert y.shape == (0, 16, 5)

    def test_pack_batch_schedules_empty_schedule(self):
        """A schedule with no steps packs to padding rows only, which
        the kernel skips: every row is the bias."""
        empty = DeviceSchedule.from_host(TileSchedule(oid=[], iid=[]), 4)
        batch = pack_batch_schedules([empty], 4, 4)
        assert batch.dep_glb.shape[0] == 4
        assert batch.oid.tolist() == [-1] * 4
        assert batch.dep_cnt.tolist() == [0] * 4
        y, b = self._run_batch(batch)
        np.testing.assert_array_equal(y, np.broadcast_to(b, y.shape))

    def test_pack_batch_schedules_empty_dep_row_zero_coeff(self):
        """A scheduled step with no deps contributes the bias only: its
        BLI coefficients never reach the accumulator."""
        sched = TileSchedule(oid=[0, 1], iid=[[0, 1], []])
        batch = pack_batch_schedules(
            [DeviceSchedule.from_host(sched, 4)], 4, 4)
        assert batch.dep_cnt.tolist() == [2, 0, 0, 0]
        assert batch.oid.tolist() == [0, 1, -1, -1]
        y, b = self._run_batch(batch)
        np.testing.assert_array_equal(y[1], np.broadcast_to(b, y[1].shape))
        assert not np.allclose(y[0], b)

    def test_schedule_dense_roundtrip(self):
        B = np.zeros((4, 4), bool)
        B[0, :2] = True
        B[2, 1:] = True
        sched = schedule_tiles(B, 4)
        oid, deps, counts = sched.dense()
        assert oid.tolist() == sched.oid
        for n, d in enumerate(sched.iid):
            assert deps[n, :counts[n]].tolist() == d
            assert not deps[n, counts[n]:].any()


class TestConfigValidation:
    def test_pipeline_tile_exceeds_plane(self):
        key = jax.random.PRNGKey(0)
        params = _layer(key, 4, 4)
        x = jnp.zeros((1, 8, 8, 4))
        with pytest.raises(ValueError, match="exceeds"):
            dcn_pipeline(x, params, tile=16)

    def test_graph_tile_exceeds_plane(self):
        convs, graph, x = _acceptance_case()
        with pytest.raises(ValueError, match="exceeds"):
            run_graph(convs, graph, x, config=GraphConfig(tile=64))

    def test_graph_input_shape_mismatch_raises(self):
        """A size-mismatched image must raise, not silently produce
        garbage from schedules built for the graph's plane."""
        convs, graph, _ = _acceptance_case()     # 13x13 graph
        with pytest.raises(ValueError, match="does not match"):
            run_graph(convs, graph, jnp.zeros((1, 8, 8, 3)),
                      config=GraphConfig(tile=4))

    def test_unknown_dispatch_rejected(self):
        with pytest.raises(ValueError, match="dispatch"):
            GraphConfig(dispatch="warp")
        with pytest.raises(ValueError, match="dispatch"):
            GraphConfig(dispatch="batched")

    def test_bad_staging_depth_rejected(self):
        with pytest.raises(ValueError, match="staging_depth"):
            GraphConfig(staging_depth=0)
        with pytest.raises(ValueError, match="staging_depth"):
            GraphConfig(dispatch="per_tile", staging_depth=-1)

    def test_graph_backend_clamps_small_images(self):
        """backend="graph" with the DEFAULT GraphConfig (tile=8) must
        still serve images smaller than the tile — the model/serving
        entry points clamp, only the raw executor rejects."""
        cfg = DcnNetConfig(name="vgg19", n_deform=1, img_size=4,
                           width_mult=0.125, num_classes=4)
        p = init_dcn_net(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 4, 3))
        y_xla = dcn_net_apply(p, cfg, x, backend="xla", fused=False)
        y_g = dcn_net_apply(p, cfg, x, backend="graph")
        np.testing.assert_allclose(np.asarray(y_g), np.asarray(y_xla),
                                   rtol=5e-3, atol=5e-3)
        eng = DcnServingEngine(p, cfg)
        y_s = eng.infer(x)
        np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_xla),
                                   rtol=5e-3, atol=5e-3)

    def test_model_backend_still_clamps_interior_planes(self):
        """Deep-stage planes shrink below the requested tile; the model
        entry points clamp per layer/group instead of erroring."""
        cfg = DcnNetConfig(name="vgg19", n_deform=2, img_size=16,
                           width_mult=0.125, num_classes=4)
        p = init_dcn_net(jax.random.PRNGKey(2), cfg)
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 16, 3))
        y_xla = dcn_net_apply(p, cfg, x, backend="xla", fused=False)
        y_g = dcn_net_apply(p, cfg, x, backend="graph",
                            graph=GraphConfig(tile=4))
        np.testing.assert_allclose(np.asarray(y_g), np.asarray(y_xla),
                                   rtol=5e-3, atol=5e-3)


class TestDcnServing:
    def _engine(self):
        cfg = DcnNetConfig(name="vgg19", n_deform=2, img_size=16,
                           width_mult=0.125, num_classes=4)
        p = init_dcn_net(jax.random.PRNGKey(2), cfg)
        return DcnServingEngine(p, cfg, graph=GraphConfig(tile=4)), cfg, p

    def test_replayed_request_hits_schedule_cache(self):
        eng, _, _ = self._engine()
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 16, 3))
        y1 = eng.infer(x)
        miss1 = eng.stats["schedule_cache_misses"]
        assert eng.stats["schedule_cache_hits"] == 0
        y2 = eng.infer(x)
        s = eng.stats
        assert s["schedule_cache_hits"] == miss1    # full replay
        assert s["schedule_cache_misses"] == miss1  # no new builds
        assert s["requests"] == 2 and s["images"] == 2
        assert s["schedule_cache_hit_rate"] == 0.5
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))

    def test_stats_expose_dispatches(self):
        eng, cfg, p = self._engine()
        x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, 16, 3))
        y = eng.infer(x)
        s = eng.stats
        assert s["kernel_dispatches"] > 0
        y_ref = dcn_net_apply(p, cfg, x, backend="xla", fused=False)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=5e-3, atol=5e-3)

    def test_engine_matches_model_graph_backend_exactly(self):
        """The engine's serve path (clamp + run_graph + head) must stay
        the same computation as dcn_net_apply(backend="graph") — pins the
        two graph entry points together bitwise."""
        eng, cfg, p = self._engine()
        x = jax.random.normal(jax.random.PRNGKey(6), (1, 16, 16, 3))
        y_eng = eng.infer(x)
        y_model = dcn_net_apply(p, cfg, x, backend="graph",
                                graph=GraphConfig(tile=4))
        np.testing.assert_array_equal(np.asarray(y_eng),
                                      np.asarray(y_model))
