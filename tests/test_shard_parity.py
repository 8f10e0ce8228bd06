"""Scale-out parity: sharded batch_fused == single-device == oracle.

Host-side shard plumbing (ShardPlan, per-shard packing, stack/unstack)
and config validation run everywhere. Device parity scenarios run in
SUBPROCESSES with XLA_FLAGS=--xla_force_host_platform_device_count=4
(the main pytest process keeps its single CPU device, per the dry-run
isolation rule) — except on the CI ``multidevice`` leg, where the whole
pytest process has 4 forced devices and the in-process class runs too.

The invariants (ISSUE 9):
* sharded ``batch_fused`` output == single-device ``batch_fused``
  BIT-exact == XLA oracle to float tolerance — across ragged batches,
  batch sizes not divisible by the device count, empty shards, and an
  empty-schedule image inside one shard;
* per-image traces are placement-independent and stay EXACTLY equal to
  the network DRAM simulator;
* serving replica placement keeps the exactly-once contract under the
  PR 8 chaos harness.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import GraphConfig, plan_batch_shards
from repro.runtime.shard import (allgather_nbytes, shard_batch_schedules,
                                 stack_rows, unstack_rows)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, timeout: int = 560, devices: int = 4):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{devices}")
    # Forced host devices are CPU devices: the child must never reach
    # for an accelerator the parent process may already hold.
    env["JAX_PLATFORMS"] = "cpu"
    # src for the package, the repo root so scripts can reuse the
    # test-suite case builders (tests.test_graph etc.).
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), _ROOT])
    script = textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}")
    return proc.stdout


# ---------------------------------------------------------------------------
# Host-side shard plumbing (no devices needed)
# ---------------------------------------------------------------------------


class TestShardPlan:
    def test_near_even_default(self):
        p = plan_batch_shards(10, 4)
        assert p.sizes == (3, 3, 2, 2)
        assert p.spans == ((0, 3), (3, 6), (6, 8), (8, 10))
        assert p.n_max == 3

    def test_explicit_sizes_with_empty_shard(self):
        p = plan_batch_shards(5, 4, sizes=[3, 0, 2, 0])
        assert p.sizes == (3, 0, 2, 0)
        assert p.spans[1] == (3, 3)
        assert p.n_max == 3

    def test_fewer_images_than_shards(self):
        p = plan_batch_shards(2, 4)
        assert p.sizes == (1, 1, 0, 0)

    def test_validation(self):
        with pytest.raises(ValueError, match="sum to"):
            plan_batch_shards(5, 2, sizes=[2, 2])
        with pytest.raises(ValueError, match="entries"):
            plan_batch_shards(4, 2, sizes=[2, 1, 1])
        with pytest.raises(ValueError, match="negative"):
            plan_batch_shards(2, 2, sizes=[3, -1])
        with pytest.raises(ValueError, match="n_shards"):
            plan_batch_shards(4, 0)

    def test_stack_unstack_round_trip(self):
        rng = np.random.default_rng(3)
        flat = jnp.asarray(rng.normal(size=(5 * 4, 6, 2)))
        for sizes in (None, [3, 0, 2, 0], [1, 1, 1, 2]):
            p = plan_batch_shards(5, 4, sizes=sizes)
            stacked = stack_rows(flat, p, 4)
            assert stacked.shape == (4, p.n_max * 4, 6, 2)
            back = unstack_rows(stacked, p, 4)
            np.testing.assert_array_equal(np.asarray(back),
                                          np.asarray(flat))

    def test_allgather_nbytes(self):
        a = jnp.zeros((3, 4), jnp.float32)
        assert allgather_nbytes(a) == 48


class TestShardPack:
    def _scheds(self, t, n):
        from repro.core.scheduler import DeviceSchedule, schedule_tiles
        from repro.core.tiles import TileGrid, tdt_from_coords
        grid = TileGrid(8, 8, 4, 4)
        key = jax.random.PRNGKey(0)
        out = []
        for i in range(n):
            c = jnp.clip(jax.random.uniform(
                jax.random.fold_in(key, i), (8, 8, 9, 2)) * 7.0, 0.0,
                None)
            B = np.asarray(tdt_from_coords(c, grid, grid))
            out.append(DeviceSchedule.from_host(schedule_tiles(B, t), t))
        return out

    def test_per_shard_ragged_padding(self):
        """Each shard keeps its own k_pad; cross-shard pad rows are
        fully elided (cnt 0, oid -1)."""
        t = 4
        scheds = self._scheds(t, 5)
        plan = plan_batch_shards(5, 4)
        sh = shard_batch_schedules(scheds, t, t, plan)
        g_max = plan.n_max * scheds[0].n_rows
        assert sh.row_id.shape == (4, g_max)
        assert sh.dep_glb.shape[:2] == (4, g_max)
        oid = np.asarray(sh.oid)
        cnt = np.asarray(sh.dep_cnt)
        # shards with one image: the trailing slab rows are padding
        rows1 = scheds[0].n_rows
        for s in (1, 2, 3):
            assert (oid[s, rows1:] == -1).all()
            assert (cnt[s, rows1:] == 0).all()

    def test_empty_schedule_image_in_one_shard(self):
        """The empty-TDT quirk schedule (one step, zero deps) packs
        into its shard without disturbing neighbours."""
        from repro.core.scheduler import DeviceSchedule, schedule_tiles
        t = 4
        empty = schedule_tiles(np.zeros((t, t), bool), t)
        assert empty.oid == [0] and empty.iid == [[]]
        scheds = self._scheds(t, 3)
        scheds[1] = DeviceSchedule.from_host(empty, t)
        plan = plan_batch_shards(3, 2)        # shard 0: imgs 0,1
        sh = shard_batch_schedules(scheds, t, t, plan)
        oid = np.asarray(sh.oid)
        cnt = np.asarray(sh.dep_cnt)
        rows = scheds[0].n_rows
        # image 1 (second on shard 0): 1 real zero-dep row, rest padded
        img1 = slice(rows, 2 * rows)
        assert (oid[0, img1] >= 0).sum() == 1
        assert (cnt[0, img1] == 0).all()

    def test_empty_shard_is_fully_elided(self):
        t = 4
        scheds = self._scheds(t, 2)
        plan = plan_batch_shards(2, 3, sizes=[1, 0, 1])
        sh = shard_batch_schedules(scheds, t, t, plan)
        assert (np.asarray(sh.oid)[1] == -1).all()
        assert (np.asarray(sh.dep_cnt)[1] == 0).all()

    def test_plan_mismatch_rejected(self):
        scheds = self._scheds(4, 2)
        with pytest.raises(ValueError, match="plan"):
            shard_batch_schedules(scheds, 4, 4, plan_batch_shards(3, 2))


class TestShardConfigValidation:
    def test_sharding_requires_batch_fused(self):
        with pytest.raises(ValueError, match="batch_fused"):
            GraphConfig(dispatch="per_tile", data_parallel=2)
        with pytest.raises(ValueError, match="batch_fused"):
            GraphConfig(dispatch="per_tile", mesh=object())

    def test_data_parallel_bounds(self):
        with pytest.raises(ValueError, match="data_parallel"):
            GraphConfig(dispatch="batch_fused", data_parallel=0)
        # data_parallel=1 is the single-device no-op, any dispatch
        GraphConfig(dispatch="per_tile", data_parallel=1)

    def test_shard_sizes_requires_sharded_config(self):
        from tests.test_graph import _acceptance_case
        from repro.runtime import run_graph
        convs, graph, x = _acceptance_case()
        with pytest.raises(ValueError, match="shard_sizes"):
            run_graph(convs, graph, x,
                      config=GraphConfig(tile=4,
                                         dispatch="batch_fused"),
                      shard_sizes=[1, 1])

    def test_oversubscribed_host_mesh_is_clear(self):
        """data_parallel beyond the live device count surfaces the
        make_host_mesh recipe, not a reshape error."""
        from tests.test_graph import _acceptance_case
        from repro.runtime import run_graph
        convs, graph, x = _acceptance_case()
        big = jax.device_count() + 1
        with pytest.raises(ValueError,
                           match="xla_force_host_platform"):
            run_graph(convs, graph, x,
                      config=GraphConfig(tile=4, dispatch="batch_fused",
                                         data_parallel=big))


# ---------------------------------------------------------------------------
# Device parity (subprocesses, 4 forced host devices)
# ---------------------------------------------------------------------------


class TestShardedParity:
    def test_pipeline_and_graph_sharded_bit_exact(self):
        """Sharded == single-device bit-exact, both == XLA oracle —
        pipeline and graph executors, ragged batch of 5 over 4 devices
        (not divisible), explicit shard_sizes with empty shards."""
        _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core.deform import (deformable_conv2d,
                                           init_deformable_conv,
                                           randomize_offset_conv)
            from repro.runtime import (GraphConfig, dcn_pipeline,
                                       run_graph, run_graph_dense)
            from tests.test_graph import _acceptance_case
            assert jax.device_count() == 4

            key = jax.random.PRNGKey(7)
            params = randomize_offset_conv(
                init_deformable_conv(key, 5, 7, 3, "dcn2"),
                jax.random.fold_in(key, 1), 0.7)
            x = jax.random.normal(jax.random.fold_in(key, 2),
                                  (5, 13, 13, 5))
            y_ref = deformable_conv2d(x, params)
            y0 = dcn_pipeline(x, params, config=GraphConfig(
                tile=4, dispatch="batch_fused",
                use_schedule_cache=False))
            for dp in (2, 4):
                y = dcn_pipeline(x, params, config=GraphConfig(
                    tile=4, dispatch="batch_fused", data_parallel=dp,
                    use_schedule_cache=False))
                assert np.array_equal(np.asarray(y), np.asarray(y0)), dp
            np.testing.assert_allclose(np.asarray(y0),
                                       np.asarray(y_ref),
                                       rtol=1e-4, atol=1e-4)

            convs, graph, _ = _acceptance_case()
            xg = jax.random.normal(jax.random.fold_in(key, 3),
                                   (5, 13, 13, 3))
            yd = run_graph_dense(convs, graph, xg)
            g0 = run_graph(convs, graph, xg, config=GraphConfig(
                tile=4, dispatch="batch_fused",
                use_schedule_cache=False))
            for dp in (2, 4):
                g = run_graph(convs, graph, xg, config=GraphConfig(
                    tile=4, dispatch="batch_fused", data_parallel=dp,
                    use_schedule_cache=False))
                assert np.array_equal(np.asarray(g), np.asarray(g0)), dp
            ge = run_graph(
                convs, graph, xg,
                config=GraphConfig(tile=4, dispatch="batch_fused",
                                   data_parallel=4,
                                   use_schedule_cache=False),
                shard_sizes=[3, 0, 2, 0])
            assert np.array_equal(np.asarray(ge), np.asarray(g0))
            np.testing.assert_allclose(np.asarray(g0), np.asarray(yd),
                                       rtol=1e-4, atol=1e-4)
            print("sharded parity OK")
        """)

    def test_sharded_trace_equals_simulator(self):
        """Per-image traces are placement-independent and EXACTLY equal
        to the network DRAM simulator under sharding."""
        _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core.simulator import simulate_network
            from repro.runtime import GraphConfig, run_graph
            from repro.runtime.fused_exec import network_sim_specs
            from tests.test_graph import _acceptance_case
            assert jax.device_count() == 4

            convs, graph, _ = _acceptance_case(seed=1)
            x = jax.random.normal(jax.random.PRNGKey(8), (5, 13, 13, 3))
            _, tr0 = run_graph(convs, graph, x, return_trace=True,
                               config=GraphConfig(
                                   tile=4, dispatch="batch_fused",
                                   use_schedule_cache=False))
            _, tr = run_graph(convs, graph, x, return_trace=True,
                              config=GraphConfig(
                                  tile=4, dispatch="batch_fused",
                                  data_parallel=4,
                                  use_schedule_cache=False))
            assert tr.shards == 4 and tr.allgather_bytes > 0
            assert len(tr.groups) == len(tr0.groups)
            for g0, g in zip(tr0.groups, tr.groups):
                assert (g0.image, g0.group) == (g.image, g.group)
                assert [r.out_tile for r in g0.records] == \\
                    [r.out_tile for r in g.records]
                assert [r.dep_tiles for r in g0.records] == \\
                    [r.dep_tiles for r in g.records]
            sim = simulate_network(network_sim_specs(tr),
                                   boundary_bytes=tr.boundary_bytes,
                                   fused=True)
            for gt, rep in zip(tr.groups, sim.groups):
                assert gt.fifo_replay().loads == rep.tile_loads
                assert gt.input_load_bytes == rep.input_read_bytes
            assert tr.total_dram_bytes == sim.total_dram_bytes
            print("sharded trace == simulator OK")
        """)

    def test_serving_replicas_exactly_once_under_chaos(self):
        """Replica-aware slot placement: sharded engine == unsharded
        bit-exact, balanced per-replica accounting, and the
        exactly-once contract under the PR 8 fault-storm harness."""
        _run("""
            import jax.numpy as jnp
            import numpy as np
            from repro.runtime import GraphConfig
            from repro.serving import DcnServingEngine
            from repro.serving.errors import RequestFailedError
            from repro.testing import FaultInjector
            from tests.test_serving import _dcn_case
            cfg, params = _dcn_case()

            def images(n, seed=0):
                rng = np.random.default_rng(seed)
                return rng.normal(
                    size=(n, 16, 16, 3)).astype(np.float32)

            shard_graph = GraphConfig(tile=4, dispatch="batch_fused",
                                      data_parallel=4)
            eng0 = DcnServingEngine(params, cfg,
                                    graph=GraphConfig(tile=4), slots=4)
            eng4 = DcnServingEngine(params, cfg, graph=shard_graph,
                                    slots=4)
            assert eng4.replicas == 4
            assert eng4._slot_replica == [0, 1, 2, 3]
            xs = [images(1, seed=i) for i in range(5)]
            r0 = [eng0.submit(x) for x in xs]
            eng0.drain()
            r4 = [eng4.submit(x) for x in xs]
            eng4.drain()
            y0 = np.concatenate([r.result() for r in r0])
            y4 = np.concatenate([r.result() for r in r4])
            assert np.array_equal(y0, y4)
            s = eng4.stats
            assert s["replicas"] == 4
            per = s["per_replica"]
            assert sum(p["images"] for p in per) == 5
            assert [p["images"] for p in per] == [2, 1, 1, 1]
            assert s["allgather_bytes"] > 0
            assert all(p["dram_bytes"] > 0 for p in per)
            snap = eng4.metrics_snapshot()
            assert "serving.replica0.dispatches" in snap

            # chaos: seeded fault storm on the sharded engine
            inj = FaultInjector(kinds=("prepass", "dispatch"),
                                rate=0.3, seed=13)
            eng = DcnServingEngine(params, cfg, graph=shard_graph,
                                   slots=4, faults=inj)
            xs8 = images(8, seed=5)
            ref = [np.asarray(eng0.infer(jnp.asarray(xs8[i][None])))[0]
                   for i in range(8)]
            reqs = [eng.submit(xs8[i]) for i in range(8)]
            done = eng.drain(max_steps=100)
            rids = [r.rid for r in done]
            assert sorted(rids) == [r.rid for r in reqs]
            assert len(rids) == len(set(rids))
            assert eng.drain() == []
            assert inj.total_fired > 0
            for i, r in enumerate(reqs):
                assert r.done
                if r.failed:
                    assert isinstance(r.error, RequestFailedError)
                else:
                    np.testing.assert_allclose(
                        r.result()[0], ref[i], rtol=2e-4, atol=2e-4)
            print("serving replicas exactly-once OK")
        """)


# ---------------------------------------------------------------------------
# In-process coverage for the CI multidevice leg (whole pytest process
# runs under 4 forced host devices there; skipped on 1-device hosts)
# ---------------------------------------------------------------------------


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 devices (multidevice CI leg)")
class TestShardedInProcess:
    def test_graph_parity_in_process(self):
        from tests.test_graph import _acceptance_case
        from repro.runtime import run_graph
        convs, graph, x = _acceptance_case()
        dp = min(jax.device_count(), 4)
        y0 = run_graph(convs, graph, x, config=GraphConfig(
            tile=4, dispatch="batch_fused", use_schedule_cache=False))
        y = run_graph(convs, graph, x, config=GraphConfig(
            tile=4, dispatch="batch_fused", data_parallel=dp,
            use_schedule_cache=False))
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))

    def test_serving_slots_cover_replicas(self):
        from tests.test_serving import _dcn_case
        from repro.serving import DcnServingEngine
        cfg, params = _dcn_case()
        with pytest.raises(ValueError, match="replica"):
            DcnServingEngine(
                params, cfg, slots=1,
                graph=GraphConfig(tile=4, dispatch="batch_fused",
                                  data_parallel=jax.device_count()))
