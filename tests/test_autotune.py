"""Simulator-guided autotuner (ISSUE 10): search, plan cache, executor
integration, serving stats.

The tuner's contract is checked from every side: tuned plans must never
lose to the greedy baseline (by construction — the greedy seed is
scored first and only strict improvements are accepted), executed
traces under a tuned plan must stay EXACTLY equal to the DRAM
simulator, numerics must match the dense reference, the persisted plan
cache must round-trip through disk and degrade cleanly on corruption,
the partition memo must not conflate greedy and tuned plans for the
same (graph, budget), and the serving engine must surface the
autotuning counters in both ``stats`` and ``metrics_snapshot()``.
"""

import json

import jax
import numpy as np
import pytest

from repro.core.deform import init_deformable_conv, randomize_offset_conv
from repro.core.simulator import simulate_network
from repro.models.dcn_models import DcnNetConfig, init_dcn_net
from repro.runtime import (ConvNode, DeformNode, GraphConfig, NetGraph,
                           PoolNode, build_graph, run_graph,
                           run_graph_dense)
from repro.runtime.fused_exec import network_sim_specs
from repro.runtime.graph import partition_graph_cached
from repro.serving import DcnServingEngine
from repro.tuning import (PlanCache, TunedGroup, TunedPlan,
                          autotune_plan, plan_cache_hits,
                          representative_input, resolve_tuned_plan)


def _conv_p(key, c_in, c_out, scale=0.2):
    return {"w": jax.random.normal(key, (3, 3, c_in, c_out)) * scale,
            "b": jax.random.normal(jax.random.fold_in(key, 1),
                                   (c_out,)) * 0.1}


def _deform_p(key, c_in, c_out, offset_scale=0.5):
    p = init_deformable_conv(key, c_in, c_out, 3, "dcn2")
    return randomize_offset_conv(p, jax.random.fold_in(key, 1),
                                 offset_scale)


def _chain_case(h=13, w=13, seed=0, offset_scale=0.5):
    """conv -> DCN -> conv -> pool -> conv: one fusible run, a boundary,
    a trailing run; h=13 does not divide the default tile."""
    key = jax.random.PRNGKey(seed)
    convs = [
        _conv_p(jax.random.fold_in(key, 0), 3, 6),
        _deform_p(jax.random.fold_in(key, 1), 6, 6, offset_scale),
        _conv_p(jax.random.fold_in(key, 2), 6, 8),
        _conv_p(jax.random.fold_in(key, 3), 8, 8),
    ]
    ph, pw = (h - 2) // 2 + 1, (w - 2) // 2 + 1
    nodes = (ConvNode(0, 3, 6, h, w), DeformNode(1, 6, 6, h, w),
             ConvNode(2, 6, 8, h, w), PoolNode(h, w, 8),
             ConvNode(3, 8, 8, ph, pw))
    graph = NetGraph(nodes, h, w, 3)
    return convs, graph


BUDGET = 512 * 1024


class TestAutotunePlan:
    def test_tuned_never_loses_to_greedy(self):
        convs, graph = _chain_case()
        for bt in (None, 4):
            plan = autotune_plan(convs, graph,
                                 onchip_budget_bytes=BUDGET,
                                 tile_hw=(4, 4), buffer_tiles=bt,
                                 budget=96)
            assert plan.dram_bytes <= plan.greedy_dram_bytes
            assert plan.candidates <= 96
            # the plan tiles every layer node exactly once, in order
            covered = [i for g in plan.groups
                       for i in range(g.start, g.stop)]
            layer_idx = [i for i, n in enumerate(graph.nodes)
                         if isinstance(n, (ConvNode, DeformNode))]
            assert covered == layer_idx

    def test_offline_trace_exact_and_numerics(self, tmp_path):
        """Executed trace under a tuned plan == DRAM simulator, and the
        tuned run matches the dense XLA reference."""
        convs, graph = _chain_case()
        x = representative_input(graph)
        cfg = GraphConfig(tile=4, buffer_tiles=4, autotune="offline",
                          autotune_budget=96,
                          plan_cache_dir=str(tmp_path))
        y, trace = run_graph(convs, graph, x, config=cfg,
                             return_trace=True)
        sim = simulate_network(network_sim_specs(trace),
                               boundary_bytes=trace.boundary_bytes,
                               fused=True)
        assert trace.total_dram_bytes == sim.total_dram_bytes
        y_ref = run_graph_dense(convs, graph, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_tuned_executed_dram_le_greedy(self, tmp_path):
        convs, graph = _chain_case()
        x = representative_input(graph)
        base = dict(tile=4, buffer_tiles=4)
        _, tr_g = run_graph(convs, graph, x,
                            config=GraphConfig(**base),
                            return_trace=True)
        _, tr_t = run_graph(convs, graph, x,
                            config=GraphConfig(
                                **base, autotune="offline",
                                autotune_budget=96,
                                plan_cache_dir=str(tmp_path)),
                            return_trace=True)
        assert tr_t.total_dram_bytes <= tr_g.total_dram_bytes

    def test_property_tuned_le_greedy_random_nets(self):
        """Hypothesis sweep: tuned <= greedy on random chains, budgets
        and FIFO depths (the tuner's by-construction guarantee)."""
        pytest.importorskip(
            "hypothesis",
            reason="hypothesis not installed; property test optional")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=12, deadline=None)
        @given(seed=st.integers(0, 1000), h=st.integers(6, 14),
               deform=st.booleans(),
               bt=st.sampled_from([None, 2, 4]),
               budget=st.integers(8, 64),
               onchip_kb=st.sampled_from([64, 256, 1024]))
        def prop(seed, h, deform, bt, budget, onchip_kb):
            key = jax.random.PRNGKey(seed)
            convs = [_conv_p(jax.random.fold_in(key, 0), 3, 4)]
            nodes = [ConvNode(0, 3, 4, h, h)]
            if deform:
                convs.append(_deform_p(jax.random.fold_in(key, 1),
                                       4, 4))
                nodes.append(DeformNode(1, 4, 4, h, h))
            convs.append(_conv_p(jax.random.fold_in(key, 2), 4, 4))
            nodes.append(ConvNode(len(convs) - 1, 4, 4, h, h))
            graph = NetGraph(tuple(nodes), h, h, 3)
            plan = autotune_plan(
                convs, graph, onchip_budget_bytes=onchip_kb * 1024,
                tile_hw=(4, 4), buffer_tiles=bt, budget=budget)
            assert plan.dram_bytes <= plan.greedy_dram_bytes
            assert plan.candidates <= budget

        prop()


class TestPlanCache:
    def _resolve(self, convs, graph, mode, tmp_path, **kw):
        return resolve_tuned_plan(
            convs, graph, autotune=mode, onchip_budget_bytes=BUDGET,
            tile_hw=(4, 4), buffer_tiles=4, budget=64,
            plan_cache_dir=str(tmp_path), **kw)

    def test_round_trip_disk(self, tmp_path):
        """offline search -> persisted file -> a FRESH cache over the
        same dir serves the identical plan without searching."""
        convs, graph = _chain_case()
        plan = self._resolve(convs, graph, "offline", tmp_path)
        assert plan is not None
        files = list(tmp_path.glob("plan-*.json"))
        assert len(files) == 1
        fresh = PlanCache(cache_dir=str(tmp_path))
        hits0 = plan_cache_hits.count
        again = resolve_tuned_plan(
            convs, graph, autotune="cached-only",
            onchip_budget_bytes=BUDGET, tile_hw=(4, 4),
            buffer_tiles=4, budget=64, plan_cache=fresh)
        assert again == plan
        assert plan_cache_hits.count == hits0 + 1

    def test_corrupt_file_is_a_clean_miss(self, tmp_path):
        """A corrupted cache file must read as a miss (cached-only ->
        None) and offline must recover by re-searching + rewriting."""
        convs, graph = _chain_case()
        plan = self._resolve(convs, graph, "offline", tmp_path)
        (f,) = tmp_path.glob("plan-*.json")
        f.write_text("{not json")
        fresh = PlanCache(cache_dir=str(tmp_path))
        miss = resolve_tuned_plan(
            convs, graph, autotune="cached-only",
            onchip_budget_bytes=BUDGET, tile_hw=(4, 4),
            buffer_tiles=4, budget=64, plan_cache=fresh)
        assert miss is None
        redo = resolve_tuned_plan(
            convs, graph, autotune="offline",
            onchip_budget_bytes=BUDGET, tile_hw=(4, 4),
            buffer_tiles=4, budget=64, plan_cache=fresh)
        # deterministic search: same plan modulo the re-search wall time
        assert (redo.key, redo.groups, redo.dram_bytes) == \
            (plan.key, plan.groups, plan.dram_bytes)
        assert json.loads(f.read_text())["key"]  # file rewritten

    def test_wrong_key_in_file_is_a_miss(self, tmp_path):
        """A file whose embedded key disagrees with its filename's key
        (e.g. a digest collision or a hand-edited file) is rejected."""
        convs, graph = _chain_case()
        plan = self._resolve(convs, graph, "offline", tmp_path)
        (f,) = tmp_path.glob("plan-*.json")
        doc = json.loads(f.read_text())
        doc["key"][0] = "0" * 40  # forge the digest
        f.write_text(json.dumps(doc))
        fresh = PlanCache(cache_dir=str(tmp_path))
        assert fresh.get(plan.key) is None

    def test_cached_only_never_searches(self, tmp_path):
        convs, graph = _chain_case()
        out = self._resolve(convs, graph, "cached-only", tmp_path)
        assert out is None
        assert list(tmp_path.glob("plan-*.json")) == []

    def test_plan_json_round_trip(self):
        plan = TunedPlan(
            key=("d" * 40, 8, 8, 1, BUDGET, 4, 4, 4, None, "alg1",
                 None),
            groups=(TunedGroup(0, 2, 4, 8),), dram_bytes=123,
            greedy_dram_bytes=456, candidates=7, search_s=0.5)
        assert TunedPlan.from_json(plan.to_json()) == plan


class TestPartitionMemoKeying:
    def test_memo_not_conflated(self):
        """Satellite 1 regression: the partition memo must key on the
        autotune mode + tuned plan — a tuned partition for the same
        (graph, budget) must not shadow the greedy one or vice versa."""
        convs, graph = _chain_case()
        greedy = partition_graph_cached(graph, BUDGET)
        plan = autotune_plan(convs, graph, onchip_budget_bytes=BUDGET,
                             tile_hw=(4, 4), buffer_tiles=4, budget=64)
        tuned = partition_graph_cached(graph, BUDGET,
                                       autotune="offline", tuned=plan)
        tile_hws = [s.tile_hw for s in tuned
                    if hasattr(s, "tile_hw")]
        assert tile_hws and all(t is not None for t in tile_hws)
        assert all(s.tile_hw is None for s in greedy
                   if hasattr(s, "tile_hw"))
        # greedy again: same memo entry (shared segment objects), and
        # NOT the tuned partition
        greedy2 = partition_graph_cached(graph, BUDGET)
        assert greedy2 == greedy and greedy2 != tuned
        assert all(a is b for a, b in zip(greedy2, greedy))
        tuned2 = partition_graph_cached(graph, BUDGET,
                                        autotune="offline", tuned=plan)
        assert all(a is b for a, b in zip(tuned2, tuned))


class TestConfigValidation:
    @pytest.mark.parametrize("dispatch", ["batch_fused", "per_tile"])
    def test_invalid_mode_rejected(self, dispatch):
        with pytest.raises(ValueError, match="autotune"):
            GraphConfig(dispatch=dispatch, autotune="aggressive")
        with pytest.raises(ValueError, match="autotune_budget"):
            GraphConfig(dispatch=dispatch, autotune="offline",
                        autotune_budget=0)
        # valid modes construct fine
        GraphConfig(dispatch=dispatch, autotune="cached-only")


class TestServingAutotune:
    def _case(self, img=16, seed=2):
        cfg = DcnNetConfig(name="vgg19", n_deform=2, img_size=img,
                           width_mult=0.125, num_classes=4)
        params = init_dcn_net(jax.random.PRNGKey(seed), cfg)
        return cfg, params

    def test_stats_and_metrics_surface_autotune(self, tmp_path):
        """Satellite 6: plan_cache_hits / autotune_search_s /
        tuned_groups appear in stats AND metrics_snapshot and agree;
        a second engine over the same cache dir hits the cache and
        reports zero search time."""
        cfg, params = self._case()
        g = GraphConfig(tile=4, buffer_tiles=4, autotune="offline",
                        autotune_budget=64,
                        plan_cache_dir=str(tmp_path))
        eng = DcnServingEngine(params, cfg, graph=g, slots=2)
        s = eng.stats
        assert s["autotune"] == "offline"
        assert s["tuned_groups"] == eng.tuned_groups > 0
        assert s["autotune_search_s"] == eng.tuned_plan.search_s
        snap = eng.metrics_snapshot()
        for k in ("plan_cache_hits", "tuned_groups",
                  "autotune_search_s"):
            assert snap[f"serving.{k}"] == s[k]

        eng2 = DcnServingEngine(params, cfg, graph=g, slots=2)
        s2 = eng2.stats
        assert eng2.tuned_plan == eng.tuned_plan
        assert s2["plan_cache_hits"] >= 1
        assert s2["autotune_search_s"] == 0.0

        x = np.random.default_rng(0).normal(
            size=(16, 16, 3)).astype(np.float32)
        eng2.submit(x)
        (done,) = eng2.drain()
        ref = DcnServingEngine(params, cfg,
                               graph=GraphConfig(tile=4,
                                                 buffer_tiles=4),
                               slots=2)
        ref.submit(x)
        (done_ref,) = ref.drain()
        np.testing.assert_allclose(np.asarray(done.result()),
                                   np.asarray(done_ref.result()),
                                   rtol=1e-4, atol=1e-4)

    def test_autotune_off_engine_untouched(self):
        cfg, params = self._case()
        eng = DcnServingEngine(params, cfg,
                               graph=GraphConfig(tile=4), slots=2)
        s = eng.stats
        assert s["autotune"] == "off"
        assert s["tuned_groups"] == 0
        assert s["plan_cache_hits"] == 0
        assert eng.tuned_plan is None
