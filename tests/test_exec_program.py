"""The compiled group execute against the eager execute it replaces.

``_exec_group_batch_fused`` runs a conv-only group as one compiled
program, and a group with DCN layers as a lead program, then per DCN
layer the batch-fused kernel and a post program; the tile-valid masks are
a constant of the post program. ``_eager_execute`` below is the op-by-op
execute that did that work before (one mask upload per tile, the conv
layers round-tripping through tiles); the tests require the same planes
from the same prepass artifacts, within the tolerance the batch-fused
tests hold against the XLA reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.deform import (DeformableConvParams, conv2d,
                               randomize_offset_conv)
from repro.core.tiles import TileGrid
from repro.kernels.dcn_fused import dcn_fused_batch
from repro.models.dcn_models import DcnNetConfig, init_dcn_net
from repro.runtime import (ConvNode, DeformNode, FusedGroup, GraphConfig,
                           NetGraph, build_graph, run_graph_dense)
from repro.runtime import fused_exec
from repro.runtime.fused_exec import (_exec_group_batch_fused,
                                      _group_batch_prepass, _segment_grid,
                                      _tile_valid_masks,
                                      apply_boundary_batch, exec_programs)
from repro.runtime.graph import partition_graph_cached
from repro.runtime.packing import plane_to_tiles, tiles_to_plane
from repro.serving import DcnServingEngine
from tests.test_graph import _conv_p, _deform_p

IMG = 32
TILE = 3          # ragged grids: 4x4 planes in 3x3 tiles
TOL = dict(rtol=1e-4, atol=1e-4)


def _tile_valid_mask(grid, tile):
    """One tile's (tp, 1) mask, built on its own: the reference."""
    tr, tc = divmod(tile, grid.cols)
    rr = np.arange(tr * grid.th, (tr + 1) * grid.th)
    cc = np.arange(tc * grid.tw, (tc + 1) * grid.tw)
    valid = (rr[:, None] < grid.h) & (cc[None, :] < grid.w)
    return valid.reshape(-1, 1).astype(np.float32)


def _eager_execute(planes, group, convs, cfg, art):
    """The op-by-op batch-fused execute of one group."""
    n = planes.shape[0]
    grid = art.grid
    h, w = grid.h, grid.w
    tp = grid.th * grid.tw
    t = grid.num_tiles
    masks_arr = jnp.stack(
        [jnp.asarray(_tile_valid_mask(grid, ti), planes.dtype)
         for ti in range(t)])
    last = group.n_layers - 1
    flat = jax.vmap(
        lambda p: plane_to_tiles(p, grid))(planes).reshape(n * t, tp, -1)
    for j, node in enumerate(group.nodes):
        p = convs[node.param_idx]
        if isinstance(node, DeformNode):
            ops = art.layer_ops[j]
            kk = node.kernel_size ** 2
            y = dcn_fused_batch(
                flat, ops.batch.row_id, ops.batch.dep_glb,
                ops.batch.dep_cnt, ops.idx, ops.coeff,
                p.w.reshape(kk, node.c_in, node.c_out), p.b, t_in=t,
                kernel_size=node.kernel_size, block_p=cfg.block_p,
                interpret=True)[:, :tp]
            if node.relu:
                y = jax.nn.relu(y)
            y = y * masks_arr[jnp.maximum(ops.batch.oid, 0)]
            if j == last:
                target = jnp.where(ops.batch.oid >= 0, ops.batch.row_id,
                                   n * t)
                y_all = jnp.zeros((n * t + 1, tp, node.c_out), y.dtype)
                flat = y_all.at[target].set(y)[:-1]
            else:
                flat = y
        else:
            pl_j = jax.vmap(lambda ti: tiles_to_plane(ti, grid, h, w))(
                flat.reshape(n, t, tp, node.c_in))
            yp = conv2d(pl_j, p["w"], p["b"])
            if node.relu:
                yp = jax.nn.relu(yp)
            flat = jax.vmap(lambda pj: plane_to_tiles(pj, grid))(
                yp).reshape(n * t, tp, node.c_out)
    return jax.vmap(lambda ti: tiles_to_plane(ti, grid, h, w))(
        flat.reshape(n, t, tp, group.c_out))


def _net(name, seed=4):
    cfg = DcnNetConfig(name=name, n_deform=8, img_size=IMG,
                       width_mult=0.125, num_classes=10)
    key = jax.random.PRNGKey(seed)
    params = init_dcn_net(key, cfg)
    convs = [
        randomize_offset_conv(p, jax.random.fold_in(key, 100 + i),
                              2.0 / p.w.shape[2])
        if isinstance(p, DeformableConvParams) else p
        for i, p in enumerate(params["convs"])]
    return build_graph(cfg), convs


def _chain(h=13, w=13, seed=0):
    """One fused group conv -> DCN -> conv -> DCN -> DCN -> conv: lead
    convs, convs between kernels, kernels back to back, a conv last."""
    key = jax.random.PRNGKey(seed)
    chans = [(3, 6, False), (6, 6, True), (6, 6, False), (6, 8, True),
             (8, 8, True), (8, 8, False)]
    convs, nodes = [], []
    for i, (ci, co, deform) in enumerate(chans):
        k = jax.random.fold_in(key, i)
        convs.append(_deform_p(k, ci, co) if deform else _conv_p(k, ci, co))
        nodes.append((DeformNode if deform else ConvNode)(i, ci, co, h, w))
    return NetGraph(tuple(nodes), h, w, 3), convs


NETS = {"vgg19-8": lambda: _net("vgg19"),
        "segnet-8": lambda: _net("segnet"),
        "chain": _chain}


@pytest.fixture(scope="module")
def nets():
    return {}


def _ragged_schedules(monkeypatch):
    """Every other Algorithm-1 run returns the empty-TDT schedule (one
    step, zero deps), so batches mix full and one-row schedules and the
    last layer's scatter drops padding rows."""
    real = fused_exec.schedule_tiles
    calls = []

    def schedule_tiles(comp, m, **kw):
        calls.append(None)
        if len(calls) % 2:
            return real(np.zeros_like(comp), m, **kw)
        return real(comp, m, **kw)

    monkeypatch.setattr(fused_exec, "schedule_tiles", schedule_tiles)


@pytest.mark.parametrize("name,batch,ragged", [
    ("vgg19-8", 1, False), ("vgg19-8", 4, True),
    ("segnet-8", 2, False), ("segnet-8", 3, True),
    ("chain", 3, False)])
def test_compiled_execute_equals_eager(nets, name, batch, ragged,
                                       monkeypatch):
    """Group by group, from the same prepass artifacts, the compiled
    execute gives the eager execute's plane; without ragged schedules
    the network's output is the XLA reference's. (``chain`` ends in a
    conv, so its DCN layers run in plane order: no padding rows.)"""
    if name not in nets:
        nets[name] = NETS[name]()
    graph, convs = nets[name]
    if ragged:
        _ragged_schedules(monkeypatch)
    cfg = GraphConfig(tile=TILE if name == "vgg19-8" else 4,
                      dispatch="batch_fused", use_schedule_cache=False)
    segments = partition_graph_cached(graph, cfg.onchip_budget_bytes)
    x = jnp.asarray(np.random.default_rng(batch).normal(
        size=(batch, graph.in_h, graph.in_w, graph.in_c)).astype(
            np.float32))
    deform_at = [isinstance(s, FusedGroup)
                 and any(isinstance(nd, DeformNode) for nd in s.nodes)
                 for s in segments]
    planes = stage1 = x
    padded_rows = 0
    for s, seg in enumerate(segments):
        need_out = any(deform_at[s + 1:])
        if not isinstance(seg, FusedGroup):
            planes = apply_boundary_batch(planes, seg)
            if need_out:
                stage1 = apply_boundary_batch(stage1, seg)
            continue
        grid = _segment_grid(seg, *cfg.tile_hw)
        art, stage1 = _group_batch_prepass(
            stage1, seg, convs, grid, grid.num_tiles, cfg, None, None,
            need_out_plane=need_out, interp=True, segment=s)
        padded_rows += sum(int((np.asarray(ops.batch.oid) < 0).sum())
                           for ops in art.layer_ops if ops is not None)
        before = exec_programs.count
        got, dispatches = _exec_group_batch_fused(planes, seg, convs, cfg,
                                                  True, art)
        assert exec_programs.count == before + 1
        assert dispatches == seg.n_layers
        want = _eager_execute(planes, seg, convs, cfg, art)
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
        planes = got
    assert (padded_rows > 0) == ragged
    if not ragged:
        np.testing.assert_allclose(
            np.asarray(planes), np.asarray(run_graph_dense(convs, graph, x)),
            **TOL)


@pytest.mark.parametrize("kind", ["conv", "deform"])
def test_execute_uploads_nothing(kind):
    """Once compiled, a group's execute moves no data host -> device:
    a conv-only group never builds tiles or masks, and a DCN group's
    masks are a constant of its post program."""
    graph, convs = _net("vgg19")
    cfg = GraphConfig(tile=TILE, dispatch="batch_fused",
                      use_schedule_cache=False)
    segments = partition_graph_cached(graph, cfg.onchip_budget_bytes)
    s, seg = next((s, g) for s, g in enumerate(segments)
                  if isinstance(g, FusedGroup)
                  and any(nd.kind == "deform" for nd in g.nodes)
                  == (kind == "deform"))
    planes = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, seg.h, seg.w, seg.c_in)).astype(np.float32))
    grid = _segment_grid(seg, *cfg.tile_hw)
    art, _ = _group_batch_prepass(planes, seg, convs, grid,
                                  grid.num_tiles, cfg, None, None,
                                  need_out_plane=False, interp=True,
                                  segment=s)
    want, _ = _exec_group_batch_fused(planes, seg, convs, cfg, True, art)
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        got, _ = _exec_group_batch_fused(planes, seg, convs, cfg, True, art)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("width", [2, 4])
def test_steps_of_one_width_lower_nothing(width):
    """After the first step of a width, steps of new images lower no
    program, and every group of every step runs the compiled execute."""
    cfg = DcnNetConfig(name="vgg19", n_deform=8, img_size=IMG,
                       width_mult=0.125, num_classes=10)
    eng = DcnServingEngine(init_dcn_net(jax.random.PRNGKey(1), cfg), cfg,
                           graph=GraphConfig(tile=TILE), slots=width)
    groups = [s for s in partition_graph_cached(
        eng.net_graph, eng._step_cfg.onchip_budget_bytes)
        if isinstance(s, FusedGroup)]
    rng = np.random.default_rng(width)

    def step():
        for _ in range(width):
            eng.submit(rng.normal(size=(IMG, IMG, 3)).astype(np.float32))
        assert len(eng.step()) == width
        return eng.stats

    first = step()
    assert first["exec_programs"] == len(groups)
    for k in (2, 3):
        s = step()
        assert s["compiles"] == first["compiles"]
        assert s["exec_programs"] == k * len(groups)
    assert eng.metrics_snapshot()["serving.exec_programs"] == \
        3 * len(groups)


@pytest.mark.parametrize("h,w,th,tw", [(4, 4, 3, 3), (13, 13, 4, 4),
                                       (9, 14, 4, 3), (8, 8, 8, 8),
                                       (28, 28, 8, 8)])
def test_tile_valid_masks_equal_per_tile(h, w, th, tw):
    grid = TileGrid(h, w, th, tw)
    masks = _tile_valid_masks(grid)
    assert masks.shape == (grid.num_tiles, th * tw, 1)
    assert masks.dtype == np.float32
    for t in range(grid.num_tiles):
        np.testing.assert_array_equal(masks[t], _tile_valid_mask(grid, t))
