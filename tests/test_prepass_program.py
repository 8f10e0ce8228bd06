"""The compiled group prepass against the plain functions it replaces.

``_group_batch_prepass`` runs each fused group's stage-1 chain, floors,
TDTs and plane-order packing as one compiled program, fetches the TDTs
and floors once, and assembles the schedule rows in numpy. These tests
call the eager functions that did that work before — ``conv2d``,
``offsets_to_coords``, ``tdt_from_coords``, ``coords_digest``,
``pack_plane_operands``, the jnp ``tdt_dispatch_arrays`` and
``pack_batch_schedules`` over device arrays — and require the same
artifacts: integer products equal, float products within 1e-6 relative.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.deform import (DeformableConvParams, conv2d,
                               offsets_to_coords, randomize_offset_conv)
from repro.core.scheduler import DeviceSchedule, pow2_pad, schedule_tiles
from repro.core.tiles import (compose_tdt_chain, tdt_from_coords,
                              tdt_standard_conv)
from repro.kernels.dcn_schedule import tdt_dispatch_arrays
from repro.kernels.ops import round_up
from repro.models.dcn_models import DcnNetConfig, init_dcn_net
from repro.runtime import GraphConfig, ScheduleCache, build_graph
from repro.runtime.cache import chain_digest, conv_digest, coords_digest
from repro.runtime.fused_exec import (_advance_dense_batch,
                                      _group_batch_prepass, _segment_grid,
                                      apply_boundary_batch,
                                      prepass_programs)
from repro.runtime.graph import (DeformNode, FusedGroup,
                                 partition_graph_cached)
from repro.runtime.packing import pack_batch_schedules, pack_plane_operands
from repro.serving import DcnServingEngine

IMG = 32
TILE = 3          # ragged grids: 4x4 planes in 3x3 tiles


def _vgg19_8(seed=4):
    cfg = DcnNetConfig(name="vgg19", n_deform=8, img_size=IMG,
                       width_mult=0.125, num_classes=10)
    key = jax.random.PRNGKey(seed)
    params = init_dcn_net(key, cfg)
    params["convs"] = [
        randomize_offset_conv(p, jax.random.fold_in(key, 100 + i),
                              2.0 / p.w.shape[2])
        if isinstance(p, DeformableConvParams) else p
        for i, p in enumerate(params["convs"])]
    return cfg, params


@pytest.fixture(scope="module")
def vgg():
    return _vgg19_8()


def _images(n, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(n, IMG, IMG, 3)).astype(np.float32))


def _reference(planes, group, convs, grid, m, p_pad, needs_plane):
    """The eager prepass: per-layer coordinates, advanced plane, per-image
    TDTs, composite schedules and dispatch rows, batch operands."""
    n, t = planes.shape[0], grid.num_tiles
    k_pad = pow2_pad(t)
    last = group.n_layers - 1
    plane, coords = planes, []
    for node, need in zip(group.nodes, needs_plane):
        p = convs[node.param_idx]
        coords.append(
            offsets_to_coords(conv2d(plane, p.w_off, p.b_off).astype(
                jnp.float32), node.kernel_size, node.variant)
            if isinstance(node, DeformNode) else None)
        if need:
            plane = _advance_dense_batch(plane, node, p, None)
    tdts, scheds, rows, digests = [], [], [], []
    for i in range(n):
        b_layers = [tdt_standard_conv(grid, grid, nd.kernel_size)
                    if c is None else np.asarray(tdt_from_coords(c[i], grid,
                                                                 grid))
                    for nd, c in zip(group.nodes, coords)]
        ds = DeviceSchedule.from_host(
            schedule_tiles(compose_tdt_chain(b_layers), m), t)
        per_layer = []
        for j, node in enumerate(group.nodes):
            if not isinstance(node, DeformNode):
                per_layer.append(None)
                continue
            dep, cnt = tdt_dispatch_arrays(jnp.asarray(b_layers[j]), k_pad)
            if j == last:
                oid = jnp.asarray(ds.oid)
                sel = jnp.maximum(oid, 0)
                per_layer.append(DeviceSchedule(
                    oid, dep[sel], jnp.where(oid >= 0, cnt[sel], 0),
                    jnp.zeros_like(oid)))
            else:
                ar = jnp.arange(t, dtype=jnp.int32)
                per_layer.append(DeviceSchedule(ar, dep, cnt,
                                                jnp.zeros_like(ar)))
        tdts.append(b_layers)
        scheds.append(ds)
        rows.append(per_layer)
        digests.append(chain_digest(
            [conv_digest(nd.kernel_size, grid) if c is None
             else coords_digest(c[i], grid)
             for nd, c in zip(group.nodes, coords)], grid))
    batches, operands = [], []
    for j, c in enumerate(coords):
        if c is None:
            batches.append(None)
            operands.append(None)
            continue
        batches.append(pack_batch_schedules([r[j] for r in rows], t, t))
        idx, coeff = jax.vmap(lambda ci: pack_plane_operands(ci, grid,
                                                             p_pad))(c)
        kk = group.nodes[j].kernel_size ** 2
        operands.append((idx.reshape(n * t, p_pad, kk, 4),
                         coeff.reshape(n * t, p_pad, kk, 4)))
    return plane, tdts, scheds, batches, operands, digests


def _assert_close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-30)
    assert float(np.abs(got - want).max()) <= 1e-6 * scale


@pytest.mark.parametrize("use_cache", [False, True])
@pytest.mark.parametrize("batch", [1, 3])
def test_compiled_prepass_equals_eager_functions(vgg, batch, use_cache):
    cfg, params = vgg
    convs = params["convs"]
    gcfg = GraphConfig(tile=TILE, dispatch="batch_fused")
    segments = partition_graph_cached(build_graph(cfg),
                                      gcfg.onchip_budget_bytes)
    deform_at = [isinstance(s, FusedGroup)
                 and any(isinstance(nd, DeformNode) for nd in s.nodes)
                 for s in segments]
    cache = ScheduleCache(maxsize=64) if use_cache else None
    planes = _images(batch, seed=batch)
    deform_groups = 0
    for s, seg in enumerate(segments):
        need_out = any(deform_at[s + 1:])
        if not isinstance(seg, FusedGroup):
            if need_out:
                planes = apply_boundary_batch(planes, seg)
            continue
        grid = _segment_grid(seg, *gcfg.tile_hw)
        m = grid.num_tiles
        tp = grid.th * grid.tw
        p_pad = (tp if tp % min(gcfg.block_p, tp) == 0
                 else round_up(tp, gcfg.block_p))
        needs_plane = [need_out or any(isinstance(nd, DeformNode)
                                       for nd in seg.nodes[j + 1:])
                       for j in range(seg.n_layers)]
        before = prepass_programs.count
        art, plane = _group_batch_prepass(
            planes, seg, convs, grid, m, gcfg, None, cache,
            need_out_plane=need_out, interp=True, segment=s)
        assert prepass_programs.count == before + 1
        ref_plane, tdts, scheds, batches, operands, digests = _reference(
            planes, seg, convs, grid, m, p_pad, needs_plane)

        _assert_close(plane, ref_plane)
        for i, bundle in enumerate(art.bundles):
            for got, want in zip(bundle.b_layers, tdts[i]):
                np.testing.assert_array_equal(np.asarray(got), want)
            for f in ("oid", "dep_tbl", "dep_cnt", "overlap"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(bundle.ds, f)),
                    np.asarray(getattr(scheds[i], f)))
            assert bundle.ds.to_host() == scheds[i].to_host()
        for ops, want, operand in zip(art.layer_ops, batches, operands):
            assert (ops is None) == (want is None)
            if ops is None:
                continue
            for f in want._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(ops.batch, f)),
                    np.asarray(getattr(want, f)))
            np.testing.assert_array_equal(np.asarray(ops.idx),
                                          np.asarray(operand[0]))
            _assert_close(ops.coeff, operand[1])
        if cache is not None:
            key_tail = (grid.th, grid.tw, m, gcfg.schedule, "dense")
            for i, digest in enumerate(digests):
                assert cache.get((digest,) + key_tail) is art.bundles[i]
        deform_groups += any(isinstance(nd, DeformNode) for nd in seg.nodes)
        planes = plane
    assert deform_groups == 2


def test_steps_of_one_width_compile_once(vgg):
    """After the first step of width 3, steps of new images lower no
    program, and every group of every step runs the compiled prepass."""
    cfg, params = vgg
    eng = DcnServingEngine(params, cfg, graph=GraphConfig(tile=TILE),
                           slots=3)
    groups = [s for s in partition_graph_cached(
        eng.net_graph, eng._step_cfg.onchip_budget_bytes)
        if isinstance(s, FusedGroup)]

    def step(seed):
        for img in np.asarray(_images(3, seed)):
            eng.submit(img)
        assert len(eng.step()) == 3
        return eng.stats

    first = step(10)
    assert first["prepass_programs"] == len(groups)
    for k, seed in enumerate((11, 12), start=2):
        s = step(seed)
        assert s["compiles"] == first["compiles"]
        assert s["prepass_programs"] == k * len(groups)
    assert eng.metrics_snapshot()["serving.prepass_programs"] == \
        3 * len(groups)


def _tdt(n_out, n_in, density, seed):
    b = np.random.default_rng(seed).random((n_out, n_in)) < density
    b[0] = False                       # a row with no dependencies
    return b


@pytest.mark.parametrize("n_out,n_in,k_pad,density", [
    (6, 6, 8, 0.5),        # k_pad above n_in: zero-filled slots
    (9, 12, 4, 0.2),       # k_pad below n_in: rows cut to k_pad
    (20, 40, 64, 0.6),     # long tied rows: only a stable sort keeps ids
    (5, 5, 8, 0.0),        # no dependencies anywhere
])
def test_host_dispatch_rows_equal_jnp(n_out, n_in, k_pad, density):
    """A numpy TDT takes ``tdt_dispatch_arrays``' numpy path; its rows
    equal the jnp path's."""
    b = _tdt(n_out, n_in, density, seed=n_out * n_in)
    dep, cnt = tdt_dispatch_arrays(b, k_pad)
    dep_j, cnt_j = tdt_dispatch_arrays(jnp.asarray(b), k_pad)
    assert isinstance(dep, np.ndarray) and isinstance(cnt, np.ndarray)
    assert dep.dtype == np.int32 and cnt.dtype == np.int32
    np.testing.assert_array_equal(dep, np.asarray(dep_j))
    np.testing.assert_array_equal(cnt, np.asarray(cnt_j))


def test_host_pack_batch_schedules_equals_jnp():
    """Host-built schedules assemble in numpy, equal to the jnp path."""
    t = 9
    scheds = [DeviceSchedule.from_host(
        schedule_tiles(_tdt(t, t, d, seed=s), t), t)
        for s, d in enumerate((0.3, 0.0, 0.7))]
    host = pack_batch_schedules(scheds, t, t)
    dev = pack_batch_schedules(
        [dataclasses.replace(s, oid=jnp.asarray(s.oid),
                             dep_tbl=jnp.asarray(s.dep_tbl),
                             dep_cnt=jnp.asarray(s.dep_cnt))
         for s in scheds], t, t)
    for f in host._fields:
        got = getattr(host, f)
        assert isinstance(got, np.ndarray) and got.dtype == np.int32
        np.testing.assert_array_equal(got, np.asarray(getattr(dev, f)))
