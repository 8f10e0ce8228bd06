"""Core paper machinery: deformable conv Eq.1-3, TDT, Algorithm 1,
traffic simulator, fusion planner."""

import functools
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DramEnergyModel, FifoBuffer, LayerShape,
                        access_histogram, bilinear_sample, bli_coefficients,
                        deformable_conv2d, dram_energy,
                        fused_deformable_conv2d, init_deformable_conv,
                        make_square_grid, offsets_to_coords,
                        per_pixel_input_tiles, plan_fusion, schedule_tiles,
                        sequential_schedule, simulate_strategies,
                        tdt_from_coords)
from repro.core.deform import conv2d
from repro.core.fusion import FusionMode


def _rand_coords(key, h, w, kk, max_r=None):
    hi = jnp.array([h - 1.001, w - 1.001])
    return jax.random.uniform(key, (h, w, kk, 2)) * hi


class TestDeformableConv:
    def test_bli_coefficients_sum_to_one(self):
        coords = jax.random.uniform(jax.random.PRNGKey(0), (50, 2)) * 10
        _, coeffs = bli_coefficients(coords)
        np.testing.assert_allclose(coeffs.sum(-1), 1.0, rtol=1e-6)

    def test_bli_integer_coords_exact(self):
        """At integer coordinates BLI returns the feature exactly."""
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8, 3))
        rr, cc = jnp.meshgrid(jnp.arange(8.0), jnp.arange(8.0), indexing="ij")
        coords = jnp.stack([rr, cc], -1)[None, :, :, None, :]
        out = bilinear_sample(x, coords)
        np.testing.assert_allclose(out[:, :, :, 0], x, atol=1e-6)

    def test_zero_offsets_equal_standard_conv(self):
        """With zero offsets the deformable conv IS the standard conv."""
        key = jax.random.PRNGKey(2)
        params = init_deformable_conv(key, 8, 16)  # w_off zero-init
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 10, 10, 8))
        y_def = deformable_conv2d(x, params)
        y_std = conv2d(x, params.w, params.b)
        # Border differs (clamped sampling vs zero pad); compare interior.
        np.testing.assert_allclose(y_def[:, 2:-2, 2:-2], y_std[:, 2:-2, 2:-2],
                                   rtol=1e-4, atol=1e-4)

    def test_dcn1_vs_dcn2_offset_channels(self):
        p1 = init_deformable_conv(jax.random.PRNGKey(0), 4, 4, variant="dcn1")
        p2 = init_deformable_conv(jax.random.PRNGKey(0), 4, 4, variant="dcn2")
        assert p1.w_off.shape[-1] == 2
        assert p2.w_off.shape[-1] == 18

    def test_fused_matches_unfused(self):
        key = jax.random.PRNGKey(4)
        params = init_deformable_conv(key, 6, 12)
        params = params._replace(
            w_off=jax.random.normal(key, params.w_off.shape) * 0.4)
        x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, 9, 6))
        np.testing.assert_allclose(fused_deformable_conv2d(x, params),
                                   deformable_conv2d(x, params),
                                   rtol=1e-5, atol=1e-5)

    def test_fused_grads_match(self):
        key = jax.random.PRNGKey(6)
        params = init_deformable_conv(key, 4, 4)
        params = params._replace(
            w_off=jax.random.normal(key, params.w_off.shape) * 0.3)
        x = jax.random.normal(jax.random.PRNGKey(7), (1, 8, 8, 4))
        g1 = jax.grad(lambda p: deformable_conv2d(x, p).sum())(params)
        g2 = jax.grad(lambda p: fused_deformable_conv2d(x, p).sum())(params)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)

    def test_fused_grads_match_wrt_input_and_dcn1(self):
        """Checkpoint-path gradients agree with the reference for d/dx as
        well as d/dparams, across variants and with offset clamping."""
        key = jax.random.PRNGKey(8)
        params = init_deformable_conv(key, 4, 4, variant="dcn1")
        params = params._replace(
            w_off=jax.random.normal(key, params.w_off.shape) * 0.5)
        x = jax.random.normal(jax.random.PRNGKey(9), (1, 8, 8, 4))

        def loss(fn, x, p):
            return (fn(x, p, variant="dcn1", max_displacement=2.0) ** 2).sum()

        gx1, gp1 = jax.grad(lambda x, p: loss(deformable_conv2d, x, p),
                            argnums=(0, 1))(x, params)
        gx2, gp2 = jax.grad(lambda x, p: loss(fused_deformable_conv2d, x, p),
                            argnums=(0, 1))(x, params)
        np.testing.assert_allclose(gx1, gx2, rtol=1e-4, atol=1e-4)
        for a, b in zip(jax.tree.leaves(gp1), jax.tree.leaves(gp2)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)

    def test_max_displacement_clamps(self):
        offsets = jnp.full((1, 4, 4, 18), 100.0)
        coords = offsets_to_coords(offsets, 3, "dcn2", max_displacement=2.0)
        centre_plus = jnp.max(coords[..., 0])
        assert centre_plus <= 3 + 1 + 2.0  # centre + tap + clamp


class TestTDT:
    def test_tdt_covers_neighbours(self):
        h = w = 20
        grid = make_square_grid(h, w, 5)
        coords = _rand_coords(jax.random.PRNGKey(0), h, w, 9)
        B = np.asarray(tdt_from_coords(coords, grid, grid))
        assert B.shape == (25, 25)
        assert B.any(axis=1).all()  # every output tile has deps
        # dependency implied by per-pixel tiles
        pp = np.asarray(per_pixel_input_tiles(coords, grid))
        for o in range(25):
            r0, c0 = (o // 5) * 4, (o % 5) * 4
            needed = np.unique(pp[r0:r0 + 4, c0:c0 + 4])
            assert B[o, needed].all()

    def test_access_histogram_totals(self):
        h = w = 10
        coords = _rand_coords(jax.random.PRNGKey(1), h, w, 9)
        hist = access_histogram(coords, h, w)
        assert int(hist.sum()) == h * w * 9 * 4


def _paper_schedule(B, m):
    """Algorithm 1 as the paper states it: each step sweeps the whole TDT
    for ``|B[o] & B[curr]|``, rebuilds the occupancy vector from the
    resident set and classifies the next tile's inputs as full bit
    vectors; the buffer is a deque FIFO. Returns (oid, iid, overlaps)."""
    n_out, n_in = B.shape
    os_mask = B.any(axis=1)
    queue, resident = deque(), set()

    def touch(t):
        if t in resident:
            return
        if len(queue) >= m:
            resident.discard(queue.popleft())
        queue.append(t)
        resident.add(t)

    first = int(np.argmax(np.where(os_mask, B.sum(axis=1), -1)))
    oid, iid, overlaps = [first], [np.flatnonzero(B[first]).tolist()], []
    for t in iid[0]:
        touch(t)
    os_mask[first] = False
    while os_mask.any():
        curr = oid[-1]
        overlap = (B & B[curr]).sum(axis=1)
        overlap[~os_mask] = -1
        nxt = int(np.argmax(overlap))
        oc = np.zeros(n_in, dtype=bool)
        oc[list(resident)] = True
        loaded = oc & B[nxt]
        last = B[curr] & B[nxt] & ~loaded
        seq = B[nxt] & ~loaded & ~last
        order = (np.flatnonzero(loaded).tolist()
                 + np.flatnonzero(seq).tolist()
                 + np.flatnonzero(last).tolist())
        oid.append(nxt)
        iid.append(order)
        overlaps.append(int((B[curr] & B[nxt]).sum()))
        for t in order:
            touch(t)
        os_mask[nxt] = False
    return oid, iid, overlaps


@functools.cache
def _reference_tdt(kind):
    """TDTs for the exactness test, one per ``kind``."""
    rng = np.random.default_rng(7)
    if kind.startswith("random"):
        n, density = {"random_sparse": (30, 0.15),
                      "random_dense": (24, 0.5)}[kind]
        return rng.random((n, n + 6)) < density
    if kind.startswith("coords"):
        # Measured tables: 3x3 DCN-II taps with ~2-px offsets on a grid
        # of 8-px tiles (224^2 -> 784 tiles, 112^2 -> 196 tiles).
        size = int(kind[len("coords"):])
        offsets = 2.0 * jax.random.normal(jax.random.PRNGKey(size),
                                          (1, size, size, 18))
        coords = offsets_to_coords(offsets, 3, "dcn2")[0]
        grid = make_square_grid(size, size, size // 8)
        return np.asarray(tdt_from_coords(coords, grid, grid))
    if kind == "all_false":
        return np.zeros((9, 9), dtype=bool)
    if kind == "empty_rows":
        B = rng.random((20, 16)) < 0.3
        B[[0, 3, 4, 11, 19]] = False
        return B
    if kind == "zero_overlap":
        # Disjoint rows: every overlap is 0, so each step falls to the
        # lowest remaining id; rows 2 and 5 have no dependencies.
        B = np.zeros((8, 12), dtype=bool)
        for o, deps in {0: [1], 1: [3, 4], 3: [0, 2], 4: [5],
                        6: [6, 7, 8], 7: [9]}.items():
            B[o, deps] = True
        return B
    raise ValueError(kind)


class TestScheduler:
    def _tdt(self, n=25, density=0.25, seed=0):
        rng = np.random.default_rng(seed)
        B = rng.random((n, n)) < density
        B[np.arange(n), np.arange(n)] = True
        return B

    def test_schedule_covers_all_tiles(self):
        B = self._tdt()
        s = schedule_tiles(B, 4)
        assert sorted(s.oid) == list(range(25))
        for o, loads in zip(s.oid, s.iid):
            assert set(loads) == set(np.flatnonzero(B[o]))

    def test_first_tile_has_most_deps(self):
        B = self._tdt(seed=3)
        s = schedule_tiles(B, 4)
        assert B[s.oid[0]].sum() == B.sum(axis=1).max()

    @pytest.mark.parametrize("m", ["1", "4", "quarter", "n_in"])
    @pytest.mark.parametrize("kind", [
        "random_sparse", "random_dense", "coords224", "coords112",
        "all_false", "empty_rows", "zero_overlap"])
    def test_matches_paper_reference_loop(self, kind, m):
        """The overlap-matrix loop gives byte-identical oid, iid and
        reuse overlaps to the paper's full-sweep procedure, with and
        without FIFO evictions."""
        B = _reference_tdt(kind)
        n_in = B.shape[1]
        m = {"quarter": max(1, n_in // 4), "n_in": n_in}.get(m) or int(m)
        s = schedule_tiles(B, m)
        assert (s.oid, s.iid, s.reuse_overlap) == _paper_schedule(B, m)

    def test_fifo_buffer(self):
        buf = FifoBuffer(2)
        assert not buf.touch(1) and not buf.touch(2)
        assert buf.touch(1)           # hit
        assert not buf.touch(3)       # evicts 1 (FIFO: 1 oldest)
        assert not buf.touch(1)       # 1 was evicted -> miss
        assert buf.loads == 4 and buf.hits == 1

    def test_schedule_is_permutation_on_random_coord_fields(self):
        """Every schedule is a permutation of the output tiles that have
        dependencies — on measured TDTs, not just synthetic matrices."""
        h = w = 24
        grid = make_square_grid(h, w, 4)
        for seed in range(4):
            coords = _rand_coords(jax.random.PRNGKey(100 + seed), h, w, 9)
            B = np.asarray(tdt_from_coords(coords, grid, grid))
            for m in (1, 4, grid.num_tiles):
                s = schedule_tiles(B, m)
                dep_rows = np.flatnonzero(B.any(axis=1)).tolist()
                assert sorted(s.oid) == dep_rows
                assert len(s.oid) == len(set(s.oid))  # no repeats
                for o, loads in zip(s.oid, s.iid):
                    assert sorted(loads) == np.flatnonzero(B[o]).tolist()

    def test_fifo_occupancy_matches_independent_model(self):
        """Replaying real schedules: every hit/miss decision and the
        resident set match an independent deque FIFO model, and occupancy
        never exceeds M (the paper's input buffer is a hard capacity)."""
        from collections import deque
        h = w = 20
        grid = make_square_grid(h, w, 5)
        coords = _rand_coords(jax.random.PRNGKey(9), h, w, 9)
        B = np.asarray(tdt_from_coords(coords, grid, grid))
        for m in (1, 2, 5):
            buf = FifoBuffer(m)
            model = deque(maxlen=m)  # append on full evicts the oldest
            for loads in schedule_tiles(B, m).iid:
                for t in loads:
                    assert buf.touch(t) == (t in model)
                    if t not in model:
                        model.append(t)
                    assert len(buf.resident) <= m
                    assert set(buf.queue) == buf.resident == set(model)

    def test_traffic_ordering_on_random_coord_fields(self):
        """Paper Fig. 16 invariant, scheduled <= bitvec <= naive, holds on
        random coordinate fields across seeds and buffer sizes."""
        h = w = 24
        grid = make_square_grid(h, w, 4)
        for seed in range(4):
            coords = _rand_coords(jax.random.PRNGKey(200 + seed), h, w, 9)
            B = np.asarray(tdt_from_coords(coords, grid, grid))
            pp = np.asarray(per_pixel_input_tiles(coords, grid))
            for buf_tiles in (2, 4, 8):
                rep = simulate_strategies(
                    B, pp, grid, channels=8, c_out=8, kernel_size=3,
                    buffer_bytes=buf_tiles * grid.tile_bytes(8))
                assert (rep["scheduled"].tile_loads
                        <= rep["bitvec"].tile_loads
                        <= rep["naive"].tile_loads)

    def test_scheduled_never_worse_than_sequential(self):
        for seed in range(5):
            B = self._tdt(seed=seed, density=0.3)
            from repro.core.scheduler import FifoBuffer as FB
            for m in (3, 6, 12):
                seq = sequential_schedule(B)
                sch = schedule_tiles(B, m)
                def replay(s):
                    buf = FB(m)
                    for loads in s.iid:
                        for t in loads:
                            buf.touch(t)
                    return buf.loads
                assert replay(sch) <= replay(seq) * 1.05  # allow tie+noise


class TestSimulator:
    def test_strategy_ordering_matches_paper(self):
        """Fig. 14/16: naive >= bitvec >= scheduled in DRAM tile loads."""
        h = w = 40
        grid = make_square_grid(h, w, 5)
        coords = _rand_coords(jax.random.PRNGKey(2), h, w, 9)
        B = np.asarray(tdt_from_coords(coords, grid, grid))
        pp = np.asarray(per_pixel_input_tiles(coords, grid))
        rep = simulate_strategies(B, pp, grid, channels=64, c_out=64,
                                  kernel_size=3, buffer_bytes=32 * 1024)
        assert rep["naive"].tile_loads >= rep["bitvec"].tile_loads
        assert rep["bitvec"].tile_loads >= rep["scheduled"].tile_loads

    def test_fusion_removes_intermediate(self):
        h = w = 20
        grid = make_square_grid(h, w, 5)
        coords = _rand_coords(jax.random.PRNGKey(3), h, w, 9)
        B = np.asarray(tdt_from_coords(coords, grid, grid))
        pp = np.asarray(per_pixel_input_tiles(coords, grid))
        kw = dict(in_grid=grid, channels=16, c_out=16, kernel_size=3,
                  buffer_bytes=8192)
        fused = simulate_strategies(B, pp, fused=True, **kw)["scheduled"]
        staged = simulate_strategies(B, pp, fused=False, **kw)["scheduled"]
        assert fused.intermediate_bytes == 0
        assert staged.intermediate_bytes == 2 * h * w * 9 * 16
        assert staged.total_dram_bytes > fused.total_dram_bytes

    def test_energy_monotone_in_traffic(self):
        h = w = 20
        grid = make_square_grid(h, w, 5)
        coords = _rand_coords(jax.random.PRNGKey(4), h, w, 9)
        B = np.asarray(tdt_from_coords(coords, grid, grid))
        pp = np.asarray(per_pixel_input_tiles(coords, grid))
        rep = simulate_strategies(B, pp, grid, 16, 16, 3, 8192)
        e = {k: dram_energy(r, exec_time_s=1e-3) for k, r in rep.items()}
        assert e["naive"] >= e["scheduled"]

    def test_dram_model_positive(self):
        m = DramEnergyModel()
        assert m.read_pj_per_byte > 0 and m.write_pj_per_byte > 0
        assert m.energy_j(1e6, 1e6, 1e-3) > 0


class TestFusionPlanner:
    def test_small_layer_fuses(self):
        plan = plan_fusion(LayerShape(h=28, w=28, c_in=64, c_out=64),
                           onchip_budget_bytes=16 * 2 ** 20)
        assert plan.mode == FusionMode.FUSED
        assert plan.dram_bytes_saved > 0

    def test_huge_layer_stages(self):
        plan = plan_fusion(LayerShape(h=512, w=512, c_in=2048, c_out=2048),
                           onchip_budget_bytes=64 * 1024)
        assert plan.mode == FusionMode.STAGED

    def test_vmem_fits_budget_when_fused(self):
        budget = 8 * 2 ** 20
        plan = plan_fusion(LayerShape(h=56, w=56, c_in=128, c_out=128),
                           onchip_budget_bytes=budget)
        if plan.mode == FusionMode.FUSED:
            assert plan.vmem_bytes <= budget
