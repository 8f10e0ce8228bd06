"""Edge-case tests for runtime/packing.py and the schedule cache.

Packing rewrites global sampling coordinates into packed-buffer addresses;
the cases that historically break such address converters are coordinates
clamped at image borders, rectangular (th != tw) tiles, and offset planes
that push every sample out of range. Each case is oracle-checked against
the XLA reference through the full pipeline, plus direct table-level
invariants.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.deform import (deformable_conv2d, init_deformable_conv,
                               offsets_to_coords, randomize_offset_conv)
from repro.core.tiles import TileGrid
from repro.runtime import dcn_pipeline, default_schedule_cache
from repro.runtime.cache import ScheduleCache, coords_digest
from repro.runtime.packing import (build_neighbour_tables, pack_output_tile,
                                   plane_to_tiles, tiles_to_plane)


def _layer(key, c_in, c_out, offset_scale=0.5):
    params = init_deformable_conv(key, c_in, c_out, 3, "dcn2")
    return randomize_offset_conv(params, jax.random.fold_in(key, 1),
                                 offset_scale)


class TestPackingEdgeCases:
    def test_coords_clamped_at_borders(self):
        """Large offsets drive many samples onto the clamp boundary; the
        pipeline must still match the reference exactly."""
        key = jax.random.PRNGKey(0)
        params = _layer(key, 4, 6, offset_scale=5.0)   # wild offsets
        x = jax.random.normal(jax.random.fold_in(key, 2), (1, 12, 12, 4))
        y_ref = deformable_conv2d(x, params)
        y = dcn_pipeline(x, params, tile=4)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("h,w,tile", [
        (12, 10, (3, 5)),     # rectangular tiles, divisible
        (13, 11, (3, 5)),     # rectangular tiles, non-divisible both axes
        (9, 16, (2, 8)),      # extreme aspect ratio
    ])
    def test_rectangular_tiles(self, h, w, tile):
        key = jax.random.PRNGKey(h * 17 + w)
        params = _layer(key, 5, 7)
        x = jax.random.normal(jax.random.fold_in(key, 2), (2, h, w, 5))
        y_ref = deformable_conv2d(x, params)
        y = dcn_pipeline(x, params, tile=tile)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_all_out_of_range_offset_plane(self):
        """Huge constant offset bias: every sampling coordinate clamps to
        the far image border — one input tile serves the whole plane."""
        key = jax.random.PRNGKey(3)
        params = init_deformable_conv(key, 4, 4, 3, "dcn2")
        params = params._replace(
            b_off=jnp.full(params.b_off.shape, 100.0))     # way out of range
        x = jax.random.normal(jax.random.fold_in(key, 2), (1, 12, 12, 4))
        y_ref = deformable_conv2d(x, params)
        y = dcn_pipeline(x, params, tile=4)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        # the clamped coordinates all decode into the bottom-right tile
        offsets = jnp.zeros((1, 12, 12, 2 * 9)) + 100.0
        coords = offsets_to_coords(offsets, 3, "dcn2")[0]
        grid = TileGrid(12, 12, 4, 4)
        nb = build_neighbour_tables(coords, grid)
        assert set(np.unique(nb.tile_id)) == {grid.num_tiles - 1}

    def test_neighbour_tables_always_in_range(self):
        key = jax.random.PRNGKey(4)
        params = _layer(key, 3, 3, offset_scale=8.0)
        x = jax.random.normal(jax.random.fold_in(key, 2), (1, 13, 11, 3))
        from repro.core.deform import conv2d
        offsets = conv2d(x, params.w_off, params.b_off)
        coords = offsets_to_coords(offsets.astype(jnp.float32), 3, "dcn2")[0]
        grid = TileGrid(13, 11, 3, 5)
        nb = build_neighbour_tables(coords, grid)
        assert nb.tile_id.min() >= 0
        assert nb.tile_id.max() < grid.num_tiles
        assert nb.offset.min() >= 0
        assert nb.offset.max() < grid.th * grid.tw

    def test_pack_padded_pixels_have_zero_coeff(self):
        """Output tiles overhanging the plane pack coeff=0 for the padded
        pixels, so their contribution is discarded."""
        h, w = 5, 5
        grid = TileGrid(h, w, 4, 4)    # 2x2 grid, heavy overhang
        coords = offsets_to_coords(jnp.zeros((1, h, w, 18)), 3, "dcn2")[0]
        nb = build_neighbour_tables(coords, grid)
        deps = list(range(grid.num_tiles))
        idx, coeff = pack_output_tile(nb, grid, grid.num_tiles - 1, deps,
                                      p_pad=16)
        tp = grid.th * grid.tw
        valid = np.zeros((grid.th, grid.tw), bool)
        valid[:h - 4, :w - 4] = True    # only 1x1 of the last tile is real
        flat = valid.reshape(-1)
        assert idx.shape == (16, 9, 4) and coeff.shape == (16, 9, 4)
        assert np.all(coeff[:tp][~flat] == 0)      # plane-overhang pixels
        assert np.any(coeff[:tp][flat] != 0)       # the real pixel samples

    def test_plane_tiles_roundtrip_rectangular(self):
        x = jnp.arange(13 * 11 * 3, dtype=jnp.float32).reshape(13, 11, 3)
        grid = TileGrid(13, 11, 3, 5)
        np.testing.assert_array_equal(
            np.asarray(tiles_to_plane(plane_to_tiles(x, grid), grid, 13, 11)),
            np.asarray(x))


class TestScheduleCache:
    def test_repeated_input_hits(self):
        """Same batch twice: the second run's schedules all come from the
        LRU cache, and the trace counters surface it."""
        key = jax.random.PRNGKey(7)
        params = _layer(key, 4, 4)
        x = jax.random.normal(jax.random.fold_in(key, 2), (2, 12, 12, 4))
        default_schedule_cache().clear()
        y1, t1 = dcn_pipeline(x, params, tile=4, return_trace=True)
        assert t1.schedule_cache_hits == 0
        assert t1.schedule_cache_misses == 2
        y2, t2 = dcn_pipeline(x, params, tile=4, return_trace=True)
        assert t2.schedule_cache_hits == 2
        assert t2.schedule_cache_misses == 0
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=0, atol=0)

    def test_cache_disabled(self):
        from repro.runtime import GraphConfig
        key = jax.random.PRNGKey(8)
        params = _layer(key, 4, 4)
        x = jax.random.normal(jax.random.fold_in(key, 2), (1, 8, 8, 4))
        _, tr_off = dcn_pipeline(
            x, params, return_trace=True,
            config=GraphConfig(tile=4, use_schedule_cache=False))
        assert tr_off.schedule_cache_hits == 0
        assert tr_off.schedule_cache_misses == 0

    def test_digest_distinguishes_floor_changes(self):
        grid = TileGrid(8, 8, 4, 4)
        base = np.full((8, 8, 9, 2), 3.4)
        shifted = base + 0.2           # same cell
        crossed = base + 0.7           # floor flips 3 -> 4
        assert coords_digest(base, grid) == coords_digest(shifted, grid)
        assert coords_digest(base, grid) != coords_digest(crossed, grid)

    def test_lru_eviction(self):
        c = ScheduleCache(maxsize=2)
        c.put("a", 1)
        c.put("b", 2)
        assert c.get("a") == 1         # refresh "a": "b" is now oldest
        c.put("c", 3)
        assert c.get("b") is None
        assert c.get("a") == 1 and c.get("c") == 3
        info = c.info()
        assert info["size"] == 2 and info["maxsize"] == 2

    def test_different_buffer_capacity_misses(self):
        """M is part of the key: capacity changes rebuild the schedule."""
        key = jax.random.PRNGKey(9)
        params = _layer(key, 4, 4)
        x = jax.random.normal(jax.random.fold_in(key, 2), (1, 8, 8, 4))
        default_schedule_cache().clear()
        _, t1 = dcn_pipeline(x, params, tile=4, buffer_tiles=2,
                             return_trace=True)
        _, t2 = dcn_pipeline(x, params, tile=4, buffer_tiles=3,
                             return_trace=True)
        assert t1.schedule_cache_misses == 1
        assert t2.schedule_cache_misses == 1
