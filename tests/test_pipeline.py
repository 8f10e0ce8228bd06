"""Oracle tests for the tile-pipeline executor (repro.runtime).

The executor — stage-1 offsets -> TDT -> Algorithm-1 schedule -> packed
tiles -> fused Pallas kernel (interpret mode on CPU) -> scatter — must be
numerically indistinguishable from the XLA reference
``core.deform.deformable_conv2d`` on real batches, including shapes that
do not divide by the tile size, and its execution trace must agree with
the DRAM-traffic simulator run on the same coordinates.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.deform import (conv2d, deformable_conv2d,
                               init_deformable_conv, offsets_to_coords,
                               randomize_offset_conv)
from repro.core.simulator import simulate_strategies
from repro.core.tiles import TileGrid, per_pixel_input_tiles, tdt_from_coords
from repro.models.dcn_models import DcnNetConfig, dcn_net_apply, init_dcn_net
from repro.runtime import GraphConfig, dcn_pipeline


def _layer(key, c_in, c_out, variant="dcn2", offset_scale=0.5,
           dtype=jnp.float32):
    """Deformable-conv params with a *non-zero* offset conv (real
    deformation, unlike the zero init) in the requested dtype."""
    params = init_deformable_conv(key, c_in, c_out, 3, variant, dtype)
    return randomize_offset_conv(params, jax.random.fold_in(key, 1),
                                 offset_scale)


class TestPipelineOracle:
    @pytest.mark.parametrize("h,w,tile,variant,dtype", [
        (16, 16, 8, "dcn2", jnp.float32),    # divisible, 2x2 grid
        (16, 16, 4, "dcn2", jnp.float32),    # smaller tiles, 4x4 grid
        (13, 13, 8, "dcn1", jnp.float32),    # non-divisible (edge tiles)
        (13, 13, 8, "dcn2", jnp.float32),    # non-divisible, dcn2
        (12, 10, 4, "dcn2", jnp.float32),    # rectangular plane
        (16, 16, 8, "dcn1", jnp.float32),    # dcn1 variant
        (16, 16, 16, "dcn2", jnp.float32),   # single tile == whole plane
        (16, 16, 8, "dcn2", jnp.bfloat16),   # bf16 features
        (13, 13, 8, "dcn2", jnp.bfloat16),   # bf16 + non-divisible
    ])
    def test_matches_xla_reference(self, h, w, tile, variant, dtype):
        key = jax.random.PRNGKey(h * 31 + w * 7 + tile)
        c_in, c_out = 6, 10
        params = _layer(key, c_in, c_out, variant, dtype=dtype)
        x = jax.random.normal(jax.random.fold_in(key, 2), (2, h, w, c_in),
                              dtype)
        y_ref = deformable_conv2d(x, params, variant=variant)
        y_pipe = dcn_pipeline(x, params, variant=variant, tile=tile,
                              interpret=True)
        assert y_pipe.shape == y_ref.shape == (2, h, w, c_out)
        assert y_pipe.dtype == x.dtype
        tol = 1e-4 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(y_pipe, np.float32),
                                   np.asarray(y_ref, np.float32),
                                   rtol=tol, atol=tol)

    def test_sequential_schedule_same_result(self):
        """Tile execution order must not change the numerics."""
        key = jax.random.PRNGKey(42)
        params = _layer(key, 4, 8)
        x = jax.random.normal(jax.random.fold_in(key, 2), (1, 13, 13, 4))
        y_alg1 = dcn_pipeline(x, params, tile=4, schedule="alg1")
        y_seq = dcn_pipeline(x, params, tile=4, schedule="sequential")
        np.testing.assert_allclose(np.asarray(y_alg1), np.asarray(y_seq),
                                   rtol=1e-6, atol=1e-6)

    def test_buffer_capacity_does_not_change_numerics(self):
        """M only reorders loads; results are capacity-independent."""
        key = jax.random.PRNGKey(7)
        params = _layer(key, 4, 6)
        x = jax.random.normal(jax.random.fold_in(key, 2), (1, 16, 16, 4))
        outs = [dcn_pipeline(x, params, tile=4, buffer_tiles=m)
                for m in (1, 3, 16)]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                       rtol=1e-6, atol=1e-6)

    def test_zero_buffer_capacity_raises(self):
        """buffer_tiles=0 must raise, not silently mean 'unlimited'."""
        key = jax.random.PRNGKey(13)
        params = _layer(key, 4, 4)
        x = jax.random.normal(jax.random.fold_in(key, 2), (1, 8, 8, 4))
        with pytest.raises(ValueError, match="capacity"):
            dcn_pipeline(x, params, tile=4, buffer_tiles=0)

    def test_max_displacement_respected(self):
        key = jax.random.PRNGKey(11)
        params = _layer(key, 4, 4, offset_scale=3.0)
        x = jax.random.normal(jax.random.fold_in(key, 2), (1, 12, 12, 4))
        y_ref = deformable_conv2d(x, params, max_displacement=1.5)
        y_pipe = dcn_pipeline(x, params, max_displacement=1.5, tile=4)
        np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)


class TestLayerTrace:
    def _run(self, h=16, w=16, tile=8, m=2, seed=0):
        key = jax.random.PRNGKey(seed)
        params = _layer(key, 4, 4, offset_scale=1.0)
        x = jax.random.normal(jax.random.fold_in(key, 2), (1, h, w, 4))
        y, trace = dcn_pipeline(x, params, tile=tile, buffer_tiles=m,
                                return_trace=True)
        offsets = conv2d(x, params.w_off, params.b_off)
        coords = offsets_to_coords(offsets.astype(jnp.float32), 3, "dcn2")
        return y, trace, coords[0], TileGrid(h, w, tile, tile)

    def test_schedule_covers_every_output_tile(self):
        _, trace, _, grid = self._run()
        im = trace.groups[0]
        executed = sorted(r.out_tile for r in im.records)
        assert executed == list(range(grid.num_tiles))

    def test_fifo_replay_matches_simulator(self):
        """The executed load sequence, replayed through the FIFO model,
        reproduces the simulator's 'scheduled' tile-load count exactly."""
        m = 2
        _, trace, coords, grid = self._run(m=m)
        B = np.asarray(tdt_from_coords(coords, grid, grid))
        pp = np.asarray(per_pixel_input_tiles(coords, grid))
        tile_bytes = grid.tile_bytes(4, 4)
        rep = simulate_strategies(B, pp, grid, channels=4, c_out=4,
                                  kernel_size=3,
                                  buffer_bytes=m * tile_bytes,
                                  dtype_bytes=4)
        (im,) = trace.groups
        assert im.fifo_replay().loads == rep["scheduled"].tile_loads
        assert im.packed_bytes == im.packed_tile_loads * tile_bytes

    def test_packed_deps_match_tdt(self):
        """Each dispatch packs exactly the TDT row of its output tile."""
        _, trace, coords, grid = self._run(h=13, w=13, tile=8)
        B = np.asarray(tdt_from_coords(coords, grid, grid))
        for r in trace.groups[0].records:
            assert (sorted(r.dep_tiles)
                    == np.flatnonzero(B[r.out_tile]).tolist())


class TestPipelineModelBackend:
    def test_pipeline_backend_matches_xla(self):
        cfg = DcnNetConfig(name="vgg19", n_deform=2, img_size=16,
                           width_mult=0.125, num_classes=4)
        p = init_dcn_net(jax.random.PRNGKey(2), cfg)
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 16, 3))
        y_xla = dcn_net_apply(p, cfg, x, backend="xla", fused=False)
        y_pipe = dcn_net_apply(p, cfg, x, backend="pipeline",
                               pipeline=GraphConfig(tile=2))
        np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_xla),
                                   rtol=5e-3, atol=5e-3)

    def test_unknown_backend_raises(self):
        cfg = DcnNetConfig(name="vgg19", n_deform=1, img_size=16,
                           width_mult=0.125, num_classes=4)
        p = init_dcn_net(jax.random.PRNGKey(0), cfg)
        x = jnp.zeros((1, 16, 16, 3))
        with pytest.raises(ValueError, match="backend"):
            dcn_net_apply(p, cfg, x, backend="tpu-v9")
