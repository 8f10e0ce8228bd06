"""Compile the serving path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler that ships with jax compiles for a
``v5e:2x2`` topology that is described, not attached, so a kernel the
Mosaic lowering would refuse (unaligned blocks, lane-changing reshapes,
VMEM overflow) fails here instead of on the chip. Nothing runs, so these
tests say nothing about results; the interpret-mode tests pin those.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and the test workers all import this file.
"""

import contextlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core.deform import DeformableConvParams
from repro.core.tiles import TileGrid
from repro.kernels.dcn_fused import (_dcn_fused_batch_jit,
                                     _dcn_fused_batch_sharded_jit)
from repro.kernels.dcn_schedule import (greedy_schedule_arrays,
                                        tdt_from_coords_device)
from repro.runtime.fused_exec import (_group_lead_program,
                                      _group_post_program,
                                      _group_prepass_program)
from repro.runtime.graph import ConvNode, DeformNode

TILE = 8
TP = TILE * TILE          # pixels per tile
KK = 9                    # 3x3 taps
C_OUT = 512
T_IN = 16                 # a 28x28 plane in 8x8 tiles
K_PAD = 16


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2; the persistent compilation cache is off
    meanwhile (a described chip's entry cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, precision=None, **static):
    ctx = (jax.default_matmul_precision(precision) if precision
           else contextlib.nullcontext())
    with ctx:
        text = fn.lower(*args, **static).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def _fused_operands(s, rows, c_in):
    """idx/coeff/w/b operands of the fused kernels, ``rows`` tiles."""
    return (_shape(s, (rows, TP, KK, 4), jnp.int32),
            _shape(s, (rows, TP, KK, 4), jnp.float32),
            _shape(s, (KK, c_in, C_OUT), jnp.float32),
            _shape(s, (C_OUT,), jnp.float32))


@pytest.mark.parametrize("precision", [None, "highest"])
@pytest.mark.parametrize("c_in", [3, 64, 256, 512])
class TestFusedKernels:
    """C_in not a multiple of 128 used to fail Mosaic's shape cast."""

    @pytest.mark.parametrize("n", [1, 4])
    def test_batch_kernel(self, one_chip, c_in, precision, n):
        """Width 1 is the serving engine's degraded step."""
        s = one_chip
        _compile(_dcn_fused_batch_jit,
                 _shape(s, (n * T_IN, TP, c_in), jnp.float32),
                 _shape(s, (n * T_IN,), jnp.int32),
                 _shape(s, (n * T_IN, K_PAD), jnp.int32),
                 _shape(s, (n * T_IN,), jnp.int32),
                 *_fused_operands(s, n * T_IN, c_in),
                 precision=precision, t_in=T_IN, kernel_size=3,
                 block_p=128, interpret=False)


@pytest.mark.parametrize("plane", [28, 224])
def test_tdt_kernel(one_chip, plane):
    grid = TileGrid(plane, plane, TILE, TILE)
    _compile(tdt_from_coords_device,
             _shape(one_chip, (plane, plane, KK, 2), jnp.float32),
             in_grid=grid, out_grid=grid, interpret=False)


@pytest.mark.parametrize("n", [16, 784])
def test_greedy_kernel(one_chip, n):
    _compile(greedy_schedule_arrays,
             _shape(one_chip, (n, n), jnp.bool_), m=n, interpret=False)


def test_sharded_batch_kernel(topo):
    """The shard_map'd batch kernel over four described chips."""
    d, c_in = 4, 256
    mesh = Mesh(topo.devices, ("data",))
    split = NamedSharding(mesh, PartitionSpec("data"))
    full = NamedSharding(mesh, PartitionSpec())
    rows = 2 * T_IN
    _compile(_dcn_fused_batch_sharded_jit,
             _shape(split, (d, rows, TP, c_in), jnp.float32),
             _shape(split, (d, rows), jnp.int32),
             _shape(split, (d, rows, K_PAD), jnp.int32),
             _shape(split, (d, rows), jnp.int32),
             _shape(split, (d, rows, TP, KK, 4), jnp.int32),
             _shape(split, (d, rows, TP, KK, 4), jnp.float32),
             _shape(full, (KK, c_in, C_OUT), jnp.float32),
             _shape(full, (C_OUT,), jnp.float32),
             mesh=mesh, axis="data", t_in=T_IN, kernel_size=3,
             block_p=128, interpret=False)


@pytest.mark.parametrize("plane,c_in,advance", [(28, 256, True),
                                                (14, 512, False)])
def test_group_prepass_program(one_chip, plane, c_in, advance):
    """The compiled prepass of a deformable group at VGG19-8's widths,
    batch 4: offset conv, coordinates, floors, TDTs, plane-order
    operands and (for a group a later deformable layer reads) the dense
    advance. An XLA program: it holds no Pallas kernel."""
    s = one_chip
    f32 = jnp.float32
    params = [DeformableConvParams(_shape(s, (3, 3, c_in, 2 * KK), f32),
                                   _shape(s, (2 * KK,), f32),
                                   _shape(s, (3, 3, c_in, C_OUT), f32),
                                   _shape(s, (C_OUT,), f32))]
    with jax.default_matmul_precision("highest"):
        _group_prepass_program.lower(
            _shape(s, (4, plane, plane, c_in), f32), params,
            nodes=(DeformNode(0, c_in, C_OUT, plane, plane),),
            grid=TileGrid(plane, plane, TILE, TILE), p_pad=TP,
            needs_plane=(advance,), max_displacement=None).compile()


def test_batch_kernel_at_segnet_224(one_chip):
    """SegNet-8's 224² layer at batch 8: 6,272 grid rows. Its tables
    (32 dep slots after narrowing) outgrow SMEM in one call, so the
    program holds four equal calls of the kernel."""
    s = one_chip
    rows, t_in, c = 8 * 784, 784, 64
    text = _compile(_dcn_fused_batch_jit,
                    _shape(s, (rows, TP, c), jnp.float32),
                    _shape(s, (rows,), jnp.int32),
                    _shape(s, (rows, 32), jnp.int32),
                    _shape(s, (rows,), jnp.int32),
                    _shape(s, (rows, TP, KK, 4), jnp.int32),
                    _shape(s, (rows, TP, KK, 4), jnp.float32),
                    _shape(s, (KK, c, c), jnp.float32),
                    _shape(s, (c,), jnp.float32),
                    precision="highest", t_in=t_in, kernel_size=3,
                    block_p=128, interpret=False)
    assert text.count("_dcn_fused_batch_jit.") >= 4


def test_group_prepass_program_at_segnet_224(one_chip):
    """The compiled prepass of SegNet-8's last group (224², 64 -> 64,
    784 tiles, batch 8): its TDTs are 8 x 784 x 784."""
    s = one_chip
    f32 = jnp.float32
    params = [DeformableConvParams(_shape(s, (3, 3, 64, 2 * KK), f32),
                                   _shape(s, (2 * KK,), f32),
                                   _shape(s, (3, 3, 64, 64), f32),
                                   _shape(s, (64,), f32))]
    with jax.default_matmul_precision("highest"):
        _group_prepass_program.lower(
            _shape(s, (8, 224, 224, 64), f32), params,
            nodes=(DeformNode(0, 64, 64, 224, 224),),
            grid=TileGrid(224, 224, TILE, TILE), p_pad=TP,
            needs_plane=(False,), max_displacement=None).compile()


@pytest.mark.parametrize("n,c_in,tiled", [(4, 64, False), (8, 64, True)])
def test_group_lead_program_at_224(one_chip, n, c_in, tiled):
    """A conv-only group's whole execute at 224² (64 -> 64): one conv
    and its ReLU on the plane. With a grid and no conv before the
    group's first DCN layer, the plane as SegNet-8's 224² kernel rows
    (8 x 784 tiles)."""
    s = one_chip
    f32 = jnp.float32
    nodes, params = (), []
    if not tiled:
        nodes = (ConvNode(0, c_in, 64, 224, 224),)
        params = [{"w": _shape(s, (3, 3, c_in, 64), f32),
                   "b": _shape(s, (64,), f32)}]
    with jax.default_matmul_precision("highest"):
        _group_lead_program.lower(
            _shape(s, (n, 224, 224, c_in), f32), params, nodes=nodes,
            grid=TileGrid(224, 224, TILE, TILE) if tiled else None
        ).compile()


@pytest.mark.parametrize("n,plane,c_out", [(4, 28, 512), (8, 224, 64)])
def test_group_post_program(one_chip, n, plane, c_out):
    """What follows a group's last DCN layer: ReLU, the tile-valid
    masks as a constant, the scatter of the scheduled rows and the
    untile, at VGG19-8's 28² layer (C 512, batch 4) and SegNet-8's 224²
    layer (8 x 784 rows scattered)."""
    s = one_chip
    grid = TileGrid(plane, plane, TILE, TILE)
    rows = n * grid.num_tiles
    _group_post_program.lower(
        _shape(s, (rows, TP, c_out), jnp.float32),
        _shape(s, (rows,), jnp.int32), _shape(s, (rows,), jnp.int32), [],
        nodes=(), grid=grid, n=n, relu=True, scatter=True,
        to_rows=False).compile()
