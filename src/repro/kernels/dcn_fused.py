"""Pallas TPU kernel: fused BLI (+) main-conv for one output tile (§IV-D).

The deformed-feature tensor is K*K x the size of the input feature map —
the paper's fusion keeps it on-chip. Here the fused kernel materializes the
deformed patch matrix (bp, KK*C_in) **only in VMEM/VREGs** and immediately
contracts it with the main-conv weights:

    deformed (KK*bp, C)  = 4-hot(idx, coeff) (KK*bp, S) @ x_tile (S, C)
    patches  (bp, KK*C)  = concat_t deformed[t*bp:(t+1)*bp]  (lane axis)
    out      (bp, O)     = patches @ w (KK*C, O) + b

Two chained MXU matmuls per block; HBM traffic is x_tile + indices +
weights + out — the deformed intermediate never leaves the core. This is
the TPU-native form of the paper's Fig. 18 fusion. The deformed rows are
tap-major, so the patch matrix is a lane concatenation of row slices:
a ``(bp*KK, C) -> (bp, KK*C)`` reshape would move sublanes into lanes,
which Mosaic cannot lower unless C is a multiple of 128.

Two entry points: ``dcn_fused_tile`` computes ONE output tile per call
(the per-tile dispatch loop), ``dcn_fused_batch`` runs the concatenated
Algorithm-1 tile schedules of a whole batch as a single ``pallas_call``
grid — the scheduled-tile index is the leading grid dimension and a
scalar-prefetched dep table drives the input-tile DMA sequence, so the
scheduled tiles stream back-to-back through the core with no per-tile
host dispatch (the paper's §IV-C execution model).
``dcn_fused_batch_sharded`` runs that grid per device of a mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec

from repro.obs import get_tracer


def _fused_kernel(idx_ref, coeff_ref, x_ref, w_ref, b_ref, o_ref,
                  *, s_pixels: int, kk: int):
    """One bp-pixel output block, full C_out.

    idx_ref:   (KK*bp, 4) int32, tap-major (see :func:`_tap_major`)
    coeff_ref: (KK*bp, 4) f32
    x_ref:     (S, C)
    w_ref:     (KK*C, O)
    b_ref:     (1, O)
    o_ref:     (bp, O)
    """
    idx = idx_ref[...]
    coeff = coeff_ref[...].astype(jnp.float32)
    rows = idx.shape[0]                      # KK * bp

    cols = jax.lax.broadcasted_iota(jnp.int32, (rows, s_pixels), 1)
    w_bli = jnp.zeros((rows, s_pixels), jnp.float32)
    for j in range(4):
        onehot = (cols == idx[:, j:j + 1]).astype(jnp.float32)
        w_bli = w_bli + onehot * coeff[:, j:j + 1]

    x = x_ref[...].astype(jnp.float32)       # (S, C)
    deformed = jnp.dot(w_bli, x, preferred_element_type=jnp.float32)
    o_ref[...] = _contract_taps(deformed, w_ref, b_ref,
                                kk).astype(o_ref.dtype)


def _contract_taps(deformed, w_ref, b_ref, kk: int):
    """Main conv over a tap-major deformed block of (KK*bp, C) rows, tap
    ``t`` owning rows ``[t*bp, (t+1)*bp)``: their lane concatenation is
    the (bp, KK*C) patch matrix, contracted with ``w`` (KK*C, O) in one
    matmul (see the module docstring)."""
    bp = deformed.shape[0] // kk
    patches = jnp.concatenate(
        [deformed[t * bp:(t + 1) * bp] for t in range(kk)], axis=1)
    w = w_ref[...].astype(jnp.float32)       # (KK*C, O)
    acc = jnp.dot(patches, w, preferred_element_type=jnp.float32)
    return acc + b_ref[...].astype(jnp.float32)


def _tap_major(a: jax.Array, bp: int) -> jax.Array:
    """(..., P, KK, 4) pixel-major operand -> (..., P*KK, 4) rows ordered
    (pixel block, tap, pixel): each ``bp``-pixel block's ``KK*bp`` rows
    are tap-major, as :func:`_contract_taps` reads them."""
    *lead, p, kk, four = a.shape
    a = a.reshape(*lead, p // bp, bp, kk, four)
    a = jnp.swapaxes(a, -3, -2)
    return a.reshape(*lead, p * kk, four)


@functools.partial(jax.jit,
                   static_argnames=("kernel_size", "block_p", "interpret"))
def _dcn_fused_tile_jit(
    x_tile: jax.Array,   # (S, C_in) flattened halo tile
    idx: jax.Array,      # (P, KK, 4) int32 flat neighbour indices
    coeff: jax.Array,    # (P, KK, 4) float BLI coefficients
    w: jax.Array,        # (KK, C_in, C_out) main conv weights
    b: jax.Array,        # (C_out,)
    *,
    kernel_size: int = 3,
    block_p: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fused Eq.2+3 on one tile -> (P, C_out)."""
    s, c = x_tile.shape
    p, kk, _ = idx.shape
    o = w.shape[-1]
    assert kk == kernel_size * kernel_size, (kk, kernel_size)
    bp = min(block_p, p)
    if p % bp:
        raise ValueError(f"P={p} must tile by {bp}; pad upstream")

    idx2 = _tap_major(idx, bp)
    coeff2 = _tap_major(coeff, bp)
    w2 = w.reshape(kk * c, o)
    b2 = b.reshape(1, o)

    return pl.pallas_call(
        functools.partial(_fused_kernel, s_pixels=s, kk=kk),
        grid=(p // bp,),
        in_specs=[
            pl.BlockSpec((bp * kk, 4), lambda i: (i, 0)),
            pl.BlockSpec((bp * kk, 4), lambda i: (i, 0)),
            pl.BlockSpec((s, c), lambda i: (0, 0)),
            pl.BlockSpec((kk * c, o), lambda i: (0, 0)),
            pl.BlockSpec((1, o), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bp, o), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((p, o), x_tile.dtype),
        interpret=interpret,
    )(idx2, coeff2, x_tile, w2, b2)


# ---------------------------------------------------------------------------
# Batch-fused dispatch: ONE pallas_call for the schedules of a whole batch.
# ---------------------------------------------------------------------------

# SMEM bytes one batch-fused call may give its scalar-prefetched tables
# (row ids, dep table, dep counts); a TPU v5e core has 1 MiB of SMEM.
BATCH_PREFETCH_SMEM_BYTES = 256 * 1024


def batch_grid_chunk(g: int, k_pad: int) -> int:
    """Grid rows per batch-fused call: all ``g`` when the tables of ``g``
    rows fit :data:`BATCH_PREFETCH_SMEM_BYTES`, else the fewest equal
    chunks that do (224² SegNet layers at batch 8: 6,272 rows)."""
    fit = max(1, BATCH_PREFETCH_SMEM_BYTES // (4 * (k_pad + 2)))
    calls = -(-g // fit)
    return -(-g // calls)


def _batch_kernel(row_ref, dep_ref, cnt_ref, idx_ref, coeff_ref, x_ref,
                  w_ref, b_ref, o_ref, acc_ref,
                  *, tp: int, kk: int, k_pad: int, t_in: int):
    """One (batch-grid row, pixel block, dep slot) step.

    row_ref:   (G,) int32 scalar prefetch — per grid row, the flat
               ``img * T_out + out_tile`` row of the idx/coeff operands
               (clamped on padded rows; consumed by the BlockSpecs).
    dep_ref:   (G, k_pad) int32 scalar prefetch — GLOBAL dep tile ids
               ``img * T_in + dep``; rows beyond an image's schedule
               length are pre-filled with the image's last real dep so
               the clamped x index map repeats the block and the DMA is
               elided across image boundaries.
    cnt_ref:   (G,) int32 true dep count; 0 marks a ragged-padding row,
               whose compute is skipped entirely.
    idx_ref:   (1, KK*bp, 4) int32 plane-global packed addresses
               ``tile_id * tp + offset`` (schedule-independent: packed
               once per image in plane order), tap-major.
    x_ref:     (1, tp, C) — input tile ``dep[g, k]`` of image ``img``.
    acc_ref:   (KK*bp, C) f32 VMEM scratch.

    The BLI contraction is decomposed over dep slots: slot k's partial
    4-hot matmul sees only the one input tile the grid just fetched.
    idx is global to the image's tile array, so the slot localises it
    against that tile (``idx - dep * tp``); addresses outside it match
    no column. The deformed patch matrix never leaves VMEM (same §IV-D
    fusion as the per-tile kernel).
    """
    g = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k < cnt_ref[g])
    def _accumulate():
        idx = idx_ref[0]
        coeff = coeff_ref[0].astype(jnp.float32)
        rows = idx.shape[0]                  # KK * bp
        dep_local = dep_ref[g, k] % t_in     # image-local dep tile id
        local = idx - dep_local * tp         # in [0, tp) iff in this tile
        cols = jax.lax.broadcasted_iota(jnp.int32, (rows, tp), 1)
        w_bli = jnp.zeros((rows, tp), jnp.float32)
        for j in range(4):
            onehot = (cols == local[:, j:j + 1]).astype(jnp.float32)
            w_bli = w_bli + onehot * coeff[:, j:j + 1]
        x = x_ref[0].astype(jnp.float32)     # (tp, C)
        acc_ref[...] += jnp.dot(w_bli, x,
                                preferred_element_type=jnp.float32)

    @pl.when(k == k_pad - 1)
    def _flush():
        o_ref[0] = _contract_taps(acc_ref[...], w_ref, b_ref,
                                  kk).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("t_in", "kernel_size", "block_p",
                                    "interpret"))
def _dcn_fused_batch_jit(
    x_tiles: jax.Array,   # (N*T_in, tp, C_in) every image's input tiles
    row_id: jax.Array,    # (G,) int32 img*T_out + out_tile (clamped)
    dep_glb: jax.Array,   # (G, k_pad) int32 img*T_in + dep, load order
    dep_cnt: jax.Array,   # (G,) int32 true dep count (0 = padded row)
    idx: jax.Array,       # (N*T_out, P, KK, 4) int32 plane-global addrs
    coeff: jax.Array,     # (N*T_out, P, KK, 4) float BLI coefficients
    w: jax.Array,         # (KK, C_in, C_out) shared main conv weights
    b: jax.Array,         # (C_out,)
    *,
    t_in: int,
    kernel_size: int = 3,
    block_p: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fused Eq.2+3 over the concatenated schedules of a WHOLE BATCH ->
    (G, P, C_out), one row per batch-grid slot.

    The grid form of :func:`dcn_fused_tile`: all N images' Algorithm-1
    schedules are concatenated (ragged-padded per image) into one
    leading grid dimension, so a layer segment costs ONE kernel dispatch
    per batch instead of one per scheduled tile. Weights are shared
    across the grid; the per-image tile arrays are addressed through the
    scalar-prefetched global ids (``img * T_in + dep``), and ragged
    padding rows (``dep_cnt == 0``) skip compute with their DMAs elided
    by the clamped index map. The caller scatters valid rows back by
    ``row_id``.
    """
    nt_in, tp, c = x_tiles.shape
    g_rows, p, kk, _ = idx.shape
    k_pad = dep_glb.shape[1]
    o = w.shape[-1]
    assert kk == kernel_size * kernel_size, (kk, kernel_size)
    bp = min(block_p, p)
    if p % bp:
        raise ValueError(f"P={p} must tile by {bp}; pad upstream")
    if nt_in % t_in:
        raise ValueError(f"x_tiles rows {nt_in} not a multiple of "
                         f"t_in={t_in}")
    g = row_id.shape[0]
    if g == 0:          # empty batch grid: nothing to dispatch
        return jnp.zeros((0, p, o), x_tiles.dtype)

    idx2 = _tap_major(idx, bp)
    coeff2 = _tap_major(coeff, bp)
    w2 = w.reshape(kk * c, o)
    b2 = b.reshape(1, o)

    def x_index(gi, j, k, row, dep, cnt):
        # Clamp padding slots to the last real dep (pre-filled across
        # whole padded rows): consecutive padding steps repeat the block
        # index, so no DMA is issued for them.
        return (dep[gi, jnp.minimum(k, jnp.maximum(cnt[gi] - 1, 0))], 0, 0)

    def call(row, dep, cnt):
        rows = row.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows, p // bp, k_pad),
            in_specs=[
                pl.BlockSpec((1, bp * kk, 4),
                             lambda gi, j, k, row, dep, cnt: (row[gi], j, 0)),
                pl.BlockSpec((1, bp * kk, 4),
                             lambda gi, j, k, row, dep, cnt: (row[gi], j, 0)),
                pl.BlockSpec((1, tp, c), x_index),
                pl.BlockSpec((kk * c, o),
                             lambda gi, j, k, row, dep, cnt: (0, 0)),
                pl.BlockSpec((1, o), lambda gi, j, k, row, dep, cnt: (0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, bp, o), lambda gi, j, k, row, dep, cnt: (gi, j, 0)),
            scratch_shapes=[pltpu.VMEM((kk * bp, c), jnp.float32)],
        )
        return pl.pallas_call(
            functools.partial(_batch_kernel, tp=tp, kk=kk, k_pad=k_pad,
                              t_in=t_in),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, p, o), x_tiles.dtype),
            interpret=interpret,
        )(row, dep, cnt, idx2, coeff2, x_tiles, w2, b2)

    # The three scalar-prefetched tables live in SMEM for the whole call,
    # so a grid too long for it runs as several calls of equal length;
    # the last is padded with dep_cnt == 0 rows, whose outputs are cut.
    chunk = batch_grid_chunk(g, k_pad)
    if chunk == g:
        return call(row_id, dep_glb, dep_cnt)
    pad = -g % chunk
    row_id = jnp.pad(row_id, (0, pad))
    dep_glb = jnp.pad(dep_glb, ((0, pad), (0, 0)))
    dep_cnt = jnp.pad(dep_cnt, (0, pad))
    return jnp.concatenate(
        [call(row_id[a:a + chunk], dep_glb[a:a + chunk],
              dep_cnt[a:a + chunk]) for a in range(0, g + pad, chunk)])[:g]


# ---------------------------------------------------------------------------
# Sharded batch-fused dispatch: the batch grid above, SPMD over a device
# mesh's "data" axis. Each device runs the concatenated Algorithm-1
# schedules of its LOCAL images only — the paper's replicated-lane
# scaling unit — with zero collective contact inside the kernel (the
# executor all-gathers once, at the logits).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis", "t_in", "kernel_size",
                                    "block_p", "interpret"))
def _dcn_fused_batch_sharded_jit(
    x_tiles: jax.Array,   # (D, N_loc*T_in, tp, C_in) per-shard tiles
    row_id: jax.Array,    # (D, G_loc) int32 shard-LOCAL idx/coeff rows
    dep_glb: jax.Array,   # (D, G_loc, k_pad) int32 shard-LOCAL dep ids
    dep_cnt: jax.Array,   # (D, G_loc) int32 true dep count (0 = padded)
    idx: jax.Array,       # (D, N_loc*T_out, P, KK, 4) int32
    coeff: jax.Array,     # (D, N_loc*T_out, P, KK, 4) float
    w: jax.Array,         # (KK, C_in, C_out) replicated weights
    b: jax.Array,         # (C_out,) replicated bias
    *,
    mesh,
    axis: str,
    t_in: int,
    kernel_size: int,
    block_p: int,
    interpret: bool,
) -> jax.Array:
    """Per-device :func:`_dcn_fused_batch_jit` over mesh axis ``axis`` ->
    (D, G_loc, P, C_out), shard ``s`` computed entirely on device ``s``.

    Every operand except the weights carries a leading shard axis of
    size ``D == mesh.shape[axis]``; ``shard_map`` hands each device its
    own slab (leading dim 1), which runs the ordinary batch-fused grid
    over its local images. G_loc / k_pad are the max over shards, but
    the dep tables are packed PER SHARD: a shard with shorter schedules
    keeps its own ragged padding (``dep_cnt == 0`` rows skip compute and
    their DMAs are elided by the clamped index map), so one slow replica
    never inflates another's real work.
    """
    spec = PartitionSpec(axis)

    def body(xt, row, dep, cnt, ix, cf, wl, bl):
        y = _dcn_fused_batch_jit(xt[0], row[0], dep[0], cnt[0], ix[0],
                                 cf[0], wl, bl, t_in=t_in,
                                 kernel_size=kernel_size,
                                 block_p=block_p, interpret=interpret)
        return y[None]

    # check_vma=False: the body has no collective, and the pallas_call
    # output carries no varying-axes annotation for the checker to read.
    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(spec,) * 6 + (PartitionSpec(),
                                              PartitionSpec()),
                      out_specs=spec, check_vma=False)
    return f(x_tiles, row_id, dep_glb, dep_cnt, idx, coeff, w, b)


# ---------------------------------------------------------------------------
# Public dispatch wrappers: the jitted kernels above, plus a telemetry
# span per host dispatch. Spans cannot live INSIDE the jitted functions
# (they would fire once at trace time, not per call), so each entry
# point is a thin host wrapper that opens ``dispatch.<mode>`` on the
# current ``repro.obs`` tracer. Dispatch is asynchronous: the span times
# the host's enqueue of the call (and its compile on a first call), not
# the kernel's run on the device, which a profiler trace shows. Disabled
# tracer = one extra attribute check per dispatch; calls from inside
# jit/vmap traces (``x`` is a JAX tracer) skip the span entirely.
# ---------------------------------------------------------------------------


def _span_dispatch(name: str, x, **attrs):
    tr = get_tracer()
    if not tr.enabled or isinstance(x, jax.core.Tracer):
        return None
    return tr.span(name, **attrs)


def dcn_fused_tile(x_tile, idx, coeff, w, b, *, kernel_size: int = 3,
                   block_p: int = 128, interpret: bool = False):
    """Fused Eq.2+3 on one tile -> (P, C_out) (see module docstring)."""
    sp = _span_dispatch("dispatch.per_tile", x_tile,
                        pixels=int(idx.shape[0]), c_out=int(w.shape[-1]))
    if sp is None:
        return _dcn_fused_tile_jit(x_tile, idx, coeff, w, b,
                                   kernel_size=kernel_size,
                                   block_p=block_p, interpret=interpret)
    with sp:
        return _dcn_fused_tile_jit(x_tile, idx, coeff, w, b,
                                   kernel_size=kernel_size,
                                   block_p=block_p, interpret=interpret)


def dcn_fused_batch(x_tiles, row_id, dep_glb, dep_cnt, idx, coeff, w, b,
                    *, t_in: int, kernel_size: int = 3,
                    block_p: int = 128, interpret: bool = False):
    """Fused Eq.2+3 over a whole batch's schedules -> (G, P, C_out)."""
    sp = _span_dispatch("dispatch.batch_fused", x_tiles,
                        grid_rows=int(row_id.shape[0]),
                        c_out=int(w.shape[-1]))
    if sp is None:
        return _dcn_fused_batch_jit(x_tiles, row_id, dep_glb, dep_cnt,
                                    idx, coeff, w, b, t_in=t_in,
                                    kernel_size=kernel_size,
                                    block_p=block_p, interpret=interpret)
    with sp:
        return _dcn_fused_batch_jit(x_tiles, row_id, dep_glb, dep_cnt,
                                    idx, coeff, w, b, t_in=t_in,
                                    kernel_size=kernel_size,
                                    block_p=block_p, interpret=interpret)


def dcn_fused_batch_sharded(x_tiles, row_id, dep_glb, dep_cnt, idx, coeff,
                            w, b, *, mesh, axis: str = "data", t_in: int,
                            kernel_size: int = 3, block_p: int = 128,
                            interpret: bool = False):
    """Fused Eq.2+3 over per-device shards of a batch's schedules ->
    (D, G_loc, P, C_out); shard ``s`` runs on mesh device ``s``."""
    d = mesh.shape[axis]
    for name, arr in (("x_tiles", x_tiles), ("row_id", row_id),
                      ("dep_glb", dep_glb), ("dep_cnt", dep_cnt),
                      ("idx", idx), ("coeff", coeff)):
        if arr.shape[0] != d:
            raise ValueError(
                f"{name} leading dim {arr.shape[0]} != mesh "
                f"{axis!r} axis size {d}")
    sp = _span_dispatch("dispatch.batch_fused_sharded", x_tiles,
                        shards=int(d),
                        grid_rows=int(row_id.shape[0] * row_id.shape[1]),
                        c_out=int(w.shape[-1]))
    # Place each shard on its own mesh device and replicate the weights
    # explicitly: operands may arrive committed to the default device.
    split = NamedSharding(mesh, PartitionSpec(axis))
    full = NamedSharding(mesh, PartitionSpec())
    x_tiles, row_id, dep_glb, dep_cnt, idx, coeff = jax.device_put(
        (x_tiles, row_id, dep_glb, dep_cnt, idx, coeff), split)
    w, b = jax.device_put((w, b), full)
    if sp is None:
        return _dcn_fused_batch_sharded_jit(
            x_tiles, row_id, dep_glb, dep_cnt, idx, coeff, w, b,
            mesh=mesh, axis=axis, t_in=t_in, kernel_size=kernel_size,
            block_p=block_p, interpret=interpret)
    with sp:
        return _dcn_fused_batch_sharded_jit(
            x_tiles, row_id, dep_glb, dep_cnt, idx, coeff, w, b,
            mesh=mesh, axis=axis, t_in=t_in, kernel_size=kernel_size,
            block_p=block_p, interpret=interpret)
