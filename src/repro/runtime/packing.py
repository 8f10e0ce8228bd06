"""Tile packing for the graph executor.

The fused Pallas kernel (``kernels.dcn_fused``) consumes a flat packed
input buffer ``x_packed (S, C)`` plus per-output-pixel ``(idx, coeff)``
tensors whose indices address *that buffer* — the software analogue of the
paper's on-chip input buffer and address converter (Eq. 4): global
``(row, col)`` sample coordinates are rewritten into buffer-local
addresses ``slot(tile) * tile_pixels + offset_in_tile``.

Shapes that do not divide by the tile size are handled by padding the
feature plane up to ``rows*th x cols*tw``: sampling coordinates are
clamped in-range upstream (``core.deform.offsets_to_coords``), so padded
pixels are never addressed, and padded *output* pixels are packed with
``coeff = 0`` and discarded on scatter.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.deform import bli_coefficients
from repro.core.scheduler import DeviceSchedule, pow2_pad
from repro.core.tiles import TileGrid
from repro.obs import get_tracer


def plane_to_tiles(x: jax.Array, grid: TileGrid) -> jax.Array:
    """(H, W, C) -> (num_tiles, th*tw, C), zero-padded to the tile grid."""
    h, w, c = x.shape
    hp, wp = grid.rows * grid.th, grid.cols * grid.tw
    if (hp, wp) != (h, w):
        x = jnp.pad(x, ((0, hp - h), (0, wp - w), (0, 0)))
    x = x.reshape(grid.rows, grid.th, grid.cols, grid.tw, c)
    return x.transpose(0, 2, 1, 3, 4).reshape(grid.num_tiles,
                                              grid.th * grid.tw, c)


def tiles_to_plane(y_tiles: jax.Array, grid: TileGrid, h: int, w: int,
                   ) -> jax.Array:
    """(num_tiles, th*tw, C) -> (H, W, C): inverse of ``plane_to_tiles``."""
    c = y_tiles.shape[-1]
    y = y_tiles.reshape(grid.rows, grid.cols, grid.th, grid.tw, c)
    y = y.transpose(0, 2, 1, 3, 4).reshape(grid.rows * grid.th,
                                           grid.cols * grid.tw, c)
    return y[:h, :w]


class NeighbourTables(NamedTuple):
    """Per-pixel BLI neighbour data in host memory (one image).

    All arrays are (H, W, KK, 4) over the 4 integer-grid neighbours in the
    order (r0,c0) (r0,c1) (r1,c0) (r1,c1) — matching Eq. 5 / the kernels.
    """

    tile_id: np.ndarray   # int32 input-tile id of each neighbour
    offset: np.ndarray    # int32 raster offset of the neighbour in its tile
    coeff: np.ndarray     # float32 BLI coefficients (eta, theta, mu, gamma)


def build_neighbour_tables(coords: jax.Array, grid: TileGrid,
                           ) -> NeighbourTables:
    """coords (H, W, KK, 2) float -> host-side neighbour tables.

    Uses the exact clipping/coefficient rules of the XLA reference
    (``core.deform.bilinear_sample``) so the pipeline is bit-compatible
    with it up to matmul association order.
    """
    floor_rc, coeffs = bli_coefficients(coords)
    floor_rc = np.asarray(floor_rc)
    r0 = np.clip(floor_rc[..., 0], 0, grid.h - 1)
    c0 = np.clip(floor_rc[..., 1], 0, grid.w - 1)
    r1 = np.clip(r0 + 1, 0, grid.h - 1)
    c1 = np.clip(c0 + 1, 0, grid.w - 1)
    nb_r = np.stack([r0, r0, r1, r1], axis=-1)
    nb_c = np.stack([c0, c1, c0, c1], axis=-1)
    tile_id = (nb_r // grid.th) * grid.cols + (nb_c // grid.tw)
    offset = (nb_r % grid.th) * grid.tw + (nb_c % grid.tw)
    return NeighbourTables(tile_id.astype(np.int32),
                           offset.astype(np.int32),
                           np.asarray(coeffs, np.float32))


def pack_output_tile(
    nb: NeighbourTables,
    grid: TileGrid,
    out_tile: int,
    dep_tiles: list[int],
    p_pad: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Build the kernel's (idx, coeff) operands for one output tile.

    Rewrites each neighbour's global (tile_id, offset) into an address in
    the packed buffer that concatenates ``dep_tiles`` in load order:
    ``slot * tile_pixels + offset``. Output pixels beyond the real plane
    (tile overhangs the H x W extent) get ``coeff = 0`` so they contribute
    zeros that the scatter discards.

    Returns idx (p_pad, KK, 4) int32 and coeff (p_pad, KK, 4) float32.
    """
    th, tw, cols = grid.th, grid.tw, grid.cols
    tp = th * tw
    kk = nb.coeff.shape[2]

    slot = np.zeros(grid.num_tiles, np.int32)
    slot[np.asarray(dep_tiles, np.int64)] = np.arange(len(dep_tiles),
                                                      dtype=np.int32)

    tr, tc = divmod(out_tile, cols)
    rr = np.arange(tr * th, (tr + 1) * th)
    cc = np.arange(tc * tw, (tc + 1) * tw)
    valid = (rr[:, None] < grid.h) & (cc[None, :] < grid.w)    # (th, tw)
    rr_c = np.minimum(rr, grid.h - 1)
    cc_c = np.minimum(cc, grid.w - 1)

    t_ids = nb.tile_id[rr_c][:, cc_c]                          # (th,tw,KK,4)
    offs = nb.offset[rr_c][:, cc_c]
    cfs = nb.coeff[rr_c][:, cc_c] * valid[..., None, None]

    # TDT guarantee: every neighbour tile of a real pixel in ``out_tile``
    # is in ``dep_tiles``; padded pixels carry coeff 0 and may point at
    # slot 0 harmlessly.
    idx = slot[t_ids] * tp + offs
    idx = np.where(valid[..., None, None], idx, 0).astype(np.int32)

    idx = idx.reshape(tp, kk, 4)
    cfs = cfs.reshape(tp, kk, 4).astype(np.float32)
    if p_pad != tp:
        idx = np.pad(idx, ((0, p_pad - tp), (0, 0), (0, 0)))
        cfs = np.pad(cfs, ((0, p_pad - tp), (0, 0), (0, 0)))
    return idx, cfs


# ---------------------------------------------------------------------------
# Batch-fused packing: plane-ordered global-address operands + the
# batch-stacking path (concatenated per-image schedules).
# ---------------------------------------------------------------------------


def pack_plane_operands(coords: jax.Array, grid: TileGrid, p_pad: int,
                        ) -> tuple[jax.Array, jax.Array]:
    """(idx, coeff) kernel operands for EVERY output tile, in plane order,
    with PLANE-GLOBAL packed addresses ``tile_id * tile_pixels + offset``.

    Unlike :func:`pack_output_tile`, the addresses do not depend on any
    schedule's dep-slot assignment — the batch-fused kernel localises
    them against the scalar-prefetched dep id per slot. That makes the
    packing pure jnp on the sampling coordinates: with the device
    scheduling backend the whole prepass stays on-device (zero host
    round trip). Numerics match ``build_neighbour_tables`` +
    ``pack_output_tile`` exactly (same Eq. 4/5 formulas).

    coords: (H, W, KK, 2) -> idx/coeff (num_tiles, p_pad, KK, 4).
    """
    h, w, kk, _ = coords.shape
    th, tw, rows, cols = grid.th, grid.tw, grid.rows, grid.cols
    tp = th * tw

    floor_rc, coeffs = bli_coefficients(coords)
    r0 = jnp.clip(floor_rc[..., 0], 0, grid.h - 1)
    c0 = jnp.clip(floor_rc[..., 1], 0, grid.w - 1)
    r1 = jnp.clip(r0 + 1, 0, grid.h - 1)
    c1 = jnp.clip(c0 + 1, 0, grid.w - 1)
    nb_r = jnp.stack([r0, r0, r1, r1], axis=-1)            # (H, W, KK, 4)
    nb_c = jnp.stack([c0, c1, c0, c1], axis=-1)
    idx = ((nb_r // th) * cols + nb_c // tw) * tp \
        + (nb_r % th) * tw + nb_c % tw

    # Replicate-pad ragged edges; overhang output pixels carry coeff 0
    # (their contribution is discarded on scatter) and address 0.
    r_idx = jnp.minimum(jnp.arange(rows * th), h - 1)
    c_idx = jnp.minimum(jnp.arange(cols * tw), w - 1)
    valid = ((jnp.arange(rows * th) < h)[:, None]
             & (jnp.arange(cols * tw) < w)[None, :])
    idx_p = jnp.where(valid[..., None, None], idx[r_idx][:, c_idx], 0)
    cf_p = coeffs[r_idx][:, c_idx] * valid[..., None, None]

    def to_tiles(a):
        a = a.reshape(rows, th, cols, tw, kk, 4)
        a = a.transpose(0, 2, 1, 3, 4, 5).reshape(rows * cols, tp, kk, 4)
        if p_pad != tp:
            a = jnp.pad(a, ((0, 0), (0, p_pad - tp), (0, 0), (0, 0)))
        return a

    return (to_tiles(idx_p).astype(jnp.int32),
            to_tiles(cf_p).astype(jnp.float32))


class BatchDispatch(NamedTuple):
    """Concatenated per-image schedules as batch-fused kernel operands.

    One row per (image, schedule step) slot, images back to back with
    per-image base offsets already applied (``img * t_out`` for output
    rows, ``img * t_in`` for dep tiles). Ragged schedule lengths pad to
    the uniform per-image row count with ``oid = -1`` / ``dep_cnt = 0``
    slots whose dep entries repeat the image's last real dep (so the
    kernel's clamped index map elides their DMAs across the image
    boundary).
    """

    row_id: jax.Array    # (G,) int32 img*t_out + max(oid, 0)
    dep_glb: jax.Array   # (G, k_pad) int32 img*t_in + dep (load order)
    dep_cnt: jax.Array   # (G,) int32, 0 on padded slots
    oid: jax.Array       # (G,) int32 concatenated oids, -1 on padding
    img_id: jax.Array    # (G,) int32


def pack_batch_schedules(scheds: list[DeviceSchedule], t_in: int,
                         t_out: int) -> BatchDispatch:
    """Batch-stacking path: concatenate per-image dense schedules into
    one batch grid over the ``DeviceSchedule`` arrays. Device schedules
    go through jnp and stay on-device end-to-end; when every schedule is
    host-built (numpy arrays) the assembly runs in numpy, op for op the
    same, and returns numpy arrays for the caller to upload once. All
    images must share the tile grid (same uniform row count per
    image)."""
    if not scheds:
        raise ValueError("empty batch")
    n_rows = scheds[0].n_rows
    if any(s.n_rows != n_rows for s in scheds):
        raise ValueError("per-image schedules disagree on row count — "
                         "images in a batch must share the tile grid")
    xp = np if all(isinstance(a, np.ndarray) for s in scheds
                   for a in (s.oid, s.dep_tbl, s.dep_cnt)) else jnp
    k_pad = max(s.k_pad for s in scheds)
    rows, deps, cnts, oids, imgs = [], [], [], [], []
    with get_tracer().span("pack.batch_schedules", batch=len(scheds),
                           rows=n_rows):
        for i, s in enumerate(scheds):
            oid_i = xp.asarray(s.oid).reshape(-1)
            dep_i = xp.asarray(s.dep_tbl)
            cnt_i = xp.asarray(s.dep_cnt).reshape(-1)
            if dep_i.shape[1] < k_pad:
                dep_i = xp.pad(dep_i,
                               ((0, 0), (0, k_pad - dep_i.shape[1])))
            valid = oid_i >= 0
            # Padded suffix rows repeat the image's last real dep so
            # their (skipped) grid steps issue no fresh DMA.
            last_row = xp.maximum(xp.sum(valid) - 1, 0)
            last_dep = dep_i[last_row,
                             xp.maximum(cnt_i[last_row] - 1, 0)]
            dep_i = xp.where(valid[:, None], dep_i, last_dep)
            rows.append(i * t_out + xp.maximum(oid_i, 0))
            deps.append(i * t_in + dep_i)
            cnts.append(cnt_i)
            oids.append(oid_i)
            imgs.append(xp.full((n_rows,), i, xp.int32))
        return BatchDispatch(
            row_id=xp.concatenate(rows).astype(xp.int32),
            dep_glb=xp.concatenate(deps).astype(xp.int32),
            dep_cnt=xp.concatenate(cnts).astype(xp.int32),
            oid=xp.concatenate(oids).astype(xp.int32),
            img_id=xp.concatenate(imgs))


def narrow_dep_slots(batch: BatchDispatch, floor: int) -> BatchDispatch:
    """A host-built batch's dep table cut to the slots its rows use: the
    largest dep count rounded up to a power of two, at least ``floor``
    and never wider than the table was. The kernel reads no slot past a
    row's count, so it computes the same over fewer grid steps and less
    SMEM; ``floor`` keeps the width, and with it the compiled kernel,
    the same from one batch to the next. Device tables are returned as
    they are: their largest count would have to wait for the device."""
    if not isinstance(batch.dep_cnt, np.ndarray):
        return batch
    k_pad = batch.dep_glb.shape[1]
    need = pow2_pad(int(batch.dep_cnt.max(initial=0)))
    k = min(k_pad, max(floor, need))
    if k == k_pad:
        return batch
    return batch._replace(dep_glb=np.ascontiguousarray(batch.dep_glb[:, :k]))
