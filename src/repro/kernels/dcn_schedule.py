"""Pallas TPU kernels: on-device tile scheduling (paper §IV-B/C).

On the paper's ASIC the tile scheduler is a dedicated hardware block next
to the NNA: it builds the Tile Dependency Table from the stage-1 offsets
(Fig. 9's boundary comparator + decoder) and runs Algorithm 1's greedy
max-overlap selection (AND + non-zero-bit adder tree + pipelined max
comparator) concurrently with the PE array. Until now our runtime
emulated that block on the host (``core.tiles.tdt_from_coords`` +
``core.scheduler.schedule_tiles``); this module moves both steps into
Pallas kernels so scheduling runs on-device like the paper's hardware:

  * :func:`tdt_from_coords_device` — the TDT scatter. One grid step per
    *output* tile: its pixel block's sampling coordinates are floored,
    clipped and decoded to input-tile ids (the boundary-comparator
    circuit as an integer divide), then reduced into one row of the TDT
    with a masked segment reduction (``max`` over a one-hot lane
    compare) instead of the host ``.at[].set`` scatter.
  * :func:`greedy_schedule_arrays` — Algorithm 1. The grid dimension IS
    the scheduling step; VMEM scratch carries the executed-tile bitmask
    and the FIFO residency state (per-input-tile last-load sequence
    numbers), the current tile id and the global load counter across
    steps. Each step computes every candidate's overlap
    with the current tile as one vector AND + popcount (the paper's
    adder tree), argmaxes (the pipelined comparator, first-max ties like
    the host), classifies the next tile's inputs into Algorithm 1's
    three priority classes, and advances the FIFO state exactly as the
    host :class:`~repro.core.scheduler.FifoBuffer` would.

Both kernels are bit-exact against the host reference —
``core.scheduler.schedule_tiles(..., backend="device")`` consumes them
and must produce byte-identical ``TileSchedule``s
(tests/test_device_schedule.py pins this on every oracle config).

The FIFO state trick: with load-only insertion and FIFO eviction, a tile
is resident iff its last-load sequence number is among the ``m`` most
recent loads, i.e. ``seq[t] > loads_total - m``. Within one scheduling
step the loaded-class tiles are touched first and are all hits (they
were resident when the step began), and the seq/last-class tiles are all
loads (they were not), so the per-step update is a pure vector rank
assignment — no per-touch loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Sentinel "never loaded" sequence number: always evicted under
# ``seq > loads_total - m`` for any reachable loads_total/m.
_NEVER_LOADED = -(1 << 30)


# ---------------------------------------------------------------------------
# TDT scatter kernel: sampling coordinates -> tile dependency table rows.
# ---------------------------------------------------------------------------


def _tdt_kernel(rc_ref, o_ref, *, h: int, w: int, th: int, tw: int,
                cols: int):
    """One output tile's TDT row from its pixel block's coordinates.

    rc_ref: (1, tpkk, 2) f32 — per (tile pixel, kernel tap) the sample's
            row and column coordinate.
    o_ref:  (T, n_in) int32 — the whole table, resident in VMEM across
            the grid; step ``i`` writes row ``i`` (0/1).

    Fig. 9's circuit: each coordinate's 4 BLI neighbours are clipped to
    the plane, decoded to an input-tile id, and OR-reduced over the
    block into the row — a masked segment reduction replacing the host
    scatter. Coordinates arrive as columns and the row is a lane vector,
    so the decode needs no in-kernel transpose.
    """
    i = pl.program_id(0)
    rc = rc_ref[0]                                         # (tpkk, 2)
    r = rc[:, 0:1]
    c = rc[:, 1:2]
    r0 = jnp.clip(jnp.floor(r).astype(jnp.int32), 0, h - 1)
    c0 = jnp.clip(jnp.floor(c).astype(jnp.int32), 0, w - 1)
    r1 = jnp.clip(r0 + 1, 0, h - 1)
    c1 = jnp.clip(c0 + 1, 0, w - 1)

    tpkk = rc.shape[0]
    n_in = o_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (tpkk, n_in), 1)
    row = jnp.zeros((1, n_in), jnp.int32)
    for rr, cc in ((r0, c0), (r0, c1), (r1, c0), (r1, c1)):
        # Coordinates are clipped non-negative: truncating division is
        # the floor the host decoder takes.
        tid = (jax.lax.div(rr, th) * cols
               + jax.lax.div(cc, tw))                      # (tpkk, 1)
        hit = (lane == tid).astype(jnp.int32)
        row = jnp.maximum(row, jnp.max(hit, axis=0, keepdims=True))
    o_ref[pl.ds(i, 1), :] = row


@functools.partial(jax.jit,
                   static_argnames=("in_grid", "out_grid", "interpret"))
def tdt_from_coords_device(coords: jax.Array, in_grid, out_grid,
                           interpret: bool = False) -> jax.Array:
    """Build the TDT on-device (bit-exact vs ``core.tiles.tdt_from_coords``).

    coords: (H, W, KK, 2) absolute float sampling coordinates (the
            stage-1 offset planes after ``offsets_to_coords``).
    returns B: (out_grid.num_tiles, in_grid.num_tiles) bool.

    Ragged edge tiles are handled by replicate-padding the coordinate
    gather: a padded slot repeats the plane's last row/column pixel,
    which lives in the same edge tile, so its neighbour marks are
    already present and the table is unchanged.
    """
    h, w, kk, _ = coords.shape
    th, tw = out_grid.th, out_grid.tw
    rows, cols = out_grid.rows, out_grid.cols
    t_out = out_grid.num_tiles
    tp = th * tw
    tpkk = tp * kk
    n_in = in_grid.num_tiles

    r_idx = jnp.minimum(jnp.arange(rows * th, dtype=jnp.int32), h - 1)
    c_idx = jnp.minimum(jnp.arange(cols * tw, dtype=jnp.int32), w - 1)
    ct = coords.astype(jnp.float32)[r_idx][:, c_idx]
    rc = (ct.reshape(rows, th, cols, tw, kk, 2)
          .transpose(0, 2, 1, 3, 4, 5)
          .reshape(t_out, tpkk, 2))

    out = pl.pallas_call(
        functools.partial(_tdt_kernel, h=in_grid.h, w=in_grid.w,
                          th=in_grid.th, tw=in_grid.tw, cols=in_grid.cols),
        grid=(t_out,),
        in_specs=[pl.BlockSpec((1, tpkk, 2), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((t_out, n_in), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((t_out, n_in), jnp.int32),
        interpret=interpret,
    )(rc)
    return out > 0


# ---------------------------------------------------------------------------
# Greedy max-overlap selection kernel: Algorithm 1 on-device.
# ---------------------------------------------------------------------------


def _greedy_kernel(b_ref, oid_ref, klass_ref, ovl_ref,
                   exec_ref, seq_ref, tot_ref, cur_ref, *, m: int):
    """One Algorithm-1 scheduling step (the grid dimension is the step).

    b_ref:     (n_out, n_in) int32 0/1 TDT — full block every step.
    oid_ref:   (n_out, 1)    int32 — tile scheduled per step (-1 = done).
    klass_ref: (n_out, n_in) int32 — per step, the input priority class
               per input tile: 0 = loadedVec, 1 = seqLoadVec, 2 =
               lastLoadVec, 3 = not a dependency. The host reconstructs
               the load order as ids(0) asc ++ ids(1) asc ++ ids(2) asc.
    ovl_ref:   (n_out, 1)    int32 — per step, |B[curr] & B[next]|.
               The three outputs stay resident in VMEM across the grid;
               step ``i`` writes their row ``i``.
    exec_ref:  VMEM (n_out, 1) int32 scratch — executed-tile bitmask.
    seq_ref:   VMEM (1, n_in) int32 scratch — FIFO last-load seq numbers.
    tot_ref:   VMEM (1, 1) int32 scratch — loads so far.
    cur_ref:   VMEM (1, 1) int32 scratch — the current tile id.

    All state is vector-valued and every update is a select on ``take``,
    so no value moves from a vector register into a scalar one.
    """
    i = pl.program_id(0)
    n_out, n_in = b_ref.shape

    @pl.when(i == 0)
    def _init():
        exec_ref[...] = jnp.zeros_like(exec_ref)
        seq_ref[...] = jnp.full_like(seq_ref, _NEVER_LOADED)
        tot_ref[...] = jnp.zeros_like(tot_ref)
        cur_ref[...] = jnp.zeros_like(cur_ref)

    b = b_ref[...]
    executed = exec_ref[...]                               # (n_out, 1)
    seqs = seq_ref[...]                                    # (1, n_in)
    loads_total = tot_ref[...]                             # (1, 1)
    curr = cur_ref[...]                                    # (1, 1)
    is_first = jnp.full((1, 1), i, jnp.int32) == 0

    # Candidate scores: dependency count on the first step (Algorithm 1
    # line 2), overlap with the current tile (AND + adder tree) after.
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (n_out, n_in), 0)
    currdep = jnp.sum(
        jnp.where((row_iota == curr) & jnp.logical_not(is_first), b, 0),
        axis=0, keepdims=True)                             # (1, n_in)
    overlap = jnp.sum(b * currdep, axis=1, keepdims=True)  # (n_out, 1)
    dep_cnt = jnp.sum(b, axis=1, keepdims=True)
    score = jnp.where(is_first, dep_cnt, overlap)
    valid = (dep_cnt > 0) & (executed == 0)
    masked = jnp.where(valid, score, -1)
    # First maximum wins ties — the paper's pipelined comparator and the
    # host np.argmax agree on this.
    tile_iota = jax.lax.broadcasted_iota(jnp.int32, (n_out, 1), 0)
    best = jnp.max(masked, axis=0, keepdims=True)          # (1, 1)
    nxt = jnp.min(jnp.where(masked == best, tile_iota, n_out),
                  axis=0, keepdims=True)                   # (1, 1)
    # The host schedules its argmax pick unconditionally on the first
    # step (even a dependency-free tile 0 when the TDT is empty); later
    # steps only run while un-executed dependent tiles remain.
    any_valid = jnp.max(valid.astype(jnp.int32), axis=0, keepdims=True)
    take = is_first | (any_valid > 0)                      # (1, 1)

    nxtdep = jnp.sum(jnp.where(row_iota == nxt, b, 0),
                     axis=0, keepdims=True) > 0            # (1, n_in)
    resident = seqs > (loads_total - m)
    loaded = resident & nxtdep
    lastv = (currdep > 0) & nxtdep & ~loaded
    seqv = nxtdep & ~loaded & ~lastv

    # FIFO advance: seq-class loads first (ascending id), then
    # last-class; rank within each class via an inclusive triangular
    # prefix sum (exact in f32 for any realistic tile count).
    tri = (jax.lax.broadcasted_iota(jnp.int32, (n_in, n_in), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (n_in, n_in), 1)
           ).astype(jnp.float32)
    seqf = seqv.astype(jnp.float32)
    lastf = lastv.astype(jnp.float32)
    rank_seq = jnp.dot(seqf, tri,
                       preferred_element_type=jnp.float32)
    rank_last = jnp.dot(lastf, tri,
                        preferred_element_type=jnp.float32)
    n_seq = jnp.sum(seqv.astype(jnp.int32), axis=1, keepdims=True)
    n_last = jnp.sum(lastv.astype(jnp.int32), axis=1, keepdims=True)
    new_seqs = jnp.where(
        seqv, loads_total + rank_seq.astype(jnp.int32),
        jnp.where(lastv, loads_total + n_seq + rank_last.astype(jnp.int32),
                  seqs))

    klass = jnp.where(loaded, 0,
                      jnp.where(seqv, 1, jnp.where(lastv, 2, 3)))
    ovl = jnp.sum(((currdep > 0) & nxtdep).astype(jnp.int32), axis=1,
                  keepdims=True)
    oid_ref[pl.ds(i, 1), :] = jnp.where(take, nxt, -1)
    klass_ref[pl.ds(i, 1), :] = jnp.where(take, klass, 3).astype(jnp.int32)
    ovl_ref[pl.ds(i, 1), :] = jnp.where(take, ovl, 0)

    exec_ref[...] = jnp.where(take & (tile_iota == nxt), 1, executed)
    seq_ref[...] = jnp.where(take, new_seqs, seqs)
    tot_ref[...] = jnp.where(take, loads_total + n_seq + n_last,
                             loads_total)
    cur_ref[...] = jnp.where(take, nxt, curr)


@functools.partial(jax.jit, static_argnames=("k_pad",))
def dispatch_arrays_from_klass(
    oid_seq: jax.Array,   # (n_out, 1) or (n_out,) int32, -1 padded suffix
    klass: jax.Array,     # (n_out, n_in) int32 priority classes (0/1/2/3)
    k_pad: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Device-side schedule -> dispatch handoff (zero host round-trip).

    Converts the greedy kernel's per-step class rows into the dense
    operands the batch-fused dispatch consumes, entirely as jnp ops on
    device — the host never rebuilds a ``TileSchedule`` on this path:

      oid     (n_out,)       int32 — scheduled tile per step (-1 padding)
      dep_tbl (n_out, k_pad) int32 — dependent input tiles in LOAD order:
              class 0 (loaded) ids asc ++ class 1 (seq) asc ++ class 2
              (last) asc — exactly the host loop's order,
              recovered with one stable argsort over the class row.
      dep_cnt (n_out,)       int32 — true dep count (0 on padded steps).

    ``k_pad`` must be >= n_in or any schedule's max dep count; the
    static choice ``pow2_pad(n_in)`` needs no host sync.
    """
    oid = oid_seq.reshape(-1).astype(jnp.int32)
    n_out, n_in = klass.shape
    # Stable sort on the class alone: ids ascend within each class.
    order = jnp.argsort(klass.astype(jnp.int32), axis=1)   # (n_out, n_in)
    cnt = jnp.sum(klass < 3, axis=1).astype(jnp.int32)
    if k_pad < n_in:
        order = order[:, :k_pad]  # only valid if max cnt <= k_pad
    elif k_pad > n_in:
        order = jnp.pad(order, ((0, 0), (0, k_pad - n_in)))
    # Zero out padding slots so rows match the host dense() convention.
    slot = jax.lax.broadcasted_iota(jnp.int32, (n_out, k_pad), 1)
    dep_tbl = jnp.where(slot < cnt[:, None], order, 0).astype(jnp.int32)
    return oid, dep_tbl, cnt


def tdt_dispatch_arrays(b, k_pad: int):
    """Dense dispatch rows straight from a TDT (no scheduling): per output
    tile its dependent input tiles in ascending id order + counts. Used
    for interior fused-group layers, whose grid order is plane order.
    A numpy TDT (the host scheduling backend) gives numpy rows; anything
    else goes through jnp and stays on device for the batch-fused
    handoff. Both sort stably: numpy's default sort is not, and ids
    would then leave ascending order within a row."""
    xp = np if isinstance(b, np.ndarray) else jnp
    bi = xp.asarray(b).astype(xp.int32)
    n_out, n_in = bi.shape
    order = xp.argsort(1 - bi, axis=1, stable=True)        # deps first, asc
    cnt = xp.sum(bi, axis=1).astype(xp.int32)
    if k_pad < n_in:
        order = order[:, :k_pad]
    elif k_pad > n_in:
        order = xp.pad(order, ((0, 0), (0, k_pad - n_in)))
    slot = xp.arange(k_pad)[None, :]
    return xp.where(slot < cnt[:, None], order, 0).astype(xp.int32), cnt


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def greedy_schedule_arrays(
    b: jax.Array,        # (n_out, n_in) bool/int TDT
    m: int,              # FIFO input-buffer capacity in tiles
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Run Algorithm 1 on-device over a tile dependency table.

    Returns dense device arrays the host assembles into a
    ``TileSchedule`` (``core.scheduler.assemble_device_schedule``):

      oid_seq (n_out, 1)    int32 — scheduled tile per step, -1 padding
                                    once every dependent tile is done
                                    (padding is a contiguous suffix).
      klass   (n_out, n_in) int32 — per step, each input tile's priority
                                    class (0 loaded / 1 seq / 2 last /
                                    3 not a dependency).
      ovl     (n_out, 1)    int32 — per step, reuse overlap with the
                                    previously scheduled tile.
    """
    b = b.astype(jnp.int32)
    n_out, n_in = b.shape
    if m < 1:
        raise ValueError("buffer capacity must be >= 1 tile")
    whole = [pl.BlockSpec((n_out, 1), lambda i: (0, 0)),
             pl.BlockSpec((n_out, n_in), lambda i: (0, 0)),
             pl.BlockSpec((n_out, 1), lambda i: (0, 0))]
    return pl.pallas_call(
        functools.partial(_greedy_kernel, m=m),
        grid=(n_out,),
        in_specs=[pl.BlockSpec((n_out, n_in), lambda i: (0, 0))],
        out_specs=whole,
        out_shape=[
            jax.ShapeDtypeStruct((n_out, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_out, n_in), jnp.int32),
            jax.ShapeDtypeStruct((n_out, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_out, 1), jnp.int32),
            pltpu.VMEM((1, n_in), jnp.int32),
            pltpu.VMEM((1, 1), jnp.int32),
            pltpu.VMEM((1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(b)
