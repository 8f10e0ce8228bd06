"""Runtime tile scheduler — paper §IV-C, Algorithm 1, Fig. 10.

The paper's bit-vector-based tile scheduling:

  * output tile scheduling — greedily pick the un-executed output tile
    whose input-tile dependency vector overlaps most with the current one
    (hardware: AND + non-zero-bit adder tree + pipelined max comparator;
    ties go to the lowest tile id).
  * input tile scheduling  — order the dependent input tiles of the
    *next* output tile in three priority classes:
       1. already resident on-chip            (loadedVec)   — reuse first,
       2. everything else                     (seqLoadVec)  — middle,
       3. shared with the *current* tile but
          not resident                        (lastLoadVec) — loaded last so
          they stay resident for the upcoming reuse.
  * FIFO replacement for the on-chip input-tile buffer (paper: "An FIFO
    strategy is used for the input tile replacement for efficient hardware
    implementation").

Two backends. The default host backend computes the overlap matrix
O = B·Bᵀ once per TDT (:func:`overlap_matrix`, a sparse integer product)
and then reads one row of it a step: the next tile is the first argmax
of ``O[curr]`` over the remaining tiles, and the input classes are
decided on the next tile's few dependencies against per-tile FIFO load
sequence numbers. Its cost is O(Σ_i |B[:, i]|² + n_out²) for O plus
O(n_out + deps) a step — not the O(n_out · n_in) table sweep a step of
the literal procedure. ``backend="device"`` runs the same greedy
selection as a Pallas kernel
(``kernels.dcn_schedule.greedy_schedule_arrays``) — the step loop
becomes the kernel grid, the resident-set bitmask lives in VMEM, and the
host only reassembles the emitted order — matching the paper's on-chip
scheduler architecture. Both backends are bit-exact: they produce
byte-identical ``TileSchedule``s on every input. On TPU the schedule
orders the Pallas grid / DMA sequence (``kernels.dcn_fused``).

The module also provides the two ablation baselines of paper Fig. 14-16:
``sequential_schedule`` (W/ bit vector + W/O scheduling) and the naive
per-pixel path lives in ``repro.core.simulator``.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from repro.obs import default_registry

# Instrumentation: counts host-side ``TileSchedule`` constructions.
#
# The batch-fused executors promise a zero-host-round-trip hot path with
# ``schedule_backend="device"`` — device schedule arrays flow straight
# into the dispatch operands, and the Python ``TileSchedule`` is only
# assembled lazily for traces. Tests pin that promise by snapshotting
# this counter around an executor call; it lives in the process-wide
# ``repro.obs`` registry so metrics snapshots carry it too.
host_schedule_builds = default_registry().counter(
    "host_schedule_builds",
    help="host-side TileSchedule constructions (0 on the device "
         "scheduling hot path)")


def pow2_pad(x: int) -> int:
    """Smallest power of two >= max(1, x) — the packed dep-slot padding
    policy shared by the schedule and both executors (uniform packed
    geometry -> one kernel compilation per layer)."""
    return 1 << (max(1, x) - 1).bit_length()


@dataclass
class TileSchedule:
    """Result of Algorithm 1.

    oid:  execution order of output tiles (len = #output tiles with deps).
    iid:  per scheduled output tile, the ordered list of its dependent
          input tiles (priority classes already applied).
    """

    oid: list[int]
    iid: list[list[int]]
    # Diagnostics filled by the scheduler:
    # Per transition: |B[curr] & B[next]|
    reuse_overlap: list[int] = field(default_factory=list)

    def dense(self, k_pad: int | None = None
              ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Schedule as dense arrays for the kernel grid.

        The batch-fused kernel runs the schedule as ONE ``pallas_call``
        whose leading grid dimension is the scheduled-tile index, so it
        needs arrays, not Python lists (:meth:`DeviceSchedule.from_host`):

          oid    (T,)        int32 — output tiles in execution order
          deps   (T, k_pad)  int32 — dependent input tiles in load order,
                                     rows zero-padded past their count
          counts (T,)        int32 — true dep count per scheduled tile

        ``k_pad`` defaults to the max dep count rounded up to a power of
        two (uniform packed-buffer geometry -> one kernel compilation).
        """
        t = len(self.oid)
        counts = np.array([len(d) for d in self.iid], np.int32).reshape(t)
        k_max = int(counts.max()) if t else 1
        if k_pad is None:
            k_pad = pow2_pad(k_max)
        elif k_pad < k_max:
            raise ValueError(f"k_pad={k_pad} below max dep count {k_max}")
        oid = np.asarray(self.oid, np.int32).reshape(t)
        deps = np.zeros((t, k_pad), np.int32)
        # Row-major boolean fill: row n takes the next counts[n] ids.
        deps[np.arange(k_pad) < counts[:, None]] = np.fromiter(
            itertools.chain.from_iterable(self.iid), np.int32,
            int(counts.sum()))
        return oid, deps, counts


class FifoBuffer:
    """FIFO-replacement on-chip tile buffer model (capacity = M tiles)."""

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError("buffer capacity must be >= 1 tile")
        self.capacity = int(capacity)
        self.queue: list[int] = []  # front = oldest
        self.resident: set[int] = set()
        self.loads = 0  # number of DRAM tile loads issued
        self.hits = 0   # number of on-chip reuse hits

    def touch(self, tile: int) -> bool:
        """Access ``tile``; load it if absent. Returns True on a hit."""
        if tile in self.resident:
            self.hits += 1
            return True
        self.loads += 1
        if len(self.queue) >= self.capacity:
            evicted = self.queue.pop(0)
            self.resident.discard(evicted)
        self.queue.append(tile)
        self.resident.add(tile)
        return False


def _ids_of(vec: np.ndarray) -> list[int]:
    return np.flatnonzero(vec).tolist()


def overlap_matrix(B: np.ndarray) -> np.ndarray:
    """O = B·Bᵀ as exact int32 counts: ``O[a, b] = |B[a] & B[b]|``.

    A sparse product: every input tile adds one to ``O[a, b]`` for each
    pair of output tiles (a, b) that both depend on it, so the cost is
    Σ_i |B[:, i]|² increments plus the n_out² table, not the dense
    n_out² · n_in.
    """
    n_out = B.shape[0]
    rows, cols = np.nonzero(B)
    by_tile = np.argsort(cols, kind="stable")
    tile, dependant = cols[by_tile], rows[by_tile]
    size = np.bincount(tile, minlength=B.shape[1])
    start = np.cumsum(size) - size           # each group's first entry
    reps = size[tile]                        # pairs per entry
    a = np.repeat(dependant, reps)
    k = np.arange(a.size) - np.repeat(np.cumsum(reps) - reps, reps)
    b = dependant[np.repeat(start[tile], reps) + k]
    counts = np.bincount(a * n_out + b, minlength=n_out * n_out)
    return counts.astype(np.int32).reshape(n_out, n_out)


def schedule_tiles(B, buffer_tiles: int, backend: str = "host",
                   *, interpret: bool | None = None) -> TileSchedule:
    """Full Algorithm 1: bit-vector based tile scheduling.

    B: (n_out, n_in) bool tile-dependency table (TDT). May be a device
       array (it stays on-device for ``backend="device"``).
    buffer_tiles: M, on-chip input-buffer capacity in tiles.
    backend: "host" — the numpy loop below; "device" — the Pallas
       greedy-selection kernel (bit-exact with the host loop; see
       :func:`schedule_tiles_device`). ``interpret`` only applies to the
       device backend (None = auto: interpret off-accelerator).

    Returns the output-tile execution order and the per-tile input-load
    order. The on-chip occupancy used for the priority classes is the
    same FIFO model (:class:`FifoBuffer`) the execution replays.

    The host loop computes the overlap matrix once
    (:func:`overlap_matrix`) and reads one row of it a step: the
    scheduled tiles' columns are set to -1, so ``argmax(O[curr])`` is
    the remaining tile with the largest ``|B[curr] & B[o]|``, lowest id
    first, and ``O[curr, nxt]`` is the step's reuse overlap. FIFO
    residency is a load sequence number per input tile: with load-only
    insertion and oldest-first eviction, a tile is resident iff it was
    loaded by one of the last M loads. A step thus costs O(n_out) for
    the row plus O(deps of the next tile) for its input classes; O
    costs O(Σ_i |B[:, i]|² + n_out²) once.
    """
    if backend == "device":
        return schedule_tiles_device(B, buffer_tiles, interpret=interpret)
    if backend != "host":
        raise ValueError(f"unknown schedule backend: {backend!r}")
    if buffer_tiles < 1:
        raise ValueError("buffer capacity must be >= 1 tile")
    B = np.asarray(B, dtype=bool)
    n_out, n_in = B.shape
    n_deps = B.sum(axis=1)
    ends = np.cumsum(n_deps).tolist()
    cols = np.nonzero(B)[1].tolist()
    deps = [cols[e - n:e] for e, n in zip(ends, n_deps.tolist())]

    overlap = overlap_matrix(B)
    overlap[:, n_deps == 0] = -1             # never scheduled
    last_load = [-buffer_tiles - 1] * n_in   # resident iff >= loads - M
    loads = 0

    # line 2: first output tile = the one requiring the most input tiles.
    first = int(np.argmax(np.where(n_deps > 0, n_deps, -1)))
    overlap[:, first] = -1
    for t in deps[first]:
        last_load[t] = loads
        loads += 1
    oid = [first]
    iid = [deps[first]]
    overlaps: list[int] = []

    curr = first
    for _ in range(int((n_deps > 0).sum()) - 1):
        row = overlap[curr]
        nxt = int(row.argmax())
        overlaps.append(int(row[nxt]))
        overlap[:, nxt] = -1
        shared = set(deps[curr])
        resident_from = loads - buffer_tiles
        loaded_ids, seq_ids, last_ids = [], [], []
        for t in deps[nxt]:
            if last_load[t] >= resident_from:
                loaded_ids.append(t)
            elif t in shared:
                last_ids.append(t)
            else:
                seq_ids.append(t)
        for t in seq_ids + last_ids:
            last_load[t] = loads
            loads += 1
        oid.append(nxt)
        iid.append(loaded_ids + seq_ids + last_ids)
        curr = nxt

    host_schedule_builds.bump()
    return TileSchedule(oid=oid, iid=iid, reuse_overlap=overlaps)


def assemble_device_schedule(oid_seq: np.ndarray, klass: np.ndarray,
                             overlap: np.ndarray) -> TileSchedule:
    """Assemble a ``TileSchedule`` from the device greedy kernel's dense
    outputs (``kernels.dcn_schedule.greedy_schedule_arrays``).

    oid_seq: (n_out,) or (n_out, 1) int32 — scheduled tile per step, -1
             once every dependent tile is done (a contiguous suffix).
    klass:   (n_out, n_in) int32 — per-step input priority class
             (0 loadedVec / 1 seqLoadVec / 2 lastLoadVec / 3 non-dep);
             the load order is ids(0) asc ++ ids(1) asc ++ ids(2) asc,
             exactly the host loop's three classes.
    overlap: (n_out,) or (n_out, 1) int32 — per-step reuse overlap.

    This residual host work is O(total deps) bookkeeping — the O(T^2 *
    n_in) selection ran on-device.
    """
    oid_seq = np.asarray(oid_seq).reshape(-1)
    klass = np.asarray(klass)
    overlap = np.asarray(overlap).reshape(-1)
    n_sched = int((oid_seq >= 0).sum())
    iid = []
    for t in range(n_sched):
        row = klass[t]
        iid.append(np.flatnonzero(row == 0).tolist()
                   + np.flatnonzero(row == 1).tolist()
                   + np.flatnonzero(row == 2).tolist())
    host_schedule_builds.bump()
    return TileSchedule(oid=oid_seq[:n_sched].tolist(), iid=iid,
                        reuse_overlap=overlap[1:n_sched].tolist())


def schedule_tiles_device(B, buffer_tiles: int,
                          *, interpret: bool | None = None) -> TileSchedule:
    """Algorithm 1 via the on-device greedy selection kernel.

    Bit-exact vs the host ``schedule_tiles`` loop on every TDT: same
    first-tile choice, same first-max tie-breaks, same three input
    priority classes under the same FIFO residency model (the kernel
    tracks it as per-tile load sequence numbers in VMEM).
    """
    # Imported lazily: the numpy host path must stay importable without
    # pulling the Pallas toolchain in.
    import jax

    from repro.kernels.dcn_schedule import greedy_schedule_arrays
    from repro.kernels.ops import resolve_interpret

    oid_seq, klass, ovl = greedy_schedule_arrays(
        jax.numpy.asarray(B), int(buffer_tiles),
        interpret=resolve_interpret(interpret))
    return assemble_device_schedule(np.asarray(oid_seq), np.asarray(klass),
                                    np.asarray(ovl))


def sequential_schedule(B: np.ndarray) -> TileSchedule:
    """Ablation baseline: 'W/ bit vector + W/O scheduling' (paper Fig. 14).

    Output tiles execute in sequential id order; each loads its dependent
    input tiles (deduplicated via the TDT) in ascending id order.
    """
    B = np.asarray(B, dtype=bool)
    oid = [o for o in range(B.shape[0]) if B[o].any()]
    iid = [_ids_of(B[o]) for o in oid]
    host_schedule_builds.bump()
    return TileSchedule(oid=oid, iid=iid)


# ---------------------------------------------------------------------------
# Dense device-schedule handoff (batch-fused dispatch, zero host round-trip)
# ---------------------------------------------------------------------------


@dataclass
class DeviceSchedule:
    """Algorithm-1 schedule as dense dispatch-ready arrays.

    The batch-fused executor consumes schedules in exactly the dense form
    the batch-fused kernel's scalar-prefetch machinery needs, so with
    ``schedule_backend="device"`` the greedy kernel's outputs flow here
    as device arrays end-to-end — no host reassembly, no Python
    ``TileSchedule`` on the hot path. All arrays have ``n_out`` rows
    (one per possible scheduling step); the padded suffix past the real
    schedule length carries ``oid = -1`` / ``dep_cnt = 0`` and is what
    ragged batch concatenation elides.

      oid     (n_out,)        int32 — scheduled tile per step, -1 padding
      dep_tbl (n_out, k_pad)  int32 — dependent input tiles in LOAD order
                                      (the three Algorithm-1 priority
                                      classes), rows zero-padded
      dep_cnt (n_out,)        int32 — true dep count per step
      overlap (n_out,)        int32 — per-step reuse overlap diagnostic

    Arrays may live on device (jax) or host (numpy) — both backends emit
    bit-identical values. ``to_host()`` lazily assembles the classic
    ``TileSchedule`` for traces and simulator cross-checks.
    """

    oid: Any
    dep_tbl: Any
    dep_cnt: Any
    overlap: Any
    _host: TileSchedule | None = None

    @property
    def n_rows(self) -> int:
        return int(self.oid.shape[0])

    @property
    def k_pad(self) -> int:
        return int(self.dep_tbl.shape[1])

    def to_host(self) -> TileSchedule:
        """Assemble (and memoize) the host ``TileSchedule`` — OFF the hot
        path: traces and cross-checks only."""
        if self._host is None:
            oid = np.asarray(self.oid).reshape(-1)
            dep = np.asarray(self.dep_tbl)
            cnt = np.asarray(self.dep_cnt).reshape(-1)
            ovl = np.asarray(self.overlap).reshape(-1)
            n_sched = int((oid >= 0).sum())
            host_schedule_builds.bump()
            self._host = TileSchedule(
                oid=oid[:n_sched].tolist(),
                iid=[dep[t, :cnt[t]].tolist() for t in range(n_sched)],
                reuse_overlap=ovl[1:n_sched].tolist())
        return self._host

    @classmethod
    def from_host(cls, sched: TileSchedule, n_out: int,
                  k_pad: int | None = None) -> "DeviceSchedule":
        """Dense padded form of a host-built schedule (numpy arrays).

        Pads to ``n_out`` rows so batch concatenation sees the same
        uniform per-image row count as the device path.
        """
        t = len(sched.oid)
        if t > n_out:
            raise ValueError(f"schedule has {t} steps > n_out={n_out}")
        oid_d, deps_d, cnt_d = sched.dense(k_pad)
        oid = np.full((n_out,), -1, np.int32)
        oid[:t] = oid_d
        dep_tbl = np.zeros((n_out, deps_d.shape[1]), np.int32)
        dep_tbl[:t] = deps_d
        cnt = np.zeros((n_out,), np.int32)
        cnt[:t] = cnt_d
        ovl = np.zeros((n_out,), np.int32)
        ro = np.asarray(sched.reuse_overlap[:max(t - 1, 0)], np.int32)
        ovl[1:1 + ro.size] = ro   # sequential schedules carry no overlaps
        return cls(oid, dep_tbl, cnt, ovl, _host=sched)


def schedule_arrays_device(B, m: int, *, k_pad: int | None = None,
                           interpret: bool | None = None) -> DeviceSchedule:
    """Algorithm 1 on-device, emitted directly as dispatch arrays.

    Unlike :func:`schedule_tiles_device` the result never touches the
    host: ``greedy_schedule_arrays`` runs the selection, and the class
    rows are converted to load-ordered dep tables with a stable device
    argsort (``kernels.dcn_schedule.dispatch_arrays_from_klass``).
    ``k_pad`` defaults to ``pow2_pad(n_in)`` — static, so no host sync
    on the data-dependent max dep count.
    """
    import jax

    from repro.kernels.dcn_schedule import (dispatch_arrays_from_klass,
                                            greedy_schedule_arrays)
    from repro.kernels.ops import resolve_interpret

    B = jax.numpy.asarray(B)
    n_in = B.shape[1]
    if k_pad is None:
        k_pad = pow2_pad(n_in)
    oid_seq, klass, ovl = greedy_schedule_arrays(
        B, int(m), interpret=resolve_interpret(interpret))
    oid, dep_tbl, cnt = dispatch_arrays_from_klass(oid_seq, klass, k_pad)
    return DeviceSchedule(oid, dep_tbl, cnt, ovl.reshape(-1))
