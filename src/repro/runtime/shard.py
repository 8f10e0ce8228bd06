"""Batch-dimension sharding of the batch-fused dispatch (scale-out).

The paper scales its accelerator by replicating the tile pipeline
behind one scheduler; the executor analogue is sharding the batch axis
of ``dispatch="batch_fused"`` across a device mesh. This module holds
the host-side plumbing that stays identical for the pipeline and graph
executors:

* :class:`ShardPlan` — a contiguous partition of the batch over the
  mesh's ``"data"`` axis (serving passes explicit per-replica sizes so
  slot placement and shard placement agree).
* :func:`shard_batch_schedules` — per-shard ``pack_batch_schedules``:
  each shard keeps its OWN ragged padding (``k_pad`` / row count from
  its local images only), then pads to the cross-shard max with fully
  elided rows (``dep_cnt=0``, clamped-index DMA reuse) so a slow
  replica never inflates another replica's real work.
* :func:`stack_rows` / :func:`unstack_rows` — reshuffle flat per-image
  row blocks into the ``(D, n_max*rows, ...)`` shard-stacked layout the
  sharded kernel consumes, and back. ``unstack_rows`` on the logits is
  the ONE all-gather of the whole sharded run.

Scheduling, packing and traces are untouched: per-image schedules are
built exactly as in the single-device path, so executed traces stay
equal to the DRAM simulator regardless of placement.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.runtime.packing import pack_batch_schedules


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """A contiguous partition of ``n`` batch images over shards.

    ``spans[s] = (start, stop)`` is shard ``s``'s image range; spans
    cover ``range(n)`` in order, and may be empty (a replica with no
    occupied slots still participates in the SPMD dispatch with a fully
    padded grid).
    """

    n: int
    spans: tuple[tuple[int, int], ...]

    @property
    def n_shards(self) -> int:
        return len(self.spans)

    @property
    def sizes(self) -> tuple[int, ...]:
        return tuple(b - a for a, b in self.spans)

    @property
    def n_max(self) -> int:
        """Images on the fullest shard — the uniform SPMD slab size."""
        return max(self.sizes) if self.spans else 0


def plan_batch_shards(n: int, n_shards: int,
                      sizes: Sequence[int] | None = None) -> ShardPlan:
    """Partition ``n`` images contiguously over ``n_shards`` shards.

    Default is the near-even split (first ``n % n_shards`` shards get
    one extra image). ``sizes`` pins an explicit per-shard image count
    (the serving engine's replica-aware placement), which must sum to
    ``n``; zeros are allowed.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if n < 0:
        raise ValueError(f"n must be >= 0, got {n}")
    if sizes is None:
        base, extra = divmod(n, n_shards)
        sizes = [base + (1 if s < extra else 0) for s in range(n_shards)]
    else:
        sizes = [int(s) for s in sizes]
        if len(sizes) != n_shards:
            raise ValueError(f"sizes has {len(sizes)} entries for "
                             f"{n_shards} shards")
        if any(s < 0 for s in sizes):
            raise ValueError(f"negative shard size in {sizes}")
        if sum(sizes) != n:
            raise ValueError(f"shard sizes {sizes} sum to {sum(sizes)}, "
                             f"expected {n}")
    spans, at = [], 0
    for s in sizes:
        spans.append((at, at + s))
        at += s
    return ShardPlan(n=n, spans=tuple(spans))


def resolve_shard_mesh(mesh, data_parallel: int | None):
    """The effective mesh of a config's ``mesh=`` / ``data_parallel=``
    knobs, or None for the single-device path.

    An explicit ``mesh`` wins; ``data_parallel=D`` is the convenience
    spelling that builds a ``(D, 1)`` host mesh at run time (device
    availability is checked there, not at config construction, so
    configs stay picklable/buildable before jax initialises devices).
    """
    if mesh is None:
        if not data_parallel or int(data_parallel) <= 1:
            return None
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(data=int(data_parallel))
    if dict(mesh.shape).get("data", 1) <= 1:
        return None
    return mesh


class ShardedDispatch(NamedTuple):
    """Per-shard :class:`~repro.runtime.packing.BatchDispatch` arrays,
    stacked to the cross-shard max grid size. All ids are shard-LOCAL
    (row/dep bases restart at 0 per shard); ``oid`` is -1 on padding
    rows of either origin (ragged image schedules or shard-size
    padding)."""

    row_id: jax.Array    # (D, G_loc) int32
    dep_glb: jax.Array   # (D, G_loc, k_pad) int32
    dep_cnt: jax.Array   # (D, G_loc) int32, 0 on padded slots
    oid: jax.Array       # (D, G_loc) int32, -1 on padding


def shard_batch_schedules(scheds, t_in: int, t_out: int,
                          plan: ShardPlan) -> ShardedDispatch:
    """Concatenate each shard's image schedules independently, then pad
    to the uniform SPMD slab. The per-shard packs keep their own ragged
    ``k_pad``; cross-shard padding rows carry ``dep_cnt = 0`` and repeat
    the shard's last real dep (DMA elision), so uniformity costs no
    real work."""
    if len(scheds) != plan.n:
        raise ValueError(f"{len(scheds)} schedules for a plan of "
                         f"{plan.n} images")
    packs = [pack_batch_schedules(list(scheds[a:b]), t_in, t_out)
             if b > a else None
             for a, b in plan.spans]
    n_rows = scheds[0].n_rows if scheds else t_out
    g_max = plan.n_max * n_rows
    k_max = max((p.dep_glb.shape[1] for p in packs if p is not None),
                default=1)
    rows, deps, cnts, oids = [], [], [], []
    for p in packs:
        if p is None or p.row_id.shape[0] == 0:
            rows.append(jnp.zeros((g_max,), jnp.int32))
            deps.append(jnp.zeros((g_max, k_max), jnp.int32))
            cnts.append(jnp.zeros((g_max,), jnp.int32))
            oids.append(jnp.full((g_max,), -1, jnp.int32))
            continue
        g = p.row_id.shape[0]
        dep = p.dep_glb
        if dep.shape[1] < k_max:
            dep = jnp.pad(dep, ((0, 0), (0, k_max - dep.shape[1])),
                          mode="edge")
        if g < g_max:
            dep = jnp.pad(dep, ((0, g_max - g), (0, 0)), mode="edge")
        rows.append(jnp.pad(p.row_id, (0, g_max - g)))
        deps.append(dep)
        cnts.append(jnp.pad(p.dep_cnt, (0, g_max - g)))
        oids.append(jnp.pad(p.oid, (0, g_max - g), constant_values=-1))
    return ShardedDispatch(
        row_id=jnp.stack(rows).astype(jnp.int32),
        dep_glb=jnp.stack(deps).astype(jnp.int32),
        dep_cnt=jnp.stack(cnts).astype(jnp.int32),
        oid=jnp.stack(oids).astype(jnp.int32))


def stack_rows(flat: jax.Array, plan: ShardPlan, rows: int) -> jax.Array:
    """(n*rows, ...) image-major rows -> (D, n_max*rows, ...) shard
    slabs, zero-padding shards below ``n_max`` images. ``rows`` is the
    per-image row count (tiles per plane, or 1 for whole planes)."""
    slab = plan.n_max * rows
    parts = []
    for a, b in plan.spans:
        blk = flat[a * rows:b * rows]
        pad = slab - blk.shape[0]
        if pad:
            blk = jnp.pad(blk, ((0, pad),) + ((0, 0),) * (blk.ndim - 1))
        parts.append(blk)
    return jnp.stack(parts)


def unstack_rows(stacked: jax.Array, plan: ShardPlan,
                 rows: int) -> jax.Array:
    """Inverse of :func:`stack_rows`: drop shard padding and restore the
    flat image-major row order. On the final logits this is the run's
    single all-gather — every shard's slab crosses to the host/default
    device exactly once."""
    parts = [stacked[s, :(b - a) * rows]
             for s, (a, b) in enumerate(plan.spans) if b > a]
    if not parts:
        out = stacked.reshape((0,) + stacked.shape[2:])
    else:
        out = jnp.concatenate(parts)
    # Land on the default device, so every op after the gather runs
    # with the single-device program (and its reduction order) instead
    # of a GSPMD-partitioned one.
    return jax.device_put(out, jax.devices()[0])


def allgather_nbytes(arr: jax.Array) -> int:
    """Byte volume of gathering ``arr`` from its shards — the measured
    collective cost the scale-out bench reports."""
    return int(arr.size) * int(arr.dtype.itemsize)
