"""The network-graph runtime — the paper's execution path.

Per deformable layer and batch:

  stage 1   offset conv -> sampling coordinates      (core.deform)
  TDT       coords -> tile dependency table          (core.tiles)
  schedule  Algorithm 1 / sequential ordering        (core.scheduler)
  pack      per-pixel (idx, coeff) tensors and the
            batch grid's dep tables, padded for
            shapes not divisible by the tile size    (runtime.packing)
  execute   fused BLI(+)conv Pallas kernel over the
            batch's scheduled tiles, scattered back
            into the (N, H, W, C_out) output         (kernels.dcn_fused)

A graph IR over the model backbone (runtime.graph) is partitioned into
cross-layer fused groups (§IV-D network-wide); each group runs ONE
Algorithm-1 schedule over a composite TDT chained through its layers
(runtime.fused_exec), and one deformable layer is a one-node graph
(``dcn_pipeline``). The staging queue overlaps prepass with execution
(runtime.staging). Host-side schedules are memoized in an LRU keyed on
quantized coordinates (runtime.cache).

Executors emit traces (runtime.trace) whose byte counts are cross-checked
against the DRAM-traffic simulator in benchmarks/bench_scheduling.py,
bench_fusion.py and bench_graph.py.
"""

from repro.runtime.cache import ScheduleCache, default_schedule_cache
from repro.runtime.fused_exec import (
    GraphConfig,
    TileBuffer,
    dcn_pipeline,
    run_graph,
    run_graph_dense,
)
from repro.runtime.graph import (
    ConvNode,
    DeformNode,
    FusedGroup,
    NetGraph,
    PoolNode,
    UpsampleNode,
    build_graph,
    partition_graph,
    partition_graph_cached,
    partition_graph_tuned,
)
from repro.runtime.packing import (
    BatchDispatch,
    NeighbourTables,
    build_neighbour_tables,
    pack_batch_schedules,
    pack_output_tile,
    pack_plane_operands,
    plane_to_tiles,
)
from repro.runtime.staging import clamp_tile_config
from repro.runtime.shard import (
    ShardPlan,
    plan_batch_shards,
    resolve_shard_mesh,
)
from repro.runtime.trace import (
    GroupTrace,
    ImageTrace,
    LatencyStats,
    LayerBufferStats,
    NetworkTrace,
    OverlapSpans,
    TileRecord,
)

__all__ = [
    "BatchDispatch",
    "NeighbourTables",
    "build_neighbour_tables",
    "pack_batch_schedules",
    "pack_output_tile",
    "pack_plane_operands",
    "plane_to_tiles",
    "dcn_pipeline",
    "ScheduleCache",
    "default_schedule_cache",
    "ShardPlan",
    "plan_batch_shards",
    "resolve_shard_mesh",
    "GraphConfig",
    "TileBuffer",
    "clamp_tile_config",
    "run_graph",
    "run_graph_dense",
    "ConvNode",
    "DeformNode",
    "FusedGroup",
    "NetGraph",
    "PoolNode",
    "UpsampleNode",
    "build_graph",
    "partition_graph",
    "partition_graph_cached",
    "partition_graph_tuned",
    "GroupTrace",
    "ImageTrace",
    "LatencyStats",
    "LayerBufferStats",
    "NetworkTrace",
    "OverlapSpans",
    "TileRecord",
]
