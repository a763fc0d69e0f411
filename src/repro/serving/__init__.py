"""repro.serving — the online query-serving layer over live sketches.

Turns the offline reproduction into an always-on service (DESIGN.md
§Serving):

  registry  multi-tenant sketch registry; owns per-tenant ingest loops
  snapshot  double-buffered epoch-stamped read snapshots (snapshot isolation)
  engine    batched query planner: heterogeneous requests -> dense jitted
            calls, with per-(tenant, epoch) closure caching for reachability
  loadgen   open-loop load generator reporting QPS and p50/p99 latency

Entry points: ``launch/query_serve.py`` (ingest + serving end to end) and
the chip benchmark (``bench/``).
"""
from repro.serving.engine import (
    ClosureCache,
    QueryEngine,
    Request,
    Result,
    edge_freq,
    heavy_nodes,
    node_in,
    node_out,
    path_weight,
    reach,
    subgraph_weight,
)
from repro.serving.loadgen import (
    LoadReport,
    OpenLoopLoadGen,
    WorkloadMix,
    mix_for_sketch,
    synth_requests,
    warm_bucket_ladder,
)
from repro.serving.registry import SketchRegistry, Tenant, TenantKey
from repro.serving.sharding import (
    ShardKey,
    ShardStreamView,
    ShardedQueryEngine,
    ShardedSnapshot,
    ShardedTenant,
    attach_shards,
    read_shard_manifest,
    sharded_conservation,
    sharded_direct_answers,
    write_shard_manifest,
)
from repro.serving.snapshot import Snapshot, SnapshotBuffer

__all__ = [
    "ShardKey",
    "ShardStreamView",
    "ShardedQueryEngine",
    "ShardedSnapshot",
    "ShardedTenant",
    "attach_shards",
    "read_shard_manifest",
    "sharded_conservation",
    "sharded_direct_answers",
    "write_shard_manifest",
    "ClosureCache",
    "QueryEngine",
    "Request",
    "Result",
    "edge_freq",
    "heavy_nodes",
    "node_in",
    "node_out",
    "path_weight",
    "reach",
    "subgraph_weight",
    "LoadReport",
    "OpenLoopLoadGen",
    "WorkloadMix",
    "mix_for_sketch",
    "synth_requests",
    "warm_bucket_ladder",
    "SketchRegistry",
    "Tenant",
    "TenantKey",
    "Snapshot",
    "SnapshotBuffer",
]
