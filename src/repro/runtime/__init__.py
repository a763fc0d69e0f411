"""repro.runtime — background ingest behind the serving engine.

Turns the PR 1 registry into a genuinely concurrent system (DESIGN.md
§Runtime): per-tenant ``IngestWorker`` threads pull stream batches from
bounded queues with explicit backpressure (block / drop-oldest / spill,
all drops accounted), fold them into the registry's delta sketch, and
publish epochs under a pluggable ``PublishPolicy``; the ``Runtime``
supervisor owns worker lifecycle (start, health, graceful drain-and-stop,
crash-like kill), the per-tenant online reservoir sample, crash-safe
checkpointing through ``repro.checkpoint.store``, and live metrics (queue
depth, ingest lag, edges/s, publish latency, epoch age).

Since PR 5 the worker's execution venue is an **execution backend**
(``runtime/backend.py``): ``backend="thread"`` keeps the classic in-process
worker threads; ``backend="process"`` runs each worker in a spawn-safe
multiprocessing child that owns its sketch and ships epoch-stamped
snapshot publications back into the parent's ``SnapshotBuffer`` — K-shard
ingest then scales past the GIL.

Entry points: ``launch/query_serve.py --background-ingest
[--runtime-backend process] [--shards K]`` and the chip benchmark
(``bench/``).
"""
from repro.runtime.backend import (
    ExecutionBackend,
    ProcessBackend,
    ProcessWorker,
    ThreadBackend,
    WorkerFailure,
    resolve_backend,
)
from repro.runtime.metrics import RateEWMA, WorkerMetrics
from repro.runtime.policies import (
    EveryNBatches,
    PublishPolicy,
    QueueDrainWatermark,
    WallClockInterval,
    make_policy,
)
from repro.runtime.queueing import (
    BACKPRESSURE_POLICIES,
    BLOCK,
    DROP_OLDEST,
    SPILL,
    BoundedEdgeQueue,
    QueueItem,
)
from repro.runtime.supervisor import Runtime, StreamPump, TenantRuntime
from repro.runtime.worker import (
    CREATED,
    DRAINING,
    FAILED,
    RUNNING,
    STOPPED,
    IngestWorker,
    restore_worker_state,
)

__all__ = [
    "ExecutionBackend",
    "ProcessBackend",
    "ProcessWorker",
    "ThreadBackend",
    "WorkerFailure",
    "resolve_backend",
    "RateEWMA",
    "WorkerMetrics",
    "EveryNBatches",
    "PublishPolicy",
    "QueueDrainWatermark",
    "WallClockInterval",
    "make_policy",
    "BACKPRESSURE_POLICIES",
    "BLOCK",
    "DROP_OLDEST",
    "SPILL",
    "BoundedEdgeQueue",
    "QueueItem",
    "Runtime",
    "StreamPump",
    "TenantRuntime",
    "IngestWorker",
    "restore_worker_state",
    "CREATED",
    "RUNNING",
    "DRAINING",
    "STOPPED",
    "FAILED",
]
