"""Multi-tenant sketch registry: one live-ingesting sketch per tenant key.

A *tenant* is one (dataset, sketch kind, budget, seed) combination — the unit
of isolation for the always-on query service.  The registry owns, per tenant:

  * the seekable stream (batch i is a pure function of (seed, i)),
  * the bootstrap sample -> VertexStats -> partition plan,
  * the ingest loop position (next unread batch), and
  * the ``SnapshotBuffer`` holding the live delta + published snapshot.

``launch/query_serve.py`` (cooperative mode) drives tenants by
alternating ``tenant.step(n)`` (ingest) with engine query batches against
``tenant.snapshot`` — the double buffer guarantees the queries stay
epoch-consistent while ingest runs.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Iterator

from repro.core import (
    CountMin,
    GSketch,
    KMatrix,
    KMatrixAccel,
    MatrixSketch,
    vertex_stats_from_sample,
)
from repro.core import sketch_backend as resolve_sketch_backend
from repro.core import countmin, gsketch, kmatrix, kmatrix_accel, matrix_sketch
from repro.obs.trace import get_trace_log
from repro.serving.snapshot import Snapshot, SnapshotBuffer
from repro.streams import make_stream, sample_stream

SKETCHES = {
    "countmin": (CountMin, countmin),
    "gsketch": (GSketch, gsketch),
    "tcm": (MatrixSketch, matrix_sketch),
    "gmatrix": (MatrixSketch, matrix_sketch),
    "kmatrix": (KMatrix, kmatrix),
}


def build_sketch(name: str, budget: int, stats, depth: int, seed: int,
                 partitioner: str = "banded", backend: str | None = None):
    """Construct any sketch kind from a byte budget (+ stats if partitioned).

    For ``kmatrix`` the physical layout is a *backend* choice
    (``sketch_backend``: arg > $REPRO_SKETCH_BACKEND > platform default):
    ``pallas`` builds the width-class ``KMatrixAccel`` whose ingest runs the
    MXU kernel, ``flat`` the classic flat-pool scatter ``KMatrix``.  Every
    layer above (snapshots, workers, engine, checkpoints) is
    layout-agnostic, dispatching on the returned module.
    """
    cls, mod = SKETCHES[name]
    if name == "countmin":
        return cls.create(bytes_budget=budget, depth=depth, seed=seed), mod
    if name in ("tcm", "gmatrix"):
        return cls.create(bytes_budget=budget, depth=depth, seed=seed,
                          kind=name), mod
    if name == "gsketch":
        return cls.create(bytes_budget=budget, stats=stats, depth=depth,
                          seed=seed), mod
    if resolve_sketch_backend(backend) == "pallas":
        return KMatrixAccel.create(
            bytes_budget=budget, stats=stats, depth=depth, seed=seed,
            partitioner=partitioner), kmatrix_accel
    return cls.create(bytes_budget=budget, stats=stats, depth=depth,
                      seed=seed, partitioner=partitioner), mod


@dataclasses.dataclass(frozen=True)
class TenantKey:
    dataset: str
    kind: str
    budget_kb: int
    seed: int = 0

    @property
    def tenant_id(self) -> str:
        return f"{self.dataset}/{self.kind}/{self.budget_kb}kb/s{self.seed}"


@dataclasses.dataclass(frozen=True)
class TenantOrigin:  # wire-type
    """How to rebuild a registry-opened tenant from scratch, anywhere.

    Tenant construction is deterministic — stream, bootstrap sample,
    partition plan and hash family are all pure functions of the registry
    config + the open() arguments — so this small picklable spec is enough
    for another address space (the process execution backend's spawn-safe
    children, ``runtime/backend.py``) to rebuild a tenant with the
    *identical* sketch layout, making shipped counter pytrees loadable
    leaf-for-leaf on either side.
    """

    registry: dict  # SketchRegistry(**registry) reproduces the config
    dataset: str
    kind: str
    budget_kb: int
    seed: int = 0
    # set only for shard tenants (one shard of an open_sharded tenant)
    n_shards: int | None = None
    shard_seed: int | None = None
    shard_index: int | None = None

    def rebuild(self) -> "Tenant":
        reg = SketchRegistry(**self.registry)
        if self.n_shards is None:
            return reg.open(self.dataset, self.kind, self.budget_kb,
                            seed=self.seed)
        sharded = reg.open_sharded(self.dataset, self.kind, self.budget_kb,
                                   seed=self.seed, n_shards=self.n_shards,
                                   shard_seed=self.shard_seed)
        return sharded.shards[self.shard_index]


class Tenant:
    """One registered sketch + its stream position + snapshot buffer.

    ``offset``/``step`` are owned by exactly one ingest driver at a time:
    either the cooperative caller of ``step()`` or (exclusively) a
    ``repro.runtime`` worker thread.  ``snapshot`` is safe to read from any
    thread at any time (immutable reference swap).
    """

    def __init__(self, key: TenantKey, stream, buffer: SnapshotBuffer,
                 mod) -> None:
        self.key = key
        self.stream = stream
        self.buffer = buffer
        self.mod = mod
        self.offset = 0  # next stream batch to ingest
        # rebuild spec stamped by the registry (None for hand-built tenants;
        # the process execution backend requires it)
        self.origin: TenantOrigin | None = None

    @property
    def snapshot(self) -> Snapshot:
        return self.buffer.snapshot

    @property
    def epoch(self) -> int:
        return self.buffer.epoch

    @property
    def exhausted(self) -> bool:
        return self.offset >= self.stream.num_batches

    def step(self, n_batches: int = 1) -> int:
        """Ingest up to ``n_batches`` more stream batches into the live delta.

        Returns the number actually consumed (0 once the stream is drained).
        """
        done = 0
        while done < n_batches and not self.exhausted:
            self.buffer.ingest(self.stream.batch(self.offset))
            self.offset += 1
            done += 1
        return done

    def publish(self) -> Snapshot:
        return self.buffer.publish()


class SketchRegistry:
    """Registry of live tenants, keyed by (dataset, kind, budget, seed)."""

    def __init__(self, *, depth: int = 5, batch_size: int = 8192,
                 sample_size: int = 30_000, scale: float = 1.0,
                 partitioner: str = "banded",
                 sketch_backend: str | None = None) -> None:
        self.depth = depth
        self.batch_size = batch_size
        self.sample_size = sample_size
        self.scale = scale
        self.partitioner = partitioner
        # resolved once at registry build, not per tenant open: a registry
        # whose tenants straddle two layouts would break merge/restore
        # interchange assumptions downstream
        self.sketch_backend = resolve_sketch_backend(sketch_backend)
        self._tenants: dict[TenantKey, Tenant] = {}
        self._sharded: dict = {}  # (key, n_shards, shard_seed) -> ShardedTenant
        # get-or-create must be atomic once background workers can race
        # opens: two tenants for one key would double-ingest the stream
        self._lock = threading.Lock()

    def config(self) -> dict:
        """The constructor kwargs that reproduce this registry (all plain
        picklable values; ``sketch_backend`` ships resolved so a rebuild on
        a different platform still picks the same layout)."""
        return {
            "depth": self.depth,
            "batch_size": self.batch_size,
            "sample_size": self.sample_size,
            "scale": self.scale,
            "partitioner": self.partitioner,
            "sketch_backend": self.sketch_backend,
        }

    def open(self, dataset: str, kind: str, budget_kb: int,
             seed: int = 0) -> Tenant:
        """Get-or-create the tenant for a key (idempotent, thread-safe)."""
        key = TenantKey(dataset, kind, budget_kb, seed)
        with self._lock:
            if key in self._tenants:
                return self._tenants[key]
        stream, sketch, mod = self._bootstrap(key)
        with self._lock:
            if key in self._tenants:  # lost the build race; first one wins
                return self._tenants[key]
            with get_trace_log().span("kmatrix.registry.alloc",
                                      key=key.tenant_id):
                buffer = SnapshotBuffer(sketch, mod, tenant_id=key.tenant_id,
                                        kind=kind)
            tenant = Tenant(key, stream, buffer, mod)
            tenant.origin = TenantOrigin(self.config(), dataset, kind,
                                         budget_kb, seed)
            self._tenants[key] = tenant
            return tenant

    def open_sharded(self, dataset: str, kind: str, budget_kb: int,
                     seed: int = 0, *, n_shards: int, shard_seed: int = 0):
        """Get-or-create a ``ShardedTenant``: K shard tenants over ONE layout.

        The master sketch is built exactly like ``open`` would build it
        (same stream, same bootstrap sample, same partition plan and hash
        family) and every shard gets an ``empty_like`` clone — that shared
        layout is what makes the merge of the shards bit-identical to an
        unsharded ingest of the same stream (DESIGN.md §Sharding).  Each
        shard's stream is a ``ShardStreamView`` filtering the base stream by
        the ``ShardPlan`` hash band of the source vertex.
        """
        from repro.core.partitioning import ShardPlan
        from repro.serving.sharding import (ShardKey, ShardStreamView,
                                            ShardedTenant)

        key = TenantKey(dataset, kind, budget_kb, seed)
        skey = (key, n_shards, shard_seed)
        with self._lock:
            if skey in self._sharded:
                return self._sharded[skey]
        stream, sketch, mod = self._bootstrap(key)
        plan = ShardPlan(n_shards, seed=shard_seed)
        shards = []
        with get_trace_log().span("kmatrix.registry.alloc",
                                  key=key.tenant_id):
            for s in range(n_shards):
                shard_key = ShardKey(key, s, n_shards)
                view = ShardStreamView(stream, plan, s)
                buffer = SnapshotBuffer(mod.empty_like(sketch), mod,
                                        tenant_id=shard_key.tenant_id,
                                        kind=kind)
                shard = Tenant(shard_key, view, buffer, mod)
                shard.origin = TenantOrigin(
                    self.config(), dataset, kind, budget_kb, seed,
                    n_shards=n_shards, shard_seed=shard_seed, shard_index=s)
                shards.append(shard)
        tenant = ShardedTenant(key, plan, shards, mod)
        with self._lock:
            if skey in self._sharded:  # lost the build race; first one wins
                return self._sharded[skey]
            self._sharded[skey] = tenant
            return tenant

    def _bootstrap(self, key: TenantKey):
        """(stream, sketch, module) of a key: the seekable stream, its
        bootstrap sample, and the sketch built from the sample's partition
        plan, with zeroed counters.  The set-up phases are spans keyed by
        the tenant id (``kmatrix.registry.sample``, ``.plan``; the caller's
        ``.alloc`` covers the snapshot buffers)."""
        span = get_trace_log().span
        stream = make_stream(key.dataset, batch_size=self.batch_size,
                             seed=key.seed, scale=self.scale)
        # Paper §V-A: a reservoir sample of the stream bootstraps the
        # partitioner before any counter is allocated.
        n_sample = max(int(self.sample_size * self.scale), 1000)
        with span("kmatrix.registry.sample", key=key.tenant_id):
            sample = sample_stream(stream, n_sample, seed=key.seed + 1)
        # build_sketch plans and zeroes the sketch in one call; the zeroing
        # is dispatched, not waited for, so this span is the plan's
        with span("kmatrix.registry.plan", key=key.tenant_id):
            stats = vertex_stats_from_sample(*sample)
            sketch, mod = build_sketch(key.kind, key.budget_kb * 1024, stats,
                                       self.depth, key.seed, self.partitioner,
                                       backend=self.sketch_backend)
        return stream, sketch, mod

    def get(self, key: TenantKey) -> Tenant:
        return self._tenants[key]

    def __contains__(self, key: TenantKey) -> bool:
        return key in self._tenants

    def __len__(self) -> int:
        return len(self._tenants)

    def tenants(self) -> Iterator[Tenant]:
        return iter(self._tenants.values())

    def step_all(self, n_batches: int = 1) -> int:
        """Advance every tenant's ingest loop; returns total batches consumed."""
        return sum(t.step(n_batches) for t in self.tenants())

    def publish_all(self) -> list[Snapshot]:
        return [t.publish() for t in self.tenants()]
