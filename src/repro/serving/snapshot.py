"""Double-buffered, epoch-stamped read snapshots over live-ingesting sketches.

The serving contract (DESIGN.md §Serving): queries never observe a
half-ingested sketch.  Each tenant owns a ``SnapshotBuffer`` with two sides:

  front  — the *published* ``Snapshot``: an immutable, epoch-stamped sketch
           that every query in flight reads.  JAX arrays are immutable, so
           holding the pytree reference IS the isolation mechanism — no
           copies, no locks.
  back   — the *delta*: an ``empty_like`` twin (same layout, routing and
           hash seeds) that absorbs ingest batches.

``publish()`` folds the delta into the front via counter-additive ``merge``
(one elementwise add over the pool — cheap regardless of how many batches
accumulated), bumps the epoch, and resets the delta to zeros.  Readers of the
previous epoch keep their reference and stay consistent; the epoch number is
the cache key for everything derived from a snapshot (notably the boolean
closure matrices cached by the query engine).

Ingest fast path (DESIGN.md §Ingest-fast-path): with ``REPRO_DONATE`` on
(the default) and a ``DONATION_SAFE`` sketch module, the ingest/publish
kernels donate the delta pytree to XLA, which updates the counter buffers
in place instead of round-tripping a fresh depth×budget pytree per
dispatch.  The front is NEVER donated — published snapshots stay immutable
and isolation still costs zero copies.  Donation's one hazard is
use-after-donate (reading a reference that the kernel consumed); every
such path here resolves values under ``_lock`` before the next dispatch
can donate them, ``state()`` hands out private copies, and the
``use-after-donate`` rule in ``repro.analysis`` lints the discipline.
"""
from __future__ import annotations

import dataclasses
import itertools
import os
import threading
from collections import namedtuple
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.types import EdgeBatch
from repro.obs.trace import get_trace_log


@dataclasses.dataclass(frozen=True)
class Snapshot:
    """An immutable point-in-time view of a tenant's sketch.

    ``epoch`` is monotonically increasing per tenant and uniquely identifies
    the counter state: two queries against the same (tenant_id, epoch) are
    guaranteed to see identical answers.
    """

    tenant_id: str
    epoch: int
    sketch: Any  # KMatrix | MatrixSketch | GSketch | CountMin
    kind: str
    n_edges: int  # cumulative non-padding stream updates folded in

    def __repr__(self) -> str:  # keep array payload out of logs
        return (f"Snapshot({self.tenant_id!r}, epoch={self.epoch}, "
                f"kind={self.kind!r}, n_edges={self.n_edges})")


class StaleDelta(RuntimeError):
    """A delta publish was based on an epoch that is not the current front.

    Raised by :meth:`SnapshotBuffer.adopt_published` in delta mode when the
    shipped ``base_epoch`` disagrees with the front's epoch — folding the
    delta in would double- or under-count.  The adopting transport reacts
    by skipping the publish and requesting a full-leaves resync from the
    worker (DESIGN.md §Net, ack-gap rules).
    """


_anon_ids = itertools.count()


def donation_enabled() -> bool:
    """The ``REPRO_DONATE`` kill-switch (default ON).

    Donation makes each ingest dispatch mutate the delta's device buffers in
    place instead of allocating a fresh depth×budget counter pytree per
    batch.  ``REPRO_DONATE=0`` (or ``false``/``off``) restores the copying
    kernels for debugging — bit-identical counters either way, gated by the
    donation parity tests in ``tests/test_ingest_fastpath.py``.
    """
    return os.environ.get("REPRO_DONATE", "1").strip().lower() \
        not in ("0", "false", "off")


# One jitted kernel kit per (sketch MODULE, donate) pair, shared by every
# buffer of that module.  jax.jit caches compilations per wrapped callable:
# a per-buffer lambda would recompile the identical graph once per tenant —
# K shards of one tenant (serving/sharding.py) share a layout, so per-buffer
# caches would pay K compiles for one graph and the sharded ingest wall
# would be mostly XLA compilation.  Distinct layouts/shapes still compile
# separately (jit keys on shapes + statics), so sharing is always safe.
#
#   ingest          (sk, batch, pending)      counts weight>0 on device
#   ingest_counted  (sk, batch, inc, pending) host-supplied count — the
#                   dedup path pre-aggregates (src, dst) rows on the host,
#                   so the device batch no longer carries one row per
#                   stream update and the weight>0 count must come from
#                   the raw items instead
#   publish         (front, delta) -> (merged, zeroed delta)
#   publish_keep    same graph, NEVER donates — for adopt_published (the
#                   incoming delta aliases wire/decoded buffers the caller
#                   still owns) and capture_publish_delta (the stashed
#                   pre-merge reference must outlive the call)
#
# When donating, only the sketch argument is donated — never ``pending``.
# The pending scalar is a fresh 4/8-byte output per dispatch, and holding
# its reference gives callers a completion fence: it becomes ready exactly
# when that dispatch finished executing (SnapshotBuffer.dispatch_token).
# ``publish`` reads it back only when some ingest since the previous
# publish came without a host count; a counted-only epoch is stamped from
# the buffer's host shadow of the same total, so it waits on no dispatch.
_KernelKit = namedtuple(
    "_KernelKit", ["ingest", "ingest_counted", "publish", "publish_keep"])
_KERNELS: dict = {}


def _shared_kernels(mod, donate: bool) -> "_KernelKit":
    key = (mod, bool(donate))
    kit = _KERNELS.get(key)
    if kit is None:
        def _ingest(sk, batch, pending):
            return (mod.ingest(sk, batch),
                    pending + jnp.sum((batch.weight > 0).astype(pending.dtype)))

        def _ingest_counted(sk, batch, inc, pending):
            return mod.ingest(sk, batch), pending + inc

        # One fused publish kernel: fold delta into front, zero the delta.
        # Safe to jit (which skips merge's hash-family check): the delta is
        # empty_like(front) by construction, so the families always match.
        def _publish(front, delta):
            return mod.merge(front, delta), mod.empty_like(delta)

        if donate:
            kit = _KernelKit(
                ingest=jax.jit(_ingest, donate_argnums=(0,)),
                ingest_counted=jax.jit(_ingest_counted, donate_argnums=(0,)),
                publish=jax.jit(_publish, donate_argnums=(1,)),
                # reuse the non-donating kit's publish so the keep variant
                # compiles once per module, not once per (module, donate)
                publish_keep=_shared_kernels(mod, False).publish,
            )
        else:
            jit_publish = jax.jit(_publish)
            kit = _KernelKit(
                ingest=jax.jit(_ingest),
                ingest_counted=jax.jit(_ingest_counted),
                publish=jit_publish,
                publish_keep=jit_publish,
            )
        _KERNELS[key] = kit
    return kit


def _private_copy(tree):
    """Deep-copy every leaf so the result shares no device buffer (and no
    Array object) with ``tree``.  Required before a pytree may be donated:
    ``empty_like``/checkpoint templates can alias hash-family or routing
    leaves with the front sketch by reference, and donating a shared leaf
    would delete it out from under every other holder."""
    return jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), tree)


class SnapshotBuffer:
    """Double buffer: live delta sketch (ingest side) + published Snapshot."""

    def __init__(self, sketch: Any, mod: Any, *, tenant_id: str | None = None,
                 kind: str = "", donate: bool | None = None) -> None:
        self._mod = mod
        # Buffer donation (ISSUE 10): when on, the jitted ingest/publish
        # kernels donate the delta pytree so XLA scatters into the existing
        # device buffers instead of allocating a fresh counter pytree per
        # dispatch.  Requires the sketch module to declare alias-safety
        # (DONATION_SAFE) — its kernels must never need a donated leaf after
        # the call — and honours the REPRO_DONATE kill-switch.  After every
        # donating call the old delta/pending references are DEAD (reading
        # them raises "Array has been deleted"); every read path below
        # therefore resolves values inside _lock and state() hands out
        # private copies.  The use-after-donate analysis rule lints this
        # contract statically.
        env_donate = donation_enabled() if donate is None else bool(donate)
        self.donate = env_donate and bool(getattr(mod, "DONATION_SAFE", False))
        # tenant_id keys every per-(tenant, epoch) cache downstream (notably
        # the engine's closure cache).  Two buffers must never share an id:
        # same-named tenants from differently-configured registries reach
        # the same epoch with different counters, and a shared engine would
        # serve one tenant the other's closures.  The instance suffix makes
        # the id unique per buffer while keeping the readable prefix.
        self._tenant_id = f"{tenant_id or 'anon'}#{next(_anon_ids)}"
        self._kind = kind or getattr(sketch, "kind", type(sketch).__name__.lower())
        self._front = Snapshot(self._tenant_id, 0, sketch,  # guarded-by(writes): _lock
                               self._kind, 0)
        self._delta = mod.empty_like(sketch)  # guarded-by: _lock
        if self.donate:
            # empty_like may reuse hash-family/routing leaves of `sketch`
            # by reference; the delta is about to be donated every dispatch,
            # so it must own every one of its buffers outright
            self._delta = _private_copy(self._delta)
        # device-side counter: avoids a host sync per ingest batch; folded
        # into the ingest kernel so each batch is ONE dispatch
        self._pending = jnp.zeros((), jnp.int64 if jax.config.x64_enabled  # guarded-by: _lock
                                  else jnp.int32)
        # host shadow of _pending: exact while every ingest since the last
        # publish carried a host count, None once one did not
        self._host_pending: int | None = 0  # guarded-by: _lock
        self._kernels = _shared_kernels(mod, self.donate)
        # Delta-publication support (runtime/backend.py): with the flag on,
        # each publish() stashes the pre-merge delta pytree (an immutable
        # reference — zero copies) so a remote worker can ship ONLY what
        # accumulated since the previous epoch instead of the whole sketch.
        self.capture_publish_delta = False
        self.last_publish_delta: Any = None
        # Guards the back buffer (_delta/_pending) and the front swap against
        # a checkpointing thread reading ``state()`` mid-operation.  Readers
        # of ``snapshot`` need no lock: the property is one atomic reference
        # read and the pytree behind it is immutable.
        self._lock = threading.Lock()

    @property
    def snapshot(self) -> Snapshot:
        return self._front

    @property
    def epoch(self) -> int:
        return self._front.epoch

    @property
    def pending_edges(self) -> int:
        """Non-padding updates sitting in the delta (host sync; diagnostics
        and conservation accounting only — not the ingest hot path).

        The device_get happens INSIDE the lock: with donation on, a
        reference captured under the lock can be donated (and deleted) by a
        concurrent ingest the instant the lock is released."""
        with self._lock:
            return int(jax.device_get(self._pending))

    @property
    def overflow_edges(self) -> int:
        """Ingest updates that took the accel backend's scatter-fallback
        (per-partition capacity exceeded), front + live delta.  0 for
        layouts without overflow accounting.  Host sync; diagnostics only —
        surfaced through runtime metrics and the serve bench.  Delta leaf
        resolved inside the lock — see ``pending_edges``."""
        with self._lock:
            front = getattr(self._front.sketch, "overflow", None)
            delta = getattr(self._delta, "overflow", None)
            delta_total = (int(jax.device_get(delta))
                           if delta is not None else 0)
        if front is None:
            return 0
        return int(jax.device_get(front)) + delta_total

    def ingest(self, batch: EdgeBatch, count: int | None = None) -> None:
        """Absorb a batch into the back buffer; published readers unaffected.

        ``count`` (optional) is the number of weight>0 updates the batch
        *represents*.  When the caller pre-aggregated duplicate (src, dst)
        rows on the host (runtime/worker.py dedup path), the dispatched
        rows no longer map 1:1 to stream updates, so the device-side
        weight>0 count would under-report; the host count keeps the pending
        ledger bit-identical to the un-deduped replay.  While every ingest
        since the last publish carries one, ``publish`` stamps the epoch
        from their sum on the host instead of reading the device back.
        """
        with self._lock:
            if count is None:
                self._delta, self._pending = self._kernels.ingest(  # donates: 0
                    self._delta, batch, self._pending)
                self._host_pending = None
            else:
                count = int(count)
                self._delta, self._pending = self._kernels.ingest_counted(  # donates: 0
                    self._delta, batch, count, self._pending)
                if self._host_pending is not None:
                    self._host_pending += count

    def dispatch_token(self):
        """Opaque completion fence for everything dispatched so far.

        Returns the current pending scalar — a (never-donated) output of
        the most recent ingest kernel, so ``jax.block_until_ready`` on it
        returns exactly when that dispatch (and, by device-stream order,
        every earlier one) has finished executing.  The pipelined worker
        uses this to know when a zero-copy host staging buffer may be
        refilled (core/types.EdgeBatch.from_numpy shares memory with its
        numpy inputs on CPU, so reuse-while-in-flight would corrupt the
        dispatch).
        """
        with self._lock:
            return self._pending

    def publish(self) -> Snapshot:
        """Fold the delta into the front buffer and stamp a new epoch.

        The epoch's edge count comes from the host shadow when every
        ingest since the previous publish carried a host count (the dedup
        path): then nothing here waits on the device, and the returned
        front is a future of the merge, as it always was.  After an
        uncounted ingest the pending count is fetched from the device —
        the ingest path's only host sync point, which waits for every
        dispatch in flight.
        """
        with self._lock:
            epoch = self._front.epoch + 1
            if self._host_pending is None:
                with get_trace_log().span("kmatrix.snapshot.publish_sync",
                                          key=epoch):
                    pending = int(jax.device_get(self._pending))
            else:
                with get_trace_log().span(
                        "kmatrix.snapshot.publish_host_count", key=epoch):
                    pending = self._host_pending
            if self.capture_publish_delta:
                # the outgoing delta is exactly what this publish folds in;
                # the reference stays valid (JAX arrays are immutable) —
                # which is also why this path must take the NEVER-donating
                # publish kernel: donating the delta here would delete the
                # stashed reference before the transport ships it
                self.last_publish_delta = self._delta
                kern = self._kernels.publish_keep
            else:
                kern = self._kernels.publish
            merged, delta = kern(self._front.sketch, self._delta)  # donates: 1
            self._front = Snapshot(
                self._tenant_id,
                epoch,
                merged,
                self._kind,
                self._front.n_edges + pending,
            )
            self._delta = delta
            self._pending = jnp.zeros_like(self._pending)
            self._host_pending = 0
            return self._front

    def adopt_published(self, sketch: Any, epoch: int, n_edges: int, *,
                        delta: Any = None,
                        base_epoch: int | None = None) -> Snapshot:
        """Install an externally-produced published front (runtime/backend.py).

        The remote execution backends fold batches into a sketch living in
        a child process and ship each published epoch back; this swaps that
        state in as the new front WITHOUT touching the local delta (which
        stays empty — the remote side owns the write path).  Same isolation
        contract as ``publish``: readers holding the previous front keep a
        consistent immutable epoch.  The caller must adopt epochs in
        publication order (the backend's FIFO result pipe guarantees that).

        Two modes:

          full   ``sketch`` is the worker's whole published front;
                 installed verbatim (replace).
          delta  ``sketch`` is ignored; ``delta`` is the pytree the worker
                 accumulated since its previous publish, and is folded into
                 the current front through the SAME jitted merge the
                 worker's own publish used — bit-identical counters on both
                 sides.  ``base_epoch`` must equal the current front epoch
                 or the fold would mis-count: any gap raises
                 :class:`StaleDelta` (the transport then requests a
                 full-leaves resync).
        """
        with self._lock:
            if delta is not None:
                if base_epoch is None or int(base_epoch) != self._front.epoch:
                    raise StaleDelta(
                        f"delta publish for epoch {epoch} is based on epoch "
                        f"{base_epoch}, but the front is at epoch "
                        f"{self._front.epoch}; a full resync is required")
                # publish_keep, never the donating kernel: the incoming
                # delta's leaves are decoded wire views whose host buffers
                # the transport still owns — donation would write into them
                sketch, _ = self._kernels.publish_keep(
                    self._front.sketch, delta)
            self._front = Snapshot(self._tenant_id, int(epoch),
                                   sketch, self._kind, int(n_edges))
            return self._front

    # ------------------------------------------------------------ checkpoint
    def state(self) -> dict:
        """Mutually-consistent (front, delta, pending, epoch, n_edges) view.

        The returned pytrees are immutable JAX arrays, so the caller can
        serialize them outside the lock (crash-safe checkpointing in
        ``repro.runtime``).  The front is always safe to hand out by
        reference (it is never donated); with donation on, the delta and
        pending are handed out as PRIVATE COPIES — the live references get
        donated (deleted) by the very next ingest, which would leave the
        caller serializing dead buffers.
        """
        with self._lock:
            delta, pending = self._delta, self._pending
            if self.donate:
                delta = _private_copy(delta)
                pending = jnp.array(pending, copy=True)
            return {
                "front": self._front.sketch,
                "delta": delta,
                "pending": pending,
                "epoch": self._front.epoch,
                "n_edges": self._front.n_edges,
            }

    def load_state(self, state: dict) -> Snapshot:
        """Restore a checkpointed ``state()`` (same sketch layout required)."""
        with self._lock:
            self._front = Snapshot(
                self._tenant_id,
                int(state["epoch"]),
                jax.tree_util.tree_map(jnp.asarray, state["front"]),
                self._kind,
                int(state["n_edges"]),
            )
            # jnp.asarray is a zero-copy identity on device arrays and can
            # share memory with host numpy buffers on CPU; a delta about to
            # be donated must own private buffers, so copy outright
            restore = _private_copy if self.donate \
                else (lambda t: jax.tree_util.tree_map(jnp.asarray, t))
            self._delta = restore(state["delta"])
            self._pending = jnp.array(state["pending"],
                                      dtype=self._pending.dtype, copy=True)
            # a restore is off the hot path: read the restored count once
            # so the next counted-only epoch needs no device read either
            self._host_pending = int(jax.device_get(self._pending))
            return self._front
