"""The cell driver: one general generator for every traffic mix.

It drives the program's served path through its public pieces only:
``SketchRegistry.open`` builds the tenant (sample, partition plan, sketch),
``Runtime.attach(pump=False)`` and ``start()`` bring up the bounded queue and
the ingest worker, the benchmark's producer thread feeds edges through
``TenantRuntime.submit``, and, where the mix has queries, the benchmark's
open-loop query thread sends them to ``QueryEngine.execute`` on
``tenant.snapshot``.  No backend is pinned: the layout the platform default
picks is reported.

What a mix may set (``bench/traffic/<name>.json``):

``client_batch``      edges per client batch submitted
``ingest``            ``{"mode": "saturate"}`` (keep the queue full) or
                      ``{"mode": "rate", "edges_per_s": R}`` (open loop)
``publish_policy``    the runtime's publish policy (``every:N``, ``drain``)
``warm_epochs``       epochs published before the window opens (saturate)
``warm_s``            seconds of traffic before the window opens (rate)
``queries``           absent, or ``{"qps", "mix", "zipf_a", "batch_max",
                      "heavy_universe_max", "heavy_threshold", "path_len",
                      "subgraph_edges", "check_answers"}``
"""
from __future__ import annotations

import dataclasses
import sys
import threading
import time

import numpy as np

from kbench import loadgen
from kbench.stream import Lap


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Window:
    """Everything the window recorded; all times on ``time.perf_counter``."""

    t_open: float = 0.0
    t_close: float = 0.0
    # per submitted client batch: (due, submit start, submit end,
    # queue depth just before the put, cumulative edges after it)
    batches: list = dataclasses.field(default_factory=list)
    # per publish: (time, n_edges, epoch), appended by the worker thread
    publishes: list = dataclasses.field(default_factory=list)
    # per engine call: (first request index, last + 1, start, end,
    # snapshot n_edges, snapshot epoch, values or None on error)
    query_batches: list = dataclasses.field(default_factory=list)
    query_wake_late: list = dataclasses.field(default_factory=list)
    query_errors: int = 0
    compiles: list = dataclasses.field(default_factory=list)
    dedup_start: tuple = (0, 0)
    dedup_end: tuple = (0, 0)


class Cell:
    """One configuration under one traffic mix, for one seed."""

    def __init__(self, cfg: dict, traffic: dict, seed: int) -> None:
        self.cfg = cfg
        self.traffic = traffic
        self.seed = int(seed)
        self.w = Window()
        self._stop = threading.Event()
        self.submitted_edges = 0
        self.requests: list = []
        self.engine = None
        self.setup_phases: dict = {}

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        """Build the tenant and runtime and warm every shape the window
        uses.  Everything here counts as set-up."""
        import jax
        from repro.core.types import EdgeBatch
        from repro.runtime.supervisor import Runtime
        from repro.serving.registry import SketchRegistry

        g, sk, rt = self.cfg["graph"], self.cfg["sketch"], self.cfg["runtime"]
        tr = self.traffic
        phases = self.setup_phases
        t = time.perf_counter()
        self.lap = Lap(g, sk["registry_batch_size"], self.seed,
                       tr["client_batch"])
        self.registry = SketchRegistry(
            depth=sk["depth"], batch_size=sk["registry_batch_size"],
            sample_size=sk["sample_size"], scale=g["scale"],
            partitioner=sk["partitioner"])
        phases["lap_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.tenant = self.registry.open(g["dataset"], sk["kind"],
                                         sk["budget_kb"],
                                         seed=g["graph_seed"])
        phases["registry_open_s"] = time.perf_counter() - t
        t = time.perf_counter()
        spec = self.tenant.stream.spec
        if (spec.n_nodes, spec.n_edges, spec.alpha_src, spec.alpha_dst) != (
                g["n_nodes"], g["n_edges"], g["alpha_src"], g["alpha_dst"]):
            raise SystemExit(f"configuration graph {g} does not match the "
                             f"program's stream {spec}")
        sketch = self.tenant.snapshot.sketch
        self.layout = "pallas" if hasattr(sketch, "pools") else "flat"
        self.runtime = Runtime(
            queue_capacity=rt["queue_capacity"],
            backpressure=rt["backpressure"],
            publish_policy=tr["publish_policy"], dedup=rt["dedup"])
        self.handle = self.runtime.attach(self.tenant, pump=False,
                                          on_publish=self._on_publish)
        # the dispatch shapes: every padded row count the worker can send
        # (its granule rule, ``IngestWorker._ingest_coalesced``, read off
        # the worker this runtime built), warmed with weight-0 rows
        # (padding by the EdgeBatch contract) and a zero count, so no
        # counter moves
        buf = self.tenant.buffer
        worker = self.handle.worker
        granule = max(256, worker.coalesce_target // 4)
        top = tr["client_batch"] * worker.coalesce_batches
        for rows in range(granule, top + granule, granule):
            z = np.zeros(rows, np.int32)
            buf.ingest(EdgeBatch.from_numpy(z, z, z), count=0)
        buf.publish()
        jax.block_until_ready(buf.dispatch_token())
        phases["warm_ingest_publish_s"] = time.perf_counter() - t
        t = time.perf_counter()
        q = tr.get("queries")
        if q:
            from repro.serving.engine import QueryEngine

            self.engine = QueryEngine()
            n_nodes = g["n_nodes"]
            self.requests = loadgen.synth_requests(
                int(q["qps"] * (tr["warm_s"] + 60)) + q["batch_max"],
                q["mix"], n_nodes=n_nodes, seed=self.seed,
                zipf_a=q["zipf_a"], path_len=q["path_len"],
                subgraph_edges=q["subgraph_edges"],
                heavy_universe=min(n_nodes, q["heavy_universe_max"]),
                heavy_threshold=q["heavy_threshold"])
            for batch in loadgen.engine_shapes(self.requests,
                                               q["batch_max"]):
                self.engine.execute(self.tenant.snapshot, batch)
            phases["warm_engine_s"] = time.perf_counter() - t
        self.runtime.start()

    # ------------------------------------------------------------ threads
    def _on_publish(self, snap) -> None:
        self.w.publishes.append((time.perf_counter(), snap.n_edges,
                                 snap.epoch))

    def _submit(self, k: int, due: float) -> bool:
        src, dst, w = self.lap.client_batch_numpy(k)
        depth = self.handle.queue.depth()
        t_a = time.perf_counter()
        with _annotate("bench.submit"):
            while not self.handle.submit(src, dst, w, timeout=0.25):
                if self._stop.is_set():
                    return False
        t_b = time.perf_counter()
        self.submitted_edges += int(np.count_nonzero(w > 0))
        self.w.batches.append((due if due else t_a, t_a, t_b, depth,
                               self.submitted_edges))
        return True

    def _produce(self) -> None:
        mode = self.traffic["ingest"]["mode"]
        k = 0
        if mode == "saturate":
            while not self._stop.is_set():
                if self._submit(k, 0.0):
                    k += 1
            return
        interval = self.traffic["client_batch"] \
            / float(self.traffic["ingest"]["edges_per_s"])
        t0 = self._t_traffic
        while True:
            due = t0 + k * interval
            if self._closing(due):
                return
            now = time.perf_counter()
            if due > now:
                with _annotate("bench.producer_wait"):
                    time.sleep(due - now)
                continue
            if not self._submit(k, due):
                return
            k += 1

    def _closing(self, due: float) -> bool:
        """No more work once the window has closed, or past its end."""
        t_close = self.w.t_close
        return self._stop.is_set() or (t_close > 0 and due >= t_close)

    def _query(self) -> None:
        q = self.traffic["queries"]
        interval = 1.0 / float(q["qps"])
        batch_max = int(q["batch_max"])
        reqs, n_req = self.requests, len(self.requests)
        t0 = self._t_traffic
        i = 0
        slept = False
        while True:
            due = t0 + i * interval
            if self._closing(due):
                return
            now = time.perf_counter()
            if due > now:
                with _annotate("bench.query_wait"):
                    time.sleep(min(due - now, 0.05))
                slept = True
                continue
            if slept:
                self.w.query_wake_late.append((due, now - due))
                slept = False
            j = i + 1
            while (j - i < batch_max and t0 + j * interval <= now
                   and not self._closing(t0 + j * interval)):
                j += 1
            batch = [reqs[x % n_req] for x in range(i, j)]
            snap = self.tenant.snapshot
            with _annotate("bench.query_execute"):
                t_a = time.perf_counter()
                try:
                    values = [r.value for r in
                              self.engine.execute(snap, batch)]
                except Exception as exc:  # noqa: BLE001 — counted, reported
                    log(f"engine error: {exc!r}")
                    self.w.query_errors += j - i
                    values = None
                t_b = time.perf_counter()
            self.w.query_batches.append((i, j, t_a, t_b, snap.n_edges,
                                         snap.epoch, values))
            i = j

    # ------------------------------------------------------------- window
    def run(self, seconds: float, trace_dir: str | None = None) -> Window:
        """Warm traffic, then measure for ``seconds``, then drain."""
        import jax

        def on_compile(event, duration, **_):
            if "backend_compile" in event:
                self.w.compiles.append((time.perf_counter(), duration))

        jax.monitoring.register_event_duration_secs_listener(on_compile)
        tr = self.traffic
        self._t_traffic = time.perf_counter() + 0.05
        threads = [threading.Thread(target=self._produce, name="bench-producer",
                                    daemon=True)]
        if tr.get("queries"):
            threads.append(threading.Thread(target=self._query,
                                            name="bench-queries",
                                            daemon=True))
        for t in threads:
            t.start()
        t_warm = time.perf_counter()
        self._warm(tr)
        self.setup_phases["warm_traffic_s"] = time.perf_counter() - t_warm
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self.w.dedup_start = self._dedup_rows()
        window = _annotate("bench.window")
        window.__enter__()
        self.w.t_open = time.perf_counter()
        self.w.t_close = self.w.t_open + seconds
        time.sleep(seconds)
        window.__exit__(None, None, None)
        self.w.dedup_end = self._dedup_rows()
        if trace_dir:
            jax.profiler.stop_trace()
        self._stop.set()
        for t in threads:
            t.join(timeout=120)
            if t.is_alive():
                raise RuntimeError(f"{t.name} did not stop")
        self.report = self.runtime.stop(drain=True, timeout=300)
        return self.w

    def _dedup_rows(self) -> tuple:
        snap = self.handle.worker.metrics_snapshot()
        return snap["dedup_raw_rows"], snap["dedup_unique_rows"]

    def _warm(self, tr: dict) -> None:
        """Traffic before the window: the queue filled and a few epochs
        published (saturating mixes), or a fixed time (rate mixes)."""
        if tr["ingest"]["mode"] == "saturate":
            cap = self.cfg["runtime"]["queue_capacity"]
            deadline = time.perf_counter() + 240
            while time.perf_counter() < deadline:
                full = self.handle.queue.stats()["max_depth_seen"] >= cap
                if full and len(self.w.publishes) >= tr["warm_epochs"]:
                    return
                time.sleep(0.01)
            raise RuntimeError("warm-up never filled the queue")
        time.sleep(tr["warm_s"])

    # ----------------------------------------------------------- results
    def final_counters(self) -> tuple[int, dict]:
        """(published n_edges, host copy of every counter array) of the
        final snapshot, in the program's own shapes."""
        snap = self.tenant.snapshot
        sk = snap.sketch
        if self.layout == "pallas":
            blocks = {f"pool.{c}": np.asarray(p) for c, p in
                      enumerate(sk.pools)}
        else:
            blocks = {"pool.0": np.asarray(sk.pool)}
        blocks["conn"] = np.asarray(sk.conn)
        return int(snap.n_edges), blocks

    def release(self) -> None:
        """Drop every reference to the program's device state."""
        for name in ("runtime", "handle", "tenant", "registry", "engine"):
            setattr(self, name, None)
