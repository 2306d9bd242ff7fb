"""Continuous batching for conv-net serving: async request queue,
deadline-driven batch formation, multi-model LRU program cache.

The paper's full-board mode (§5.2: ~20 replicated IP cores, 4.48 GOPS) is
a *serving* configuration — the fabric earns its throughput only if the
host keeps its lanes full.  The submit-and-wait engine this module
replaces did not: every caller blocked on its own microbatch, partial
batches burned padded lanes, and each network needed its own engine and
compiled program.  The FPGA-CNN acceleration surveys (Guo et al. 2017,
Jiang et al. 2025 — PAPERS.md) both name batch scheduling and on-chip
resource reuse, not raw MACs, as what decides deployed throughput; this
is the host half of that argument.

Three pieces, composable and individually testable:

* :class:`RequestQueue` — thread-safe admission into two priority lanes
  (``interactive`` / ``bulk``).  **Batch formation is deadline-driven**:
  a batch launches when some model has a full batch, when the oldest
  queued request hits the configured latency deadline, or when a
  synchronous caller is draining — never by waiting for stragglers.
  Bulk requests **age into the interactive lane** after
  ``bulk_aging_ms`` (ordered by original enqueue time), so interactive
  traffic preempts bulk without ever starving it.  Formation is a pure
  function of (queue contents, clock) so tests drive every reason —
  ``full`` / ``deadline`` / ``drain`` — with a fake clock and no
  threads.

* :class:`ProgramCache` — a bounded LRU of compiled
  ``(network, backend, mesh)`` programs.  One engine serves the whole zoo off
  one backend/scheduler; eviction and recompile are *measured* (hit /
  miss / eviction counters, ``engine.compile`` spans), bounded
  (``capacity``), and observable (``cache.size`` gauge).

* :class:`ContinuousBatchingEngine` — the serving loop.  ``submit_async``
  returns a :class:`concurrent.futures.Future` per request; a single
  worker thread forms batches, pads them onto the fixed ``[batch,H,W,C]``
  program shape, and dispatches through ``MultiCoreScheduler``.  Dispatch
  uses JAX **async dispatch**: up to ``max_inflight`` batches are in
  flight with unmaterialized device results while the next batch forms
  and launches (slot reuse across in-flight batches), and results
  materialize (``np.asarray``) only at retirement.  With ``route=True``
  and a per-model ``NetworkTunePlan``, each formed batch is routed
  through the ``MultiCoreScheduler`` mode the §5.2 cost model
  predicts fastest for that *(network, formed-batch-size)* pair
  (``core/autotune.route_batch``) — small deadline-launched batches take
  the single-image kout/spatial modes, full batches take batch sharding.

Telemetry (all through the PR 9 obs layer, in the engine's own
``MetricsRegistry``): ``queue.depth`` / ``queue.depth.peak`` gauges,
``queue_wait_us`` + honest enqueue→result ``request_latency_us``
histograms, ``batch_formed.{full,deadline,drain}`` and
``cache.{hits,misses,evictions}`` counters, ``batch_fill``, and
``route.<mode>`` counters when routing is live; jit cache misses in the
global ``jax.compiles`` counter (``obs.watch_compiles``).

With obs enabled the worker thread's time splits into spans that do not
overlap, each also on a ``jax.profiler`` trace's host plane:
``engine.wait`` (forming a batch, or waiting on the queue for work),
``engine.stage`` (stacking and padding the batch's images),
``engine.put`` (the host-to-device copy), ``sched.run`` (the launch),
``engine.ready`` (waiting for the device result), ``engine.fetch`` (the
device-to-host copy) and ``engine.resolve`` (setting each request's
result, the futures' callbacks included).  Their args are per batch:
``reason``, ``fill``, ``n``.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs

PRIORITIES = ("interactive", "bulk")
FORMATION_REASONS = ("full", "deadline", "drain")

# a synchronous caller waiting on its own requests must fail loudly, not
# hang CI, if the worker dies — generous because interpret-mode compiles
# of large plans take minutes on CPU
SUBMIT_TIMEOUT_S = 600.0


@dataclasses.dataclass
class ServeRequest:
    """One admitted single-image request (engine-internal)."""
    uid: int
    model: str
    image: np.ndarray                    # [H, W, C] float32
    priority: str
    enqueue_ns: int
    deadline_ns: int
    future: Future


@dataclasses.dataclass
class FormedBatch:
    """A launched batch: which model, which requests, and why it left
    the queue (``full`` / ``deadline`` / ``drain``)."""
    model: str
    requests: List[ServeRequest]
    reason: str


class RequestQueue:
    """Two-lane priority queue with deadline-driven batch formation.

    Admission (``push_many``) is thread-safe and atomic: a caller's
    requests become visible to the batch former all at once, so a
    synchronous ``submit`` of R images can never have its first
    ``batch`` images split by a racing deadline.  ``form`` decides, for
    a given clock reading, whether a batch should launch and why:

    * ``full`` — some model has at least ``batch`` queued requests; the
      winning model is the one owning the oldest request in formation
      order (interactive + aged bulk by enqueue time, then fresh bulk);
    * ``deadline`` — the oldest queued request (either lane) is past
      ``deadline_ms``; its model launches with whatever it has;
    * ``drain`` — a synchronous caller is waiting; partial batches
      launch rather than idling until the deadline.

    Bulk requests older than ``bulk_aging_ms`` are *promoted*: they
    merge into the interactive ordering by original enqueue time, so a
    saturating interactive load cannot starve them (they out-age it).

    ``clock`` is injectable (perf_counter_ns by default) so formation
    semantics are unit-testable without threads or sleeps."""

    def __init__(self, registry: obs.MetricsRegistry, *,
                 deadline_ms: float = 5.0, bulk_aging_ms: float = 50.0,
                 clock: Callable[[], int] = time.perf_counter_ns):
        if deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        self.cond = threading.Condition()
        self.deadline_ns = int(deadline_ms * 1e6)
        self.aging_ns = int(bulk_aging_ms * 1e6)
        self.clock = clock
        self._lanes: Dict[str, deque] = {p: deque() for p in PRIORITIES}
        self._depth = registry.gauge("queue.depth")
        self._peak = registry.gauge("queue.depth.peak")
        self._depth.set(0)
        self._peak.set(0)

    # -- admission -----------------------------------------------------------

    def push_many(self, reqs: Sequence[ServeRequest]) -> None:
        with self.cond:
            for r in reqs:
                if r.priority not in self._lanes:
                    raise ValueError(f"unknown priority {r.priority!r}; "
                                     f"have {PRIORITIES}")
                self._lanes[r.priority].append(r)
            d = self._len_locked()
            self._depth.set(d)
            if d > (self._peak.value or 0):
                self._peak.set(d)
            self.cond.notify_all()

    def _len_locked(self) -> int:
        return sum(len(q) for q in self._lanes.values())

    def __len__(self) -> int:
        with self.cond:
            return self._len_locked()

    # -- formation -----------------------------------------------------------

    def next_deadline_ns(self) -> Optional[int]:
        """Earliest queued deadline (caller must hold ``cond``)."""
        heads = [q[0].deadline_ns for q in self._lanes.values() if q]
        return min(heads) if heads else None

    def form(self, batch: int, *, drain: bool = False,
             now_ns: Optional[int] = None) -> Optional[FormedBatch]:
        with self.cond:
            return self.form_locked(batch, drain=drain, now_ns=now_ns)

    def form_locked(self, batch: int, *, drain: bool = False,
                    now_ns: Optional[int] = None) -> Optional[FormedBatch]:
        """Formation decision for one clock reading (hold ``cond``)."""
        now = self.clock() if now_ns is None else now_ns
        inter, bulk = self._lanes["interactive"], self._lanes["bulk"]
        if not inter and not bulk:
            return None
        promoted = [r for r in bulk if now - r.enqueue_ns >= self.aging_ns]
        fresh = [r for r in bulk if now - r.enqueue_ns < self.aging_ns]
        # formation order: interactive + aged bulk by original enqueue
        # time (aged bulk is older than the interactive flood that would
        # otherwise starve it), then fresh bulk FIFO
        urgent = sorted([*inter, *promoted], key=lambda r: r.enqueue_ns)
        ordered = urgent + fresh
        counts: Dict[str, int] = {}
        for r in ordered:
            counts[r.model] = counts.get(r.model, 0) + 1
        model = reason = None
        for r in ordered:                    # oldest full model wins
            if counts[r.model] >= batch:
                model, reason = r.model, "full"
                break
        if reason is None:
            oldest = min((q[0] for q in self._lanes.values() if q),
                         key=lambda r: r.enqueue_ns)
            if now >= oldest.deadline_ns:
                model, reason = oldest.model, "deadline"
            elif drain:
                model, reason = ordered[0].model, "drain"
            else:
                return None
        take = [r for r in ordered if r.model == model][:batch]
        taken = set(id(r) for r in take)
        for lane in self._lanes.values():
            kept = [r for r in lane if id(r) not in taken]
            lane.clear()
            lane.extend(kept)
        self._depth.set(self._len_locked())
        return FormedBatch(model=model, requests=take, reason=reason)


class ProgramCache:
    """Bounded LRU of compiled programs, keyed by ``(network, backend,
    mesh)``.

    ``get`` is get-or-build: a hit refreshes recency, a miss runs
    ``build()`` (the caller wraps it in an ``engine.compile`` span) and
    evicts the least-recently-used entries past ``capacity``.  Hit /
    miss / eviction counters and a ``cache.size`` gauge live in the
    engine registry, so eviction + recompile is measured and bounded —
    the multi-model serving contract."""

    def __init__(self, capacity: int, registry: obs.MetricsRegistry):
        if capacity < 1:
            raise ValueError(f"cache capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Any, Any]" = OrderedDict()
        self._hits = registry.counter("cache.hits")
        self._misses = registry.counter("cache.misses")
        self._evictions = registry.counter("cache.evictions")
        self._size = registry.gauge("cache.size")
        self._size.set(0)

    def get(self, key, build: Callable[[], Any]):
        with self._lock:
            if key in self._entries:
                self._hits.inc()
                self._entries.move_to_end(key)
                return self._entries[key]
            self._misses.inc()
            value = build()
            self._entries[key] = value
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self._evictions.inc()
            self._size.set(len(self._entries))
            return value

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._entries

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def keys(self) -> List[Any]:
        with self._lock:
            return list(self._entries)


@dataclasses.dataclass
class _Model:
    """One registered network: quantized weights, admission shape, the
    static scheduler verdict, and (when routing) the per-formed-size
    route table."""
    name: str
    qnet: Any
    input_shape: Tuple[int, int, int]
    classes: int
    tune: Any
    backend_name: str
    sched: Any
    routes: Dict[int, Tuple[str, Any, str]] = \
        dataclasses.field(default_factory=dict)


class ContinuousBatchingEngine:
    """Multi-model continuous-batching engine over compiled int8
    NetworkPlan programs.

    ``add_model`` registers a quantized network (admission keyed by its
    input shape) and eagerly compiles its default program into the LRU
    cache.  ``submit_async`` enqueues single-image requests and returns
    futures; ``submit`` is the synchronous convenience (enqueue, drain,
    stack).  One worker thread forms batches (full / deadline / drain),
    dispatches them through the scheduler with JAX async dispatch, and
    keeps up to ``max_inflight`` device results unmaterialized while the
    next batch launches.

    Per-request latency (``request_latency_us`` → ``latency_
    percentiles()``) is **enqueue→result** — it includes queue wait.

    ``route=True`` + a per-model ``tune`` (NetworkTunePlan) routes each
    formed batch through the scheduler mode ``autotune.route_batch``
    predicts fastest for its size; the routed kout/spatial programs are
    distinct cache entries (they compile against sharded backends)."""

    def __init__(self, *, batch: int = 8, n_cores: int = 1,
                 backend: str = "pallas", deadline_ms: float = 5.0,
                 bulk_aging_ms: float = 50.0, cache_capacity: int = 4,
                 max_inflight: int = 2, route: bool = False,
                 clock: Callable[[], int] = time.perf_counter_ns):
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        if max_inflight < 1:
            raise ValueError(
                f"max_inflight must be >= 1, got {max_inflight}")
        self.batch = batch
        self.n_cores = n_cores
        self.backend = backend
        self.route = route
        self.clock = clock
        self.metrics = obs.MetricsRegistry()
        self.queue = RequestQueue(self.metrics, deadline_ms=deadline_ms,
                                  bulk_aging_ms=bulk_aging_ms, clock=clock)
        self.cache = ProgramCache(cache_capacity, self.metrics)
        self._requests = self.metrics.counter("requests")
        self._batches = self.metrics.counter("batches")
        self._padded = self.metrics.counter("padded")
        self._formed = {r: self.metrics.counter(f"batch_formed.{r}")
                        for r in FORMATION_REASONS}
        self._latency = self.metrics.histogram("request_latency_us")
        self._queue_wait = self.metrics.histogram("queue_wait_us")
        self._fill = self.metrics.histogram(
            "batch_fill", bounds=[i / 16 for i in range(1, 17)])
        self._models: Dict[str, _Model] = {}
        self._inflight: deque = deque()
        self._uid_lock = threading.Lock()
        self._uid = 0
        self._drain_waiters = 0
        self._worker: Optional[threading.Thread] = None
        self._worker_lock = threading.Lock()
        self._stopping = False
        self.max_inflight = max_inflight
        obs.watch_compiles()

    # -- model registry ------------------------------------------------------

    def add_model(self, qnet, *, name: Optional[str] = None,
                  tune=None) -> str:
        """Register a quantized network and eagerly compile its default
        program (an ``engine.compile`` span + a cache miss).  Returns
        the model name used for admission."""
        from repro.core.scheduler import MultiCoreScheduler, SchedulerConfig
        name = name or qnet.plan.name
        if name in self._models:
            raise ValueError(f"model {name!r} already registered")
        if tune is not None and tune.network != qnet.plan.name:
            raise ValueError(
                f"tune plan is for network {tune.network!r}, "
                f"engine serves {qnet.plan.name!r}")
        if tune is not None:
            sched = MultiCoreScheduler.from_tune(tune)
            backend_name = self._shard_backend_name(sched)
        else:
            sched = MultiCoreScheduler(
                SchedulerConfig(n_cores=self.n_cores))
            backend_name = self.backend
        entry = _Model(
            name=name, qnet=qnet,
            input_shape=tuple(qnet.plan.input_shape),
            classes=qnet.plan.activation_shapes()[-1][-1],
            tune=tune, backend_name=backend_name, sched=sched)
        self._models[name] = entry
        self._compiled(entry, backend_name, sched)  # eager default program
        return name

    def _shard_backend_name(self, sched) -> str:
        """kout/spatial verdicts put the cores INSIDE the program as a
        sharded backend; batch verdicts shard around it."""
        from repro.core.convcore import register_backend
        if sched.config.mode in ("kout", "spatial"):
            sb = sched.shard_backend(self.backend)
            register_backend(sb)
            return sb.name
        return self.backend

    def models(self) -> List[str]:
        return sorted(self._models)

    def _resolve(self, model: Optional[str],
                 shape: Tuple[int, ...]) -> _Model:
        """Admission: by name (shape-checked) or, with ``model=None``,
        by unique input-shape match across the registered zoo."""
        if not self._models:
            raise ValueError("no models registered (add_model first)")
        if model is not None:
            entry = self._models.get(model)
            if entry is None:
                raise ValueError(f"unknown model {model!r}; "
                                 f"have {self.models()}")
            if tuple(shape) != entry.input_shape:
                raise ValueError(
                    f"model {model!r} wants input shape "
                    f"{entry.input_shape}, got {tuple(shape)}")
            return entry
        matches = [e for e in self._models.values()
                   if e.input_shape == tuple(shape)]
        if len(matches) != 1:
            raise ValueError(
                f"input shape {tuple(shape)} matches "
                f"{[e.name for e in matches] or 'no'} models — pass "
                f"model= (have {self.models()})")
        return matches[0]

    # -- compilation ---------------------------------------------------------

    def _compiled(self, entry: _Model, backend_name: str, sched):
        """(program, tile_plans, core_config) for one (model, backend,
        mesh) point, through the LRU cache.  The program closes over
        ``sched.place``'s copy of the weights, so it serves only
        schedulers on the same mesh (``None``: one device)."""
        from repro.core.convcore import ConvCoreConfig
        from repro.core.network import make_int8_program, program_tile_plans

        def build():
            cfg = ConvCoreConfig(backend=backend_name, int8=True)
            with obs.span("engine.compile", network=entry.qnet.plan.name,
                          model=entry.name, backend=backend_name,
                          batch=self.batch):
                if entry.tune is not None:
                    tile_plans = entry.tune.tile_plans
                else:
                    tile_plans = program_tile_plans(entry.qnet.plan, cfg)
                program = make_int8_program(sched.place(entry.qnet), cfg,
                                            tile_plans=tile_plans)
            return program, tile_plans, cfg

        return self.cache.get((entry.name, backend_name, sched.mesh), build)

    # -- admission / submission ----------------------------------------------

    def _next_uids(self, n: int) -> range:
        with self._uid_lock:
            lo = self._uid
            self._uid += n
        return range(lo, lo + n)

    def submit_async(self, images, *, model: Optional[str] = None,
                     priority: str = "interactive"):
        """Enqueue requests; returns one Future per image (a bare Future
        for a single [H,W,C] image, a list for a [R,H,W,C] stack).  Each
        future resolves to that request's [classes] float32 logits."""
        if priority not in PRIORITIES:
            raise ValueError(f"unknown priority {priority!r}; "
                             f"have {PRIORITIES}")
        imgs = np.asarray(images, np.float32)
        single = imgs.ndim == 3
        if single:
            imgs = imgs[None]
        entry = self._resolve(model, imgs.shape[1:])
        now = self.clock()
        reqs = [ServeRequest(uid=u, model=entry.name, image=imgs[i],
                             priority=priority, enqueue_ns=now,
                             deadline_ns=now + self.queue.deadline_ns,
                             future=Future())
                for i, u in enumerate(self._next_uids(imgs.shape[0]))]
        self._requests.inc(len(reqs))
        self._ensure_worker()
        self.queue.push_many(reqs)
        futures = [r.future for r in reqs]
        return futures[0] if single else futures

    def submit(self, images, *, model: Optional[str] = None,
               priority: str = "interactive") -> np.ndarray:
        """Synchronous convenience: enqueue, drain, stack.  [R,H,W,C]
        (or one [H,W,C]) → [R, classes] logits in request order.  While
        a synchronous caller waits, the queue drains — partial batches
        launch immediately instead of idling until the deadline."""
        imgs = np.asarray(images, np.float32)
        if imgs.ndim == 3:
            imgs = imgs[None]
        if imgs.shape[0] == 0:
            entry = self._resolve(model, imgs.shape[1:]) \
                if model or self._models else None
            k = entry.classes if entry is not None else 0
            return np.zeros((0, k), np.float32)
        with self.queue.cond:
            self._drain_waiters += 1
        try:
            futures = self.submit_async(imgs, model=model,
                                        priority=priority)
            out = [f.result(timeout=SUBMIT_TIMEOUT_S) for f in futures]
        finally:
            with self.queue.cond:
                self._drain_waiters -= 1
        return np.stack(out)

    # -- the serving loop ----------------------------------------------------

    def _ensure_worker(self) -> None:
        with self._worker_lock:
            if self._stopping:
                raise RuntimeError("engine is closed")
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(
                    target=self._serve_loop, daemon=True,
                    name="conv-serve-worker")
                self._worker.start()

    def _serve_loop(self) -> None:
        while True:
            fb = None
            retire_idle = False
            with obs.span("engine.wait"), self.queue.cond:
                while not self._stopping:
                    fb = self.queue.form_locked(
                        self.batch, drain=self._drain_waiters > 0)
                    if fb is not None:
                        break
                    if self._inflight:
                        retire_idle = True    # use idle time to retire
                        break
                    nxt = self.queue.next_deadline_ns()
                    timeout = None if nxt is None else \
                        max((nxt - self.clock()) / 1e9, 0.0)
                    self.queue.cond.wait(timeout=timeout)
                if self._stopping and fb is None and not retire_idle:
                    break
            try:
                if fb is not None:
                    self._dispatch(fb)
                    while len(self._inflight) > self.max_inflight:
                        self._retire_one()
                elif self._inflight:
                    self._retire_one()
            except BaseException as e:        # never strand submitters
                if fb is not None:
                    for r in fb.requests:
                        if not r.future.done():
                            r.future.set_exception(e)
        # stop: drain whatever is queued, then materialize everything
        while True:
            fb = self.queue.form(self.batch, drain=True)
            if fb is None:
                break
            try:
                self._dispatch(fb)
            except BaseException as e:
                for r in fb.requests:
                    if not r.future.done():
                        r.future.set_exception(e)
        while self._inflight:
            self._retire_one()

    def _route_for(self, entry: _Model,
                   n_real: int) -> Tuple[str, Any, Optional[str]]:
        """(backend_name, scheduler, routed-mode) for one formed batch.
        Static verdict unless routing is on AND the model carries a
        tune plan (the route table needs its per-layer costs)."""
        if not self.route or entry.tune is None:
            return entry.backend_name, entry.sched, None
        cached = entry.routes.get(n_real)
        if cached is None:
            from repro.core.autotune import route_batch
            from repro.core.scheduler import (MultiCoreScheduler,
                                              SchedulerConfig)
            budget = self.n_cores if self.n_cores > 1 \
                else max(entry.tune.n_cores, 1)
            mode, cores, _ = route_batch(entry.tune.layers, n_real, budget)
            sched = MultiCoreScheduler(
                SchedulerConfig(n_cores=cores, mode=mode))
            bname = self._shard_backend_name(sched)
            cached = entry.routes[n_real] = (bname, sched, mode)
        self.metrics.counter(f"route.{cached[2]}").inc()
        return cached

    def _dispatch(self, fb: FormedBatch) -> None:
        entry = self._models[fb.model]
        n_real = len(fb.requests)
        pad = self.batch - n_real
        now = self.clock()
        for r in fb.requests:
            self._queue_wait.observe((now - r.enqueue_ns) / 1e3)
        self._formed[fb.reason].inc()
        self._fill.observe(n_real / self.batch)
        if pad:
            self._padded.inc(pad)
        span_args = {"reason": fb.reason, "fill": n_real / self.batch,
                     "n": n_real}
        with obs.span("engine.stage", **span_args):
            chunk = np.stack([r.image for r in fb.requests])
            if pad:
                chunk = np.concatenate(
                    [chunk, np.zeros((pad, *entry.input_shape), np.float32)])
        backend_name, sched, _ = self._route_for(entry, n_real)
        program, _, _ = self._compiled(entry, backend_name, sched)
        with obs.span("engine.put", **span_args):
            x = sched.put(chunk)
        dev = sched.run(program, x)
        # async dispatch: the device result stays unmaterialized; the
        # next batch forms and launches while this one computes
        self._inflight.append((dev, fb, span_args))

    def _retire_one(self) -> None:
        dev, fb, span_args = self._inflight.popleft()
        try:
            with obs.span("engine.ready", **span_args):
                dev.block_until_ready()
            with obs.span("engine.fetch", **span_args):
                logits = np.asarray(dev)
        except BaseException as e:
            for r in fb.requests:
                if not r.future.done():
                    r.future.set_exception(e)
            return
        now = self.clock()
        self._batches.inc()
        with obs.span("engine.resolve", **span_args):
            for i, r in enumerate(fb.requests):
                self._latency.observe((now - r.enqueue_ns) / 1e3)
                r.future.set_result(logits[i])

    # -- stats / lifecycle ---------------------------------------------------

    @property
    def stats(self) -> Dict[str, int]:
        """The classic counter triple (requests / batches / padded)."""
        return {"requests": self._requests.value,
                "batches": self._batches.value,
                "padded": self._padded.value}

    def formation_counts(self) -> Dict[str, int]:
        return {r: c.value for r, c in self._formed.items()}

    def cache_stats(self) -> Dict[str, int]:
        return {"hits": self.metrics.counter("cache.hits").value,
                "misses": self.metrics.counter("cache.misses").value,
                "evictions":
                    self.metrics.counter("cache.evictions").value,
                "size": len(self.cache),
                "capacity": self.cache.capacity}

    def latency_percentiles(self) -> Dict[str, float]:
        """p50/p90/p99 (+count/mean) of honest enqueue→result latency in
        µs (queue wait INCLUDED)."""
        return self._latency.summary()

    def close(self, timeout: float = SUBMIT_TIMEOUT_S) -> None:
        """Stop the worker after draining queued work (idempotent)."""
        with self._worker_lock:
            worker = self._worker
            self._stopping = True
        with self.queue.cond:
            self.queue.cond.notify_all()
        if worker is not None and worker.is_alive():
            worker.join(timeout=timeout)

    def __enter__(self) -> "ContinuousBatchingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
