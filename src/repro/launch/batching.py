"""Continuous-batching request scheduler: an engine slot *is* the
request lifecycle.

The paper's FPGA keeps detection at line rate because the pipeline
never drains between streams; the software analogue is continuous
batching: requests attach to a `SlotPool` slot on arrival, replay
their history through the engine in fixed-size chunks (chunked
prefill — long histories never trigger a fresh compile because the
chunk shape is constant), interleave with the decode-phase trickle of
live samples every tick, and detach/recycle the slot on completion.

ONE compiled (chunk_t, C) program per capacity bucket serves every
tenant mix: each tick makes a single fused engine call in which slot c
retires `min(pending_c, chunk_t)` samples via the engine's per-slot
`valid_lens` vector — a prefill-heavy slot rides the full chunk, a
decode-phase slot retires its one live sample, and a slot with nothing
pending is suspended at vlen=0 (frozen state, no flags, no detach) —
all in the same call.

Three scheduler-level optimisations ride on that fused call:

  * **Async double-buffered tick loop** — `step()` dispatches the
    fused call and returns without fetching its outputs (JAX async
    dispatch keeps the device busy); the next tick's host bookkeeping
    (admission, `take`, vlens assembly) overlaps with the in-flight
    device compute, and the *previous* tick's outputs are fetched only
    then — or earlier, when `results()`/`telemetry()` consume them or
    a request completes.  Bit-exact with the synchronous loop: the
    engine-call sequence depends only on host-side counters, never on
    fetched verdicts (`tests/test_batching.py::test_async_equals_sync`).
    `measure_latency=True` keeps the fully synchronous loop (block
    after every call) so per-call wall times stay honest.

  * **Deep dispatch pipeline** — `pipeline_depth=d` keeps up to `d`
    fused calls dispatched-but-unfetched at once.  Slots touched by a
    still-in-flight call are *fenced* from re-dispatch (each slot sits
    in at most one in-flight call, so its chunks are fetched in
    dispatch order no matter when each call retires), which makes
    retirement safely out-of-order: any in-flight call whose outputs
    have already landed retires immediately, and the oldest call is
    force-retired when the pipeline is full — or when every ready slot
    is fenced, so a tick with work always dispatches.  Gateway-visible
    results are bit-exact with depth 1 (chunk-exactness makes the
    per-slot sample stream independent of how ticks partition it).
    Depth beyond 1 pays off under staggered load — admission waves and
    decode trickles touching disjoint slot sets — where successive
    calls genuinely overlap on device; under uniform load every ready
    slot is fenced by the previous call and the loop degrades
    gracefully to the depth-1 double buffer.  `measure_latency=True`
    overrides the pipeline (every call blocks at dispatch), keeping
    wall times honest.

  * **Adaptive chunk_t** — when every ready slot is in decode phase
    (pending <= `decode_t`, default 1), the tick rides a short cached
    (decode_t, C) program instead of the full (chunk_t, C) one:
    decode-only ticks stop paying a chunk_t-deep program to retire one
    sample per slot.  Both shapes are cached per capacity bucket (the
    jit program cache keyed on (capacity, t, layout) — see
    `SlotPool.stats()["programs"]`), so after warmup no tick
    recompiles.

  * **Priority classes / weighted admission** — `Request(priority=)`
    names an admission class; `class_weights` gives each class a
    weighted-deficit share of slot acquisitions, so a burst of bulk
    prefills cannot starve latency-class tenants.  Per-class
    queue-wait/latency telemetry is in `stats()["classes"]`.

Ragged interleaved execution is bit-exact with running each request
alone — per-slot valid-length masking inside the kernels
(tests/test_ragged.py) plus slot independence, verified end-to-end by
tests/test_batching.py on the Q path.

Admission is a bounded queue: `submit` returns False when the queue is
full (caller backpressure), and requests wait in their class queue
while every bucket of the pool is occupied (`PoolFull` backpressure
inside the scheduler).  Per-request telemetry (queue wait, per-call
(wall, retired) latency pairs, flag counts) is kept for the serving
benchmark and the gateway in `launch/serve.py`.
"""
from __future__ import annotations

import time
from collections import OrderedDict, deque
from collections.abc import Mapping
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.engine import PoolFull, ShardedPool, SlotMajor, SlotPool
from repro.obs import (EventBus, LATENCY_MS_BUCKETS, MetricsRegistry,
                       NULL_SPAN, NULL_TRACER, TICK_BUCKETS, auto_name)

__all__ = ["Request", "RequestStats", "BatchingScheduler",
           "EvictedRequest"]

QUEUED, PREFILL, DECODE, DONE = "queued", "prefill", "decode", "done"


class EvictedRequest(KeyError):
    """The request completed but its record aged out of the
    `keep_finished` retention window — distinct from a rid that was
    never submitted, so callers can tell "gone" from "wrong"."""


@dataclass
class Request:
    """One tenant stream: a history to replay + live samples to come.

    `m` is this tenant's outlier sensitivity (None: scheduler default).
    `closed` requests complete once their pending samples drain; open
    requests keep their slot and wait for `feed`.  `priority` names
    the admission class (see `BatchingScheduler(class_weights=)`).

    Under the ensemble backend, `detectors` selects this tenant's
    detector subset and `vote` its vote mode / threshold fraction
    (None: the backend's defaults) — threaded to the slot at admission
    (`SlotPool.acquire` -> `StreamEngine.attach`).
    """

    rid: str
    history: np.ndarray = field(
        default_factory=lambda: np.zeros((0,), np.float32))
    m: Optional[float] = None
    closed: bool = False
    priority: str = "default"
    detectors: Optional[Tuple[str, ...]] = None
    vote: Optional[object] = None


@dataclass
class RequestStats:
    """Per-request telemetry, filled in as the lifecycle advances.

    `chunk_latency_s` holds (wall_s, retired_this_call) pairs: the
    fused call's wall time is shared by every member slot, so honest
    percentiles weight each observation by the samples that request
    actually retired in the call, instead of attributing the whole
    wall to a slot that retired one sample.

    While a request runs, its counters (`samples`, `flags`,
    `prefill_chunks`, `decode_steps`, `det_flags`, `det_scores`) live
    in the scheduler's per-request rows, which each retired call
    updates as columns.  They are copied here (a fold, counted by
    `sched_stats_folds_total`) when the request completes, before its
    `done` event, and whenever it is read through
    `BatchingScheduler.telemetry()` or `stats_by_rid`.  A `RequestStats`
    object kept from an earlier read holds the values of that read.
    """

    rid: str
    submitted_tick: int
    priority: str = "default"
    admitted_tick: Optional[int] = None
    done_tick: Optional[int] = None
    slot: Optional[int] = None
    # sharded scheduling only: the current shard (None on a single
    # pool) and how many times the rebalancer moved this stream
    shard: Optional[int] = None
    migrations: int = 0
    samples: int = 0
    flags: int = 0
    prefill_chunks: int = 0
    decode_steps: int = 0
    chunk_latency_s: List[Tuple[float, int]] = field(default_factory=list)
    # ensemble backend only: per-detector flag counts ({name: count},
    # selection-masked — an unselected detector never appears)
    det_flags: Dict[str, int] = field(default_factory=dict)
    # ensemble backend only: per-detector score-stream sums over every
    # retired sample ({name: float} — the kernel's float score streams,
    # NOT selection-gated; divide by `samples` for the running mean)
    det_scores: Dict[str, float] = field(default_factory=dict)

    @property
    def queue_wait_ticks(self) -> Optional[int]:
        if self.admitted_tick is None:
            return None
        return self.admitted_tick - self.submitted_tick


class _Run:
    """Internal per-request runtime record (admitted requests only)."""

    __slots__ = ("req", "slot", "shard", "pending", "cursor", "phase",
                 "stats", "ecc_parts", "outlier_parts", "hist_len",
                 "consumed", "inflight", "seq", "row")

    def __init__(self, req: Request, slot: int, stats: RequestStats,
                 shard: int = 0, seq: int = 0, row: int = 0):
        self.req = req
        self.seq = seq  # admission order
        self.slot = slot
        self.shard = shard
        self.row = row  # its counters in `_Rows`: kept across migrations
        self.pending = np.asarray(req.history, np.float32).reshape(-1)
        self.cursor = 0
        # the replayed prefix: everything backlogged at admission is
        # prefill; samples fed after admission are the decode trickle
        self.hist_len = self.pending.shape[0]
        self.consumed = 0
        self.phase = PREFILL if self.avail else DECODE
        self.stats = stats
        self.ecc_parts: List[np.ndarray] = []
        self.outlier_parts: List[np.ndarray] = []
        self.inflight = 0  # dispatched calls not yet host-fetched

    @property
    def avail(self) -> int:
        return self.pending.shape[0] - self.cursor

    @property
    def place(self) -> Tuple[int, int]:
        """(shard, local slot) — the fencing key: local slot indices
        collide across shards, the pair never does."""
        return (self.shard, self.slot)

    def push(self, samples: np.ndarray) -> None:
        samples = np.asarray(samples, np.float32).reshape(-1)
        # drop the consumed prefix before growing, keeping push O(new)
        if self.cursor:
            self.pending = self.pending[self.cursor:]
            self.cursor = 0
        self.pending = np.concatenate([self.pending, samples])

    def take(self, n: int) -> np.ndarray:
        out = self.pending[self.cursor:self.cursor + n]
        self.cursor += n
        self.consumed += n
        if self.phase == PREFILL and self.consumed >= self.hist_len:
            self.phase = DECODE  # history cursor passed the prefix
        return out


class _InFlight:
    """One dispatched-but-unfetched fused call (device arrays are JAX
    async futures; fetching them is the sync point)."""

    __slots__ = ("out", "runs", "slots", "ns", "t_len", "tick", "t0",
                 "sync_wall", "shard")

    def __init__(self, out, runs, slots, ns, t_len, tick, t0, sync_wall,
                 shard=None):
        self.out = out              # {"ecc", "outlier"} device arrays
        # the member runs, and as arrays their slots and sample counts
        # at dispatch time: columns, not a tuple per member, so a call
        # leaves no per-member object for the garbage collector
        self.runs = runs
        self.slots = slots
        self.ns = ns
        self.t_len = t_len
        self.tick = tick
        self.t0 = t0
        self.sync_wall = sync_wall  # honest wall when measured sync
        self.shard = shard          # which shard's engine ran the call


class _Rows:
    """The running requests' counters, one row each: a run takes a row
    at admission and gives it back at completion, and a retired call
    adds to all its members' rows with one fancy-indexed add per field.
    `det_flags` / `det_scores` hold one column per ensemble member
    (none off the ensemble); `scored` marks rows that have had score
    sums added.  Score sums stay float64, as the dicts they replace."""

    FIELDS = ("samples", "flags", "prefill_chunks", "decode_steps",
              "det_flags", "det_scores", "scored")

    def __init__(self, k: int, capacity: int = 64):
        self.size = 0          # rows ever handed out
        self._free: List[int] = []
        self.samples = np.zeros((capacity,), np.int64)
        self.flags = np.zeros((capacity,), np.int64)
        self.prefill_chunks = np.zeros((capacity,), np.int64)
        self.decode_steps = np.zeros((capacity,), np.int64)
        self.det_flags = np.zeros((capacity, k), np.int64)
        self.det_scores = np.zeros((capacity, k), np.float64)
        self.scored = np.zeros((capacity,), bool)

    def take(self) -> int:
        if self._free:
            row = self._free.pop()
            for f in self.FIELDS:
                getattr(self, f)[row] = 0
            return row
        cap = self.samples.shape[0]
        if self.size == cap:
            for f in self.FIELDS:
                a = getattr(self, f)
                grown = np.zeros((2 * cap,) + a.shape[1:], a.dtype)
                grown[:cap] = a
                setattr(self, f, grown)
        self.size += 1
        return self.size - 1

    def give(self, row: int) -> None:
        self._free.append(row)


class _StatsView(Mapping):
    """`BatchingScheduler.stats_by_rid`: every request's `RequestStats`
    by rid, a running request's folded from its row when read."""

    def __init__(self, stats: Dict[str, RequestStats], fold):
        self._stats = stats
        self._fold = fold

    def __getitem__(self, rid: str) -> RequestStats:
        st = self._stats[rid]
        self._fold(rid)
        return st

    def __contains__(self, rid) -> bool:
        return rid in self._stats

    def __iter__(self):
        return iter(self._stats)

    def __len__(self) -> int:
        return len(self._stats)


def _by_admission(run: _Run) -> int:
    return run.seq


def _host_ready(out) -> bool:
    """True when a dispatched call's outputs have already landed (its
    fetch would not block).  `jax.Array.is_ready` where available;
    conservatively False otherwise — the depth bound still forces
    retirement, so opportunism is an optimization, never a liveness
    requirement."""
    is_ready = getattr(out["outlier"], "is_ready", None)
    if is_ready is None:
        return False
    try:
        return bool(is_ready())
    except Exception:
        return False


class BatchingScheduler:
    """Continuous batching of TEDA detection requests over a SlotPool.

    >>> sched = BatchingScheduler("pallas", chunk_t=64,
    ...                           class_weights={"latency": 4, "bulk": 1})
    >>> sched.submit(Request("tenant-a", history, m=2.5,
    ...                      priority="latency"))
    >>> sched.feed("tenant-a", live_chunk); sched.step()
    >>> sched.close("tenant-a"); sched.drain()
    >>> sched.results("tenant-a")["outlier"]

    One `step()` = admit what the deficit-weighted class queues allow,
    one fused ragged engine call retiring min(pending, t) samples per
    slot on the adaptive (t, C) program, retire the *previous* tick's
    host-fetched outputs, complete what finished.  All engine options
    pass through to the pool.
    """

    def __init__(self, backend: str = "scan", *,
                 buckets: Tuple[int, ...] = (8, 16, 32, 64),
                 chunk_t: int = 32, decode_t: int = 1, m: float = 3.0,
                 queue_limit: int = 64, collect: bool = True,
                 measure_latency: bool = False,
                 pipeline_depth: int = 1,
                 keep_finished: int = 1024,
                 call_log_len: int = 4096,
                 latency_log_len: int = 4096,
                 class_weights: Optional[Dict[str, float]] = None,
                 shards: int = 1, shard_devices=None,
                 ring_vnodes: int = 128,
                 rebalance_every: int = 0,
                 rebalance_threshold: int = 2,
                 registry=None, tracer=None,
                 name: Optional[str] = None,
                 **engine_opts):
        if chunk_t < 2:
            raise ValueError("chunk_t must be >= 2")
        if not 1 <= decode_t <= chunk_t:
            raise ValueError(
                f"decode_t must lie in [1, chunk_t={chunk_t}], "
                f"got {decode_t}")
        # observability (repro.obs): the scheduler's hand-rolled
        # counters live in registry instruments now — `stats()` reads
        # them back, the tracer records tick spans, the event bus
        # streams verdicts at retirement (`subscribe()`)
        self.registry = (MetricsRegistry() if registry is None
                         else registry)
        self.tracer = NULL_TRACER if tracer is None else tracer
        self.name = auto_name("sched") if name is None else str(name)
        self.events = EventBus()
        self._init_instruments()
        # decode-only ticks retire 1 sample/slot of the (decode_t, C)
        # program: a small block keeps the padded time extent (and
        # interpret-mode cost) proportionate
        engine_opts.setdefault("block_t", 8)
        # shards > 1 swaps the single SlotPool for a ShardedPool: one
        # logical pool over N shards with consistent-hash routing and
        # live migration; each tick dispatches one fused call per shard
        # with work, async and fenced exactly like the single pool
        self.n_shards = int(shards)
        if self.n_shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        self._sharded = self.n_shards > 1
        self.rebalance_every = int(rebalance_every)
        if self.rebalance_every < 0:
            raise ValueError(
                f"rebalance_every must be >= 0, got {rebalance_every}")
        if self._sharded:
            self.pool = ShardedPool(
                backend, shards=self.n_shards, buckets=buckets, m=m,
                vnodes=ring_vnodes, devices=shard_devices,
                rebalance_threshold=rebalance_threshold,
                registry=self.registry, tracer=self.tracer,
                events=self.events, name=f"{self.name}/pool",
                **engine_opts)
        else:
            self.pool = SlotPool(backend, buckets=buckets, m=m,
                                 registry=self.registry,
                                 tracer=self.tracer,
                                 name=f"{self.name}/pool", **engine_opts)
        # detector-ensemble serving: when the backend carries a
        # detector axis, verdict columns come back as per-detector flag
        # bitmasks ("ecc" stream) and the scheduler accounts flags per
        # detector at retirement
        be = self.pool.engine.backend
        self._ensemble = bool(getattr(be, "aux_rows", 0))
        self._det_names: Tuple[str, ...] = tuple(
            getattr(be, "detectors", ()) or ())
        self.chunk_t = int(chunk_t)
        self.decode_t = int(decode_t)
        self.queue_limit = int(queue_limit)
        self.collect = collect
        # measure_latency=True keeps the synchronous loop (block after
        # every fused call) so per-call wall times are honest device
        # latencies; False runs the async double-buffered loop
        self.measure_latency = measure_latency
        # pipeline_depth > 1 keeps several fused calls in flight with
        # slot fencing + out-of-order retirement (see module docs);
        # depth 1 is the PR 5 double buffer, bit-for-bit
        self.pipeline_depth = int(pipeline_depth)
        if self.pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}")
        # retention caps: a forever-running gateway must not accumulate
        # per-request records without bound.  The oldest finished
        # requests (results + telemetry; their rid becomes reusable)
        # and engine-call log entries are evicted past these limits.
        self.keep_finished = int(keep_finished)
        self.latency_log_len = int(latency_log_len)
        if class_weights is not None and any(
                w <= 0 for w in class_weights.values()):
            raise ValueError(
                f"class weights must be positive: {class_weights}")
        self._weights: Dict[str, float] = dict(class_weights or {})
        self._ctor_classes = frozenset(self._weights)
        self._queues: "OrderedDict[str, deque]" = OrderedDict()
        self._queued: Dict[str, Request] = {}   # the queues' requests
        self._deficit: Dict[str, float] = {}
        self.runs: Dict[str, _Run] = {}     # admitted, not yet done
        # the admitted runs with samples pending, and those closed: a
        # tick visits these, not every run (`_by_admission` orders them)
        self._ready: Dict[str, _Run] = {}
        self._closing: Dict[str, _Run] = {}
        self._admitted = 0
        self._finished: Dict[str, _Run] = {}
        self._evicted: deque = deque(maxlen=max(4096, self.keep_finished))
        # rid -> live entries in the ring (a rid can re-enter after a
        # resubmit cycle, so membership is refcounted, not a set)
        self._evicted_counts: Dict[str, int] = {}
        # every request's RequestStats by rid; a running request's
        # counters live in its row of `_rows` until folded (`_fold`)
        self._stats: Dict[str, RequestStats] = {}
        self._rows = _Rows(len(self._det_names))
        self.stats_by_rid = _StatsView(self._stats, self._fold_rid)
        self.call_log: deque = deque(maxlen=int(call_log_len))
        self._inflight: deque = deque()   # dispatched, not host-fetched
        self._deferred_flagged: List[str] = []

    def _init_instruments(self) -> None:
        """Create the scheduler's registry instruments (the counters
        `tick_no`/`completed`/`rejected`/`short_ticks` read back as
        properties, plus the running latency/wait histograms that make
        `stats()` an O(1) snapshot)."""
        reg, lbl = self.registry, {"sched": self.name}
        self._c_ticks = reg.counter(
            "sched_ticks_total", "scheduler ticks",
            ("sched",)).labels(**lbl)
        self._c_short = reg.counter(
            "sched_short_ticks_total",
            "ticks that rode the short (decode_t, C) program",
            ("sched",)).labels(**lbl)
        self._c_completed = reg.counter(
            "sched_completed_total", "requests completed",
            ("sched",)).labels(**lbl)
        self._c_rejected = reg.counter(
            "sched_rejected_submits_total",
            "submits rejected by the bounded admission queue",
            ("sched",)).labels(**lbl)
        self._c_submitted = reg.counter(
            "sched_submitted_total", "requests accepted into a queue",
            ("sched",)).labels(**lbl)
        self._c_calls = reg.counter(
            "sched_calls_total", "fused engine calls dispatched",
            ("sched",)).labels(**lbl)
        self._c_samples = reg.counter(
            "sched_samples_retired_total",
            "samples retired across all requests",
            ("sched",)).labels(**lbl)
        self._c_flags = reg.counter(
            "sched_flags_total", "outlier verdicts raised",
            ("sched",)).labels(**lbl)
        self._c_h2d = reg.counter(
            "sched_h2d_bytes_total",
            "bytes of chunks and valid lengths sent to the device",
            ("sched",)).labels(**lbl)
        self._c_d2h = reg.counter(
            "sched_d2h_bytes_total",
            "bytes of call outputs fetched from the device",
            ("sched",)).labels(**lbl)
        self._c_folds = reg.counter(
            "sched_stats_folds_total",
            "copies of a running request's counter row into its "
            "RequestStats (completion, telemetry() and stats_by_rid "
            "reads)", ("sched",)).labels(**lbl)
        self._g_inflight = reg.gauge(
            "sched_inflight_calls",
            "dispatched fused calls not yet host-fetched",
            ("sched",)).labels(**lbl)
        self._h_wall = reg.histogram(
            "sched_call_wall_ms",
            "fused-call wall time, weighted by samples retired",
            ("sched",), buckets=LATENCY_MS_BUCKETS).labels(**lbl)
        # per-class families: children created lazily per priority
        self._f_queued = reg.gauge(
            "sched_class_queued", "requests waiting for admission",
            ("sched", "class"))
        self._f_running = reg.gauge(
            "sched_class_running", "admitted, not yet completed",
            ("sched", "class"))
        self._f_cls_done = reg.counter(
            "sched_class_completed_total", "completions per class",
            ("sched", "class"))
        self._f_wait = reg.histogram(
            "sched_queue_wait_ticks", "submit-to-admission wait",
            ("sched", "class"), buckets=TICK_BUCKETS)
        self._f_latency = reg.histogram(
            "sched_request_latency_ticks", "submit-to-done latency",
            ("sched", "class"), buckets=TICK_BUCKETS)
        self._classes: Dict[str, dict] = {}
        # per-detector flag counts under the ensemble backend; children
        # created lazily per member detector at first flag
        self._f_det_flags = reg.counter(
            "sched_detector_flags_total",
            "per-detector flags raised (ensemble backend, "
            "selection-masked)", ("sched", "detector"))
        self._det_counters: Dict[str, object] = {}

    def _det_counter(self, detector: str):
        c = self._det_counters.get(detector)
        if c is None:
            c = self._f_det_flags.labels(sched=self.name,
                                         detector=detector)
            self._det_counters[detector] = c
        return c

    def _cls(self, cls: str) -> dict:
        """The cached per-class instrument children for one priority."""
        ch = self._classes.get(cls)
        if ch is None:
            lbl = {"sched": self.name, "class": cls}
            ch = {"queued": self._f_queued.labels(**lbl),
                  "running": self._f_running.labels(**lbl),
                  "completed": self._f_cls_done.labels(**lbl),
                  "wait": self._f_wait.labels(**lbl),
                  "latency": self._f_latency.labels(**lbl)}
            self._classes[cls] = ch
        return ch

    # ------------------------------------------- registry-backed counts
    @property
    def tick_no(self) -> int:
        return int(self._c_ticks.value)

    @property
    def completed(self) -> int:
        return int(self._c_completed.value)

    @property
    def rejected(self) -> int:
        return int(self._c_rejected.value)

    @property
    def short_ticks(self) -> int:
        """Ticks that rode the (decode_t, C) program."""
        return int(self._c_short.value)

    def subscribe(self, maxlen: int = 4096):
        """A `Subscription` streaming this scheduler's events
        (admitted / chunk_retired / done / evicted) as they flush —
        verdicts at retirement, not completion.  See `repro.obs.events`."""
        return self.events.subscribe(maxlen=maxlen)

    # --------------------------------------------------------- intake
    @property
    def queue(self) -> List[Request]:
        """Queued-for-admission requests across every class (FIFO
        within a class; class interleaving is decided at admission)."""
        return [req for q in self._queues.values() for req in q]

    @property
    def queued_total(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def submit(self, req: Request) -> bool:
        """Queue a request for admission; False = queue full (caller
        backpressure — retry later or shed load)."""
        if req.rid in self._stats:
            raise ValueError(f"duplicate request id {req.rid!r}")
        if self.queued_total >= self.queue_limit:
            self._c_rejected.inc()
            return False
        # rid is reusable post-evict (stale ring entries age out inert)
        self._evicted_counts.pop(req.rid, None)
        if req.priority not in self._weights:
            # unknown classes admit at unit weight (documented) rather
            # than rejecting: the weights dict is a tuning knob
            self._weights[req.priority] = 1.0
        self._stats[req.rid] = RequestStats(
            rid=req.rid, submitted_tick=self.tick_no,
            priority=req.priority)
        self._queues.setdefault(req.priority, deque()).append(req)
        self._queued[req.rid] = req
        self._c_submitted.inc()
        self._cls(req.priority)["queued"].inc()
        return True

    def feed(self, rid: str, samples) -> None:
        """Append live (decode-phase) samples to a request's stream."""
        run = self.runs.get(rid)
        if run is not None:
            if run.req.closed:
                raise ValueError(f"request {rid!r} is closed")
            run.push(samples)
            if run.avail:
                self._ready[rid] = run
            return
        req = self._queued.get(rid)
        if req is not None:  # not yet admitted: samples are backlog
            if req.closed:
                raise ValueError(f"request {rid!r} is closed")
            req.history = np.concatenate(
                [np.asarray(req.history, np.float32).reshape(-1),
                 np.asarray(samples, np.float32).reshape(-1)])
            return
        raise KeyError(f"unknown or finished request {rid!r}")

    def close(self, rid: str) -> None:
        """No more live samples: the request completes once drained."""
        run = self.runs.get(rid)
        if run is not None:
            run.req.closed = True
            self._closing[rid] = run
            return
        req = self._queued.get(rid)
        if req is not None:
            req.closed = True
            return
        raise KeyError(f"unknown or finished request {rid!r}")

    # --------------------------------------------------------- the tick
    def _admit(self, events: dict) -> None:
        """Admit what the class queues allow (`_admit_round`), traced
        as one `admission` span when a queue holds a request."""
        if not (self.tracer.enabled and self.queued_total):
            self._admit_round(events)
            return
        before = len(events["admitted"])
        with self.tracer.span("admission", device=True,
                              tick=self.tick_no) as span:
            self._admit_round(events)
            span.set(admitted=len(events["admitted"]) - before)

    def _admit_round(self, events: dict) -> None:
        """Weighted-deficit round robin across the class queues.

        Every pass tops each backlogged class's deficit up by its
        weight; a class admits heads while its deficit covers the unit
        cost.  Drained classes are pruned entirely (no deficit
        hoarding, and per-class state stays bounded by the *backlogged*
        class count, not every priority string ever seen — ctor-declared
        weights are the one retained configuration), `PoolFull` ends
        the round — leftover deficits carry to the next tick, so a
        class starved by backpressure catches up first.

        Sharded pools narrow the backpressure: `PoolFull` from one
        shard's ladder blocks only the class whose head is routed
        there (FIFO within the class holds); other classes keep
        admitting — their streams may route to shards with room.  On a
        single pool a full ladder still ends the whole round, exactly
        as before.
        """
        blocked: set = set()
        while True:
            for c in [c for c, q in self._queues.items() if not q]:
                del self._queues[c]
                self._deficit.pop(c, None)
                if c not in self._ctor_classes:
                    self._weights.pop(c, None)
            backlogged = [c for c in self._queues if c not in blocked]
            if not backlogged:
                return
            # top every backlogged class up *before* admitting, so a
            # round cut short by PoolFull credits all of them equally
            for cls in backlogged:
                self._deficit[cls] = (self._deficit.get(cls, 0.0)
                                      + self._weights[cls])
            for cls in backlogged:
                q = self._queues[cls]
                while q and self._deficit[cls] >= 1.0:
                    req = q[0]
                    try:
                        if self._sharded:
                            shard, slot = self.pool.acquire(
                                req.rid, m=req.m,
                                detectors=req.detectors, vote=req.vote)
                        else:
                            shard, slot = 0, int(self.pool.acquire(
                                1, m=req.m, detectors=req.detectors,
                                vote=req.vote)[0])
                    except PoolFull:
                        if not self._sharded:
                            return  # whole pool full: round over
                        blocked.add(cls)  # this head's shard is full
                        break
                    q.popleft()
                    del self._queued[req.rid]
                    self._deficit[cls] -= 1.0
                    st = self._stats[req.rid]
                    st.admitted_tick = self.tick_no
                    st.slot = slot
                    if self._sharded:
                        st.shard = shard
                    run = self.runs[req.rid] = _Run(
                        req, slot, st, shard=shard, seq=self._admitted,
                        row=self._rows.take())
                    self._admitted += 1
                    if run.avail:
                        self._ready[req.rid] = run
                    if req.closed:
                        self._closing[req.rid] = run
                    events["admitted"].append(req.rid)
                    ch = self._cls(req.priority)
                    ch["queued"].dec()
                    ch["running"].inc()
                    ch["wait"].observe(st.queue_wait_ticks)
                    if self.tracer.enabled:
                        self.tracer.instant(
                            "admit", tick=self.tick_no, rid=req.rid,
                            slot=slot, cls=req.priority)
                    self.events.publish(
                        "admitted", self.tick_no, req.rid, slot=slot,
                        priority=req.priority)

    def _dispatch(self, members: List[_Run]) -> None:
        """Dispatch one fused ragged call per shard holding ready
        members (a single call on an unsharded pool).  The per-shard
        split cannot change any slot's retirement: each slot still
        takes n = min(pending, t_len), and the short-tick choice only
        drops t_len when every member of that call fits under it."""
        if not self._sharded:
            self._dispatch_group(members, 0)
            return
        by_shard: Dict[int, List[_Run]] = {}
        for run in members:
            by_shard.setdefault(run.shard, []).append(run)
        for shard in sorted(by_shard):
            self._dispatch_group(by_shard[shard], shard)

    def _dispatch_group(self, members: List[_Run],
                        shard: int) -> None:
        """One fused ragged engine call on one shard: slot c retires
        min(pending_c, t) samples via the per-slot valid-length vector;
        everyone else is suspended at vlen=0.  The chunk is a
        slot-major (C, t) `SlotMajor` frame: each member's samples are
        one contiguous row write, and the engine transposes the frame
        on the device.  Decode-only ticks (every member's pending <=
        decode_t) ride the short cached (decode_t, C) program instead
        of the full chunk.  Traced as an `assemble` span (the frame and
        valid-length vector built on host) then a `dispatch` span (the
        pool and engine call)."""
        tracer = self.tracer
        with (tracer.span("assemble", device=True, tick=self.tick_no,
                          slots=len(members), shard=shard)
              if tracer.enabled else NULL_SPAN) as span:
            cap = (self.pool.shard_capacity(shard) if self._sharded
                   else self.pool.capacity)
            t_len = self.chunk_t
            if all(r.avail <= self.decode_t for r in members):
                t_len = self.decode_t
                self._c_short.inc()
            span.set(t=t_len)
            x = np.zeros((cap, t_len), np.float32)
            vlens = np.zeros((cap,), np.int32)
            for run in members:
                n = min(run.avail, t_len)
                x[run.slot, :n] = run.take(n)
                if not run.avail:
                    del self._ready[run.req.rid]
                vlens[run.slot] = n
                run.inflight += 1
            slots = np.fromiter((r.slot for r in members), np.intp,
                                len(members))
            ns = vlens[slots].astype(np.intp)
            x = x.view(SlotMajor)
        self._c_calls.inc()
        h2d = x.nbytes + vlens.nbytes
        self._c_h2d.inc(h2d)
        with (tracer.span("dispatch", device=True, tick=self.tick_no,
                          t=t_len, slots=len(members), shard=shard,
                          samples=int(ns.sum()), h2d_bytes=h2d)
              if tracer.enabled else NULL_SPAN):
            t0 = time.perf_counter()
            if self._sharded:
                out = self.pool.process_shard(shard, x, valid_lens=vlens)
            else:
                out = self.pool.process(x, valid_lens=vlens)
            sync_wall = None
            if self.measure_latency:
                jax.block_until_ready(out["ecc"])
                sync_wall = time.perf_counter() - t0
        self._inflight.append(_InFlight(
            out, members, slots, ns, t_len, self.tick_no, t0, sync_wall,
            shard=shard if self._sharded else None))
        self._g_inflight.set(len(self._inflight))

    def _retire(self, inf: _InFlight, events: Optional[dict]) -> None:
        """Fetch one in-flight call's outputs to host and account them.

        The np.asarray fetch is the sync point; in the async loop it
        lands one tick after dispatch, overlapped with the next call's
        device compute.  With `events=None` (a flush outside `step`),
        flagged rids are deferred into the next tick's events.
        Every member's verdict streams on the event bus here — this is
        the retirement moment, the earliest a verdict exists on host.
        The flags and the bitmask arrive slot-major, (C, t), as the
        engine returns them for a `SlotMajor` frame.
        Traced as a `retire` span (the fetch, with the bytes it moves)
        then an `account` span (everything after it).
        """
        out = inf.out
        # the ensemble backend's "ecc" stream is the per-detector flag
        # bitmask — fetched even with collect=False, it feeds the
        # per-detector counters; its per-detector (K, T, C) float
        # score streams ride the same fetch (RequestStats /
        # chunk_retired telemetry)
        want_ecc = self.collect or self._ensemble
        want_scores = self._ensemble and "scores" in out
        # sizes of the output arrays, read before the fetch: no sync
        d2h = (out["outlier"].nbytes
               + (out["ecc"].nbytes if want_ecc else 0)
               + (out["scores"].nbytes if want_scores else 0))
        self._c_d2h.inc(d2h)
        tracer = self.tracer
        with (tracer.span("retire", device=True, tick=self.tick_no,
                          dispatch_tick=inf.tick, t=inf.t_len,
                          slots=len(inf.runs), d2h_bytes=d2h)
              if tracer.enabled else NULL_SPAN):
            outlier = np.asarray(out["outlier"])
            ecc = np.asarray(out["ecc"]) if want_ecc else None
            scores = np.asarray(out["scores"]) if want_scores else None
        with (tracer.span("account", device=True, tick=self.tick_no,
                          slots=len(inf.runs))
              if tracer.enabled else NULL_SPAN):
            self._account(inf, outlier, ecc, scores, events)

    def _account(self, inf: _InFlight, outlier: np.ndarray,
                 ecc: Optional[np.ndarray], scores: Optional[np.ndarray],
                 events: Optional[dict]) -> None:
        """Account one fetched call as columns: the call log and wall
        histogram, every member's counter row by one array update per
        field, the flag counters, then per member only its latency
        pair, its `collect` parts and its `chunk_retired` event.  The
        slot-major outlier and bitmask arrive with one contiguous row
        per slot, so the members' rows are one gather of whole rows;
        the gathered rows are read-only, so `collect` and the events
        keep views of them, not copies."""
        wall = (inf.sync_wall if inf.sync_wall is not None
                else time.perf_counter() - inf.t0)
        runs, slots, ns = inf.runs, inf.slots, inf.ns
        m = len(runs)
        rows = np.fromiter((r.row for r in runs), np.intp, m)
        retired = int(ns.sum())
        self.call_log.append({
            "kind": "fused", "t": inf.t_len, "slots": m,
            "retired": retired,
            "wall_s": wall, "sync": inf.sync_wall is not None})
        # running latency instrument: each call weighted by the samples
        # it retired (stats() reads percentiles back O(1) — the old
        # per-call re-sort of the whole log is gone)
        self._h_wall.observe(wall * 1e3, weight=max(retired, 1))
        self._c_samples.inc(retired)
        # every member's rows gathered once, (slots, t) up to the longest
        # it retired: row i is runs[i]'s slot, valid over its first n
        # samples (the engine zeroes each slot's outputs past them)
        t_max = int(ns.max())
        cols = outlier[slots, :t_max]
        cols.flags.writeable = False
        n_flags = cols.sum(axis=1)
        counts = sums = bits = None
        if self._ensemble:
            counts, sums, bits = self._members(ecc, scores, slots, t_max)
        elif self.collect:
            bits = ecc[slots, :t_max]
            bits.flags.writeable = False
        acc = self._rows
        acc.samples[rows] += ns
        acc.flags[rows] += n_flags
        multi = ns > 1      # a multi-sample (chunked) ride
        acc.prefill_chunks[rows] += multi
        acc.decode_steps[rows] += ~multi   # the 1-sample decode trickle
        if counts is not None:
            acc.det_flags[rows] += counts
        if sums is not None:
            # float32 sums added to float64 rows: bit for bit what
            # per-member float additions give
            acc.det_scores[rows] += sums
            acc.scored[rows] = True
        total = int(n_flags.sum())
        if total:
            self._c_flags.inc(total)
            flagged = (events["flagged"] if events is not None
                       else self._deferred_flagged)
            flagged.extend([runs[i].req.rid
                            for i in np.flatnonzero(n_flags).tolist()])
        # each member's rows as views, made once for `collect` and the
        # events both
        stream = self.events.active
        outs, eccs = [], []
        cap = self.latency_log_len
        for i, (run, n) in enumerate(zip(runs, ns.tolist())):
            lat = run.stats.chunk_latency_s
            if len(lat) < cap:
                lat.append((wall, n))
            if self.collect or stream:
                outs.append(cols[i, :n])
                if bits is not None:
                    eccs.append(bits[i, :n])
            if self.collect:
                run.outlier_parts.append(outs[i])
                run.ecc_parts.append(eccs[i])
            run.inflight -= 1
        if stream:
            self.events.publish_many(
                "chunk_retired", self.tick_no,
                zip([r.req.rid for r in runs],
                    self._retired_events(inf, outs, eccs, n_flags, counts,
                                         sums)))
        self._g_inflight.set(len(self._inflight))

    def _retired_events(self, inf: _InFlight, outs, eccs, n_flags,
                        counts, sums):
        """The data of each member's `chunk_retired` event: its outlier
        and bitmask row views, the rest from `.tolist()` of the call's
        arrays.  The (members, K) arrays are listed flat and sliced per
        member: one list a call, where a list per member would outlive
        the loop and add to what the garbage collector scans."""
        names = self._det_names
        k = len(names)
        n_flags = n_flags.tolist()
        counts = counts.ravel().tolist() if counts is not None else None
        sums = sums.ravel().tolist() if sums is not None else None
        for i, (slot, n) in enumerate(zip(inf.slots.tolist(),
                                          inf.ns.tolist())):
            data = {"slot": slot, "n": n, "flags": n_flags[i],
                    "dispatch_tick": inf.tick, "outlier": outs[i]}
            if inf.shard is not None:
                data["shard"] = inf.shard
            if self.collect:
                data["ecc"] = eccs[i]
            j = i * k
            if counts is not None:
                data["det_flags"] = {d: c for d, c in
                                     zip(names, counts[j:j + k]) if c}
                data["detectors"] = names
            if sums is not None:
                data["det_scores"] = dict(zip(names, sums[j:j + k]))
            yield data

    def _members(self, ecc: np.ndarray, scores: Optional[np.ndarray],
                 slots: np.ndarray, t_max: int):
        """The ensemble's per-member columns of one call, for every
        member slot at once: (members, K) per-detector flag counts,
        (members, K) float32 score sums (None without scores) and the
        read-only bitmask rows (each member's first n samples valid;
        None without `collect`).  Counts the per-detector flag
        counters.  The engine zeroes the bitmask and the scores past
        each slot's valid rows, so rows are reduced whole, up to the
        call's longest `t_max`.  The slot-major bitmask is gathered by
        row first, and the gathered rows are the `collect` rows; the
        (K, t, C) scores are gathered first when the call holds few of
        the pool's slots, else reduced first and then picked.  Traced
        as a `members` span (`k` detectors, `slots`)."""
        tracer = self.tracer
        names = self._det_names
        with (tracer.span("members", device=True, tick=self.tick_no,
                          k=len(names), slots=len(slots))
              if tracer.enabled else NULL_SPAN):
            # bit d of the "ecc" bitmask is detectors[d]
            bits = ecc[slots, :t_max]
            counts = np.stack([np.count_nonzero(bits & (1 << d), axis=1)
                               for d in range(len(names))])
            for d, det in enumerate(names):
                total = int(counts[d].sum())
                if total:
                    self._det_counter(det).inc(total)
            sums = None
            if scores is not None:
                # row d of the score block is detectors[d]'s float
                # score stream
                if 4 * len(slots) < scores.shape[2]:
                    sums = scores[:, :t_max, slots].sum(axis=1).T
                else:
                    sums = scores[:, :t_max].sum(axis=1)[:, slots].T
            bits.flags.writeable = False
            return counts.T, sums, bits if self.collect else None

    def _fold(self, run: _Run) -> None:
        """Copy a running request's counter row into its RequestStats."""
        self._c_folds.inc()
        acc, r, st = self._rows, run.row, run.stats
        st.samples = int(acc.samples[r])
        st.flags = int(acc.flags[r])
        st.prefill_chunks = int(acc.prefill_chunks[r])
        st.decode_steps = int(acc.decode_steps[r])
        if self._ensemble:
            names = self._det_names
            st.det_flags.clear()
            st.det_flags.update(
                (d, c) for d, c in zip(names, acc.det_flags[r].tolist())
                if c)
            if acc.scored[r]:
                st.det_scores.update(zip(names, acc.det_scores[r].tolist()))

    def _fold_rid(self, rid: str) -> None:
        """`_fold` for a `stats_by_rid` read: running requests only
        (queued ones have no row, finished ones were folded at
        completion)."""
        run = self.runs.get(rid)
        if run is not None:
            self._fold(run)

    def _flush(self, events: Optional[dict] = None) -> None:
        """Retire every in-flight call (the consume-side sync)."""
        if not self._inflight:
            return
        with (self.tracer.span("flush", tick=self.tick_no,
                               calls=len(self._inflight))
              if self.tracer.enabled else NULL_SPAN):
            while self._inflight:
                self._retire(self._inflight.popleft(), events)

    def step(self) -> dict:
        """One scheduler tick; returns {admitted, flagged, completed}.

        In the async loop, `flagged` events surface on the tick whose
        retirement fetched them — one tick after dispatch.  Traced as
        one `tick` span holding a span per phase.
        """
        self._c_ticks.inc()
        if not self.tracer.enabled:
            return self._tick()
        with self.tracer.span("tick", device=True, tick=self.tick_no):
            return self._tick()

    def _tick(self) -> dict:
        events: dict = {"admitted": [], "flagged": [], "completed": []}
        if self._deferred_flagged:
            events["flagged"].extend(self._deferred_flagged)
            self._deferred_flagged.clear()
        # host bookkeeping first: admission + take + vlens assembly all
        # overlap with the previous tick's in-flight device compute
        if (self._sharded and self.rebalance_every
                and self.tick_no > 0
                and self.tick_no % self.rebalance_every == 0):
            self._rebalance()
        self._admit(events)
        ready = sorted(self._ready.values(), key=_by_admission)
        deep = self.pipeline_depth > 1 and not self.measure_latency
        if deep and ready:
            # fence: a slot in a still-in-flight call cannot join a new
            # one (its chunks must be fetched in dispatch order).  When
            # every ready slot is fenced, force-retire oldest calls
            # until one frees up — a tick with work always dispatches.
            # The fence key is (shard, slot): local slot indices
            # collide across shards, the pair never does.
            def _free():
                fenced = {r.place for i in self._inflight
                          for r in i.runs}
                return [r for r in ready if r.place not in fenced]
            free = _free()
            while not free and self._inflight:
                self._retire(self._inflight.popleft(), events)
                free = _free()
            if free:
                self._dispatch(free)
        elif ready:
            self._dispatch(ready)
        if deep:
            # out-of-order retirement: calls whose outputs already
            # landed on host retire now, whatever their dispatch order
            # (fencing makes per-slot order immune to it); then the
            # oldest calls retire until the pipeline fits its depth
            # (each shard dispatches its own call, so a K-shard pool
            # keeps depth*K calls in flight)
            for inf in [i for i in self._inflight
                        if _host_ready(i.out)]:
                self._inflight.remove(inf)
                self._retire(inf, events)
            depth_cap = self.pipeline_depth * self.n_shards
            while len(self._inflight) > depth_cap:
                self._retire(self._inflight.popleft(), events)
        else:
            # retire everything dispatched *before* this tick; this
            # tick's call stays in flight across the tick boundary (the
            # double buffer) unless the loop is synchronous
            while self._inflight and (
                    self.measure_latency
                    or self._inflight[0].tick < self.tick_no):
                self._retire(self._inflight.popleft(), events)

        done = [r.req.rid for r in sorted(self._closing.values(),
                                          key=_by_admission)
                if r.avail == 0]
        if any(self.runs[rid].inflight for rid in done):
            # completion consumes results: sync the tail call now so
            # done_tick/telemetry are final the tick the stream drains
            self._flush(events)
        if done:
            with (self.tracer.span("complete", device=True,
                                   tick=self.tick_no, completed=len(done))
                  if self.tracer.enabled else NULL_SPAN):
                self._complete(done, events)
        return events

    def _complete(self, done: List[str], events: dict) -> None:
        """Release the drained requests' slots and record them done,
        evicting the oldest finished records past `keep_finished`."""
        for rid in done:
            run = self.runs.pop(rid)
            del self._closing[rid]
            run.phase = DONE
            self._fold(run)
            self._rows.give(run.row)
            st = run.stats
            st.done_tick = self.tick_no
            if self._sharded:
                self.pool.release(rid)
            else:
                self.pool.release([run.slot])
            self._c_completed.inc()
            ch = self._cls(st.priority)
            ch["running"].dec()
            ch["completed"].inc()
            ch["latency"].observe(st.done_tick - st.submitted_tick)
            events["completed"].append(rid)
            self.events.publish("done", self.tick_no, rid,
                                slot=run.slot, samples=st.samples,
                                flags=st.flags, priority=st.priority)
            self._finished[rid] = run
            while len(self._finished) > self.keep_finished:
                old = next(iter(self._finished))  # oldest completion
                del self._finished[old]
                self._stats.pop(old, None)
                self._note_evicted(old)
                self.events.publish("evicted", self.tick_no, old)

    def _rebalance(self) -> None:
        """Run the pool's occupancy rebalancer and mirror the moves
        into scheduler bookkeeping.  Streams with in-flight calls are
        pinned in place: migration's state fetch must not race a
        dispatched chunk, and the fence key (shard, slot) must stay
        stable while a call referencing it is outstanding."""
        avoid = {rid for rid, r in self.runs.items() if r.inflight}
        moves = self.pool.rebalance(avoid=avoid, tick=self.tick_no)
        for rid, _src, dst, new_slot in moves:
            run = self.runs[rid]
            run.shard = dst
            run.slot = new_slot
            st = run.stats
            st.shard = dst
            st.slot = new_slot
            st.migrations += 1

    def _note_evicted(self, rid: str) -> None:
        if len(self._evicted) == self._evicted.maxlen:
            old = self._evicted.popleft()
            n = self._evicted_counts.get(old, 0) - 1
            if n <= 0:
                self._evicted_counts.pop(old, None)
            else:
                self._evicted_counts[old] = n
        self._evicted.append(rid)
        self._evicted_counts[rid] = self._evicted_counts.get(rid, 0) + 1

    def drain(self, max_ticks: int = 100_000) -> int:
        """Tick until every submitted request has completed; returns
        the number of ticks it took.  Raises immediately — naming the
        rids — when progress is impossible because requests are still
        open (no pending samples, not closed): they hold their slots
        waiting for `feed`, and only `close()` lets them finish."""
        start = self.tick_no
        while self.queued_total or self.runs:
            if self._sharded:
                # pool-wide headroom is not enough here: each class's
                # FIFO head is pinned to its ring shard, so progress
                # needs *that* shard (not just any shard) to have room
                can_admit = any(
                    self.pool.shard_free(self.pool.route(q[0].rid)) > 0
                    for q in self._queues.values() if q)
            else:
                can_admit = bool(self.queued_total) and (
                    self.pool.occupancy < self.pool.max_capacity)
            has_work = (self._inflight
                        or any(r.avail > 0 for r in self.runs.values()))
            completing = any(r.req.closed and r.avail == 0
                             for r in self.runs.values())
            if not (can_admit or has_work or completing):
                open_rids = sorted(rid for rid, r in self.runs.items()
                                   if not r.req.closed)
                raise RuntimeError(
                    f"drain stalled: requests {open_rids} are open with "
                    "no pending samples — they wait on feed() forever; "
                    "close() them (or feed more data) before drain()")
            if self.tick_no - start >= max_ticks:
                raise RuntimeError(
                    f"drain exceeded {max_ticks} ticks with "
                    f"{self.queued_total} queued / {len(self.runs)} "
                    "running requests")
            self.step()
        self._flush()
        return self.tick_no - start

    # --------------------------------------------------------- results
    def _missing(self, rid: str) -> KeyError:
        if rid in self._evicted_counts:
            return EvictedRequest(
                f"request {rid!r} completed and was evicted "
                f"(keep_finished={self.keep_finished}); raise the "
                "retention cap to keep results longer")
        return KeyError(f"unknown request {rid!r}")

    def results(self, rid: str) -> dict:
        """Per-sample verdicts of a request, in stream order.  Syncs
        the async loop: any of the request's in-flight samples are
        fetched before returning."""
        run = self.runs.get(rid) or self._finished.get(rid)
        if run is None:
            raise self._missing(rid)
        if not self.collect:
            raise RuntimeError("scheduler built with collect=False")
        if run.inflight:
            self._flush()  # consume-side sync point
        cat = (lambda parts, dt: np.concatenate(parts)
               if parts else np.zeros((0,), dt))
        return {"ecc": cat(run.ecc_parts, np.float32),
                "outlier": cat(run.outlier_parts, bool)}

    def telemetry(self, rid: str) -> RequestStats:
        """The request's `RequestStats`: a running request's in-flight
        calls are retired first (the sync point) and its counter row
        folded in, so the counts cover every sample dispatched so far."""
        st = self._stats.get(rid)
        if st is None:
            raise self._missing(rid)
        run = self.runs.get(rid)
        if run is not None:
            if run.inflight:
                self._flush()  # consume-side sync point
            self._fold(run)
        return st

    def request_phase(self, rid: str) -> str:
        """Lifecycle phase of a request: queued/prefill/decode/done."""
        run = self.runs.get(rid)
        if run is not None:
            return run.phase
        if rid in self._finished:
            return DONE
        if rid in self._stats:
            return QUEUED
        raise self._missing(rid)

    def stats(self) -> dict:
        """Aggregate scheduler telemetry (the serving-bench payload),
        read back from the obs registry in O(instruments) — nothing is
        re-sorted or re-scanned per call.

        `chunk_latency` percentiles come from the running weighted
        wall-time histogram (each fused call weighted by the samples
        it retired, estimated at bucket edges); `classes` carries
        per-priority-class state counts plus queue-wait and
        completion-latency percentiles over *every* request the class
        ever saw (retention eviction no longer shifts them);
        `programs` lists the (capacity, t, layout) program cache — its
        size going flat after warmup is the no-recompile guarantee of
        the adaptive path.
        """
        lat = {}
        if self._h_wall.count:
            lat = {"calls": len(self.call_log),
                   "p50_ms": self._h_wall.quantile(0.5),
                   "p95_ms": self._h_wall.quantile(0.95)}
        classes: Dict[str, dict] = {}
        for cls, ch in self._classes.items():
            c = {"queued": int(ch["queued"].value),
                 "running": int(ch["running"].value),
                 "completed": int(ch["completed"].value)}
            for key, h in (("queue_wait_ticks", ch["wait"]),
                           ("latency_ticks", ch["latency"])):
                if h.count:
                    c[f"{key}_p50"] = h.quantile(0.5)
                    c[f"{key}_p95"] = h.quantile(0.95)
            classes[cls] = c
        out = {"ticks": self.tick_no, "completed": self.completed,
               "running": len(self.runs), "queued": self.queued_total,
               "rejected_submits": self.rejected,
               "inflight_calls": len(self._inflight),
               "pipeline_depth": self.pipeline_depth,
               "short_ticks": self.short_ticks,
               "chunk_latency": lat, "classes": classes,
               "stats_folds": int(self._c_folds.value),
               "h2d_bytes": int(self._c_h2d.value),
               "d2h_bytes": int(self._c_d2h.value),
               "programs": self.pool.programs(),
               "pool": self.pool.stats()}
        if self._sharded:
            out["shards"] = self.n_shards
            out["migrations"] = self.pool.migrations
            out["imbalance"] = self.pool.imbalance
        if self._ensemble:
            out["detector_flags"] = {
                d: int(c.value) for d, c in self._det_counters.items()}
        return out
