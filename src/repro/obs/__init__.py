"""repro.obs — dependency-free observability for the serving stack.

Four parts, all host-side and zero-overhead when unused:

  * `metrics` — Counter/Gauge/Histogram instruments with labels in a
    `MetricsRegistry` (JSON snapshot + Prometheus text exposition);
    the engine, pool and scheduler keep their telemetry here and
    `stats()` reads it back O(1).  The scheduler counts the bytes it
    sends to and fetches from the device
    (`sched_h2d_bytes_total`, `sched_d2h_bytes_total`).
  * `trace` — `TickTracer`, a bounded ring buffer of span events
    exportable as Chrome trace-event JSON for Perfetto; `NULL_TRACER`
    is the free disabled default.  `BatchingScheduler.step()` records
    one `tick` span holding a span per phase: `admission` (with
    `acquire` per `SlotPool.acquire`), `assemble`, `dispatch`,
    `retire` (the output fetch), `account`, `complete`, and `flush`
    when it syncs early; instants mark `admit`, `pool.resize` and
    `shard.migrate`.  Timestamps are relative to `TickTracer.origin`,
    a `time.perf_counter()` value.
  * `compiles` — `compile_watch()`, the process's one listener on
    JAX's compile events: `jax_compiles_total`,
    `jax_compile_seconds_total` and `jax_compile_cache_hits_total` in
    the global registry, and a `compile` span in every enabled tracer.
  * `events` — `EventBus`: the scheduler streams structured events
    (admitted / chunk_retired / done / evicted) at retirement via
    `BatchingScheduler.subscribe()` and `serve_streams(on_event=)`.

See README §observability.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram,
                               LATENCY_MS_BUCKETS, MetricsRegistry,
                               TICK_BUCKETS, auto_name, get_registry)
from repro.obs.compiles import CompileWatch, compile_watch
from repro.obs.trace import NULL_SPAN, NULL_TRACER, NullTracer, TickTracer
from repro.obs.events import Event, EventBus, Subscription

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "auto_name", "LATENCY_MS_BUCKETS", "TICK_BUCKETS",
    "CompileWatch", "compile_watch",
    "TickTracer", "NullTracer", "NULL_TRACER", "NULL_SPAN",
    "Event", "EventBus", "Subscription",
]
