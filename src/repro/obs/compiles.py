"""JAX's own compile events, counted and traced.

JAX reports each backend compile of a jitted program through
`jax.monitoring`: the event `/jax/core/compile/backend_compile_duration`
with its duration, which covers an XLA compile or a load from the
persistent compilation cache, and `/jax/compilation_cache/cache_hits`
for each such load.  Importing this module installs one process-wide
listener on both (JAX's listener lists are process-wide, so a second
listener would count every compile twice), which `compile_watch()`
returns.  It feeds

  * `jax_compiles_total`, `jax_compile_seconds_total` and
    `jax_compile_cache_hits_total` in the process-global registry
    (`get_registry()`), from the import on;
  * a `compile` span, ending when JAX reports it and lasting the
    reported duration, into every enabled `TickTracer` (each attaches
    itself on construction and is held weakly).

It is installed on import, not by the first tracer, so that building
a tracer stays a few microseconds long: its `origin` then lies within
microseconds of any point of the constructor's interval.

A compile inside a serving window is a stall the window pays for: the
benchmark counts the `compile` spans in its traced stretch.
"""
from __future__ import annotations

import time
import weakref

import jax.monitoring

from repro.obs.metrics import MetricsRegistry, get_registry

__all__ = ["CompileWatch", "compile_watch"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileWatch:
    """Counters and tracers fed by JAX's compile events.  Read the
    counts whole, or per phase with `mark()` / `since(mark)`."""

    def __init__(self, registry: MetricsRegistry):
        self._c_compiles = registry.counter(
            "jax_compiles_total",
            "backend compiles of jitted programs (persistent-cache "
            "loads included)")
        self._c_seconds = registry.counter(
            "jax_compile_seconds_total",
            "seconds spent in backend compiles")
        self._c_hits = registry.counter(
            "jax_compile_cache_hits_total",
            "programs loaded from the persistent compilation cache")
        self._tracers = weakref.WeakSet()

    def attach(self, tracer) -> None:
        """Record this tracer's `compile` spans from now on (held
        weakly: a dropped tracer is forgotten)."""
        self._tracers.add(tracer)

    def on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event != COMPILE_EVENT:
            return
        end = time.perf_counter()
        self._c_compiles.inc()
        self._c_seconds.inc(duration)
        for tracer in list(self._tracers):
            if tracer.enabled:
                tracer.complete("compile", end - duration, end,
                                fun=str(kwargs.get("fun_name", "")))

    def on_event(self, event: str, **kwargs) -> None:
        if event == CACHE_HIT_EVENT:
            self._c_hits.inc()

    @property
    def compiles(self) -> int:
        return int(self._c_compiles.value)

    @property
    def compile_s(self) -> float:
        return self._c_seconds.value

    @property
    def hits(self) -> int:
        return int(self._c_hits.value)

    def mark(self) -> tuple:
        return (self.compiles, self.compile_s, self.hits)

    def since(self, mark: tuple) -> dict:
        return {"compiles": self.compiles - mark[0],
                "compile_s": self.compile_s - mark[1],
                "cache_hits": self.hits - mark[2]}


_WATCH = CompileWatch(get_registry())
jax.monitoring.register_event_duration_secs_listener(_WATCH.on_duration)
jax.monitoring.register_event_listener(_WATCH.on_event)


def compile_watch() -> CompileWatch:
    """The process's one `CompileWatch`."""
    return _WATCH
