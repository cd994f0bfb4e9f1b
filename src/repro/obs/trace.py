"""Tick-level tracer: bounded ring buffer of span events, Chrome
trace-event JSON export.

The serving tick is a pipeline (`BatchingScheduler.step()`: admission,
assemble, dispatch, retire, account, complete, with pool resizes and
JAX compiles as out-of-band events); this records it as spans with
monotonic timestamps and tick/rid/slot attribution, in a ring buffer
that grows to `capacity` and then overwrites its oldest events, so a
forever-running gateway traces at O(capacity) memory.
`to_chrome_trace()` emits the Chrome trace-event JSON that Perfetto
(ui.perfetto.dev) and `chrome://tracing` open directly.

Off by default and zero-cost when disabled: components hold the
module's `NULL_TRACER` singleton (``enabled = False``, no-op
`span`/`instant`), and guard any argument assembly behind
``tracer.enabled`` — a disabled serving run records nothing and pays
nothing beyond one attribute check per site.

With ``annotate_device=True`` (and jax importable), spans marked
``device=True`` also enter a `jax.profiler.TraceAnnotation`, so host
spans line up with the device trace when a run is captured under
`jax.profiler.trace()`.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import List, Optional

from repro.obs.compiles import compile_watch

__all__ = ["TickTracer", "NullTracer", "NULL_TRACER", "NULL_SPAN"]

try:  # optional pass-through to device traces
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # pragma: no cover - jax is a baked-in dep here
    _TraceAnnotation = None


class _NullSpan:
    """Reusable no-op context manager (one shared instance)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        return None


#: the shared no-op span: ``with (tracer.span(...) if tracer.enabled
#: else NULL_SPAN):`` keeps a disabled site at one attribute check
NULL_SPAN = _NullSpan()


class NullTracer:
    """The disabled recorder: every call is a no-op.

    Components default to the `NULL_TRACER` singleton so the tracing
    hooks cost one truthiness check when tracing is off.
    """

    enabled = False

    def span(self, name: str, device: bool = False, **args):
        return NULL_SPAN

    def instant(self, name: str, **args) -> None:
        return None

    def events(self) -> List[dict]:
        return []

    def to_chrome_trace(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}


NULL_TRACER = NullTracer()


class _Span:
    """Context manager recording one duration ("X") event on exit."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann")

    def __init__(self, tracer: "TickTracer", name: str, device: bool,
                 args: dict):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._ann = (_TraceAnnotation(name)
                     if device and tracer._annotate_device
                     and _TraceAnnotation is not None else None)

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **args) -> None:
        """Add arguments known only once the span's work is done."""
        self._args.update(args)

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer._record(self._name, "X", self._t0,
                             dur_s=t1 - self._t0, args=self._args)
        return False


class TickTracer:
    """Bounded ring-buffer recorder of scheduler/pool/engine events.

    >>> tracer = TickTracer(capacity=4096)
    >>> with tracer.span("dispatch", device=True, tick=3, t=32):
    ...     out = pool.process(x, valid_lens=vlens)
    >>> tracer.instant("pool.resize", frm=8, to=16)
    >>> tracer.dump("trace.json")          # open in ui.perfetto.dev

    `capacity` bounds memory: past it the oldest events are
    overwritten (`dropped` counts the overwrites).  Timestamps are
    `time.perf_counter()` microseconds relative to `origin`, a
    `perf_counter()` value taken as the constructor's last act —
    monotonic, shared by every component handed this tracer; an
    event's host-clock time is ``origin + ts * 1e-6``.  Construction
    is O(1) whatever the capacity (the ring grows as it fills), so a
    caller timing the constructor finds `origin` at its end.

    Every enabled tracer also receives JAX's backend compiles as
    `compile` spans (`repro.obs.compiles`).
    """

    enabled = True

    def __init__(self, capacity: int = 65536,
                 annotate_device: bool = False):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1: {capacity}")
        self.capacity = int(capacity)
        self._annotate_device = bool(annotate_device)
        self._buf: List[dict] = []
        self._head = 0          # next write position
        self.total = 0          # events ever recorded
        self._pid = os.getpid()
        self._lock = threading.Lock()
        compile_watch().attach(self)
        self.origin = time.perf_counter()

    # ------------------------------------------------------ recording
    def _record(self, name: str, ph: str, t_start: float, *,
                dur_s: Optional[float] = None,
                args: Optional[dict] = None) -> None:
        ev = {"name": name, "ph": ph, "pid": self._pid,
              "tid": threading.get_ident(),
              "ts": (t_start - self.origin) * 1e6}
        if dur_s is not None:
            ev["dur"] = dur_s * 1e6
        if args:
            ev["args"] = args
        with self._lock:
            if len(self._buf) < self.capacity:
                self._buf.append(ev)
            else:
                self._buf[self._head] = ev
            self._head = (self._head + 1) % self.capacity
            self.total += 1

    def span(self, name: str, device: bool = False, **args) -> _Span:
        """Context manager recording a duration span; `device=True`
        additionally enters a `jax.profiler.TraceAnnotation` when the
        tracer was built with ``annotate_device=True``."""
        return _Span(self, name, device, args)

    def instant(self, name: str, **args) -> None:
        """Record a zero-duration instant event."""
        self._record(name, "i", time.perf_counter(), args=args)

    def complete(self, name: str, start: float, end: float,
                 **args) -> None:
        """Record a duration span whose `perf_counter()` start and end
        are already known (work timed by someone else)."""
        self._record(name, "X", start, dur_s=end - start, args=args)

    # ------------------------------------------------------ inspection
    def __len__(self) -> int:
        return min(self.total, self.capacity)

    @property
    def dropped(self) -> int:
        """Events overwritten by ring wraparound."""
        return max(0, self.total - self.capacity)

    def events(self) -> List[dict]:
        """Retained events, oldest first (recording order survives
        wraparound)."""
        with self._lock:
            if self.total < self.capacity:
                return list(self._buf)
            return (self._buf[self._head:] + self._buf[:self._head])

    def to_chrome_trace(self) -> dict:
        """The Chrome trace-event JSON document (Perfetto-loadable);
        events are sorted by timestamp as the viewers expect."""
        evs = sorted(self.events(), key=lambda e: e["ts"])
        return {"traceEvents": evs, "displayTimeUnit": "ms",
                "otherData": {"recorded": self.total,
                              "dropped": self.dropped}}

    def dump(self, path) -> None:
        """Write the Chrome trace JSON to `path`."""
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
            f.write("\n")
