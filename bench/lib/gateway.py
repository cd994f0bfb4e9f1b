"""The system under test, as a client sees it: a `BatchingScheduler`
built from a configuration file, its verdicts taken from the event bus
with the host clock's time of delivery, and the harness's own host
spans around each call into it."""
from __future__ import annotations

import time
from contextlib import contextmanager



class Spans:
    """The harness's host spans on the host clock, each also entered as
    a `jax.profiler.TraceAnnotation` so that a profiler trace places
    it beside the device's operations."""

    def __init__(self):
        import jax
        self._ann = jax.profiler.TraceAnnotation
        self.recording = False
        self.spans = []   # (name, start, end) while recording

    @contextmanager
    def span(self, name: str):
        if not self.recording:
            yield
            return
        t0 = time.perf_counter()
        with self._ann(name):
            yield
        self.spans.append((name, t0, time.perf_counter()))


class Gateway:
    """One scheduler, its event sink and its spans."""

    def __init__(self, cfg: dict, *, trace: bool, interpret=None):
        from repro.launch.batching import BatchingScheduler
        from repro.obs import TickTracer
        opts = {}
        if "fmt" in cfg:
            from repro.fixedpoint import QFormat
            f = cfg["fmt"]
            opts["fmt"] = QFormat(f["word_len"], f["frac_len"],
                                  f["rounding"])
        if "detectors" in cfg:
            opts["detectors"] = tuple(cfg["detectors"])
        if interpret is not None:
            opts["interpret"] = interpret
        # further scheduler settings of the deployment, as named by
        # BatchingScheduler (e.g. shards); "shard_devices": "each"
        # gives every shard a device of its own
        extra = dict(cfg.get("scheduler", {}))
        if extra.get("shard_devices") == "each":
            import jax
            extra["shard_devices"] = jax.devices()[:int(extra["shards"])]
        opts.update(extra)
        self.tracer = None
        self.tracer_t0 = None
        if trace:
            t0 = time.perf_counter()
            self.tracer = TickTracer(capacity=1 << 20,
                                     annotate_device=True)
            self.tracer_t0 = (t0 + time.perf_counter()) / 2
        n = int(cfg["streams"])
        self.sched = BatchingScheduler(
            cfg["backend"], buckets=tuple(cfg["buckets"]),
            chunk_t=int(cfg["chunk_t"]), decode_t=int(cfg["decode_t"]),
            m=float(cfg["m"]), queue_limit=2 * n,
            collect=bool(cfg["collect"]), tracer=self.tracer, **opts)
        self.spans = Spans()
        self._inbox = []
        clock = time.perf_counter
        inbox = self._inbox

        def sink(ev):
            if ev.kind == "chunk_retired":
                inbox.append((clock(), ev))

        self.sched.events.attach(sink)

    def step(self) -> None:
        with self.spans.span("step"):
            self.sched.step()

    def take_events(self) -> list:
        """Verdict events delivered since the last call, with their
        delivery times."""
        out = list(self._inbox)
        self._inbox.clear()
        return out

    def quiesce(self) -> None:
        """Wait until the device has finished every call dispatched so
        far (the traced stretch starts and ends on such a point)."""
        import jax
        pool = self.sched.pool
        for p in getattr(pool, "pools", [pool]):
            jax.block_until_ready(p.engine.state)

    def program_spans(self, args: bool = False) -> list:
        """The program's own spans (dispatch / retire / flush), as
        (name, start, end) on the host clock, with the span's
        arguments (tick, slots, shard, ...) as a fourth item when
        `args`."""
        if self.tracer is None:
            return []
        out = []
        for ev in self.tracer.events():
            if ev.get("ph") == "X":
                t0 = self.tracer_t0 + ev["ts"] * 1e-6
                span = (ev["name"], t0, t0 + ev["dur"] * 1e-6)
                out.append(span + (ev.get("args", {}),) if args else span)
        return out
