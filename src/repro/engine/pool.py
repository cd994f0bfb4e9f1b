"""Autoscaling slot pool: bucketed capacities over `StreamEngine`.

Growing or shrinking tenancy must not recompile every shape: a JAX
program is specialized on the (T, C) chunk shape, so an engine whose
capacity tracked occupancy exactly would pay a fresh compile on every
attach/detach.  `SlotPool` quantizes capacity to a fixed bucket ladder
(e.g. 8/16/32/64): acquiring a slot beyond the current bucket re-pads
the packed state up to the next bucket, releasing the last tenants of a
bucket re-pads it down — and every bucket's engine (with its compiled
chunk programs) is cached, so a tenancy level seen before costs zero
compiles.  Slot indices are stable across resizes (state is padded at
the tail, never compacted), which is what lets a scheduler treat a slot
as a request lifecycle (`launch/batching.py`).

`PoolFull` (capacity exhausted at the top bucket) is the backpressure
signal — explicit, with occupancy attached, never a silent drop.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.engine.engine import StreamEngine
from repro.engine.state import EngineState, slot_mask
from repro.obs import NULL_TRACER, MetricsRegistry, auto_name

__all__ = ["SlotPool", "PoolFull"]


class PoolFull(RuntimeError):
    """All buckets are full: acquisition must wait for a release."""

    def __init__(self, msg: str, occupancy: int, capacity: int):
        super().__init__(msg)
        self.occupancy = occupancy
        self.capacity = capacity


class SlotPool:
    """Bucketed autoscaling pool of TEDA engine slots.

    >>> pool = SlotPool("pallas", buckets=(8, 16, 32, 64))
    >>> a, b = pool.acquire(2, m=2.5)       # capacity snaps to 8
    >>> out = pool.process(chunk)           # chunk: (T, pool.capacity)
    >>> pool.release([a])                   # may shrink back a bucket

    All engine options (`fmt`, `block_t`, `interpret`, ...) pass
    through to the per-bucket `StreamEngine`s.
    """

    # the spans a traced pool records, one per call of the same name
    SPANS = ("acquire", "release")

    def __init__(self, backend: str = "scan", *,
                 buckets: Tuple[int, ...] = (8, 16, 32, 64),
                 m: float = 3.0, registry=None, tracer=None,
                 name: Optional[str] = None, **engine_opts):
        if not buckets or any(b <= 0 for b in buckets):
            raise ValueError(f"buckets must be positive: {buckets}")
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.backend_name = backend
        self.default_m = float(m)
        self._opts = dict(engine_opts, m=m)
        self._engines: dict[int, StreamEngine] = {}
        self._bucket = self.buckets[0]
        # observability (repro.obs): the registry is shared with every
        # per-bucket engine (engine series are labelled `<pool>/capN`),
        # so one snapshot covers the whole pool; the tracer records an
        # `acquire` / `release` span per call and a `pool.resize` instant
        self.registry = (MetricsRegistry() if registry is None
                         else registry)
        self.tracer = NULL_TRACER if tracer is None else tracer
        self.name = auto_name("pool") if name is None else str(name)
        lbl = {"pool": self.name}
        self._g_occupancy = self.registry.gauge(
            "pool_occupancy", "attached tenant slots",
            ("pool",)).labels(**lbl)
        self._g_capacity = self.registry.gauge(
            "pool_capacity", "current bucket capacity",
            ("pool",)).labels(**lbl)
        self._g_capacity.set(self._bucket)
        self._c_grows = self.registry.counter(
            "pool_grows_total", "bucket grow transitions",
            ("pool",)).labels(**lbl)
        self._c_shrinks = self.registry.counter(
            "pool_shrinks_total", "bucket shrink transitions",
            ("pool",)).labels(**lbl)
        self._c_full = self.registry.counter(
            "pool_full_total",
            "PoolFull backpressure raises (acquire beyond top bucket)",
            ("pool",)).labels(**lbl)

    # ------------------------------------------------------- engines
    def _engine_for(self, bucket: int) -> StreamEngine:
        eng = self._engines.get(bucket)
        if eng is None:
            eng = StreamEngine(bucket, self.backend_name,
                               auto_attach=False,
                               registry=self.registry,
                               name=f"{self.name}/cap{bucket}",
                               **self._opts)
            self._engines[bucket] = eng
        return eng

    @property
    def engine(self) -> StreamEngine:
        """The live engine at the current bucket capacity."""
        return self._engine_for(self._bucket)

    @property
    def capacity(self) -> int:
        return self._bucket

    @property
    def max_capacity(self) -> int:
        return self.buckets[-1]

    @property
    def resizes(self) -> int:
        """Grow + shrink transitions (read from the obs registry)."""
        return int(self._c_grows.value + self._c_shrinks.value)

    @property
    def occupancy(self) -> int:
        return len(self.engine.active_slots)

    @property
    def free_slots(self) -> np.ndarray:
        return np.flatnonzero(~self.engine.active_mask)

    # ------------------------------------------------------- resizing
    def _resize(self, bucket: int) -> None:
        """Re-pad the packed state into `bucket`'s cached engine."""
        if bucket == self._bucket:
            return
        src, dst = self.engine, self._engine_for(bucket)
        st, keep = src.state, min(self._bucket, bucket)

        def pad(v, fill):
            v = np.asarray(v)[:keep]
            out = np.full((bucket,), fill, v.dtype)
            out[:keep] = v
            return jnp.asarray(out)

        def pad2(v):
            # (R, C) detector-axis aux: pad the slot axis.  A raw host
            # copy of whatever rows the backend's StateSpec declares —
            # element *bits* carry over untouched, which is the aux
            # migration contract: opaque regions (e.g. the teda-q
            # member's int32 Q registers bitcast into the f32 block,
            # some of which alias NaN patterns) survive resizes exactly.
            v = np.asarray(v)[:, :keep]
            out = np.zeros((v.shape[0], bucket), v.dtype)
            out[:, :keep] = v
            return jnp.asarray(out)

        dst.state = EngineState(k=pad(st.k, 0), mean=pad(st.mean, 0),
                                var=pad(st.var, 0),
                                active=pad(src.active_mask, False),
                                aux=(None if st.aux is None
                                     else pad2(st.aux)))
        new_m = np.full((bucket,), self.default_m, np.float32)
        new_m[:keep] = src.slot_m[:keep]
        dst.set_m(None, new_m)
        if getattr(src, "_ensemble", False):
            # per-slot detector selection rides along with the state
            dst._det_w[:, :keep] = src._det_w[:, :keep]
            dst._det_w[:, keep:] = np.asarray(
                dst.backend.weights, np.float32)[:, None]
            dst._det_thr[:keep] = src._det_thr[:keep]
            dst._det_thr[keep:] = dst.backend.default_threshold
            src._reset_detectors(np.ones((self._bucket,), bool))
        # the old engine keeps only its compiled programs, not tenants
        src.state = EngineState(
            k=jnp.zeros_like(st.k), mean=jnp.zeros_like(st.mean),
            var=jnp.zeros_like(st.var),
            active=jnp.zeros_like(st.active),
            aux=None if st.aux is None else jnp.zeros_like(st.aux))
        (self._c_grows if bucket > self._bucket
         else self._c_shrinks).inc()
        if self.tracer.enabled:
            self.tracer.instant("pool.resize", pool=self.name,
                                frm=self._bucket, to=bucket)
        self._bucket = bucket
        self._g_capacity.set(bucket)

    def _bucket_holding(self, n_slots: int, max_idx: int) -> Optional[int]:
        """Smallest bucket with room for `n_slots` keeping index
        `max_idx` addressable; None if even the top bucket is too small."""
        for b in self.buckets:
            if b >= n_slots and b > max_idx:
                return b
        return None

    # ------------------------------------------------------- tenancy
    def acquire(self, n: int = 1, *, m: Optional[float] = None,
                detectors=None, vote=None) -> np.ndarray:
        """Attach `n` new tenants, growing the bucket if needed.

        Returns the acquired slot indices (stable across resizes).
        Raises `PoolFull` when the top bucket cannot hold them — the
        scheduler's backpressure signal.  `detectors` / `vote` select
        the new tenants' detector subset and vote mode under the
        ensemble backend (`StreamEngine.attach`).

        Traced as one `acquire` span (`resized` says whether it
        re-padded, which fetches the packed state and so waits for the
        call in flight).
        """
        if not self.tracer.enabled:
            return self._acquire(n, m, detectors, vote)
        bucket = self._bucket
        with self.tracer.span("acquire", device=True, n=n,
                              pool=self.name) as span:
            try:
                return self._acquire(n, m, detectors, vote)
            finally:
                span.set(resized=self._bucket != bucket)

    def _acquire(self, n, m, detectors, vote) -> np.ndarray:
        act = self.engine.active_slots
        need = len(act) + n
        if need > self._bucket:
            max_idx = int(act.max()) if len(act) else -1
            target = self._bucket_holding(need, max_idx)
            if target is None:
                self._c_full.inc()
                raise PoolFull(
                    f"pool full: want {n} more slots with "
                    f"{len(act)}/{self.max_capacity} active at the "
                    f"top bucket", len(act), self.max_capacity)
            self._resize(target)
        idx = self.engine.attach(n=n, m=m, detectors=detectors,
                                 vote=vote)
        self._g_occupancy.set(need)
        return idx

    def release(self, slots) -> None:
        """Detach tenants; shrink to the smallest bucket that still
        addresses every remaining active slot.

        Traced as one `release` span, shaped like `acquire`.
        """
        mask = slot_mask(slots, self._bucket)
        if not self.tracer.enabled:
            self._release(mask)
            return
        bucket = self._bucket
        with self.tracer.span("release", device=True, n=int(mask.sum()),
                              pool=self.name) as span:
            try:
                self._release(mask)
            finally:
                span.set(resized=self._bucket != bucket)

    def _release(self, mask: np.ndarray) -> None:
        self.engine.detach(mask)
        act = self.engine.active_slots
        self._g_occupancy.set(len(act))
        max_idx = int(act.max()) if len(act) else -1
        target = self._bucket_holding(len(act), max_idx)
        if target is not None and target < self._bucket:
            self._resize(target)

    # ------------------------------------------------------- processing
    def process(self, x, active=None, valid_lens=None) -> dict:
        """Feed one (T, capacity) chunk, or one slot-major (capacity, T)
        `SlotMajor` frame, to the current bucket's engine.  A slot-major
        frame gets its `ecc` and `outlier` back slot-major, (capacity,
        T), transposed on the device (see `StreamEngine.process`).

        `active` is the per-call participation mask and `valid_lens`
        the per-slot ragged retire counts (see `StreamEngine.process`);
        chunk width — and the `valid_lens` vector length — must equal
        the *current* `pool.capacity`: schedulers re-read it after
        acquire/release (`_resize` re-pads the packed *state* across
        buckets, but per-call vectors are built fresh each tick).

        Non-blocking: the returned verdicts are async-dispatch futures
        (see `StreamEngine.process`).  A later `_resize` is the one
        state-dependent sync point — it fetches the packed state to
        re-pad it, so it waits for in-flight calls; resizes are rare
        (bucket transitions only) and never invalidate outputs already
        dispatched at the old capacity.
        """
        return self.engine.process(x, active=active,
                                   valid_lens=valid_lens)

    def programs(self) -> list:
        """Every (capacity, T, layout) program-cache key executed so
        far, across all cached bucket engines (layout "slot" for a
        `SlotMajor` frame, "time" for a (T, C) chunk).  Flat after
        warmup = the adaptive-chunk path recompiles nothing."""
        return sorted((cap, t, layout)
                      for cap, eng in self._engines.items()
                      for t, layout in eng.program_shapes)

    def stats(self) -> dict:
        return {"bucket": self._bucket, "buckets": list(self.buckets),
                "occupancy": self.occupancy, "resizes": self.resizes,
                "compiled_buckets": sorted(self._engines),
                "programs": self.programs()}
