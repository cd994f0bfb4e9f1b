"""Stateful multi-stream TEDA engine with ragged multi-tenant slots.

`StreamEngine` owns packed per-stream state (`engine/state.py`) and
processes arbitrary-length (T, C) chunks as they arrive, carrying exact
state across calls for every backend in the registry
(`engine/backends.py`).  Multi-tenancy is ragged by construction: every
slot has its own `k` and its own outlier threshold `m` (tenants run
different sensitivity levels in one batch), an `active` mask gates
state advancement, and `attach` / `detach` / `reset` recycle a slot for
a new tenant mid-flight without touching neighbours.  `process` takes
optional per-call raggedness controls: `valid_lens` gives every slot
its own retired-sample count for the call (0..T — one fused kernel
program serves prefill-heavy and decode-phase slots together), and the
`active` participation mask is the vlen=0 special case kept as sugar,
so a scheduler can freeze slots that have no data this step without
releasing them (the continuous-batching suspend, `launch/batching.py`).
A scheduler hands its chunk over slot-major (`SlotMajor`, (C, T)) and
reads the verdicts back the same way; the transposes run on the device.

With a `mesh`, chunk processing fans out over the channel axis via
`shard_map` (`make_channel_fanout` below) — channels are
independent, so multi-device scale needs no collectives.  With a
`device`, the packed state is committed to that one device, so every
call on it runs there (one shard per chip in `engine/sharded.py`).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.teda import TedaState
from repro.engine.backends import get_backend
from repro.engine.state import (EngineState, engine_init, engine_process,
                                engine_reset, slot_mask)
from repro.obs import MetricsRegistry, auto_name

__all__ = ["SlotMajor", "StreamEngine"]


class SlotMajor(np.ndarray):
    """A host chunk laid out slot-major: (capacity, T), row c holding
    slot c's samples, so a scheduler writes each slot's samples as one
    contiguous row.  Mark a plain array with `frame.view(SlotMajor)`;
    it passes through `SlotPool.process` / `ShardedPool.process_shard`
    as it is, and `StreamEngine.process` transposes it on the device and
    returns the per-sample verdicts slot-major too."""


def make_channel_fanout(fn, mesh: Mesh, axis_name: str = "data"):
    """shard_map fan-out of an independent-channel stream processor.

    `fn(x, k, mean, var, vlen, m) -> ((k', mean', var'),
    (ecc, outlier))` — the `repro.engine` backend contract: x is (T, C)
    with C independent univariate streams on the lane axis, the state
    rows (and the per-slot valid-length vector `vlen` and threshold
    vector `m`) are (C,) vectors, and the per-sample outputs are
    (T, C).  Channels are independent TEDA
    modules (the paper's replicated-module scaling, §5.2.1), so the
    fan-out needs no collectives: each device runs `fn` on its C/D
    channel slice.  The caller must keep C divisible by the axis size
    (StreamEngine asserts this).
    """
    vec = P(axis_name)
    row = P(None, axis_name)
    return jax.shard_map(
        fn, mesh=mesh, check_vma=False,
        in_specs=(row, vec, vec, vec, vec, vec),
        out_specs=((vec, vec, vec), (row, row)),
    )


def _slot_major(core):
    """`core` on a (C, T) chunk: transposed to (T, C) on the way in,
    its per-sample `ecc` and `outlier` back to (C, T) on the way out,
    and anything after them (the ensemble's (K, T, C) scores) as it
    is.  The transposes run on the device, beside the kernel."""
    def rows(x, *args):
        st, (ecc, outlier, *rest) = core(x.T, *args)
        return st, (ecc.T, outlier.T, *rest)
    return rows


@jax.jit
def _settle(state: EngineState, touched, active) -> EngineState:
    """Every attach, detach and reset since the state was last read, in
    one update.  Each of the three zeroes its slots (`engine_reset`)
    and leaves their active bit where the host mirror has it, so the
    touched slots end zeroed with the mirror's bit: the state of the
    calls one by one, bit for bit.  One compiled program per state
    signature (capacity, dtype, aux rows, placement); the two host
    masks are its transfers.  No donation: a resize or a migration
    reads the old state after it."""
    return engine_reset(state, touched)._replace(
        active=jnp.where(touched, active, state.active))


class StreamEngine:
    """Stateful multi-stream TEDA detector over `capacity` slots.

    >>> eng = StreamEngine(capacity=256, backend="pallas", m=3.0)
    >>> verdicts = eng.process(chunk)          # chunk: (T, 256)
    >>> rows = eng.process(frame.view(SlotMajor))  # frame: (256, T)
    >>> eng.reset([7])                         # recycle slot 7 mid-flight
    >>> eng.detach([3]); eng.attach([3], m=2.5)  # slot 3: new tenant

    Chunks may have any length T >= 1; state is carried exactly across
    calls (bit-for-bit on the Q path).  With `mesh=`, processing fans
    out over the channel axis via shard_map for multi-device scale.
    """

    def __init__(self, capacity: int, backend: str = "scan", *,
                 m: float = 3.0, fmt=None, block_t: int = 256,
                 block_c: Optional[int] = None,
                 interpret: Optional[bool] = None, lane_pad: int = 128,
                 mesh=None, axis_name: str = "data", device=None,
                 auto_attach: bool = True, registry=None,
                 name: Optional[str] = None, **backend_opts):
        if mesh is not None and device is not None:
            raise ValueError("pass a mesh (channel fan-out) or a device "
                             "(single-device placement), not both")
        self.capacity = int(capacity)
        self.device = device
        self.default_m = float(m)
        # observability (repro.obs): process-call / samples-retired /
        # program-shape counters, labelled by engine instance (JAX's
        # own compile events are counted by `repro.obs.compile_watch`)
        self.registry = (MetricsRegistry() if registry is None
                         else registry)
        self.name = auto_name("engine") if name is None else str(name)
        lbl = {"engine": self.name}
        self._c_calls = self.registry.counter(
            "engine_process_calls_total",
            "process() chunk calls", ("engine",)).labels(**lbl)
        self._c_samples = self.registry.counter(
            "engine_samples_retired_total",
            "samples retired across all slots (per the caller's "
            "valid_lens)", ("engine",)).labels(**lbl)
        self._c_programs = self.registry.counter(
            "engine_programs_compiled_total",
            "distinct (capacity, T, layout) programs executed",
            ("engine",)).labels(**lbl)
        # the host mirror of state.active (`active_mask`) is read back
        # from the device only after a state assigned from outside
        self._c_active_fetches = self.registry.counter(
            "engine_active_fetches_total",
            "host mirror of the active mask read back from the device "
            "(after a state assigned from outside)",
            ("engine",)).labels(**lbl)
        # block_c tiles the kernel grid's channel axis into parallel
        # strips (multi-core TPU scaling at wide capacity); extra
        # keyword options flow to the backend factory untouched (e.g.
        # verdict=False selects the full-trajectory Q path)
        self.backend = get_backend(backend, m=m, fmt=fmt, block_t=block_t,
                                   block_c=block_c, interpret=interpret,
                                   lane_pad=lane_pad, **backend_opts)
        # aux-carrying backends (the detector ensemble) grow the packed
        # state by backend.aux_rows rows per slot and take per-slot
        # detector-selection weights + vote thresholds each call
        n_aux = int(getattr(self.backend, "aux_rows", 0) or 0)
        self._ensemble = n_aux > 0
        if self._ensemble and mesh is not None:
            raise ValueError(
                "mesh fan-out is not supported with the ensemble "
                "backend (the aux state axis is not sharded)")
        self.state = engine_init(self.capacity, self.backend.state_dtype,
                                 active=auto_attach, aux_rows=n_aux)
        self._active_host = self._frozen(
            np.full((self.capacity,), bool(auto_attach)))
        if self._ensemble:
            self._det_names = tuple(self.backend.detectors)
            self._det_w = np.broadcast_to(
                np.asarray(self.backend.weights, np.float32)[:, None],
                (len(self._det_names), self.capacity)).copy()
            self._det_thr = np.full((self.capacity,),
                                    self.backend.default_threshold,
                                    np.float32)
        # per-slot outlier sensitivity, eq (6) m — float even on the Q
        # path (the backend quantizes m^2+1 itself)
        self._m = np.full((self.capacity,), self.default_m, np.float32)
        # (T, layout) of every chunk this engine has executed: together
        # with the capacity they key the jit program cache, so a flat
        # set after warmup means no tick recompiles (the adaptive-chunk
        # guarantee surfaced through SlotPool.stats()["programs"])
        self._t_shapes: set = set()

        if self._ensemble:
            def core(x, k, mean, var, aux, vlen, m, sel, thr):
                st, outs = engine_process(
                    EngineState(k=k, mean=mean, var=var, active=vlen > 0,
                                aux=aux),
                    x, self.backend, m=m, valid_lens=vlen, sel=sel,
                    thr=thr)
                return ((st.k, st.mean, st.var, st.aux),
                        (outs["ecc"], outs["outlier"], outs["scores"]))
        else:
            def core(x, k, mean, var, vlen, m):
                st, outs = engine_process(
                    EngineState(k=k, mean=mean, var=var, active=vlen > 0),
                    x, self.backend, m=m, valid_lens=vlen)
                return ((st.k, st.mean, st.var),
                        (outs["ecc"], outs["outlier"]))

        self._mesh = mesh
        if mesh is not None:
            n_shards = dict(mesh.shape)[axis_name]
            if self.capacity % n_shards:
                raise ValueError(
                    f"capacity {self.capacity} not divisible by mesh "
                    f"axis {axis_name!r} ({n_shards} shards)")
            core = make_channel_fanout(core, mesh, axis_name)
        # one core, two entries: the (T, C) chunk and the slot-major
        # (C, T) frame, the latter transposed around the (fanned-out)
        # core so that sharding propagates through the transposes
        self._fn = jax.jit(core)
        self._fn_rows = jax.jit(_slot_major(core))

    @property
    def state(self) -> EngineState:
        """The packed device state, with every slot update so far."""
        if self._touched is not None:
            self._state = _settle(self._state, self._touched,
                                  self._active_host)
            self._touched = None
        return self._state

    @state.setter
    def state(self, st: EngineState) -> None:
        # a pinned engine re-commits every state it is handed (resizes
        # and migrations build theirs from host arrays), so its jitted
        # calls keep following the state onto its device.  A state from
        # outside may hold any mask: the host mirror is read back from
        # it once, when next needed.
        self._state = (st if self.device is None
                       else jax.device_put(st, self.device))
        self._active_host = None
        self._touched = None

    @property
    def active_mask(self) -> np.ndarray:
        """Host mirror of `state.active`, a read-only (capacity,) bool
        array.  attach/detach/reset set it and `process` keeps the mask
        itself, so slot admin and per-call metrics never wait on the
        device; only a state assigned from outside is read back, once
        (`engine_active_fetches_total`)."""
        if self._active_host is None:
            self._active_host = self._frozen(np.asarray(self._state.active))
            self._c_active_fetches.inc()
        return self._active_host

    @staticmethod
    def _frozen(mask: np.ndarray) -> np.ndarray:
        mask = np.array(mask, bool)
        mask.flags.writeable = False
        return mask

    def _stage(self, mask: np.ndarray, active: np.ndarray) -> None:
        """Record a slot update on the host: zero the `mask` slots and
        give the mirror `active`.  The device sees it when the state is
        next read (`process`, a resize, a migration), folded with every
        other update since into one dispatch (`_settle`)."""
        self._touched = (np.array(mask, bool) if self._touched is None
                         else self._touched | mask)
        self._active_host = self._frozen(active)

    # ------------------------------------------------------ slot admin
    def attach(self, slots=None, n: Optional[int] = None, *,
               m: Optional[float] = None, detectors=None, vote=None):
        """Activate slots for new streams; returns the slot indices.

        With `slots=None`, grabs the first `n` free slots (all free
        slots when `n` is also None).  Attaching an occupied slot, or
        asking for slots on a full engine, raises with the current
        occupancy — JAX scatter silently drops out-of-range updates, so
        without the check a bad attach would look like a success while
        clobbering (or skipping) a live tenant.  `m` sets the new
        tenants' outlier sensitivity (default: the engine's `m`).

        Under the ensemble backend, `detectors` selects the subset of
        the backend's detectors these tenants run (default: all of
        them) and `vote` their vote mode / threshold fraction (default:
        the backend's) — see `set_detectors`.  Both raise on a
        non-ensemble backend.
        """
        occupied = self.active_mask
        n_act, cap = int(occupied.sum()), self.capacity
        if slots is None:
            free = np.flatnonzero(~occupied)
            if n is None and not len(free):
                raise ValueError(
                    f"no free slots: engine full ({n_act}/{cap} active)")
            if n is not None and len(free) < n:
                raise ValueError(
                    f"wanted {n} free slots, have {len(free)} "
                    f"({n_act}/{cap} active)")
            idx = free if n is None else free[:n]
        else:
            idx = np.atleast_1d(np.asarray(slots))
            busy = np.unique(idx[occupied[idx]]) if idx.size else idx
            if busy.size:
                raise ValueError(
                    f"slots {busy.tolist()} already attached "
                    f"({n_act}/{cap} active); detach or reset them first")
        mask = slot_mask(idx, cap)
        self._stage(mask, occupied | mask)
        self._m[idx] = self.default_m if m is None else float(m)
        if detectors is not None or vote is not None:
            self.set_detectors(idx, detectors=detectors, vote=vote)
        elif self._ensemble:
            self._reset_detectors(idx)
        return idx

    def detach(self, slots):
        mask = slot_mask(slots, self.capacity)
        self._stage(mask, self.active_mask & ~mask)
        # recycled slots revert to the default sensitivity/detectors
        self._m[mask] = self.default_m
        if self._ensemble:
            self._reset_detectors(mask)

    def _reset_detectors(self, slots: np.ndarray) -> None:
        """Give `slots` (indices or a boolean mask) the backend's
        default detectors and vote."""
        self._det_w[:, slots] = np.asarray(
            self.backend.weights, np.float32)[:, None]
        self._det_thr[slots] = self.backend.default_threshold

    def set_detectors(self, slots=None, *, detectors=None,
                      vote=None) -> None:
        """Re-select the detector subset / vote mode of live slots.

        `detectors` is a subset of the backend's ensemble members
        (None keeps all of them); unselected members get weight 0 on
        those slots — their state still advances (the shared fabric is
        detector-agnostic) but they contribute neither flags nor vote
        weight, so a masked slot is exactly a smaller ensemble.  `vote`
        is a mode name ("any" / "majority" / "all") or a weight
        fraction in (0, 1]; None keeps the backend's mode, re-evaluated
        over the *selected* weights.  Only valid under the ensemble
        backend.
        """
        if not self._ensemble:
            raise ValueError(
                f"backend {self.backend.name!r} has no detector "
                "ensemble; per-slot detectors need backend='ensemble'")
        from repro.detectors import vote_threshold
        mask = np.asarray(slot_mask(slots, self.capacity))
        if detectors is None:
            w = np.asarray(self.backend.weights, np.float32)
        else:
            chosen = ((detectors,) if isinstance(detectors, str)
                      else tuple(detectors))
            unknown = [d for d in chosen if d not in self._det_names]
            if unknown or not chosen:
                raise ValueError(
                    f"detectors must be a non-empty subset of this "
                    f"ensemble's members {list(self._det_names)}, got "
                    f"{detectors!r}")
            w = np.asarray(
                [self.backend.weights[d] if name in chosen else 0.0
                 for d, name in enumerate(self._det_names)], np.float32)
        thr = vote_threshold(self.backend.vote if vote is None else vote,
                             w)
        self._det_w[:, mask] = w[:, None]
        self._det_thr[mask] = thr

    def detector_config(self, slot: int) -> dict:
        """The live detector selection of one slot: {"detectors":
        selected member names, "weights": (K,) per-member weights,
        "threshold": the vote-weight threshold}."""
        if not self._ensemble:
            raise ValueError(
                f"backend {self.backend.name!r} has no detector "
                "ensemble")
        w = self._det_w[:, slot]
        return {"detectors": tuple(n for d, n in enumerate(self._det_names)
                                   if w[d] > 0),
                "weights": w.copy(),
                "threshold": float(self._det_thr[slot])}

    def reset(self, slots=None):
        self._stage(slot_mask(slots, self.capacity), self.active_mask)

    def set_m(self, slots, m) -> None:
        """Retune the outlier sensitivity of the selected slots.

        With integer `slots`, a vector `m` is matched positionally
        (`set_m([3, 1], [2.0, 5.0])` sets slot 3 to 2.0 and slot 1 to
        5.0); `slots` may also be None (all) or a bool mask.
        """
        m = np.asarray(m, np.float32)
        if slots is None:
            self._m[:] = m
            return
        slots = np.asarray(slots)
        if slots.dtype == bool:
            self._m[slots.reshape(self.capacity)] = m
            return
        idx = np.atleast_1d(slots).astype(int)
        if idx.size and (idx.min() < 0 or idx.max() >= self.capacity):
            raise IndexError(
                f"slot indices {np.unique(idx).tolist()} out of range "
                f"for capacity {self.capacity}")
        self._m[idx] = m

    # ------------------------------------------------------ processing
    def _account(self, t_len: int, layout: str, vc, had_vlens: bool,
                 active) -> None:
        """Update the obs instruments for one `process` call.

        `vc` is the concrete valid_lens (None when traced under an
        outer jit — the retired count is then unknowable on host and
        skipped; calls/programs still count).
        """
        t_key = int(t_len)
        if (t_key, layout) not in self._t_shapes:
            self._t_shapes.add((t_key, layout))
            self._c_programs.inc()
        self._c_calls.inc()
        if had_vlens and vc is None:
            return
        amask = self.active_mask
        if active is not None:
            amask = amask & slot_mask(active, self.capacity)
        if not had_vlens:
            retired = t_key * int(amask.sum())
        elif vc.ndim == 0:
            retired = int(vc) * int(amask.sum())
        else:
            retired = int(vc[amask].sum())
        if retired:
            self._c_samples.inc(retired)

    def process(self, x: jnp.ndarray, active=None,
                valid_lens=None) -> dict:
        """Feed one (T, capacity) chunk, or one slot-major (capacity, T)
        `SlotMajor` frame; returns per-sample verdicts.

        A `SlotMajor` frame is transposed to (T, C) on the device, inside
        the jitted call, and `ecc` and `outlier` come back slot-major,
        (C, T): row c is slot c's verdicts, for a host that reads each
        slot's as one contiguous row.  The ensemble's (K, T, C)
        `scores` keep their layout.  Both layouts run the same program
        around the kernel and give the same values, bit for bit.

        `valid_lens` makes the call ragged: a scalar or per-slot
        (capacity,) int vector, slot c retires exactly valid_lens[c]
        leading rows of its column (0..T) in this one fused call — its
        state freezes after its own prefix (bit-for-bit on the Q path)
        and it never flags beyond it.  vlen=0 is the suspend: frozen,
        no flags, still attached.

        `active` optionally restricts the call to a subset of slots (a
        bool mask or integer indices) — sugar for vlen=0 on everyone
        else, composable with `valid_lens`.  Detached slots are always
        held at vlen=0 regardless of either argument.

        The call is non-blocking: the returned `ecc`/`outlier` (and the
        carried state) are JAX async-dispatch futures, so a scheduler
        can overlap its next tick's host bookkeeping with the device
        compute and fetch verdicts only when it consumes them
        (`launch/batching.py`'s double-buffered loop).
        """
        rows = isinstance(x, SlotMajor)
        x = jnp.asarray(x)
        if x.ndim != 2 or x.shape[int(not rows)] != self.capacity:
            want = (f"({self.capacity}, T) slot-major" if rows
                    else f"(T, {self.capacity})")
            raise ValueError(f"chunk must be {want}, got {x.shape}")
        t_len = x.shape[int(rows)]
        fn = self._fn_rows if rows else self._fn
        st = self.state
        part = st.active if active is None else jnp.logical_and(
            st.active, slot_mask(active, self.capacity))
        vc = None
        if valid_lens is None:
            vl = jnp.full((self.capacity,), t_len, jnp.int32)
        else:
            vl = jnp.asarray(valid_lens, jnp.int32)
            try:
                vc = np.asarray(vl)  # concrete: host bounds check
            except Exception:
                vc = None  # traced under jit
            if vc is not None and vc.size and (
                    vc.min() < 0 or vc.max() > t_len):
                raise ValueError(
                    f"valid_lens must lie in [0, T={t_len}], got "
                    f"[{vc.min()}, {vc.max()}]")
            if vl.ndim == 0:
                vl = jnp.broadcast_to(vl, (self.capacity,))
            elif vl.shape != (self.capacity,):
                raise ValueError(
                    f"valid_lens must be scalar or ({self.capacity},), "
                    f"got {vl.shape}")
        vl = jnp.where(part, vl, 0)
        # uniform sensitivity keeps the kernels' scalar fast path (the
        # in-kernel verdict); only a genuinely mixed batch pays the
        # vector-m eq (6) re-evaluation.  The fan-out path shards m as
        # a (C,) vector, and the ensemble kernel broadcasts m itself,
        # so both always take the vector form.
        mv = self._m
        if self._mesh is None and not self._ensemble \
                and (mv == mv[0]).all():
            mv = mv[0]
        self._account(t_len, "slot" if rows else "time", vc,
                      valid_lens is not None, active)
        if self._ensemble:
            (k, mean, var, aux), (bits, vote, scores) = fn(
                x, st.k, st.mean, st.var, st.aux, vl,
                jnp.asarray(self.backend.quantize_m(mv)),
                jnp.asarray(self._det_w), jnp.asarray(self._det_thr))
            self._state = EngineState(k=k, mean=mean, var=var,
                                      active=st.active, aux=aux)
            # det_flags doubles as the backend-native "ecc" stream so
            # the serving stack's fetch plumbing stays structurally
            # unchanged; both keys alias the same array.  "scores" is
            # the (K, T, C) per-detector float score-stream block.
            return {"ecc": bits, "outlier": vote, "det_flags": bits,
                    "scores": scores}
        (k, mean, var), (ecc, outlier) = fn(
            x, st.k, st.mean, st.var, vl,
            jnp.asarray(self.backend.quantize_m(mv)))
        self._state = EngineState(k=k, mean=mean, var=var,
                                  active=st.active)
        return {"ecc": ecc, "outlier": outlier}

    # ------------------------------------------------------- introspection
    @property
    def active_slots(self) -> np.ndarray:
        return np.flatnonzero(self.active_mask)

    @property
    def samples_seen(self) -> np.ndarray:
        """Per-slot sample counts (the honest per-channel k)."""
        return np.asarray(self.state.k)

    @property
    def slot_m(self) -> np.ndarray:
        """Per-slot outlier sensitivity (eq (6) m), a (capacity,) copy."""
        return self._m.copy()

    @property
    def program_shapes(self) -> list:
        """Sorted (T, layout) of the chunks this engine has executed,
        layout "time" for a (T, C) chunk and "slot" for a `SlotMajor`
        frame — each is one entry of the jit program cache at this
        capacity."""
        return sorted(self._t_shapes)

    def teda_state(self) -> TedaState:
        """The packed state in the `repro.core` TedaState layout."""
        return TedaState(k=self.state.k, mean=self.state.mean[:, None],
                         var=self.state.var)
