"""Packed per-stream engine state + the pure functional core.

The paper's FPGA pipeline is a *stateful online* detector — one sample
in, one verdict out, O(1) state carried forever.  `EngineState` packs
that state for C independent univariate TEDA modules (the paper's
replicated-module scaling) as per-channel `k` / mean / var vectors plus
an `active` occupancy mask, so every slot is ragged: its own stream
position, recyclable for a new tenant mid-flight via
`engine_attach` / `engine_detach` / `engine_reset`.

Everything here is pure and jittable — `core/guard.py` runs
`engine_step` inside a compiled train step.
This module is a leaf (it depends only on `core/teda.py`): the backend
registry and the stateful `StreamEngine` wrapper live one level up in
`engine/backends.py` / `engine/engine.py`.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.teda import TedaOutput, TedaState, teda_step

__all__ = ["EngineState", "engine_init", "engine_process", "engine_step",
           "engine_reset", "engine_attach", "engine_detach", "slot_mask"]


class EngineState(NamedTuple):
    """Packed per-stream state: C independent univariate TEDA modules.

    k:      (C,) — samples absorbed per slot (honest per-channel count).
    mean:   (C,) — recursive mean, eq (2).
    var:    (C,) — recursive variance, eq (3).
    active: (C,) bool — slot occupancy; inactive slots never advance.
    aux:    (R, C) detector-axis carry rows, or None.  The "ensemble"
            backend packs its K-detector shared fabric here (prefix-sum
            tails + variance carry, R = backend.aux_rows — see
            `repro.detectors`); the TEDA backends carry no aux and the
            field stays None.  `mean`/`var` are derived mirrors of the
            aux rows under the ensemble backend.

    dtype is float32, or int32 Q-values under the "pallas-q" backend.
    """

    k: jnp.ndarray
    mean: jnp.ndarray
    var: jnp.ndarray
    active: jnp.ndarray
    aux: Optional[jnp.ndarray] = None


def engine_init(capacity: int, dtype=jnp.float32,
                active: bool = True, aux_rows: int = 0) -> EngineState:
    """Fresh packed state for `capacity` slots (Algorithm 1 init).

    Each field gets its own buffer — aliased zeros would break buffer
    donation when the state is carried through a jitted step.
    `aux_rows` > 0 allocates the detector-axis carry block (the
    ensemble backend's `backend.aux_rows`).
    """
    return EngineState(k=jnp.zeros((capacity,), dtype),
                       mean=jnp.zeros((capacity,), dtype),
                       var=jnp.zeros((capacity,), dtype),
                       active=jnp.full((capacity,), active),
                       aux=(jnp.zeros((aux_rows, capacity), dtype)
                            if aux_rows else None))


def slot_mask(slots, capacity: int):
    """Normalize a slot selector to a (C,) bool mask.

    `slots` may be None (all slots), a bool mask, or integer indices.
    A concrete selector gives a host (numpy) mask, so slot admin never
    waits on the device to build one; a traced selector under jit gives
    a traced mask.  Concrete indices are bounds-checked — JAX scatter
    silently drops out-of-range indices, which would turn attach/reset
    on a bad slot into a successful-looking no-op.
    """
    if isinstance(slots, jax.core.Tracer):
        if slots.dtype == bool:
            return slots.reshape((capacity,))
        return jnp.zeros((capacity,), bool).at[slots].set(True)
    if slots is None:
        return np.ones((capacity,), bool)
    idx = np.asarray(slots)
    if idx.dtype == bool:
        return idx.reshape((capacity,))
    mask = np.zeros((capacity,), bool)
    if not idx.size:
        return mask
    if idx.min() < 0 or idx.max() >= capacity:
        raise IndexError(
            f"slot indices {np.unique(idx).tolist()} out of range for "
            f"capacity {capacity}")
    mask[idx] = True
    return mask


def engine_reset(state: EngineState, slots=None) -> EngineState:
    """Zero the TEDA state of the selected slots (k=mean=var=0), keeping
    occupancy — the mid-flight recycle for a new tenant on a live slot."""
    m = slot_mask(slots, state.k.shape[0])
    zero = jnp.zeros((), state.k.dtype)
    return EngineState(k=jnp.where(m, zero, state.k),
                       mean=jnp.where(m, zero, state.mean),
                       var=jnp.where(m, zero, state.var),
                       active=state.active,
                       aux=(None if state.aux is None
                            else jnp.where(m[None, :], zero, state.aux)))


def engine_attach(state: EngineState, slots) -> EngineState:
    """Activate (and zero) the selected slots for new streams."""
    m = slot_mask(slots, state.k.shape[0])
    state = engine_reset(state, m)
    return state._replace(active=jnp.logical_or(state.active, m))


def engine_detach(state: EngineState, slots) -> EngineState:
    """Deactivate the selected slots; their state is cleared and they
    stop advancing (and flagging) until re-attached."""
    m = slot_mask(slots, state.k.shape[0])
    state = engine_reset(state, m)
    return state._replace(active=jnp.logical_and(state.active, ~m))


def engine_process(state: EngineState, x: jnp.ndarray, backend,
                   m=None, valid_lens=None, sel=None,
                   thr=None) -> Tuple[EngineState, dict]:
    """Advance the packed state through one (T, C) chunk.

    `backend` follows the `engine.backends.Backend` contract (duck-typed
    so this module stays a leaf).  Inactive slots are frozen (their
    state does not advance) and never flag.  `m` optionally overrides
    the backend's constructed threshold — a scalar or per-slot (C,)
    vector (tenants at different sensitivity levels in one batch).

    `valid_lens` (per-slot (C,) int vector) makes the call ragged: slot
    c retires exactly valid_lens[c] leading rows (0..T) of its column —
    the backend freezes each slot's state after its own prefix, slots
    with vlen=0 are frozen bit-exactly at the packed state (no float
    round-trip through the backend), and no slot flags at rows beyond
    its valid length.  The caller owns folding occupancy/participation
    into the vector (inactive slot => vlen 0).  `None` is the uniform
    path: every active slot retires all T rows.

    Returns (state', {"ecc": (T, C), "outlier": (T, C) bool}) — `ecc`
    is in the backend's native domain (Q int32 for "pallas-q").

    Aux-carrying backends (`backend.aux_rows > 0`, i.e. the ensemble)
    take the extra per-slot `sel` selection weights / `thr` vote
    thresholds and return a 7-tuple — `ecc` is then the per-detector
    flag bitmask, `outlier` the fused vote, and the output dict grows
    "scores": the (K, T, C) per-detector float score streams (zeroed
    on frozen/inactive slots); the aux block freezes with the same
    masks as k/mean/var.
    """
    if getattr(backend, "aux_rows", 0):
        return _engine_process_aux(state, x, backend, m, valid_lens,
                                   sel, thr)
    if valid_lens is None:
        kf, mf, vf, ecc, outlier = backend.process(x, state.k, state.mean,
                                                   state.var, m=m)
        act = state.active
        new = EngineState(
            k=jnp.where(act, kf.astype(state.k.dtype), state.k),
            mean=jnp.where(act, mf, state.mean),
            var=jnp.where(act, vf, state.var),
            active=act,
        )
        outs = {"ecc": ecc,
                "outlier": jnp.logical_and(outlier, act[None, :])}
        return new, outs

    vl = jnp.asarray(valid_lens, jnp.int32)
    kf, mf, vf, ecc, outlier = backend.process(
        x, state.k, state.mean, state.var, m=m, valid_lens=vl)
    adv = vl > 0  # fully-suspended slots: exact engine-level freeze
    new = EngineState(
        k=jnp.where(adv, kf.astype(state.k.dtype), state.k),
        mean=jnp.where(adv, mf, state.mean),
        var=jnp.where(adv, vf, state.var),
        active=state.active,
    )
    rows = jnp.arange(x.shape[0], dtype=vl.dtype)[:, None]
    outs = {"ecc": ecc,
            "outlier": jnp.logical_and(outlier, rows < vl[None, :])}
    return new, outs


def _engine_process_aux(state: EngineState, x, backend, m, valid_lens,
                        sel, thr) -> Tuple[EngineState, dict]:
    """The aux-carrying (ensemble) leg of `engine_process`.

    The backend's kernel already zeroes flags and votes beyond each
    slot's valid prefix, so the ragged leg passes the verdicts through;
    the uniform leg gates on `active` exactly like the TEDA leg.
    """
    if valid_lens is None:
        kf, mf, vf, auxf, bits, vote, scores = backend.process(
            x, state.k, state.mean, state.var, aux=state.aux, m=m,
            sel=sel, thr=thr)
        act = state.active
        new = EngineState(
            k=jnp.where(act, kf.astype(state.k.dtype), state.k),
            mean=jnp.where(act, mf, state.mean),
            var=jnp.where(act, vf, state.var),
            active=act,
            aux=jnp.where(act[None, :], auxf, state.aux))
        outs = {"ecc": jnp.where(act[None, :], bits, 0),
                "outlier": jnp.logical_and(vote, act[None, :]),
                "scores": jnp.where(act[None, None, :], scores, 0.0)}
        return new, outs

    vl = jnp.asarray(valid_lens, jnp.int32)
    kf, mf, vf, auxf, bits, vote, scores = backend.process(
        x, state.k, state.mean, state.var, aux=state.aux, m=m,
        valid_lens=vl, sel=sel, thr=thr)
    adv = vl > 0
    new = EngineState(
        k=jnp.where(adv, kf.astype(state.k.dtype), state.k),
        mean=jnp.where(adv, mf, state.mean),
        var=jnp.where(adv, vf, state.var),
        active=state.active,
        aux=jnp.where(adv[None, :], auxf, state.aux))
    rows = jnp.arange(x.shape[0], dtype=vl.dtype)[:, None]
    live = rows < vl[None, :]
    outs = {"ecc": jnp.where(live, bits, 0),
            "outlier": jnp.logical_and(vote, live),
            "scores": jnp.where(live[None], scores, 0.0)}
    return new, outs


def engine_step(state: EngineState, x: jnp.ndarray,
                m: float | jnp.ndarray = 3.0
                ) -> Tuple[EngineState, TedaOutput]:
    """Single-sample fast path: one packed update for x (C,).

    The T=1 analog of `engine_process` for in-loop monitors (the
    training-loss guard) — one `teda_step` on the packed vectors,
    cheap enough to live inside a jitted train step.  Float-state
    only (the Q datapath goes through `engine_process`).
    """
    if jnp.issubdtype(state.k.dtype, jnp.integer):
        raise TypeError(
            "engine_step is float-state only; Q-format (int32) state "
            "advances through engine_process with the 'pallas-q' backend")
    ts, out = teda_step(
        TedaState(k=state.k, mean=state.mean[:, None], var=state.var),
        x[:, None], m)
    act = state.active
    new = EngineState(k=jnp.where(act, ts.k, state.k),
                      mean=jnp.where(act, ts.mean[:, 0], state.mean),
                      var=jnp.where(act, ts.var, state.var),
                      active=act)
    out = out._replace(outlier=jnp.logical_and(out.outlier, act))
    return new, out
