"""Jitted public wrappers around the TEDA Pallas kernels.

One contract layer for all four kernel entry points (full float, slim
verdict-only float, full Q-format, slim verdict-only Q-format):
`state_vectors` normalizes carried state to honest per-channel (C,)
vectors — a per-channel `k` is preserved end-to-end, never collapsed to
a shared scalar — and `_pad_layout` owns the lane/sublane padding.  The
kernels mask padded time rows internally against the true valid length,
so the final state is *always* returned, for every T (no `final=None`
path remains).

`m` may be a scalar or a per-channel (C,) vector (multi-tenant slots
run different sensitivity levels in one batch).  The kernels take a
scalar threshold constant in SMEM, but only the OUTLIER comparison
depends on it — state and eccentricity do not — so the vector case
re-evaluates eq (6) outside the kernel from the kernel's own `ecc`,
with the exact same arithmetic (`div_qi` on the Q path), keeping the
per-slot verdicts bit-consistent with a scalar-`m` run of that slot.

`valid_lens` may likewise be a scalar or a per-channel (C,) vector:
vlen[c] leading rows of channel c are valid (0..T), so one fused call
can retire a *different* number of samples per slot — each channel's
carried state freezes after its own vlen[c] rows, bit-exact on the Q
path with a per-channel isolated run of that prefix.  `None` (the
uniform fast case: the whole chunk is valid for every channel) skips
the ragged verdict masking entirely and is bit-identical to a
broadcast vlen=T vector — the kernels have a single vector code path.
Per-sample outputs at rows >= vlen[c] are unspecified except `outlier`,
which is guaranteed False there.

`block_c` tiles the channel axis into independent grid strips (the
kernels' 2-D `(channel-block, time-block)` grid); channels are fully
independent in TEDA, so every block_c produces identical bits — `None`
keeps one strip spanning all lanes while the tile fits the VMEM budget
(`kernels/ragged.py` `TILE_ELEMS`) and splits wider pools.  On
multi-core TPUs the strips are the unit of core parallelism; the
channel extent is padded up to a block multiple and padded lanes carry
vlen=0 (frozen at state zero, no verdicts).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.teda import TedaState
from repro.fixedpoint.qformat import QFormat, div_qi
from repro.fixedpoint.teda_q import msq1_const
from repro.kernels.ragged import (default_interpret, mask_ragged_rows,
                                  norm_block_c, pad_layout, round_up,
                                  vlen_vec)
from repro.kernels.teda_scan import teda_pallas_call
from repro.kernels.teda_q_scan import teda_q_pallas_call

__all__ = ["teda_scan_tpu", "teda_scan_verdict", "teda_q_scan_tpu",
           "teda_q_scan_verdict", "default_interpret", "state_vectors"]

# the helpers moved to `kernels/ragged.py` (shared with the ensemble
# wrapper); the underscore aliases remain for existing importers
_round_up = round_up
_vlen_vec = vlen_vec
_mask_ragged_rows = mask_ragged_rows
_pad_layout = pad_layout


def state_vectors(state: Optional[TedaState], c: int, dtype
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Normalize carried state to per-channel (k, mean, var) (C,) vectors.

    Accepts `k` as a scalar or per-channel vector (multi-tenant slots sit
    at different stream positions), `mean` as (C,), (C, 1) or scalar, and
    `var` likewise.  This is the single state-layout definition shared by
    every kernel wrapper and by `repro.engine`.
    """
    if state is None:
        z = jnp.zeros((c,), dtype)
        return z, z, z

    def vec(v):
        v = jnp.asarray(v, dtype)
        v = v.reshape(-1) if v.ndim else v
        return jnp.broadcast_to(v, (c,))

    return vec(state.k), vec(state.mean), vec(state.var)


def _k_rows(k0, t_len, dtype):
    """Global iteration index of every row: k0 + 1 .. k0 + T, (T, C)."""
    return k0[None, :] + jnp.arange(1, t_len + 1, dtype=dtype)[:, None]


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_c", "interpret",
                                    "lane_pad", "verdict_only"))
def _padded_call(x, m, vlen, k0, sum0, var0, *, block_t, block_c,
                 interpret, lane_pad, verdict_only):
    # lane-padded channels get vlen=0 from the zero pad: frozen at state 0
    t_len, c = x.shape
    xp, (vlp, kp, sp, vp), sl = _pad_layout(x, (vlen, k0, sum0, var0),
                                            block_t, lane_pad, block_c)
    scal = jnp.asarray(m, jnp.float32).reshape(1)
    outs = teda_pallas_call(xp, scal, vlp, kp, sp, vp, block_t=block_t,
                            block_c=block_c, interpret=interpret,
                            verdict_only=verdict_only)
    rows, (fk, fsum, fvar) = outs[:-3], outs[-3:]
    return tuple(r[sl] for r in rows) + (fk[0, :c], fsum[0, :c],
                                         fvar[0, :c])


@functools.partial(jax.jit,
                   static_argnames=("fmt", "block_t", "block_c",
                                    "interpret", "lane_pad",
                                    "verdict_only"))
def _padded_q_call(xq, msq1, vlen, k0, mean0, var0, *, fmt, block_t,
                   block_c, interpret, lane_pad, verdict_only):
    # zero-padded channels stay at mean=var=0 (vlen=0: frozen carries)
    t_len, c = xq.shape
    xp, (vlp, kp, mp, vp), sl = _pad_layout(xq, (vlen, k0, mean0, var0),
                                            block_t, lane_pad, block_c)
    scal = jnp.asarray(msq1, jnp.int32).reshape(1)
    outs = teda_q_pallas_call(xp, scal, vlp, kp, mp, vp, fmt=fmt,
                              block_t=block_t, block_c=block_c,
                              interpret=interpret,
                              verdict_only=verdict_only)
    rows, (fk, fmean, fvar) = outs[:-3], outs[-3:]
    return tuple(r[sl] for r in rows) + (fk[0, :c], fmean[0, :c],
                                         fvar[0, :c])


def teda_scan_verdict(x: jnp.ndarray, m: float | jnp.ndarray = 3.0,
                      state: Optional[TedaState] = None, *,
                      valid_lens=None, block_t: int = 256,
                      block_c: Optional[int] = None,
                      interpret: Optional[bool] = None,
                      lane_pad: int = 128):
    """Slim-output TEDA kernel: (final state, {ecc, outlier}).

    HBM write traffic per sample drops from 16B (mean+var+ecc+i32 flag)
    to 5B (ecc + i8 flag) — the memory-roofline optimization recorded in
    EXPERIMENTS.md §Perf.  The kernel masks each channel's ragged tail
    against its valid length, so a bit-exact final state is returned
    for every T — this is the engine's float hot path.  `m` may be
    per-channel (C,); eq (6) is then re-evaluated outside the kernel
    (see module docs).  `valid_lens` may be a scalar or per-channel
    (C,) vector of leading valid row counts (see module docs).
    `block_c` tiles the channel axis into parallel grid strips.
    """
    if interpret is None:
        interpret = default_interpret()
    x = jnp.asarray(x)
    t_len, c = x.shape
    k0, mean0, var0 = state_vectors(state, c, jnp.float32)
    vlen, ragged = _vlen_vec(valid_lens, t_len, c, jnp.float32)
    m_arr = jnp.asarray(m, jnp.float32)
    per_slot = m_arr.ndim > 0
    ecc, outlier, fk, fsum, fvar = _padded_call(
        x, jnp.float32(0.0) if per_slot else m_arr, vlen, k0, mean0 * k0,
        var0, block_t=block_t, block_c=norm_block_c(block_c, block_t, c, lane_pad),
        interpret=interpret, lane_pad=lane_pad, verdict_only=True)
    if per_slot:
        k_all = _k_rows(k0, t_len, jnp.float32)
        thr = (m_arr[None, :] * m_arr[None, :] + 1.0) / (2.0 * k_all)
        outlier = jnp.logical_and(ecc * 0.5 > thr, k_all >= 2.0)
    if ragged:
        outlier = _mask_ragged_rows(outlier, vlen, t_len)
    final = TedaState(k=fk, mean=(fsum / jnp.maximum(fk, 1.0))[:, None],
                      var=fvar)
    return final, {"ecc": ecc, "outlier": outlier.astype(bool)}


def teda_scan_tpu(x: jnp.ndarray, m: float | jnp.ndarray = 3.0,
                  state: Optional[TedaState] = None, *,
                  valid_lens=None, block_t: int = 256,
                  block_c: Optional[int] = None,
                  interpret: Optional[bool] = None,
                  lane_pad: int = 128) -> Tuple[TedaState, dict]:
    """TEDA over x (T, C) — C independent univariate streams.

    Returns (final TedaState with k (C,) / mean (C, 1) / var (C,),
    outputs dict of (T, C) arrays: mean, var, ecc, zeta, threshold,
    outlier).  Per-channel state (including k) carries exactly across
    calls for arbitrary chunk lengths.  `m` may be per-channel (C,);
    eq (6) is then re-evaluated outside the kernel (see module docs).
    `valid_lens` may be a scalar or per-channel (C,) vector of leading
    valid row counts — one call retires vlen[c] samples per channel.
    `block_c` tiles the channel axis into parallel grid strips.
    """
    if interpret is None:
        interpret = default_interpret()
    x = jnp.asarray(x)
    t_len, c = x.shape
    k0, mean0, var0 = state_vectors(state, c, jnp.float32)
    vlen, ragged = _vlen_vec(valid_lens, t_len, c, jnp.float32)
    m_arr = jnp.asarray(m, jnp.float32)
    per_slot = m_arr.ndim > 0

    mean, var, ecc, outlier, fk, fsum, fvar = _padded_call(
        x, jnp.float32(0.0) if per_slot else m_arr, vlen, k0, mean0 * k0,
        var0, block_t=block_t, block_c=norm_block_c(block_c, block_t, c, lane_pad),
        interpret=interpret, lane_pad=lane_pad, verdict_only=False)

    k_all = _k_rows(k0, t_len, jnp.float32)
    zeta = ecc * 0.5
    thr = (m_arr ** 2 + 1.0) / (2.0 * k_all)
    if per_slot:
        outlier = jnp.logical_and(zeta > thr, k_all >= 2.0)
    if ragged:
        outlier = _mask_ragged_rows(outlier, vlen, t_len)
    final = TedaState(k=fk, mean=(fsum / jnp.maximum(fk, 1.0))[:, None],
                      var=fvar)
    outs = {"mean": mean, "var": var, "ecc": ecc, "zeta": zeta,
            "threshold": thr, "outlier": outlier.astype(bool)}
    return final, outs


def _quantize_in(x, fmt: QFormat):
    if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        return fmt.quantize(x)
    return jnp.asarray(x, jnp.int32)


def teda_q_scan_verdict(x: jnp.ndarray, fmt: QFormat,
                        m: float | jnp.ndarray = 3.0,
                        state: Optional[TedaState] = None, *,
                        valid_lens=None, block_t: int = 256,
                        block_c: Optional[int] = None,
                        interpret: Optional[bool] = None,
                        lane_pad: int = 128) -> Tuple[TedaState, dict]:
    """Slim-output Q-format TEDA kernel: (final state, {ecc, outlier}).

    The serving engine consumes only the verdict stream and the carried
    state, and the full wrapper's extra work is expensive out of all
    proportion on the Q path: per-row mean/var HBM writes inside the
    kernel, plus a host-side (T, C) *bit-serial* `div_qi` re-derivation
    of the eq (6) threshold that the engine never reads (~WL iterations
    per element — it dominated the PR 6 pallas-q profile).  This wrapper
    skips both: with scalar `m` the kernel's own in-loop verdict (the
    same `_q_step_u` bits) is returned as-is, so `ecc`/`outlier`/final
    state are bit-exact with `teda_q_scan_tpu` and with the pure-JAX
    `teda_q_scan_chan` oracle.  Per-channel `m` still re-evaluates
    eq (6) outside with the same `div_qi` arithmetic (only then is the
    threshold actually needed).  `block_c` tiles the channel axis into
    parallel grid strips.  This is the engine's Q hot path.
    """
    fmt.validate()
    if interpret is None:
        interpret = default_interpret()
    xq = _quantize_in(x, fmt)
    t_len, c = xq.shape
    k0, mean0, var0 = state_vectors(state, c, jnp.int32)
    vlen, ragged = _vlen_vec(valid_lens, t_len, c, jnp.int32)
    msq1 = msq1_const(fmt, m)
    per_slot = jnp.asarray(msq1).ndim > 0

    ecc, outlier, fk, fmean, fvar = _padded_q_call(
        xq, jnp.int32(0) if per_slot else msq1, vlen, k0, mean0, var0,
        fmt=fmt, block_t=block_t, block_c=norm_block_c(block_c, block_t, c, lane_pad),
        interpret=interpret, lane_pad=lane_pad, verdict_only=True)

    if per_slot:
        k_all = _k_rows(k0, t_len, jnp.int32)
        thr = div_qi(fmt, jnp.broadcast_to(jnp.asarray(msq1, jnp.int32),
                                           k_all.shape), 2 * k_all)
        outlier = jnp.logical_and(ecc >> 1 > thr, k_all >= 2)
    if ragged:
        outlier = _mask_ragged_rows(outlier, vlen, t_len)
    final = TedaState(k=fk, mean=fmean[:, None], var=fvar)
    return final, {"ecc": ecc, "outlier": outlier.astype(bool)}


def teda_q_scan_tpu(x: jnp.ndarray, fmt: QFormat,
                    m: float | jnp.ndarray = 3.0,
                    state: Optional[TedaState] = None, *,
                    valid_lens=None, block_t: int = 256,
                    block_c: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    lane_pad: int = 128) -> Tuple[TedaState, dict]:
    """Bit-accurate Q-format TEDA kernel over x (T, C) channel streams.

    Float input is quantized through `fmt`; int32 input is taken as
    already-quantized Q values.  Bit-exact with the pure-JAX
    `fixedpoint.teda_q_scan_chan` (same per-row step function).  The
    kernel freezes the carried state on padded tail rows, so the final
    state is exact — and always returned — for every T.  Returns
    (TedaState with k (C,) int32, Q int32 mean (C, 1) / var (C,),
    outputs dict of (T, C) arrays: mean, var, ecc, zeta, threshold — all
    Q int32 — and bool outlier).  `m` may be per-channel (C,); eq (6) is
    then re-evaluated outside the kernel with the same `div_qi`
    arithmetic, so per-slot verdicts stay bit-exact (see module docs).
    `valid_lens` may be a scalar or per-channel (C,) vector of leading
    valid row counts — one fused call retires vlen[c] samples per
    channel, bit-exact with per-channel isolated runs of each prefix.
    `block_c` tiles the channel axis into parallel grid strips.  The
    serving hot path is `teda_q_scan_verdict`; this full wrapper keeps
    the complete (T, C) Q trajectory (mean/var/zeta/threshold) for
    oracle tests and offline analysis.
    """
    fmt.validate()
    if interpret is None:
        interpret = default_interpret()
    xq = _quantize_in(x, fmt)
    t_len, c = xq.shape
    k0, mean0, var0 = state_vectors(state, c, jnp.int32)
    vlen, ragged = _vlen_vec(valid_lens, t_len, c, jnp.int32)
    msq1 = msq1_const(fmt, m)
    per_slot = jnp.asarray(msq1).ndim > 0

    mean, var, ecc, outlier, fk, fmean, fvar = _padded_q_call(
        xq, jnp.int32(0) if per_slot else msq1, vlen, k0, mean0, var0,
        fmt=fmt, block_t=block_t, block_c=norm_block_c(block_c, block_t, c, lane_pad),
        interpret=interpret, lane_pad=lane_pad, verdict_only=False)

    k_all = _k_rows(k0, t_len, jnp.int32)
    zeta = ecc >> 1
    thr = div_qi(fmt, jnp.broadcast_to(jnp.asarray(msq1, jnp.int32),
                                       k_all.shape), 2 * k_all)
    if per_slot:
        outlier = jnp.logical_and(zeta > thr, k_all >= 2)
    if ragged:
        outlier = _mask_ragged_rows(outlier, vlen, t_len)
    final = TedaState(k=fk, mean=fmean[:, None], var=fvar)
    outs = {"mean": mean, "var": var, "ecc": ecc, "zeta": zeta,
            "threshold": thr, "outlier": outlier.astype(bool)}
    return final, outs
