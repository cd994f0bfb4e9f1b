"""Jitted public wrapper around the fused ensemble kernel + its oracle.

`ensemble_scan` is the contract layer (`kernels/ops.py`'s role for the
TEDA kernels): it owns the lane/sublane padding via the shared
`kernels/ragged.py` helpers, normalizes carried state to the packed
`EnsembleState(k, aux)` layout — whose row structure is the
`StateSpec` of `detectors/spec.py`, not a fixed formula — defaults the
per-channel selection weights and vote threshold, and returns
per-sample detector bitmasks, fused vote verdicts and per-detector
float score streams alongside the advanced state.

`ensemble_ref` is the conformance target: it composes the per-detector
pure-JAX `lax.scan` oracles (each carrying its own natural state — the
RDE moments, the z-score ring buffer, the TEDA recursion, the HST mass
tables, the Q registers) and fuses their flags on host with the same
float32 detector-order accumulation the kernel uses.  The fused kernel
must agree with it on every flag for well-separated data (and
*bit-exactly* for the hst / teda-q members), and with the standalone
TEDA "pallas" backend bit-for-bit on the TEDA lane (equal block_t).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.detectors import (DEFAULT_DETECTORS, DEFAULT_WINDOW, DETECTORS,
                             ensemble_spec)
from repro.detectors.hst import hst_init, hst_scan
from repro.detectors.teda_q import teda_q_member_scan
from repro.detectors.zscore import zscore_init
from repro.kernels.ensemble_scan import ensemble_pallas_call, max_block_c
from repro.kernels.ragged import default_interpret, norm_block_c, pad_layout

__all__ = ["EnsembleState", "ensemble_init", "ensemble_scan",
           "ensemble_ref"]


class EnsembleState(NamedTuple):
    """Packed shared state of the fused ensemble over C channels.

    k:   (C,) samples absorbed per channel (shared by every detector).
    aux: (spec.rows, C) — the `ensemble_spec(detectors, window)` block:
         the shared moment fabric in rows [0, 2W] (W-deep running-sum
         prefix tail, W-deep sum-of-squares tail, TEDA variance carry),
         then each non-moment member's opaque regions in detector
         order (see `repro.detectors.spec`).
    """

    k: jnp.ndarray
    aux: jnp.ndarray


def ensemble_init(c: int, window: int = DEFAULT_WINDOW,
                  dtype=jnp.float32,
                  detectors=DEFAULT_DETECTORS) -> EnsembleState:
    spec = ensemble_spec(detectors, window)
    return EnsembleState(k=jnp.zeros((c,), dtype),
                         aux=spec.init_aux(c, dtype))


def _check_detectors(detectors) -> Tuple[str, ...]:
    detectors = tuple(detectors)
    unknown = [d for d in detectors if d not in DETECTORS]
    if unknown or not detectors or len(set(detectors)) != len(detectors):
        raise ValueError(
            f"detectors must be a non-empty unique subset of "
            f"{sorted(DETECTORS)}, got {detectors!r}")
    return detectors


@functools.partial(jax.jit,
                   static_argnames=("window", "detectors", "fmt",
                                    "block_t", "block_c", "interpret",
                                    "lane_pad"))
def _padded_ensemble_call(x, vlen, k0, m, thr, sel, aux, *, window,
                          detectors, fmt, block_t, block_c, interpret,
                          lane_pad):
    # lane-padded channels get vlen=0 from the zero pad: frozen at
    # state 0, weight 0 (no votes) — same convention as the TEDA path
    t_len, c = x.shape
    xp, (vlp, kp, mp, thp), sl = pad_layout(x, (vlen, k0, m, thr),
                                            block_t, lane_pad, block_c)
    cp = xp.shape[1]
    selp = jnp.pad(sel, ((0, 0), (0, cp - c)))
    auxp = jnp.pad(aux, ((0, 0), (0, cp - c)))
    outs = ensemble_pallas_call(
        xp, vlp, kp, mp, thp, selp, auxp, block_t=block_t,
        block_c=block_c, window=window, detectors=detectors, fmt=fmt,
        interpret=interpret)
    bits, vote, fk, auxf = outs[:4]
    scores = jnp.stack([s[sl] for s in outs[4:]])  # (K, T, C)
    return bits[sl], vote[sl], fk[0, :c], auxf[:, :c], scores


def _sel_thr(sel, thr, n_det: int, c: int):
    """Normalize selection weights to (K, C) and the vote threshold to
    (C,); `thr=None` defaults to majority over the selected weights."""
    if sel is None:
        sel = jnp.ones((n_det, c), jnp.float32)
    else:
        sel = jnp.asarray(sel, jnp.float32)
        sel = sel[:, None] if sel.ndim == 1 else sel
        sel = jnp.broadcast_to(sel, (n_det, c))
    if thr is None:
        thr = jnp.sum(sel, axis=0) / 2.0  # majority (ties flag)
    else:
        thr = jnp.broadcast_to(jnp.asarray(thr, jnp.float32).reshape(-1)
                               if jnp.asarray(thr).ndim else
                               jnp.asarray(thr, jnp.float32), (c,))
    return sel, thr


def _check_fmt(detectors, fmt):
    if "teda-q" in detectors and fmt is None:
        raise ValueError(
            "the teda-q ensemble member needs fmt=QFormat(...) — the "
            "Q datapath's word/fraction lengths are part of the "
            "detector's definition")
    return fmt if "teda-q" in detectors else None


def ensemble_scan(x: jnp.ndarray, m=3.0,
                  state: Optional[EnsembleState] = None, *,
                  detectors=DEFAULT_DETECTORS,
                  window: int = DEFAULT_WINDOW, sel=None, thr=None,
                  fmt=None, valid_lens=None, block_t: int = 256,
                  block_c: Optional[int] = None,
                  interpret: Optional[bool] = None,
                  lane_pad: int = 128) -> Tuple[EnsembleState, dict]:
    """Fused K-detector ensemble over x (T, C) channel streams.

    Returns (final EnsembleState, {"det_flags": (T, C) int32 bitmask —
    bit d set iff detectors[d] flagged the sample on a channel where it
    is selected, "vote": (T, C) bool fused verdict, "scores": (K, T, C)
    f32 per-detector score streams — row d is detectors[d]'s native
    score (eccentricity / Cauchy density / squared z-score / HST cell
    mass / dequantized Q eccentricity), zero beyond a channel's valid
    prefix and NOT selection-gated}).  `m` is a scalar or per-channel
    (C,) sensitivity shared by every detector; `sel` the (K,) or (K, C)
    selection weights (0 = unselected; None = all selected at unit
    weight); `thr` the per-channel vote threshold (None: majority of
    the selected weight — see `detectors.vote_threshold` for the named
    modes); `fmt` the QFormat of the "teda-q" member (required iff it
    is in `detectors`).  `valid_lens` is the per-channel ragged prefix,
    `block_t`/`block_c`/`lane_pad` the kernel grid knobs — all with the
    exact semantics of the TEDA wrappers in `kernels/ops.py`.
    """
    detectors = _check_detectors(detectors)
    fmt = _check_fmt(detectors, fmt)
    if interpret is None:
        interpret = default_interpret()
    x = jnp.asarray(x, jnp.float32)
    t_len, c = x.shape
    if state is None:
        state = ensemble_init(c, window, detectors=detectors)
    spec = ensemble_spec(detectors, window)
    if state.aux.shape != (spec.rows, c):
        raise ValueError(
            f"state.aux must be ({spec.rows}, {c}) for window={window} "
            f"and layout {spec.names()}, got {state.aux.shape}")
    k0 = jnp.broadcast_to(jnp.asarray(state.k, jnp.float32).reshape(-1)
                          if jnp.asarray(state.k).ndim else
                          jnp.asarray(state.k, jnp.float32), (c,))
    if valid_lens is None:
        vlen = jnp.full((c,), t_len, jnp.float32)
    else:
        vl = jnp.clip(jnp.asarray(valid_lens, jnp.float32), 0, t_len)
        vlen = jnp.broadcast_to(vl.reshape(-1) if vl.ndim else vl, (c,))
    mv = jnp.broadcast_to(jnp.asarray(m, jnp.float32).reshape(-1)
                          if jnp.asarray(m).ndim else
                          jnp.asarray(m, jnp.float32), (c,))
    sel, thr = _sel_thr(sel, thr, len(detectors), c)
    bits, vote, fk, auxf, scores = _padded_ensemble_call(
        x, vlen, k0, mv, thr, sel, jnp.asarray(state.aux, jnp.float32),
        window=window, detectors=detectors, fmt=fmt, block_t=block_t,
        block_c=norm_block_c(block_c, block_t, c, lane_pad,
                             max_block_c(block_t, detectors, window)),
        interpret=interpret, lane_pad=lane_pad)
    final = EnsembleState(k=fk, aux=auxf)
    return final, {"det_flags": bits, "vote": vote.astype(bool),
                   "scores": scores}


def ensemble_ref(x: jnp.ndarray, m=3.0, *,
                 detectors=DEFAULT_DETECTORS,
                 window: int = DEFAULT_WINDOW, sel=None, thr=None,
                 fmt=None, valid_lens=None) -> dict:
    """Oracle composition: per-detector `lax.scan` results + host vote.

    Runs every detector's pure-JAX oracle from a fresh stream start and
    fuses flags exactly the way the kernel documents: bit d of
    `det_flags` is detectors[d] (selection-masked), the vote weight sum
    accumulates in detector order in float32.  Returns {"det_flags",
    "vote", "per_detector": {name: (T, C) bool}, "per_score":
    {name: (T, C) f32}}.
    """
    detectors = _check_detectors(detectors)
    fmt = _check_fmt(detectors, fmt)
    x = jnp.asarray(x, jnp.float32)
    t_len, c = x.shape
    sel, thr = _sel_thr(sel, thr, len(detectors), c)
    per, per_score = {}, {}
    for name in detectors:
        if name == "zscore":
            _, out = DETECTORS[name](x, m, zscore_init(c, window),
                                     valid_lens=valid_lens)
        elif name == "hst":
            _, out = hst_scan(x, m, hst_init(c), window=window,
                              valid_lens=valid_lens)
        elif name == "teda-q":
            _, out = teda_q_member_scan(x, fmt, m, None,
                                        valid_lens=valid_lens)
        else:
            _, out = DETECTORS[name](x, m, None, valid_lens=valid_lens)
        per[name] = out["outlier"]
        per_score[name] = out["score"]
    if valid_lens is not None:
        # the kernel zeroes score streams beyond a channel's valid
        # prefix; the moment oracles emit unspecified values there
        vl = jnp.clip(jnp.asarray(valid_lens, jnp.float32), 0, t_len)
        vl = jnp.broadcast_to(vl.reshape(-1) if vl.ndim else vl, (c,))
        live = jnp.arange(t_len, dtype=jnp.float32)[:, None] < vl[None, :]
        per_score = {n: jnp.where(live, s, 0.0)
                     for n, s in per_score.items()}
    bits = jnp.zeros((t_len, c), jnp.int32)
    votew = jnp.zeros((t_len, c), jnp.float32)
    for d, name in enumerate(detectors):
        f = per[name] & (sel[d] > 0.0)[None, :]
        bits = bits + f.astype(jnp.int32) * (1 << d)
        votew = votew + f.astype(jnp.float32) * sel[d][None, :]
    totw = jnp.sum(sel, axis=0)
    vote = (votew >= thr[None, :]) & (totw > 0.0)[None, :]
    if valid_lens is not None:
        vl = jnp.clip(jnp.asarray(valid_lens, jnp.float32), 0, t_len)
        vl = jnp.broadcast_to(vl.reshape(-1) if vl.ndim else vl, (c,))
        vote = vote & (jnp.arange(t_len)[:, None] < vl[None, :])
    return {"det_flags": bits, "vote": vote, "per_detector": per,
            "per_score": per_score}
