"""Sliding-window z-score detector: local moments over the last W samples.

TEDA and RDE carry whole-stream moments, so a slow drift eventually
absorbs into the baseline; the windowed z-score is the complementary
lens — moments over only the last `window` samples, so it tracks drift
and flags *local* excursions:

  n_k     = min(k, W)
  mu_k    = (S_k  - S_{k-W})  / n_k        (window sum via prefix sums)
  X_k     = (S2_k - S2_{k-W}) / n_k
  sig_k   = X_k - mu_k^2                   (biased window variance)
  flag when (x_k - mu_k)^2 > m^2 * sig_k,  gated on k >= 2, sig_k > 0
  score   = (x_k - mu_k)^2 / sig_k         (the squared z-score)

The oracle carries the classic ring buffer of the last W samples; the
fused kernel carries the algebraically identical W-deep *prefix-sum
tail* (S_{k-W+1} .. S_k and the S2 twin) instead — a windowed sum is a
difference of two prefix sums, so the kernel's doubling scans already
produce everything and the ragged-prefix freeze works exactly like the
running-sum carry (validity is prefix-only, so the tail stays
contiguous).  For k <= W the window spans the whole stream
(S_{k-W} = 0) and the z-score moments coincide with RDE's.

This module is the pure-JAX `lax.scan` oracle the fused kernel is
conformance-checked against.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax.numpy as jnp

from repro.detectors.rde import scan_rows

__all__ = ["ZscoreState", "zscore_init", "zscore_scan"]


class ZscoreState(NamedTuple):
    """Per-channel carried window state.

    k: (C,) samples absorbed; ring: (W, C) the last min(k, W) samples
    (slot j holds the sample whose 1-based index i satisfies
    (i - 1) % W == j; unwritten slots are zero and fall outside the
    window sum because only min(k, W) entries are ever populated).
    """

    k: jnp.ndarray
    ring: jnp.ndarray


def zscore_init(c: int, window: int, dtype=jnp.float32) -> ZscoreState:
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    return ZscoreState(k=jnp.zeros((c,), dtype),
                       ring=jnp.zeros((window, c), dtype))


def zscore_scan(x: jnp.ndarray, m=3.0,
                state: Optional[ZscoreState] = None, *,
                window: int = 8,
                valid_lens=None) -> Tuple[ZscoreState, dict]:
    """Windowed z-score over x (T, C) — C independent channel streams.

    Returns (final ZscoreState, {"outlier": (T, C) bool, "score":
    (T, C) squared z-score}).  `m` is a scalar or per-channel (C,)
    sensitivity; `window` is static (it shapes the carried ring; when
    `state` is given its ring width wins).  `valid_lens` freezes each
    channel after its own leading prefix — the engine's ragged
    contract.  Chunk-exact: the carry is the exact last-W ring, so any
    chunking reproduces the single-shot run bit-for-bit.
    """
    x = jnp.asarray(x, jnp.float32)
    t_len, c = x.shape
    if state is None:
        state = zscore_init(c, window)
    w = state.ring.shape[0]
    m2 = jnp.broadcast_to(jnp.asarray(m, jnp.float32) ** 2, (c,))
    if valid_lens is None:
        valid = jnp.ones((t_len, c), bool)
    else:
        vlen = jnp.clip(jnp.asarray(valid_lens, jnp.float32), 0.0, t_len)
        vlen = jnp.broadcast_to(vlen.reshape(-1) if vlen.ndim else vlen,
                                (c,))
        valid = (jnp.arange(t_len, dtype=jnp.float32)[:, None]
                 < vlen[None, :])
    slots = jnp.arange(w, dtype=jnp.float32)[:, None]  # (W, 1)

    def step(carry, inp):
        k, ring = carry
        xr, v = inp
        k1 = jnp.where(v, k + 1.0, k)
        # overwrite the oldest slot, per channel: 1-based index k1 lands
        # in ring slot (k1 - 1) mod W (exact in f32 for k < 2^24)
        pos = jnp.mod(k1 - 1.0, float(w))
        hit = (slots == pos[None, :]) & v[None, :]
        ring1 = jnp.where(hit, xr[None, :], ring)
        n = jnp.minimum(jnp.maximum(k1, 1.0), float(w))
        mu = jnp.sum(ring1, axis=0) / n
        sig = jnp.sum(ring1 * ring1, axis=0) / n - mu * mu
        d2 = (xr - mu) ** 2
        ok = sig > 0.0
        z2 = jnp.where(ok, d2 / jnp.where(ok, sig, 1.0), 0.0)
        flag = v & (k1 >= 2.0) & ok & (d2 > m2 * sig)
        return (k1, ring1), (flag, z2)

    (k, ring), (outlier, score) = scan_rows(
        step, (state.k, state.ring), x, valid)
    return (ZscoreState(k=k, ring=ring),
            {"outlier": outlier, "score": score})
