"""Recursive density estimation (RDE) — Angelov's close TEDA cousin.

RDE keeps the same O(1) per-stream recursion as TEDA but scores each
sample by the Cauchy-kernel density around the running mean with the
*biased* variance from running moments:

  mu_k    = S_k / k,          S_k  = sum_{i<=k} x_i
  X_k     = S2_k / k,         S2_k = sum_{i<=k} x_i^2
  sigma_k = X_k - mu_k^2      (biased variance; >= 0 in real arithmetic)
  D_k     = 1 / (1 + (x_k - mu_k)^2 / sigma_k)

The flag mirrors TEDA's eq (6) structure as an m-sigma gate on the same
moments: outlier when (x_k - mu_k)^2 > m^2 * sigma_k, gated on k >= 2
and sigma_k > 0 (a constant prefix never flags — same guard the TEDA
kernel applies to var=0).  Both carried moments are plain prefix sums,
which is exactly why RDE fuses into the ensemble kernel for free: the
running S the TEDA mean needs is also RDE's S, and S2 is one more
doubling scan.

This module is the pure-JAX `lax.scan` oracle — sequential in time,
per-channel carried state, the conformance target the fused kernel is
checked against.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["RdeState", "rde_init", "rde_scan", "scan_rows"]


class RdeState(NamedTuple):
    """Per-channel carried RDE moments.

    k:  (C,) samples absorbed; s: (C,) running sum; s2: (C,) running
    sum of squares.  All float32.
    """

    k: jnp.ndarray
    s: jnp.ndarray
    s2: jnp.ndarray


def rde_init(c: int, dtype=jnp.float32) -> RdeState:
    z = jnp.zeros((c,), dtype)
    return RdeState(k=z, s=z, s2=z)


def scan_rows(step, carry, x, valid):
    """`lax.scan` of a member's `step` over the rows of x (T, C) with
    their validity mask; returns (carry, outputs).

    A one-row chunk runs as two rows, the second invalid (it advances
    nothing and its outputs are dropped): XLA inlines a loop of one
    trip and fuses its body with its neighbours, where the compiler
    contracts a multiply and an add the loop body keeps apart, so the
    one-sample decode after a chunked history rounded one float32 ulp
    off the whole-stream scan.
    """
    t_len = x.shape[0]
    if t_len == 1:
        x = jnp.concatenate([x, jnp.zeros_like(x)])
        valid = jnp.concatenate([valid, jnp.zeros_like(valid)])
    carry, out = jax.lax.scan(step, carry, (x, valid))
    return carry, jax.tree.map(lambda a: a[:t_len], out)


def rde_scan(x: jnp.ndarray, m=3.0, state: Optional[RdeState] = None, *,
             valid_lens=None) -> Tuple[RdeState, dict]:
    """RDE over x (T, C) — C independent univariate streams.

    Returns (final RdeState, {"outlier": (T, C) bool, "score": (T, C)
    Cauchy density in (0, 1]}).  `m` is a scalar or per-channel (C,)
    sensitivity.  `valid_lens` (scalar or per-channel (C,) vector,
    clamped to [0, T]) freezes each channel after its own leading
    prefix and masks its flags beyond it — the engine's ragged
    contract.  Chunked calls carrying the state reproduce the
    single-shot run bit-for-bit (the carry is the exact running
    moments, and each row's update reads only them).
    """
    x = jnp.asarray(x, jnp.float32)
    t_len, c = x.shape
    if state is None:
        state = rde_init(c)
    m2 = jnp.broadcast_to(jnp.asarray(m, jnp.float32) ** 2, (c,))
    if valid_lens is None:
        valid = jnp.ones((t_len, c), bool)
    else:
        vlen = jnp.clip(jnp.asarray(valid_lens, jnp.float32), 0.0, t_len)
        vlen = jnp.broadcast_to(vlen.reshape(-1) if vlen.ndim else vlen,
                                (c,))
        valid = (jnp.arange(t_len, dtype=jnp.float32)[:, None]
                 < vlen[None, :])

    def step(carry, inp):
        k, s, s2 = carry
        xr, v = inp
        k1 = jnp.where(v, k + 1.0, k)
        s1 = jnp.where(v, s + xr, s)
        s21 = jnp.where(v, s2 + xr * xr, s2)
        kd = jnp.maximum(k1, 1.0)
        mean = s1 / kd
        varb = s21 / kd - mean * mean
        d2 = (xr - mean) ** 2
        ok = varb > 0.0
        dens = 1.0 / (1.0 + jnp.where(ok, d2 / jnp.where(ok, varb, 1.0),
                                      0.0))
        flag = v & (k1 >= 2.0) & ok & (d2 > m2 * varb)
        return (k1, s1, s21), (flag, dens)

    (k, s, s2), (outlier, score) = scan_rows(
        step, (state.k, state.s, state.s2), x, valid)
    return RdeState(k=k, s=s, s2=s2), {"outlier": outlier, "score": score}
