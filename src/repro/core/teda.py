"""Paper-faithful TEDA (Typicality and Eccentricity Data Analytics).

Implements Algorithm 1 of da Silva et al., "Hardware Architecture Proposal
for TEDA algorithm to Data Streaming Anomaly Detection", verbatim:

  eq (2)  mu_k    = (k-1)/k * mu_{k-1} + x_k / k
  eq (3)  var_k   = (k-1)/k * var_{k-1} + ||x_k - mu_k||^2 / k
  eq (1)  ecc_k   = 1/k + ||x_k - mu_k||^2 / (k * var_k)
  eq (4)  typ_k   = 1 - ecc_k
  eq (5)  zeta_k  = ecc_k / 2
  eq (6)  outlier = zeta_k > (m^2 + 1) / (2k)

State is O(1) per stream: (k, mu, var). Streams are multivariate with
feature dimension N on the trailing axis; arbitrary leading batch dims are
supported (each leading index is an independent stream).

This module is the *paper-faithful baseline* (sequential recurrence,
`lax.scan` = the FPGA pipeline analog). The beyond-paper parallel forms
live in `core/scan.py` and `kernels/teda_scan.py`.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "TedaState",
    "TedaOutput",
    "teda_init",
    "teda_step",
    "teda_stream",
    "teda_threshold",
]


class TedaState(NamedTuple):
    """O(1) recursive TEDA state for one (batch of) stream(s).

    k:    (...,)   float32 — number of samples absorbed so far.
    mean: (..., N) float32 — recursive mean, eq (2).
    var:  (...,)   float32 — recursive variance, eq (3).
    """

    k: jnp.ndarray
    mean: jnp.ndarray
    var: jnp.ndarray


class TedaOutput(NamedTuple):
    """Per-sample verdict, one entry per eq of the paper."""

    ecc: jnp.ndarray  # eq (1) eccentricity xi_k
    typ: jnp.ndarray  # eq (4) typicality tau_k
    zeta: jnp.ndarray  # eq (5) normalized eccentricity
    threshold: jnp.ndarray  # eq (6) RHS, (m^2+1)/(2k)
    outlier: jnp.ndarray  # eq (6) verdict (bool); False while k < 2
    k: jnp.ndarray  # iteration index of this verdict


def teda_init(batch_shape: Tuple[int, ...] = (), n_features: int = 1,
              dtype=jnp.float32) -> TedaState:
    """Fresh state: k=0, mu=0, var=0 (Algorithm 1 initial conditions)."""
    return TedaState(
        k=jnp.zeros(batch_shape, dtype),
        mean=jnp.zeros(batch_shape + (n_features,), dtype),
        var=jnp.zeros(batch_shape, dtype),
    )


def teda_threshold(k: jnp.ndarray, m: float | jnp.ndarray) -> jnp.ndarray:
    """RHS of eq (6): (m^2 + 1) / (2k)."""
    return (jnp.asarray(m, jnp.float32) ** 2 + 1.0) / (2.0 * k)


def teda_step(state: TedaState, x: jnp.ndarray,
              m: float | jnp.ndarray = 3.0) -> Tuple[TedaState, TedaOutput]:
    """One iteration of Algorithm 1 (lines 3..15) for sample x (..., N).

    Matches the paper's MEAN / VARIANCE / ECCENTRICITY / OUTLIER modules.
    The k==1 branch (lines 3..5) sets mu <- x, var <- 0 and emits a
    non-outlier verdict (eq (5) is defined for k >= 2).
    """
    x = x.astype(state.mean.dtype)
    k = state.k + 1.0  # discretization instant of this sample
    first = k <= 1.0

    # --- MEAN module, eq (2); lines 4 / 7 -------------------------------
    mean = jnp.where(first[..., None],
                     x,
                     (k[..., None] - 1.0) / k[..., None] * state.mean
                     + x / k[..., None])

    # --- VARIANCE module, eq (3); lines 5 / 8 ---------------------------
    d2 = jnp.sum((x - mean) ** 2, axis=-1)  # ||x_k - mu_k||^2
    var = jnp.where(first, 0.0, (k - 1.0) / k * state.var + d2 / k)

    # --- ECCENTRICITY module, eq (1); line 9 ----------------------------
    # Guard var > 0 as required by eq (1): with zero variance every sample
    # sits on the mean, so the distance term vanishes.
    safe = var > 0.0
    ecc = 1.0 / k + jnp.where(safe, d2 / (k * jnp.where(safe, var, 1.0)), 0.0)

    # --- OUTLIER module, eqs (5)-(6); lines 10..14 ----------------------
    zeta = ecc / 2.0
    thr = teda_threshold(k, m)
    outlier = jnp.logical_and(zeta > thr, k >= 2.0)

    out = TedaOutput(ecc=ecc, typ=1.0 - ecc, zeta=zeta, threshold=thr,
                     outlier=outlier, k=k)
    return TedaState(k=k, mean=mean, var=var), out


def teda_stream(x: jnp.ndarray, m: float | jnp.ndarray = 3.0,
                state: Optional[TedaState] = None,
                ) -> Tuple[TedaState, TedaOutput]:
    """Run Algorithm 1 over a stream x of shape (T, ..., N) via lax.scan.

    This is the sequential, paper-faithful execution: one sample retires
    per scan step, exactly like one sample per critical-path cycle on the
    FPGA. Returns the final state and per-sample outputs stacked on axis 0.
    """
    if state is None:
        state = teda_init(x.shape[1:-1], x.shape[-1], jnp.float32)

    def body(s, xk):
        return teda_step(s, xk, m)

    return jax.lax.scan(body, state, x)


def teda_numpy_loop(x, m: float = 3.0):
    """Plain-Python reference loop (the paper's 'software platform').

    The paper's Table-5 software baseline, and an independent oracle
    for the tests. x: numpy (T, N).
    """
    import numpy as np

    T, _ = x.shape
    mu = np.zeros(x.shape[1], np.float64)
    var = 0.0
    ecc = np.zeros(T, np.float64)
    zeta = np.zeros(T, np.float64)
    thr = np.zeros(T, np.float64)
    outlier = np.zeros(T, bool)
    for i in range(T):
        k = i + 1.0
        xk = x[i].astype(np.float64)
        if i == 0:
            mu = xk.copy()
            var = 0.0
        else:
            mu = (k - 1.0) / k * mu + xk / k
            d2 = float(np.sum((xk - mu) ** 2))
            var = (k - 1.0) / k * var + d2 / k
        d2 = float(np.sum((xk - mu) ** 2))
        ecc[i] = 1.0 / k + (d2 / (k * var) if var > 0.0 else 0.0)
        zeta[i] = ecc[i] / 2.0
        thr[i] = (m * m + 1.0) / (2.0 * k)
        outlier[i] = (zeta[i] > thr[i]) and k >= 2
    return {"ecc": ecc, "zeta": zeta, "threshold": thr, "outlier": outlier,
            "mean": mu, "var": var}
