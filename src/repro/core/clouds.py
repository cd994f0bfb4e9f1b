"""TEDA data clouds — the evolving classifier built on the paper's core.

The TEDA papers the reproduction builds on ([4] Costa et al. "Unsupervised
classification of data streams based on typicality and eccentricity data
analytics", [15] TEDAClass) extend the detector into an autonomous
classifier: samples are grouped into *data clouds* (granular structures
with no predefined shape), each cloud carrying the same O(1) recursive
state (k, mu, var) as a single TEDA stream. Per sample:

  * compute the sample's normalized eccentricity w.r.t. every cloud
    (eq (5) using that cloud's statistics, sample tentatively included);
  * join every cloud where the sample is typical (zeta <= (m^2+1)/(2k),
    the complement of the paper's outlier rule) — soft labeling;
  * if eccentric to all clouds, found a new cloud at the sample.

Fixed-capacity, fully jittable (clouds live in padded arrays with an
active mask; `lax` control flow only), so it composes with pjit and can
run inside the serving/training loops like the plain guard. This is a
faithful-but-batched implementation: clouds update sequentially per
sample via lax.scan, exactly the online semantics of [4].
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

__all__ = ["CloudState", "clouds_init", "clouds_step", "clouds_run"]


class CloudState(NamedTuple):
    k: jnp.ndarray      # (C,) samples absorbed per cloud (0 = inactive)
    mean: jnp.ndarray   # (C, N)
    var: jnp.ndarray    # (C,)
    n_active: jnp.ndarray  # () int32


def clouds_init(capacity: int, n_features: int) -> CloudState:
    return CloudState(
        k=jnp.zeros((capacity,), jnp.float32),
        mean=jnp.zeros((capacity, n_features), jnp.float32),
        var=jnp.zeros((capacity,), jnp.float32),
        n_active=jnp.zeros((), jnp.int32),
    )


def _tentative(state: CloudState, x: jnp.ndarray):
    """Eq (2)/(3)/(1)/(5) with x tentatively added to every cloud."""
    k1 = state.k + 1.0
    mean1 = (state.k[:, None] * state.mean + x[None]) / k1[:, None]
    d2 = jnp.sum((x[None] - mean1) ** 2, axis=-1)
    var1 = (k1 - 1.0) / k1 * state.var + d2 / k1
    safe = var1 > 1e-12
    ecc = 1.0 / k1 + jnp.where(safe, d2 / (k1 * jnp.where(safe, var1, 1.0)),
                               0.0)
    zeta = ecc / 2.0
    return k1, mean1, var1, zeta


def clouds_step(state: CloudState, x: jnp.ndarray, m: float = 3.0
                ) -> Tuple[CloudState, jnp.ndarray]:
    """Absorb one sample x (N,). Returns (state, membership (C,) bool).

    A cloud accepts the sample when it is NOT eccentric there (paper's
    eq (6) complement). New clouds spawn in the first inactive slot; at
    capacity the sample joins its least-eccentric cloud (graceful
    saturation, as TEDAClassBDp does for bounded memory).
    """
    cap = state.k.shape[0]
    active = state.k > 0.0
    k1, mean1, var1, zeta = _tentative(state, x)
    thr = (m * m + 1.0) / (2.0 * k1)
    # pure eq (5)/(6)-complement membership. Note the detectability
    # bound: eq (3) absorbs the sample, so zeta <= (k+1)/(2k) and eq (6)
    # cannot trip while k <= m^2 (tests/test_teda.py); a cloud younger
    # than m^2 samples cannot reject, so the classifier targets the
    # TEDAClass streaming regime — concept drift with each regime
    # lasting > m^2 samples (as in [4]'s industrial-fault experiments).
    # Rapidly interleaved regimes would need the sigma-gap extension of
    # [6].
    join = jnp.logical_and(active, zeta <= thr)

    any_join = jnp.any(join)
    slot = jnp.argmin(active)  # first inactive slot
    has_room = ~active[slot]
    fallback = jnp.argmin(jnp.where(active, zeta, jnp.inf))  # saturation

    spawn = jnp.logical_and(~any_join, has_room)
    adopt = jnp.logical_and(~any_join, ~has_room)
    join = jnp.logical_or(
        join, jnp.logical_and(adopt,
                              jnp.arange(cap) == fallback))

    # update joined clouds recursively; spawn fresh cloud at x
    new_k = jnp.where(join, k1, state.k)
    new_mean = jnp.where(join[:, None], mean1, state.mean)
    new_var = jnp.where(join, var1, state.var)
    is_slot = jnp.arange(cap) == slot
    new_k = jnp.where(jnp.logical_and(spawn, is_slot), 1.0, new_k)
    new_mean = jnp.where(jnp.logical_and(spawn, is_slot)[:, None],
                         x[None], new_mean)
    new_var = jnp.where(jnp.logical_and(spawn, is_slot), 0.0, new_var)

    membership = jnp.logical_or(join, jnp.logical_and(spawn, is_slot))
    n_active = jnp.sum((new_k > 0).astype(jnp.int32))
    return CloudState(k=new_k, mean=new_mean, var=new_var,
                      n_active=n_active), membership


def clouds_run(x: jnp.ndarray, capacity: int = 16, m: float = 3.0
               ) -> Tuple[CloudState, jnp.ndarray]:
    """Stream x (T, N) through the evolving classifier via lax.scan.

    Returns (final state, memberships (T, C) bool — soft labels)."""
    state = clouds_init(capacity, x.shape[-1])

    def body(s, xi):
        return clouds_step(s, xi, m)

    return jax.lax.scan(body, state, x.astype(jnp.float32))
