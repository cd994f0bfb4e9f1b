"""Tenant metric series in the shape of Yahoo S5 / NAB: a level and a
scale per tenant, Gaussian noise, and spikes of several scales."""
from __future__ import annotations

import numpy as np


def make(sig: dict, rng, *, t_len: int, n: int) -> np.ndarray:
    """(t_len, n) float32: level N(0, 1) and scale U(scale_range) per
    tenant; a share `spike_rate` of samples carries a spike of
    U(spike_scales) scales, either sign."""
    lo, hi = sig["scale_range"]
    s_lo, s_hi = sig["spike_scales"]
    level = rng.normal(0.0, 1.0, size=(1, n)).astype(np.float32)
    scale = rng.uniform(lo, hi, size=(1, n)).astype(np.float32)
    x = level + scale * rng.standard_normal((t_len, n), dtype=np.float32)
    spikes = rng.random((t_len, n)) < sig["spike_rate"]
    size = rng.uniform(s_lo, s_hi, size=(t_len, n)).astype(np.float32)
    sign = np.where(rng.random((t_len, n)) < 0.5, -1.0, 1.0)
    return np.where(spikes, x + (sign * size * scale).astype(np.float32),
                    x).astype(np.float32)
