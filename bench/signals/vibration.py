"""Bearing vibration recordings in the shape of the CWRU 48 kHz
drive-end data: shaft harmonics, outer-race fault impulses that ring
down at a structural resonance, and sensor noise."""
from __future__ import annotations

import numpy as np


def make(sig: dict, rng, *, fs: float) -> np.ndarray:
    """(recordings, length) float32, one row per recording.  Each has
    its own harmonic phases and gain, fault phase, and per-impulse
    amplitude U(impulse_amp)."""
    r, length = int(sig["recordings"]), int(sig["length"])
    t = np.arange(length, dtype=np.float64) / fs
    out = np.empty((r, length), np.float32)
    period = 1.0 / sig["fault_hz"]
    a_lo, a_hi = sig["impulse_amp"]
    n_imp = int(length / fs / period) + 2
    for i in range(r):
        gain = rng.uniform(0.8, 1.2)
        x = np.zeros(length)
        for h, a in enumerate(sig["harmonics"], start=1):
            x += gain * a * np.sin(2 * np.pi * h * sig["shaft_hz"] * t
                                   + rng.uniform(0, 2 * np.pi))
        shifted = t + rng.uniform(0.0, period)
        tau = np.mod(shifted, period)
        amp = rng.uniform(a_lo, a_hi, size=n_imp)[
            (shifted / period).astype(np.int64)]
        x += amp * np.exp(-tau / sig["decay_s"]) * np.sin(
            2 * np.pi * sig["resonance_hz"] * tau)
        x += sig["noise"] * rng.standard_normal(length)
        out[i] = x
    return out
