"""Q-format fixed-point arithmetic, defined on exact 64-bit integers.

The fixed-point datapath of the paper (a `word_len`-bit two's-complement
word with `frac_len` fractional bits, symmetric saturation at
+-(2^(word_len-1) - 1), truncation toward zero after every multiply and
divide) written as plain integer arithmetic: the full product and the
pre-shifted dividend fit in int64 for every word up to 32 bits, so each
operator is one multiply or one floor division, clamped.  Nothing here
imitates how the program builds these operators from 32-bit pieces.

`xp` is the array module: `numpy` for short streams, `jax.numpy` under
`jax.enable_x64(True)` for long ones.  Arrays are int64.
"""
from __future__ import annotations


class QArith:
    """The operators of one Q format over the array module `xp`."""

    def __init__(self, word_len: int, frac_len: int, xp,
                 rounding: str = "trunc"):
        if rounding != "trunc":
            raise ValueError(f"only truncating rounding is defined: "
                             f"{rounding!r}")
        if not (2 <= word_len <= 32 and 0 <= frac_len < word_len):
            raise ValueError(f"bad Q format ({word_len}, {frac_len})")
        self.word_len, self.frac_len, self.xp = word_len, frac_len, xp
        self.qmax = (1 << (word_len - 1)) - 1
        self.one = 1 << frac_len

    def quantize_host(self, x):
        """float32 samples -> Q values: round half to even of x * 2^FL in
        float32 (exact: a power-of-two scale), NaN to 0, saturated."""
        import numpy as np
        v = np.round(np.asarray(x, np.float32) * np.float32(self.one))
        v = np.where(np.isnan(v), 0.0, v)
        v = np.clip(v, -2.0 ** 31, 2.0 ** 31 - 1).astype(np.int64)
        return np.clip(v, -self.qmax, self.qmax)

    def const(self, value: float) -> int:
        """A host constant in Q, rounded to nearest and saturated."""
        v = int(round(float(value) * self.one))
        return max(-self.qmax, min(self.qmax, v))

    def sat(self, v):
        return self.xp.clip(v, -self.qmax, self.qmax)

    def add(self, a, b):
        return self.sat(a + b)

    def sub(self, a, b):
        return self.sat(a - b)

    def _signed(self, mag, neg):
        return self.xp.where(neg, -mag, mag)

    def mul(self, a, b):
        xp = self.xp
        mag = xp.minimum((xp.abs(a) * xp.abs(b)) >> self.frac_len,
                         self.qmax)
        return self._signed(mag, (a < 0) != (b < 0))

    def div_qq(self, n, d):
        """Q / Q: floor(|n| * 2^FL / |d|), saturated; d == 0 saturates."""
        xp = self.xp
        ad = xp.abs(d)
        mag = xp.where(ad == 0, self.qmax,
                       (xp.abs(n) << self.frac_len) // xp.maximum(ad, 1))
        return self._signed(xp.minimum(mag, self.qmax), (n < 0) != (d < 0))

    def div_qi(self, n, k):
        """Q / positive integer: floor(|n| / k), sign of n."""
        xp = self.xp
        return self._signed(xp.minimum(xp.abs(n) // k, self.qmax), n < 0)


def counter_terms(q: QArith, k, msq1):
    """The terms that depend on the sample count alone: (k-1)/k, 1/k
    and the threshold (m^2 + 1)/(2k)."""
    return q.div_qq(k - 1, k), q.div_qi(q.one, k), q.div_qi(msq1, 2 * k)


def teda_q_step(q: QArith, k, mean, var, xq, terms):
    """One TEDA instant on the Q datapath (the paper's Algorithm 1).

    k is the 1-based sample count (int64, broadcast against the data)
    and `terms` its `counter_terms`; returns (mean', var', ecc,
    outlier):

      mean_k = (k-1)/k * mean + x/k              (x itself at k = 1)
      var_k  = (k-1)/k * var + (x - mean_k)^2/k  (0 at k = 1)
      ecc_k  = 1/k + ((x - mean_k)^2 / var_k)/k  (1/k while var_k = 0)
      outlier: ecc_k / 2 > (m^2 + 1) / (2k), from k = 2 on
    """
    xp = q.xp
    first = k <= 1
    rk, inv_k, thr = terms
    mean_n = xp.where(first, xq, q.add(q.mul(rk, mean), q.div_qi(xq, k)))
    d = q.sub(xq, mean_n)
    d2 = q.mul(d, d)
    var_n = xp.where(first, 0, q.add(q.mul(rk, var), q.div_qi(d2, k)))
    safe = var_n > 0
    ratio = q.div_qq(d2, xp.where(safe, var_n, 1))
    ecc = q.add(inv_k, xp.where(safe, q.div_qi(ratio, k), 0))
    outlier = ((ecc >> 1) > thr) & (k >= 2)
    return mean_n, var_n, ecc, outlier
