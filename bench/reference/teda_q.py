"""Plain reference of the `linerate-q` configuration: univariate TEDA on
the Q datapath, one channel per lane, every verdict compared.

The replay feeds each channel a series of recordings, each one stream
(one request) that starts from k = 0.  The passes replay only the few
recordings, so the reference runs each recording once,
as a lane of one `lax.scan` in exact int64 arithmetic on the host's
CPU (`qarith.py`), and every delivered flag of every pass is compared
with the lane of its stream.

Compared numbers (limits in the cell's workload file):
  flag_mismatch    delivered flags that differ from the reference
                   (exact datapath: limit 0)
"""
from __future__ import annotations

import numpy as np

from bench.reference.qarith import QArith, counter_terms


def _cpu_device():
    import jax
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def teda_q_flags(x: np.ndarray, word_len: int, frac_len: int,
                 m: float) -> np.ndarray:
    """(T, R) float32 streams -> (T, R) bool outlier flags on the
    Q(word_len, frac_len) datapath, each lane from k = 0.

    The same arithmetic as `qarith.teda_q_step`, arranged for a long
    stream: only the two register recurrences (MEAN, VARIANCE) run as
    sequential scans; every other operation is elementwise over the
    whole (T, R) block."""
    import jax
    import jax.numpy as jnp
    dev = _cpu_device()
    with jax.enable_x64(True):
        q = QArith(word_len, frac_len, jnp)
        msq1 = q.const(m * m + 1.0)
        t_len, _ = x.shape

        def recur(rk, term, init):
            def body(reg, inp):
                rk_t, term_t = inp
                reg = q.add(q.mul(rk_t, reg), term_t)
                return reg, reg
            return jax.lax.scan(body, init, (rk, term))[1]

        @jax.jit
        def run(xq):
            k = jnp.arange(1, t_len + 1, dtype=jnp.int64)[:, None]
            rk, inv_k, thr = counter_terms(q, k, msq1)
            # k = 1: (k-1)/k = 0, so MEAN starts at x/1 = x and
            # VARIANCE at 0, as the datapath's first-sample override
            mean = recur(rk, q.div_qi(xq, k), xq[0] * 0)
            d = q.sub(xq, mean)
            d2 = q.mul(d, d)
            var = recur(rk, jnp.where(k <= 1, 0, q.div_qi(d2, k)),
                        xq[0] * 0)
            safe = var > 0
            ratio = q.div_qq(d2, jnp.where(safe, var, 1))
            ecc = q.add(inv_k, jnp.where(safe, q.div_qi(ratio, k), 0))
            return ((ecc >> 1) > thr) & (k >= 2)

        xq = q.quantize_host(x)
        arr = (jax.device_put(xq, dev) if dev is not None
               else jnp.asarray(xq))
        return np.asarray(run(arr))


class Store:
    """What the replay keeps of each delivered verdict: per (channel,
    pass) request, the count delivered and the positions flagged."""

    def __init__(self):
        self.count = {}
        self.flagged = {}

    def add(self, key, pos: int, outlier: np.ndarray) -> None:
        self.count[key] = pos + len(outlier)
        hit = np.flatnonzero(outlier)
        if hit.size:
            self.flagged.setdefault(key, []).append(hit + pos)

    def positions(self, key) -> np.ndarray:
        parts = self.flagged.get(key)
        return (np.concatenate(parts) if parts
                else np.zeros((0,), np.int64))


def expected(recs: np.ndarray, cfg: dict, control: bool = False):
    """Reference flags of (S, L) streams, as (L, S); the control runs
    the datapath one step down in precision: a 16-bit word, Q4.11,
    which keeps the integer bits that m^2 + 1 needs."""
    fmt = cfg["fmt"]
    wl, fl = (16, 11) if control else (fmt["word_len"], fmt["frac_len"])
    return teda_q_flags(np.ascontiguousarray(recs.T), wl, fl, cfg["m"])


def control_store(store: Store, recs, cfg, stream_of) -> Store:
    """The control put in the program's place: the same requests and
    delivered counts, flags from the lower-precision datapath."""
    flags = expected(recs, cfg, control=True)
    out = Store()
    for key, n in store.count.items():
        out.add(key, 0, flags[:n, stream_of(key)])
    return out


def compare(store: Store, fed: dict, ref_flags: np.ndarray,
            stream_of, limits: dict):
    """Numbers compared, each with its limit, and the missing count."""
    mismatch = 0
    missing = 0
    for key, n_fed in fed.items():
        n = store.count.get(key, 0)
        missing += max(0, n_fed - n)
        want = np.flatnonzero(ref_flags[:n, stream_of(key)])
        got = store.positions(key)
        mismatch += int(np.setxor1d(got, want, assume_unique=True).size)
    numbers = {"flag_mismatch": (mismatch, limits["flag_mismatch"])}
    return numbers, missing
