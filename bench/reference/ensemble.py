"""Plain reference of the `fleet-ens5` configuration: the five-member
ensemble (teda, rde, zscore, hst, teda-q) and its majority vote, from
the members' definitions, over every tenant's whole sample sequence.

  teda    mean_k = S_k/k; var_k = (1/k) sum_{i=2..k} (x_i - mean_i)^2;
          ecc = 1/k + (x - mean)^2/(k var); flag ecc/2 > (m^2+1)/(2k)
  rde     varb = S2_k/k - mean^2; density 1/(1 + (x-mean)^2/varb);
          flag (x-mean)^2 > m^2 varb
  zscore  moments over the last W = 8 samples; score (x-mu_W)^2/sig_W;
          flag (x-mu_W)^2 > m^2 sig_W
  hst     8 equal cells over [-4, 4); score = the cell's count in the
          last full reference window of W*8 samples; flag score*m < W
  teda-q  TEDA on the Q11.20 datapath (`qarith.py`)
  vote    at least half of the five members (3 flags)

Every member flags only from k = 2 on, and the moment members only
where their variance is positive.  The moment members run in float64;
the program runs them in float32, so their flags may differ where a
statistic sits within rounding of its threshold, and their scores by
rounding.  hst and teda-q are exact datapaths (the hst cell is taken
in float32, as its definition states).

Compared numbers (limits in the cell's workload file):
  exact_mismatch   hst / teda-q flags and their per-sample scores
                   that differ from the reference, and votes that
                   differ from the majority of the delivered member
                   flags (limit 0)
  flag_disagree    samples where a moment member's flag differs from
                   the reference
  score_gap        the widest gap of a delivered score (each chunk's
                   per-member sum) from the reference's, over the
                   larger of the reference's magnitude and that
                   member's median magnitude
"""
from __future__ import annotations

import numpy as np

from bench.reference.qarith import QArith, counter_terms, teda_q_step

MEMBERS = ("teda", "rde", "zscore", "hst", "teda-q")
WINDOW = 8
HST_LEAVES = 8
HST_LO, HST_HI = -4.0, 4.0


def _moments(x: np.ndarray, m: float, dt):
    """teda / rde / zscore flags and scores over (T, n), every operation
    rounded to `dt` (float64 for the reference, bfloat16 for the
    control)."""
    t_len, n = x.shape
    c = lambda v: np.asarray(v, dt)
    x = c(x)
    m2 = c(m * m)
    s = np.zeros((n,), dt)
    s2 = np.zeros((n,), dt)
    var = np.zeros((n,), dt)
    tail = np.zeros((WINDOW + 1, n), dt)   # S_{k-W} .. S_k
    tail2 = np.zeros((WINDOW + 1, n), dt)
    flags = {k: np.zeros((t_len, n), bool) for k in MEMBERS[:3]}
    scores = {k: np.zeros((t_len, n), np.float64) for k in MEMBERS[:3]}
    one = c(1.0)
    zero = c(0.0)
    for t in range(t_len):
        k = c(t + 1)
        xt = x[t]
        s = c(s + xt)
        s2 = c(s2 + c(xt * xt))
        mean = c(s / k)
        dr = c(c(xt - mean) * c(xt - mean))
        # teda
        d2 = dr if t > 0 else np.zeros_like(dr)
        var = c(c(c(c(k - one) / k) * var) + c(d2 / k)) if t > 0 \
            else np.zeros_like(var)
        safe = var > 0
        ecc = c(c(one / k) + np.where(
            safe, c(d2 / c(k * np.where(safe, var, one))), zero))
        flags["teda"][t] = (c(ecc * c(0.5)) > c(c(m2 + one) / c(2 * k))) \
            & (t >= 1)
        scores["teda"][t] = ecc
        # rde
        varb = c(c(s2 / k) - c(mean * mean))
        okr = varb > 0
        flags["rde"][t] = okr & (t >= 1) & (dr > c(m2 * varb))
        scores["rde"][t] = c(one / c(one + np.where(
            okr, c(dr / np.where(okr, varb, one)), zero)))
        # zscore: window sums as prefix-sum differences
        tail = np.concatenate([tail[1:], s[None]])
        tail2 = np.concatenate([tail2[1:], s2[None]])
        w = c(min(t + 1, WINDOW))
        muw = c(c(tail[-1] - tail[0]) / w)
        sigw = c(c(c(tail2[-1] - tail2[0]) / w) - c(muw * muw))
        dz = c(c(xt - muw) * c(xt - muw))
        okz = sigw > 0
        flags["zscore"][t] = okz & (t >= 1) & (dz > c(m2 * sigw))
        scores["zscore"][t] = np.where(
            okz, c(dz / np.where(okz, sigw, one)), zero)
    return flags, scores


def _hst(x: np.ndarray, m: float, dt):
    """Half-space-tree member over (T, n); the cell index is taken in
    `dt` (float32 as defined, bfloat16 for the control)."""
    t_len, n = x.shape
    cell = np.asarray(HST_LEAVES / (HST_HI - HST_LO), dt)
    leaf = np.floor(np.asarray(
        np.asarray(x, dt) - np.asarray(HST_LO, dt), dt) * cell)
    leaf = np.clip(np.asarray(leaf, np.float64), 0,
                   HST_LEAVES - 1).astype(np.int64)
    ref = np.zeros((HST_LEAVES, n))
    cur = np.zeros((HST_LEAVES, n))
    phase = np.zeros((n,))
    cols = np.arange(n)
    wn = WINDOW * HST_LEAVES
    flags = np.zeros((t_len, n), bool)
    scores = np.zeros((t_len, n))
    for t in range(t_len):
        score = ref[leaf[t], cols]
        flags[t] = (ref.sum(axis=0) > 0) & (score * m < WINDOW)
        scores[t] = score
        cur[leaf[t], cols] += 1.0
        phase += 1.0
        flip = phase == wn
        ref[:, flip] = cur[:, flip]
        cur[:, flip] = 0.0
        phase[flip] = 0.0
    return flags, scores


def _teda_q(x: np.ndarray, m: float, word_len: int, frac_len: int):
    q = QArith(word_len, frac_len, np)
    xq = q.quantize_host(x)
    msq1 = q.const(m * m + 1.0)
    t_len, n = x.shape
    mean = np.zeros((n,), np.int64)
    var = np.zeros((n,), np.int64)
    flags = np.zeros((t_len, n), bool)
    scores = np.zeros((t_len, n))
    for t in range(t_len):
        k = np.int64(t + 1)
        mean, var, ecc, out = teda_q_step(q, k, mean, var, xq[t],
                                          counter_terms(q, k, msq1))
        flags[t] = out
        # the member's score is the eccentricity dequantized in float32
        scores[t] = ecc.astype(np.float32) / np.float32(q.one)
    return flags, scores


def expected(x: np.ndarray, cfg: dict, control: bool = False) -> dict:
    """Reference verdicts over the (T, n) streams: per-member flag
    bitmask (bit d = MEMBERS[d]), vote, and (K, T, n) scores.  The
    control computes the float members in bfloat16 and the Q member on
    a 16-bit word (Q4.11)."""
    if tuple(cfg["detectors"]) != MEMBERS:
        raise ValueError(f"reference covers {MEMBERS}, config runs "
                         f"{cfg['detectors']}")
    m = float(cfg["m"])
    fmt = cfg["fmt"]
    if control:
        import ml_dtypes
        fdt, hdt, wl, fl = ml_dtypes.bfloat16, ml_dtypes.bfloat16, 16, 11
    else:
        fdt, hdt = np.float64, np.float32
        wl, fl = fmt["word_len"], fmt["frac_len"]
    flags, scores = _moments(x, m, fdt)
    flags["hst"], scores["hst"] = _hst(x, m, hdt)
    flags["teda-q"], scores["teda-q"] = _teda_q(x, m, wl, fl)
    bits = np.zeros(x.shape, np.int64)
    for d, name in enumerate(MEMBERS):
        bits |= flags[name].astype(np.int64) << d
    votes = sum(flags[name].astype(np.int64) for name in MEMBERS)
    return {"bits": bits, "vote": votes * 2 >= len(MEMBERS),
            "scores": np.stack([scores[name] for name in MEMBERS])}


class Store:
    """What the fleet keeps of each delivered verdict: per sample the
    member bitmask and the vote, per delivered chunk its per-member
    score sums."""

    def __init__(self, t_cap: int, n: int):
        self.bits = np.full((t_cap, n), -1, np.int64)
        self.vote = np.zeros((t_cap, n), bool)
        self.chunks = []   # (stream, first position, n, (K,) sums)

    def add(self, i: int, pos: int, data: dict) -> None:
        n = int(data["n"])
        self.bits[pos:pos + n, i] = data["ecc"]
        self.vote[pos:pos + n, i] = data["outlier"]
        sums = data["det_scores"]
        self.chunks.append((i, pos, n, [sums[k] for k in MEMBERS]))


def control_store(store: Store, x: np.ndarray, cfg: dict) -> Store:
    """The control put in the program's place: the same delivered
    samples and chunks, verdicts and scores from the lower precision."""
    ctl = expected(x, cfg, control=True)
    out = Store(*store.bits.shape)
    seen = store.bits >= 0
    out.bits[seen] = ctl["bits"][seen]
    out.vote[seen] = ctl["vote"][seen]
    for i, pos, n, _ in store.chunks:
        out.chunks.append((i, pos, n, list(
            ctl["scores"][:, pos:pos + n, i].sum(axis=1))))
    return out


def compare(store: Store, lengths: np.ndarray, ref: dict, limits: dict):
    """Numbers compared, each with its limit, and the missing count.

    `lengths[i]` is the number of samples stream i was fed."""
    t_cap, n = store.bits.shape
    due = np.arange(t_cap)[:, None] < lengths[None, :]
    seen = store.bits >= 0
    missing = int((due & ~seen).sum())
    exact = sum(1 << MEMBERS.index(k) for k in ("hst", "teda-q"))
    moment = sum(1 << MEMBERS.index(k) for k in MEMBERS[:3])
    got, want = store.bits[seen], ref["bits"][seen]
    exact_mm = int(((got ^ want) & exact != 0).sum())
    # the vote is exact given the members' flags it delivered
    flagged = sum((got >> d) & 1 for d in range(len(MEMBERS)))
    exact_mm += int((store.vote[seen] != (flagged * 2 >= len(MEMBERS)))
                    .sum())
    disagree = int(((got ^ want) & moment != 0).sum())
    # chunk score sums: exact members must match per sample (1-sample
    # chunks); every member's sum is held to the relative gap
    idx = np.asarray([(i, pos, k) for i, pos, k, _ in store.chunks])
    sums = np.asarray([s for *_, s in store.chunks], np.float64)
    ref_cum = np.concatenate([np.zeros((len(MEMBERS), 1, n)),
                              np.cumsum(ref["scores"], axis=1)], axis=1)
    ii, p0, kk = idx[:, 0], idx[:, 1], idx[:, 2]
    ref_sums = (ref_cum[:, p0 + kk, ii] - ref_cum[:, p0, ii]).T
    single = kk == 1
    for name in ("hst", "teda-q"):
        d = MEMBERS.index(name)
        exact_mm += int((sums[single, d] != ref_sums[single, d]).sum())
    mag = np.abs(ref_sums)
    floor = np.median(mag, axis=0, keepdims=True)
    scale = np.maximum(np.maximum(mag, floor), np.finfo(np.float64).tiny)
    gap = float((np.abs(sums - ref_sums) / scale).max())
    numbers = {"exact_mismatch": (exact_mm, limits["exact_mismatch"]),
               "flag_disagree": (disagree, limits["flag_disagree"]),
               "score_gap": (gap, limits["score_gap"])}
    return numbers, missing
