"""Plain reference of the `fleet-ens5-16k` configuration: the members
and vote of `ensemble.py`, and a comparison mapped onto replayed gaps.

The live cell's tenants each stream their own series, compared as in
`ensemble.py` (`expected`, `compare`, `control_store`); its `Store`
keeps the delivered chunks' score sums in arrays, not in one tuple and
list per chunk, so that a fleet's verdicts do not fill the garbage
collector's heap (a run at 16,384 tenants kept 2 x 16,384 of them a
second).

In the backfill cell tenant c re-sends, on its pass p, recording
(c + p) mod R of R gap recordings, each of `gap` samples, from a cold
state.  Its verdicts are kept by the tenant's position q = p * gap + i
(i the sample's index in its pass) and compared with the reference of
the recording that position replays, at offset i (`GapStore`,
`expected_gaps`, `compare_gaps`, `control_gap_store`).  The compared
numbers and their definitions are `ensemble.py`'s:

  exact_mismatch   hst / teda-q flags that differ from the reference,
                   votes that differ from the majority of the delivered
                   member flags, and hst / teda-q one-sample scores that
                   differ (limit 0)
  flag_disagree    samples where a moment member's flag differs
  score_gap        the widest gap of a delivered chunk's per-member
                   score sum from the reference's, over the larger of
                   the reference's magnitude and that member's median
                   magnitude
"""
from __future__ import annotations

import numpy as np

from bench.reference import ensemble
from bench.reference.ensemble import (MEMBERS, compare, control_store,
                                      expected)

__all__ = ["MEMBERS", "Store", "compare", "control_store", "expected",
           "GapStore", "expected_gaps", "compare_gaps",
           "control_gap_store"]

_EXACT = sum(1 << MEMBERS.index(k) for k in ("hst", "teda-q"))
_MOMENT = sum(1 << MEMBERS.index(k) for k in MEMBERS[:3])


class ChunkLog:
    """Delivered chunks, in arrays: (stream, first position, n) and the
    K per-member score sums of each."""

    def __init__(self, cap: int = 1 << 16):
        self.n = 0
        self.idx = np.zeros((cap, 3), np.int64)
        self.sums = np.zeros((cap, len(MEMBERS)))

    def append(self, i: int, pos: int, k: int, sums: dict) -> None:
        if self.n == len(self.idx):
            self.idx = np.concatenate([self.idx, np.zeros_like(self.idx)])
            self.sums = np.concatenate([self.sums,
                                        np.zeros_like(self.sums)])
        self.idx[self.n] = i, pos, k
        self.sums[self.n] = [sums[m] for m in MEMBERS]
        self.n += 1

    def __iter__(self):
        """(stream, first position, n, [K sums]) per chunk, as
        `ensemble.Store.chunks` holds them."""
        for (i, pos, k), s in zip(self.idx[:self.n].tolist(),
                                  self.sums[:self.n].tolist()):
            yield i, pos, k, s


class Store(ensemble.Store):
    """`ensemble.Store`, with the chunks in a `ChunkLog`."""

    def __init__(self, t_cap: int, n: int):
        super().__init__(t_cap, n)
        self.chunks = ChunkLog()

    def add(self, i: int, pos: int, data: dict) -> None:
        n = int(data["n"])
        self.bits[pos:pos + n, i] = data["ecc"]
        self.vote[pos:pos + n, i] = data["outlier"]
        self.chunks.append(i, pos, n, data["det_scores"])


class GapStore:
    """What the parent keeps of each delivered verdict of a gap: per
    tenant and position its member bitmask (-1: not delivered) and
    vote, per delivered chunk its per-member score sums."""

    def __init__(self, n: int, gap: int, t_cap: int = 1024):
        self.n, self.gap = n, gap
        self.count = {}                     # (tenant, pass) -> delivered
        self.bits = np.full((n, t_cap), -1, np.int8)
        self.vote = np.zeros((n, t_cap), bool)
        self.chunks = ChunkLog()            # (tenant, q0, n, (K,) sums)

    def _room(self, q: int) -> None:
        cap = self.bits.shape[1]
        if q <= cap:
            return
        while cap < q:
            cap *= 2
        grow = cap - self.bits.shape[1]
        self.bits = np.pad(self.bits, ((0, 0), (0, grow)),
                           constant_values=-1)
        self.vote = np.pad(self.vote, ((0, 0), (0, grow)))

    def add(self, key, data: dict) -> None:
        c, p = key
        pos = self.count.get(key, 0)
        k = int(data["n"])
        q = p * self.gap + pos
        self._room(q + k)
        self.bits[c, q:q + k] = data["ecc"]
        self.vote[c, q:q + k] = data["outlier"]
        self.chunks.append(c, q, k, data["det_scores"])
        self.count[key] = pos + k


def expected_gaps(recs: np.ndarray, cfg: dict, upto: int,
                  control: bool = False) -> dict:
    """`ensemble.expected` over the first `upto` samples of each (R,
    gap) recording: (upto, R) bitmask and vote, (K, upto, R) scores."""
    return expected(np.ascontiguousarray(recs[:, :upto].T), cfg,
                    control=control)


def _lanes(store: GapStore, tenants: np.ndarray, q: np.ndarray,
           n_rec: int):
    """Recording and offset that tenant positions q replay."""
    return (tenants + q // store.gap) % n_rec, q % store.gap


def _seen(store: GapStore, n_rec: int, block: int = 1024):
    """Every delivered (tenant, position), a block of tenants at a time:
    (tenants, positions, recordings, offsets)."""
    for b in range(0, store.n, block):
        c, q = np.nonzero(store.bits[b:b + block] >= 0)
        c += b
        yield (c, q) + _lanes(store, c, q, n_rec)


def control_gap_store(store: GapStore, recs: np.ndarray, cfg: dict,
                      upto: int) -> GapStore:
    """The control put in the program's place: the same delivered
    positions and chunks, verdicts and scores from the lower
    precision."""
    ctl = expected_gaps(recs, cfg, upto, control=True)
    out = GapStore(store.n, store.gap, store.bits.shape[1])
    for c, q, r, off in _seen(store, recs.shape[0]):
        out.bits[c, q] = ctl["bits"][off, r]
        out.vote[c, q] = ctl["vote"][off, r]
    out.chunks = ChunkLog(len(store.chunks.idx))
    out.chunks.n = n = store.chunks.n
    out.chunks.idx[:n] = store.chunks.idx[:n]
    out.chunks.sums[:n] = _ref_sums(store, ctl)
    out.count = dict(store.count)
    return out


def _ref_sums(store: GapStore, ref: dict) -> np.ndarray:
    """(chunks, K) the reference's score sums over each delivered
    chunk."""
    n_rec = ref["bits"].shape[1]
    tt, q0, kk = store.chunks.idx[:store.chunks.n].T
    r0, o0 = _lanes(store, tt, q0, n_rec)
    cum = np.concatenate([np.zeros((len(MEMBERS), 1, n_rec)),
                          np.cumsum(ref["scores"], axis=1)], axis=1)
    return (cum[:, o0 + kk, r0] - cum[:, o0, r0]).T


def compare_gaps(store: GapStore, fed: dict, ref: dict, limits: dict):
    """Numbers compared, each with its limit, and the missing count.

    `fed[(c, p)]` is the number of samples tenant c sent on pass p; the
    reference `ref` is `expected_gaps` over at least the longest pass.
    """
    n_rec = ref["bits"].shape[1]
    missing = 0
    for (c, p), k in fed.items():
        q0 = p * store.gap
        seen = store.bits[c, q0:q0 + k] >= 0
        missing += k - int(seen.sum())
    exact_mm = disagree = 0
    for c, q, r, off in _seen(store, n_rec):
        got = store.bits[c, q].astype(np.int64)
        want = ref["bits"][off, r]
        exact_mm += int(((got ^ want) & _EXACT != 0).sum())
        # the vote is exact given the members' flags it delivered
        flagged = sum((got >> d) & 1 for d in range(len(MEMBERS)))
        exact_mm += int((store.vote[c, q]
                         != (flagged * 2 >= len(MEMBERS))).sum())
        disagree += int(((got ^ want) & _MOMENT != 0).sum())
    # chunk score sums: exact members must match per sample (1-sample
    # chunks); every member's sum is held to the relative gap
    n = store.chunks.n
    sums = store.chunks.sums[:n]
    ref_sums = _ref_sums(store, ref)
    single = store.chunks.idx[:n, 2] == 1
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
