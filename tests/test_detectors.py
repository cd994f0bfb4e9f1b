"""Detector-oracle conformance suite (ISSUE 8).

The per-detector pure-JAX `lax.scan` oracles (`repro.detectors`) are
the reference semantics the fused ensemble kernel is held to
(tests/test_ensemble.py); this module pins the oracles themselves:

  * chunk-exactness — feeding a stream in arbitrary chunk sizes with
    carried state reproduces the single-shot run bit-for-bit (the
    oracles are step-recursive, so chunk boundaries cannot round);
  * ragged valid_lens — each channel freezes after its own prefix,
    bit-exact with running the prefix alone;
  * detector semantics — RDE's biased-variance Cauchy density, the
    z-score window forgetting old regimes, and the TEDA adapter
    matching `core.scan.teda_scan` exactly;
  * the vote-threshold / aux-layout helpers the serving stack uses.
"""
import numpy as np
import pytest

from helpers import given_or_cases

from repro.detectors import (DEFAULT_DETECTORS, DETECTORS, aux_rows,
                             vote_threshold)
from repro.detectors.rde import rde_scan
from repro.detectors.teda import teda_detector_scan
from repro.detectors.zscore import zscore_init, zscore_scan


def _scan(name, x, m=3.0, state=None, valid_lens=None, window=4):
    if name == "zscore":
        if state is None:
            state = zscore_init(x.shape[1], window)
        return zscore_scan(x, m, state, valid_lens=valid_lens)
    return DETECTORS[name](x, m, state, valid_lens=valid_lens)


def _spiky(rng, t, c, every=7):
    x = rng.normal(size=(t, c)).astype(np.float32)
    x[::every] += 20.0  # unambiguous outliers, far from any threshold
    return x


# ------------------------------------------------- chunked == full
@pytest.mark.parametrize("detector", DEFAULT_DETECTORS)
@given_or_cases(
    "t,c,cut,seed", [(12, 3, 5, 0), (16, 2, 7, 1), (9, 4, 1, 2),
                     (20, 1, 13, 3)],
    lambda st: dict(t=st.integers(2, 24), c=st.integers(1, 5),
                    cut=st.integers(1, 23), seed=st.integers(0, 2 ** 16)),
    max_examples=12)
def test_chunked_equals_full(detector, t, c, cut, seed):
    cut = min(cut, t - 1)
    rng = np.random.default_rng(seed)
    x = _spiky(rng, t, c)
    _, full = _scan(detector, x)
    st, out_a = _scan(detector, x[:cut])
    _, out_b = _scan(detector, x[cut:], state=st)
    for key in ("outlier", "score"):
        got = np.concatenate([np.asarray(out_a[key]),
                              np.asarray(out_b[key])])
        want = np.asarray(full[key])
        if detector == "teda" and key == "score":
            # the TEDA oracle is an associative scan: a chunk boundary
            # reassociates the float32 reduction, so its eccentricity
            # matches to rounding (the repo-wide documented tolerance);
            # the step-recursive rde/zscore oracles are bit-exact
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        else:
            np.testing.assert_array_equal(
                got, want,
                err_msg=f"{detector}/{key} chunk boundary at {cut}")


# ------------------------------------------- ragged == isolated
@pytest.mark.parametrize("detector", DEFAULT_DETECTORS)
@given_or_cases(
    "t,c,seed", [(10, 3, 0), (8, 4, 1), (16, 2, 2)],
    lambda st: dict(t=st.integers(2, 16), c=st.integers(2, 5),
                    seed=st.integers(0, 2 ** 16)),
    max_examples=8)
def test_ragged_equals_isolated(detector, t, c, seed):
    rng = np.random.default_rng(seed)
    x = _spiky(rng, t, c)
    lens = rng.integers(0, t + 1, size=c).astype(np.int32)
    lens[0] = 0  # forced full suspend
    lens[-1] = t  # forced full chunk
    fin, out = _scan(detector, x, valid_lens=lens)
    ol = np.asarray(out["outlier"])
    assert not ol[np.arange(t)[:, None] >= lens[None, :]].any(), \
        "flag beyond the valid prefix"
    for s in range(c):
        n = int(lens[s])
        if n == 0:
            assert int(np.asarray(fin.k)[s]) == 0
            continue
        fin_i, ref = _scan(detector, x[:n, s:s + 1])
        np.testing.assert_array_equal(
            ol[:n, s], np.asarray(ref["outlier"])[:, 0],
            err_msg=f"{detector} slot {s} vlen {n}")
        np.testing.assert_array_equal(
            np.asarray(fin.k)[s], np.asarray(fin_i.k)[0])


# ------------------------------------------------- teda adapter
def test_teda_adapter_matches_core_scan():
    from repro.core.scan import teda_scan

    rng = np.random.default_rng(0)
    x = _spiky(rng, 24, 3)
    fin, out = teda_detector_scan(x, 2.5)
    ref_fin, ref = teda_scan(x[..., None], 2.5)
    np.testing.assert_array_equal(np.asarray(out["outlier"]),
                                  np.asarray(ref.outlier))
    np.testing.assert_array_equal(np.asarray(out["score"]),
                                  np.asarray(ref.ecc))
    np.testing.assert_array_equal(np.asarray(fin.k),
                                  np.asarray(ref_fin.k))


# ------------------------------------------------- rde semantics
def test_rde_flags_spike_and_scores_density():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(40, 1)).astype(np.float32)
    x[30, 0] += 25.0
    _, out = rde_scan(x, 3.0)
    ol = np.asarray(out["outlier"])[:, 0]
    assert ol[30], "RDE must flag the injected spike"
    assert not ol[:2].any(), "k < 2 must never flag (cold start)"
    score = np.asarray(out["score"])[:, 0]
    assert (score >= 0).all() and (score <= 1.0).all(), \
        "Cauchy density lies in [0, 1]"
    # the spike's density is far below a typical inlier's
    assert score[30] < 0.1 < score[29]


def test_rde_constant_stream_never_flags():
    x = np.full((16, 2), 3.25, np.float32)
    _, out = rde_scan(x, 3.0)
    assert not np.asarray(out["outlier"]).any()


# ------------------------------------------------- zscore semantics
def test_zscore_window_forgets_old_regime():
    """After a level shift ages out of the window, the windowed
    detector treats the new level as normal while continuing to flag
    genuine spikes against the *recent* statistics.

    The window must satisfy W - 1 > m^2: the current sample sits inside
    its own window, so the attainable z^2 is capped at W - 1 — with
    W = 16 and m = 3 a lone spike scores z^2 = 15 > 9 and flags."""
    rng = np.random.default_rng(2)
    w = 16
    a = rng.normal(0.0, 0.1, size=(24, 1)).astype(np.float32)
    b = rng.normal(50.0, 0.1, size=(28, 1)).astype(np.float32)
    b[24, 0] += 30.0  # spike vs the *new* regime
    x = np.concatenate([a, b])
    st = zscore_init(1, w)
    _, out = zscore_scan(x, 3.0, st)
    ol = np.asarray(out["outlier"])[:, 0]
    # once the window is fully inside regime b, plain b samples pass
    assert not ol[24 + w: 24 + 24].any(), \
        "windowed stats must adapt to the new level"
    assert ol[24 + 24], "spike vs recent window must still flag"


def test_zscore_state_ring_width_wins_over_window_kwarg():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(12, 2)).astype(np.float32)
    st = zscore_init(2, 4)
    fin, _ = zscore_scan(x, 3.0, st, window=16)  # kwarg ignored
    assert fin.ring.shape == (4, 2)


# ------------------------------------------------- helpers / config
def test_aux_rows_layout():
    assert aux_rows(8) == 17
    assert aux_rows(1) == 3
    with pytest.raises(ValueError):
        aux_rows(0)


def test_vote_threshold_modes():
    w = np.ones(3, np.float32)
    assert vote_threshold("any", w) == 1.0
    assert vote_threshold("majority", w) == 1.5
    assert vote_threshold("all", w) == 3.0
    assert vote_threshold(0.5, w) == 1.5
    # zero-weight (unselected) members drop out of every mode
    assert vote_threshold("all", np.array([1.0, 0.0, 1.0])) == 2.0
    assert vote_threshold("any", np.array([0.5, 0.0, 2.0])) == 0.5


@pytest.mark.parametrize("bad", ["quorum", 0.0, 1.5, -0.25, None, True])
def test_vote_threshold_rejects(bad):
    with pytest.raises(ValueError):
        vote_threshold(bad, np.ones(2, np.float32))
