"""The declarative detector-state fabric: `StateSpec` layouts, the
non-moment ensemble members ("hst", "teda-q") against their pure-JAX
oracles, per-detector score streams, the Q-format vote lane, and the
bit-exact opaque-region migration contract across bucket resizes and
shard moves.

Exactness tiers (the kernel conformance methodology):

  * hst / teda-q flags, scores and aux regions: EXACT equality — their
    lanes are small-integer f32 counts and int32 Q arithmetic, so the
    kernel must reproduce the oracle bit-for-bit.
  * moment-member (teda/rde/zscore) flags: EXACT on well-separated
    data (the PR 8 contract).
  * moment-member *scores*: allclose at ~5e-3 — `s2/k - mean^2` is
    catastrophically cancelling at small k, and XLA makes different
    fma-fusion choices in the kernel vs the oracle graph, so one-ULP
    input differences legitimately move the density by ~0.3%.

Opaque aux comparisons use int32 views: the teda-q regions are int32
payloads bitcast into the f32 block, and some payloads alias f32 NaN
patterns (NaN != NaN would fail a float compare on bit-identical
state).
"""
import numpy as np
import pytest

from helpers import given_or_cases
from repro.detectors import (DEFAULT_DETECTORS, MOMENT_MEMBERS, aux_rows,
                             ensemble_spec)
from repro.detectors.ensemble import (ensemble_init, ensemble_ref,
                                      ensemble_scan)
from repro.detectors.hst import hst_init, hst_leaf, hst_scan
from repro.detectors.spec import (HST_LEAVES, HST_RANGE, Region, StateSpec,
                                  f32_to_i32_bits, i32_to_f32_bits,
                                  member_regions)
from repro.detectors.teda_q import member_msq1, teda_q_member_scan
from repro.engine import ShardedPool, SlotPool
from repro.fixedpoint import QFormat
from repro.fixedpoint.teda_q import teda_q_scan_chan
from repro.launch.serve import serve_streams

FMT = QFormat(16, 8)
ALL = ("teda", "rde", "zscore", "hst", "teda-q")
KW = dict(block_t=8, interpret=True)


def _bits(aux):
    """Raw element bits of an aux block (NaN-safe exact comparison)."""
    return np.asarray(aux).view(np.int32)


def _stream(t, c, seed=0, burst=None):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(t, c)).astype(np.float32)
    if burst is not None:
        x[burst] += 9.0
    return x


# ---------------------------------------------------------- StateSpec
def test_spec_moment_only_keeps_historical_layout():
    spec = ensemble_spec(DEFAULT_DETECTORS, 8)
    assert spec.rows == 17 == aux_rows(8) == aux_rows(8, DEFAULT_DETECTORS)
    assert spec.names() == ("moment:s", "moment:s2", "moment:var")
    assert spec.offset("moment:s2") == 8
    assert spec.slc("moment:var") == slice(16, 17)
    assert all(r.tag == "f32" for r in spec.regions)


def test_spec_appends_opaque_regions_in_detector_order():
    spec = ensemble_spec(ALL, 8)
    # 17 moment + (8+8+1) hst + 2 teda-q
    assert spec.rows == 36 == aux_rows(8, ALL)
    assert spec.offset("hst:ref") == 17
    assert spec.offset("hst:phase") == 33
    assert spec.region("teda-q:mean").tag == "i32"
    assert spec.has("hst:cur") and not spec.has("nope")
    # swapping detector order moves the opaque groups with it
    rev = ensemble_spec(("teda-q", "hst"), 8)
    assert rev.offset("teda-q:mean") == 17
    assert rev.offset("hst:ref") == 19


def test_spec_validation_and_errors():
    with pytest.raises(ValueError, match="window must be >= 1"):
        ensemble_spec(ALL, 0)
    with pytest.raises(KeyError, match="unknown ensemble member"):
        member_regions("isolation-forest", 8)
    assert member_regions("teda", 8) == ()
    spec = ensemble_spec(ALL, 8)
    with pytest.raises(KeyError, match="no region 'nope'"):
        spec.offset("nope")
    with pytest.raises(ValueError, match="state.aux must be"):
        spec.validate_aux(np.zeros((17, 4), np.float32), 4)
    assert spec.init_aux(4).shape == (36, 4)


def test_bitcast_roundtrip_preserves_every_payload():
    # includes payloads that alias f32 NaN/denormal patterns
    payload = np.asarray([0, 1, -46, 2**31 - 1, -2**31, 0x7FC00000],
                         np.int32)
    f = i32_to_f32_bits(payload)
    np.testing.assert_array_equal(np.asarray(f32_to_i32_bits(f)), payload)


def test_spec_is_hashable_and_static():
    a = ensemble_spec(ALL, 8)
    assert a == ensemble_spec(ALL, 8)
    assert hash(a) == hash(ensemble_spec(ALL, 8))
    assert a != ensemble_spec(ALL, 4)
    assert isinstance(a.regions[0], Region) and isinstance(a, StateSpec)


# ------------------------------------------------------- HST oracle
def test_hst_leaf_binning():
    lo, hi = HST_RANGE
    x = np.asarray([lo - 10, lo, 0.0, hi - 1e-3, hi + 10], np.float32)
    leaves = np.asarray(hst_leaf(x))
    assert leaves[0] == 0 and leaves[1] == 0
    assert leaves[2] == HST_LEAVES // 2
    assert leaves[3] == HST_LEAVES - 1 and leaves[4] == HST_LEAVES - 1


def test_hst_oracle_window_flip_and_flags():
    # window=2 -> epoch length 2*HST_LEAVES=16.  A constant stream
    # fills one leaf; after the flip the reference mass is warm and a
    # far-off sample lands in an empty leaf -> score 0 -> flag.
    w, t = 2, 16
    x = np.zeros((t, 1), np.float32)
    st, out = hst_scan(x, 3.0, hst_init(1), window=w)
    assert not np.asarray(out["outlier"]).any()  # cold reference
    ref = np.asarray(st.ref)[:, 0]
    assert ref[int(hst_leaf(np.float32(0.0)))] == t  # flipped epoch mass
    assert np.asarray(st.cur).sum() == 0 and np.asarray(st.phase)[0] == 0
    nxt = np.asarray([[0.0], [3.9]], np.float32)
    st2, out2 = hst_scan(nxt, 3.0, st, window=w)
    o = np.asarray(out2["outlier"])[:, 0]
    s = np.asarray(out2["score"])[:, 0]
    assert s[0] == t and not o[0]     # dense leaf: mass 16, no flag
    assert s[1] == 0.0 and o[1]       # empty leaf: score 0 < window/m


def test_hst_oracle_chunked_carry_and_ragged_freeze():
    x = _stream(48, 3, seed=3)
    st1, o1 = hst_scan(x, 3.0, hst_init(3), window=2)
    st = hst_init(3)
    parts = []
    for i in range(0, 48, 16):
        st, o = hst_scan(x[i:i + 16], 3.0, st, window=2)
        parts.append(np.asarray(o["score"]))
    np.testing.assert_array_equal(np.concatenate(parts),
                                  np.asarray(o1["score"]))
    np.testing.assert_array_equal(np.asarray(st.ref), np.asarray(st1.ref))
    # vlen=0 freezes a channel exactly at its carried state
    stf, of = hst_scan(x, 3.0, st1, window=2, valid_lens=[48, 0, 7])
    np.testing.assert_array_equal(np.asarray(stf.ref)[:, 1],
                                  np.asarray(st1.ref)[:, 1])
    assert not np.asarray(of["outlier"])[:, 1].any()
    assert (np.asarray(of["score"])[7:, 2] == 0).all()


# ------------------------------------------ HST kernel conformance
def test_hst_kernel_exact_dense_and_ragged():
    t, c = 64, 4
    x = _stream(t, c, seed=0, burst=(40, 1))
    for vl in (None, [64, 17, 0, 33]):
        _, out = ensemble_scan(x, 3.0, detectors=("hst",),
                               valid_lens=vl, **KW)
        ref = ensemble_ref(x, 3.0, detectors=("hst",), valid_lens=vl)
        np.testing.assert_array_equal(np.asarray(out["det_flags"]),
                                      np.asarray(ref["det_flags"]))
        np.testing.assert_array_equal(  # EXACT, not allclose
            np.asarray(out["scores"][0]),
            np.asarray(ref["per_score"]["hst"]))


def test_hst_kernel_chunked_carry_bit_exact():
    t, c = 64, 4
    x = _stream(t, c, seed=1)
    st1, o1 = ensemble_scan(x, 3.0, detectors=("hst",), **KW)
    st = ensemble_init(c, detectors=("hst",))
    flags = []
    for i in range(0, t, 16):
        st, o = ensemble_scan(x[i:i + 16], 3.0, st,
                              detectors=("hst",), **KW)
        flags.append(np.asarray(o["det_flags"]))
    np.testing.assert_array_equal(np.concatenate(flags),
                                  np.asarray(o1["det_flags"]))
    np.testing.assert_array_equal(_bits(st.aux), _bits(st1.aux))
    np.testing.assert_array_equal(np.asarray(st.k), np.asarray(st1.k))


def test_hst_kernel_block_c_strip_invariance():
    t, c = 32, 256
    x = _stream(t, c, seed=2)
    vl = np.random.default_rng(2).integers(0, t + 1, c)
    st1, o1 = ensemble_scan(x, 3.0, detectors=("hst",), valid_lens=vl,
                            block_t=16, interpret=True)
    st2, o2 = ensemble_scan(x, 3.0, detectors=("hst",), valid_lens=vl,
                            block_t=16, block_c=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(o1["det_flags"]),
                                  np.asarray(o2["det_flags"]))
    np.testing.assert_array_equal(np.asarray(o1["scores"]),
                                  np.asarray(o2["scores"]))
    np.testing.assert_array_equal(_bits(st1.aux), _bits(st2.aux))


# ---------------------------------------------------- teda-q member
def test_tedaq_oracle_matches_fixedpoint_scan_chan():
    """The member oracle replays `_q_step_u` exactly — on a dense
    stream its registers and flags must equal the established
    fixed-point reference scan bit-for-bit."""
    x = _stream(48, 1, seed=4)
    (kf, meanf, varf), ref = teda_q_scan_chan(x, FMT, m=3.0)
    st, out = teda_q_member_scan(x, FMT, 3.0)
    np.testing.assert_array_equal(np.asarray(out["outlier"]),
                                  np.asarray(ref["outlier"]))
    np.testing.assert_array_equal(np.asarray(out["ecc"]),
                                  np.asarray(ref["ecc"]))
    np.testing.assert_array_equal(np.asarray(st.mean), np.asarray(meanf))
    np.testing.assert_array_equal(np.asarray(st.var), np.asarray(varf))


def test_tedaq_member_msq1_is_float32_path():
    m = np.float32(3.0)
    assert int(member_msq1(FMT, m)) == int(FMT.quantize(m * m + 1.0))


def test_tedaq_kernel_bit_exact_dense_ragged_chunked():
    t, c = 64, 4
    x = _stream(t, c, seed=5, burst=(40, 2))
    dets = ("teda-q",)
    for vl in (None, [64, 17, 0, 33]):
        _, out = ensemble_scan(x, 3.0, detectors=dets, fmt=FMT,
                               valid_lens=vl, **KW)
        ref = ensemble_ref(x, 3.0, detectors=dets, fmt=FMT,
                           valid_lens=vl)
        np.testing.assert_array_equal(np.asarray(out["det_flags"]),
                                      np.asarray(ref["det_flags"]))
        np.testing.assert_array_equal(  # dequantized ecc: EXACT
            np.asarray(out["scores"][0]),
            np.asarray(ref["per_score"]["teda-q"]))
    # chunked carry: opaque int32 registers ride the aux bit-exactly
    st1, o1 = ensemble_scan(x, 3.0, detectors=dets, fmt=FMT, **KW)
    st = ensemble_init(c, detectors=dets)
    for i in range(0, t, 16):
        st, _ = ensemble_scan(x[i:i + 16], 3.0, st, detectors=dets,
                              fmt=FMT, **KW)
    np.testing.assert_array_equal(_bits(st.aux), _bits(st1.aux))
    # the carried registers equal the oracle's final registers
    spec = ensemble_spec(dets, 8)
    stq, _ = teda_q_member_scan(x, FMT, 3.0)
    np.testing.assert_array_equal(
        _bits(st1.aux)[spec.slc("teda-q:mean")][0], np.asarray(stq.mean))
    np.testing.assert_array_equal(
        _bits(st1.aux)[spec.slc("teda-q:var")][0], np.asarray(stq.var))


def test_tedaq_requires_fmt():
    with pytest.raises(ValueError, match="teda-q ensemble member needs "
                                         "fmt=QFormat"):
        ensemble_scan(_stream(8, 2), 3.0, detectors=("teda", "teda-q"),
                      **KW)


# -------------------------------------------------- fused ensemble
def test_full_ensemble_flags_and_scores_conform():
    t, c = 64, 4
    x = _stream(t, c, seed=6, burst=(40, 1))
    vl = [64, 17, 0, 33]
    _, out = ensemble_scan(x, 3.0, detectors=ALL, fmt=FMT,
                           valid_lens=vl, **KW)
    ref = ensemble_ref(x, 3.0, detectors=ALL, fmt=FMT, valid_lens=vl)
    np.testing.assert_array_equal(np.asarray(out["det_flags"]),
                                  np.asarray(ref["det_flags"]))
    np.testing.assert_array_equal(np.asarray(out["vote"]),
                                  np.asarray(ref["vote"]))
    assert out["scores"].shape == (len(ALL), t, c)
    for d, name in enumerate(ALL):
        ker = np.asarray(out["scores"][d])
        exp = np.asarray(ref["per_score"][name])
        if name in MOMENT_MEMBERS:
            np.testing.assert_allclose(ker, exp, rtol=5e-3, atol=5e-3,
                                       err_msg=name)
        else:
            np.testing.assert_array_equal(ker, exp, err_msg=name)
    # invalid rows are zeroed in every stream
    assert (np.asarray(out["scores"])[:, :, 2] == 0).all()
    assert (np.asarray(out["scores"])[:, 17:, 1] == 0).all()


def test_moment_only_aux_identical_to_historical_shape():
    x = _stream(32, 4, seed=7)
    st, out = ensemble_scan(x, 3.0, detectors=DEFAULT_DETECTORS, **KW)
    assert st.aux.shape == (17, 4)
    assert out["scores"].shape == (3, 32, 4)


def test_q_vote_lane_host_recomputable_bit_exact():
    """The teda-q member's flag enters the same f32 detector-order
    weight accumulation as every other member: the fused vote must be
    reproducible on host from the emitted bitmask alone."""
    t, c = 64, 8
    x = _stream(t, c, seed=8, burst=(30, 3))
    w = np.asarray([1.0, 0.5, 1.0, 0.25, 2.0], np.float32)
    sel = np.broadcast_to(w[:, None], (5, c))
    thr = np.full((c,), 2.0, np.float32)
    _, out = ensemble_scan(x, 3.0, detectors=ALL, fmt=FMT, sel=sel,
                           thr=thr, **KW)
    bits = np.asarray(out["det_flags"])
    votew = np.zeros((t, c), np.float32)
    for d in range(len(ALL)):
        flag = ((bits >> d) & 1).astype(np.float32)
        votew = (votew + flag * w[d]).astype(np.float32)  # f32 order
    np.testing.assert_array_equal(np.asarray(out["vote"]), votew >= thr)
    assert bits.any()  # the burst actually flagged someone


@pytest.mark.slow
def test_q_vote_sweep_formats_and_seeds():
    """Slow sweep: the Q-vote lane stays host-recomputable and
    oracle-exact across word lengths and streams."""
    for fmt in (QFormat(16, 8), QFormat(24, 12), QFormat(32, 20)):
        for seed in range(3):
            x = _stream(96, 4, seed=seed, burst=(50, seed % 4))
            _, out = ensemble_scan(x, 3.0, detectors=ALL, fmt=fmt, **KW)
            ref = ensemble_ref(x, 3.0, detectors=ALL, fmt=fmt)
            np.testing.assert_array_equal(
                np.asarray(out["det_flags"]),
                np.asarray(ref["det_flags"]),
                err_msg=f"fmt={fmt} seed={seed}")
            np.testing.assert_array_equal(np.asarray(out["vote"]),
                                          np.asarray(ref["vote"]))
            np.testing.assert_array_equal(
                np.asarray(out["scores"][ALL.index("teda-q")]),
                np.asarray(ref["per_score"]["teda-q"]))


# ------------------------------------------------ migration contract
def _feed_pool(pool, rid, samples):
    """One ragged chunk to a sharded pool touching only `rid`'s slot."""
    s, slot = pool.lookup(rid)
    cap = pool.shard_capacity(s)
    chunk = np.zeros((len(samples), cap), np.float32)
    vl = np.zeros((cap,), np.int32)
    chunk[:, slot] = samples
    vl[slot] = len(samples)
    out = pool.process_shard(s, chunk, valid_lens=vl)
    return (np.asarray(out["outlier"])[:, slot],
            np.asarray(out["scores"])[:, :, slot])


@given_or_cases(
    "seed", [(0,), (1,), (2,)],
    lambda st: {"seed": st.integers(0, 99)}, max_examples=6)
def test_bucket_resize_carries_opaque_state_bits(seed):
    """Growing the bucket ladder re-pads the aux block as raw element
    bits: a mid-window hst/teda-q tenant sees identical verdicts and
    scores to a twin pool that never resized."""
    opts = dict(detectors=ALL, fmt=FMT, block_t=8, interpret=True)
    grow = SlotPool("ensemble", buckets=(2, 4), **opts)
    flat = SlotPool("ensemble", buckets=(4,), **opts)
    x = _stream(40, 1, seed=seed, burst=(33, 0))[:, 0]
    for pool in (grow, flat):
        pool.acquire(2, m=2.5)

    def feed(pool, samples):
        cap = pool.capacity
        chunk = np.zeros((len(samples), cap), np.float32)
        vl = np.zeros((cap,), np.int32)
        chunk[:, 0] = samples
        vl[0] = len(samples)
        out = pool.process(chunk, valid_lens=vl)
        return (np.asarray(out["outlier"])[:, 0],
                np.asarray(out["scores"])[:, :, 0])

    feed(grow, x[:20]), feed(flat, x[:20])     # warm, mid-epoch
    pre = _bits(grow.engine.state.aux)[:, :2].copy()
    grow.acquire(1)                            # 2 -> 4 bucket resize
    assert grow.capacity == 4
    np.testing.assert_array_equal(
        _bits(grow.engine.state.aux)[:, :2], pre)  # raw bits survived
    o_g, s_g = feed(grow, x[20:])
    o_f, s_f = feed(flat, x[20:])
    np.testing.assert_array_equal(o_g, o_f)
    np.testing.assert_array_equal(s_g, s_f)    # scores too, bit-for-bit
    assert o_g.any()                           # the burst flagged


@given_or_cases(
    "seed", [(0,), (1,), (2,)],
    lambda st: {"seed": st.integers(0, 99)}, max_examples=6)
def test_shard_migration_carries_opaque_state_bits(seed):
    """`ShardedPool.migrate` moves the full StateSpec column — moment
    tails, hst tables, bitcast Q registers — as raw bits; the stream's
    post-move verdicts and score streams equal the unmigrated twin's."""
    opts = dict(shards=2, buckets=(2, 4), detectors=ALL, fmt=FMT,
                block_t=8, interpret=True)
    moved = ShardedPool("ensemble", **opts)
    still = ShardedPool("ensemble", **opts)
    x = _stream(40, 1, seed=seed, burst=(33, 0))[:, 0]
    for pool in (moved, still):
        pool.acquire("a", m=2.5)
    _feed_pool(moved, "a", x[:20]), _feed_pool(still, "a", x[:20])
    src_s, src_slot = moved.lookup("a")
    eng = moved.pools[src_s].engine
    pre = _bits(eng.state.aux)[:, src_slot].copy()
    assert pre[17:].any()                      # opaque regions are warm
    dst = 1 - src_s
    new_slot = moved.migrate("a", dst)
    np.testing.assert_array_equal(
        _bits(moved.pools[dst].engine.state.aux)[:, new_slot], pre)
    o_m, s_m = _feed_pool(moved, "a", x[20:])
    o_s, s_s = _feed_pool(still, "a", x[20:])
    np.testing.assert_array_equal(o_m, o_s)
    np.testing.assert_array_equal(s_m, s_s)
    assert o_m.any()


# ----------------------------------------------- score streams e2e
def test_score_streams_reach_gateway_telemetry():
    """Per-request `det_scores` arrive end-to-end: kernel -> engine ->
    pool -> scheduler chunk_retired events -> gateway per-request
    telemetry, as per-detector means over retired samples."""
    rng = np.random.default_rng(9)
    streams = [(f"t{i}", rng.normal(size=(24,)).astype(np.float32),
                rng.normal(size=(8,)).astype(np.float32), 3.0)
               for i in range(3)]
    events = []
    res = serve_streams(
        streams, backend="ensemble", chunk_t=16, interpret=True,
        measure_latency=True, detectors=ALL, fmt=FMT, window=8,
        on_event=events.append)
    for rid, pr in res["per_request"].items():
        assert set(pr["det_scores"]) == set(ALL)
        assert pr["samples"] == 32
        # teda eccentricity and rde density are strictly positive on
        # normal data; the mean must reflect that
        assert pr["det_scores"]["teda"] > 0
        assert pr["det_scores"]["rde"] > 0
        assert all(np.isfinite(v) for v in pr["det_scores"].values())
    retired = [e for e in events if e.kind == "chunk_retired"]
    assert retired and all("det_scores" in e.data for e in retired)
    # the telemetry mean is exactly the event-stream sum / samples
    for rid, pr in res["per_request"].items():
        sums = {}
        for e in retired:
            if e.rid == rid:
                for d, s in e.data["det_scores"].items():
                    sums[d] = sums.get(d, 0.0) + s
        for d in ALL:
            assert pr["det_scores"][d] == pytest.approx(
                sums[d] / pr["samples"])


def test_engine_scores_zeroed_on_inactive_slots():
    from repro.engine import StreamEngine
    eng = StreamEngine(4, "ensemble", m=3.0, detectors=("teda", "rde"),
                       block_t=8, interpret=True, auto_attach=False)
    eng.attach([0, 2])
    out = eng.process(_stream(16, 4, seed=10))
    sc = np.asarray(out["scores"])
    assert sc.shape == (2, 16, 4)
    assert (sc[:, :, [1, 3]] == 0).all()
    assert sc[:, :, [0, 2]].any()
