"""Fused detector-ensemble conformance + serving integration (ISSUE 8).

The fused K-detector Pallas kernel must agree with the composed
per-detector `lax.scan` oracles (`ensemble_ref`) on EVERY flag — dense,
ragged vlens (including forced 0 and T), across chunk boundaries, and
across `block_c` channel strips — and per-slot detector *selection*
must be indistinguishable from running the smaller ensemble: a masked
slot's bits/vote/state equal the single-detector run bit-for-bit
(selection gates flags and vote only; the shared prefix-sum fabric
always advances).  Above the kernel, the suite pins the serving stack:
`StreamEngine.attach(detectors=..., vote=...)`, pool resize carrying
the aux block and per-slot detector config across buckets, the
scheduler's per-detector flag accounting, and the gateway's 7-tuple
streams.  The `slow`-marked sweeps run the full-width K x C grid
(multiple block_c strips) on the main-branch ensemble-full CI job.
"""
import numpy as np
import pytest

from helpers import given_or_cases

from repro.detectors import DEFAULT_DETECTORS, vote_threshold
from repro.detectors.ensemble import ensemble_init, ensemble_ref, ensemble_scan
from repro.engine import SlotPool, StreamEngine, list_backends
from repro.engine.backends import get_backend
from repro.launch.batching import BatchingScheduler, Request
from repro.launch.serve import serve_streams

# every ensemble subset the conformance matrix cares about: each member
# alone (the CI detector x pallas legs key on these ids), a pair, and
# the full fused ensemble
DSETS = [("teda",), ("rde",), ("zscore",), ("teda", "rde"),
         ("teda", "rde", "zscore")]
_IDS = ["+".join(d) for d in DSETS]


def _spiky(rng, t, c, every=7):
    x = rng.normal(size=(t, c)).astype(np.float32)
    x[::every] += 20.0  # unambiguous outliers, far from any threshold
    return x


def _ragged_lens(rng, t, c):
    lens = rng.integers(0, t + 1, size=c).astype(np.int32)
    lens[0] = 0  # forced full suspend
    lens[-1] = t  # forced full chunk
    return lens


def _kernel(x, detectors, **kw):
    kw.setdefault("block_t", 8)
    kw.setdefault("interpret", True)
    return ensemble_scan(x, 3.0, detectors=detectors, **kw)


# --------------------------------------------- kernel vs scan oracles
@pytest.mark.parametrize("detectors", DSETS, ids=_IDS)
@given_or_cases(
    "t,c,seed,ragged", [(16, 4, 0, False), (24, 3, 1, True),
                        (9, 5, 2, True)],
    lambda st: dict(t=st.integers(2, 24), c=st.integers(1, 6),
                    seed=st.integers(0, 2 ** 16), ragged=st.booleans()),
    max_examples=3)
def test_kernel_matches_oracle(detectors, t, c, seed, ragged):
    rng = np.random.default_rng(seed)
    x = _spiky(rng, t, c)
    lens = _ragged_lens(rng, t, c) if ragged else None
    fin, out = _kernel(x, detectors, valid_lens=lens)
    ref = ensemble_ref(x, 3.0, detectors=detectors, valid_lens=lens)
    np.testing.assert_array_equal(np.asarray(out["det_flags"]),
                                  np.asarray(ref["det_flags"]))
    np.testing.assert_array_equal(np.asarray(out["vote"]),
                                  np.asarray(ref["vote"]))
    want_k = np.full((c,), t) if lens is None else lens
    np.testing.assert_array_equal(np.asarray(fin.k),
                                  want_k.astype(np.float32))


def test_chunked_carry_equals_full_run():
    """Carrying EnsembleState across chunk boundaries reproduces the
    single-shot flags exactly (separated data); the float aux rows
    match to reassociation rounding, like the TEDA float path."""
    rng = np.random.default_rng(5)
    t, c, cut = 24, 4, 11
    x = _spiky(rng, t, c)
    _, full = _kernel(x, DEFAULT_DETECTORS)
    st, out_a = _kernel(x[:cut], DEFAULT_DETECTORS)
    fin, out_b = _kernel(x[cut:], DEFAULT_DETECTORS, state=st)
    for key in ("det_flags", "vote"):
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(out_a[key]),
                            np.asarray(out_b[key])]),
            np.asarray(full[key]), err_msg=key)
    fin_full, _ = _kernel(x, DEFAULT_DETECTORS)  # jit-cached re-run
    np.testing.assert_array_equal(np.asarray(fin.k),
                                  np.asarray(fin_full.k))
    np.testing.assert_allclose(np.asarray(fin.aux),
                               np.asarray(fin_full.aux),
                               rtol=1e-5, atol=1e-6)


def test_block_c_strip_invariance():
    """Channel strips are independent grid blocks: splitting the padded
    width into two block_c strips is bit-identical to one strip."""
    rng = np.random.default_rng(6)
    t, c = 12, 130  # pads to 256 lanes: block_c=128 -> 2 strips
    x = _spiky(rng, t, c)
    lens = _ragged_lens(rng, t, c)
    fa, a = _kernel(x, DEFAULT_DETECTORS, valid_lens=lens, block_c=128)
    fb, b = _kernel(x, DEFAULT_DETECTORS, valid_lens=lens, block_c=256)
    np.testing.assert_array_equal(np.asarray(a["det_flags"]),
                                  np.asarray(b["det_flags"]))
    np.testing.assert_array_equal(np.asarray(a["vote"]),
                                  np.asarray(b["vote"]))
    np.testing.assert_array_equal(np.asarray(fa.k), np.asarray(fb.k))
    np.testing.assert_array_equal(np.asarray(fa.aux), np.asarray(fb.aux))


@pytest.mark.parametrize("d,det", list(enumerate(DEFAULT_DETECTORS)),
                         ids=list(DEFAULT_DETECTORS))
def test_selection_mask_equals_single_detector(d, det):
    """Zero-weighting all but one member of the K=3 ensemble is
    bit-identical to running the K=1 ensemble of that member: same
    flags (re-based to bit d), same vote, same advanced state."""
    rng = np.random.default_rng(7)
    t, c = 16, 4
    x = _spiky(rng, t, c)
    sel = np.zeros((3, c), np.float32)
    sel[d] = 1.0
    fm, masked = _kernel(x, DEFAULT_DETECTORS, sel=sel)
    fs, single = _kernel(x, (det,))
    np.testing.assert_array_equal(
        np.asarray(masked["det_flags"]),
        np.asarray(single["det_flags"]) << d,
        err_msg=f"{det} masked-slot flags (bit {d})")
    np.testing.assert_array_equal(np.asarray(masked["vote"]),
                                  np.asarray(single["vote"]))
    # the sample counter always advances; the aux rows are NOT compared
    # here — the K=1 kernel only advances the fabric rows its member
    # reads, while selection within one ensemble never touches state
    # (test_selection_mask_leaves_state_untouched pins that)
    np.testing.assert_array_equal(np.asarray(fm.k), np.asarray(fs.k))


def test_selection_mask_leaves_state_untouched():
    """Within one ensemble, runtime selection weights gate flags and
    vote only: any sel advances k and every aux row identically."""
    rng = np.random.default_rng(15)
    t, c = 16, 4
    x = _spiky(rng, t, c)
    sel = np.zeros((3, c), np.float32)
    sel[1] = 1.0  # rde-only selection, same K=3 ensemble
    fm, _ = _kernel(x, DEFAULT_DETECTORS, sel=sel)
    ff, _ = _kernel(x, DEFAULT_DETECTORS)
    np.testing.assert_array_equal(np.asarray(fm.k), np.asarray(ff.k))
    np.testing.assert_array_equal(np.asarray(fm.aux), np.asarray(ff.aux))


def test_vote_matches_host_recompute_weighted():
    """The kernel's fused verdict equals recomputing the weighted vote
    on host from its own detector bits — float32, detector order."""
    rng = np.random.default_rng(8)
    t, c = 20, 5
    x = _spiky(rng, t, c, every=5)
    w = np.asarray([1.0, 0.5, 2.0], np.float32)
    sel = np.broadcast_to(w[:, None], (3, c)).astype(np.float32)
    thr = np.full((c,), vote_threshold("majority", w), np.float32)
    _, out = _kernel(x, DEFAULT_DETECTORS, sel=sel, thr=thr)
    bits = np.asarray(out["det_flags"])
    votew = np.zeros((t, c), np.float32)
    for d in range(3):
        votew = votew + ((bits >> d) & 1).astype(np.float32) * sel[d]
    np.testing.assert_array_equal(np.asarray(out["vote"]),
                                  votew >= thr[None, :])


def test_teda_lane_bitidentical_to_pallas_backend():
    """The ensemble's TEDA member reuses the TEDA kernel's arithmetic:
    a teda-only ensemble engine flags bit-identically to the standalone
    "pallas" backend at equal block_t, chunk by chunk."""
    rng = np.random.default_rng(9)
    c = 4
    x = _spiky(rng, 32, c)
    ep = StreamEngine(c, "pallas", m=3.0, block_t=8, interpret=True)
    ee = StreamEngine(c, "ensemble", m=3.0, detectors=("teda",),
                      block_t=8, interpret=True)
    for lo in range(0, 32, 8):
        chunk = x[lo:lo + 8]
        op = ep.process(chunk)
        oe = ee.process(chunk)
        np.testing.assert_array_equal(
            np.asarray(oe["outlier"]), np.asarray(op["outlier"]),
            err_msg=f"chunk at {lo}")
        np.testing.assert_array_equal(
            np.asarray(oe["det_flags"]).astype(bool),
            np.asarray(op["outlier"]))


# --------------------------------------------------- kernel guards
def test_ensemble_scan_rejects_bad_args():
    x = np.zeros((4, 2), np.float32)
    with pytest.raises(ValueError, match="non-empty unique subset"):
        ensemble_scan(x, detectors=())
    with pytest.raises(ValueError, match="non-empty unique subset"):
        ensemble_scan(x, detectors=("teda", "teda"))
    with pytest.raises(ValueError, match="non-empty unique subset"):
        ensemble_scan(x, detectors=("teda", "lof"))
    with pytest.raises(ValueError, match="state.aux"):
        ensemble_scan(x, state=ensemble_init(2, window=4), window=8)


def test_backend_registry_and_validation():
    be = get_backend("ensemble")
    assert be.detectors == DEFAULT_DETECTORS
    assert be.aux_rows == 17  # 2 * DEFAULT_WINDOW + 1
    assert be.default_threshold == 1.5  # majority of 3 unit weights
    # a different detection algorithm, not a TEDA executor: resolvable,
    # but not in the TEDA conformance-parametrized listing
    assert "ensemble" not in list_backends()
    assert "ensemble" in list_backends(all=True)
    with pytest.raises(ValueError, match="unknown detectors"):
        get_backend("ensemble", weights={"lof": 2.0})
    with pytest.raises(ValueError, match="one entry per detector"):
        get_backend("ensemble", weights=[1.0, 2.0])
    with pytest.raises(ValueError, match="must be positive"):
        get_backend("ensemble", weights=[1.0, 0.0, 1.0])
    with pytest.raises(ValueError, match="vote"):
        get_backend("ensemble", vote="quorum")
    with pytest.raises(ValueError, match="aux"):
        z = np.zeros((2,), np.float32)
        be.process(np.zeros((4, 2), np.float32), z, z, z)


# ------------------------------------------------ engine integration
def test_engine_slot_selection_matches_isolated_rde():
    """set_detectors([s], detectors=("rde",)) makes slot s report RDE
    alone — bit 1 of the member order, vote == the RDE flag — exactly
    as if the channel ran an rde-only ensemble; untouched slots keep
    the full ensemble."""
    rng = np.random.default_rng(10)
    c, t = 4, 16
    x = _spiky(rng, t, c)
    eng = StreamEngine(c, "ensemble", m=3.0, block_t=8, interpret=True)
    eng.set_detectors([2], detectors=("rde",), vote="any")
    cfg = eng.detector_config(2)
    assert cfg["detectors"] == ("rde",)
    assert cfg["threshold"] == 1.0
    out = eng.process(x)
    bits = np.asarray(out["det_flags"])
    ref_full = ensemble_ref(x, 3.0)
    ref_rde = ensemble_ref(x[:, 2:3], 3.0, detectors=("rde",))
    np.testing.assert_array_equal(
        bits[:, 2], np.asarray(ref_rde["det_flags"])[:, 0] << 1)
    np.testing.assert_array_equal(np.asarray(out["outlier"])[:, 2],
                                  np.asarray(ref_rde["vote"])[:, 0])
    for s in (0, 1, 3):  # unselected slots: the full default ensemble
        np.testing.assert_array_equal(
            bits[:, s], np.asarray(ref_full["det_flags"])[:, s])


def test_engine_attach_detach_detector_lifecycle():
    eng = StreamEngine(2, "ensemble", m=3.0, block_t=8, interpret=True,
                      auto_attach=False)
    eng.attach(n=2, detectors=("zscore",), vote="all")
    assert eng.detector_config(0)["detectors"] == ("zscore",)
    assert eng.detector_config(1)["detectors"] == ("zscore",)
    eng.detach([0])  # recycled slots revert to the full ensemble
    assert eng.detector_config(0)["detectors"] == DEFAULT_DETECTORS
    assert eng.detector_config(1)["detectors"] == ("zscore",)
    with pytest.raises(ValueError, match="subset"):
        eng.set_detectors([1], detectors=("iforest",))
    with pytest.raises(ValueError, match="vote"):
        eng.set_detectors([1], vote="plurality")


def test_engine_guards_non_ensemble_and_mesh():
    scan_eng = StreamEngine(2, "scan")
    with pytest.raises(ValueError, match="detector"):
        scan_eng.set_detectors([0], detectors=("rde",))
    with pytest.raises(ValueError, match="detector"):
        scan_eng.detector_config(0)
    with pytest.raises(ValueError, match="mesh"):
        StreamEngine(2, "ensemble", mesh=object())


# -------------------------------------------------- pool integration
def test_pool_resize_carries_aux_and_detector_config():
    """Growing through the bucket ladder must migrate the aux block and
    the per-slot detector selection: an rde-only tenant acquired before
    the resize keeps flagging exactly like an isolated rde run of its
    whole stream, across the boundary."""
    rng = np.random.default_rng(11)
    pool = SlotPool("ensemble", buckets=(2, 4), m=3.0, block_t=8,
                    interpret=True)
    s0 = int(pool.acquire(1, detectors=("rde",), vote="any")[0])
    x1 = _spiky(rng, 16, pool.capacity)
    out1 = pool.process(x1)
    bits1 = np.asarray(out1["det_flags"])[:, s0]
    pool.acquire(2)  # 3 live slots: forces the 2 -> 4 bucket
    assert pool.capacity == 4 and pool.resizes == 1
    assert pool.engine.detector_config(s0)["detectors"] == ("rde",)
    x2 = _spiky(rng, 16, pool.capacity)
    x2[:, s0] = _spiky(rng, 16, 1)[:, 0]
    out2 = pool.process(x2)
    bits2 = np.asarray(out2["det_flags"])[:, s0]
    stream = np.concatenate([x1[:, s0:s0 + 1], x2[:, s0:s0 + 1]])
    ref = ensemble_ref(stream, 3.0, detectors=("rde",))
    np.testing.assert_array_equal(
        np.concatenate([bits1, bits2]),
        np.asarray(ref["det_flags"])[:, 0] << 1,
        err_msg="rde-only tenant across the pool resize")


# --------------------------------------------- scheduler + gateway
def _history(rng, n, spike_at=None):
    h = rng.normal(size=(n,)).astype(np.float32)
    if spike_at is not None:
        h[spike_at] += 25.0
    return h


def test_scheduler_per_detector_flag_accounting():
    rng = np.random.default_rng(12)
    sched = BatchingScheduler("ensemble", buckets=(2, 4), chunk_t=8,
                              block_t=8, interpret=True)
    sched.submit(Request("a", _history(rng, 20, spike_at=15),
                         detectors=("teda", "rde"), vote="any"))
    sched.submit(Request("b", _history(rng, 12)))
    sched.close("a")
    sched.close("b")
    sched.drain()
    st = sched.stats_by_rid["a"]
    assert st.det_flags, "the spike must flag at least one member"
    assert set(st.det_flags) <= {"teda", "rde"}, \
        "zscore is unselected on this slot: its flags must be masked"
    totals = sched.stats()["detector_flags"]
    agg = {}
    for r in sched.stats_by_rid.values():
        for det, n in r.det_flags.items():
            agg[det] = agg.get(det, 0) + n
    assert {d: n for d, n in totals.items() if n} == agg


def test_serve_streams_seven_tuple_and_per_request_flags():
    rng = np.random.default_rng(13)
    streams = [
        ("a", _history(rng, 16, spike_at=12), _history(rng, 4), None,
         "default", ("rde",), "any"),
        ("b", _history(rng, 10), _history(rng, 6, spike_at=3), None),
    ]
    res = serve_streams(streams, backend="ensemble", buckets=(2, 4),
                        chunk_t=8, block_t=8, interpret=True)
    assert res["requests"] == 2
    fa = res["per_request"]["a"]["det_flags"]
    assert fa.get("rde", 0) >= 1, "the history spike must flag RDE"
    assert set(fa) == {"rde"}, \
        "detectors=('rde',) masks every other member's flags"
    assert res["per_request"]["b"]["det_flags"].get("rde", 0) >= 1
    assert res["per_request"]["a"]["samples"] == 20


# ------------------------------------------- full-width slow sweeps
@pytest.mark.slow
@pytest.mark.parametrize("detectors", DSETS, ids=_IDS)
def test_full_width_ragged_sweep(detectors):
    """Serving-width conformance: 260 channels (three 128-lane strips
    at block_c=128), ragged vlens, every ensemble subset — kernel
    flags and vote exact vs the composed oracles."""
    rng = np.random.default_rng(14)
    t, c = 48, 260
    x = _spiky(rng, t, c, every=5)
    lens = _ragged_lens(rng, t, c)
    fin, out = ensemble_scan(x, 3.0, detectors=detectors,
                             valid_lens=lens, block_t=16, block_c=128,
                             interpret=True)
    ref = ensemble_ref(x, 3.0, detectors=detectors, valid_lens=lens)
    np.testing.assert_array_equal(np.asarray(out["det_flags"]),
                                  np.asarray(ref["det_flags"]))
    np.testing.assert_array_equal(np.asarray(out["vote"]),
                                  np.asarray(ref["vote"]))
    np.testing.assert_array_equal(np.asarray(fin.k),
                                  lens.astype(np.float32))
