"""StreamEngine: chunked-equals-full property suite + ragged slots.

Acceptance (ISSUE 2): for each backend, feeding a stream in random-sized
chunks through `StreamEngine` must reproduce the single-shot result
bit-for-bit (Q path) / to fp32 tolerance (float paths), including
`T % block_t != 0` remainders and mid-stream resets; per-channel `k` is
preserved end-to-end and a valid final state exists for every T.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from conftest import given_or_cases

from repro.engine import (StreamEngine, engine_init, engine_step,
                          list_backends)
from repro.fixedpoint import QFormat
from repro.kernels.ref import teda_ref

FMT = QFormat(32, 20)


def _x(t, c, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(t, c)).astype(np.float32)
    x[t // 2, : max(1, c // 2)] += 20.0  # make someone flag
    return x


def _mk(c, backend, block_t=32, **kw):
    return StreamEngine(c, backend, fmt=FMT, block_t=block_t, **kw)


def _split(x, seed):
    """Random ragged chunking of x along time (chunk lens >= 1)."""
    rng = np.random.default_rng(seed)
    t = x.shape[0]
    cuts, i = [], 0
    while i < t:
        i += int(rng.integers(1, max(2, t // 3)))
        cuts.append(min(i, t))
    return np.split(x, cuts[:-1], axis=0)


def _run_chunked(eng, parts):
    outs = [eng.process(p) for p in parts]
    return {k: np.concatenate([np.asarray(o[k]) for o in outs], 0)
            for k in outs[0]}


# ------------------------------------------------- chunked == full (all)
@pytest.mark.parametrize("backend", list_backends())
@given_or_cases(
    "t,c,seed", [(70, 3, 0), (129, 2, 1), (256, 5, 2), (37, 1, 3)],
    lambda st: dict(t=st.integers(2, 300), c=st.integers(1, 8),
                    seed=st.integers(0, 2 ** 16)),
    max_examples=6)
def test_chunked_equals_full(backend, t, c, seed):
    x = _x(t, c, seed)
    full = _mk(c, backend)
    chunked = _mk(c, backend)
    out_f = full.process(x)
    out_c = _run_chunked(chunked, _split(x, seed + 1))
    if backend == "pallas-q":  # quantized datapath: exact bits
        np.testing.assert_array_equal(np.asarray(out_f["ecc"]),
                                      out_c["ecc"])
        np.testing.assert_array_equal(np.asarray(full.state.mean),
                                      np.asarray(chunked.state.mean))
        np.testing.assert_array_equal(np.asarray(full.state.var),
                                      np.asarray(chunked.state.var))
    else:
        np.testing.assert_allclose(np.asarray(out_f["ecc"]), out_c["ecc"],
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(full.state.var),
                                   np.asarray(chunked.state.var),
                                   rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out_f["outlier"]),
                                  out_c["outlier"])
    # per-channel k preserved end-to-end, valid for every T
    assert full.samples_seen.tolist() == [t] * c
    assert chunked.samples_seen.tolist() == [t] * c


@pytest.mark.parametrize("backend", list_backends())
def test_remainder_chunks_match_oracle(backend):
    """T % block_t != 0 everywhere: 3 chunks of awkward lengths."""
    x = _x(70 + 33 + 5, 2, seed=7)
    eng = _mk(2, backend, block_t=64)
    out = _run_chunked(eng, [x[:70], x[70:103], x[103:]])
    ref = teda_ref(np.asarray(x, np.float32), 3.0)
    np.testing.assert_array_equal(out["outlier"], ref["outlier"])
    np.testing.assert_allclose(np.asarray(eng.state.k), 108.0)


# -------------------------------------------------------- ragged tenancy
@pytest.mark.parametrize("backend", list_backends())
def test_mid_stream_reset_recycles_slot(backend):
    """Resetting a slot mid-flight == a fresh stream on that slot."""
    c = 4
    xa, xb = _x(57, c, seed=11), _x(61, c, seed=12)
    eng = _mk(c, backend)
    eng.process(xa)
    eng.reset([2])
    out = eng.process(xb)

    fresh = _mk(c, backend)  # slot 2's post-reset oracle: xb alone
    out_fresh = fresh.process(xb)
    np.testing.assert_array_equal(np.asarray(out["outlier"])[:, 2],
                                  np.asarray(out_fresh["outlier"])[:, 2])
    # untouched slots carried on: k = 57 + 61, reset slot k = 61
    assert eng.samples_seen.tolist() == [118, 118, 61, 118]

    cont = _mk(c, backend)  # slot 0's oracle: the uninterrupted stream
    cont.process(np.concatenate([xa, xb], 0))
    if backend == "pallas-q":
        np.testing.assert_array_equal(np.asarray(eng.state.var)[0],
                                      np.asarray(cont.state.var)[0])
    else:
        np.testing.assert_allclose(np.asarray(eng.state.var)[0],
                                   np.asarray(cont.state.var)[0],
                                   rtol=1e-4)


@pytest.mark.parametrize("backend", list_backends())
def test_detached_slots_never_advance_or_flag(backend):
    c = 4
    eng = _mk(c, backend, auto_attach=False)
    eng.attach([0, 2])
    x = _x(40, c, seed=21)
    x[:, 1] += 50.0  # would flag loudly if slot 1 were live
    out = eng.process(x)
    assert not np.asarray(out["outlier"])[:, [1, 3]].any()
    assert eng.samples_seen.tolist() == [40, 0, 40, 0]
    assert eng.active_slots.tolist() == [0, 2]
    eng.detach([0])
    assert eng.active_slots.tolist() == [2]
    assert eng.samples_seen[0] == 0  # detach clears the tenant's state


def test_attach_n_free_slots():
    eng = StreamEngine(6, "scan", auto_attach=False)
    got = eng.attach(n=4)
    assert len(got) == 4
    with pytest.raises(ValueError):
        eng.attach(n=3)  # only 2 free


def test_attach_full_engine_raises_with_occupancy():
    """Regression (ISSUE 3): attach on a full engine must raise with
    the occupancy, not no-op via scatter's silent OOB-drop semantics."""
    eng = StreamEngine(3, "scan", auto_attach=False)
    eng.attach()  # grabs all free slots
    with pytest.raises(ValueError, match=r"3/3"):
        eng.attach()
    with pytest.raises(ValueError, match=r"3/3"):
        eng.attach(n=1)


def test_attach_occupied_slot_raises():
    """An explicit attach on a live tenant's slot must not clobber it."""
    eng = StreamEngine(4, "scan", auto_attach=False)
    eng.attach([1])
    eng.process(_x(10, 4, seed=61))
    with pytest.raises(ValueError, match=r"\[1\] already attached"):
        eng.attach([1, 2])
    assert eng.samples_seen[1] == 10  # tenant untouched by the failure
    eng.detach([1])
    eng.attach([1, 2])  # fine once freed


@pytest.mark.parametrize("backend", list_backends())
def test_per_slot_m_matches_scalar_engines(backend):
    """A mixed-m batch equals per-m scalar engines column for column.

    The m values are deliberately non-dyadic: the Q backend must
    quantize the per-slot m^2+1 ROM constants on the host (exactly),
    not through the float32 tracer."""
    c = 4
    x = _x(50, c, seed=71)
    mixed = _mk(c, backend)
    mixed.set_m([0, 1], 1.7)
    mixed.set_m([2, 3], 6.3)
    out = mixed.process(x)
    lo = _mk(c, backend, m=1.7).process(x)
    hi = _mk(c, backend, m=6.3).process(x)
    got = np.asarray(out["outlier"])
    np.testing.assert_array_equal(got[:, :2], np.asarray(lo["outlier"])[:, :2])
    np.testing.assert_array_equal(got[:, 2:], np.asarray(hi["outlier"])[:, 2:])
    # sensitivity ordering: the tighter threshold flags at least as often
    assert got[:, :2].sum() >= got[:, 2:].sum()
    if backend == "pallas-q":  # ecc is m-independent and stays bit-exact
        np.testing.assert_array_equal(np.asarray(out["ecc"]),
                                      np.asarray(lo["ecc"]))


def test_msq1_vector_matches_scalar_for_awkward_m():
    """Host quantization of per-slot m^2+1 is exact: a vector of any
    (non-dyadic) m yields the same Q bits as the scalar ROM path."""
    import numpy as np
    from repro.fixedpoint.teda_q import msq1_const
    for m in (2.3, 1.7, 3.0, 6.3):
        scalar = msq1_const(FMT, m)
        vec = np.asarray(msq1_const(FMT, np.full((5,), m, np.float64)))
        assert vec.tolist() == [scalar] * 5, m
    # integer input is taken as already-quantized
    assert int(msq1_const(FMT, jnp.int32(12345))) == 12345


def test_attach_sets_tenant_m_and_detach_restores_default():
    eng = StreamEngine(3, "scan", m=3.0, auto_attach=False)
    eng.attach([0], m=1.25)
    assert eng.slot_m.tolist() == [1.25, 3.0, 3.0]
    eng.detach([0])
    assert eng.slot_m.tolist() == [3.0, 3.0, 3.0]


def test_set_m_vector_is_positional():
    """Regression: a vector m must follow the caller's slot order (a
    mask-based assign silently re-sorted it), and bad slots raise."""
    eng = StreamEngine(4, "scan", m=3.0)
    eng.set_m([3, 1], [2.0, 5.0])
    assert eng.slot_m.tolist() == [3.0, 5.0, 3.0, 2.0]
    eng.set_m(None, 4.0)
    assert eng.slot_m.tolist() == [4.0] * 4
    eng.set_m(np.array([True, False, False, True]), 1.5)
    assert eng.slot_m.tolist() == [1.5, 4.0, 4.0, 1.5]
    with pytest.raises(IndexError):
        eng.set_m([4], 2.0)


@pytest.mark.parametrize("backend", list_backends())
def test_per_call_active_mask_suspends_without_detach(backend):
    """The scheduler's suspend: masked-out slots freeze but keep state."""
    c = 4
    xa, xb = _x(16, c, seed=81), _x(16, c, seed=82)
    eng = _mk(c, backend)
    eng.process(xa, active=[0, 1])
    out = eng.process(xb, active=[1])
    assert eng.samples_seen.tolist() == [16, 32, 0, 0]
    assert not np.asarray(out["outlier"])[:, [0, 2, 3]].any()
    # slot 1 advanced exactly like an unsuspended stream
    cont = _mk(c, backend)
    cont.process(xa)
    ref = cont.process(xb)
    np.testing.assert_array_equal(np.asarray(out["outlier"])[:, 1],
                                  np.asarray(ref["outlier"])[:, 1])


def test_per_channel_k_raggedness():
    """Slots attached at different times have honestly different k."""
    eng = StreamEngine(3, "pallas", block_t=32, auto_attach=False)
    eng.attach([0])
    eng.process(_x(20, 3, seed=31))
    eng.attach([1])
    eng.process(_x(25, 3, seed=32))
    assert eng.samples_seen.tolist() == [45, 25, 0]
    st = eng.teda_state()
    assert np.asarray(st.k).tolist() == [45, 25, 0]


# ------------------------------------------------------ functional core
def test_engine_step_matches_process():
    """The T=1 fast path agrees with chunked processing."""
    c = 3
    x = _x(30, c, seed=41)
    es = engine_init(c)
    flags = []
    for row in x:
        es, out = engine_step(es, jnp.asarray(row), 3.0)
        flags.append(np.asarray(out.outlier))
    eng = StreamEngine(c, "scan")
    ref = eng.process(x)
    np.testing.assert_array_equal(np.stack(flags), np.asarray(ref["outlier"]))
    np.testing.assert_allclose(np.asarray(es.var),
                               np.asarray(eng.state.var), rtol=1e-5)


def test_unknown_backend_raises():
    with pytest.raises(KeyError):
        StreamEngine(4, "fpga")


def test_pallas_q_requires_fmt():
    with pytest.raises(ValueError):
        StreamEngine(4, "pallas-q")


@pytest.mark.parametrize("backend", list_backends())
def test_sharded_fanout_single_device(backend):
    """mesh fan-out == plain processing (1-device mesh; the multi-device
    path is exercised by tests/test_distributed.py's forked runner)."""
    import jax
    mesh = jax.make_mesh((1,), ("data",), (jax.sharding.AxisType.Auto,))
    x = _x(48, 4, seed=51)
    plain = _mk(4, backend)
    sharded = _mk(4, backend, mesh=mesh)
    o1, o2 = plain.process(x), sharded.process(x)
    np.testing.assert_array_equal(np.asarray(o1["outlier"]),
                                  np.asarray(o2["outlier"]))


def test_fanout_capacity_divisibility():
    import jax
    mesh = jax.make_mesh((1,), ("data",), (jax.sharding.AxisType.Auto,))
    StreamEngine(4, "scan", mesh=mesh)  # divisible: fine
