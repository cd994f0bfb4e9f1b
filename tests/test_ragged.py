"""Ragged-stream conformance suite (ISSUE 4 acceptance).

One fused engine call may retire a different number of samples per
slot (`process(x, valid_lens=...)`, 0..T per slot).  The contract under
test, for every backend in the registry:

  * interleaved ragged calls are bit-exact (Q path) / fp32-tolerant
    (float paths) with running each slot's stream alone on a fresh
    single-slot engine — including vlen = 0 (full suspend), vlen = T
    (full chunk) and awkward remainders in one call;
  * no slot ever flags at rows >= its valid length;
  * attach / detach / reset mid-stream compose with raggedness without
    touching neighbours;
  * the degenerate vectors match the uniform path: all-T equals a plain
    `process(x)` call bit-for-bit, all-0 advances nothing.

The hypothesis-driven cases run a trimmed width by default;
`-m slow` (main-branch CI) runs the full-width sweep.
"""
import numpy as np
import pytest

from conftest import given_or_cases

from repro.engine import StreamEngine, list_backends
from repro.fixedpoint import QFormat

FMT = QFormat(32, 20)


def _mk(c, backend, **kw):
    kw.setdefault("block_t", 8)
    return StreamEngine(c, backend, fmt=FMT, **kw)


def _ragged_lens(rng, c, t):
    """Per-slot lengths covering the edges: a forced 0, a forced T, and
    arbitrary remainders everywhere else."""
    lens = rng.integers(0, t + 1, size=c).astype(np.int32)
    lens[rng.integers(0, c)] = 0
    lens[rng.integers(0, c)] = t
    return lens


def _ragged_calls(eng, rng, c, t, n_calls, spike_every=3):
    """Drive `eng` through ragged calls; returns (per-slot streams,
    per-slot collected verdict prefixes)."""
    streams = [[] for _ in range(c)]
    got = {"ecc": [[] for _ in range(c)], "outlier": [[] for _ in range(c)]}
    for call in range(n_calls):
        lens = _ragged_lens(rng, c, t)
        x = np.zeros((t, c), np.float32)
        for s in range(c):
            xs = rng.normal(size=int(lens[s])).astype(np.float32)
            if xs.size and (call + s) % spike_every == 0:
                xs[xs.size // 2] += 25.0  # make someone flag
            x[: lens[s], s] = xs
            streams[s].append(xs)
        out = eng.process(x, valid_lens=lens)
        ol = np.asarray(out["outlier"])
        ecc = np.asarray(out["ecc"])
        # the ragged-tail guarantee: no verdicts beyond a slot's length
        assert not ol[np.arange(t)[:, None] >= lens[None, :]].any()
        for s in range(c):
            got["ecc"][s].append(ecc[: lens[s], s])
            got["outlier"][s].append(ol[: lens[s], s])
    return streams, got


def _assert_slot_matches_isolated(backend, full, got_ecc, got_out,
                                  m=3.0, err=""):
    """One slot's interleaved verdicts vs its stream alone on slot 0 of
    a fresh single-slot engine (the isolation oracle)."""
    iso = _mk(1, backend, m=m)
    ref = iso.process(full[:, None])
    np.testing.assert_array_equal(
        got_out, np.asarray(ref["outlier"])[:, 0], err_msg=err)
    if backend == "pallas-q":  # quantized datapath: exact bits
        np.testing.assert_array_equal(
            got_ecc, np.asarray(ref["ecc"])[:, 0], err_msg=err)
        return iso
    np.testing.assert_allclose(got_ecc, np.asarray(ref["ecc"])[:, 0],
                               rtol=1e-4, atol=1e-6, err_msg=err)
    return iso


# ---------------------------------------------- ragged == isolated
@pytest.mark.parametrize("backend", list_backends())
@given_or_cases(
    "c,t,n_calls,seed", [(4, 8, 3, 0), (3, 5, 4, 1), (5, 11, 2, 2),
                         (2, 16, 3, 3)],
    lambda st: dict(c=st.integers(2, 5), t=st.integers(2, 16),
                    n_calls=st.integers(1, 4),
                    seed=st.integers(0, 2 ** 16)),
    max_examples=6)
def test_ragged_equals_isolated(backend, c, t, n_calls, seed):
    rng = np.random.default_rng(seed)
    eng = _mk(c, backend)
    streams, got = _ragged_calls(eng, rng, c, t, n_calls)
    total = 0
    for s in range(c):
        full = np.concatenate(streams[s])
        total += full.size
        assert eng.samples_seen[s] == full.size
        if not full.size:
            continue
        iso = _assert_slot_matches_isolated(
            backend, full, np.concatenate(got["ecc"][s]),
            np.concatenate(got["outlier"][s]), err=f"slot {s}")
        # final carried state agrees with the isolated run too
        if backend == "pallas-q":
            np.testing.assert_array_equal(
                np.asarray(eng.state.mean)[s], np.asarray(iso.state.mean)[0])
            np.testing.assert_array_equal(
                np.asarray(eng.state.var)[s], np.asarray(iso.state.var)[0])
        else:
            np.testing.assert_allclose(
                np.asarray(eng.state.var)[s], np.asarray(iso.state.var)[0],
                rtol=1e-4, atol=1e-6)
    assert int(np.asarray(eng.samples_seen).sum()) == total


@pytest.mark.slow
@pytest.mark.parametrize("backend", list_backends())
@given_or_cases(
    "c,t,n_calls,seed", [(8, 32, 6, 10), (6, 24, 8, 11), (9, 40, 5, 12)],
    lambda st: dict(c=st.integers(2, 9), t=st.integers(2, 48),
                    n_calls=st.integers(1, 8),
                    seed=st.integers(0, 2 ** 16)),
    max_examples=25)
def test_ragged_equals_isolated_full_width(backend, c, t, n_calls, seed):
    """The full-width sweep (main-branch CI): wider slot counts, longer
    chunks, more interleaved calls — same bit-exactness contract."""
    rng = np.random.default_rng(seed)
    eng = _mk(c, backend)
    streams, got = _ragged_calls(eng, rng, c, t, n_calls)
    for s in range(c):
        full = np.concatenate(streams[s])
        if not full.size:
            continue
        _assert_slot_matches_isolated(
            backend, full, np.concatenate(got["ecc"][s]),
            np.concatenate(got["outlier"][s]), err=f"slot {s}")


# ------------------------------------- tenancy churn between ragged calls
@pytest.mark.parametrize("backend", list_backends())
def test_ragged_with_midstream_tenancy_churn(backend):
    """attach / detach / reset between ragged calls: the churned slots
    behave like fresh streams, neighbours stay bit-exact."""
    rng = np.random.default_rng(7)
    c, t = 4, 10
    eng = _mk(c, backend)
    streams = [[] for _ in range(c)]
    got = {s: ([], []) for s in range(c)}  # (ecc parts, outlier parts)

    def ragged_call(lens):
        x = np.zeros((t, c), np.float32)
        for s in range(c):
            xs = rng.normal(size=int(lens[s])).astype(np.float32)
            x[: lens[s], s] = xs
            streams[s].append(xs)
        out = eng.process(x, valid_lens=np.asarray(lens, np.int32))
        for s in range(c):
            got[s][0].append(np.asarray(out["ecc"])[: lens[s], s])
            got[s][1].append(np.asarray(out["outlier"])[: lens[s], s])

    ragged_call([3, 10, 0, 7])
    # slot 1: new tenant mid-flight (detach + attach drops its history)
    eng.detach([1])
    eng.attach([1])
    streams[1], got[1] = [], ([], [])
    # slot 3: mid-flight reset (recycle in place)
    eng.reset([3])
    streams[3], got[3] = [], ([], [])
    ragged_call([5, 4, 10, 0])
    ragged_call([0, 10, 2, 6])

    for s in range(c):
        full = np.concatenate(streams[s]) if streams[s] else \
            np.zeros((0,), np.float32)
        assert eng.samples_seen[s] == full.size
        if full.size:
            _assert_slot_matches_isolated(
                backend, full, np.concatenate(got[s][0]),
                np.concatenate(got[s][1]), err=f"slot {s}")


@pytest.mark.parametrize("backend", list_backends())
def test_ragged_detached_slot_stays_frozen(backend):
    """A detached slot is pinned at vlen 0 even when the caller's
    valid_lens claims data for it."""
    c, t = 3, 6
    eng = _mk(c, backend, auto_attach=False)
    eng.attach([0, 2])
    x = np.random.default_rng(8).normal(size=(t, c)).astype(np.float32)
    x[:, 1] += 50.0  # would flag loudly if slot 1 advanced
    out = eng.process(x, valid_lens=[4, 6, 2])
    assert eng.samples_seen.tolist() == [4, 0, 2]
    assert not np.asarray(out["outlier"])[:, 1].any()


# --------------------------------------------------- degenerate vectors
@pytest.mark.parametrize("backend", list_backends())
def test_all_full_vlen_matches_uniform_call(backend):
    """valid_lens = [T]*C is the uniform path, bit-for-bit (identical
    compiled program — the scalar case is a broadcast, not a branch)."""
    c, t = 3, 20
    x = np.random.default_rng(9).normal(size=(t, c)).astype(np.float32)
    x[t // 2, 0] += 25.0
    plain, ragged = _mk(c, backend), _mk(c, backend)
    out_p = plain.process(x)
    out_r = ragged.process(x, valid_lens=np.full((c,), t, np.int32))
    np.testing.assert_array_equal(np.asarray(out_p["ecc"]),
                                  np.asarray(out_r["ecc"]))
    np.testing.assert_array_equal(np.asarray(out_p["outlier"]),
                                  np.asarray(out_r["outlier"]))
    np.testing.assert_array_equal(np.asarray(plain.state.mean),
                                  np.asarray(ragged.state.mean))
    np.testing.assert_array_equal(np.asarray(plain.state.var),
                                  np.asarray(ragged.state.var))


@pytest.mark.parametrize("backend", list_backends())
def test_all_zero_vlen_advances_nothing(backend):
    """valid_lens = 0 everywhere: a no-op call — state frozen at the
    exact packed values (no float round-trip), zero flags."""
    c, t = 3, 12
    rng = np.random.default_rng(10)
    eng = _mk(c, backend)
    eng.process(rng.normal(size=(t, c)).astype(np.float32))
    before = eng.state
    out = eng.process(rng.normal(size=(t, c)).astype(np.float32) + 100.0,
                      valid_lens=0)
    assert not np.asarray(out["outlier"]).any()
    np.testing.assert_array_equal(np.asarray(before.k),
                                  np.asarray(eng.state.k))
    np.testing.assert_array_equal(np.asarray(before.mean),
                                  np.asarray(eng.state.mean))
    np.testing.assert_array_equal(np.asarray(before.var),
                                  np.asarray(eng.state.var))


def test_valid_lens_validation():
    eng = _mk(3, "scan")
    x = np.zeros((4, 3), np.float32)
    with pytest.raises(ValueError, match=r"\[0, T=4\]"):
        eng.process(x, valid_lens=[1, 5, 0])   # beyond T
    with pytest.raises(ValueError, match=r"\[0, T=4\]"):
        eng.process(x, valid_lens=[-1, 2, 0])  # negative
    with pytest.raises(ValueError, match="scalar or"):
        eng.process(x, valid_lens=[1, 2])      # wrong width


@pytest.mark.parametrize("c,block_t,want", [
    (4096, 8, 0),       # the scheduler's block_t: one strip fits
    (256, 256, 0),      # exactly the tile budget: one strip
    (4096, 256, 256),   # the widest 128-multiple strip within budget
    (1000, 128, 512),   # lanes padded to 1,024 first
    (1152, 128, 384),   # 9 x 128 lanes: the widest strip dividing them
    (4096, 512, 128),   # never narrower than one lane register
])
def test_default_block_c_fits_tile_budget(c, block_t, want):
    from repro.kernels.ragged import TILE_ELEMS, norm_block_c
    bc = norm_block_c(None, block_t, c, 128)
    assert bc == want
    if bc:
        assert (-(-c // 128) * 128) % bc == 0
        assert bc == 128 or block_t * bc <= TILE_ELEMS


def test_explicit_block_c_is_kept_and_validated():
    from repro.kernels.ragged import norm_block_c
    assert norm_block_c(1024, 256, 4096, 128) == 1024
    assert norm_block_c(0, 256, 4096, 128) == 0
    with pytest.raises(ValueError, match="multiple of 128"):
        norm_block_c(100, 256, 4096, 128)


@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False),
                                           ("gpu", None)])
def test_default_interpret_only_on_cpu(monkeypatch, platform, want):
    from repro.kernels import ragged
    monkeypatch.setattr(ragged.jax, "default_backend", lambda: platform)
    if want is None:  # no silent emulation on another accelerator
        with pytest.raises(RuntimeError, match="'gpu'"):
            ragged.default_interpret()
    else:
        assert ragged.default_interpret() is want
