"""Pallas TEDA kernel: shape/dtype sweeps + property tests vs ref.py."""
import numpy as np
import jax.numpy as jnp
import pytest

from helpers import given_or_cases


from repro.kernels.ops import teda_scan_tpu
from repro.kernels.ref import teda_ref


def _x(t, c, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(t, c)).astype(dtype)


def _check(x, m=3.0, block_t=64, state=None, k0=0, sum0=None, var0=None,
           rtol=5e-4):
    ref = teda_ref(np.asarray(x, np.float32), m, k0=k0, sum0=sum0, var0=var0)
    fin, out = teda_scan_tpu(jnp.asarray(x), m, state=state, block_t=block_t)
    np.testing.assert_allclose(np.asarray(out["mean"]), ref["mean"],
                               rtol=rtol, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out["var"]), ref["var"],
                               rtol=rtol, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out["ecc"]), ref["ecc"],
                               rtol=rtol, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(out["outlier"]), ref["outlier"])
    return fin, out


# ----------------------------------------------------------- shape sweeps
@pytest.mark.parametrize("t", [8, 64, 100, 256, 1000])
@pytest.mark.parametrize("c", [1, 3, 128, 200])
def test_shapes(t, c):
    _check(_x(t, c, seed=t * 1000 + c))


@pytest.mark.parametrize("block_t", [8, 32, 64, 256, 512])
def test_block_sizes(block_t):
    """Chunking must not change results (carry correctness)."""
    x = _x(777, 5, seed=11)
    _check(x, block_t=block_t)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16, np.float16])
def test_dtypes(dtype):
    x = _x(256, 4, seed=12).astype(dtype)
    # low-precision inputs are up-cast in-kernel; compare vs f32 ref loosely
    ref = teda_ref(np.asarray(x, np.float32), 3.0)
    _, out = teda_scan_tpu(jnp.asarray(x), 3.0, block_t=64)
    np.testing.assert_allclose(np.asarray(out["ecc"]), ref["ecc"],
                               rtol=2e-2, atol=1e-3)


def test_state_carry_across_calls():
    """Two chunked kernel calls == one call (streaming restart)."""
    x = _x(512, 3, seed=13)
    full_fin, full = teda_scan_tpu(jnp.asarray(x), block_t=64)
    st1, _ = teda_scan_tpu(jnp.asarray(x[:256]), block_t=64)
    st2, out2 = teda_scan_tpu(jnp.asarray(x[256:]), state=st1, block_t=64)
    np.testing.assert_allclose(np.asarray(out2["ecc"]),
                               np.asarray(full["ecc"])[256:], rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(st2.var),
                               np.asarray(full_fin.var), rtol=1e-4)


def test_spike_detection_per_channel():
    x = _x(400, 4, seed=14)
    x[300:305, 2] += 25.0
    _, out = teda_scan_tpu(jnp.asarray(x), 3.0)
    flags = np.asarray(out["outlier"])
    assert flags[300:305, 2].any()
    assert not flags[300:305, [0, 1, 3]].any()


def test_padding_rows_do_not_leak():
    """T not a multiple of block_t: padded rows must not alter outputs."""
    x = _x(70, 2, seed=15)
    fin_a, out_a = teda_scan_tpu(jnp.asarray(x), block_t=64)
    fin_b, out_b = teda_scan_tpu(jnp.asarray(x), block_t=8)
    np.testing.assert_allclose(np.asarray(out_a["ecc"]),
                               np.asarray(out_b["ecc"]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(fin_a.var), np.asarray(fin_b.var),
                               rtol=1e-5)


# ------------------------------------------------------------- properties
@given_or_cases(
    "t,c,seed,m,block_t",
    [(2, 1, 0, 1.0, 8), (77, 3, 123, 3.0, 32), (300, 9, 7, 5.0, 128),
     (129, 2, 999, 2.5, 8)],
    lambda st: dict(t=st.integers(2, 300), c=st.integers(1, 9),
                    seed=st.integers(0, 2 ** 16), m=st.floats(1.0, 5.0),
                    block_t=st.sampled_from([8, 32, 128])),
    max_examples=20)
def test_property_kernel_matches_ref(t, c, seed, m, block_t):
    _check(_x(t, c, seed=seed), m=m, block_t=block_t)


@given_or_cases(
    "seed", [0, 123, 2 ** 16],
    lambda st: dict(seed=st.integers(0, 2 ** 16)),
    max_examples=10)
def test_property_outliers_subset_of_high_zeta(seed):
    """Verdict consistency: outlier ⇒ zeta > threshold (eq 6)."""
    x = _x(200, 3, seed=seed)
    x[150] += 30
    _, out = teda_scan_tpu(jnp.asarray(x), 3.0)
    fl = np.asarray(out["outlier"])
    margin = np.asarray(out["zeta"]) - np.asarray(out["threshold"])
    assert np.all(margin[fl] > 0)


def test_verdict_only_kernel_matches_full():
    from repro.kernels.ops import teda_scan_verdict
    x = _x(512, 5, seed=21)
    x[400:404, 2] += 20.0
    fin_full, full = teda_scan_tpu(jnp.asarray(x), 3.0, block_t=64)
    fin_v, slim = teda_scan_verdict(jnp.asarray(x), 3.0, block_t=64)
    np.testing.assert_allclose(np.asarray(slim["ecc"]),
                               np.asarray(full["ecc"]), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(slim["outlier"]),
                                  np.asarray(full["outlier"]))
    assert fin_v is not None  # 512 % 64 == 0 -> state available
    np.testing.assert_allclose(np.asarray(fin_v.var),
                               np.asarray(fin_full.var), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(fin_v.mean),
                               np.asarray(fin_full.mean), rtol=1e-5)


def test_verdict_only_matches_numpy_oracle():
    """Slim path vs teda_ref: ecc/verdicts/final state, int8 flag dtype.

    Covers the verdict_only=True kernel branch against the independent
    float64 oracle, not just the full-output kernel path.
    """
    from repro.kernels.ops import teda_scan_verdict
    from repro.kernels.teda_scan import teda_pallas_call

    x = _x(256, 3, seed=23)
    x[200:203, 1] += 18.0
    ref = teda_ref(np.asarray(x, np.float32), 3.0)
    fin, slim = teda_scan_verdict(jnp.asarray(x), 3.0, block_t=64)
    np.testing.assert_allclose(np.asarray(slim["ecc"]), ref["ecc"],
                               rtol=5e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(slim["outlier"]),
                                  ref["outlier"])
    # final carried state must equal the oracle's final-row statistics
    assert fin is not None  # 256 % 64 == 0
    np.testing.assert_allclose(np.asarray(fin.mean[:, 0]),
                               ref["mean"][-1], rtol=5e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fin.var), ref["var"][-1],
                               rtol=5e-4, atol=1e-5)
    # the raw kernel emits an int8 flag (the 5B/sample HBM-write claim)
    xp = jnp.asarray(np.pad(x, ((0, 0), (0, 125))))
    scal = jnp.asarray([3.0], jnp.float32)
    vlen = jnp.full((1, 128), float(x.shape[0]), jnp.float32)
    zero = jnp.zeros((1, 128), jnp.float32)
    _, flag8, _, _, _ = teda_pallas_call(xp, scal, vlen, zero, zero, zero,
                                         block_t=64, interpret=True,
                                         verdict_only=True)
    assert flag8.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(flag8[:, :3]).astype(bool),
                                  ref["outlier"])


def test_verdict_only_final_state_when_padded():
    """T % block_t != 0: the kernel masks the padded tail in-kernel, so
    the slim path hands back an exact final state for every T."""
    from repro.kernels.ops import teda_scan_verdict
    x = _x(70, 2, seed=24)
    fin, slim = teda_scan_verdict(jnp.asarray(x), 3.0, block_t=64)
    ref = teda_ref(np.asarray(x, np.float32), 3.0)
    np.testing.assert_array_equal(np.asarray(slim["outlier"]),
                                  ref["outlier"])
    assert fin is not None
    np.testing.assert_allclose(np.asarray(fin.k), 70.0)
    np.testing.assert_allclose(np.asarray(fin.mean[:, 0]), ref["mean"][-1],
                               rtol=5e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fin.var), ref["var"][-1],
                               rtol=5e-4, atol=1e-5)


def test_verdict_only_state_carry():
    from repro.kernels.ops import teda_scan_verdict
    x = _x(256, 3, seed=22)
    st1, _ = teda_scan_verdict(jnp.asarray(x[:128]), block_t=64)
    _, out2 = teda_scan_verdict(jnp.asarray(x[128:]), state=st1,
                                block_t=64)
    _, full = teda_scan_tpu(jnp.asarray(x), block_t=64)
    np.testing.assert_allclose(np.asarray(out2["ecc"]),
                               np.asarray(full["ecc"])[128:], rtol=1e-4)


# -------------------------------------------- ragged per-channel vlen
@given_or_cases(
    "t,c,seed,block_t",
    [(24, 3, 0, 8), (70, 4, 1, 32), (129, 2, 2, 64), (40, 5, 3, 8)],
    lambda st: dict(t=st.integers(2, 200), c=st.integers(1, 6),
                    seed=st.integers(0, 2 ** 16),
                    block_t=st.sampled_from([8, 32, 64])),
    max_examples=15)
def test_vlen_vector_matches_per_channel_ref(t, c, seed, block_t):
    """One ragged call == per-channel isolated prefixes vs teda_ref,
    covering vlen = 0, vlen = T and arbitrary remainders."""
    rng = np.random.default_rng(seed)
    x = _x(t, c, seed=seed)
    lens = rng.integers(0, t + 1, size=c).astype(np.int32)
    lens[rng.integers(0, c)] = 0
    lens[rng.integers(0, c)] = t
    fin, out = teda_scan_tpu(jnp.asarray(x), 3.0, valid_lens=lens,
                             block_t=block_t)
    flags = np.asarray(out["outlier"])
    assert not flags[np.arange(t)[:, None] >= lens[None, :]].any()
    np.testing.assert_array_equal(np.asarray(fin.k), lens)
    for ch in range(c):
        n = int(lens[ch])
        if n == 0:
            assert np.asarray(fin.var)[ch] == 0.0
            continue
        ref = teda_ref(np.asarray(x[:n, ch:ch + 1], np.float32), 3.0)
        np.testing.assert_allclose(np.asarray(out["ecc"])[:n, ch],
                                   ref["ecc"][:, 0], rtol=5e-4,
                                   atol=1e-5, err_msg=f"ch{ch}")
        np.testing.assert_array_equal(flags[:n, ch], ref["outlier"][:, 0],
                                      err_msg=f"ch{ch}")
        np.testing.assert_allclose(np.asarray(fin.mean)[ch, 0],
                                   ref["mean"][-1, 0], rtol=5e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(fin.var)[ch],
                                   ref["var"][-1, 0], rtol=5e-4,
                                   atol=1e-5)


def test_vlen_degenerate_vectors_match_scalar_path():
    """All-T vlen is bit-identical to the default call (same program,
    broadcast input); all-zeros returns the initial state untouched."""
    from repro.kernels.ops import teda_scan_verdict
    x = _x(100, 3, seed=25)
    for fn in (teda_scan_tpu, teda_scan_verdict):
        fin_a, out_a = fn(jnp.asarray(x), 3.0, block_t=32)
        fin_b, out_b = fn(jnp.asarray(x), 3.0, block_t=32,
                          valid_lens=np.full((3,), 100, np.int32))
        for key in out_a:
            np.testing.assert_array_equal(np.asarray(out_a[key]),
                                          np.asarray(out_b[key]), err_msg=key)
        np.testing.assert_array_equal(np.asarray(fin_a.mean),
                                      np.asarray(fin_b.mean))
        np.testing.assert_array_equal(np.asarray(fin_a.var),
                                      np.asarray(fin_b.var))
    fin_z, out_z = teda_scan_tpu(jnp.asarray(x), 3.0, block_t=32,
                                 valid_lens=np.zeros((3,), np.int32))
    assert np.asarray(fin_z.k).tolist() == [0.0] * 3
    assert np.asarray(fin_z.mean).tolist() == [[0.0]] * 3
    assert not np.asarray(out_z["outlier"]).any()


def test_vlen_state_carry_across_ragged_calls():
    """Two ragged calls chain exactly: each channel resumes from its
    own frozen prefix state."""
    x = _x(120, 2, seed=26)
    lens1 = np.array([50, 17], np.int32)
    st1, _ = teda_scan_tpu(jnp.asarray(x[:64]), 3.0, valid_lens=lens1,
                           block_t=32)
    take2 = np.array([30, 41], np.int32)
    x2 = np.zeros((64, 2), np.float32)
    for ch, (a, b) in enumerate(zip(lens1, lens1 + take2)):
        x2[: take2[ch], ch] = x[a:b, ch]
    st2, out2 = teda_scan_tpu(jnp.asarray(x2), 3.0, state=st1,
                              valid_lens=take2, block_t=32)
    np.testing.assert_array_equal(np.asarray(st2.k), lens1 + take2)
    for ch in range(2):
        n = int(lens1[ch] + take2[ch])
        ref = teda_ref(np.asarray(x[:n, ch:ch + 1], np.float32), 3.0)
        np.testing.assert_allclose(
            np.asarray(out2["ecc"])[: take2[ch], ch],
            ref["ecc"][lens1[ch]:, 0], rtol=5e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(st2.var)[ch],
                                   ref["var"][-1, 0], rtol=5e-4, atol=1e-5)


def test_vlen_out_of_range_is_clamped():
    """Traced callers skip the engine's host bounds check, so the
    contract layer must clamp valid_lens to [0, T] — otherwise final k
    disagrees with the state the frozen carries actually hold."""
    from repro.core.scan import teda_scan
    x = _x(30, 2, seed=28)
    bad = np.array([100, -7], np.int32)     # > T and negative
    fin, out = teda_scan_tpu(jnp.asarray(x), 3.0, valid_lens=bad,
                             block_t=8)
    ref_fin, ref_out = teda_scan_tpu(jnp.asarray(x), 3.0,
                                     valid_lens=np.array([30, 0]),
                                     block_t=8)
    np.testing.assert_array_equal(np.asarray(fin.k),
                                  np.asarray(ref_fin.k))
    np.testing.assert_array_equal(np.asarray(fin.var),
                                  np.asarray(ref_fin.var))
    np.testing.assert_array_equal(np.asarray(out["outlier"]),
                                  np.asarray(ref_out["outlier"]))
    # the scan backend agrees (same clamp contract)
    sfin, _ = teda_scan(jnp.asarray(x[..., None]), 3.0, valid_lens=bad)
    np.testing.assert_array_equal(np.asarray(sfin.k), [30.0, 0.0])


def test_vlen_composes_with_per_slot_m():
    """Ragged lengths and per-slot sensitivities in one call: verdicts
    equal each channel's isolated run at its own m."""
    t, c = 60, 3
    x = _x(t, c, seed=27)
    x[10:14] += 12.0
    lens = np.array([60, 23, 0], np.int32)
    ms = np.array([1.5, 3.0, 6.0], np.float32)
    _, out = teda_scan_tpu(jnp.asarray(x), ms, valid_lens=lens, block_t=8)
    flags = np.asarray(out["outlier"])
    assert not flags[np.arange(t)[:, None] >= lens[None, :]].any()
    for ch in range(c):
        n = int(lens[ch])
        if not n:
            continue
        ref = teda_ref(np.asarray(x[:n, ch:ch + 1], np.float32),
                       float(ms[ch]))
        np.testing.assert_array_equal(flags[:n, ch], ref["outlier"][:, 0],
                                      err_msg=f"ch{ch}")
