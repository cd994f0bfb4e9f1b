"""Integer Pallas Q-TEDA kernel: bit-exactness vs the pure-JAX scan."""
import numpy as np
import jax.numpy as jnp
import pytest

from helpers import given_or_cases

from repro.fixedpoint import QFormat, teda_q_scan_chan
from repro.kernels.ops import teda_q_scan_tpu, teda_scan_tpu

FMT = QFormat(32, 20)


def _x(t, c, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(t, c)).astype(np.float32)


def _assert_bit_exact(x, fmt=FMT, m=3.0, block_t=64):
    fin_k, out_k = teda_q_scan_tpu(jnp.asarray(x), fmt, m,
                                   block_t=block_t)
    fin_s, out_s = teda_q_scan_chan(jnp.asarray(x), fmt, m)
    for key in ("mean", "var", "ecc", "outlier"):
        np.testing.assert_array_equal(np.asarray(out_k[key]),
                                      np.asarray(out_s[key]), err_msg=key)
    np.testing.assert_array_equal(np.asarray(fin_k.mean[:, 0]),
                                  np.asarray(fin_s[1]))
    np.testing.assert_array_equal(np.asarray(fin_k.var),
                                  np.asarray(fin_s[2]))
    return out_k


@pytest.mark.parametrize("t,c", [(64, 1), (100, 3), (256, 5)])
def test_kernel_bit_exact_shapes(t, c):
    _assert_bit_exact(_x(t, c, seed=t + c))


@pytest.mark.parametrize("fmt", [QFormat(16, 10), QFormat(24, 16),
                                 QFormat(32, 20, "round")])
def test_kernel_bit_exact_formats(fmt):
    _assert_bit_exact(_x(128, 2, seed=11), fmt=fmt)


@pytest.mark.parametrize("block_t", [8, 32, 128])
def test_chunking_does_not_change_bits(block_t):
    """Quantized arithmetic is order-sensitive: the chunked kernel must
    preserve the exact sequential order across chunk boundaries."""
    x = _x(160, 2, seed=12)
    out_ref = _assert_bit_exact(x, block_t=64)
    _, out = teda_q_scan_tpu(jnp.asarray(x), FMT, 3.0, block_t=block_t)
    np.testing.assert_array_equal(np.asarray(out["ecc"]),
                                  np.asarray(out_ref["ecc"]))
    np.testing.assert_array_equal(np.asarray(out["outlier"]),
                                  np.asarray(out_ref["outlier"]))


def test_time_padding_does_not_leak():
    """T not a multiple of block_t: padded tail rows must not alter
    outputs or the final state (read from the last valid row)."""
    x = _x(70, 2, seed=13)
    fin_a, out_a = teda_q_scan_tpu(jnp.asarray(x), FMT, block_t=64)
    fin_b, out_b = teda_q_scan_tpu(jnp.asarray(x), FMT, block_t=8)
    np.testing.assert_array_equal(np.asarray(out_a["ecc"]),
                                  np.asarray(out_b["ecc"]))
    np.testing.assert_array_equal(np.asarray(fin_a.var),
                                  np.asarray(fin_b.var))
    assert int(fin_a.k[0]) == 70


def test_state_carry_across_calls_bit_exact():
    x = _x(192, 3, seed=14)
    _, full = teda_q_scan_tpu(jnp.asarray(x), FMT, block_t=32)
    st1, _ = teda_q_scan_tpu(jnp.asarray(x[:96]), FMT, block_t=32)
    st2, out2 = teda_q_scan_tpu(jnp.asarray(x[96:]), FMT, state=st1,
                                block_t=32)
    np.testing.assert_array_equal(np.asarray(out2["ecc"]),
                                  np.asarray(full["ecc"])[96:])
    assert int(st2.k[0]) == 192


def test_spike_detection_per_channel():
    x = _x(300, 4, seed=15)
    x[250:255, 2] += 25.0
    out = _assert_bit_exact(x)
    flags = np.asarray(out["outlier"])
    assert flags[250:255, 2].any()


def test_quantized_verdicts_agree_with_float_kernel():
    """Acceptance: WL=32 Q kernel agrees >= 99% with the float kernel."""
    x = _x(512, 4, seed=16)
    x[400:405, 1] += 12.0
    _, out_q = teda_q_scan_tpu(jnp.asarray(x), FMT, 3.0, block_t=64)
    _, out_f = teda_scan_tpu(jnp.asarray(x), 3.0, block_t=64)
    agree = (np.asarray(out_q["outlier"])
             == np.asarray(out_f["outlier"])).mean()
    assert agree >= 0.99


def test_wrapper_composes_under_jit():
    """teda_q_scan_tpu must stay traceable — carried state (k0) is not
    concretized on the host, matching the float wrapper's contract."""
    import jax
    x = _x(64, 2, seed=18)
    st1, _ = teda_q_scan_tpu(jnp.asarray(x[:32]), FMT, block_t=32)
    f = jax.jit(lambda v, s: teda_q_scan_tpu(
        v, FMT, 3.0, state=s, block_t=32, interpret=True)[1]["ecc"])
    ecc = f(jnp.asarray(x[32:]), st1)
    _, full = teda_q_scan_tpu(jnp.asarray(x), FMT, block_t=32)
    np.testing.assert_array_equal(np.asarray(ecc),
                                  np.asarray(full["ecc"])[32:])


def test_pre_quantized_int_input_passthrough():
    """int32 input must be treated as already-quantized Q values."""
    x = _x(96, 2, seed=17)
    xq = FMT.quantize(jnp.asarray(x))
    _, out_a = teda_q_scan_tpu(xq, FMT, block_t=32)
    _, out_b = teda_q_scan_tpu(jnp.asarray(x), FMT, block_t=32)
    np.testing.assert_array_equal(np.asarray(out_a["ecc"]),
                                  np.asarray(out_b["ecc"]))


# -------------------------------------------- ragged per-channel vlen
def _assert_ragged_bit_exact(x, lens, fmt=FMT, m=3.0, block_t=8):
    """One ragged kernel call; asserts the no-flags-beyond-vlen rule."""
    t, c = x.shape
    fin, out = teda_q_scan_tpu(jnp.asarray(x), fmt, m,
                               valid_lens=np.asarray(lens, np.int32),
                               block_t=block_t)
    flags = np.asarray(out["outlier"])
    assert not flags[np.arange(t)[:, None] >= np.asarray(lens)[None, :]
                     ].any()
    return fin, out


@given_or_cases(
    "t,c,seed,block_t",
    [(24, 3, 0, 8), (64, 4, 1, 32), (100, 2, 2, 8), (40, 5, 3, 8)],
    lambda st: dict(t=st.integers(2, 128), c=st.integers(1, 6),
                    seed=st.integers(0, 2 ** 16),
                    block_t=st.sampled_from([8, 32])),
    max_examples=10)
def test_vlen_vector_matches_chan_oracle(t, c, seed, block_t):
    """Per-channel vlen vector vs `teda_q_scan_chan` on each prefix:
    exact bits for outputs AND final state, incl. vlen 0 / T / rest."""
    rng = np.random.default_rng(seed)
    x = _x(t, c, seed=seed)
    lens = rng.integers(0, t + 1, size=c).astype(np.int32)
    lens[rng.integers(0, c)] = 0
    lens[rng.integers(0, c)] = t
    fin, out = _assert_ragged_bit_exact(x, lens, block_t=block_t)
    np.testing.assert_array_equal(np.asarray(fin.k), lens)
    for ch in range(c):
        n = int(lens[ch])
        if n == 0:
            assert int(np.asarray(fin.mean)[ch, 0]) == 0
            assert int(np.asarray(fin.var)[ch]) == 0
            continue
        f, o = teda_q_scan_chan(jnp.asarray(x[:n, ch:ch + 1]), FMT, 3.0)
        np.testing.assert_array_equal(np.asarray(out["ecc"])[:n, ch],
                                      np.asarray(o["ecc"])[:, 0],
                                      err_msg=f"ch{ch}")
        np.testing.assert_array_equal(
            np.asarray(out["outlier"])[:n, ch],
            np.asarray(o["outlier"])[:, 0], err_msg=f"ch{ch}")
        np.testing.assert_array_equal(np.asarray(fin.mean)[ch, 0],
                                      np.asarray(f[1])[0])
        np.testing.assert_array_equal(np.asarray(fin.var)[ch],
                                      np.asarray(f[2])[0])


def test_vlen_degenerate_vectors_match_scalar_bits():
    """All-T vlen == the default scalar path bit-for-bit (one program,
    broadcast input); all-zeros leaves the carried state untouched."""
    x = _x(70, 3, seed=21)
    fin_a, out_a = teda_q_scan_tpu(jnp.asarray(x), FMT, block_t=8)
    fin_b, out_b = teda_q_scan_tpu(jnp.asarray(x), FMT, block_t=8,
                                   valid_lens=np.full((3,), 70, np.int32))
    for key in ("mean", "var", "ecc", "outlier"):
        np.testing.assert_array_equal(np.asarray(out_a[key]),
                                      np.asarray(out_b[key]), err_msg=key)
    np.testing.assert_array_equal(np.asarray(fin_a.mean),
                                  np.asarray(fin_b.mean))
    np.testing.assert_array_equal(np.asarray(fin_a.var),
                                  np.asarray(fin_b.var))
    # all-zeros: the frozen carries round-trip exactly
    fin_z, out_z = teda_q_scan_tpu(jnp.asarray(x), FMT, state=fin_a,
                                   valid_lens=np.zeros((3,), np.int32),
                                   block_t=8)
    np.testing.assert_array_equal(np.asarray(fin_z.k),
                                  np.asarray(fin_a.k))
    np.testing.assert_array_equal(np.asarray(fin_z.mean),
                                  np.asarray(fin_a.mean))
    np.testing.assert_array_equal(np.asarray(fin_z.var),
                                  np.asarray(fin_a.var))
    assert not np.asarray(out_z["outlier"]).any()


def test_vlen_ragged_state_carry_bit_exact():
    """Ragged call chaining: each channel resumes from its own frozen
    prefix, matching one uninterrupted oracle run bit-for-bit."""
    x = _x(90, 2, seed=22)
    lens1 = np.array([40, 9], np.int32)
    st1, _ = teda_q_scan_tpu(jnp.asarray(x[:48]), FMT, valid_lens=lens1,
                             block_t=8)
    take2 = np.array([50, 81], np.int32)
    x2 = np.zeros((88, 2), np.float32)
    for ch in range(2):
        a, b = int(lens1[ch]), int(lens1[ch] + take2[ch])
        x2[: take2[ch], ch] = x[a:b, ch]
    st2, out2 = teda_q_scan_tpu(jnp.asarray(x2), FMT, state=st1,
                                valid_lens=take2, block_t=8)
    np.testing.assert_array_equal(np.asarray(st2.k), lens1 + take2)
    for ch in range(2):
        f, o = teda_q_scan_chan(jnp.asarray(x[:90, ch:ch + 1]), FMT, 3.0)
        np.testing.assert_array_equal(
            np.asarray(out2["ecc"])[: take2[ch], ch],
            np.asarray(o["ecc"])[lens1[ch]:, 0], err_msg=f"ch{ch}")
        np.testing.assert_array_equal(np.asarray(st2.mean)[ch, 0],
                                      np.asarray(f[1])[0])
        np.testing.assert_array_equal(np.asarray(st2.var)[ch],
                                      np.asarray(f[2])[0])
