"""Core TEDA correctness: Algorithm 1 fidelity + form equivalences."""
import numpy as np
import jax
import jax.numpy as jnp

from helpers import given_or_cases

from repro.core import (teda_init, teda_step, teda_stream, teda_scan,
                        teda_threshold)
from repro.core.teda import teda_numpy_loop


def _stream(T, N, seed=0, spike=None):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(T, N)).astype(np.float32)
    if spike is not None:
        lo, hi, amp = spike
        x[lo:hi] += amp
    return x


# ---------------------------------------------------------------- fidelity
def test_first_sample_branch():
    """Algorithm 1 lines 3..5: k=1 sets mu<-x, var<-0, no outlier."""
    st0 = teda_init((), 3)
    x1 = jnp.asarray([1.0, -2.0, 5.0])
    st1, out = teda_step(st0, x1)
    np.testing.assert_allclose(st1.mean, x1)
    assert float(st1.var) == 0.0
    assert float(st1.k) == 1.0
    assert not bool(out.outlier)


def test_recursions_match_closed_form():
    """eq (2) mean equals the batch mean; eq (5)-(6) algebra."""
    x = _stream(64, 4, seed=3)
    state, out = teda_stream(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(state.mean), x.mean(0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out.zeta), np.asarray(out.ecc) / 2)
    k = np.arange(1, 65)
    np.testing.assert_allclose(np.asarray(out.threshold),
                               (3.0 ** 2 + 1) / (2 * k), rtol=1e-6)


def test_stream_matches_python_loop():
    x = _stream(500, 2, seed=1, spike=(200, 215, 7.0))
    ref = teda_numpy_loop(x, 3.0)
    _, out = teda_stream(jnp.asarray(x), 3.0)
    np.testing.assert_allclose(np.asarray(out.ecc), ref["ecc"], rtol=2e-4,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out.outlier), ref["outlier"])
    assert ref["outlier"][200:215].sum() > 0  # the fault is detected


def test_scan_equals_stream():
    """Beyond-paper parallel form == paper-faithful sequential form."""
    x = _stream(333, 5, seed=2, spike=(100, 120, 5.0))
    _, seq = teda_stream(jnp.asarray(x), 2.5)
    _, par = teda_scan(jnp.asarray(x), 2.5)
    np.testing.assert_allclose(np.asarray(par.ecc), np.asarray(seq.ecc),
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(par.outlier),
                                  np.asarray(seq.outlier))


def test_state_continuation():
    """Scanning two halves with carried state == scanning the whole."""
    x = _stream(256, 3, seed=4)
    xj = jnp.asarray(x)
    full_state, full = teda_stream(xj)
    st1, _ = teda_stream(xj[:100])
    st2, second = teda_stream(xj[100:], state=st1)
    np.testing.assert_allclose(np.asarray(st2.mean),
                               np.asarray(full_state.mean), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(st2.var),
                               np.asarray(full_state.var), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(second.ecc),
                               np.asarray(full.ecc)[100:], rtol=1e-4,
                               atol=1e-6)


def test_scan_continuation():
    x = _stream(200, 2, seed=5)
    xj = jnp.asarray(x)
    st1, out1 = teda_scan(xj[:77])
    st2, out2 = teda_scan(xj[77:], state=st1)
    _, full = teda_scan(xj)
    np.testing.assert_allclose(np.asarray(out2.ecc),
                               np.asarray(full.ecc)[77:], rtol=1e-4,
                               atol=1e-6)


def test_batched_streams_are_independent():
    """Leading batch dims = independent streams (vmap semantics)."""
    xa = _stream(128, 2, seed=6)
    xb = _stream(128, 2, seed=7, spike=(50, 60, 9.0))
    both = jnp.stack([xa, xb], axis=1)  # (T, 2, N)
    _, out = teda_stream(both)
    _, oa = teda_stream(jnp.asarray(xa))
    _, ob = teda_stream(jnp.asarray(xb))
    np.testing.assert_allclose(np.asarray(out.ecc)[:, 0],
                               np.asarray(oa.ecc), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out.ecc)[:, 1],
                               np.asarray(ob.ecc), rtol=1e-6)


def test_constant_stream_never_outlier():
    """Zero variance: eq (1) guard; ecc = 1/k, never above threshold."""
    x = jnp.ones((50, 2))
    _, out = teda_stream(x, 3.0)
    assert not bool(jnp.any(out.outlier))
    np.testing.assert_allclose(np.asarray(out.ecc),
                               1.0 / np.arange(1, 51), rtol=1e-6)


def test_m_controls_sensitivity():
    x = _stream(400, 1, seed=8, spike=(300, 310, 4.0))
    _, loose = teda_stream(jnp.asarray(x), m=5.0)
    _, tight = teda_stream(jnp.asarray(x), m=1.0)
    assert int(tight.outlier.sum()) >= int(loose.outlier.sum())


def test_jit_and_grad_safety():
    """teda_scan must be jittable and differentiable (guard integration)."""
    x = jnp.asarray(_stream(64, 2, seed=9))
    f = jax.jit(lambda v: teda_scan(v)[1].ecc.sum())
    g = jax.grad(f)(x)
    assert jnp.all(jnp.isfinite(g))


# ------------------------------------------------------------- properties
@given_or_cases(
    "t,n,seed,m",
    [(2, 1, 0, 0.5), (37, 3, 123, 3.0), (111, 2, 999, 1.5),
     (200, 6, 7, 6.0), (64, 4, 2 ** 16, 2.0)],
    lambda st: dict(t=st.integers(2, 200), n=st.integers(1, 6),
                    seed=st.integers(0, 2 ** 16), m=st.floats(0.5, 6.0)),
    max_examples=25)
def test_property_equivalence_and_invariants(t, n, seed, m):
    x = _stream(t, n, seed=seed)
    ref = teda_numpy_loop(x, m)
    _, seq = teda_stream(jnp.asarray(x), m)
    _, par = teda_scan(jnp.asarray(x), m)
    # invariant: zeta sums telescoping — sum of ecc over k samples == k * E
    # (eq 5 normalization: mean of zeta over any prefix is 1/2... checked
    # via the loop oracle instead: forms agree and verdicts identical)
    np.testing.assert_allclose(np.asarray(seq.ecc), ref["ecc"], rtol=5e-3,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(par.ecc), ref["ecc"], rtol=5e-3,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(seq.outlier), ref["outlier"])
    # typicality complement, eq (4)
    np.testing.assert_allclose(np.asarray(seq.typ),
                               1.0 - np.asarray(seq.ecc), rtol=1e-6)
    # eccentricity positivity and normalization bound (ecc in (0, 2])
    assert np.all(np.asarray(seq.ecc) > 0)


@given_or_cases(
    "seed,amp", [(0, 20.0), (123, 45.0), (2 ** 16, 80.0)],
    lambda st: dict(seed=st.integers(0, 2 ** 16),
                    amp=st.floats(20.0, 80.0)),
    max_examples=15)
def test_property_large_spike_always_detected(seed, amp):
    """A >>m-sigma spike after burn-in must trip eq (6) with m=3."""
    x = _stream(300, 2, seed=seed)
    x[250] += amp
    _, out = teda_stream(jnp.asarray(x), 3.0)
    assert bool(out.outlier[250])


def test_threshold_helper():
    np.testing.assert_allclose(teda_threshold(jnp.asarray(10.0), 3.0), 0.5)


def test_detectability_bound_k_le_m_squared():
    """zeta <= (k+1)/(2k) (eq 3 absorbs the sample), so eq (6) with m
    cannot trip at k <= m^2, the detectability bound. Verified with an
    extreme spike at every early position."""
    for spike_at in range(1, 9):  # k = spike_at + 1 <= 9 = m^2
        x = np.ones((10, 1), np.float32) * 5.0
        x[spike_at] = 1e6
        _, out = teda_stream(jnp.asarray(x[:spike_at + 1]), m=3.0)
        assert not bool(out.outlier[spike_at]), spike_at
    # but at k = 10 > m^2 the same spike trips
    x = np.ones((11, 1), np.float32) * 5.0
    x[:10] += 0.01 * np.random.default_rng(0).normal(size=(10, 1))
    x[10] = 1e6
    _, out = teda_stream(jnp.asarray(x), m=3.0)
    assert bool(out.outlier[10])
