"""Host-width divider image: bit-equality vs the bit-serial model.

`kernels/qdiv.py` is only allowed to exist because it computes exactly
the function `fixedpoint.qformat._div_mag` models clock-for-clock —
these tests are that license.  Operands cover the full int32 range,
the d == 0 guard, round-half-up ties, and quotient saturation for
several word lengths (including FL = 0, where the fast path is a single
integer divide, and a degenerate FL > INT format).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from helpers import given_or_cases

from repro.fixedpoint.qformat import QFormat, div_qi, div_qq
from repro.kernels.qdiv import fast_div_qi, fast_div_qq

FORMATS = [QFormat(32, 20), QFormat(16, 8), QFormat(24, 12),
           QFormat(32, 0), QFormat(12, 10)]

_EDGES = np.array([0, 1, -1, 2, -2, 3, -3, 7, 255, 2**20, -(2**20),
                   2**30, -(2**30), 2**31 - 1, -(2**31 - 1)], np.int64)


def _edge_grid(fmt):
    """Dense cross of adversarial operands for one format."""
    v = np.unique(np.concatenate([
        _EDGES, [fmt.qmax, -fmt.qmax, fmt.qmin, fmt.one, -fmt.one,
                 fmt.one // 2, fmt.one + 1]])).astype(np.int32)
    n, d = np.meshgrid(v, v)
    return jnp.asarray(n.ravel()), jnp.asarray(d.ravel())


@pytest.mark.parametrize("fmt", FORMATS,
                         ids=lambda f: f"Q{f.word_len}.{f.frac_len}")
def test_edge_grid_bit_equal(fmt):
    n, d = _edge_grid(fmt)
    np.testing.assert_array_equal(np.asarray(div_qq(fmt, n, d)),
                                  np.asarray(fast_div_qq(fmt, n, d)))
    np.testing.assert_array_equal(np.asarray(div_qi(fmt, n, d)),
                                  np.asarray(fast_div_qi(fmt, n, d)))


@pytest.mark.parametrize("fmt", FORMATS,
                         ids=lambda f: f"Q{f.word_len}.{f.frac_len}")
def test_random_sweep_bit_equal(fmt):
    rng = np.random.default_rng(fmt.word_len * 100 + fmt.frac_len)
    n = jnp.asarray(rng.integers(-2**31 + 1, 2**31,
                                 size=50_000).astype(np.int32))
    d = jnp.asarray(rng.integers(-2**31 + 1, 2**31,
                                 size=50_000).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(div_qq(fmt, n, d)),
                                  np.asarray(fast_div_qq(fmt, n, d)))
    np.testing.assert_array_equal(np.asarray(div_qi(fmt, n, d)),
                                  np.asarray(fast_div_qi(fmt, n, d)))


@given_or_cases(
    "num,den",
    [(1, 3), (-(2**31 - 1), 1), (2**31 - 1, -1), (5 << 20, 10 << 20),
     (123456789, -987), (0, 0), (42, 0)],
    lambda st: {"num": st.integers(-2**31 + 1, 2**31 - 1),
                "den": st.integers(-2**31 + 1, 2**31 - 1)},
    max_examples=300)
def test_property_scalar_bit_equal(num, den):
    fmt = QFormat(32, 20)
    n = jnp.asarray([num], jnp.int32)
    d = jnp.asarray([den], jnp.int32)
    assert int(div_qq(fmt, n, d)[0]) == int(fast_div_qq(fmt, n, d)[0])
    assert int(div_qi(fmt, n, d)[0]) == int(fast_div_qi(fmt, n, d)[0])


def test_division_by_one_is_identity():
    """The k=1 folding in the Q kernel rests on x/1 == x exactly."""
    fmt = QFormat(32, 20)
    rng = np.random.default_rng(0)
    x = jnp.asarray(np.concatenate([
        rng.integers(fmt.qmin, fmt.qmax + 1, size=10_000),
        [fmt.qmin, fmt.qmax, 0, 1, -1]]).astype(np.int32))
    one = jnp.ones_like(x)
    np.testing.assert_array_equal(np.asarray(fast_div_qi(fmt, x, one)),
                                  np.asarray(x))
    np.testing.assert_array_equal(np.asarray(div_qi(fmt, x, one)),
                                  np.asarray(x))
