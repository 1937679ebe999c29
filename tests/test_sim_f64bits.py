"""`repro.sim.f64bits`: IEEE-754 f64 arithmetic on int64 bit patterns must
equal numpy's float64 arithmetic bit for bit on the engine's domain
(finite values >= 0), including the cases where rounding is delicate."""
import jax
import numpy as np
import pytest

from repro.sim import f64bits as fb


def _values(seed: int, n: int = 2000) -> np.ndarray:
    rng = np.random.default_rng(seed)
    ints = rng.integers(0, 2 ** 31, n).astype(np.float64)
    return np.concatenate([
        ints,
        rng.random(n) * 30,                                # small fractions
        rng.integers(0, 2 ** 20, n) + rng.random(n),       # cycle + fraction
        np.exp(rng.uniform(-40, 40, n)),                   # wide exponents
        25.2 * rng.integers(0, 64, n),                     # mrf multiples
        rng.integers(0, 2 ** 20, n) + 25.2,                # cycle + latency
        80 / 21 * rng.integers(0, 64, n),                  # token refills
        np.nextafter(ints, np.inf), np.nextafter(ints, 0),  # ulp neighbours
        rng.integers(0, 17, n).astype(np.float64), np.zeros(n),
        np.full(n, 5e-324), np.full(n, 2.0 ** -1022),      # subnormal edge
    ])


@pytest.fixture(scope="module")
def x64():
    with jax.enable_x64(True):
        yield


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("op", ["add", "sub", "sub_near"])
def test_add_sub_match_numpy(x64, op, seed):
    rng = np.random.default_rng(100 + seed)
    a = _values(seed)
    b = a[rng.permutation(len(a))]
    if op == "add":
        got, want = jax.jit(fb.add)(fb.bits(a), fb.bits(b)), a + b
    else:
        if op == "sub_near":     # cancellation: operands one ulp to 1e-9 apart
            b = np.where(rng.random(len(a)) < 0.5, np.nextafter(a, 0),
                         a * (1 - 1e-9))
        hi, lo = np.maximum(a, b), np.minimum(a, b)
        got, want = jax.jit(fb.sub)(fb.bits(hi), fb.bits(lo)), hi - lo
    np.testing.assert_array_equal(np.asarray(got), fb.bits(want))


def test_ties_round_to_even(x64):
    # 2**53 + 1 and 2**53 + 3 are exact halfway cases in f64
    a = np.array([2.0 ** 53, 2.0 ** 53, 1.0, 2.0 ** 52])
    b = np.array([1.0, 3.0, 2.0 ** -53, 0.5])
    got = jax.jit(fb.add)(fb.bits(a), fb.bits(b))
    np.testing.assert_array_equal(np.asarray(got), fb.bits(a + b))


def test_from_int_and_floor_match_numpy(x64):
    rng = np.random.default_rng(7)
    n = np.concatenate([rng.integers(0, 2 ** 53, 5000),
                        np.arange(5000)]).astype(np.int64)
    np.testing.assert_array_equal(np.asarray(jax.jit(fb.from_int)(n)),
                                  fb.bits(n.astype(np.float64)))
    x = _values(3)
    np.testing.assert_array_equal(np.asarray(jax.jit(fb.floor)(fb.bits(x))),
                                  np.floor(x).astype(np.int64))


def test_bit_patterns_order_like_values():
    a = _values(4)
    b = a[np.random.default_rng(5).permutation(len(a))]
    assert ((fb.bits(a) < fb.bits(b)) == (a < b)).all()
    assert (fb.bits(a) < fb.INF).all()
