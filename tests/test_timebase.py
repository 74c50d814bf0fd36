"""Time words (``engine.timebase``) equal numpy float64, bit for bit.

Seeded operand pairs at the traffic's magnitudes (Table I's 0.388 ns up
to 2**25 ns clocks, latencies, whole nanoseconds) and adversarial cases:
round-half-even ties, carries into a new binade, cancellation, 0, the
engine's finite ``INF`` and ``+inf``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import timebase as tb

N_PAIRS = 1 << 20
LATENCIES = np.asarray([0.388, 0.785, 1.173, 12.0, 20.0, 42.5, 50.0, 60.0,
                        85.0, 92.5, 100.0, 150.0, 200.0])


def _times(rng, n):
    """Log-uniform over [0.388, 2**25] ns, with a share of Table I
    latencies and of whole nanoseconds."""
    x = np.exp(rng.uniform(np.log(0.388), np.log(2.0 ** 25), n))
    pick = rng.integers(0, 4, n)
    x = np.where(pick == 0, LATENCIES[rng.integers(0, len(LATENCIES), n)],
                 x)
    return np.where(pick == 1, np.round(x), x)


def _adversarial():
    ulp = 2.0 ** -52
    return np.asarray([
        0.0, 1.0, 2.0, 3.0, 0.5, 1.0 + ulp, 1.0 + 2 * ulp, 1.0 + 3 * ulp,
        2.0 - ulp, 2.0 - 2 * ulp, 2.0 ** 53, 2.0 ** 53 + 2, 2.0 ** 53 - 1,
        2.0 ** 25, 2.0 ** 25 - 2.0 ** -27, 0.388, 0.785, 0.388 + 0.785,
        1e30, 5e29, 9e29, np.inf, 2.0 ** -52, 2.0 ** -1074,
        1.5 * 2.0 ** -1022])


@pytest.fixture(scope="module")
def pairs():
    rng = np.random.default_rng(20260418)
    return _times(rng, N_PAIRS), _times(rng, N_PAIRS)


def _bits(x):
    return np.asarray(x, np.float64).view(np.int64)


def _run(fn, *args):
    with jax.enable_x64(True):
        return np.asarray(jax.jit(fn)(*(jnp.asarray(a) for a in args)))


def test_add_equals_float64(pairs):
    a, b = pairs
    got = _run(tb.add, tb.from_host(a), tb.from_host(b))
    np.testing.assert_array_equal(got, _bits(a + b))


def test_sub_equals_float64(pairs):
    a, b = pairs
    hi, lo = np.maximum(a, b), np.minimum(a, b)
    got = _run(tb.sub, tb.from_host(hi), tb.from_host(lo))
    np.testing.assert_array_equal(got, _bits(hi - lo))


def test_negative_differences_equal_float64(pairs):
    """Either order: a negative difference carries the sign bit, reads
    back through ``to_f64`` and sorts below every time."""
    a, b = pairs
    got = _run(tb.sub, tb.from_host(a), tb.from_host(b))
    np.testing.assert_array_equal(got, _bits(a - b))
    np.testing.assert_array_equal(_run(tb.to_f64, got), a - b)
    assert np.all((got < 0) == (a < b))


def test_monus_equals_float64(pairs):
    a, b = pairs
    got = _run(tb.monus, tb.from_host(a), tb.from_host(b))
    np.testing.assert_array_equal(got, _bits(np.maximum(a - b, 0.0)))


def test_mul_equals_float64(pairs):
    a, _ = pairs
    k = np.random.default_rng(7).integers(0, 512, a.size).astype(np.float64)
    got = _run(tb.mul, tb.from_host(a), k)
    np.testing.assert_array_equal(got, _bits(a * k))


@pytest.mark.parametrize("name, ref", [
    ("lt", np.less), ("le", np.less_equal), ("gt", np.greater),
    ("ge", np.greater_equal), ("maximum", np.maximum),
    ("minimum", np.minimum)])
def test_order_equals_float64(pairs, name, ref):
    a, b = pairs
    # exact ties too: every 8th pair compares a value with itself
    b = b.copy()
    b[::8] = a[::8]
    got = _run(getattr(tb, name), tb.from_host(a), tb.from_host(b))
    want = ref(a, b)
    if want.dtype == np.float64:
        want = _bits(want)
    np.testing.assert_array_equal(got, want)


def test_reductions_equal_float64(pairs):
    a, _ = pairs
    rows = a[:1 << 16].reshape(-1, 16)
    w = tb.from_host(rows)
    np.testing.assert_array_equal(_run(lambda x: tb.argmin(x, axis=1), w),
                                  np.argmin(rows, axis=1))
    np.testing.assert_array_equal(_run(lambda x: tb.max(x, axis=1), w),
                                  _bits(np.max(rows, axis=1)))
    np.testing.assert_array_equal(_run(lambda x: tb.min(x, axis=1), w),
                                  _bits(np.min(rows, axis=1)))
    np.testing.assert_array_equal(_run(tb.argsort, w[0]),
                                  np.argsort(rows[0], kind="stable"))
    np.testing.assert_array_equal(_run(tb.cummax, w[0]),
                                  _bits(np.maximum.accumulate(rows[0])))


def test_adversarial_add_and_sub():
    v = _adversarial()
    x, y = (m.ravel() for m in np.meshgrid(v, v))
    with np.errstate(invalid="ignore", over="ignore"):
        np.testing.assert_array_equal(
            _run(tb.add, tb.from_host(x), tb.from_host(y)), _bits(x + y))
        ok = np.isfinite(x) & np.isfinite(y)
        np.testing.assert_array_equal(
            _run(tb.sub, tb.from_host(x[ok]), tb.from_host(y[ok])),
            _bits(x[ok] - y[ok]))


@pytest.mark.parametrize("a, b", [
    (2.0 ** 53, 1.0),                  # tie, rounds down to even
    (2.0 ** 53 + 2, 1.0),              # tie, rounds up to even
    (2.0 ** 53, 3.0),                  # tie in the next binade
    (1.0, 2.0 ** -53),                 # tie below one ulp
    (1.0 + 2.0 ** -52, 2.0 ** -53),    # tie, odd significand rounds up
    (2.0 - 2.0 ** -52, 2.0 ** -52),    # carry into a new binade
    (1e30, 0.388),                     # the finite INF absorbs latencies
    (np.inf, 1e30),                    # +inf absorbs
    (0.0, 0.0),
])
def test_round_half_even_ties(a, b):
    got = _run(tb.add, tb.from_host(a), tb.from_host(b))
    assert got == _bits(np.float64(a) + np.float64(b))


@pytest.mark.parametrize("a, b", [
    (2.0 ** 53 + 2, 1.0),              # tie below the top
    (1.0, 2.0 ** -54),                 # sticky below the guard bits
    (2.0, 2.0 ** -53),                 # borrow out of the binade
    (1.0 + 2.0 ** -52, 1.0),           # full cancellation to one ulp
    (0.785, 0.785),                    # exact zero
])
def test_sub_ties_and_cancellation(a, b):
    got = _run(tb.sub, tb.from_host(a), tb.from_host(b))
    assert got == _bits(np.float64(a) - np.float64(b))


def test_conversions_are_exact():
    rng = np.random.default_rng(3)
    g = np.concatenate([rng.uniform(0.0, 3000.0, 4096),
                        [0.0, 1e-40, 3e-45, 1.17e-38, 3.4e38, -2.5]]
                       ).astype(np.float32)
    np.testing.assert_array_equal(_run(tb.from_f32, g),
                                  tb.from_host(g.astype(np.float64)))
    x = np.concatenate([_times(rng, 4096), [0.0, 1e30, 2.0 ** -100]])
    np.testing.assert_array_equal(_run(tb.to_f64, tb.from_host(x)), x)
    np.testing.assert_array_equal(tb.to_host(tb.from_host(x)), x)


def test_order_of_words_is_order_of_times():
    v = np.sort(_adversarial()[np.isfinite(_adversarial())])
    w = tb.from_host(v)
    assert np.all(np.diff(w) >= 0)
    assert tb.NEG < w.min() and tb.INF == tb.bits(1e30)


def test_each_operation_ticks_the_counter():
    with jax.enable_x64(True):
        def f(a, b):
            n0 = tb.op_count()
            tb.lt(tb.add(a, b), tb.maximum(tb.sub(a, b), b))
            return jnp.asarray(tb.op_count() - n0)
        got = int(jax.jit(f)(jnp.asarray(tb.bits(3.0)),
                             jnp.asarray(tb.bits(1.0))))
    assert got == 4
