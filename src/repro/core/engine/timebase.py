"""Simulated time: IEEE binary64 values held as their bit patterns.

Every instant and duration the engine decides on (core clocks, LRU
stamps, drain-ack times, resource next-free times, latencies, the crash
instant) is an ``int64`` word holding the bits of a non-negative IEEE
binary64 value.  For non-negative values the integer order of the bits
is the order of the values, so compares, ``max``/``min``, ``argmin``
and sorts are integer operations; add, subtract and the product by a
small count are written here once, with IEEE round-half-even.

Why not ``float64`` arrays: a TPU has no binary64 unit, and XLA emulates
``float64`` there with pairs of ``float32`` (about 48 significand bits),
which rounds otherwise than IEEE.  Latencies such as 0.388 ns are not
dyadic, so every buffered persist rounded differently on the chip, a
tie (a drain that ends at the instant a read issues) broke the other
way, and the chip simulated another trajectory than the CPU and the
plain reference.  Integer words round the same on every backend.

Contract of the arithmetic: operands are non-negative and finite, or
the sentinels below; :func:`mul` takes a count in ``[0, 512)``.  A
difference may be negative (:func:`sub` then sets the sign bit): it is
only converted or compared with a non-negative time.  ``NEG`` sorts below every time and is only
compared, never added.  Statistics (sums of latencies, histograms)
stay ``float64`` and read times through :func:`to_f64`.  Nothing here
bitcasts a float64: a TPU cannot.

Every function that computes on time ticks :func:`op_count` once per
call while it is traced, so the step driver can count the time
operations of one grid step (``spans.Call.time_ops``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DTYPE = jnp.int64            # the time columns' dtype
DTYPE_NAME = "int64"         # ... as the dtype lint spells it

_FRAC = (1 << 52) - 1        # binary64 fraction field
_GUARD = 9                   # extra bits kept below the significand
_HALF = 1 << (_GUARD - 1)
_TOP = 52 + _GUARD           # leading-one position of a normal sum
_EXP_INF = 0x7FF
_SIGN = -(1 << 63)           # the sign bit, as an int64


def bits(x: float) -> int:
    """The bit pattern of one float as a Python int (a traced constant)."""
    return int(np.float64(x).view(np.int64))


INF = bits(1e30)             # the engine's finite infinity
NEG = -1                     # below every time (compares only)
ZERO = 0

_OPS = [0]


def _tick() -> None:
    _OPS[0] += 1


def op_count() -> int:
    """Time operations traced so far in this process."""
    return _OPS[0]


# ------------------------------------------------------------ conversions
def from_host(x) -> np.ndarray:
    """Host float64 values -> time words (exact)."""
    return np.array(x, np.float64).view(np.int64)


def to_host(b) -> np.ndarray:
    """Time words -> host float64 values (exact)."""
    return np.array(b, np.int64).view(np.float64)


@jax.jit
def from_f32(x):
    """Traced float32 values (the traces' gaps) -> time words (exact).

    Integer work on the float32 bits: a TPU converts neither way
    between a 64-bit integer and a float64's bits."""
    u = jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.float32), jnp.int32)
    e8 = ((u >> 23) & 0xFF).astype(DTYPE)
    f23 = (u & 0x7FFFFF).astype(DTYPE)
    # float32 subnormals: lead one at bit `lead` of the fraction
    lead = 63 - jax.lax.clz(f23)
    sub_bits = (((lead + 874) << 52)
                | ((f23 << jnp.clip(52 - lead, 0, 63)) & _FRAC))
    w = jnp.where(e8 > 0, ((e8 + 896) << 52) | (f23 << 29),
                  jnp.where(f23 > 0, sub_bits, 0))
    return jnp.where(u < 0, w | _SIGN, w)


_SPLIT = 26                  # low significand bits in the second part
_F32_EXP_LO = 1 - 127        # float32 normal exponent range
_F32_EXP_HI = 254 - 127


def _pow2(n):
    """2**n as float64 through float32 bits, for n in [-126, 127]."""
    n = jnp.clip(n, _F32_EXP_LO, _F32_EXP_HI)
    return jax.lax.bitcast_convert_type(
        ((n + 127) << 23).astype(jnp.int32), jnp.float32).astype(jnp.float64)


@jax.jit
def to_f64(b):
    """Traced time words -> float64, for statistics.

    The significand goes over in two int32 parts, each scaled by a power
    of two: exact in IEEE float64 for values in [2**-100, 2**100) and
    0; on a backend that emulates float64, the nearest value it holds."""
    b = jnp.asarray(b, DTYPE)
    e, m = _parts(b & ~_SIGN)
    hi = (m >> _SPLIT).astype(jnp.int32).astype(jnp.float64)
    lo = (m & ((1 << _SPLIT) - 1)).astype(jnp.int32).astype(jnp.float64)
    v = hi * _pow2(e - (1075 - _SPLIT)) + lo * _pow2(e - 1075)
    return jnp.where(b < 0, -v, v)


# ------------------------------------------------------------- arithmetic
def _parts(x):
    """(biased exponent as int32, at least 1; significand with its
    hidden bit).  Exponent arithmetic stays in one 32-bit word."""
    e = jnp.maximum((x >> 52).astype(jnp.int32), 1)
    return e, x - ((e - 1).astype(DTYPE) << 52)


def _round(e, s):
    """Bits of ``s * 2**(e - 1075 - GUARD)`` rounded half-even, for an
    ``s`` whose leading one is at bit ``_TOP`` (or below when e == 1):
    adding ``HALF - 1`` plus the kept significand's last bit carries
    exactly when the dropped bits are above half, or at half with an odd
    significand; a carry out of the significand lands in the exponent."""
    odd = (s >> _GUARD) & 1
    return (((e - 1).astype(DTYPE) << 52)
            + ((s + (_HALF - 1) + odd) >> _GUARD))


def _align(m, d):
    """``m << GUARD`` shifted right by ``d``: (floor, sticky 0/1)."""
    big = m << _GUARD
    small = big >> d
    return small, ((small << d) != big).astype(DTYPE)


def _traced(f):
    """``f`` as one nested jitted call: tracing the engine step then
    costs one equation per time operation (its body is traced once per
    operand shape), and XLA inlines the body again."""
    body = jax.jit(f)

    @functools.wraps(f)
    def op(*args):
        _tick()
        return body(*args)
    return op


@_traced
def add(a, b):
    """IEEE ``a + b`` (round half-even); ``+inf`` absorbs."""
    a, b = jnp.asarray(a, DTYPE), jnp.asarray(b, DTYPE)
    hi, lo = jnp.maximum(a, b), jnp.minimum(a, b)
    eh, mh = _parts(hi)
    el, ml = _parts(lo)
    small, sticky = _align(ml, jnp.minimum(eh - el, 63))
    s = ((mh << _GUARD) + small) | sticky
    carry = (s >> (_TOP + 1)).astype(jnp.int32)
    s = jnp.where(carry > 0, (s >> 1) | (s & 1), s)
    return jnp.where(eh >= _EXP_INF, hi, _round(eh + carry, s))


@_traced
def sub(a, b):
    """IEEE ``a - b`` (round half-even).  Where ``a < b`` the word
    carries the sign bit: such a word is only compared with a time
    (it is below every one) or converted with :func:`to_f64`."""
    a, b = jnp.asarray(a, DTYPE), jnp.asarray(b, DTYPE)
    hi, lo = jnp.maximum(a, b), jnp.minimum(a, b)
    ea, ma = _parts(hi)
    eb, mb = _parts(lo)
    small, sticky = _align(mb, jnp.minimum(ea - eb, 63))
    # the floor of the exact difference, with the sticky bit jammed in
    s = ((ma << _GUARD) - small - sticky) | sticky
    n = jnp.minimum(jax.lax.clz(s).astype(jnp.int32) - (63 - _TOP), ea - 1)
    s = s << n
    r = jnp.where(s == 0, 0, _round(ea - n, s))
    r = jnp.where(ea >= _EXP_INF, hi, r)
    return jnp.where(a < b, r | _SIGN, r)


def monus(a, b):
    """IEEE ``max(a - b, 0)``."""
    return jnp.where(gt(a, b), sub(a, b), ZERO)


@_traced
def mul(x, k):
    """IEEE ``x * k`` for an integer-valued count ``0 <= k < 512``."""
    x = jnp.asarray(x, DTYPE)
    e, m = _parts(x)
    p = m * jnp.asarray(k).astype(DTYPE)
    lead = 63 - jax.lax.clz(p).astype(jnp.int32)
    s = p << jnp.clip(_TOP - lead, 0, 63)
    return jnp.where(p == 0, 0, _round(e + lead - 52, s))


# ------------------------------------------------------ order (integers)
def lt(a, b):
    _tick()
    return a < b


def le(a, b):
    _tick()
    return a <= b


def gt(a, b):
    _tick()
    return a > b


def ge(a, b):
    _tick()
    return a >= b


def maximum(a, b):
    _tick()
    return jnp.maximum(a, b)


def minimum(a, b):
    _tick()
    return jnp.minimum(a, b)


def max(x, axis=None):          # noqa: A001 - the module's reduction
    _tick()
    return jnp.max(x, axis=axis)


def min(x, axis=None):          # noqa: A001
    _tick()
    return jnp.min(x, axis=axis)


def argmin(x, axis=None):
    _tick()
    return jnp.argmin(x, axis=axis)


def argsort(x):
    _tick()
    return jnp.argsort(x)


def cummax(x):
    _tick()
    return jax.lax.cummax(x)
