"""Vectorized double-double (~106-bit) arithmetic over numpy float64.

The reference does its fixed-point conversions in exact big-rational
arithmetic (reference: rust/xaynet-core/src/mask/masking.rs:358-404). The
TPU-native fast path instead computes the conversion in double-double
precision: plain f64 would lose up to ~4e-7 absolute on the worst bounded-f32
configs (value range 4e19, tolerance 1e-7), while double-double keeps the
error ~1e-23 — far below the protocol tolerance of ``1/exp_shift``.

Representation: a value is ``(hi, lo)`` with ``hi + lo`` the value and
``|lo| <= ulp(hi)/2``. All functions are elementwise over numpy arrays.
"""

from __future__ import annotations

import numpy as np

_SPLITTER = 134217729.0  # 2^27 + 1


def two_sum(a, b):
    s = a + b
    bb = s - a
    err = (a - (s - bb)) + (b - bb)
    return s, err


def quick_two_sum(a, b):
    """Requires |a| >= |b|."""
    s = a + b
    err = b - (s - a)
    return s, err


def _split(a):
    c = _SPLITTER * a
    hi = c - (c - a)
    lo = a - hi
    return hi, lo


def two_prod(a, b):
    p = a * b
    ah, al = _split(a)
    bh, bl = _split(b)
    err = ((ah * bh - p) + ah * bl + al * bh) + al * bl
    return p, err


def dd(hi, lo=0.0):
    hi64 = np.asarray(hi, dtype=np.float64)
    return hi64, np.asarray(lo, dtype=np.float64) * np.ones_like(hi64)


def from_fraction(f) -> tuple[float, float]:
    """Scalar Fraction/int -> double-double (exact to ~106 bits)."""
    from fractions import Fraction

    f = Fraction(f)
    hi = float(f)
    lo = float(f - Fraction(hi))
    return hi, lo


def from_fraction_scaled(f) -> tuple[float, float, int]:
    """Scalar Fraction -> (m_hi, m_lo, k) with value = (m_hi + m_lo) * 2^k.

    The mantissa is normalized into [1/2, 2), so fractions whose magnitude
    over- or under-flows float64 (e.g. the reciprocal of a BMAX exp_shift)
    are still represented exactly to ~106 bits; the caller applies ``2^k``
    via ldexp after its multiplications.
    """
    from fractions import Fraction

    f = Fraction(f)
    if f == 0:
        return 0.0, 0.0, 0
    k = f.numerator.bit_length() - f.denominator.bit_length()
    m = f / Fraction(2) ** k  # |m| in [1/2, 2)
    hi = float(m)
    lo = float(m - Fraction(hi))
    return hi, lo, k


def add(a_hi, a_lo, b_hi, b_lo):
    s, e = two_sum(a_hi, b_hi)
    e = e + a_lo + b_lo
    return quick_two_sum(s, e)


def sub(a_hi, a_lo, b_hi, b_lo):
    return add(a_hi, a_lo, -b_hi, -b_lo)


def add_f(a_hi, a_lo, f):
    s, e = two_sum(a_hi, f)
    e = e + a_lo
    return quick_two_sum(s, e)


def mul(a_hi, a_lo, b_hi, b_lo):
    p, e = two_prod(a_hi, b_hi)
    e = e + a_hi * b_lo + a_lo * b_hi
    return quick_two_sum(p, e)


def mul_f(a_hi, a_lo, f):
    p, e = two_prod(a_hi, f)
    e = e + a_lo * f
    return quick_two_sum(p, e)


def div(a_hi, a_lo, b_hi, b_lo):
    q1 = a_hi / b_hi
    # r = a - b*q1
    p_hi, p_lo = mul_f(b_hi, b_lo, q1)
    r_hi, r_lo = sub(a_hi, a_lo, p_hi, p_lo)
    q2 = r_hi / b_hi
    p_hi, p_lo = mul_f(b_hi, b_lo, q2)
    r_hi, r_lo = sub(r_hi, r_lo, p_hi, p_lo)
    q3 = r_hi / b_hi
    q_hi, q_lo = quick_two_sum(q1, q2)
    return add_f(q_hi, q_lo, q3)


def floor_i64(a_hi, a_lo):
    """Elementwise floor of a double-double as int64, exact for |value| < 2^63.

    Both words are floored and the two integers are added in int64, so the
    low word is not rounded into the high one: above 2^53 ``a_hi`` is an
    integer already and ``a_lo`` (up to ulp(a_hi)/2 >= 1) carries the units
    that one float64 cannot hold."""
    f = np.floor(a_hi)
    frac = (a_hi - f) + a_lo  # a_hi - f is exact; frac is small
    return f.astype(np.int64) + np.floor(frac).astype(np.int64)


def to_float(a_hi, a_lo):
    return a_hi + a_lo
