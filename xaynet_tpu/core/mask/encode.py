"""Fixed-point encode/decode between weights and finite-group elements.

The masking pipeline (reference: rust/xaynet-core/src/mask/masking.rs:358-404)
maps a weight ``w`` to a group element:

    shifted = floor((clamp(scalar * w, -A, A) + A) * E)

with ``A = add_shift`` and ``E = exp_shift``; unmasking inverts it
(masking.rs:190-231):

    w = ((n / E) - nb_models * A) / scalar_sum

The reference computes this in exact big-rational arithmetic per weight. Here:

- **fast path** (f32 data, bounded B0-B6): vectorized numpy double-double
  arithmetic producing int64 fixed-point values that convert straight into
  limb tensors. The value reaches ``2 * 1e6 * 1e10 = 2e16`` at B6, past the
  2^53 one float64 holds, so the final floor is taken on both words in
  int64 (``dd.floor_i64``). With a dyadic scalar (1, 1/8, ...) every step is
  exact and the result equals the exact path bit for bit at every bound;
  with any other scalar its double-double (2^-106 relative) can land a value
  that is an exact integer one unit low (about one element in 2,000,000 at
  scalar 1/12: PERF.md §6, PR 23), inside the protocol's ``1/exp_shift``
  tolerance. ``tests/test_encode_exact.py`` holds this route, its limb form
  and the native masker to the exact path at B0, B2, B4 and B6;
- **exact path** (f64 / integer data types, Bmax): python-int / Fraction math,
  bit-identical to the reference semantics.
"""

from __future__ import annotations

from fractions import Fraction

import numpy as np

from ...ops import dd
from ...ops import limbs as limb_ops
from ...telemetry import codec
from ...telemetry import unmask as unmask_stages
from .config import BoundType, DataType, MaskConfig

# ---------------------------------------------------------------------------
# encode
# ---------------------------------------------------------------------------


def clamp_scalar(scalar: Fraction, unit_config: MaskConfig) -> Fraction:
    """Clamp the scalar from above by the unit config's add_shift."""
    a1 = unit_config.add_shift
    return a1 if scalar > a1 else scalar


def has_fast_path(config: MaskConfig) -> bool:
    return config.data_type is DataType.F32 and config.bound_type is not BoundType.BMAX


def encode_unit(scalar_clamped: Fraction, unit_config: MaskConfig) -> int:
    """Fixed-point encode of the (clamped) scalar — always exact (one value)."""
    t = scalar_clamped + unit_config.add_shift
    return (t.numerator * unit_config.exp_shift) // t.denominator


def encode_vect_exact(weights, scalar_clamped: Fraction, config: MaskConfig) -> list[int]:
    """Exact reference-semantics encode (python Fractions)."""
    a = config.add_shift
    e = config.exp_shift
    out = []
    for w in weights:
        # numpy scalars (e.g. float32) are not Rational; unwrap to python
        scaled = scalar_clamped * Fraction(w.item() if hasattr(w, "item") else w)
        c = -a if scaled < -a else (a if scaled > a else scaled)
        t = c + a
        out.append((t.numerator * e) // t.denominator)
    return out


def encode_vect_fast(weights: np.ndarray, scalar_clamped: Fraction, config: MaskConfig) -> np.ndarray:
    """Vectorized double-double encode for bounded-f32 configs -> int64."""
    assert has_fast_path(config)
    w = np.asarray(weights, dtype=np.float64)  # f32 -> f64 is exact
    s_hi, s_lo = dd.from_fraction(scalar_clamped)
    a = float(int(config.add_shift))  # 1, 100, 1e4, 1e6 — exact
    e = float(config.exp_shift)  # 1e10 — exact in f64

    hi, lo = dd.mul_f(np.full_like(w, s_hi), np.full_like(w, s_lo), w)
    # clamp to [-a, a]
    over = (hi > a) | ((hi == a) & (lo > 0))
    under = (hi < -a) | ((hi == -a) & (lo < 0))
    hi = np.where(over, a, np.where(under, -a, hi))
    lo = np.where(over | under, 0.0, lo)
    # (c + a) * e, floored
    hi, lo = dd.add_f(hi, lo, a)
    hi, lo = dd.mul_f(hi, lo, e)
    # up to 2 * 1e6 * 1e10 = 2e16 at B6, above 2^53: floored in int64
    return np.maximum(dd.floor_i64(hi, lo), 0)


def encode_vect_limbs(weights, scalar_clamped: Fraction, config: MaskConfig) -> np.ndarray:
    """Encode weights into ``uint32[n, L]`` limb tensors (unmasked)."""
    n_limb = limb_ops.n_limbs_for_order(config.order)
    if has_fast_path(config) and isinstance(weights, np.ndarray) and weights.dtype in (
        np.float32,
        np.float64,
    ):
        shifted = encode_vect_fast(weights, scalar_clamped, config)
        out = np.zeros((shifted.shape[0], n_limb), dtype=np.uint32)
        out[:, 0] = (shifted & 0xFFFFFFFF).astype(np.uint32)
        if n_limb > 1:
            out[:, 1] = (shifted >> 32).astype(np.uint32)
        return out
    values = encode_vect_exact(weights, scalar_clamped, config)
    return limb_ops.ints_to_limbs(values, n_limb)


# ---------------------------------------------------------------------------
# decode (unmask)
# ---------------------------------------------------------------------------


def decode_scalar_sum(unit_value: int, unit_config: MaskConfig, nb_models: int) -> Fraction:
    """Recover the aggregated scalar sum from the unmasked unit — exact."""
    return Fraction(unit_value, unit_config.exp_shift) - nb_models * unit_config.add_shift


def decode_vect_exact(
    values: list[int], config: MaskConfig, nb_models: int, scalar_sum: Fraction
) -> list[Fraction]:
    a = config.add_shift
    e = config.exp_shift
    shift = nb_models * a
    return [(Fraction(v, e) - shift) / scalar_sum for v in values]


def _decode_native(limbs, c_int: int, recip: Fraction):
    """Native double-double decode of wire rows or of fetched planes, read
    in place; None when unavailable/out of range."""
    from ...utils import native

    lib = native.load()
    if isinstance(limbs, limb_ops.PlanarLimbs):
        n, (n_limb, plane_stride) = limbs.length, limbs.planes.shape
        limbs = limbs.planes
    else:
        (n, n_limb), plane_stride = limbs.shape, 0
    if (
        lib is None
        or not hasattr(lib, "xn_decode_f64")
        or n_limb > 4
        or c_int < 0
        or c_int.bit_length() > 120
    ):
        return None
    inv_hi, inv_lo = dd.from_fraction(recip)
    c_le = c_int.to_bytes(limb_ops.draw_width_for(c_int) or 1, "little")
    arr = np.ascontiguousarray(limbs, dtype=np.uint32)
    out = np.empty(n, dtype=np.float64)
    import ctypes

    rc = lib.xn_decode_f64(
        native.np_u32p(arr),
        n,
        n_limb,
        plane_stride,
        native.as_u8p(c_le),
        len(c_le),
        ctypes.c_double(inv_hi),
        ctypes.c_double(inv_lo),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
    )
    return out if rc == 0 else None


def decode_vect_any(
    limbs: np.ndarray, config: MaskConfig, nb_models: int, scalar_sum: Fraction
) -> np.ndarray:
    """Unmask decode -> float64 for ANY config family (arbitrary limb width).

    Replaces the per-element ``Fraction`` loop for i32/i64/f64/Bmax configs:
    the cancellation-prone step ``v - nb_models * A * E`` is done in exact
    multi-limb integer arithmetic (native C++ when available, vectorized
    numpy otherwise); the cancellation-free difference is then decoded from
    its top three 32-bit limbs in double-double. Worst-case relative error
    ~2^-64 (when the leading limb is small), far below both the 1/exp_shift
    protocol tolerance and the float64 output rounding that follows
    (reference: rust/xaynet-core/src/mask/masking.rs:190-231).
    """
    if isinstance(limbs, limb_ops.PlanarLimbs):
        limbs = limbs.wire()  # no cell: this family's kernels read wire rows
    n, n_limb = limbs.shape
    unmask_stages.count_pass("decode", 8 * n)
    c_int = nb_models * int(config.add_shift) * config.exp_shift
    recip = Fraction(1, 1) / (config.exp_shift * scalar_sum)
    c_nlimbs = max(1, (c_int.bit_length() + 31) // 32)
    c_limbs = limb_ops.int_to_limbs(c_int, c_nlimbs)
    # normalized mantissa + exponent: BMAX reciprocals don't fit float64
    inv_hi, inv_lo, inv_exp = dd.from_fraction_scaled(recip)

    from ...utils import native

    lib = native.load()
    if lib is not None and hasattr(lib, "xn_decode_exact") and n_limb <= 96 and c_nlimbs <= 96:
        arr = np.ascontiguousarray(limbs, dtype=np.uint32)
        c_arr = np.ascontiguousarray(c_limbs, dtype=np.uint32)
        out = np.empty(n, dtype=np.float64)
        import ctypes

        rc = lib.xn_decode_exact(
            native.np_u32p(arr),
            n,
            n_limb,
            native.np_u32p(c_arr),
            c_nlimbs,
            ctypes.c_double(inv_hi),
            ctypes.c_double(inv_lo),
            ctypes.c_int32(inv_exp),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        )
        if rc == 0:
            codec.count("decode", True, n)
            return out

    # numpy fallback: exact vectorized limb subtract, then top-96-bit decode
    codec.count("decode", False, n)
    ell = max(n_limb, c_nlimbs) + 1
    c_ext = limb_ops.int_to_limbs(c_int, ell)
    d = np.zeros((n, ell), dtype=np.uint32)
    borrow = np.zeros(n, dtype=np.int64)
    for j in range(ell):
        vj = limbs[:, j].astype(np.int64) if j < n_limb else np.zeros(n, dtype=np.int64)
        s = vj - int(c_ext[j]) - borrow
        d[:, j] = (s & 0xFFFFFFFF).astype(np.uint32)
        borrow = (s < 0).astype(np.int64)
    neg = borrow == 1
    if neg.any():  # two's-complement negate the negative rows
        carry = neg.astype(np.int64)
        for j in range(ell):
            inv = np.where(neg, (~d[:, j]).astype(np.int64) & 0xFFFFFFFF, d[:, j].astype(np.int64))
            s = inv + carry
            d[:, j] = (s & 0xFFFFFFFF).astype(np.uint32)
            carry = s >> 32
    # top three limbs -> <= 96-bit double-double, exponent applied via ldexp
    # (same scheme as the native kernel: no intermediate over/underflow)
    rows = np.arange(n)
    t = ell - 1 - np.argmax((d != 0)[:, ::-1], axis=1)  # top nonzero limb (0 if none)
    l0 = d[rows, t].astype(np.float64)
    l1 = np.where(t >= 1, d[rows, np.maximum(t - 1, 0)], 0).astype(np.float64)
    l2 = np.where(t >= 2, d[rows, np.maximum(t - 2, 0)], 0).astype(np.float64)
    hi = l0 * 18446744073709551616.0  # * 2^64, exact
    hi, lo = dd.add_f(hi, np.zeros(n), l1 * 4294967296.0)  # + l1 * 2^32, exact
    hi, lo = dd.add(hi, lo, l2, np.zeros(n))
    hi, lo = dd.mul(hi, lo, np.full(n, inv_hi), np.full(n, inv_lo))
    exp = (32 * (t.astype(np.int64) - 2) + inv_exp).astype(np.int32)
    # Bmax extremes can exceed float64 range; inf is the intended result
    # there (oracle-checked in tests/test_decode_exact.py), not an error
    with np.errstate(over="ignore"):
        out = np.ldexp(hi, exp) + np.ldexp(lo, exp)
    return np.where(neg, -out, out)


def decode_vect_fast(
    limbs, config: MaskConfig, nb_models: int, scalar_sum: Fraction
) -> np.ndarray:
    """Vectorized double-double decode -> float64 array (f32-accurate+).

    ``limbs`` is wire ``uint32[n, L]`` (the host arm) or the
    :class:`~xaynet_tpu.ops.limbs.PlanarLimbs` a device arm fetched, whose
    planes the native kernel reads where they lie. The array returned is
    freshly written and the caller's: the served round stores and
    broadcasts it as it is.

    Structured for memory-bandwidth: scaling by 2^32 is exact on both dd
    components (no renormalization pass), constants broadcast as scalars,
    and the division by ``E * scalar_sum`` becomes one dd multiply by a
    precomputed dd reciprocal (~1e-32 relative, far below tolerance).
    """
    assert has_fast_path(config)
    planar = isinstance(limbs, limb_ops.PlanarLimbs)
    n = limbs.length if planar else limbs.shape[0]
    c_int = nb_models * int(config.add_shift) * config.exp_shift
    recip = Fraction(1, 1) / (config.exp_shift * scalar_sum)
    native_out = _decode_native(limbs, c_int, recip)
    codec.count("decode", native_out is not None, n)
    unmask_stages.count_pass("decode", 8 * n)
    if native_out is not None:
        return native_out
    # limb j of every element, in either layout
    cols = [plane[:n] for plane in limbs.planes] if planar else list(limbs.T)
    # limbs -> double-double value (high to low; power-of-two scaling exact)
    hi = cols[-1].astype(np.float64)
    lo = np.zeros(n)
    for col in cols[-2::-1]:
        hi = hi * 4294967296.0
        lo = lo * 4294967296.0
        hi, lo = dd.add_f(hi, lo, col.astype(np.float64))
    # subtract nb_models * A * E (exact integer; scalar dd constant)
    c_hi, c_lo = dd.from_fraction(nb_models * int(config.add_shift) * config.exp_shift)
    hi, lo = dd.add(hi, lo, -c_hi, -c_lo)
    # multiply by the dd reciprocal of E * scalar_sum
    r_hi, r_lo = dd.from_fraction(Fraction(1, 1) / (config.exp_shift * scalar_sum))
    hi, lo = dd.mul(hi, lo, r_hi, r_lo)
    return dd.to_float(hi, lo)
