"""The plain reference: what a PET round's global model has to be.

Python/numpy integer arithmetic, independent of the program: nothing here
imports ``xaynet_tpu`` or takes anything the program has made. The
participants' weights come from the benchmark's own seeded generator
(``weights_fixed``), the accepted set from the round's seed dictionary.

The published rule (xaynet-core ``mask/masking.rs``): a weight ``w`` of a
participant with scalar ``s`` is encoded as

    floor((clamp(s * w, -A, A) + A) * E)

with ``A = add_shift`` and ``E = exp_shift``; the coordinator sums the
encodings of the ``nb`` accepted participants (the masks cancel) and decodes

    ((S / E) - nb * A) / scalar_sum,      scalar_sum = sum of decoded scalars

to the nearest float64. Weights are multiples of 2^-23 in [-1, 1), so every
step below is exact in int64 or in Python integers.
"""

from __future__ import annotations

from fractions import Fraction

import numpy as np

FIXED_BITS = 23  # weights are k / 2^23 with k an integer in [-2^23, 2^23)


def weights_fixed(seed: int, index: int, n: int) -> np.ndarray:
    """Participant ``index``'s weights as int32 fixed-point numerators:
    seeded, different per participant and per position."""
    rng = np.random.default_rng([int(seed), int(index)])
    return rng.integers(-(1 << FIXED_BITS), 1 << FIXED_BITS, n, dtype=np.int32)


def to_f32(fixed: np.ndarray) -> np.ndarray:
    """The f32 weights a participant masks (exact: 24 significant bits)."""
    return (fixed.astype(np.float32) / np.float32(1 << FIXED_BITS)).astype(np.float32)


def round_to_bf16(w: np.ndarray) -> np.ndarray:
    """f32 weights rounded to bfloat16 (nearest even), back in f32: the
    lower-precision control, never the timed path."""
    bits = np.ascontiguousarray(w, dtype=np.float32).view(np.uint32)
    rounded = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return rounded.view(np.float32)


def encode_fixed(fixed: np.ndarray, scalar_den: int, add_shift: int, exp_shift: int) -> np.ndarray:
    """``floor((w / scalar_den + A) * E)`` for ``w = fixed / 2^23``, int64, exact.
    ``|w / scalar_den| <= 1 <= A`` so the clamp never binds; ``A * E`` is an
    integer, so the floor applies to ``fixed * E / (scalar_den * 2^23)``."""
    num = fixed.astype(np.int64) * np.int64(exp_shift)  # |.| <= 2^23 * 1e10 < 2^63
    return np.int64(add_shift * exp_shift) + np.floor_divide(num, np.int64(scalar_den << FIXED_BITS))


def encode_exact(w: float, scalar: Fraction, add_shift: int, exp_shift: int) -> int:
    """The rule itself in rationals, one weight (the tests hold
    ``encode_fixed`` to it)."""
    scaled = scalar * Fraction(w)
    clamped = max(-Fraction(add_shift), min(Fraction(add_shift), scaled))
    t = (clamped + add_shift) * exp_shift
    return t.numerator // t.denominator


def scalar_sum(nb: int, scalar_den: int, add_shift: int, exp_shift: int) -> Fraction:
    """Sum of the ``nb`` accepted participants' decoded scalars: each sent
    ``floor((1/scalar_den + A) * E)``."""
    unit = ((Fraction(1, scalar_den) + add_shift) * exp_shift)
    unit = unit.numerator // unit.denominator
    return Fraction(nb * unit, exp_shift) - nb * add_shift


def decode(sums: np.ndarray, nb: int, scalar_den: int, add_shift: int, exp_shift: int) -> np.ndarray:
    """Integer sums of encodings -> the float64 global model, each element
    the correctly rounded quotient of two Python integers."""
    ssum = scalar_sum(nb, scalar_den, add_shift, exp_shift)
    c = nb * add_shift * exp_shift
    # (S - c) / (E * ssum) = (S - c) * ssum.den / (E * ssum.num)
    den = exp_shift * ssum.numerator
    mul = ssum.denominator
    return np.array([((int(s) - c) * mul) / den for s in sums.tolist()], dtype=np.float64)


def sample_positions(seed: int, n: int, sample: int, edge: int) -> np.ndarray:
    """Positions compared: all of them when ``sample`` is 0 or covers the
    model, else a seeded sample plus the first and last ``edge``."""
    if sample <= 0 or sample + 2 * edge >= n:
        return np.arange(n, dtype=np.int64)
    rng = np.random.default_rng([int(seed), 0x5A3B1E])
    picked = rng.choice(n, size=sample, replace=False)
    edges = np.concatenate([np.arange(edge), np.arange(n - edge, n)])
    return np.unique(np.concatenate([picked, edges])).astype(np.int64)


def reference_model(seed: int, accepted: list[int], n: int, scalar_den: int, add_shift: int,
                    exp_shift: int, positions: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(the reference model at ``positions``, the float64 mean of the
    accepted participants' weights at every position)."""
    sums = np.zeros(len(positions), dtype=np.int64)
    fixed_total = np.zeros(n, dtype=np.int64)
    for index in accepted:
        fixed = weights_fixed(seed, index, n)
        fixed_total += fixed
        sums += encode_fixed(fixed[positions], scalar_den, add_shift, exp_shift)
    nb = len(accepted)
    model = decode(sums, nb, scalar_den, add_shift, exp_shift)
    mean = fixed_total.astype(np.float64) / float(nb << FIXED_BITS)  # |sum| < 2^53: exact
    return model, mean


def compare(model: np.ndarray, ref: np.ndarray, positions: np.ndarray, mean: np.ndarray,
            scalar_den: int, exp_shift: int) -> dict:
    """The numbers compared, each beside its limit. The distance from the
    float64 mean is bounded by the protocol's quantisation: each accepted
    participant's ``w / scalar_den`` is truncated to ``1 / exp_shift``, and the
    decode divides the sum by ``nb / scalar_den``, so the error stays under
    ``scalar_den / exp_shift`` (the smoke's ``n_update / exp_shift``)."""
    model = np.ascontiguousarray(model, dtype=np.float64)
    out = {"model_length": int(model.shape[0]), "model_length_want": int(mean.shape[0]),
           "positions_compared": int(len(positions))}
    if model.shape != mean.shape:
        out.update(mismatched_positions=None, max_abs_error=None)
        return out
    got = model[positions]
    out["mismatched_positions"] = int(np.count_nonzero(got.view(np.uint64) != ref.view(np.uint64)))
    out["mismatched_limit"] = 0
    out["max_abs_error"] = float(np.max(np.abs(model - mean)))
    out["max_abs_error_limit"] = scalar_den / exp_shift
    return out
