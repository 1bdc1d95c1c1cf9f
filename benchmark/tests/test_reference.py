"""The plain reference against cases worked out by hand, and against the
rule in rationals."""

from fractions import Fraction

import numpy as np
import pytest

from benchmark.harness import reference as R

A, E = 1, 10**10


def test_encode_hand_computed():
    # w = 0.5, N = 2: floor((0.25 + 1) * 1e10)
    assert R.encode_fixed(np.array([1 << 22], dtype=np.int32), 2, A, E)[0] == 12_500_000_000
    # w = -1, N = 1: floor(0 * 1e10); w = 1 - 2^-23, N = 1: floor((2 - 2^-23) * 1e10)
    got = R.encode_fixed(np.array([-(1 << 23), (1 << 23) - 1], dtype=np.int32), 1, A, E)
    assert got.tolist() == [0, 2 * E - 1193]  # 1e10 / 2^23 = 1192.09..., the floor takes 1193 off
    # w = -2^-23, N = 1024: floor(-1e10 / 2^33) = -2 below 1e10
    assert R.encode_fixed(np.array([-1], dtype=np.int32), 1024, A, E)[0] == E - 2


@pytest.mark.parametrize("scalar_den", [1, 3, 32, 1000, 1024])
def test_encode_fixed_is_the_published_rule(scalar_den):
    fixed = R.weights_fixed(5, 1, 400)
    w = R.to_f32(fixed)
    assert np.array_equal((w.astype(np.float64) * 2**23).astype(np.int64), fixed)  # exact f32
    want = [R.encode_exact(float(x), Fraction(1, scalar_den), A, E) for x in w]
    assert R.encode_fixed(fixed, scalar_den, A, E).tolist() == want


def test_decode_hand_computed():
    # two participants of N = 2 with w = 0.5 and w = -0.25: mean 0.125
    sums = R.encode_fixed(np.array([1 << 22], dtype=np.int32), 2, A, E) \
        + R.encode_fixed(np.array([-(1 << 21)], dtype=np.int32), 2, A, E)
    assert R.scalar_sum(2, 2, A, E) == 1
    assert R.decode(sums, 2, 2, A, E).tolist() == [0.125]
    # only one of the two accepted: scalar_sum 1/2, the model is that participant's weight
    one = R.encode_fixed(np.array([1 << 22], dtype=np.int32), 2, A, E)
    assert R.scalar_sum(1, 2, A, E) == Fraction(1, 2)
    assert R.decode(one, 1, 2, A, E).tolist() == [0.5]


def test_decode_is_the_correctly_rounded_quotient():
    nb = scalar_den = 3  # 1/3 is not exact: the unit's floor shows in scalar_sum
    sums = np.array([3 * E + 1, 2 * E + 12345], dtype=np.int64)
    ssum = R.scalar_sum(nb, scalar_den, A, E)
    assert ssum == Fraction(3 * 3333333333, E)
    want = [float((Fraction(int(s), E) - nb * A) / ssum) for s in sums]
    assert R.decode(sums, nb, scalar_den, A, E).tolist() == want


def test_reference_model_and_compare():
    n, scalar_den, accepted = 300, 8, [0, 1, 2, 5]
    pos = R.sample_positions(1, n, 0, 0)
    assert pos.tolist() == list(range(n))
    ref, mean = R.reference_model(3, accepted, n, scalar_den, A, E, pos)
    ws = np.stack([R.to_f32(R.weights_fixed(3, i, n)) for i in accepted]).astype(np.float64)
    assert np.allclose(mean, ws.mean(axis=0), rtol=0, atol=1e-15)
    assert np.max(np.abs(ref - mean)) <= scalar_den / E
    cmp = R.compare(ref.copy(), ref, pos, mean, scalar_den, E)
    assert cmp["mismatched_positions"] == 0 and cmp["max_abs_error"] <= cmp["max_abs_error_limit"]
    off = ref.copy()
    off[17] = np.nextafter(off[17], 1.0)  # one position, one unit in the last place
    assert R.compare(off, ref, pos, mean, scalar_den, E)["mismatched_positions"] == 1
    assert R.compare(ref[:-1], ref, pos, mean, scalar_den, E)["mismatched_positions"] is None


def test_sample_positions_cover_edges_and_are_seeded():
    pos = R.sample_positions(2**31 + 7, 100_000, 1000, 16)
    assert set(range(16)) <= set(pos.tolist()) and set(range(99_984, 100_000)) <= set(pos.tolist())
    assert 1000 <= len(pos) <= 1032 and (np.diff(pos) > 0).all()
    assert np.array_equal(pos, R.sample_positions(2**31 + 7, 100_000, 1000, 16))


def test_bf16_rounding_moves_the_model_by_about_4e_3():
    w = R.to_f32(R.weights_fixed(1, 0, 10_000))
    r = R.round_to_bf16(w)
    assert (r.view(np.uint32) & 0xFFFF == 0).all()
    err = np.abs(r.astype(np.float64) - w)
    assert 1e-3 < err.max() <= 2.0**-8
    assert np.array_equal((r.astype(np.float64) * 2**23) % 1, np.zeros(len(r)))  # still k / 2^23
