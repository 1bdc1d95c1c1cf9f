"""The fast encodes against the exact one over the whole bounded-f32 range,
and the native routes for 3- and 4-limb elements against the any-width ones.

``encode_vect_exact`` is the rule in Python rationals. ``encode_vect_fast``
(numpy double-double), ``encode_vect_limbs`` and the native masker
(``xn_mask_f32``, what ``Masker.mask`` and the SDK run) have to equal it bit
for bit at every bound B0-B6: at B6 the fixed-point value reaches 2e16, past
the 2^53 that one float64 holds, which is where a floor taken in one double
went wrong before.
"""

from fractions import Fraction

import numpy as np
import pytest

from xaynet_tpu.core.mask import (
    BoundType,
    DataType,
    GroupType,
    Masker,
    MaskConfig,
    ModelType,
    Scalar,
)
from xaynet_tpu.core.mask.encode import (
    encode_vect_exact,
    encode_vect_fast,
    encode_vect_limbs,
)
from xaynet_tpu.core.mask.seed import MaskSeed
from xaynet_tpu.ops import limbs as limb_ops
from xaynet_tpu.utils import native

BOUNDS = [BoundType.B0, BoundType.B2, BoundType.B4, BoundType.B6]
MODELS = [ModelType.M3, ModelType.M6]
# dyadic scalars are exact in double-double; 1 and 1/8 are the benchmark's
SCALARS = [Fraction(1), Fraction(1, 8), Fraction(1, 1024)]
needs_native = pytest.mark.skipif(native.load() is None, reason="native library unavailable")


def _config(bound, model):
    return MaskConfig(GroupType.INTEGER, DataType.F32, bound, model)


def _weights(config, seed):
    """f32 weights over the whole bound and past it: spread over [-A, A]
    (at B6 the upper half encodes above 2^53), a band just inside and just
    outside both clamps, far outside, tiny values of both signs (a low word
    that only moves the floor), and the edges themselves."""
    a = float(config.add_shift)
    rng = np.random.default_rng(seed)
    return np.concatenate([
        rng.uniform(-a, a, 3000),
        a * (1 + rng.uniform(-1e-6, 1e-6, 200)),
        -a * (1 + rng.uniform(-1e-6, 1e-6, 200)),
        rng.uniform(-4 * a, 4 * a, 200),
        rng.normal(0, 1e-20, 100),
        [a, -a, 0.0, -0.0, np.nextafter(np.float32(a), np.float32(0)),
         -np.nextafter(np.float32(a), np.float32(0)), 2.0**-149, -(2.0**-149)],
    ]).astype(np.float32)


@pytest.mark.parametrize("model", MODELS, ids=lambda m: m.name)
@pytest.mark.parametrize("bound", BOUNDS, ids=lambda b: b.name)
def test_fast_encodes_equal_the_exact_one(bound, model):
    config = _config(bound, model)
    for i, scalar in enumerate(SCALARS):
        w = _weights(config, seed=100 + i)
        want = encode_vect_exact(w, scalar, config)
        assert encode_vect_fast(w, scalar, config).tolist() == want
        assert limb_ops.limbs_to_ints(encode_vect_limbs(w, scalar, config)) == want
        if bound is BoundType.B6 and scalar == 1:
            above = sum(v > 2**53 for v in want)
            assert 0 < above < len(want)  # both sides of 2^53 are tested
        if scalar == 1:
            inside = np.abs(w.astype(np.float64)) < float(config.add_shift)
            assert 0 < inside.sum() < len(w)  # clamped and unclamped weights


@needs_native
@pytest.mark.parametrize("model", MODELS, ids=lambda m: m.name)
@pytest.mark.parametrize("bound", BOUNDS, ids=lambda b: b.name)
def test_native_masker_equals_the_exact_encode(bound, model):
    """``Masker.mask`` on f32 weights takes ``xn_mask_f32``; subtracting the
    seed's own mask leaves the encoding it added."""
    config = _config(bound, model)
    pair = config.pair()
    order_limbs = limb_ops.order_limbs_for(config.order)
    for i, scalar in enumerate(SCALARS[:2]):
        w = _weights(config, seed=200 + i)
        seed = MaskSeed(bytes([i + 1]) * 32)
        _, masked = Masker(pair, seed).mask(
            Scalar(scalar.numerator, scalar.denominator), w)
        mask = seed.derive_mask(len(w), pair)
        encoded = limb_ops.mod_sub(masked.vect.data, mask.vect.data, order_limbs)
        assert limb_ops.limbs_to_ints(encoded) == encode_vect_exact(w, scalar, config)


# --- the native any-width loops (wire codec, validity, sampler) --------------


def _numpy_wire_to_limbs(raw, count, bpn):
    n_limb = limb_ops.n_limbs_for_bytes(bpn)
    padded = np.zeros((count, n_limb * 4), dtype=np.uint8)
    padded[:, :bpn] = raw.reshape(count, bpn)
    return padded.view("<u4")


@needs_native
@pytest.mark.parametrize("bpn", list(range(1, 21)))
def test_native_wire_codecs_equal_the_byte_loops_at_every_width(bpn):
    rng = np.random.default_rng(bpn)
    for count in (1, 2, 3, 257):
        raw = rng.integers(0, 256, count * bpn, dtype=np.uint8)
        limbs = limb_ops.bytes_le_to_limbs(raw, count, bpn)
        assert np.array_equal(limbs, _numpy_wire_to_limbs(raw, count, bpn))
        assert limb_ops.limbs_to_bytes_le(limbs, bpn) == raw.tobytes()


@needs_native
@pytest.mark.parametrize("n_limb", [1, 2, 3, 4, 5])
def test_native_validity_equals_the_limb_compare(n_limb):
    rng = np.random.default_rng(n_limb)
    order = (1 << (32 * n_limb - 5)) + 12345
    assert limb_ops.n_limbs_for_order(order) == n_limb
    below = limb_ops.ints_to_limbs(
        [0, 1, order - 1, order // 2] + [int(v) % order for v in rng.integers(0, 2**62, 50)],
        n_limb)
    assert limb_ops.all_lt_order(below, order)
    for bad in (order, order + 1, (1 << (32 * n_limb)) - 1,
                order + (1 << 32 * (n_limb - 1))):
        data = np.concatenate([below, limb_ops.ints_to_limbs([bad], n_limb), below])
        assert not limb_ops.all_lt_order(data, order)
        assert not limb_ops.elements_lt_order(data, order).all()


@needs_native
@pytest.mark.parametrize("order", [
    _config(BoundType.B6, ModelType.M6).order,  # 75 bits in 10 draw bytes: 1.65% accepted
    _config(BoundType.B4, ModelType.M6).order,  # 68 bits in 9
    2**72 + 1, 2**100 - 3, 2**127 + 5, 2**128 - 1,
], ids=lambda o: f"{o.bit_length()}bit")
def test_native_sampler_equals_python_at_wide_orders(order):
    """Same attempts, same acceptance, same end cursor as the sequential
    sampler, also across buffer refills and from a cursor inside a block."""
    from xaynet_tpu.core.crypto.chacha import ChaChaStream
    from xaynet_tpu.core.crypto.prng import StreamSampler, generate_integer

    seed = b"\x2a" * 32
    oracle = ChaChaStream(seed)
    count = 3000 if order.bit_length() == 75 else 300  # 3000 x 60 x 10 B: three refills
    head = generate_integer(oracle, 251)  # leaves the cursor off a block edge
    expected = [generate_integer(oracle, order) for _ in range(count)]
    tail = generate_integer(oracle, order)

    sampler = StreamSampler(seed)
    assert sampler.draw_int(251) == head
    assert limb_ops.limbs_to_ints(sampler.draw_limbs(count, order)) == expected
    assert sampler.draw_int(order) == tail
