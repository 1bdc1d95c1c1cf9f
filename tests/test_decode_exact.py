"""Vectorized exact-path unmask decode vs the Fraction oracle.

``decode_vect_any`` replaces the per-element Python ``Fraction`` loop for
every config family outside the bounded-f32 fast path (i32/i64/f64/Bmax).
The reference computes these decodes in exact big-rational arithmetic
(reference: rust/xaynet-core/src/mask/masking.rs:190-231); here the
cancellation step is exact multi-limb integer arithmetic and the final
rounding is double-double, verified against the Fraction oracle on every
family, with both the native C++ kernel and the numpy fallback.
"""

import math
from fractions import Fraction

import numpy as np
import pytest

from xaynet_tpu.core.mask.config import (
    BoundType,
    DataType,
    GroupType,
    MaskConfig,
    ModelType,
)
from xaynet_tpu.core.mask.encode import decode_vect_any, decode_vect_exact
from xaynet_tpu.ops import limbs as limb_ops

CASES = [
    MaskConfig(GroupType.INTEGER, DataType.I32, BoundType.B0, ModelType.M3),
    MaskConfig(GroupType.INTEGER, DataType.I64, BoundType.B0, ModelType.M3),
    MaskConfig(GroupType.PRIME, DataType.F64, BoundType.B6, ModelType.M6),
    MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.BMAX, ModelType.M3),
    MaskConfig(GroupType.POWER2, DataType.F64, BoundType.BMAX, ModelType.M9),
    MaskConfig(GroupType.PRIME, DataType.I32, BoundType.B2, ModelType.M12),
]


def _check(cfg: MaskConfig, force_numpy: bool, monkeypatch):
    if force_numpy:
        monkeypatch.setenv("XAYNET_TPU_NO_NATIVE", "1")
        import xaynet_tpu.utils.native as nat

        monkeypatch.setattr(nat, "_tried", False)
        monkeypatch.setattr(nat, "_lib", None)

    rng = np.random.default_rng(7)
    order = cfg.order
    L = limb_ops.n_limbs_for_order(order)
    nb, ssum = 3, Fraction(3, 7)
    c = nb * int(cfg.add_shift) * cfg.exp_shift
    # realistic unmasked values: near C (small decoded weights), plus extremes
    vals = [min(order - 1, max(0, c + int(d))) for d in rng.integers(-(10**12), 10**12, 64)]
    vals += [0, order - 1, min(order - 1, c)]
    limbs = limb_ops.ints_to_limbs(vals, L)

    want = decode_vect_exact(vals, cfg, nb, ssum)
    got = decode_vect_any(limbs, cfg, nb, ssum)

    for g, w in zip(got, want):
        g = float(g)
        if math.isinf(g):
            # decoded magnitude exceeds float64 range (Bmax extremes): the
            # oracle must agree it's out of range
            assert abs(w) > Fraction(2) ** 1024
            continue
        err = abs(Fraction(g) - w)
        # ~2^-95 relative from the top-96-bit rounding, plus the float64
        # output rounding itself (2^-53 relative, or denormal absolute ulp)
        tol = max(abs(w) * Fraction(1, 2**50), Fraction(1, 2**1070))
        assert err <= tol, (cfg, float(w), g, float(err))


@pytest.mark.parametrize("cfg", CASES, ids=lambda c: f"{c.group_type.name}-{c.data_type.name}-{c.bound_type.name}")
def test_decode_native(cfg, monkeypatch):
    _check(cfg, force_numpy=False, monkeypatch=monkeypatch)


@pytest.mark.parametrize("cfg", CASES, ids=lambda c: f"{c.group_type.name}-{c.data_type.name}-{c.bound_type.name}")
def test_decode_numpy_fallback(cfg, monkeypatch):
    _check(cfg, force_numpy=True, monkeypatch=monkeypatch)


def test_unmask_array_uses_vectorized_exact_path():
    """Full unmask on an i64 config (no fast path) stays within tolerance."""
    from xaynet_tpu.core.mask import Aggregation, Masker, MaskSeed, Scalar
    from xaynet_tpu.core.mask.model import Model

    # B2 bounds clamp weights to [-100, 100]; keep test values inside
    cfg = MaskConfig(GroupType.INTEGER, DataType.I64, BoundType.B2, ModelType.M3)
    pair = cfg.pair()
    values = [-3, 0, 1, 2, 5, -1]
    model = Model([Fraction(v) for v in values])
    masker = Masker(pair, MaskSeed(b"\x17" * 32))
    seed, masked = masker.mask(Scalar.unit(), model)
    agg = Aggregation.from_object(masked)
    mask = seed.derive_mask(len(values), pair)
    out = agg.unmask_array(mask)
    assert np.allclose(out, values, atol=2.0 / cfg.exp_shift)


# --------------------------------------------------------------------------
# the bounded-f32 decode (``xn_decode_f64``): planes read in place and the
# element axis on the library's threads, bit for bit the one-thread wire pass
# --------------------------------------------------------------------------

# (limbs, C = nb_models * add_shift * exp_shift, bound of the values): the
# cells' two orders at their rounds' counts, B6's, and one synthetic width
# on either side (no bounded-f32 order has 1 or 4 limbs)
_M6 = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)
_PRIME_M3 = MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3)
_B6M6 = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B6, ModelType.M6)
F64_WIDTHS = {
    "1-limb": (1, 3 * 10**8, 1 << 32),
    "integer-b0-m6": (2, 12 * int(_M6.add_shift) * _M6.exp_shift, _M6.order),
    "prime-b0-m3": (2, 192 * int(_PRIME_M3.add_shift) * _PRIME_M3.exp_shift, _PRIME_M3.order),
    "integer-b6-m6": (3, 8 * int(_B6M6.add_shift) * _B6M6.exp_shift, _B6M6.order),
    "4-limb": (4, 5 * 10**30, 1 << 118),
}
# under, at and over run_sliced's alignment (4096) and minimum slice (2^19)
F64_LENGTHS = [1, 2, 3, 4, 5, 4095, 4096, 4097, 2**19 - 1, 2**19 + 1, 3 * 2**19 + 7, 2**21 + 4097]
F64_RECIP = Fraction(16, 12 * 10**10)


def _f64_case(width: str, n: int):
    """Seeded unmasked values, about half of them under C (a negative
    difference), as wire rows and as padded planes with junk in the pad."""
    n_limbs, c_int, bound = F64_WIDTHS[width]
    rng = np.random.default_rng([n_limbs, n])
    # uniform under min(2C, bound), so the values straddle C: the limb the
    # bound's top bits fall in is bounded, those above it are zero
    top = min(2 * c_int, bound)
    lead = (top.bit_length() - 1) // 32
    wire = rng.integers(0, 1 << 32, size=(n, n_limbs), dtype=np.uint64)
    wire[:, lead] %= top >> (32 * lead)
    wire[:, lead + 1 :] = 0
    wire = wire.astype(np.uint32)
    if n > 2:
        wire[-1] = limb_ops.ints_to_limbs([c_int], n_limbs)[0]  # a zero difference
        wire[-2] = 0  # the most negative one
    stride = n + 37
    planes = rng.integers(0, 1 << 32, size=(n_limbs, stride), dtype=np.uint64).astype(np.uint32)
    planes[:, :n] = wire.T
    return wire, limb_ops.PlanarLimbs(planes, n), c_int


def _f64_digests(width: str, n: int) -> tuple[str, str, np.ndarray]:
    import hashlib

    from xaynet_tpu.core.mask import encode

    wire, planar, c_int = _f64_case(width, n)
    of_wire = encode._decode_native(wire, c_int, F64_RECIP)
    of_planes = encode._decode_native(planar, c_int, F64_RECIP)
    assert of_wire is not None and of_planes is not None, "the native library serves 1-4 limbs"
    assert of_wire.dtype == np.float64 and of_wire.shape == of_planes.shape == (n,)
    digest = lambda a: hashlib.sha256(a.view(np.uint64).tobytes()).hexdigest()  # noqa: E731
    return digest(of_wire), digest(of_planes), of_planes


_ONE_THREAD_SCRIPT = """
import json, sys
sys.path.insert(0, {root!r}); sys.path.insert(0, {tests!r})
import test_decode_exact as t
print(json.dumps({{f"{{w}}/{{n}}": t._f64_digests(w, n)[:2]
                  for w in t.F64_WIDTHS for n in t.F64_LENGTHS}}))
"""


@pytest.fixture(scope="module")
def one_thread_digests():
    """Every case decoded in a process of its own with
    ``XAYNET_NATIVE_THREADS=1`` (``fold_threads()`` reads it once)."""
    import json
    import os
    import subprocess
    import sys

    from xaynet_tpu.utils import native

    if native.load() is None:
        pytest.skip("no native library")
    tests = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, XAYNET_NATIVE_THREADS="1", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         _ONE_THREAD_SCRIPT.format(root=os.path.dirname(tests), tests=tests)],
        env=env, capture_output=True, text=True, timeout=300, check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("n", F64_LENGTHS)
@pytest.mark.parametrize("width", list(F64_WIDTHS))
def test_threaded_planar_decode_equals_the_one_thread_wire_decode(width, n, one_thread_digests):
    of_wire, of_planes, values = _f64_digests(width, n)
    one_wire, one_planes = one_thread_digests[f"{width}/{n}"]
    # planes or rows, one thread or many: the same float64 to the bit
    assert of_planes == of_wire == one_wire == one_planes
    # and the right ones (the first elements against exact rationals)
    wire, _planar, c_int = _f64_case(width, n)
    for got, value in zip(values[:8], limb_ops.limbs_to_ints(wire[:8])):
        want = (value - c_int) * F64_RECIP
        assert abs(Fraction(float(got)) - want) <= abs(want) * Fraction(1, 2**50)
    if n > 2:
        assert values[-1] == 0.0 and values[-2] < 0.0


@pytest.mark.parametrize("force_numpy", [False, True], ids=["native", "numpy"])
@pytest.mark.parametrize("cfg", [_M6, _PRIME_M3, _B6M6], ids=["m6", "prime-m3", "b6m6"])
def test_decode_vect_fast_reads_planes_as_it_reads_rows(cfg, force_numpy, monkeypatch):
    """The entry the served round calls: wire rows (the host arm) and the
    planes a device arm fetched give the same array on either route, count
    the same elements, and the planar pass makes no transposition."""
    from xaynet_tpu.core.mask.encode import decode_vect_fast
    from xaynet_tpu.telemetry import codec, unmask as unmask_stages

    if force_numpy:
        monkeypatch.setenv("XAYNET_TPU_NO_NATIVE", "1")
        import xaynet_tpu.utils.native as nat

        monkeypatch.setattr(nat, "_tried", False)
        monkeypatch.setattr(nat, "_lib", None)
    width = {id(_M6): "integer-b0-m6", id(_PRIME_M3): "prime-b0-m3", id(_B6M6): "integer-b6-m6"}[id(cfg)]
    n, nb = 4097, {id(_M6): 12, id(_PRIME_M3): 192, id(_B6M6): 8}[id(cfg)]
    wire, planar, _c = _f64_case(width, n)
    route = "generic" if force_numpy else "fast"
    passes = lambda: {k[0]: c.value for k, c in unmask_stages.MODEL_BYTES.children()}  # noqa: E731
    counted = lambda: codec.ELEMENTS.labels(op="decode", route=route).value  # noqa: E731
    before, elements = passes(), counted()
    of_wire = decode_vect_fast(wire, cfg, nb, Fraction(nb, 16))
    of_planes = decode_vect_fast(planar, cfg, nb, Fraction(nb, 16))
    assert np.array_equal(of_wire.view(np.uint64), of_planes.view(np.uint64))
    assert of_planes.flags.writeable and of_planes.flags.owndata
    assert counted() - elements == 2 * n
    after = passes()
    assert after["decode"] - before.get("decode", 0) == 2 * 8 * n
    assert after.get("transpose", 0) == before.get("transpose", 0)
    # a wire caller pays the transposition, and it is counted
    assert np.array_equal(planar.wire(), wire)
    assert passes()["transpose"] - before.get("transpose", 0) == 4 * wire.size


def test_decode_vect_any_accepts_the_planes_a_device_arm_fetched():
    cfg = CASES[1]  # i64: no fast path
    n_limbs = limb_ops.n_limbs_for_order(cfg.order)
    c = 3 * int(cfg.add_shift) * cfg.exp_shift
    wire = limb_ops.ints_to_limbs([c + d for d in (-5, 0, 7, 10**6)], n_limbs)
    planes = np.zeros((n_limbs, 9), dtype=np.uint32)
    planes[:, :4] = wire.T
    of_wire = decode_vect_any(wire, cfg, 3, Fraction(3, 7))
    of_planes = decode_vect_any(limb_ops.PlanarLimbs(planes, 4), cfg, 3, Fraction(3, 7))
    assert np.array_equal(of_wire.view(np.uint64), of_planes.view(np.uint64))
