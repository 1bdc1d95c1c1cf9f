"""Limb arithmetic vs python big-int oracle."""

import random

import numpy as np
import pytest

from xaynet_tpu.ops import limbs as limb_ops

ORDERS = [
    20_000_000_000_001,
    2**45,
    2**96,
    200_000_000_000_000_000_000_000_000_017,  # Prime F64 B6 M3
    (2**128 - 159),  # arbitrary large modulus
]


@pytest.mark.parametrize("order", ORDERS)
def test_roundtrip_ints(order):
    rng = random.Random(42)
    values = [rng.randrange(order) for _ in range(64)]
    n_limb = limb_ops.n_limbs_for_order(order)
    arr = limb_ops.ints_to_limbs(values, n_limb)
    assert limb_ops.limbs_to_ints(arr) == values


@pytest.mark.parametrize("order", ORDERS)
def test_bytes_roundtrip(order):
    rng = random.Random(1)
    values = [rng.randrange(order) for _ in range(32)]
    bpn = ((order - 1).bit_length() + 7) // 8
    n_limb = limb_ops.n_limbs_for_order(order)
    arr = limb_ops.ints_to_limbs(values, n_limb)
    wire = limb_ops.limbs_to_bytes_le(arr, bpn)
    assert wire == b"".join(v.to_bytes(bpn, "little") for v in values)
    back = limb_ops.bytes_le_to_limbs(wire, 32, bpn)
    assert limb_ops.limbs_to_ints(back) == values


@pytest.mark.parametrize("order", ORDERS)
def test_mod_add_sub(order):
    rng = random.Random(7)
    a = [rng.randrange(order) for _ in range(128)]
    b = [rng.randrange(order) for _ in range(128)]
    n_limb = limb_ops.n_limbs_for_order(order)
    ol = limb_ops.order_limbs_for(order)
    aa = limb_ops.ints_to_limbs(a, n_limb)
    bb = limb_ops.ints_to_limbs(b, n_limb)

    s = limb_ops.mod_add(aa, bb, ol)
    assert limb_ops.limbs_to_ints(s) == [(x + y) % order for x, y in zip(a, b)]

    d = limb_ops.mod_sub(aa, bb, ol)
    assert limb_ops.limbs_to_ints(d) == [(x - y) % order for x, y in zip(a, b)]


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("k", [1, 2, 3, 8, 17])
def test_batch_mod_sum(order, k):
    rng = random.Random(k)
    n_limb = limb_ops.n_limbs_for_order(order)
    ol = limb_ops.order_limbs_for(order)
    rows = [[rng.randrange(order) for _ in range(16)] for _ in range(k)]
    stack = np.stack([limb_ops.ints_to_limbs(r, n_limb) for r in rows])
    got = limb_ops.limbs_to_ints(limb_ops.batch_mod_sum(stack, ol))
    want = [sum(rows[i][j] for i in range(k)) % order for j in range(16)]
    assert got == want


def test_edge_values():
    order = 2**64 - 59
    n_limb = limb_ops.n_limbs_for_order(order)
    ol = limb_ops.order_limbs_for(order)
    a = limb_ops.ints_to_limbs([order - 1, 0, order - 1], n_limb)
    b = limb_ops.ints_to_limbs([order - 1, 0, 1], n_limb)
    assert limb_ops.limbs_to_ints(limb_ops.mod_add(a, b, ol)) == [order - 2, 0, 0]
    assert limb_ops.limbs_to_ints(limb_ops.mod_sub(b, a, ol)) == [0, 0, 2 % order]


def _wire_fold_against_oracle(order, k, n, seed):
    """Fold k wire rows into an accumulator row with the host aggregator's
    native single-pass kernel (``fold_wire_batch_host``) and with the numpy
    reference (``batch_mod_sum`` + ``mod_add``); both must equal the python
    big-int sum. A row of maximal elements (order - 1) is among them."""
    from xaynet_tpu.utils import native

    nl, ol = limb_ops.n_limbs_for_order(order), limb_ops.order_limbs_for(order)
    rng = np.random.default_rng(seed)

    def row():
        return [int(rng.integers(0, min(order, 2**63))) % order for _ in range(n)]

    vals = [row(), [order - 1] * n] + [row() for _ in range(k - 1)]
    acc = limb_ops.ints_to_limbs(vals[0], nl)
    stack = np.stack([limb_ops.ints_to_limbs(v, nl) for v in vals[1:]])
    want = [sum(v[i] for v in vals) % order for i in range(n)]
    out = limb_ops.fold_wire_batch_host(acc, stack, ol)
    if native.load() is not None:
        assert out is not None  # the library serves every width
    if out is not None:
        assert limb_ops.limbs_to_ints(out) == want
    ref = limb_ops.mod_add(acc, limb_ops.batch_mod_sum(stack, ol), ol)
    assert limb_ops.limbs_to_ints(ref) == want


@pytest.mark.parametrize(
    "order,k",
    [
        (2**48 - 59, 9),          # prime-ish, 2 limbs
        ((1 << 45) * 10**3, 16),  # integer-style composite, 2 limbs
        (1 << 64, 5),             # power2 boundary: natural u64 wrap
        (1 << 32, 7),             # power2 boundary: one limb
        (2**31 - 1, 12),          # one limb, odd order
    ],
    ids=["prime-2limb", "integer-2limb", "pow2-64", "pow2-32", "odd-1limb"],
)
def test_fold_wire_batch_host_matches_bigint_oracle(order, k):
    """Native single-pass u64 fold == python big-int result (1 and 2 limb
    orders, prime / integer / power2-boundary, elements at order-1)."""
    _wire_fold_against_oracle(order, k, n=257, seed=3)


def test_fold_host_oversized_batch_uses_generic_kernel():
    """(K+1) * order over the u64 bound routes to the generic n-limb
    kernel and stays exact: (8+1) * 2^62 > 2^64 -> no u64 fast path."""
    _wire_fold_against_oracle(1 << 62, 8, n=33, seed=4)


def test_fold_host_nlimb_matches_bigint_oracle():
    """Generic n-limb single-pass fold: exact vs the big-int oracle across
    multi-limb orders (f64 families through a Bmax-scale 1384-bit order),
    batch sizes, and the pow2-boundary wraparound case."""
    import numpy as np

    from xaynet_tpu.ops import limbs as L
    from xaynet_tpu.utils import native

    if native.load() is None:
        import pytest

        pytest.skip("native library unavailable")
    rng = np.random.default_rng(11)
    orders = [2**65 + 7, 2**96, 2**96 - 17, 2**127 - 1, (1 << 192) - 237,
              (1 << 1384) - 1234567]
    for order in orders:
        nl, ol = L.n_limbs_for_order(order), L.order_limbs_for(order)
        for k in (1, 8, 31):
            n = 17

            def big():
                b = 0
                for _ in range(nl):
                    b = (b << 32) | int(rng.integers(0, 2**32))
                return b % order

            vals = [[big() for _ in range(n)] for _ in range(k + 1)]
            acc = L.ints_to_limbs(vals[0], nl)
            stack = np.stack([L.ints_to_limbs(v, nl) for v in vals[1:]])
            out = L.fold_wire_batch_host(acc, stack, ol)
            assert out is not None, (order.bit_length(), k)
            want = [sum(v[i] for v in vals) % order for i in range(n)]
            assert L.limbs_to_ints(out) == want, (order.bit_length(), k)


def test_wire_codec_native_matches_numpy_oracle():
    """Native wire<->limb codecs: exact vs the numpy pad/slice path across
    the wire-width grid (incl. the bytewise tail element and the 173-byte
    f64/Bmax worst case), plus serialize round-trip."""
    import numpy as np

    from xaynet_tpu.ops import limbs as L

    rng = np.random.default_rng(7)
    for bpn in [1, 2, 3, 4, 5, 6, 7, 8, 9, 12, 16, 21, 173]:
        n_limb = max(1, (bpn + 3) // 4)
        for count in (1, 2, 57):  # count=1 exercises the tail-only path
            buf = rng.integers(0, 256, size=count * bpn, dtype=np.uint8).tobytes()
            got = L.bytes_le_to_limbs(buf, count, bpn)
            raw = np.frombuffer(buf, dtype=np.uint8, count=count * bpn)
            padded = np.zeros((count, n_limb * 4), dtype=np.uint8)
            padded[:, :bpn] = raw.reshape(count, bpn)
            want = padded.view("<u4")
            assert np.array_equal(got, want), (bpn, count)
            assert L.limbs_to_bytes_le(got, bpn) == buf, (bpn, count)


def test_all_lt_order_matches_elementwise():
    """Scalar validity count == np.all over the per-element compare, incl.
    the 2^(32L) boundary orders and exact order-1/order edge values."""
    import numpy as np

    from xaynet_tpu.ops import limbs as L

    rng = np.random.default_rng(8)
    for order in [251, 2**20 + 7, 2**32, 2**52 - 47, 2**64 - 59, 2**64, 2**96]:
        nl = L.n_limbs_for_order(order)
        data = rng.integers(0, 2**32, size=(500, nl), dtype=np.uint32)
        assert L.all_lt_order(data, order) == bool(
            np.all(L.elements_lt_order(data, order))
        ), order
        ok = L.ints_to_limbs([0, order // 2, order - 1], nl)
        assert L.all_lt_order(ok, order) is True, order
        if order != 1 << (32 * nl):
            mixed = np.vstack([ok, L.ints_to_limbs([order], nl)])
            assert L.all_lt_order(mixed, order) is False, order
