"""Finite-group limb arithmetic as JAX/XLA device kernels.

Device counterpart of ``xaynet_tpu.ops.limbs`` (the numpy oracle): masked
models are ``uint32[n, L]`` limb tensors; modular addition is a carry chain
(statically unrolled over the small limb count) plus a conditional subtract
of the group order — flat, branch-free elementwise code that XLA fuses into
a single memory-bound kernel. The batch reducer pads to a power of two and
tree-halves, so aggregating K updates costs ``ceil(log2 K)`` fused
elementwise passes over HBM.

These kernels implement the coordinator hot loop the reference runs as
sequential big-int loops (reference: rust/xaynet-core/src/mask/masking.rs:292-316).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_U32 = jnp.uint32


def _as_order(order_limbs) -> np.ndarray:
    # trace-time constant: the tiny host-side order tuple, never a traced
    # value — not a device sync even inside a jitted caller
    return np.asarray(order_limbs, dtype=np.uint32)  # lint: sync-ok


def add_limbs(a: jax.Array, b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Limbwise ``a + b`` with carry propagation; returns (sum, carry_out)."""
    n_limb = a.shape[-1]
    outs = []
    carry = jnp.zeros(a.shape[:-1], dtype=_U32)
    for j in range(n_limb):
        s1 = a[..., j] + b[..., j]  # wraps mod 2^32
        c1 = (s1 < a[..., j]).astype(_U32)
        s2 = s1 + carry
        c2 = (s2 < s1).astype(_U32)
        outs.append(s2)
        carry = c1 | c2
    return jnp.stack(outs, axis=-1), carry


def sub_limbs(a: jax.Array, b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Limbwise ``a - b`` with borrow propagation; returns (diff, borrow_out)."""
    n_limb = a.shape[-1]
    outs = []
    borrow = jnp.zeros(a.shape[:-1], dtype=_U32)
    for j in range(n_limb):
        d1 = a[..., j] - b[..., j]
        b1 = (a[..., j] < b[..., j]).astype(_U32)
        d2 = d1 - borrow
        b2 = (d1 < borrow).astype(_U32)
        outs.append(d2)
        borrow = b1 | b2
    return jnp.stack(outs, axis=-1), borrow


def lt_const(a: jax.Array, order_limbs: np.ndarray) -> jax.Array:
    """Lexicographic ``a < order`` over the trailing limb axis."""
    order_limbs = _as_order(order_limbs)
    lt = jnp.zeros(a.shape[:-1], dtype=bool)
    decided = jnp.zeros(a.shape[:-1], dtype=bool)
    for j in range(a.shape[-1] - 1, -1, -1):
        col = a[..., j]
        o = _U32(int(order_limbs[j]))
        lt = lt | (~decided & (col < o))
        decided = decided | (col != o)
    return lt


def mod_add(a: jax.Array, b: jax.Array, order_limbs: np.ndarray) -> jax.Array:
    """``(a + b) mod order`` assuming ``a, b < order`` (branch-free).

    Works for the ``order == 2^(32L)`` boundary case too: the order limbs are
    all zero there, so ``lt_const`` is always false and the subtract of zero
    is the identity — reduction degenerates to the natural wraparound.
    """
    order_limbs = _as_order(order_limbs)
    s, carry = add_limbs(a, b)
    ge = (carry != 0) | ~lt_const(s, order_limbs)
    o = jnp.asarray(order_limbs, dtype=_U32)
    d, _ = sub_limbs(s, jnp.broadcast_to(o, s.shape))
    return jnp.where(ge[..., None], d, s)


def mod_sub(a: jax.Array, b: jax.Array, order_limbs: np.ndarray) -> jax.Array:
    """``(a - b) mod order`` assuming ``a, b < order``."""
    order_limbs = _as_order(order_limbs)
    d, borrow = sub_limbs(a, b)
    o = jnp.asarray(order_limbs, dtype=_U32)
    d2, _ = add_limbs(d, jnp.broadcast_to(o, d.shape))
    return jnp.where((borrow != 0)[..., None], d2, d)


def batch_mod_sum(stack: jax.Array, order_limbs: np.ndarray) -> jax.Array:
    """Modular sum over axis 0 of ``uint32[K, n, L]`` via pow2 tree reduce.

    Zero rows are valid group elements, so padding K to a power of two with
    zeros keeps every level exact; shapes stay static for jit.
    """
    k = stack.shape[0]
    if k == 0:
        raise ValueError("empty batch")
    k2 = 1 << (k - 1).bit_length()
    if k2 != k:
        pad = jnp.zeros((k2 - k, *stack.shape[1:]), dtype=stack.dtype)
        stack = jnp.concatenate([stack, pad], axis=0)
    while stack.shape[0] > 1:
        half = stack.shape[0] // 2
        stack = mod_add(stack[:half], stack[half:], order_limbs)
    return stack[0]


@partial(jax.jit, static_argnames=("order_tuple",), donate_argnums=(0,))
def _aggregate_batch_kernel(acc: jax.Array, stack: jax.Array, order_tuple: tuple[int, ...]) -> jax.Array:
    order_limbs = np.asarray(order_tuple, dtype=np.uint32)
    batch = batch_mod_sum(stack, order_limbs)
    return mod_add(acc, batch, order_limbs)


def aggregate_batch(acc: jax.Array, stack: jax.Array, order_limbs: np.ndarray) -> jax.Array:
    """Fold ``uint32[K, n, L]`` updates into the running accumulator (jitted)."""
    return _aggregate_batch_kernel(acc, stack, tuple(int(x) for x in _as_order(order_limbs)))


def _assemble_limbs(byte_plane, bpn: int, n_limbs: int):
    """Little-endian limb assembly shared by the two byte unpackers: limb j
    is ``byte_plane(4j) | byte_plane(4j+1) << 8 | ...`` over the byte planes
    that exist (``< bpn``). ``byte_plane(i)`` returns byte i of every
    element as ``uint8[..., n]``; each plane is widened AFTER it is sliced,
    so no widened copy of the whole input is ever materialized."""
    limbs = []
    for j in range(n_limbs):
        if 4 * j >= bpn:
            limbs.append(jnp.zeros(byte_plane(0).shape, dtype=_U32))
            continue
        w = byte_plane(4 * j).astype(_U32)
        for i in range(1, min(4, bpn - 4 * j)):
            w = w | (byte_plane(4 * j + i).astype(_U32) << _U32(8 * i))
        limbs.append(w)
    return jnp.stack(limbs, axis=-2)


_LANES = 128  # elements a row of the de-interleave holds: the TPU's lane width
_BLOCK_ROWS = 4096  # rows one pass of the de-interleave takes (3.7 MB of wire bytes at bpn = 7)


def _deinterleave_weights(bpn: int) -> np.ndarray:
    """The ``[128 * bpn, 128 * H]`` matrix (``H = ceil(bpn / 2)`` 16-bit
    halves an element) that takes a row of 128 interleaved elements to their
    halves, plane by plane: column ``h * 128 + e`` has 1 at byte ``2h`` of
    element ``e`` (row ``e * bpn + 2h``) and 256 at its byte ``2h + 1``. A
    trace-time constant."""
    halves = (bpn + 1) // 2
    w = np.zeros((_LANES * bpn, _LANES * halves), dtype=np.float32)
    e = np.arange(_LANES)
    for h in range(halves):
        w[e * bpn + 2 * h, h * _LANES + e] = 1.0
        if 2 * h + 1 < bpn:
            w[e * bpn + 2 * h + 1, h * _LANES + e] = 256.0
    return w


def _deinterleave_rows(rows: jax.Array, bpn: int, n_limbs: int) -> jax.Array:
    """``uint8[..., r, 128 * bpn]`` (each row 128 interleaved elements) ->
    planar ``uint32[..., L, r * 128]``, by ONE matrix product: the bytes of
    an element lie ``bpn`` apart along the lanes, and a lane-strided gather
    is the slowest thing the chip does (0.43 s a byte plane at 25.5M
    elements on a v5e: PERF.md section 6, PR 54), while a product with a 0/1/256
    matrix is what it does best. Exact: a byte and the weights 1 and 256 are
    bfloat16 numbers, a product of two is a float32 number, and a column
    sums two of them to less than 2**16."""
    halves = (bpn + 1) // 2
    w = jnp.asarray(_deinterleave_weights(bpn), dtype=jnp.bfloat16)
    out = jax.lax.dot_general(
        rows.astype(jnp.bfloat16), w, (((rows.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(_U32)  # [..., r, H * 128]
    out = out.reshape(*out.shape[:-1], halves, _LANES)
    limbs = []
    for j in range(n_limbs):
        if 2 * j >= halves:
            limbs.append(jnp.zeros(out.shape[:-2] + (_LANES,), dtype=_U32))
            continue
        word = out[..., 2 * j, :]
        if 2 * j + 1 < halves:
            word = word | (out[..., 2 * j + 1, :] << _U32(16))
        limbs.append(word)
    planar = jnp.stack(limbs, axis=-3)  # [..., L, r, 128]
    return planar.reshape(*planar.shape[:-2], -1)


def wire_bytes_to_planar(data: jax.Array, count: int, bpn: int) -> jax.Array:
    """Wire element block ``uint8[..., count*bpn]`` -> planar ``uint32[..., L, count]``.

    The wire layout is ``count`` fixed-width little-endian integers of
    ``bpn`` bytes each (serialization.py / reference vect.rs:24-80). Pure
    byte shuffling, so the coordinator can ship RAW wire bytes to the
    device (``bpn/(4L)`` of the limb-tensor size, e.g. 6/8 for the
    f32/B0/M3 configs, 7/8 for M6) and never pay a host-side parse.
    The block is read as rows of 128 elements (``128 * bpn`` bytes: whole
    lanes, where a reshape to ``[..., count, bpn]`` would put a minor
    dimension of ``bpn`` under the TPU's 128-lane tiling, 18x padding at
    bpn = 7, which the v5e compiler refuses at n = 25M) and de-interleaved
    a row at a time by a matrix product (:func:`_deinterleave_rows`),
    ``_BLOCK_ROWS`` rows a pass of a loop that writes each pass's planes
    where they belong in the result: what the product holds beside its
    input and output (the bytes widened, the halves as float32) is then a
    pass's and not the vector's, 4.6x the block for a group of four at 25M
    otherwise. The rows past the last whole pass go through the same
    product once; the elements past the last whole row, under 128 of them,
    are the stride-``bpn`` slices ``data[..., i::bpn]``, which is what the
    whole block used to be. Designed to run inside a jitted caller.
    """
    from . import limbs as host_limbs

    if data.shape[-1] != count * bpn:
        raise ValueError("wire block length must be count * bytes_per_number")
    n_limbs = host_limbs.n_limbs_for_bytes(bpn)
    lead = data.shape[:-1]
    row_bytes = _LANES * bpn
    whole = count // _LANES
    passes, rest = divmod(whole, _BLOCK_ROWS)
    out = jnp.zeros((*lead, n_limbs, count), dtype=_U32)

    def place(out, block, n_rows, first_row):
        planes = _deinterleave_rows(block.reshape(*lead, n_rows, row_bytes), bpn, n_limbs)
        return jax.lax.dynamic_update_slice_in_dim(out, planes, first_row * _LANES, axis=-1)

    if passes:
        pass_bytes = _BLOCK_ROWS * row_bytes

        def one_pass(i, out):
            block = jax.lax.dynamic_slice_in_dim(data, i * pass_bytes, pass_bytes, axis=-1)
            return place(out, block, _BLOCK_ROWS, i * _BLOCK_ROWS)

        out = jax.lax.fori_loop(0, passes, one_pass, out)
    if rest:
        block = data[..., passes * _BLOCK_ROWS * row_bytes : whole * row_bytes]
        out = place(out, block, rest, passes * _BLOCK_ROWS)
    if count % _LANES:
        tail = data[..., whole * row_bytes :]
        planes = _assemble_limbs(lambda i: tail[..., i::bpn], bpn, n_limbs)
        out = jax.lax.dynamic_update_slice_in_dim(out, planes, whole * _LANES, axis=-1)
    return out


def packed_planar_to_limbs(packed: jax.Array, n_limbs: int) -> jax.Array:
    """Packed byte-planar ``uint8[..., bpn, n]`` -> planar ``uint32[..., L, n]``.

    Device twin of ``limbs.unpack_planar`` (the packed staging codec): limb
    j assembles from byte-planes ``4j .. min(4j+4, bpn)`` with the same
    shift-or chain as :func:`wire_bytes_to_planar`, but every read is a
    CONTIGUOUS plane (the byte-planar layout keeps the model axis minor).
    Pure byte shuffling — designed to run inside a jitted caller so the
    packed bytes, not the 4L-byte planar, are what crosses host->device.
    """
    from . import limbs as host_limbs

    bpn = packed.shape[-2]
    if n_limbs < host_limbs.n_limbs_for_bytes(bpn):
        raise ValueError("limb width too small for the packed width")
    return _assemble_limbs(lambda i: packed[..., i, :], bpn, n_limbs)


# standalone jitted entry for callers that unpack OUTSIDE their own jit
# (e.g. ahead of the Pallas shard fold, whose kernel wants planar input):
# one shared trace cache, keyed on shape + the static limb count
packed_planar_to_limbs_jit = jax.jit(packed_planar_to_limbs, static_argnums=(1,))


def planar_all_lt_const(planar: jax.Array, order: int) -> jax.Array:
    """``all(element < order)`` per leading index over planar ``[..., L, n]``.

    The device version of the wire parser's element-validity check, one
    bool per leading index (per update for a ``[K, L, n]`` batch; a scalar
    for a single ``[L, n]`` tensor). Owns the ``order == 2^(32 L)``
    boundary case (every bit pattern valid) exactly like the host
    ``limbs.elements_lt_order`` — callers never special-case it. The
    compare walks the limb PLANES (model axis stays minor): moving the
    limb axis last would tile a minor dimension of L on TPU.
    """
    from .fold_jax import _int_to_limbs_list, p_lt_const

    n_limb = planar.shape[-2]
    if order == 1 << (32 * n_limb):
        return jnp.ones(planar.shape[:-2], dtype=bool)
    lt = p_lt_const(jnp.moveaxis(planar, -2, 0), _int_to_limbs_list(order, n_limb))
    return jnp.all(lt, axis=-1)
