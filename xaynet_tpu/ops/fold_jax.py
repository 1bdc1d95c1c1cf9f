"""Single-pass lazy-carry batch aggregation (the fast device fold).

The naive way to aggregate K masked updates is a pairwise tree of modular
adds — ``log2 K`` full passes over HBM. This kernel does it in ONE pass over
the staged batch:

1. split each uint32 limb into its 16-bit halves *inside the reduction*
   (mask and shift; XLA fuses the elementwise split into the reduce input,
   so the batch is read exactly once);
2. plain-sum the halves over K — sums of 16-bit values stay below 2^32 for
   K <= 65535, so no carries are needed during the reduction;
3. carry-propagate the 16-bit column sums into an (L+1)-limb value
   (``value < K * order``);
4. reduce modulo the order with ``ceil(log2 K)`` conditional subtracts of
   ``order << b`` (tiny passes over the [L+1, n] result);
5. fold into the running accumulator with one modular add.

Device arrays are **planar**: ``uint32[L, n]`` (limb-major), so the model
axis is the innermost dimension and maps onto the full VPU lane width — a
wire-layout ``[n, L]`` device array with a trailing dim of 2-3 tiles
catastrophically on TPU (the (8,128) tile pads the minor dim ~64x). The
wire->planar transpose is a cheap host-side memcpy (``wire_to_planar``)
done once per staged update during ingest.

Replaces the reference's per-update sequential big-int loop
(rust/xaynet-core/src/mask/masking.rs:292-316).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_U32 = jnp.uint32

MAX_LAZY_BATCH = 65535  # 16-bit lazy-carry headroom


def _int_to_limbs_list(value: int, n_limbs: int) -> tuple[int, ...]:
    return tuple((value >> (32 * i)) & 0xFFFFFFFF for i in range(n_limbs))


# --- planar helpers: arrays are uint32[L, n] ------------------------------


def p_add(a, b):
    """Planar limbwise add with carry; returns (sum, carry)."""
    outs = []
    carry = jnp.zeros_like(a[0])
    for j in range(a.shape[0]):
        s1 = a[j] + b[j]
        c1 = (s1 < a[j]).astype(_U32)
        s2 = s1 + carry
        c2 = (s2 < s1).astype(_U32)
        outs.append(s2)
        carry = c1 | c2
    return jnp.stack(outs), carry


def p_sub(a, b):
    """Planar limbwise subtract with borrow; returns (diff, borrow)."""
    outs = []
    borrow = jnp.zeros_like(a[0])
    for j in range(a.shape[0]):
        d1 = a[j] - b[j]
        b1 = (a[j] < b[j]).astype(_U32)
        d2 = d1 - borrow
        b2 = (d1 < borrow).astype(_U32)
        outs.append(d2)
        borrow = b1 | b2
    return jnp.stack(outs), borrow


def p_lt_const(a, const_limbs: tuple[int, ...]):
    lt = jnp.zeros(a.shape[1:], dtype=bool)
    decided = jnp.zeros(a.shape[1:], dtype=bool)
    for j in range(a.shape[0] - 1, -1, -1):
        o = _U32(const_limbs[j])
        lt = lt | (~decided & (a[j] < o))
        decided = decided | (a[j] != o)
    return lt


def p_cond_sub_const(a, const_limbs: tuple[int, ...]):
    """Subtract the constant wherever ``a >= const`` (one fused pass)."""
    ge = ~p_lt_const(a, const_limbs)
    c = jnp.stack([jnp.full(a.shape[1:], cl, dtype=_U32) for cl in const_limbs])
    d, _ = p_sub(a, c)
    return jnp.where(ge[None, :], d, a)


def p_mod_add(a, b, order: int):
    """Planar ``(a + b) mod order`` for ``a, b < order`` (handles 2^(32L))."""
    n_limb = a.shape[0]
    s, carry = p_add(a, b)
    if order == 1 << (32 * n_limb):
        return s  # wraparound IS the reduction
    ol = _int_to_limbs_list(order, n_limb)
    ge = (carry != 0) | ~p_lt_const(s, ol)
    c = jnp.stack([jnp.full(s.shape[1:], x, dtype=_U32) for x in ol])
    d, _ = p_sub(s, c)
    return jnp.where(ge[None, :], d, s)


def p_mod_sub(a, b, order: int):
    """Planar ``(a - b) mod order`` for ``a, b < order``."""
    n_limb = a.shape[0]
    d, borrow = p_sub(a, b)
    if order == 1 << (32 * n_limb):
        return d
    ol = _int_to_limbs_list(order, n_limb)
    c = jnp.stack([jnp.full(d.shape[1:], x, dtype=_U32) for x in ol])
    d2, _ = p_add(d, c)
    return jnp.where((borrow != 0)[None, :], d2, d)


# --- the fold -------------------------------------------------------------


def _reduce_and_add(acc, lo, hi, k: int, order: int):
    """Shared tail of the lazy-carry folds: 16-bit column sums ``lo``/``hi``
    (planar ``uint32[L, n]``, each < ``k * 2^16``) -> carry-propagate ->
    reduce modulo the order -> modular add into ``acc``."""
    n_limb, n = acc.shape
    carry = jnp.zeros(n, dtype=_U32)
    limbs32 = []
    for j in range(n_limb):
        t_lo = lo[j] + carry
        t_hi = hi[j] + (t_lo >> _U32(16))
        limbs32.append((t_lo & _U32(0xFFFF)) | (t_hi << _U32(16)))
        carry = t_hi >> _U32(16)
    limbs32.append(carry)
    value = jnp.stack(limbs32)
    kbits = max(1, (k - 1).bit_length())
    for b in range(kbits - 1, -1, -1):
        value = p_cond_sub_const(value, _int_to_limbs_list(order << b, n_limb + 1))
    return p_mod_add(acc, value[:n_limb], order)


def _check_lazy_batch(k: int) -> None:
    if k > MAX_LAZY_BATCH:
        raise ValueError(f"batch of {k} exceeds lazy-carry headroom {MAX_LAZY_BATCH}")


@partial(jax.jit, static_argnames=("order",), donate_argnums=(0,))
def fold_planar_batch(acc, stack_planar, order: int):
    """Fold planar ``uint32[K, L, n]`` updates into the planar ``[L, n]`` acc.

    Single full pass over the batch: the 16-bit halves are split with a mask
    and a shift INSIDE the two K-reductions (XLA fuses both into one read of
    the batch), so every tensor keeps the model axis minor. A u16 bitcast
    would be the obvious split, but it materializes ``[..., n, 2]`` — a
    minor dimension of 2 that the TPU's (8,128) tiling pads 64x (25.6 GB at
    n = 25M; the v5e compiler refuses it).
    """
    k = stack_planar.shape[0]
    _check_lazy_batch(k)
    lo = jnp.sum(stack_planar & _U32(0xFFFF), axis=0, dtype=_U32)
    hi = jnp.sum(stack_planar >> _U32(16), axis=0, dtype=_U32)
    return _reduce_and_add(acc, lo, hi, k, order)


@partial(jax.jit, static_argnames=("n_limbs", "order"), donate_argnums=(0,))
def fold_packed_batch(acc, packed, n_limbs: int, order: int):
    """Fold PACKED byte-planar ``uint8[K, bpn, n]`` updates into the planar
    ``[L, n]`` accumulator, so only the ``bpn``-byte packed planes cross
    host->device (the EQuARX insight applied to the staging transfer).

    The byte planes are summed over K FIRST (one widening reduction, the
    batch read once) and limbs are assembled from the ``[bpn, n]`` plane
    sums: limb j's 16-bit column sums are ``S[4j] + (S[4j+1] << 8)`` and
    ``S[4j+2] + (S[4j+3] << 8)`` — exactly the sums the planar fold takes,
    each < ``K * 2^16``. Unpacking before the reduction would materialize
    the widened ``uint32[K, bpn, n]`` (10.4 GB at K=16, n=25M).
    """
    k, bpn, n = packed.shape
    _check_lazy_batch(k)
    if 4 * n_limbs < bpn:
        raise ValueError("limb width too small for the packed width")
    sums = jnp.sum(packed, axis=0, dtype=_U32)  # [bpn, n]

    def half(b: int):
        # byte planes b, b+1 as one 16-bit column sum (planes past bpn are 0)
        if b >= bpn:
            return jnp.zeros(n, dtype=_U32)
        if b + 1 >= bpn:
            return sums[b]
        return sums[b] + (sums[b + 1] << _U32(8))

    lo = jnp.stack([half(4 * j) for j in range(n_limbs)])
    hi = jnp.stack([half(4 * j + 2) for j in range(n_limbs)])
    return _reduce_and_add(acc, lo, hi, k, order)


def wire_to_planar(stack: np.ndarray) -> np.ndarray:
    """Host: wire-layout ``[K, n, L]`` (or ``[n, L]``) -> planar ``[K, L, n]``."""
    stack = np.asarray(stack, dtype=np.uint32)
    if stack.ndim == 2:
        return np.ascontiguousarray(stack.T)
    return np.ascontiguousarray(stack.transpose(0, 2, 1))


def planar_to_wire(planar: np.ndarray) -> np.ndarray:
    """Host: planar ``[L, n]`` -> wire-layout ``[n, L]``."""
    return np.ascontiguousarray(np.asarray(planar).T)
