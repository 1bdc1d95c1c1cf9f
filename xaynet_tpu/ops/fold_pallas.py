"""Pallas TPU kernels: the lazy-carry batch fold and the fused mask pipeline.

**Batch fold** (``fold_planar_batch_pallas``): fuses the whole aggregation
fold (16-bit split -> K-sum -> carry propagate -> modular reduce ->
accumulate) into one kernel so the staged batch makes exactly one HBM->VMEM
trip per tile with no intermediate HBM materialization. Grid: one program
per model-axis tile; each program loops the K updates of its tile in VMEM.
Equivalent to ``fold_jax.fold_planar_batch`` (the XLA version, which remains
the fallback and the CPU/interpret oracle). Layouts match: planar
``uint32[K, L, n]`` batch, ``uint32[L, n]`` accumulator.

**Fused mask pipeline** (``mask_fold_planar_pallas``): the Sum2 hot loop —
keystream generation -> lexicographic rejection sampling -> modular add —
as ONE kernel over the planar mask accumulator. Each launch folds a whole
seed group: per seed, the ChaCha keystream is generated and
rejection-sampled with the exact ``StreamSampler`` semantics
(``chacha_jax.derive_uniform_limbs_ingraph`` traced INSIDE the kernel body,
so the acceptance rule has one source of truth) and the accepted limbs are
modularly added straight into the accumulator held in VMEM — the per-seed
mask itself is a kernel-local value and never materializes in HBM. The
rejection cursor is inherently sequential along the keystream, so the fused
kernel batches over SEEDS (the model axis of one mask cannot shard without
deriving its prefix); the interpret route is the CPU/CI path and the real
Mosaic lowering stays behind the mask-kernel auto-calibration race
(``ops.masking_jax``), which falls back to the XLA batch route when the
compile fails or loses.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fold_jax import MAX_LAZY_BATCH

_U32 = jnp.uint32

TILE = 2048  # model-axis elements per grid program (VMEM-friendly)


def _limbs(value: int, n_limbs: int) -> tuple[int, ...]:
    return tuple((value >> (32 * i)) & 0xFFFFFFFF for i in range(n_limbs))


def _fold_kernel(acc_ref, stack_ref, out_ref, *, k: int, n_limb: int, order: int):
    """One model-axis tile: sum K updates lazily, reduce, accumulate."""
    # 16-bit column sums over K (values < K * 2^16 <= 2^32)
    lo = jnp.zeros((n_limb, stack_ref.shape[2]), dtype=_U32)
    hi = jnp.zeros((n_limb, stack_ref.shape[2]), dtype=_U32)
    for i in range(k):  # statically unrolled; stack tile lives in VMEM
        limbs = stack_ref[i]
        lo = lo + (limbs & _U32(0xFFFF))
        hi = hi + (limbs >> _U32(16))

    # carry-propagate into an (L+1)-limb value < K * order
    carry = jnp.zeros((stack_ref.shape[2],), dtype=_U32)
    value = []
    for j in range(n_limb):
        t_lo = lo[j] + carry
        t_hi = hi[j] + (t_lo >> _U32(16))
        value.append((t_lo & _U32(0xFFFF)) | (t_hi << _U32(16)))
        carry = t_hi >> _U32(16)
    value.append(carry)

    # conditional subtracts of order << b
    kbits = max(1, (k - 1).bit_length())
    for b in range(kbits - 1, -1, -1):
        const = _limbs(order << b, n_limb + 1)
        lt = jnp.zeros_like(value[0], dtype=jnp.bool_)
        decided = jnp.zeros_like(lt)
        for j in range(n_limb, -1, -1):
            o = _U32(const[j])
            lt = lt | (~decided & (value[j] < o))
            decided = decided | (value[j] != o)
        ge = ~lt
        borrow = jnp.zeros_like(value[0])
        new_value = []
        for j in range(n_limb + 1):
            d1 = value[j] - _U32(const[j])
            b1 = (value[j] < _U32(const[j])).astype(_U32)
            d2 = d1 - borrow
            b2 = (d1 < borrow).astype(_U32)
            new_value.append(jnp.where(ge, d2, value[j]))
            borrow = b1 | b2
        value = new_value

    # modular add into the accumulator (top limb of value is now zero)
    acc = acc_ref[:]
    carry = jnp.zeros_like(value[0])
    summed = []
    for j in range(n_limb):
        s1 = acc[j] + value[j]
        c1 = (s1 < acc[j]).astype(_U32)
        s2 = s1 + carry
        c2 = (s2 < s1).astype(_U32)
        summed.append(s2)
        carry = c1 | c2
    if order == 1 << (32 * n_limb):
        out_ref[:] = jnp.stack(summed)
        return
    ol = _limbs(order, n_limb)
    lt = jnp.zeros_like(summed[0], dtype=jnp.bool_)
    decided = jnp.zeros_like(lt)
    for j in range(n_limb - 1, -1, -1):
        o = _U32(ol[j])
        lt = lt | (~decided & (summed[j] < o))
        decided = decided | (summed[j] != o)
    ge = (carry != 0) | ~lt
    borrow = jnp.zeros_like(summed[0])
    reduced = []
    for j in range(n_limb):
        d1 = summed[j] - _U32(ol[j])
        b1 = (summed[j] < _U32(ol[j])).astype(_U32)
        d2 = d1 - borrow
        b2 = (d1 < borrow).astype(_U32)
        reduced.append(jnp.where(ge, d2, summed[j]))
        borrow = b1 | b2
    out_ref[:] = jnp.stack(reduced)


@partial(jax.jit, static_argnames=("order", "interpret", "tile_size"), donate_argnums=(0,))
def fold_planar_batch_pallas(
    acc, stack_planar, order: int, interpret: bool = False, tile_size: int | None = None
):
    """Pallas version of ``fold_jax.fold_planar_batch`` (same contract).

    Model lengths that don't divide the tile are zero-padded internally
    (zeros are valid group elements) and sliced back afterwards.
    ``tile_size`` overrides the default tile (``TILE``).
    """
    k, n_limb, n = stack_planar.shape
    if k > MAX_LAZY_BATCH:
        raise ValueError(f"batch of {k} exceeds lazy-carry headroom {MAX_LAZY_BATCH}")
    tile = min(tile_size if tile_size else TILE, n)
    padded_n = -(-n // tile) * tile
    if padded_n != n:
        pad = padded_n - n
        acc = jnp.pad(acc, ((0, 0), (0, pad)))
        stack_planar = jnp.pad(stack_planar, ((0, 0), (0, 0), (0, pad)))
    grid = (padded_n // tile,)
    out = pl.pallas_call(
        partial(_fold_kernel, k=k, n_limb=n_limb, order=order),
        out_shape=jax.ShapeDtypeStruct((n_limb, padded_n), jnp.uint32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_limb, tile), lambda i: (0, i)),
            pl.BlockSpec((k, n_limb, tile), lambda i: (0, 0, i)),
        ],
        out_specs=pl.BlockSpec((n_limb, tile), lambda i: (0, i)),
        interpret=interpret,
    )(acc, stack_planar)
    return out[:, :n] if padded_n != n else out


# --- fused mask pipeline: keystream -> reject-sample -> modular add --------


def _mask_fold_kernel(
    kw_ref, off_ref, acc_ref, out_acc_ref, out_off_ref, *, count, order, chunk_candidates
):
    """Fold every seed's freshly-derived mask into the planar accumulator.

    The whole body is pure traced code: the derivation reuses the in-graph
    sampler (same keystream, same rejection rule, same count-th-accept
    cursor handoff as the scalar ``StreamSampler``), the per-seed mask is a
    loop-carried value (VMEM-resident, never written back), and only the
    accumulator and the end cursors leave the kernel.
    """
    from . import chacha_jax
    from .fold_jax import p_mod_add

    kws = kw_ref[...]  # [B, 8] seed key words
    offs = off_ref[...]  # [B] byte cursors (post unit draw)
    acc = acc_ref[...]  # [L, count] planar mask accumulator

    def one_seed(b, carry):
        acc, ends = carry
        kw = jax.lax.dynamic_index_in_dim(kws, b, keepdims=False)
        mask, end = chacha_jax.derive_uniform_limbs_ingraph(
            kw, offs[b], count, order, chunk_candidates
        )
        acc = p_mod_add(acc, jnp.transpose(mask), order)
        return acc, ends.at[b].set(end)

    acc, ends = jax.lax.fori_loop(
        0, kws.shape[0], one_seed, (acc, jnp.zeros(kws.shape[0], jnp.int32))
    )
    out_acc_ref[...] = acc
    out_off_ref[...] = ends


@partial(
    jax.jit,
    static_argnames=("count", "order", "chunk_candidates", "interpret"),
    donate_argnums=(0,),
)
def mask_fold_planar_pallas(
    acc,
    key_words,
    byte_offsets,
    count: int,
    order: int,
    chunk_candidates: int | None = None,
    interpret: bool = False,
):
    """Derive + modularly fold a seed group's masks into ``acc`` in ONE kernel.

    ``acc`` is the planar ``uint32[L, count]`` mask accumulator (donated),
    ``key_words`` ``uint32[B, 8]``, ``byte_offsets`` ``int32[B]`` the
    keystream cursors each seed's vector draw resumes at (the unit draw's
    consumed-bytes handoff). Returns ``(new_acc, end_offsets int32[B])``;
    every seed's contribution is bit-identical to
    ``MaskSeed.derive_mask(...).vect`` folded with a modular add, but the
    mask tensor itself never exists outside the kernel. ``chunk_candidates``
    bounds the per-trip keystream footprint (tiny budgets force the
    multi-trip rejection path — the golden tests pin that case).
    """
    if key_words.ndim != 2 or key_words.shape[1] != 8:
        raise ValueError("key_words must be uint32[B, 8]")
    b = key_words.shape[0]
    out = pl.pallas_call(
        partial(
            _mask_fold_kernel, count=count, order=order, chunk_candidates=chunk_candidates
        ),
        out_shape=(
            jax.ShapeDtypeStruct(acc.shape, jnp.uint32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
        ),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(key_words, jnp.asarray(byte_offsets, jnp.int32), acc)
    return out
