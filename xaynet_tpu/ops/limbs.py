"""Multi-limb finite-group arithmetic over numpy arrays (host path).

The reference stores masked models as ``Vec<BigUint>`` and aggregates them
with per-element big-integer modular adds (reference:
rust/xaynet-core/src/mask/masking.rs:292-316). The TPU-native design instead
represents a mask object as a fixed-width limb tensor

    ``uint32[n, L]``  (limb 0 = least-significant 32 bits)

so that aggregation is a flat, branch-free, vectorizable elementwise kernel:
limb add with carry propagation followed by a conditional subtract of the
group order. This module is the numpy host implementation and the conformance
oracle for the JAX/Pallas device kernels in ``xaynet_tpu.ops.limbs_jax``.
"""

from __future__ import annotations

import numpy as np

from ..telemetry import codec

_U32 = np.uint32
_U64 = np.uint64
_MASK32 = np.uint64(0xFFFFFFFF)


def wire_width_for(order: int) -> int:
    """THE wire/pack width of one group element, in bytes:
    ``bytes_per_number = ceil(bits(order - 1) / 8)``.

    This module is the single source of truth for width math — the packed
    planar codec, the wire serializers, ``MaskConfig.bytes_per_number`` and
    the device unpack all derive from here, and the ``width`` lint rule
    (tools/analysis) rejects hand-computed copies of the expression
    anywhere else under ``xaynet_tpu/``.
    """
    return max(1, ((order - 1).bit_length() + 7) // 8)  # lint: width-ok


def draw_width_for(order: int) -> int:
    """The rejection-sampler DRAW width in bytes: the byte length of the
    order *itself* (the reference sizes its candidate buffer with
    ``max_int.to_bytes_le()``), which exceeds :func:`wire_width_for` when
    the order is a power of two at a byte boundary (e.g. 2^88, 2^96)."""
    return (order.bit_length() + 7) // 8  # lint: width-ok


def n_limbs_for_bytes(nbytes: int) -> int:
    """Byte width -> uint32 limb count (whole limbs)."""
    return max(1, (nbytes + 3) // 4)  # lint: width-ok


def n_limbs_for_order(order: int) -> int:
    """Number of 32-bit limbs for elements of the group of this order.

    Matches the wire width: ``bytes_per_number = ceil(bits(order - 1) / 8)``
    rounded up to whole limbs.
    """
    return n_limbs_for_bytes(wire_width_for(order))


def order_limbs_for(order: int) -> np.ndarray:
    """Group order as an L-limb constant for the modular kernels.

    When the order is exactly ``2^(32L)`` (e.g. 2^96 from the catalogue) it
    does not fit L limbs; the kernels then see all-zero limbs, which is
    correct: the reduction condition degenerates to the carry bit and the
    conditional subtract becomes the natural wraparound.
    """
    n_limb = n_limbs_for_order(order)
    if order == 1 << (32 * n_limb):
        return np.zeros(n_limb, dtype=_U32)
    return int_to_limbs(order, n_limb)


def all_lt_order(data: np.ndarray, order: int) -> bool:
    """``bool(np.all(elements_lt_order(data, order)))`` without the bool
    temporaries — native single-pass count of out-of-group elements (the
    per-update validity check on the coordinator's ingest path)."""
    n_limb = n_limbs_for_order(order)
    if order == 1 << (32 * n_limb):
        return True
    flat = np.ascontiguousarray(data.reshape(-1, n_limb), dtype=_U32)
    from ..utils import native

    lib = native.load()
    codec.count("validate", lib is not None, flat.shape[0])
    if lib is not None:
        ol = np.ascontiguousarray(int_to_limbs(order, n_limb))
        bad = lib.xn_count_ge(
            native.np_u32p(flat), flat.shape[0], n_limb, native.np_u32p(ol)
        )
        return bad == 0
    return bool(np.all(lt_const(flat, int_to_limbs(order, n_limb))))


def elements_lt_order(data: np.ndarray, order: int) -> np.ndarray:
    """Per-row validity ``element < order`` handling the 2^(32L) boundary."""
    n_limb = n_limbs_for_order(order)
    if order == 1 << (32 * n_limb):
        return np.ones(data.shape[:-1], dtype=bool)
    return lt_const(data, int_to_limbs(order, n_limb))


def int_to_limbs(value: int, n_limbs: int) -> np.ndarray:
    out = np.zeros(n_limbs, dtype=_U32)
    for i in range(n_limbs):
        out[i] = (value >> (32 * i)) & 0xFFFFFFFF
    if value >> (32 * n_limbs):
        raise OverflowError("value does not fit in the limb width")
    return out


def limbs_to_int(limbs: np.ndarray) -> int:
    value = 0
    for i in range(limbs.shape[-1] - 1, -1, -1):
        value = (value << 32) | int(limbs[..., i])
    return value


def ints_to_limbs(values, n_limbs: int) -> np.ndarray:
    """Convert an iterable of python ints to a ``uint32[n, L]`` limb array."""
    values = list(values)
    out = np.zeros((len(values), n_limbs), dtype=_U32)
    for i, v in enumerate(values):
        for j in range(n_limbs):
            out[i, j] = (v >> (32 * j)) & 0xFFFFFFFF
        if v >> (32 * n_limbs):
            raise OverflowError("value does not fit in the limb width")
    return out


def limbs_to_ints(arr: np.ndarray) -> list[int]:
    arr = np.asarray(arr, dtype=_U32)
    n, n_limb = arr.shape
    out = [0] * n
    for j in range(n_limb - 1, -1, -1):
        col = arr[:, j]
        for i in range(n):
            out[i] = (out[i] << 32) | int(col[i])
    return out


def bytes_le_to_limbs(
    buf: bytes | np.ndarray, count: int, bytes_per_number: int, op: str | None = "parse"
) -> np.ndarray:
    """Parse ``count`` fixed-width little-endian integers into ``uint32[count, L]``.

    Native single-pass codec when available (~memory bandwidth; the numpy
    pad/slice path measures ~370 MB/s and parse sits on the coordinator's
    per-update critical path — one 25M-param update is a 150 MB payload):
    one 8-byte load an element up to 8 wire bytes, a per-byte loop above.
    ``op`` names the operation the route is counted under (the sampler
    converts its own draws and counts them as ``derive``).
    """
    n_limb = n_limbs_for_bytes(bytes_per_number)
    raw = np.frombuffer(buf, dtype=np.uint8, count=count * bytes_per_number)
    from ..utils import native

    lib = native.load()
    if op is not None:
        codec.count(op, lib is not None, count)
    if lib is not None and count > 0:
        raw_c = np.ascontiguousarray(raw)
        out = np.empty((count, n_limb), dtype=_U32)
        lib.xn_wire_to_limbs(
            native.np_u8p(raw_c), count, bytes_per_number, n_limb, native.np_u32p(out)
        )
        return out
    padded = np.zeros((count, n_limb * 4), dtype=np.uint8)
    padded[:, :bytes_per_number] = raw.reshape(count, bytes_per_number)
    return padded.view("<u4").astype(_U32, copy=False)


def limbs_to_bytes_le(arr: np.ndarray, bytes_per_number: int) -> bytes:
    """Serialize ``uint32[n, L]`` limbs as fixed-width little-endian integers."""
    arr = np.ascontiguousarray(np.asarray(arr, dtype=_U32))
    n = arr.shape[0]
    from ..utils import native

    lib = native.load()
    # native codec assumes the wire width and limb count agree (L == ceil(bpn/4))
    if lib is not None and n > 0 and arr.shape[1] == n_limbs_for_bytes(bytes_per_number):
        out = np.empty(n * bytes_per_number, dtype=np.uint8)
        lib.xn_limbs_to_wire(
            native.np_u32p(arr), n, bytes_per_number, arr.shape[1], native.np_u8p(out)
        )
        return out.tobytes()
    raw = arr.astype("<u4").view(np.uint8).reshape(n, -1)
    return raw[:, :bytes_per_number].tobytes()


def lt_const(a: np.ndarray, order_limbs: np.ndarray) -> np.ndarray:
    """Lexicographic ``a < order`` per element, over the trailing limb axis."""
    shape = a.shape[:-1]
    lt = np.zeros(shape, dtype=bool)
    decided = np.zeros(shape, dtype=bool)
    for j in range(a.shape[-1] - 1, -1, -1):
        col = a[..., j]
        o = order_limbs[j]
        lt |= (~decided) & (col < o)
        decided |= col != o
    return lt


def add_limbs(a: np.ndarray, b: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Limbwise ``a + b`` with carry propagation; returns (sum, carry_out)."""
    n_limb = a.shape[-1]
    out = np.empty_like(a)
    carry = np.zeros(a.shape[:-1], dtype=_U64)
    for j in range(n_limb):
        s = a[..., j].astype(_U64) + b[..., j].astype(_U64) + carry
        out[..., j] = (s & _MASK32).astype(_U32)
        carry = s >> np.uint64(32)
    return out, carry.astype(_U32)


def sub_limbs(a: np.ndarray, b: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Limbwise ``a - b`` with borrow propagation; returns (diff, borrow_out)."""
    n_limb = a.shape[-1]
    out = np.empty_like(a)
    borrow = np.zeros(a.shape[:-1], dtype=_U64)
    for j in range(n_limb):
        d = a[..., j].astype(_U64) - b[..., j].astype(_U64) - borrow
        out[..., j] = (d & _MASK32).astype(_U32)
        borrow = (d >> np.uint64(63)) & np.uint64(1)  # underflow wraps in u64
    return out, borrow.astype(_U32)


def _native_binop(name: str, a: np.ndarray, b: np.ndarray, order_limbs: np.ndarray):
    """Run an elementwise modular op in the native library when possible.

    Any leading batch dimensions flatten into the element axis (the op is
    elementwise over rows of L limbs).
    """
    if a.ndim < 2 or a.shape != b.shape or a.shape[-1] != order_limbs.shape[0]:
        return None
    from ..utils import native

    lib = native.load()
    if lib is None:
        return None
    shape = a.shape
    a = np.ascontiguousarray(a, dtype=_U32).reshape(-1, shape[-1])
    b = np.ascontiguousarray(b, dtype=_U32).reshape(-1, shape[-1])
    ol = np.ascontiguousarray(order_limbs, dtype=_U32)
    out = np.empty_like(a)
    getattr(lib, name)(
        native.np_u32p(a),
        native.np_u32p(b),
        native.np_u32p(out),
        a.shape[0],
        a.shape[1],
        native.np_u32p(ol),
    )
    return out.reshape(shape)


def mod_add(a: np.ndarray, b: np.ndarray, order_limbs: np.ndarray) -> np.ndarray:
    """``(a + b) mod order`` assuming ``a, b < order`` (branch-free)."""
    fast = _native_binop("xn_mod_add", a, b, order_limbs)
    if fast is not None:
        return fast
    s, carry = add_limbs(a, b)
    # sum >= order  <=>  carry set (sum overflowed the limb width) or s >= order
    ge = carry.astype(bool) | ~lt_const(s, order_limbs)
    d, _ = sub_limbs(s, np.broadcast_to(order_limbs, s.shape))
    return np.where(ge[..., None], d, s)


def mod_sub(a: np.ndarray, b: np.ndarray, order_limbs: np.ndarray) -> np.ndarray:
    """``(a - b) mod order`` assuming ``a, b < order``."""
    fast = _native_binop("xn_mod_sub", a, b, order_limbs)
    if fast is not None:
        return fast
    d, borrow = sub_limbs(a, b)
    d2, _ = add_limbs(d, np.broadcast_to(order_limbs, d.shape))
    return np.where(borrow.astype(bool)[..., None], d2, d)


def batch_mod_sum(stack: np.ndarray, order_limbs: np.ndarray) -> np.ndarray:
    """Modular sum over axis 0 of ``uint32[K, n, L]``.

    Native single-pass fold when available (u64 kernel for <=2-limb
    orders, generic n-limb kernel for the rest); numpy pairwise tree
    reduce otherwise — each pairwise step keeps every element ``< order``,
    so the depth is ``ceil(log2 K)`` and every level is a flat elementwise
    kernel.
    """
    if stack.shape[0] > 1:
        fast = fold_wire_batch_host(
            np.zeros_like(stack[0]), stack, order_limbs
        )
        if fast is not None:
            return fast
    while stack.shape[0] > 1:
        k = stack.shape[0]
        half = k // 2
        merged = mod_add(stack[:half], stack[half : 2 * half], order_limbs)
        if k % 2:
            merged = np.concatenate([merged, stack[2 * half :]], axis=0)
        stack = merged
    return stack[0]


def native_fold_threads() -> int:
    """The native library's process-wide fold worker budget
    (``XAYNET_NATIVE_THREADS`` or its 2x-cores default), or 1 when the
    library is unavailable. The shard planner divides this into per-shard
    budgets instead of re-implementing the policy in Python."""
    from ..utils import native

    lib = native.load()
    return int(lib.xn_fold_threads()) if lib is not None else 1


def u64_fold_applicable(k: int, n_limb: int, order_limbs: np.ndarray) -> bool:
    """Whether the native single-pass u64 fold is exact for this shape: a
    <= 2-limb order whose K+1-term running sum fits u64 (pow2-boundary
    orders — all-zero limbs — wrap exactly for any K)."""
    if n_limb > 2:
        return False
    if not np.any(order_limbs):
        return True
    order = limbs_to_int(order_limbs)
    return (k + 1) <= ((1 << 64) // order)


def fold_planar_slice_host(
    acc: np.ndarray,
    stack: np.ndarray,
    out: np.ndarray,
    col0: int,
    col1: int,
    order_limbs: np.ndarray,
    n_threads: int = 0,
    acc_cols: int | None = None,
) -> bool:
    """Fold the model-axis column slice ``[col0, col1)`` of the planar
    ``uint32[K, L, n]`` batch into the same slice of ``acc``, writing
    ``out`` — reading the batch IN PLACE through its strides, so one
    shard's fold touches zero bytes outside its slice and the staged batch
    is never copied per shard.

    ``acc``/``out`` are either full-width ``[L, n]`` buffers (the slice is
    addressed at ``col0``) or contiguous per-shard ``[L, col1-col0]``
    buffers (pass ``acc_cols=col1-col0``; the slice starts at column 0 —
    the donated per-shard accumulators of the sharded streaming fold).
    ``n_threads`` > 0 pins this call's native worker count (the per-shard
    budget when shard folds run concurrently); 0 keeps the process default.

    Returns False when no native path applies (caller falls back to a
    copy + :func:`fold_planar_batch_host`); requirements otherwise match
    the u64 kernel (use :func:`u64_fold_applicable`).
    """
    k, n_limb, n = stack.shape
    width = col1 - col0
    a_cols = acc_cols if acc_cols is not None else n
    if acc.shape != (n_limb, a_cols) or out.shape != acc.shape:
        raise ValueError("accumulator/out shape mismatch")
    if not (acc.flags.c_contiguous and out.flags.c_contiguous and stack.flags.c_contiguous):
        raise ValueError("slice fold requires C-contiguous buffers")
    if out is acc:
        raise ValueError("out must not alias acc")
    if not u64_fold_applicable(k, n_limb, order_limbs):
        return False
    from ..utils import native

    lib = native.load()
    if lib is None:
        return False
    off = 0 if acc_cols is not None else col0
    lib.xn_fold_planar_u64_strided(
        native.np_u32p_at(acc, off),
        native.np_u32p_at(stack, col0),
        native.np_u32p_at(out, off),
        width,
        a_cols,  # acc/out plane stride
        n,  # stack row (limb-plane) stride
        n_limb * n,  # stack batch (update) stride
        n_limb,
        k,
        native.np_u32p(np.ascontiguousarray(order_limbs, dtype=_U32)),
        max(0, int(n_threads)),
    )
    return True


def fold_planar_batch_host(
    acc: np.ndarray, stack: np.ndarray, order_limbs: np.ndarray,
    out: np.ndarray | None = None, n_threads: int = 0,
) -> np.ndarray:
    """Single-pass host fold of planar ``uint32[K, L, n]`` updates into the
    planar ``uint32[L, n]`` accumulator (host analogue of
    ``ops.fold_jax.fold_planar_batch``; reference hot loop:
    rust/xaynet-core/src/mask/masking.rs:292-316).

    Native fast path for orders that fit 64 bits (every 1-2 limb config) —
    reads the batch once instead of XLA-CPU's strided half-word reduction
    or the ``ceil(log2 K)``-pass pairwise tree. Falls back to the pairwise
    numpy tree otherwise.

    ``out`` optionally receives the result (contiguous, same shape/dtype as
    ``acc``, not aliasing ``acc``): at 25M params a fresh 200 MB result
    buffer costs ~0.15 s of page faults per fold, so steady-state callers
    (the aggregator's native kernel) ping-pong two buffers instead. Only
    the native path honors it; callers must use the RETURNED array either
    way. ``n_threads`` > 0 pins the native worker count for this call (the
    per-shard budget of the sharded streaming fold); 0 keeps the process
    default.
    """
    k, n_limb, n = stack.shape
    if acc.shape != (n_limb, n):
        raise ValueError("accumulator/batch shape mismatch")
    if u64_fold_applicable(k, n_limb, order_limbs):
        from ..utils import native

        lib = native.load()
        if lib is not None:
            acc_c = np.ascontiguousarray(acc, dtype=_U32)
            stack_c = np.ascontiguousarray(stack, dtype=_U32)
            if (
                out is not None
                and out.shape == acc_c.shape
                and out.dtype == _U32
                and out.flags.c_contiguous
                and out is not acc_c
            ):
                pass  # reuse the caller's spare buffer
            else:
                out = np.empty_like(acc_c)
            lib.xn_fold_planar_u64_strided(
                native.np_u32p(acc_c),
                native.np_u32p(stack_c),
                native.np_u32p(out),
                n,
                n,  # acc/out plane stride (full width)
                n,  # stack row stride
                n_limb * n,  # stack batch stride
                n_limb,
                k,
                native.np_u32p(np.ascontiguousarray(order_limbs, dtype=_U32)),
                max(0, int(n_threads)),
            )
            return out
    # fallback: wire layout pairwise tree (exact for any limb count)
    wire = np.ascontiguousarray(stack.transpose(0, 2, 1))
    folded = batch_mod_sum(wire, order_limbs)
    acc_wire = np.ascontiguousarray(acc.T)
    return np.ascontiguousarray(mod_add(acc_wire, folded, order_limbs).T)


# ---------------------------------------------------------------------------
# packed planar codec
#
# Masked limb CONTENTS are uniform-random and incompressible, but the
# REPRESENTATION is not: group orders rarely fill their uint32 limbs, so a
# planar ``uint32[..., L, n]`` tensor packs losslessly to the wire width
# ``bpn = wire_width_for(order)`` bytes per element (6 instead of 8 for the
# standard 2-limb f32 configs — a 25% cut in staged/transferred bytes).
# The packed layout is BYTE-PLANAR ``uint8[..., bpn, n]``: byte-plane b
# holds byte b of every element, so pack/unpack are strided plane copies
# (no per-element gather), the device unpack is the same shift-or chain as
# the wire unpack but over contiguous planes, and the native packed fold
# streams bpn unit-stride byte planes exactly like the planar u64 fold
# streams its limb planes. Lossless iff every element < 2^(8*bpn) — true
# for every validated group element (element < order <= 2^(8*bpn)).
# ---------------------------------------------------------------------------


def pack_planar(planar: np.ndarray, bpn: int, out: np.ndarray | None = None) -> np.ndarray:
    """Planar ``uint32[..., L, n]`` -> packed byte-planar ``uint8[..., bpn, n]``.

    ``out`` optionally receives the result (the streaming pipeline packs
    straight into its ring buffers). Elements must be < 2^(8*bpn) (i.e.
    validated group elements); higher bytes are DROPPED by design.
    """
    planar = np.asarray(planar, dtype=_U32)
    n_limb, n = planar.shape[-2], planar.shape[-1]
    if bpn > 4 * n_limb:
        raise ValueError("pack width exceeds the limb width")
    if out is None:
        out = np.empty((*planar.shape[:-2], bpn, n), dtype=np.uint8)
    if (
        planar.ndim == 2
        and planar.flags.c_contiguous
        and out.ndim == 2
        and out.strides[-1] == 1
        and _native_pack_planar(planar, bpn, out)
    ):
        codec.count("stage", True, n)
        return out
    codec.count("stage", False, planar.size // n_limb)
    if planar.flags.c_contiguous:
        # little-endian u32 planes viewed as bytes: element i's byte b lives
        # at [..., b // 4, 4 * i + (b % 4)] — one strided plane copy per
        # byte-plane, no arithmetic temporaries
        raw = planar.view(np.uint8)
        for b in range(bpn):
            out[..., b, :] = raw[..., b // 4, b % 4 :: 4]
    else:
        # strided views (a transposed wire slice): shift-and-mask per plane
        for b in range(bpn):
            out[..., b, :] = (
                (planar[..., b // 4, :] >> _U32(8 * (b % 4))) & _U32(0xFF)
            ).astype(np.uint8)
    return out


def _native_pack_planar(planar: np.ndarray, bpn: int, out: np.ndarray) -> bool:
    """Native plane pack of one contiguous planar ``[L, n]`` into byte-planar
    ``out[bpn, *]`` (row stride from ``out.strides[0]``)."""
    from ..utils import native

    lib = native.load()
    if lib is None or not hasattr(lib, "xn_pack_planar_planes"):
        return False
    lib.xn_pack_planar_planes(
        native.np_u32p(planar),
        planar.shape[-1],
        planar.shape[-1],  # input plane stride
        bpn,
        native.np_u8p(out),
        out.strides[0],
        0,
    )
    return True


def pack_planar_slice(
    planar: np.ndarray,
    lo: int,
    hi: int,
    bpn: int,
    out: np.ndarray,
    n_threads: int = 0,
) -> np.ndarray:
    """Pack the column slice ``[lo, hi)`` of one contiguous planar
    ``uint32[L, n]`` row into byte-planar ``out[bpn, >= hi-lo]`` in place
    (native plane kernel: unit-stride reads AND writes; shift-and-mask
    numpy fallback)."""
    n_limb, n = planar.shape
    width = hi - lo
    if bpn > 4 * n_limb:
        raise ValueError("pack width exceeds the limb width")
    view = out[:, :width]
    from ..utils import native

    lib = native.load()
    if (
        lib is not None
        and hasattr(lib, "xn_pack_planar_planes")
        and planar.flags.c_contiguous
        and out.strides[-1] == 1
    ):
        lib.xn_pack_planar_planes(
            native.np_u32p_at(planar, lo),
            width,
            n,  # input plane stride
            bpn,
            native.np_u8p(view),
            out.strides[0],
            max(0, int(n_threads)),
        )
        codec.count("stage", True, width)
        return view
    codec.count("stage", False, width)
    for b in range(bpn):
        view[b, :] = (
            (planar[b // 4, lo:hi] >> _U32(8 * (b % 4))) & _U32(0xFF)
        ).astype(np.uint8)
    return view


def pack_wire_slice(
    stack: np.ndarray,
    lo: int,
    hi: int,
    bpn: int,
    out: np.ndarray,
    n_threads: int = 0,
) -> np.ndarray:
    """Pack the element-column slice ``[lo, hi)`` of a wire-layout
    ``uint32[K, n, L]`` batch into byte-planar ``out[K, bpn, >= hi-lo]``
    IN PLACE through its strides — the per-shard staging-ring pack of the
    streaming pipeline. Native kernel when available (plane-major
    unit-stride writes, ~memcpy speed; numpy's byte gather for the same
    copy measures ~3x a planar transpose), strided numpy copy otherwise.
    """
    k, n, n_limb = stack.shape
    width = hi - lo
    if bpn > 4 * n_limb:
        raise ValueError("pack width exceeds the limb width")
    if not stack.flags.c_contiguous:
        stack = np.ascontiguousarray(stack, dtype=_U32)
    from ..utils import native

    lib = native.load()
    view = out[:, :, :width]
    if (
        lib is not None
        and hasattr(lib, "xn_pack_wire_planes")
        and out.strides[-1] == 1
    ):
        for i in range(k):
            lib.xn_pack_wire_planes(
                native.np_u32p_at(stack, (i * n + lo) * n_limb),
                width,
                n_limb,
                bpn,
                native.np_u8p_at(out, i * out.strides[0]),
                out.strides[1],
                max(0, int(n_threads)),
            )
        codec.count("stage", True, k * width)
        return view
    codec.count("stage", False, k * width)
    raw = stack.view(np.uint8)  # [K, n, 4L]
    view[...] = np.moveaxis(raw[:, lo:hi, :bpn], -1, -2)
    return view


def pack_wire(stack: np.ndarray, bpn: int, out: np.ndarray | None = None) -> np.ndarray:
    """Wire-layout ``uint32[..., n, L]`` -> packed byte-planar
    ``uint8[..., bpn, n]`` (the staging-ring pack for wire-layout submit
    paths: byte b of element i is byte ``b`` of its little-endian wire
    row). Native plane-pack kernel for the 3-D batch shape, one strided
    numpy transpose copy otherwise."""
    stack = np.ascontiguousarray(stack, dtype=_U32)
    n_limb = stack.shape[-1]
    if bpn > 4 * n_limb:
        raise ValueError("pack width exceeds the limb width")
    if out is None:
        out = np.empty((*stack.shape[:-2], bpn, stack.shape[-2]), dtype=np.uint8)
    if stack.ndim == 3 and out.ndim == 3:
        return pack_wire_slice(stack, 0, stack.shape[1], bpn, out)
    codec.count("stage", False, stack.size // n_limb)
    raw = stack.view(np.uint8)  # [..., n, 4L]
    out[...] = np.moveaxis(raw[..., :bpn], -1, -2)
    return out


def unpack_planar(packed: np.ndarray, n_limbs: int, out: np.ndarray | None = None) -> np.ndarray:
    """Packed byte-planar ``uint8[..., bpn, n]`` -> planar ``uint32[..., L, n]``."""
    packed = np.asarray(packed, dtype=np.uint8)
    bpn, n = packed.shape[-2], packed.shape[-1]
    if n_limbs < n_limbs_for_bytes(bpn):
        raise ValueError("limb width too small for the packed width")
    if out is None or not out.flags.c_contiguous:
        out = np.zeros((*packed.shape[:-2], n_limbs, n), dtype=_U32)
    else:
        out[...] = 0
    raw = out.view(np.uint8)
    for b in range(bpn):
        raw[..., b // 4, b % 4 :: 4] = packed[..., b, :]
    return out


def fold_packed_slice_host(
    acc: np.ndarray,
    packed: np.ndarray,
    out: np.ndarray,
    col0: int,
    col1: int,
    order_limbs: np.ndarray,
    n_threads: int = 0,
    acc_cols: int | None = None,
) -> bool:
    """Fold the column slice ``[col0, col1)`` of a PACKED byte-planar
    ``uint8[K, bpn, n]`` batch into the planar ``uint32[L, *]`` accumulator
    slice — the native single-pass u64 fold reading the packed bytes in
    place (25% less batch traffic at bpn=6 vs the unpacked planar fold).

    Buffer addressing matches :func:`fold_planar_slice_host`; returns False
    when no native path applies (caller unpacks and takes the planar fold).
    Requirements: u64-applicable order (<= 2 limbs, K+1 headroom) and
    ``bpn <= 8``.
    """
    k, bpn, n = packed.shape
    width = col1 - col0
    n_limb = acc.shape[0]
    a_cols = acc_cols if acc_cols is not None else n
    if acc.shape != (n_limb, a_cols) or out.shape != acc.shape:
        raise ValueError("accumulator/out shape mismatch")
    if not (acc.flags.c_contiguous and out.flags.c_contiguous and packed.flags.c_contiguous):
        raise ValueError("packed slice fold requires C-contiguous buffers")
    if out is acc:
        raise ValueError("out must not alias acc")
    if bpn > 8 or not u64_fold_applicable(k, n_limb, order_limbs):
        return False
    from ..utils import native

    lib = native.load()
    if lib is None or not hasattr(lib, "xn_fold_packed_u64_strided"):
        return False
    off = 0 if acc_cols is not None else col0
    lib.xn_fold_packed_u64_strided(
        native.np_u32p_at(acc, off),
        native.np_u8p_at(packed, col0),
        native.np_u32p_at(out, off),
        width,
        a_cols,  # acc/out plane stride (elements)
        n,  # packed byte-plane stride (bytes)
        bpn * n,  # packed batch (update) stride (bytes)
        n_limb,
        bpn,
        k,
        native.np_u32p(np.ascontiguousarray(order_limbs, dtype=_U32)),
        max(0, int(n_threads)),
    )
    return True


def fold_packed_batch_host(
    acc: np.ndarray,
    packed: np.ndarray,
    order_limbs: np.ndarray,
    out: np.ndarray | None = None,
    n_threads: int = 0,
) -> np.ndarray:
    """Single-pass host fold of PACKED byte-planar ``uint8[K, bpn, n]``
    updates into the planar ``uint32[L, n]`` accumulator.

    Native fast path reads the packed bytes directly (the fold's dominant
    cost is the one mandatory read of the batch, and packed planes are
    ``bpn / 4L`` of the unpacked bytes); without it the batch unpacks once
    on the host and takes :func:`fold_planar_batch_host`. ``out``/
    ``n_threads`` behave exactly like the planar fold's.
    """
    k, bpn, n = packed.shape
    n_limb = acc.shape[0]
    if acc.shape != (n_limb, n):
        raise ValueError("accumulator/batch shape mismatch")
    acc_c = np.ascontiguousarray(acc, dtype=_U32)
    packed_c = np.ascontiguousarray(packed, dtype=np.uint8)
    if (
        out is not None
        and out.shape == acc_c.shape
        and out.dtype == _U32
        and out.flags.c_contiguous
        and out is not acc_c
    ):
        pass  # reuse the caller's spare buffer
    else:
        out = np.empty_like(acc_c)
    if fold_packed_slice_host(
        acc_c, packed_c, out, 0, n, order_limbs, n_threads=n_threads
    ):
        return out
    # no native packed path: one host unpack, then the planar fold (which
    # may still take its own native or pairwise route)
    planar = unpack_planar(packed_c, n_limb)
    return fold_planar_batch_host(acc_c, planar, order_limbs, out=out, n_threads=n_threads)


def fold_wire_batch_host(
    acc: np.ndarray, stack: np.ndarray, order_limbs: np.ndarray
) -> np.ndarray | None:
    """Native single-pass fold over wire-layout ``uint32[K, n, L]`` into the
    wire ``uint32[n, L]`` accumulator; None when no native path applies
    (callers fall back to the pairwise tree).

    For 2-limb configs a wire row is one little-endian u64, so every access
    is a contiguous 8-byte load; multi-limb orders (f64 families through
    the 44-limb Bmax) take the generic blocked n-limb kernel. Either way:
    no transposes, one read of the batch.
    """
    k, n, n_limb = stack.shape
    if acc.shape != (n, n_limb):
        return None
    from ..utils import native

    lib = native.load()
    if lib is None:
        return None
    order = limbs_to_int(order_limbs) or (1 << (32 * n_limb))
    # generic single-pass kernel for any limb count (f64 families through
    # the 44-limb Bmax order) and for 2-limb orders whose running sum
    # overflows u64; the u64 kernel otherwise
    generic = n_limb > 2 or (np.any(order_limbs) and (k + 1) > ((1 << 64) // order))
    if generic and (n_limb > 63 or k > 65535):
        return None
    acc_c = np.ascontiguousarray(acc, dtype=_U32)
    stack_c = np.ascontiguousarray(stack, dtype=_U32)
    out = np.empty_like(acc_c)
    args = (
        native.np_u32p(acc_c),
        native.np_u32p(stack_c),
        native.np_u32p(out),
        n,
        n_limb,
        k,
        native.np_u32p(np.ascontiguousarray(order_limbs, dtype=_U32)),
    )
    if generic:
        return out if lib.xn_fold_wire_nlimb(*args) == 0 else None
    lib.xn_fold_wire_u64(*args)
    return out
