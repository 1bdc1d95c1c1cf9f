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

import sys
import threading

import numpy as np

from ..telemetry import codec
from ..telemetry import unmask as unmask_stages

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


def packed_staging_usable(order: int) -> bool:
    """Whether packed byte-planar staging shrinks anything for this group:
    the wire width must be narrower than the limb width (at the
    ``order == 2^(32L)`` boundary bpn == 4L and packing is a no-op)."""
    return wire_width_for(order) < 4 * n_limbs_for_order(order)


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


def planes_lt_order(planes: np.ndarray, order: int) -> bool:
    """:func:`all_lt_order` of a byte-planar block ``uint8[bpn, n]`` (a wire
    v2 vector as it arrives: plane ``b`` holds byte ``b`` of every element)
    with no limb row made: elements are compared from the top plane down
    against the order's bytes, so all but the few whose top byte ties the
    order's are decided by the top plane alone. Native kernel on the
    library's threads; numpy plane compares otherwise (``generic``)."""
    bpn, n = planes.shape
    if order >> (8 * bpn):
        return True  # every value the planes can hold is a group element
    if planes.dtype != np.uint8 or planes.strides[1] != 1:
        planes = np.ascontiguousarray(planes, dtype=np.uint8)
    order_le = np.frombuffer(order.to_bytes(bpn, "little"), dtype=np.uint8)
    from ..utils import native

    lib = native.load()
    codec.count("validate", lib is not None, n)
    if lib is not None:
        return 0 == lib.xn_count_ge_planes(
            native.np_u8p(planes), n, planes.strides[0], bpn, native.np_u8p(order_le)
        )
    return _count_ge_planes(planes, order_le) == 0


def _count_ge_planes(planes: np.ndarray, order_le: np.ndarray) -> int:
    """numpy's count of the elements of byte planes that are ``>=`` the
    order, from the top plane down (the ``generic`` twin of
    ``xn_count_ge_planes``)."""
    bpn, n = planes.shape
    bad = 0
    # tied[i]: element i equals the order in every plane above the current one
    tied = np.ones(n, dtype=bool)
    for b in range(bpn - 1, -1, -1):
        bad += int(np.count_nonzero(tied & (planes[b] > order_le[b])))
        tied &= planes[b] == order_le[b]
        if not tied.any():
            return bad
    return bad + int(np.count_nonzero(tied))  # the elements equal to the order


class PlaneBuffers:
    """The ``uint8[bpn, count]`` planes :func:`wire_to_planes` writes for a
    parse, on pages kept from earlier messages.

    A vector-sized array of fresh pages costs more to touch than the pass
    that fills it (on the chip's host 4 us a page: 190 of the 192 ms of one
    178.9 MB relayout, and eight workers' faults queue behind each other;
    PERF.md section 6, PR 51), so up to ``keep`` buffers are kept and handed
    out again. Nobody gives a buffer back: every array that reaches a
    buffer's memory is a numpy view of it and holds a reference to it, so a
    kept buffer that nothing but this object refers to is free, and is found
    so by its reference count. A buffer still referred to (a vector waiting
    for its slot copy, one kept by a test) is left alone, and where all are,
    or the size asked for is not the kept buffers', fresh pages are handed
    out as ``np.empty`` would. ``keep = 0`` keeps nothing."""

    def __init__(self, keep: int = 0):
        self._keep = max(0, int(keep))
        self._lock = threading.Lock()
        # flat uint8 arrays that own their memory  # guarded-by: _lock
        self._kept: list[np.ndarray] = [np.empty(0, dtype=np.uint8)]
        # what the scan below reads of a buffer nothing else refers to
        self._unshared = self._shared_by()[0]
        self._kept.clear()

    def _shared_by(self) -> list[int]:
        return [sys.getrefcount(buf) for buf in self._kept]

    def take(self, bpn: int, count: int) -> np.ndarray:
        """``uint8[bpn, count]``, contents undefined, the caller's until its
        last view of them is gone."""
        nbytes = bpn * count
        with self._lock:
            shared_by = self._shared_by()
            if self._kept and self._kept[0].size != nbytes:
                if any(n != self._unshared for n in shared_by):
                    return np.empty((bpn, count), dtype=np.uint8)
                self._kept.clear()  # another round's vectors: start anew
            for buf, n in zip(self._kept, shared_by):
                if n == self._unshared:
                    return buf.reshape(bpn, count)
            buf = np.empty(nbytes, dtype=np.uint8)
            if len(self._kept) < self._keep:
                self._kept.append(buf)
            return buf.reshape(bpn, count)


def wire_to_planes(
    wire: np.ndarray,
    count: int,
    bpn: int,
    order: int,
    out: np.ndarray | None = None,
    column: int = 0,
    n_threads: int = 1,
) -> tuple[np.ndarray, int]:
    """``count`` interleaved ``bpn``-byte little-endian elements (a wire v1
    element block, or a segment of one) -> byte planes, plane ``b`` holding
    byte ``b`` of every element, and the number of elements ``>=`` the
    order: ``bytes_le_to_limbs`` + ``all_lt_order`` + ``pack_wire`` in one
    pass and no limb row (``xn_wire_to_planes``; numpy's transpose and plane
    compares otherwise, ``generic``). The planes are the bytes a wire v2 body
    carries and a packed staging slot holds.

    ``out`` (``uint8[bpn, >= column + count]``, unit column stride) receives
    the elements at columns ``[column, column + count)``; a fresh
    ``uint8[bpn, count]`` otherwise (a parse brings :class:`PlaneBuffers`'). Returns ``(planes, bad)``. On one thread
    of the caller's unless told otherwise: the parse runs on every ``pet-msg``
    worker at once (PERF.md section 6, PR 51)."""
    raw = np.frombuffer(wire, dtype=np.uint8, count=count * bpn)
    if out is None:
        out = np.empty((bpn, count), dtype=np.uint8)
    if (
        out.dtype != np.uint8 or out.ndim != 2 or out.shape[0] != bpn
        or out.shape[1] < column + count or (out.shape[1] > 1 and out.strides[1] != 1)
    ):
        raise ValueError("expected uint8[bpn, >= column + count] planes of unit column stride")
    # None: every value bpn bytes can hold is a group element, nothing to compare
    order_le = (
        None if order >> (8 * bpn)
        else np.frombuffer(order.to_bytes(bpn, "little"), dtype=np.uint8)
    )
    from ..utils import native

    lib = native.load()
    fast = lib is not None
    codec.count("parse", fast, count)
    if order_le is not None:
        codec.count("validate", fast, count)
    if count == 0:
        return out, 0
    if fast:
        bad = lib.xn_wire_to_planes(
            native.np_u8p(raw), count, bpn, native.np_u8p_at(out, column), out.strides[0],
            None if order_le is None else native.np_u8p(order_le), max(0, int(n_threads)),
        )
        return out, int(bad)
    view = out[:, column : column + count]
    view[...] = raw.reshape(count, bpn).T
    return out, 0 if order_le is None else _count_ge_planes(view, order_le)


def copy_planes(planes: np.ndarray, out: np.ndarray) -> None:
    """Copy byte planes ``uint8[bpn, w]`` into ``out[bpn, w]`` through both
    arrays' plane strides (a column range of a wire v2 body into a staging
    slot, whose planes are wider): the slot write of the packed wire, a copy
    and no relayout. The library's threads share the column axis (a fresh
    slot's pages are first touched there); numpy's copy otherwise."""
    bpn, width = planes.shape
    if out.shape != planes.shape or out.dtype != np.uint8 or planes.dtype != np.uint8:
        raise ValueError("expected uint8[bpn, width] planes and a destination of their shape")
    from ..utils import native

    lib = native.load()
    fast = lib is not None and width > 0 and planes.strides[1] == 1 and out.strides[1] == 1
    codec.count("stage", fast, width)
    if fast:
        lib.xn_copy_planes(
            native.np_u8p(planes), planes.strides[0], native.np_u8p(out), out.strides[0],
            bpn, width,
        )
    else:
        out[...] = planes


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


class PlanarLimbs:
    """Unmasked group elements as a device arm fetched them: ``planes`` is
    ``uint32[L, stride]``, limb ``j`` of element ``i`` at ``planes[j, i]``,
    and the first ``length`` columns are the model (``stride`` is the padded
    length on one device). The unmask decode reads the planes in place
    (``core/mask/encode.py``); :meth:`wire` is for a caller that wants the
    wire layout and pays the transposition for it. A type of its own and
    not a bare array: a vector of 1-4 elements has planes and wire rows of
    the same shape."""

    __slots__ = ("planes", "length")

    def __init__(self, planes: np.ndarray, length: int):
        assert planes.ndim == 2 and planes.dtype == _U32 and planes.shape[1] >= length
        self.planes = planes
        self.length = length

    @property
    def nbytes(self) -> int:
        return self.planes.shape[0] * self.length * 4

    def wire(self) -> np.ndarray:
        """The wire layout ``uint32[length, L]``: one strided pass."""
        unmask_stages.count_pass("transpose", self.nbytes)
        return np.ascontiguousarray(self.planes[:, : self.length].T)


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


def limbs_into_wire(
    arr: np.ndarray, bytes_per_number: int, out: np.ndarray, planar: bool = False
) -> None:
    """Write ``uint32[n, L]`` limbs as ``n`` fixed-width little-endian
    integers into ``out``, a writable contiguous ``uint8[n * bytes_per_number]``
    (a view of the message being composed: the block is packed where it is
    sent from, never in a buffer of its own). ``planar`` writes the v2
    byte-planar layout instead: plane ``b`` holds byte ``b`` of every element.
    """
    arr = np.ascontiguousarray(np.asarray(arr, dtype=_U32))
    n = arr.shape[0]
    if out.dtype != np.uint8 or out.size != n * bytes_per_number or not (
        out.flags.c_contiguous and out.flags.writeable
    ):
        raise ValueError("destination is not a writable contiguous uint8[n * bytes_per_number]")
    from ..utils import native

    lib = native.load()
    # native codecs assume the wire width and limb count agree (L == ceil(bpn/4))
    fast = lib is not None and n > 0 and arr.shape[1] == n_limbs_for_bytes(bytes_per_number)
    if fast and planar:
        # the staging ring's plane pack, with the message as its destination:
        # plane-major unit-stride writes. On one thread, as the v1 kernel
        # below: a forge seals a message a process on every core at once, and
        # sixteen threads each (the first try) made its seal of 24 messages
        # 2.5 s longer than numpy's strided copy had (PERF.md section 6, PR 50)
        lib.xn_pack_wire_planes(
            native.np_u32p(arr), n, arr.shape[1], bytes_per_number, native.np_u8p(out), n, 1
        )
        return
    if fast:
        lib.xn_limbs_to_wire(
            native.np_u32p(arr), n, bytes_per_number, arr.shape[1], native.np_u8p(out)
        )
        return
    rows = arr.astype("<u4", copy=False).view(np.uint8).reshape(n, 4 * arr.shape[1])
    rows = rows[:, :bytes_per_number]
    if planar:
        # one strided pass from the limbs' own bytes: no interleaved block
        out.reshape(bytes_per_number, n)[...] = rows.T
    else:
        out.reshape(n, bytes_per_number)[...] = rows


def limbs_to_bytes_le(arr: np.ndarray, bytes_per_number: int) -> bytes:
    """Serialize ``uint32[n, L]`` limbs as fixed-width little-endian integers."""
    out = np.empty(len(arr) * bytes_per_number, dtype=np.uint8)
    limbs_into_wire(arr, bytes_per_number, out)
    return out.tobytes()


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
