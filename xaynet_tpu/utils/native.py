"""Loader for the native host kernels (ctypes, lazy on-demand build).

``libxaynet_native.so`` is built from ``native/xaynet_native.cpp`` on first
use (plain ``make``; no network). Everything has a pure-Python/numpy
fallback — set ``XAYNET_TPU_NO_NATIVE=1`` to force it.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

logger = logging.getLogger("xaynet.native")

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libxaynet_native.so")

_ABI_VERSION = 15

_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> bool:
    # build ONLY the kernel library this loader consumes — the participant
    # library additionally links libsodium, which may be absent on hosts
    # that only need the numpy-fallback-compatible kernels
    for args in (
        ["make", "-s", "libxaynet_native.so"],
        ["make", "-s", "libxaynet_native.so", "ARCHFLAGS="],
    ):
        try:
            subprocess.run(
                args, cwd=_NATIVE_DIR, check=True, capture_output=True, timeout=120
            )
            return True
        except Exception as e:  # retry without SIMD flags, then give up
            failure = f"{e}: {(getattr(e, 'stderr', None) or b'')[-400:]!r}"
            logger.debug("native build failed (%s): %s", args, failure)
    # said once, aloud: everything falls back to numpy and Python from here
    logger.warning("native library not built, using the fallbacks: %s", failure)
    return False


def ensure_built() -> None:
    """Build the library where it is missing or older than its source.
    Processes that may get here at the same time (test workers) take a lock
    of their own around the call: ``make`` writes the file in place."""
    if not os.path.isdir(_NATIVE_DIR):
        return
    src = os.path.join(_NATIVE_DIR, "xaynet_native.cpp")
    stale = os.path.exists(src) and (
        not os.path.exists(_LIB_PATH)
        or os.path.getmtime(src) > os.path.getmtime(_LIB_PATH)
    )
    if stale:
        _build()


def load() -> Optional[ctypes.CDLL]:
    """The native library, or None when unavailable/disabled."""
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("XAYNET_TPU_NO_NATIVE"):
        return None
    # rebuild BEFORE the first dlopen: once a (stale) library is loaded,
    # re-dlopening the same path returns the already-loaded image, so the
    # staleness check must be mtime-based, not load-and-inspect
    ensure_built()
    if not os.path.exists(_LIB_PATH):
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
        if lib.xn_abi_version() != _ABI_VERSION:
            logger.warning("native library ABI mismatch; using python fallback")
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        lib.xn_chacha20_blocks.argtypes = [u8p, ctypes.c_uint64, ctypes.c_uint64, u8p]
        lib.xn_chacha20_blocks.restype = None
        lib.xn_sample_uniform.argtypes = [
            u8p,
            ctypes.c_uint64,
            ctypes.c_uint64,
            u8p,
            ctypes.c_uint32,
            u8p,
        ]
        lib.xn_sample_uniform.restype = ctypes.c_uint64
        # streaming derive-and-sum (ABI 11): the masks of k seeds sampled
        # and summed in one call, threads inside (core/mask/derive_sum.py)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.xn_derive_sum.argtypes = [
            u8p,  # k seeds of 32 bytes
            u64p,  # k keystream byte offsets (after each seed's unit draw)
            ctypes.c_uint64,  # k
            ctypes.c_uint64,  # n
            u8p,  # order, little-endian
            ctypes.c_uint32,  # its byte length: the draw width
            ctypes.c_uint32,  # accumulator stride in bytes: 8, 12 or 16
            u8p,  # accumulator, n * stride zeroed bytes (may be `out`)
            ctypes.c_uint32,  # eager: modular add instead of lazy sums
            ctypes.c_uint32,  # n_limbs of an output element
            u32p,  # out uint32[n, n_limbs]
            u64p,  # k end cursors
            ctypes.c_uint32,  # threads
            ctypes.c_uint32,  # groups of threads, an accumulator each
            ctypes.c_uint64,  # candidates a segment
        ]
        lib.xn_derive_sum.restype = ctypes.c_int
        lib.xn_mod_add.argtypes = [u32p, u32p, u32p, ctypes.c_uint64, ctypes.c_uint32, u32p]
        lib.xn_mod_add.restype = None
        lib.xn_fold_wire_u64.argtypes = [
            u32p,
            u32p,
            u32p,
            ctypes.c_uint64,
            ctypes.c_uint32,
            ctypes.c_uint64,
            u32p,
        ]
        lib.xn_fold_wire_u64.restype = None
        lib.xn_pack_wire_planes.argtypes = [
            u32p,
            ctypes.c_uint64,  # n elements
            ctypes.c_uint32,  # n_limbs (element stride in u32)
            ctypes.c_uint32,  # bpn
            u8p,
            ctypes.c_uint64,  # out plane stride (bytes)
            ctypes.c_uint32,  # n_threads (0 = process default)
        ]
        lib.xn_pack_wire_planes.restype = None
        lib.xn_pack_planar_planes.argtypes = [
            u32p,
            ctypes.c_uint64,  # n elements
            ctypes.c_uint64,  # input plane stride (u32 elements)
            ctypes.c_uint32,  # bpn
            u8p,
            ctypes.c_uint64,  # out plane stride (bytes)
            ctypes.c_uint32,  # n_threads
        ]
        lib.xn_pack_planar_planes.restype = None
        lib.xn_mod_sub.argtypes = [u32p, u32p, u32p, ctypes.c_uint64, ctypes.c_uint32, u32p]
        lib.xn_mod_sub.restype = None
        lib.xn_copy_bytes.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64]
        lib.xn_copy_bytes.restype = None
        # ABI 12: a plane stride, and the element axis on fold_threads()
        lib.xn_decode_f64.argtypes = [
            u32p,
            ctypes.c_uint64,  # n elements
            ctypes.c_uint32,  # n_limbs
            ctypes.c_uint64,  # plane stride in u32 (0 = the wire layout)
            u8p,
            ctypes.c_uint32,
            ctypes.c_double,
            ctypes.c_double,
            ctypes.POINTER(ctypes.c_double),
        ]
        lib.xn_decode_f64.restype = ctypes.c_int
        lib.xn_decode_exact.argtypes = [
            u32p,
            ctypes.c_uint64,
            ctypes.c_uint32,
            u32p,
            ctypes.c_uint32,
            ctypes.c_double,
            ctypes.c_double,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_double),
        ]
        lib.xn_decode_exact.restype = ctypes.c_int
        lib.xn_mask_f32.argtypes = [
            u8p,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_uint64,
            u8p,
            ctypes.c_uint32,
            ctypes.c_uint32,
            ctypes.c_double,
            ctypes.c_double,
            ctypes.c_double,
            ctypes.c_double,
            u8p,
        ]
        lib.xn_mask_f32.restype = ctypes.c_uint64
        lib.xn_wire_to_limbs.argtypes = [
            u8p,
            ctypes.c_uint64,
            ctypes.c_uint32,
            ctypes.c_uint32,
            u32p,
        ]
        lib.xn_wire_to_limbs.restype = None
        lib.xn_limbs_to_wire.argtypes = [
            u32p,
            ctypes.c_uint64,
            ctypes.c_uint32,
            ctypes.c_uint32,
            u8p,
        ]
        lib.xn_limbs_to_wire.restype = None
        lib.xn_count_ge.argtypes = [u32p, ctypes.c_uint64, ctypes.c_uint32, u32p]
        lib.xn_count_ge.restype = ctypes.c_uint64
        # ABI 13: a wire v2 (byte-planar) vector scanned against the order
        # and copied into its staging slot as planes
        lib.xn_count_ge_planes.argtypes = [
            u8p,
            ctypes.c_uint64,  # n elements
            ctypes.c_uint64,  # plane stride (bytes)
            ctypes.c_uint32,  # bpn
            u8p,  # the order, bpn little-endian bytes
        ]
        lib.xn_count_ge_planes.restype = ctypes.c_uint64
        lib.xn_copy_planes.argtypes = [
            u8p,
            ctypes.c_uint64,  # source plane stride (bytes)
            u8p,
            ctypes.c_uint64,  # destination plane stride (bytes)
            ctypes.c_uint32,  # bpn
            ctypes.c_uint64,  # bytes of each plane to copy
        ]
        lib.xn_copy_planes.restype = None
        # ABI 14: a wire v1 (interleaved) vector relaid to checked byte
        # planes in one pass
        lib.xn_wire_to_planes.argtypes = [
            u8p,
            ctypes.c_uint64,  # count elements
            ctypes.c_uint32,  # bpn
            u8p,
            ctypes.c_uint64,  # plane stride (bytes)
            u8p,  # the order, bpn little-endian bytes; NULL admits all
            ctypes.c_uint32,  # n_threads (0 = process default)
        ]
        lib.xn_wire_to_planes.restype = ctypes.c_uint64
        lib.xn_fold_wire_nlimb.argtypes = list(lib.xn_fold_wire_u64.argtypes)
        lib.xn_fold_wire_nlimb.restype = ctypes.c_int
        # the REST server's direct body read (ABI 9): poll + recv in C, one
        # release of the interpreter lock for the whole body
        lib.xn_recv_exactly.argtypes = [
            ctypes.c_int,  # fd of a non-blocking stream socket
            u8p,
            ctypes.c_uint64,  # start: bytes of buf already filled
            ctypes.c_uint64,  # len of buf
            ctypes.c_double,  # seconds allowed
        ]
        lib.xn_recv_exactly.restype = ctypes.c_uint64
        # ABI 15: what the worker threads that the calling thread started
        # and joined have spent, for the spans that read their usage
        lib.xn_workers_spent.argtypes = [ctypes.POINTER(ctypes.c_uint64)]
        lib.xn_workers_spent.restype = None
        _lib = lib
        from ..telemetry import tracing

        tracing.set_workers_reader(_workers_spent)
    except (OSError, AttributeError) as e:
        # AttributeError: a stale prebuilt .so missing newer symbols when the
        # rebuild could not run — degrade to the python fallback, not a crash
        logger.warning("native library load failed; using python fallback: %s", e)
        _lib = None
    return _lib


_workers_buf = threading.local()


def _workers_spent() -> tuple:
    """What the library's worker threads started and joined by the calling
    thread have spent so far, as ``telemetry/tracing.py`` reads a usage:
    user and system seconds, minor and major faults, voluntary and
    involuntary switches."""
    buf = getattr(_workers_buf, "buf", None)
    if buf is None:
        buf = _workers_buf.buf = (ctypes.c_uint64 * 6)()
    _lib.xn_workers_spent(buf)
    return (1e-6 * buf[0], 1e-6 * buf[1], buf[2], buf[3], buf[4], buf[5])


# ``bytearray(n)`` zero-fills its n bytes under the interpreter lock: 110 ms
# of the event loop for a 179 MB body that recv() (or a sealed box's open) is
# about to overwrite. The C API's constructor, given no source, allocates and
# touches nothing: ``uninitialised_bytearray(None, n)``.
uninitialised_bytearray = ctypes.PYFUNCTYPE(
    ctypes.py_object, ctypes.c_char_p, ctypes.c_ssize_t
)(("PyByteArray_FromStringAndSize", ctypes.pythonapi))


# The same for ``bytes`` (and where its buffer lies), for :func:`tobytes`.
_uninitialised_bytes = ctypes.PYFUNCTYPE(
    ctypes.py_object, ctypes.c_char_p, ctypes.c_ssize_t
)(("PyBytes_FromStringAndSize", ctypes.pythonapi))
_bytes_address = ctypes.PYFUNCTYPE(ctypes.c_void_p, ctypes.py_object)(
    ("PyBytes_AsString", ctypes.pythonapi)
)


def as_u8p(buf) -> "ctypes.pointer":
    return ctypes.cast(ctypes.c_char_p(bytes(buf)), ctypes.POINTER(ctypes.c_uint8))


def np_u8p(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def np_u32p(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))


def np_u64p(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


def np_u8p_at(arr, byte_offset: int):
    """Pointer to ``arr``'s buffer offset by ``byte_offset`` bytes (the
    packed-plane twin of :func:`np_u32p_at`)."""
    return ctypes.cast(
        ctypes.c_void_p(arr.ctypes.data + byte_offset),
        ctypes.POINTER(ctypes.c_uint8),
    )


def np_u32p_at(arr, element_offset: int):
    """Pointer to ``arr``'s buffer offset by ``element_offset`` uint32
    elements — how the plane-pack kernels address one shard's column
    slice of a larger C-contiguous array without materializing a copy."""
    return ctypes.cast(
        ctypes.c_void_p(arr.ctypes.data + 4 * element_offset),
        ctypes.POINTER(ctypes.c_uint32),
    )


# under this many bytes numpy's own copy is as fast as starting threads
_THREADED_COPY_MIN = 1 << 22


def tobytes(arr) -> bytes:
    """``arr.tobytes()`` of a C-contiguous array, the copy made on the
    library's threads: serialising a vector-sized model into a fresh
    ``bytes`` is bound by the first touch of its pages (204 MB: 210-240 ms
    on one thread of the chip's host), which every core then shares. The
    ``bytes`` is allocated uninitialised and filled before anyone else
    holds it. Short arrays, and every array without the library, take
    numpy's copy."""
    lib = load()
    if lib is None or arr.nbytes < _THREADED_COPY_MIN or not arr.flags.c_contiguous:
        return arr.tobytes()
    out = _uninitialised_bytes(None, arr.nbytes)
    lib.xn_copy_bytes(arr.ctypes.data, _bytes_address(out), arr.nbytes)
    return out
