"""ChaCha20-Poly1305 open and seal and Ed25519 verify as foreign calls.

The ``cryptography`` wheel keeps the interpreter lock for its whole pass
over a buffer: while one ``pet-msg`` worker opens or verifies a 179 MB
update, the event loop and every other thread's Python stand still
(PERF.md §5). The same primitives live in the system's ``libcrypto.so.3``,
the library Python's own ``_hashlib`` links; called through ``ctypes.CDLL``
the lock is released for the call, so four workers run four passes at once
and the loop keeps running. Nothing is linked: the library is looked up at
run time, and where it does not load the wheel serves every length.

Same primitives, same answers as the wheel (RFC 8439 AEAD, RFC 8032
Ed25519 as OpenSSL verifies it: ``s < L`` enforced, small-order keys not
refused), so which route ran cannot be told from the result.

Below ``UNLOCKED_MIN`` bytes a call here costs more than it frees (a
``ctypes`` call with its contexts is tens of microseconds against the
wheel's few, and a thread that gave the lock up waits a switch interval to
get it back), so callers keep the wheel there: seed boxes, Sum messages,
task signatures, multipart chunks.

The seal is the open's twin for the sending side (a participant's Update or
Sum2 message, 179 MB and up): in place, so the message is sealed in the
buffer it was composed in and sent from, where the wheel's ``encrypt``
returns a fresh buffer of the message's length and the caller needs a
second one to put the ephemeral key in front.
"""

from __future__ import annotations

import ctypes
from typing import Optional

import numpy as np

from ...telemetry.registry import get_registry

# Where the foreign call starts to pay. Measured on the chip's idle host, one
# thread calling beside a main thread of pure Python (tools/bench_open_verify.py
# --sweep; PERF.md §6, PR 32), ms a call, wheel / libcrypto:
#   1 MiB  open 1.1 / 6.4   verify  3.1 /  7.5   main's longest stall 7.0 / 0.6
#   4 MiB  open 4.5 / 8.0   verify 11.2 / 12.0                       11.9 / 0.5
#   8 MiB  open 7.6 / 10.2  verify 17.3 / 17.1                       12.2 / 0.6
# A foreign call costs one hand-over of the lock, about 6 ms beside a busy
# thread (the 5 ms switch interval), whatever the length. Under 4 MiB the
# wheel holds the lock for less than that, and stalls others no longer than
# any thread of Python does (7 ms); from 4 MiB its hold (verify 5.7 ms alone,
# doubling with the length) is what the others wait for, while an open and a
# verify together cost the caller 4 ms more than the wheel's 16 (8 MiB: 2 of
# 25; even from there on).
UNLOCKED_MIN = 4 << 20

TAG_LENGTH = 16

BYTES = get_registry().counter(
    "xaynet_crypto_bytes_total",
    "Bytes through a sealed-box open or seal (the box) or an Ed25519 verify "
    "(the signed bytes), by the route chosen for them: unlocked = a foreign call "
    "into the system's libcrypto with the interpreter lock released, wheel = "
    "the cryptography wheel (or the pure-Python stand-in), which holds the "
    "lock: short inputs, and every input where no library loads.",
    ("op", "route"),
)

_EVP_CTRL_AEAD_GET_TAG = 0x10
_EVP_CTRL_AEAD_SET_TAG = 0x11
_EVP_PKEY_ED25519 = 1087
# EVP_DecryptUpdate and EVP_EncryptUpdate take an int length and a body
# reaches 1 << 32
_PIECE = 1 << 30


class _Lib:
    """The calls used here. The three that pass over the data are bound
    through ``ctypes.CDLL``, which releases the interpreter lock around a
    call; the others (contexts, key, tag: microseconds) through
    ``ctypes.PyDLL``, which keeps it, so an open, a seal or a verify gives
    the lock up once and not seven times: a thread that gives it up beside a busy one
    waits a switch interval (5 ms) to get it back."""

    def __init__(self, soname: str):
        unlocked, held = ctypes.CDLL(soname), ctypes.PyDLL(soname)
        vp, i, sz = ctypes.c_void_p, ctypes.c_int, ctypes.c_size_t
        for dll, name, restype, argtypes in (
            (held, "EVP_chacha20_poly1305", vp, []),
            (held, "EVP_CIPHER_CTX_new", vp, []),
            (held, "EVP_CIPHER_CTX_free", None, [vp]),
            (held, "EVP_DecryptInit_ex", i, [vp, vp, vp, ctypes.c_char_p, ctypes.c_char_p]),
            (unlocked, "EVP_DecryptUpdate", i, [vp, vp, ctypes.POINTER(i), vp, i]),
            (held, "EVP_CIPHER_CTX_ctrl", i, [vp, i, i, vp]),
            (held, "EVP_DecryptFinal_ex", i, [vp, vp, ctypes.POINTER(i)]),
            (held, "EVP_EncryptInit_ex", i, [vp, vp, vp, ctypes.c_char_p, ctypes.c_char_p]),
            (unlocked, "EVP_EncryptUpdate", i, [vp, vp, ctypes.POINTER(i), vp, i]),
            (held, "EVP_EncryptFinal_ex", i, [vp, vp, ctypes.POINTER(i)]),
            (held, "EVP_PKEY_new_raw_public_key", vp, [i, vp, ctypes.c_char_p, sz]),
            (held, "EVP_PKEY_free", None, [vp]),
            (held, "EVP_MD_CTX_new", vp, []),
            (held, "EVP_MD_CTX_free", None, [vp]),
            (held, "EVP_DigestVerifyInit", i, [vp, vp, vp, vp, vp]),
            (unlocked, "EVP_DigestVerify", i, [vp, ctypes.c_char_p, sz, vp, sz]),
            (held, "ERR_clear_error", None, []),
        ):
            fn = getattr(dll, name)  # AttributeError: an older libcrypto
            fn.restype, fn.argtypes = restype, argtypes
            setattr(self, name, fn)


_lib: Optional[_Lib] = None
_tried = False


def _open_library() -> _Lib:
    return _Lib("libcrypto.so.3")


def load() -> Optional[_Lib]:
    """The system's libcrypto with the calls below bound, or ``None``: it
    does not load, it is older than the calls, or it does not give the known
    answers (a build or a provider configuration without ChaCha20-Poly1305
    or Ed25519, as under FIPS, where every long message would else be
    refused while the wheel, which carries its own OpenSSL, opens it)."""
    global _lib, _tried
    if not _tried:
        try:
            lib = _open_library()
            _lib = lib if _answers(lib) else None
        except (OSError, AttributeError):
            _lib = None
        _tried = True  # after _lib: a second thread's first call must not read None
    return _lib


def choose(op: str, length: int) -> bool:
    """Choose the route of one ``op`` ("open", "seal" or "verify") over ``length``
    bytes and count them on it: True for the foreign call."""
    foreign = length >= UNLOCKED_MIN and load() is not None
    BYTES.labels(op=op, route="unlocked" if foreign else "wheel").inc(length)
    return foreign


def _address(buffer) -> tuple[int, np.ndarray]:
    """The address of a contiguous buffer's first byte, read-only or not,
    and the array that pins the buffer for as long as the caller holds it
    (through the foreign call)."""
    pin = np.frombuffer(buffer, dtype=np.uint8)
    return pin.ctypes.data, pin


def open_into(
    key: bytes, nonce: bytes, box, out, aad: bytes = b"", lib: Optional[_Lib] = None
) -> bool:
    """ChaCha20-Poly1305-IETF open of ``box`` (ciphertext ‖ 16-byte tag)
    into ``out[: len(box) - 16]``; ``out`` may be ``box`` itself (in place).
    False where the tag does not verify, and ``out`` then holds nothing a
    caller may use. One pass, the lock released. A sealed box has no
    associated data; ``aad`` is there for the RFC's vectors."""
    lib = lib or load()
    src, pin_src = _address(box)
    dst, pin_dst = _address(out)
    n = pin_src.size - TAG_LENGTH
    if n < 0 or pin_dst.size < n or not pin_dst.flags.writeable:
        raise ValueError("box shorter than its tag, or no writable room for its plaintext")
    ctx = lib.EVP_CIPHER_CTX_new()
    try:
        ok = lib.EVP_DecryptInit_ex(ctx, lib.EVP_chacha20_poly1305(), None, key, nonce) == 1
        done, outl = 0, ctypes.c_int(0)
        if ok and aad:
            ok = lib.EVP_DecryptUpdate(ctx, None, ctypes.byref(outl), aad, len(aad)) == 1
        while ok and done < n:
            take = min(_PIECE, n - done)
            ok = lib.EVP_DecryptUpdate(ctx, dst + done, ctypes.byref(outl), src + done, take) == 1
            done += take
        ok = ok and lib.EVP_CIPHER_CTX_ctrl(
            ctx, _EVP_CTRL_AEAD_SET_TAG, TAG_LENGTH, src + n
        ) == 1
        ok = ok and lib.EVP_DecryptFinal_ex(ctx, dst + n, ctypes.byref(outl)) == 1
    finally:
        lib.EVP_CIPHER_CTX_free(ctx)
    if not ok:
        lib.ERR_clear_error()
    return ok


def seal_into(
    key: bytes, nonce: bytes, plain, out, aad: bytes = b"", lib: Optional[_Lib] = None
) -> bool:
    """ChaCha20-Poly1305-IETF seal of ``plain`` into ``out[: len(plain) + 16]``
    (ciphertext ‖ 16-byte tag); ``out`` may begin where ``plain`` does (in
    place: the tag is written behind the ciphertext). False where the
    library refuses, and ``out`` then holds nothing a caller may send. One
    pass, the lock released. A sealed box has no associated data; ``aad`` is
    there for the RFC's vectors."""
    lib = lib or load()
    src, pin_src = _address(plain)
    dst, pin_dst = _address(out)
    n = pin_src.size
    if pin_dst.size < n + TAG_LENGTH or not pin_dst.flags.writeable:
        raise ValueError("no writable room for the ciphertext and its tag")
    ctx = lib.EVP_CIPHER_CTX_new()
    try:
        ok = lib.EVP_EncryptInit_ex(ctx, lib.EVP_chacha20_poly1305(), None, key, nonce) == 1
        done, outl = 0, ctypes.c_int(0)
        if ok and aad:
            ok = lib.EVP_EncryptUpdate(ctx, None, ctypes.byref(outl), aad, len(aad)) == 1
        while ok and done < n:
            take = min(_PIECE, n - done)
            ok = lib.EVP_EncryptUpdate(ctx, dst + done, ctypes.byref(outl), src + done, take) == 1
            done += take
        ok = ok and lib.EVP_EncryptFinal_ex(ctx, dst + n, ctypes.byref(outl)) == 1
        ok = ok and lib.EVP_CIPHER_CTX_ctrl(
            ctx, _EVP_CTRL_AEAD_GET_TAG, TAG_LENGTH, dst + n
        ) == 1
    finally:
        lib.EVP_CIPHER_CTX_free(ctx)
    if not ok:
        lib.ERR_clear_error()
    return ok


def ed25519_verify(public: bytes, signature: bytes, data, lib: Optional[_Lib] = None) -> bool:
    """Ed25519 verify of ``signature`` over ``data`` (any contiguous
    buffer), the pass over ``data`` with the lock released."""
    lib = lib or load()
    if len(signature) != 64 or len(public) != 32:
        return False
    addr, pin = _address(data)
    pkey = lib.EVP_PKEY_new_raw_public_key(_EVP_PKEY_ED25519, None, bytes(public), 32)
    mdctx = lib.EVP_MD_CTX_new()
    try:
        ok = (
            bool(pkey)
            and lib.EVP_DigestVerifyInit(mdctx, None, None, None, pkey) == 1
            and lib.EVP_DigestVerify(mdctx, bytes(signature), 64, addr, pin.size) == 1
        )
    finally:
        lib.EVP_MD_CTX_free(mdctx)
        lib.EVP_PKEY_free(pkey)
    if not ok:
        lib.ERR_clear_error()
    return ok


def _answers(lib: _Lib) -> bool:
    """Whether ``lib`` opens a known box, seals a known one (RFC 8439
    section 2.8.2) and verifies a known signature (RFC 8032 section 7.1,
    TEST 1), and refuses box and signature once damaged."""
    key, nonce = bytes(range(32)), bytes(12)
    box = bytes.fromhex("60d93b5fc8927b847dc08860b4c9956ea82b48a0c247")
    public = bytes.fromhex("d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a")
    signature = bytes.fromhex(
        "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
        "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"
    )
    rfc_plain = (
        b"Ladies and Gentlemen of the class of '99: If I could offer you "
        b"only one tip for the future, sunscreen would be it."
    )
    rfc_box = bytes.fromhex(
        "d31a8d34648e60db7b86afbc53ef7ec2a4aded51296e08fea9e2b5a736ee62d6"
        "3dbea45e8ca9671282fafb69da92728b1a71de0a9e060b2905d6a5b67ecd3b36"
        "92ddbd7f2d778b8c9803aee328091b58fab324e4fad675945585808b4831d7bc"
        "3ff4def08e4b7a9de576d26586cec64b6116"
        "1ae10b594f09e26a7e902ecbd0600691"
    )
    out, sealed = bytearray(6), bytearray(len(rfc_box))
    return (
        open_into(key, nonce, box, out, lib=lib)
        and bytes(out) == b"xaynet"
        and not open_into(key, nonce, box[:-1] + b"\x00", out, lib=lib)
        and seal_into(
            bytes(range(0x80, 0xA0)), bytes.fromhex("070000004041424344454647"),
            rfc_plain, sealed, aad=bytes.fromhex("50515253c0c1c2c3c4c5c6c7"), lib=lib,
        )
        and bytes(sealed) == rfc_box
        and ed25519_verify(public, signature, b"", lib=lib)
        and not ed25519_verify(public, signature, b"x", lib=lib)
    )
