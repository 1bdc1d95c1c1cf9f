"""Sealed-box asymmetric encryption (C25519).

Functional port of the reference's `EncryptKeyPair` /
`PublicEncryptKey::encrypt` / `SecretEncryptKey::decrypt` (reference:
rust/xaynet-core/src/crypto/encrypt.rs:16-164). A sealed box is anonymous
public-key encryption: an ephemeral X25519 key agrees a shared secret with
the recipient's public key; the ephemeral public key travels in the
ciphertext header.

Construction: ``eph_pk(32) || ChaCha20Poly1305(msg)`` with
``key = HKDF-SHA256(X25519(eph_sk, pk), info = eph_pk || pk)`` and a zero
nonce (the key is single-use). Overhead = 32 + 16 = 48 bytes = SEALBYTES,
matching the reference's wire constant.

Backend: the ``cryptography`` wheel when importable, otherwise the
pure-stdlib RFC-conformant fallback (``_purecrypto``) — byte-identical
output, not constant-time; fine for tests/simulation, pip the wheel for
production coordinators.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

try:  # native primitives when the wheel is present ...
    from cryptography.exceptions import InvalidTag
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric.x25519 import X25519PrivateKey, X25519PublicKey
    from cryptography.hazmat.primitives.ciphers.aead import ChaCha20Poly1305
    from cryptography.hazmat.primitives.kdf.hkdf import HKDF

    _HAVE_CRYPTO = True
except ImportError:  # ... pure-stdlib fallback otherwise (see _purecrypto)
    from . import _purecrypto

    _HAVE_CRYPTO = False

from ...utils import native
from . import unlocked

SEALBYTES = 48
PUBLIC_KEY_LENGTH = 32
SECRET_KEY_LENGTH = 32
SEED_LENGTH = 32

_ZERO_NONCE = b"\x00" * 12


class DecryptError(ValueError):
    """Sealed box could not be opened."""


def _agree(secret: bytes, public: bytes) -> bytes:
    """X25519 of our secret key and their public key."""
    if not _HAVE_CRYPTO:
        return _purecrypto.x25519(secret, public)
    return X25519PrivateKey.from_private_bytes(secret).exchange(
        X25519PublicKey.from_public_bytes(public)
    )


def _derive_key(shared: bytes, eph_pk: bytes, recipient_pk: bytes) -> bytes:
    info = b"xaynet-tpu-sealedbox" + eph_pk + recipient_pk
    if not _HAVE_CRYPTO:
        return _purecrypto.hkdf_sha256(shared, info, 32)
    hkdf = HKDF(algorithm=hashes.SHA256(), length=32, salt=None, info=info)
    return hkdf.derive(shared)


@dataclass(frozen=True)
class PublicEncryptKey:
    bytes_: bytes

    def __post_init__(self):
        if len(self.bytes_) != PUBLIC_KEY_LENGTH:
            raise ValueError("public encrypt key must be 32 bytes")

    def as_bytes(self) -> bytes:
        return self.bytes_

    def encrypt(self, message: bytes) -> bytes:
        """Seal ``message`` for this public key (anyone can seal)."""
        box = native.uninitialised_bytearray(None, len(message) + SEALBYTES)
        box[PUBLIC_KEY_LENGTH : len(box) - unlocked.TAG_LENGTH] = message
        self.encrypt_in_place(box)
        return bytes(box)

    def encrypt_in_place(self, box: "bytearray | memoryview") -> str:
        """:meth:`encrypt` for a caller that composed the message where the
        box will be: ``box`` holds the plaintext at ``[32 : len(box) - 16]``
        and leaves as the sealed box (ephemeral key in front, tag behind),
        with no second buffer of the message's size. A long message is
        sealed by a foreign call with the interpreter lock released
        (``unlocked``), a short one by the wheel. Returns the route taken
        (``unlocked.choose``'s, for the caller's span)."""
        if len(box) < SEALBYTES:
            raise ValueError("no room for the ephemeral key and the tag")
        view = memoryview(box)
        if view.readonly:
            raise TypeError("encrypt_in_place needs a writable buffer")
        ephemeral = EncryptKeyPair.generate()
        eph_pk = ephemeral.public.as_bytes()
        key = _derive_key(_agree(ephemeral.secret.as_bytes(), self.bytes_), eph_pk, self.bytes_)
        view[:PUBLIC_KEY_LENGTH] = eph_pk
        sealed = view[PUBLIC_KEY_LENGTH:]
        plain = sealed[: len(sealed) - unlocked.TAG_LENGTH]
        # one algorithm, routed by the box's length (unlocked.UNLOCKED_MIN)
        if unlocked.choose("seal", len(sealed)):
            if not unlocked.seal_into(key, _ZERO_NONCE, plain, sealed):
                raise RuntimeError("libcrypto refused to seal")
            return "unlocked"
        if _HAVE_CRYPTO:
            sealed[:] = ChaCha20Poly1305(key).encrypt(_ZERO_NONCE, plain, None)
        else:
            sealed[:] = _purecrypto.chacha20poly1305_encrypt(key, _ZERO_NONCE, bytes(plain))
        return "wheel"


@dataclass(frozen=True)
class SecretEncryptKey:
    bytes_: bytes

    def __post_init__(self):
        if len(self.bytes_) != SECRET_KEY_LENGTH:
            raise ValueError("secret encrypt key must be 32 bytes")

    def as_bytes(self) -> bytes:
        return self.bytes_

    def public_key(self) -> PublicEncryptKey:
        if _HAVE_CRYPTO:
            sk = X25519PrivateKey.from_private_bytes(self.bytes_)
            return PublicEncryptKey(sk.public_key().public_bytes_raw())
        return PublicEncryptKey(_purecrypto.x25519_public(self.bytes_))

    def decrypt(
        self, sealed: "bytes | bytearray | memoryview", pk: "PublicEncryptKey | None" = None
    ) -> "bytes | memoryview":
        """Open a sealed box addressed to this key.

        ``pk`` (our own public key) is accepted for reference API parity; it
        is recomputed when omitted. ``sealed`` is read through the buffer
        protocol and never written to, whatever its type: the ciphertext of
        a 179 MB upload is not copied first. A long box is opened by a
        foreign call with the interpreter lock released (``unlocked``) into
        one buffer allocated at the plaintext's length, and the plaintext is
        a view of it; a short one by the wheel, as ``bytes``.
        """
        return self._open(sealed, pk, in_place=False)

    def decrypt_in_place(
        self, sealed: "bytearray | memoryview", pk: "PublicEncryptKey | None" = None
    ) -> "bytes | memoryview":
        """:meth:`decrypt` for a caller that owns ``sealed`` and gives it up:
        a long box's plaintext is written over its ciphertext, so no second
        buffer of the body's size exists, and the view returned keeps
        ``sealed`` alive. Whatever the outcome, ``sealed`` no longer holds
        the box. A read-only buffer is refused."""
        if memoryview(sealed).readonly:
            raise TypeError("decrypt_in_place needs a writable buffer")
        return self._open(sealed, pk, in_place=True)

    def _open(self, sealed, pk: "PublicEncryptKey | None", in_place: bool) -> "bytes | memoryview":
        if len(sealed) < SEALBYTES:
            raise DecryptError("sealed box too short")
        my_pk = pk.as_bytes() if pk is not None else self.public_key().as_bytes()
        view = memoryview(sealed)
        eph_pk, ct = bytes(view[:32]), view[32:]
        key = _derive_key(_agree(self.bytes_, eph_pk), eph_pk, my_pk)
        # one algorithm, routed by the box's length (unlocked.UNLOCKED_MIN)
        if unlocked.choose("open", len(ct)):
            size = len(ct) - unlocked.TAG_LENGTH
            out = ct if in_place else native.uninitialised_bytearray(None, size)
            if not unlocked.open_into(key, _ZERO_NONCE, ct, out):
                raise DecryptError("sealed box authentication failed")
            return memoryview(out)[:size]
        if _HAVE_CRYPTO:
            try:
                return ChaCha20Poly1305(key).decrypt(_ZERO_NONCE, ct, None)
            except InvalidTag as e:
                raise DecryptError("sealed box authentication failed") from e
        try:
            return _purecrypto.chacha20poly1305_decrypt(key, _ZERO_NONCE, bytes(ct))
        except _purecrypto.AeadTagError as e:
            raise DecryptError("sealed box authentication failed") from e


@dataclass(frozen=True)
class EncryptKeyPair:
    public: PublicEncryptKey
    secret: SecretEncryptKey

    @classmethod
    def generate(cls) -> "EncryptKeyPair":
        if not _HAVE_CRYPTO:
            return cls.derive_from_seed(os.urandom(SEED_LENGTH))
        sk = X25519PrivateKey.generate()
        return cls(
            public=PublicEncryptKey(sk.public_key().public_bytes_raw()),
            secret=SecretEncryptKey(sk.private_bytes_raw()),
        )

    @classmethod
    def derive_from_seed(cls, seed: bytes) -> "EncryptKeyPair":
        """Deterministic keypair from a 32-byte seed."""
        if len(seed) != SEED_LENGTH:
            raise ValueError("seed must be 32 bytes")
        if not _HAVE_CRYPTO:
            return cls(
                public=PublicEncryptKey(_purecrypto.x25519_public(seed)),
                secret=SecretEncryptKey(bytes(seed)),
            )
        sk = X25519PrivateKey.from_private_bytes(seed)
        return cls(
            public=PublicEncryptKey(sk.public_key().public_bytes_raw()),
            secret=SecretEncryptKey(sk.private_bytes_raw()),
        )


def generate_seed() -> bytes:
    return os.urandom(SEED_LENGTH)
