"""The sealed-box open and the Ed25519 verify on their two routes
(core/crypto/unlocked.py, docs/DESIGN.md §16 "How a message is opened"): the
foreign call into the system's libcrypto with the interpreter lock released,
for long inputs, and the wheel's call for short ones. Same plaintext, same
verdicts, same exceptions; the route follows the input's length and nothing
else; ``decrypt()`` never writes to its argument and ``decrypt_in_place()``
opens a writable buffer over itself; the lock is really free."""

import asyncio
import threading
import time

import numpy as np
import pytest

from xaynet_tpu.core.crypto import unlocked
from xaynet_tpu.core.crypto.encrypt import DecryptError, EncryptKeyPair, SEALBYTES
from xaynet_tpu.core.crypto.sign import SigningKeyPair, verify_detached
from xaynet_tpu.core.mask.serialization import DecodeError
from xaynet_tpu.core.message import Message, Sum, Tag

pytestmark = pytest.mark.skipif(
    unlocked.load() is None, reason="the system's libcrypto does not load here"
)

MIN = unlocked.UNLOCKED_MIN
LENGTHS = [0, 1, 47, 48, MIN - 1, MIN, MIN + 1, (1 << 20) + 1, 8 << 20]
KINDS = ["bytes", "bytearray", "memoryview"]
ROUTES = ["wheel", "unlocked"]

KEYS = EncryptKeyPair.derive_from_seed(bytes(range(32)))
SIGNER = SigningKeyPair.derive_from_seed(bytes(range(32, 64)))


@pytest.fixture
def route(request, monkeypatch):
    """Force every length onto one route."""
    monkeypatch.setattr(unlocked, "UNLOCKED_MIN", 0 if request.param == "unlocked" else 1 << 62)
    return request.param


def _bytes_of(n: int) -> bytes:
    block = np.random.default_rng(n).integers(0, 256, min(n, 1 << 16), dtype=np.uint8).tobytes()
    return (block * (n // len(block) + 1))[:n] if n else b""


_CASES: dict = {}


def _case(n: int) -> tuple[bytes, bytes, bytes]:
    """(plaintext, its sealed box, its signature), made once a length."""
    if n not in _CASES:
        plain = _bytes_of(n)
        _CASES[n] = (plain, KEYS.public.encrypt(plain), SIGNER.sign(plain).as_bytes())
    return _CASES[n]


def _as(kind: str, data: bytes):
    """``data`` as ``bytes``, a ``bytearray``, or a ``memoryview`` sliced out
    of the middle of a larger buffer."""
    if kind == "bytes":
        return data
    if kind == "bytearray":
        return bytearray(data)
    return memoryview(bytearray(b"\xaa" * 7 + data + b"\xbb" * 5))[7 : 7 + len(data)]


def _moved() -> dict:
    return {key: child.value for key, child in unlocked.BYTES.children()}


def _delta(before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in _moved().items() if v != before.get(k, 0)}


# --- parity ---------------------------------------------------------------


@pytest.mark.parametrize("route", ROUTES, indirect=True)
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n", LENGTHS)
def test_open_gives_the_plaintext_on_both_routes(n, kind, route):
    plain, sealed, _ = _case(n)
    before = _moved()
    out = KEYS.secret.decrypt(_as(kind, sealed), KEYS.public)
    assert bytes(out) == plain
    assert _delta(before) == {("open", route): n + SEALBYTES - 32}


@pytest.mark.parametrize("route", ROUTES, indirect=True)
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n", LENGTHS)
def test_verify_gives_the_verdict_on_both_routes(n, kind, route):
    plain, _, signature = _case(n)
    before = _moved()
    assert verify_detached(SIGNER.public, signature, _as(kind, plain)) is True
    assert _delta(before) == ({("verify", route): n} if n else {})
    if n:
        other = bytearray(plain)
        other[n // 2] ^= 1
        assert verify_detached(SIGNER.public, signature, _as(kind, bytes(other))) is False


def _flip(data: bytes, at: int) -> bytes:
    out = bytearray(data)
    out[at] ^= 0x01
    return bytes(out)


@pytest.mark.parametrize("route", ROUTES, indirect=True)
@pytest.mark.parametrize("n", [48, (1 << 20) + 1])
@pytest.mark.parametrize("what", ["tag", "body", "ephemeral key", "truncated", "short"])
def test_a_damaged_box_is_refused_alike(what, n, route):
    _, sealed, _ = _case(n)
    damaged = {
        "tag": _flip(sealed, len(sealed) - 1),
        "body": _flip(sealed, 32 + n // 2),
        "ephemeral key": _flip(sealed, 5),
        "truncated": sealed[:-1],
        "short": sealed[: SEALBYTES - 1],
    }[what]
    with pytest.raises(DecryptError):
        KEYS.secret.decrypt(damaged, KEYS.public)
    with pytest.raises(DecryptError):
        KEYS.secret.decrypt_in_place(bytearray(damaged), KEYS.public)


@pytest.mark.parametrize("route", ROUTES, indirect=True)
@pytest.mark.parametrize("n", [1, (1 << 20) + 1])
@pytest.mark.parametrize("what", ["signature R", "signature s", "public key", "short signature",
                                  "short key", "s not reduced"])
def test_a_damaged_signature_is_refused_alike(what, n, route):
    plain, _, signature = _case(n)
    public = SIGNER.public
    order = (1 << 252) + 27742317777372353535851937790883648493
    unreduced = signature[:32] + (int.from_bytes(signature[32:], "little") + order).to_bytes(32, "little")
    public, signature = {
        "signature R": (public, _flip(signature, 3)),
        "signature s": (public, _flip(signature, 40)),
        "public key": (_flip(public, 7), signature),
        "short signature": (public, signature[:63]),
        "short key": (public[:31], signature),
        "s not reduced": (public, unreduced),  # RFC 8032 5.1.7: s < L, on both routes
    }[what]
    assert verify_detached(public, signature, plain) is False


@pytest.mark.parametrize("route", ROUTES, indirect=True)
def test_a_message_with_a_bad_signature_raises_decode_error(route):
    message = Message(
        participant_pk=SIGNER.public, coordinator_pk=KEYS.public.as_bytes(),
        payload=Sum(sum_signature=b"\x01" * 64, ephm_pk=b"\x02" * 32), tag=Tag.SUM,
    ).to_bytes(SIGNER.secret)
    assert Message.from_bytes(message).participant_pk == SIGNER.public
    with pytest.raises(DecodeError, match="invalid message signature"):
        Message.from_bytes(_flip(message, len(message) - 1))


# --- the RFCs' vectors ------------------------------------------------------

# RFC 8439, 2.8.2
_AEAD_KEY = bytes(range(0x80, 0xA0))
_AEAD_NONCE = bytes.fromhex("070000004041424344454647")
_AEAD_AAD = bytes.fromhex("50515253c0c1c2c3c4c5c6c7")
_AEAD_PLAIN = (
    b"Ladies and Gentlemen of the class of '99: If I could offer you "
    b"only one tip for the future, sunscreen would be it."
)
_AEAD_BOX = bytes.fromhex(
    "d31a8d34648e60db7b86afbc53ef7ec2a4aded51296e08fea9e2b5a736ee62d6"
    "3dbea45e8ca9671282fafb69da92728b1a71de0a9e060b2905d6a5b67ecd3b36"
    "92ddbd7f2d778b8c9803aee328091b58fab324e4fad675945585808b4831d7bc"
    "3ff4def08e4b7a9de576d26586cec64b6116"
    "1ae10b594f09e26a7e902ecbd0600691"
)


@pytest.mark.parametrize("route_name", ROUTES)
def test_rfc_8439_aead_vector(route_name):
    if route_name == "wheel":
        from cryptography.hazmat.primitives.ciphers.aead import ChaCha20Poly1305

        assert ChaCha20Poly1305(_AEAD_KEY).decrypt(_AEAD_NONCE, _AEAD_BOX, _AEAD_AAD) == _AEAD_PLAIN
        return
    out = bytearray(len(_AEAD_PLAIN))
    assert unlocked.open_into(_AEAD_KEY, _AEAD_NONCE, _AEAD_BOX, out, _AEAD_AAD) is True
    assert bytes(out) == _AEAD_PLAIN
    assert unlocked.open_into(_AEAD_KEY, _AEAD_NONCE, _AEAD_BOX, out) is False  # the AAD is bound
    box = bytearray(_AEAD_BOX)  # in place: the plaintext lies over the ciphertext
    assert unlocked.open_into(_AEAD_KEY, _AEAD_NONCE, box, box, _AEAD_AAD) is True
    assert bytes(box[: len(_AEAD_PLAIN)]) == _AEAD_PLAIN


# RFC 8032, 7.1: TEST 1, TEST 2, TEST 3, TEST SHA(abc)
_ED25519_VECTORS = [
    ("d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a", "",
     "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
     "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"),
    ("3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c", "72",
     "92a009a9f0d4cab8720e820b5f642540a2b27b5416503f8fb3762223ebdb69da"
     "085ac1e43e15996e458f3613d0f11d8c387b2eaeb4302aeeb00d291612bb0c00"),
    ("fc51cd8e6218a1a38da47ed00230f0580816ed13ba3303ac5deb911548908025", "af82",
     "6291d657deec24024827e69c3abe01a30ce548a284743a445e3680d7db5ac3ac"
     "18ff9b538d16f290ae67f760984dc6594a7c15e9716ed28dc027beceea1ec40a"),
    ("ec172b93ad5e563bf4932c70e1245034c35467ef2efd4d64ebf819683467e2bf",
     "ddaf35a193617abacc417349ae20413112e6fa4e89a97ea20a9eeee64b55d39a"
     "2192992a274fc1a836ba3c23a3feebbd454d4423643ce80e2a9ac94fa54ca49f",
     "dc2a4459e7369633a52b1bf277839a00201009a3efbf3ecb69bea2186c26b589"
     "09351fc9ac90b3ecfdfbc7c66431e0303dca179c138ac17ad9bef1177331a704"),
]


@pytest.mark.parametrize("route", ROUTES, indirect=True)
@pytest.mark.parametrize("vector", range(len(_ED25519_VECTORS)))
def test_rfc_8032_ed25519_vectors(vector, route):
    public, message, signature = (bytes.fromhex(x) for x in _ED25519_VECTORS[vector])
    assert verify_detached(public, signature, message) is True
    assert verify_detached(public, signature, message + b"\x00") is False


# Adversarial encodings: small-order and non-canonical keys and points, s at and
# over the order (the twelve vectors of Chalkias, Garillot, Nikolaenko, "Taming
# the many EdDSAs", 2020, and the identity as a key). What matters here is not
# which of them OpenSSL accepts but that the foreign call is never laxer, nor
# stricter, than the wheel: (message, public key, signature).
_IDENTITY = "01" + "00" * 31
_EDGE_VECTORS = [
    ("68656c6c6f", _IDENTITY, _IDENTITY + "00" * 32),
    ("68656c6c6f", "ec" + "ff" * 30 + "7f", _IDENTITY + "00" * 32),
    ("78", "ee" + "ff" * 30 + "7f", _IDENTITY + "00" * 32),
    ("8c93255d71dcab10e8f379c26200f3c7bd5f09d9bc3068d3ef4edeb4853022b6",
     "c7176a703d4dd84fba3c0b760d10670f2a2053fa2c39ccc64ec7fd7792ac03fa",
     "c7176a703d4dd84fba3c0b760d10670f2a2053fa2c39ccc64ec7fd7792ac037a" + "00" * 32),
    ("9bd9f44f4dcc75bd531b56b2cd280b0bb38fc1cd6d1230e14861d861de092e79",
     "c7176a703d4dd84fba3c0b760d10670f2a2053fa2c39ccc64ec7fd7792ac03fa",
     "f7badec5b8abeaf699583992219b7b223f1df3fbbea919844e3f7c554a43dd43"
     "a5bb704786be79fc476f91d3f3f89b03984d8068dcf1bb7dfc6637b45450ac04"),
    ("aebf3f2601a0c8c5d39cc7d8911642f740b78168218da8471772b35f9d35b9ab",
     "f7badec5b8abeaf699583992219b7b223f1df3fbbea919844e3f7c554a43dd43",
     "c7176a703d4dd84fba3c0b760d10670f2a2053fa2c39ccc64ec7fd7792ac03fa"
     "8c4bd45aecaca5b24fb97bc10ac27ac8751a7dfe1baff8b953ec9f5833ca260e"),
    ("9bd9f44f4dcc75bd531b56b2cd280b0bb38fc1cd6d1230e14861d861de092e79",
     "cdb267ce40c5cd45306fa5d2f29731459387dbf9eb933b7bd5aed9a765b88d4d",
     "9046a64750444938de19f227bb80485e92b83fdb4b6506c160484c016cc1852f"
     "87909e14428a7a1d62e9f22f3d3ad7802db02eb2e688b6c52fcd6648a98bd009"),
    ("e47d62c63f830dc7a6851a0b1f33ae4bb2f507fb6cffec4011eaccd55b53f56c",
     "cdb267ce40c5cd45306fa5d2f29731459387dbf9eb933b7bd5aed9a765b88d4d",
     "160a1cb0dc9c0258cd0a7d23e94d8fa878bcb1925f2c64246b2dee1796bed512"
     "5ec6bc982a269b723e0668e540911a9a6a58921d6925e434ab10aa7940551a09"),
    ("e47d62c63f830dc7a6851a0b1f33ae4bb2f507fb6cffec4011eaccd55b53f56c",
     "cdb267ce40c5cd45306fa5d2f29731459387dbf9eb933b7bd5aed9a765b88d4d",
     "21122a84e0b5fca4052f5b1235c80a537878b38f3142356b2c2384ebad4668b7"
     "e40bc836dac0f71076f9abe3a53f9c03c1ceeeddb658d0030494ace586687405"),
    ("85e241a07d148b41e47d62c63f830dc7a6851a0b1f33ae4bb2f507fb6cffec40",
     "442aad9f089ad9e14647b1ef9099a1ff4798d78589e66f28eca69c11f582a623",
     "e96f66be976d82e60150baecff9906684aebb1ef181f67a7189ac78ea23b6c0e"
     "547f7690a0e2ddcd04d87dbc3490dc19b3b3052f7ff0538cb68afb369ba3a514"),
    ("85e241a07d148b41e47d62c63f830dc7a6851a0b1f33ae4bb2f507fb6cffec40",
     "442aad9f089ad9e14647b1ef9099a1ff4798d78589e66f28eca69c11f582a623",
     "8ce5b96c8f26d0ab6c47958c9e68b937104cd36e13c33566acd2fe8d38aa1942"
     "7e71f98a473474f2f13f06f97c20d58cc3f54b8bd0d272f42b695dd7e89a8c22"),
    ("9bedc267423725d473888631ebf45988bad3db83851ee85c85e241a07d148b41",
     "f7badec5b8abeaf699583992219b7b223f1df3fbbea919844e3f7c554a43dd43",
     "ecffffffffffffffffffffffffffffffffffffffffffffffffffffffffffffff"
     "03be9678ac102edcd92b0210bb34d7428d12ffc5df5f37e359941266a4e35f0f"),
    ("9bedc267423725d473888631ebf45988bad3db83851ee85c85e241a07d148b41",
     "f7badec5b8abeaf699583992219b7b223f1df3fbbea919844e3f7c554a43dd43",
     "ecffffffffffffffffffffffffffffffffffffffffffffffffffffffffffffff"
     "ca8c5b64cd208982aa38d4936621a4775aa233aa0505711d8fdcfdaa943d4908"),
    ("e96b7021eb39c1a163b6da4e3093dcd3f21387da4cc4572be588fafae23c155b",
     "ecffffffffffffffffffffffffffffffffffffffffffffffffffffffffffffff",
     "a9d55260f765261eb9b84e106f665e00b867287a761990d7135963ee0a7d59dc"
     "a5bb704786be79fc476f91d3f3f89b03984d8068dcf1bb7dfc6637b45450ac04"),
    ("39a591f5321bbe07fd5a23dc2f39d025d74526615746727ceefd6e82ae65c06f",
     "ecffffffffffffffffffffffffffffffffffffffffffffffffffffffffffffff",
     "a9d55260f765261eb9b84e106f665e00b867287a761990d7135963ee0a7d59dc"
     "a5bb704786be79fc476f91d3f3f89b03984d8068dcf1bb7dfc6637b45450ac04"),
]


@pytest.mark.parametrize("vector", range(len(_EDGE_VECTORS)))
def test_adversarial_encodings_get_one_verdict_on_both_routes(vector, monkeypatch):
    message, public, signature = (bytes.fromhex(x) for x in _EDGE_VECTORS[vector])
    verdicts = {}
    for route_name, floor in (("wheel", 1 << 62), ("unlocked", 0)):
        monkeypatch.setattr(unlocked, "UNLOCKED_MIN", floor)
        verdicts[route_name] = verify_detached(public, signature, message)
    assert verdicts["unlocked"] is verdicts["wheel"], verdicts


# --- whose buffer it is ------------------------------------------------------


@pytest.mark.parametrize("route", ROUTES, indirect=True)
@pytest.mark.parametrize("kind", KINDS)
def test_decrypt_never_writes_to_its_argument(kind, route):
    plain, sealed, _ = _case((1 << 20) + 1)
    given = _as(kind, sealed)
    out = KEYS.secret.decrypt(given, KEYS.public)
    assert bytes(given) == sealed and bytes(out) == plain
    with pytest.raises(DecryptError):  # nor where the tag fails
        KEYS.secret.decrypt(_as(kind, _flip(sealed, 40)), KEYS.public)


@pytest.mark.parametrize("given", ["bytes", "read-only memoryview"])
def test_the_in_place_entry_refuses_a_read_only_buffer(given):
    _, sealed, _ = _case(MIN)
    with pytest.raises(TypeError, match="writable"):
        KEYS.secret.decrypt_in_place(sealed if given == "bytes" else memoryview(sealed), KEYS.public)


@pytest.mark.parametrize("kind", ["bytearray", "memoryview"])
def test_a_long_box_is_opened_over_its_own_buffer(kind):
    plain, sealed, _ = _case(MIN)
    given = _as(kind, sealed)
    out = KEYS.secret.decrypt_in_place(given, KEYS.public)
    assert isinstance(out, memoryview) and bytes(out) == plain
    # the plaintext lies where the ciphertext lay: no second buffer
    assert np.shares_memory(np.frombuffer(out, np.uint8), np.frombuffer(given, np.uint8))
    assert bytes(memoryview(given)[32 : 32 + len(plain)]) == plain


def test_a_short_box_given_up_is_opened_by_the_wheel():
    plain, sealed, _ = _case(48)
    before = _moved()
    assert KEYS.secret.decrypt_in_place(bytearray(sealed), KEYS.public) == plain
    assert _delta(before) == {("open", "wheel"): 48 + 16}


# --- the route follows the length --------------------------------------------


@pytest.mark.parametrize("n,route_name", [(MIN - 1, "wheel"), (MIN, "unlocked")])
def test_the_crossover_routes_the_open(n, route_name):
    plain = _bytes_of(n - 16)  # the box the route is chosen on: plaintext and tag
    sealed = KEYS.public.encrypt(plain)  # counted as a seal, before the snapshot
    before = _moved()
    assert bytes(KEYS.secret.decrypt(sealed, KEYS.public)) == plain
    assert _delta(before) == {("open", route_name): n}


@pytest.mark.parametrize("n,route_name", [(MIN - 1, "wheel"), (MIN, "unlocked")])
def test_the_crossover_routes_the_verify(n, route_name):
    plain = _bytes_of(n)
    before = _moved()
    assert verify_detached(SIGNER.public, SIGNER.sign(plain).as_bytes(), plain)
    assert _delta(before) == {("verify", route_name): n}


def test_small_protocol_messages_stay_on_the_wheel():
    """A seed box (80 bytes) and a task signature (35 bytes signed)."""
    from xaynet_tpu.core.mask.seed import MaskSeed

    before = _moved()
    seed = MaskSeed.generate()
    assert seed.encrypt(KEYS.public).decrypt(KEYS.secret, KEYS.public).as_bytes() == seed.as_bytes()
    assert verify_detached(SIGNER.public, SIGNER.sign(b"s" * 32 + b"sum").as_bytes(), b"s" * 32 + b"sum")
    assert set(_delta(before)) == {("seal", "wheel"), ("open", "wheel"), ("verify", "wheel")}


# --- the pipeline -----------------------------------------------------------


def _round_over_rest(monkeypatch) -> dict:
    """One served round (tests/test_message_stages.py's) whose 120 KB updates
    count as large bodies: read directly into a ``bytearray``, and over the
    crossover."""
    import test_message_stages as served

    from xaynet_tpu.server import rest

    monkeypatch.setattr(rest, "DIRECT_BODY_MIN", 1 << 10)
    monkeypatch.setattr(unlocked, "UNLOCKED_MIN", 1 << 10)
    before = _moved()
    asyncio.run(asyncio.wait_for(served._round(), timeout=120))
    return _delta(before)


def test_a_served_round_opens_its_updates_in_place_and_unlocked(monkeypatch):
    moved = _round_over_rest(monkeypatch)
    updates = 4 * 6 * 20_011  # N_UPDATE bodies of over 6 bytes an element
    assert moved[("open", "unlocked")] > updates and moved[("verify", "unlocked")] > updates
    # Sum messages and seed boxes are under a KiB
    assert 0 < moved[("open", "wheel")] < 1 << 14


def test_with_no_library_a_round_passes_on_the_wheel(monkeypatch):
    def refuse():
        raise OSError("libcrypto.so.3: cannot open shared object file")

    monkeypatch.setattr(unlocked, "_open_library", refuse)
    monkeypatch.setattr(unlocked, "_tried", False)
    monkeypatch.setattr(unlocked, "_lib", None)
    moved = _round_over_rest(monkeypatch)
    assert unlocked.load() is None
    assert {route_name for _, route_name in moved} == {"wheel"}
    assert moved[("open", "wheel")] > 4 * 6 * 20_011


@pytest.mark.parametrize("missing", ["EVP_DecryptInit_ex", "EVP_DigestVerifyInit"])
def test_a_library_without_an_algorithm_is_not_used(missing, monkeypatch):
    """A libcrypto that loads but cannot do ChaCha20-Poly1305 or Ed25519 (a
    FIPS provider) must not refuse every long message: the known answers
    fail at load and the wheel serves."""
    def crippled():
        lib = unlocked._Lib("libcrypto.so.3")
        setattr(lib, missing, lambda *args: 0)
        return lib

    monkeypatch.setattr(unlocked, "_open_library", crippled)
    monkeypatch.setattr(unlocked, "_tried", False)
    monkeypatch.setattr(unlocked, "_lib", None)
    monkeypatch.setattr(unlocked, "UNLOCKED_MIN", 0)
    assert unlocked.load() is None
    plain, sealed, signature = _case((1 << 20) + 1)
    before = _moved()
    assert KEYS.secret.decrypt(sealed, KEYS.public) == plain
    assert verify_detached(SIGNER.public, signature, plain) is True
    assert {route_name for _, route_name in _delta(before)} == {"wheel"}


def test_the_pipeline_gives_up_a_bytearray_and_only_that(monkeypatch):
    """``_decrypt_parse_one`` opens a ``bytearray`` in place (the body
    ``rest.py`` read) and leaves ``bytes`` alone; the parsed fields are
    ``bytes`` whatever the plaintext was a view of."""
    from xaynet_tpu.server.events import PhaseName
    from xaynet_tpu.server.services import MessageWorkers, PetMessageHandler

    monkeypatch.setattr(unlocked, "UNLOCKED_MIN", 64)
    workers = MessageWorkers(1)  # its own, closed below: the process's stay up
    handler = PetMessageHandler(events=None, request_tx=None, workers=workers)
    message = Message(
        participant_pk=SIGNER.public, coordinator_pk=KEYS.public.as_bytes(),
        payload=Sum(sum_signature=b"\x01" * 64, ephm_pk=b"\x02" * 32), tag=Tag.SUM,
    )
    sealed = KEYS.public.encrypt(message.to_bytes(SIGNER.secret))
    try:
        for given in (sealed, bytearray(sealed)):
            parsed = handler._decrypt_parse_one(given, KEYS, PhaseName.SUM)
            assert type(parsed.participant_pk) is bytes and parsed.participant_pk == SIGNER.public
            assert type(parsed.payload.ephm_pk) is bytes and {parsed.payload.ephm_pk: 1}
            assert (bytes(given) == sealed) is isinstance(given, bytes)
    finally:
        workers.close()


# --- the lock ---------------------------------------------------------------


def _longest_stall_while(work) -> tuple[float, float]:
    """Run ``work`` on a thread while this one counts in pure Python; the
    call's seconds and the longest time this thread could not run."""
    took = []

    def run():
        t0 = time.perf_counter()
        work()
        took.append(time.perf_counter() - t0)

    thread = threading.Thread(target=run)
    # from before the start: a thread that takes the lock at once and keeps it
    # holds this one inside ``start()``
    stall, last = 0.0, time.perf_counter()
    thread.start()
    while thread.is_alive():
        now = time.perf_counter()
        stall, last = max(stall, now - last), now
    thread.join()
    return took[0], stall


@pytest.mark.parametrize("op", ["open", "verify"])
def test_the_lock_is_free_while_a_large_message_is_opened_and_verified(op, monkeypatch):
    """The contrast, not an absolute time: on the wheel the main thread stands
    still for nearly the whole call (over 90% of it on an idle host), on the
    unlocked route it keeps running."""
    plain = _bytes_of(64 << 20)
    sealed = KEYS.public.encrypt(plain)
    signature = SIGNER.sign(plain).as_bytes()

    def call():
        if op == "verify":
            return lambda: verify_detached(SIGNER.public, signature, plain) or pytest.fail("verdict")
        box = bytearray(sealed)  # the copy holds the lock: made before the call is timed
        return lambda: KEYS.secret.decrypt_in_place(box, KEYS.public)

    shares = {}
    for route_name, floor in (("wheel", 1 << 62), ("unlocked", 0)):
        monkeypatch.setattr(unlocked, "UNLOCKED_MIN", floor)
        # the best of five: another process may take this thread's core
        shares[route_name] = min(
            stall / took for took, stall in (_longest_stall_while(call()) for _ in range(5))
        )
    assert shares["wheel"] > 0.6, shares
    assert shares["unlocked"] < 0.25, shares
