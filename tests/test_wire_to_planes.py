"""A v1 body relaid once, where it is parsed (ISSUE 51): the kernel that
writes checked byte planes from interleaved wire bytes in one pass
(``xn_wire_to_planes`` / ``ops/limbs.py::wire_to_planes``), its verdict, and
the parse that uses it where the consumer's slots are byte planes
(``parse_mask_vect(planes=)``), on pages kept from earlier messages
(``PlaneBuffers``).

Held to the road it replaces: ``bytes_le_to_limbs`` (wire bytes -> limb
rows), ``all_lt_order`` (the scan) and ``pack_wire`` (limb rows -> planes).
The planes are byte for byte the plane pack's, the count of elements out of
the group is the limb scan's, and the parse raises the same ``DecodeError``
for the same bodies.
"""

import struct
import sys
import threading
from fractions import Fraction

import numpy as np
import pytest

from xaynet_tpu.core.mask import BoundType, DataType, GroupType, MaskConfig, ModelType
from xaynet_tpu.core.mask import serialization
from xaynet_tpu.core.mask.masking import Masker
from xaynet_tpu.core.mask.model import Scalar
from xaynet_tpu.core.mask.object import LazyWireMaskVect, MaskVect, wire_route
from xaynet_tpu.core.mask.serialization import (
    DecodeError,
    parse_mask_object,
    parse_mask_vect,
    parse_mask_vect_stream,
    serialize_mask_object,
    serialize_mask_vect,
)
from xaynet_tpu.core.message import Message, Sum2, Update
from xaynet_tpu.core.message.encoder import ChunkReader
from xaynet_tpu.core.message.payloads import parse_payload, parse_payload_stream
from xaynet_tpu.ops import limbs as host_limbs
from xaynet_tpu.telemetry.registry import get_registry
from xaynet_tpu.utils import native

MASKS = {
    "prime-b0m3": MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3),  # 45 bits
    "integer-b0m6": MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6),  # 55
    "integer-b6m6": MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B6, ModelType.M6),  # 75
    # an order of 2^88 on 11 wire bytes: every value the bytes hold is an element
    "power2-b4m12": MaskConfig(GroupType.POWER2, DataType.F32, BoundType.B4, ModelType.M12),
}
PLANES = host_limbs.PlaneBuffers(keep=2)  # what a handler brings to the parse
# more than one thread's slice (512k elements) and no multiple of it
SLICED = 1_100_003
COUNTS = (0, 1, 7, 4095, 4096, 4097, SLICED)


def _codec(op: str, route: str) -> float:
    return get_registry().sample_value(
        "xaynet_codec_elements_total", {"op": op, "route": route}) or 0.0


def _no_library(monkeypatch) -> None:
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)


def _rows(count: int, bpn: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng([count, bpn, seed])
    return np.frombuffer(rng.bytes(count * bpn), dtype=np.uint8).reshape(count, bpn).copy()


def _packed_by_the_limb_road(wire: np.ndarray, count: int, bpn: int) -> np.ndarray:
    """What the three-pass road leaves in a slot: limb rows, then the plane pack."""
    limbs = host_limbs.bytes_le_to_limbs(wire, count, bpn, op=None)
    return host_limbs.pack_wire(limbs[None], bpn)[0]


# --- (a): the planes ------------------------------------------------------------


@pytest.mark.parametrize("library", ["native", "numpy"])
@pytest.mark.parametrize("count", COUNTS)
@pytest.mark.parametrize("bpn", range(1, 17))
def test_the_planes_are_the_plane_packs_byte_for_byte(bpn, count, library, monkeypatch):
    assert native.load() is not None
    wire = _rows(count, bpn, 51).reshape(-1)
    want = _packed_by_the_limb_road(wire, count, bpn)
    assert want.shape == (bpn, count) and np.array_equal(want, wire.reshape(count, bpn).T)
    if library == "numpy":
        _no_library(monkeypatch)
    route = "fast" if library == "native" else "generic"
    admits_all = 1 << (8 * bpn)
    for n_threads in (1, 3, 0) if library == "native" else (1,):
        parsed0 = _codec("parse", route)
        planes, bad = host_limbs.wire_to_planes(wire, count, bpn, admits_all, n_threads=n_threads)
        assert planes.dtype == np.uint8 and planes.shape == (bpn, count)
        assert planes.flags.c_contiguous and np.array_equal(planes, want) and bad == 0
        assert _codec("parse", route) - parsed0 == count
        # a plane stride wider than the count, and columns that do not start
        # at the plane's first: a segment of a block, a slot of a ring
        wide = np.full((bpn, count + 37), 0xA5, dtype=np.uint8)
        out, bad = host_limbs.wire_to_planes(
            wire, count, bpn, admits_all, out=wide, column=5, n_threads=n_threads)
        assert out is wide and bad == 0
        assert np.array_equal(wide[:, 5 : 5 + count], want)
        assert np.all(wide[:, :5] == 0xA5) and np.all(wide[:, 5 + count :] == 0xA5)


def test_an_element_wider_than_sixteen_bytes_stays_in_the_library():
    """F64/BMAX is 264 wire bytes: relaid natively, a byte at a time."""
    count, bpn = 301, 264
    wire = _rows(count, bpn, 7).reshape(-1)
    fast0, generic0 = _codec("parse", "fast"), _codec("parse", "generic")
    planes, bad = host_limbs.wire_to_planes(wire, count, bpn, 1 << (8 * bpn))
    assert np.array_equal(planes, wire.reshape(count, bpn).T) and bad == 0
    assert (_codec("parse", "fast") - fast0, _codec("parse", "generic") - generic0) == (count, 0)


@pytest.mark.parametrize("shape", [(6, 9), (7, 10, 1), (8,)])
def test_a_destination_of_another_shape_is_refused(shape):
    wire = _rows(10, 7, 1).reshape(-1)
    with pytest.raises(ValueError, match="planes of unit column stride"):
        host_limbs.wire_to_planes(wire, 10, 7, 1 << 56, out=np.zeros(shape, dtype=np.uint8))
    with pytest.raises(ValueError, match="planes of unit column stride"):
        host_limbs.wire_to_planes(wire, 10, 7, 1 << 56, out=np.zeros((7, 12), np.uint8), column=3)
    with pytest.raises(ValueError, match="planes of unit column stride"):
        host_limbs.wire_to_planes(wire, 10, 7, 1 << 56, out=np.zeros((7, 20), np.uint8)[:, ::2])


# --- (b): the verdict -----------------------------------------------------------


def _valid_rows(config: MaskConfig, n: int, seed: int) -> np.ndarray:
    """``n`` group elements as wire rows, one in ~100 tying the order's top byte."""
    bpn, order = config.bytes_per_number, config.order
    rows = _rows(n, bpn, seed)
    top = order.to_bytes(bpn + 1, "little")[bpn - 1] if order >> (8 * bpn) == 0 else 0
    if top:
        rows[:, -1] %= top  # the top byte under the order's: in the group whatever follows
        ties = np.arange(0, n, 97)
        rows[ties] = np.frombuffer((order - 1).to_bytes(bpn, "little"), dtype=np.uint8)
    return rows


VALUES = ("order-1", "order", "order+1", "all-ones")
PLACES = ("first", "middle", "last")


@pytest.mark.parametrize("library", ["native", "numpy"])
@pytest.mark.parametrize("where", PLACES)
@pytest.mark.parametrize("what", VALUES)
@pytest.mark.parametrize("mask", list(MASKS))
def test_the_count_and_the_decode_error_equal_the_limb_roads(mask, what, where, library,
                                                             monkeypatch):
    config, n = MASKS[mask], 70_001  # whole 32-element turns and a tail
    order, bpn = config.order, config.bytes_per_number
    admits_all = bool(order >> (8 * bpn))
    value = {"order-1": order - 1, "order": order, "order+1": order + 1,
             "all-ones": (1 << (8 * bpn)) - 1}[what]
    value &= (1 << (8 * bpn)) - 1  # what bpn bytes can say of it (2^88 -> 0)
    position = {"first": 0, "middle": n // 2 + 3, "last": n - 1}[where]
    rows = _valid_rows(config, n, 51)
    rows[position] = np.frombuffer(value.to_bytes(bpn, "little"), dtype=np.uint8)
    wire = rows.reshape(-1)
    limbs = host_limbs.bytes_le_to_limbs(wire, n, bpn, op=None)
    want_bad = int(np.count_nonzero(~host_limbs.elements_lt_order(limbs, order)))
    assert want_bad == (0 if admits_all else int(value >= order))
    blob = config.to_bytes() + struct.pack(">I", n) + wire.tobytes()
    try:
        limb_road = parse_mask_vect(blob)[0]
    except DecodeError as err:
        limb_road = str(err)

    if library == "numpy":
        _no_library(monkeypatch)
    route = "fast" if library == "native" else "generic"
    for n_threads in (1, 0):
        validated0 = _codec("validate", route)
        planes, bad = host_limbs.wire_to_planes(wire, n, bpn, order, n_threads=n_threads)
        assert bad == want_bad and np.array_equal(planes, rows.T)
        # an order that admits all compares nothing, as all_lt_order
        assert _codec("validate", route) - validated0 == (0 if admits_all else n)
    # more than one out of the group, in a vector turn and in the tail
    if not admits_all:
        rows[[1, n // 3, n - 2]] = 0xFF
        assert host_limbs.wire_to_planes(rows.reshape(-1), n, bpn, order)[1] == want_bad + 3

    if isinstance(limb_road, str):
        assert limb_road == "mask vector element >= group order"
        with pytest.raises(DecodeError, match="^mask vector element >= group order$"):
            parse_mask_vect(blob, planes=PLANES)
        with pytest.raises(DecodeError, match="^mask vector element >= group order$"):
            parse_mask_vect_stream(ChunkReader([blob[:13], blob[13:]]), planes=PLANES)
    else:
        vect, consumed = parse_mask_vect(blob, planes=PLANES)
        assert consumed == len(blob) and vect.is_valid() and vect == limb_road


def test_the_fallback_counts_every_element_out_of_the_group(monkeypatch):
    """numpy's plane compares give a count, not a first hit: ties above,
    ties all the way down (the order itself), and larger top bytes."""
    _no_library(monkeypatch)
    order, bpn = 0x0102030405, 5
    values = [0, order - 1, order, order + 1, 0x0102030500, 0x0102030404, 0x0200000000,
              0x01FFFFFFFF, 0x0102030405, 0x00FFFFFFFF]
    wire = np.frombuffer(b"".join(v.to_bytes(bpn, "little") for v in values), dtype=np.uint8)
    planes, bad = host_limbs.wire_to_planes(wire, len(values), bpn, order)
    assert bad == sum(v >= order for v in values) == 6
    assert host_limbs.planes_lt_order(planes, order) is False
    assert host_limbs.planes_lt_order(planes[:, :2], order) is True


# --- (c): the parse -------------------------------------------------------------

N = 4133


def _masked(config: MaskConfig, seed: int = 23):
    w = np.random.default_rng(seed).uniform(-1, 1, N).astype(np.float32)
    return Masker(config.pair()).mask(Scalar.from_fraction(Fraction(1, 4)), w)[1]


@pytest.mark.parametrize("streamed", [False, True], ids=["buffer", "stream"])
@pytest.mark.parametrize("mask", list(MASKS))
def test_the_parse_with_the_hint_equals_the_parse_without(mask, streamed, monkeypatch):
    config = MASKS[mask]
    masked = _masked(config)
    blob = serialize_mask_vect(masked.vect)
    bpn = config.bytes_per_number

    def parse(**hint):
        if streamed:
            return parse_mask_vect_stream(ChunkReader([blob[:11], blob[11:]]), **hint)
        vect, consumed = parse_mask_vect(blob, **hint)
        assert consumed == len(blob)
        return vect

    rows = parse()
    assert type(rows) is MaskVect and wire_route(rows) == ("legacy", "relayout")
    calls, real = [], serialization.planar_to_interleaved
    monkeypatch.setattr(serialization, "planar_to_interleaved",
                        lambda *a: calls.append(a[1]) or real(*a))
    generic0, validated0 = _codec("parse", "generic"), _codec("validate", "fast")
    planes = parse(planes=PLANES)
    # which wire the message came on, and what layout its block has: apart
    assert isinstance(planes, LazyWireMaskVect)
    assert (planes.packed_wire, planes.planar, planes.checked, planes.materialized) \
        == (False, True, True, False)
    assert wire_route(planes) == ("legacy", "copy") and len(planes) == N
    assert planes.planar_block.shape == (bpn, N)
    assert np.array_equal(planes.planar_block, host_limbs.pack_wire(masked.vect.data[None], bpn)[0])
    # planes of its own: the body is not kept alive past the parse
    assert not np.shares_memory(planes.wire_block, np.frombuffer(blob, dtype=np.uint8))
    assert planes.wire_block.base is None or not isinstance(
        planes.wire_block.base, (bytes, memoryview))
    # the verdict rides on the object: nothing is scanned again
    assert planes.is_valid() and planes.check_planes()
    assert _codec("validate", "fast") - validated0 == (0 if config.order >> (8 * bpn) else N)
    assert calls == [] and _codec("parse", "generic") == generic0
    # a serialiser that is handed the object back writes what it is asked to
    assert serialize_mask_vect(planes, planar=True) == serialize_mask_vect(masked.vect, planar=True)
    assert not planes.materialized
    assert serialize_mask_vect(planes) == blob
    # .data is the old parse's limb rows, counted as the fallback it is
    assert planes.materialized and calls == [N] and _codec("parse", "generic") - generic0 == N
    assert planes.data.dtype == np.uint32 and np.array_equal(planes.data, rows.data)
    assert planes == rows and rows == planes and planes == masked.vect
    assert wire_route(planes) == ("legacy", "relayout")


@pytest.mark.parametrize("mask", list(MASKS))
def test_a_v2_body_and_a_lazy_parse_take_no_notice_of_the_hint(mask):
    config = MASKS[mask]
    masked = _masked(config)
    v2 = parse_mask_vect(serialize_mask_vect(masked.vect, planar=True), planes=PLANES)[0]
    assert (v2.packed_wire, v2.planar, v2.checked) == (True, True, True)
    assert wire_route(v2) == ("packed", "copy") and v2 == masked.vect
    blob = serialize_mask_vect(masked.vect)
    lazy = parse_mask_vect(blob, lazy=True, planes=PLANES)[0]
    assert (lazy.packed_wire, lazy.planar, lazy.checked) == (False, False, False)
    assert wire_route(lazy) == ("legacy", "device")
    # a view of the body: wire ingest uploads it as it came
    assert np.shares_memory(lazy.wire_block, np.frombuffer(blob, dtype=np.uint8))


@pytest.mark.parametrize("streamed", [False, True], ids=["buffer", "stream"])
@pytest.mark.parametrize("mask", list(MASKS))
def test_the_hint_reaches_the_update_payloads_vector_alone(mask, streamed):
    """Down ``parse_payload`` -> ``Update.from_bytes`` -> ``parse_mask_object``:
    an Update's vector becomes planes; its unit, a Sum2 message's mask and a
    parse without the hint keep limb rows."""
    from xaynet_tpu.core.message.message import Tag

    config = MASKS[mask]
    masked = _masked(config)
    update = Update(sum_signature=b"\1" * 64, update_signature=b"\2" * 64, masked_model=masked,
                    local_seed_dict={}).to_bytes()
    sum2 = Sum2(sum_signature=b"\3" * 64, model_mask=masked).to_bytes()

    def parse(tag, blob, **hint):
        if streamed:
            return parse_payload_stream(tag, ChunkReader([blob[:200], blob[200:]]), **hint)
        return parse_payload(tag, False, blob, **hint)

    got = parse(Tag.UPDATE, update, planes_update_vect=PLANES)
    assert wire_route(got.masked_model.vect) == ("legacy", "copy")
    assert got.wire_planar is False  # the message's flag, not the block's layout
    assert got.masked_model.unit == masked.unit and got.masked_model == masked
    assert got.to_bytes() == update
    plain = parse(Tag.UPDATE, update)
    assert type(plain.masked_model.vect) is MaskVect and plain.masked_model == got.masked_model
    vote = parse(Tag.SUM2, sum2, planes_update_vect=PLANES)
    assert type(vote.model_mask.vect) is MaskVect and vote.model_mask == masked
    # both hints: lazy goes first (wire ingest unpacks on the device)
    both = parse(Tag.UPDATE, update, lazy_update_vect=True, planes_update_vect=PLANES)
    assert wire_route(both.masked_model.vect) == ("legacy", "device")


def test_a_mask_object_parses_alike_with_the_hint():
    config = MASKS["integer-b0m6"]
    masked = _masked(config)
    blob = serialize_mask_object(masked)
    obj, consumed = parse_mask_object(blob, planes_vect=PLANES)
    assert consumed == len(blob) and obj.is_valid()
    assert wire_route(obj.vect) == ("legacy", "copy")
    assert obj == masked  # materialises the limb rows
    with pytest.raises(DecodeError, match="mask vector data truncated"):
        parse_mask_object(blob[: len(blob) // 2], planes_vect=PLANES)


def test_a_whole_message_parses_its_update_vector_into_planes():
    from xaynet_tpu.core.crypto.sign import SigningKeyPair

    config = MASKS["integer-b0m6"]
    masked = _masked(config)
    keys = SigningKeyPair.generate()
    payload = Update(sum_signature=b"\1" * 64, update_signature=b"\2" * 64, masked_model=masked,
                     local_seed_dict={})
    raw = Message(participant_pk=keys.public, coordinator_pk=b"\7" * 32,
                  payload=payload).to_bytes(keys.secret)
    message = Message.from_bytes(raw, planes_update_vect=PLANES)
    assert wire_route(message.payload.masked_model.vect) == ("legacy", "copy")
    assert message.payload.masked_model == masked
    assert type(Message.from_bytes(raw).payload.masked_model.vect) is MaskVect


# --- the pages the planes lie on --------------------------------------------------


def _address(a: np.ndarray) -> int:
    return a.__array_interface__["data"][0]


def test_kept_pages_are_handed_out_again_only_when_nothing_refers_to_them():
    pool = host_limbs.PlaneBuffers(keep=2)
    first = pool.take(7, 1000)
    assert first.shape == (7, 1000) and first.dtype == np.uint8 and first.flags.c_contiguous
    first[...] = 1
    second = pool.take(7, 1000)
    assert not np.shares_memory(first, second)
    third = pool.take(7, 1000)  # both kept buffers are someone's: fresh pages, not kept
    assert not np.shares_memory(third, first) and not np.shares_memory(third, second)
    where = {_address(first), _address(second)}
    assert _address(third) not in where
    del third
    assert _address(pool.take(7, 1000)) not in where  # still both busy
    # views of views, a flat one, an ndarray made from one: each holds the pages
    held = np.asarray(first[2:4]).reshape(-1)[3:][::2]
    assert np.shares_memory(held, first)
    del first
    again = pool.take(7, 1000)
    assert _address(again) not in where and np.all(held == 1)
    del again, held
    assert _address(pool.take(7, 1000)) in where  # the first's pages, free now
    del second
    a, b = pool.take(7, 1000), pool.take(7, 1000)
    assert {_address(a), _address(b)} == where


def test_another_size_restarts_the_kept_pages_unless_some_are_in_use():
    pool = host_limbs.PlaneBuffers(keep=2)
    a = pool.take(7, 1000)
    other = pool.take(10, 1000)  # a stray size while a vector of the round's is alive
    assert other.shape == (10, 1000)
    del other
    assert _address(pool.take(7, 1000)) != _address(a)
    where = _address(a)
    del a
    b = pool.take(10, 1000)  # all idle: the round's vectors changed size
    c_addr = _address(b)
    del b
    assert _address(pool.take(10, 1000)) == c_addr
    assert pool.take(7, 1000).shape == (7, 1000) and where  # and back again


def test_a_pool_that_keeps_nothing_hands_out_fresh_pages():
    pool = host_limbs.PlaneBuffers()
    a = pool.take(3, 50)
    a[...] = 7
    b = pool.take(3, 50)
    assert not np.shares_memory(a, b) and np.all(a == 7)
    assert pool.take(0, 0).shape == (0, 0) and pool.take(5, 0).shape == (5, 0)


def test_no_two_holders_ever_share_pages():
    """More takers than cores and than kept buffers, each writing its own
    mark, doing something else, and reading it back."""
    pool, errors, stop = host_limbs.PlaneBuffers(keep=3), [], threading.Event()
    interval = sys.getswitchinterval()

    def taker(mark: int):
        try:
            for turn in range(300):
                planes = pool.take(4, 2048)
                planes[...] = mark
                view = planes[1:3, 100:200]
                del planes
                sum(range(50))
                if not np.all(view == mark):
                    errors.append((mark, turn))
                    return
                del view
        except Exception as err:  # pragma: no cover - the assertion below reports it
            errors.append(err)

    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=taker, args=(i + 1,)) for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        stop.set()
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and errors == []


def test_a_parse_writes_on_the_last_vectors_pages_once_that_vector_is_gone():
    config = MASKS["integer-b0m6"]
    first, second = _masked(config, 1), _masked(config, 2)
    pool = host_limbs.PlaneBuffers(keep=1)
    kept = parse_mask_vect(serialize_mask_vect(first.vect), planes=pool)[0]
    where = _address(kept.wire_block)
    beside = parse_mask_vect(serialize_mask_vect(second.vect), planes=pool)[0]
    # the first vector is alive: the second got pages of its own, the first's are whole
    assert _address(beside.wire_block) != where
    assert kept == first.vect and beside == second.vect
    del kept, beside
    after = parse_mask_vect(serialize_mask_vect(second.vect), planes=pool)[0]
    assert _address(after.wire_block) == where and after == second.vect
