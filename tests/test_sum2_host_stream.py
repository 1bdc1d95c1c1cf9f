"""The host's streaming derive-and-sum (``core/mask/derive_sum.py``,
``xn_derive_sum``; docs/DESIGN.md section 15): the sum of the masks of K seeds
without a mask in memory, over any number of threads.

Two references hold it: ``Aggregation`` over ``MaskSeed.derive_mask`` for the
sum (bit for bit), and the sequential ``generate_integer`` walk of each seed's
keystream for the end cursor. Every case runs with segments of a few
candidates, so that lengths of tens of elements cross many segment borders,
and with pinned thread and group counts, which must not change a bit.
"""

from __future__ import annotations

import numpy as np
import pytest

from xaynet_tpu.core.crypto.chacha import BLOCK_BYTES, ChaChaStream
from xaynet_tpu.core.crypto.prng import StreamSampler, generate_integer
from xaynet_tpu.core.mask import Aggregation, AggregationError, MaskConfig, MaskSeed
from xaynet_tpu.core.mask import derive_sum
from xaynet_tpu.core.mask._orders_data import ORDERS
from xaynet_tpu.core.mask.config import _BOUND_KEY, _DATA_KEY, _GROUP_KEY, _MODEL_KEY
from xaynet_tpu.core.mask.masking import check_nb_models
from xaynet_tpu.ops import limbs
from xaynet_tpu.utils import native

_G = {v: k for k, v in _GROUP_KEY.items()}
_D = {v: k for k, v in _DATA_KEY.items()}
_B = {v: k for k, v in _BOUND_KEY.items()}
_M = {v: k for k, v in _MODEL_KEY.items()}

SEGMENT = 16  # candidates: a length of 40 is several segments at any acceptance


def _config(key) -> MaskConfig:
    return MaskConfig(_G[key[0]], _D[key[1]], _B[key[2]], _M[key[3]])


def _by_width() -> dict[int, tuple]:
    """The catalogue entry of each draw width that accepts most often (the
    sequential oracle pays every rejection in Python)."""
    best: dict[int, tuple] = {}
    for key in sorted(ORDERS):
        order = ORDERS[key]
        bpn = limbs.draw_width_for(order)
        rate = order / 2 ** (8 * bpn)
        if bpn not in best or rate > best[bpn][1]:
            best[bpn] = (key, rate)
    return {bpn: key for bpn, (key, _) in best.items()}


WIDTHS = _by_width()
STREAMED = sorted(b for b in WIDTHS if b <= 16)  # every width xn_derive_sum serves
WIDER = min(b for b in WIDTHS if b > 16)  # the bounded wave's
needs_native = pytest.mark.skipif(native.load() is None, reason="native library unavailable")


def _seeds(k: int, salt: int) -> list[bytes]:
    return [bytes([i + 1, salt & 0xFF, 0xA5]) + bytes(29) for i in range(k)]


def _reference(seeds, n, pair):
    agg = Aggregation(pair, n)
    for seed in seeds:
        agg.aggregate(MaskSeed(seed).derive_mask(n, pair))
    return agg.object.unit.data, agg.object.vect.data


def _walk(seed: bytes, offset: int, n: int, order: int) -> tuple[list[int], int, list[int]]:
    """The sequential sampler from keystream byte ``offset``: the ``n`` draws,
    the end cursor, and the candidate index of each accepted attempt."""
    bpn = limbs.draw_width_for(order)
    stream = ChaChaStream(seed)
    stream.read(offset)
    values, accepted_at, attempt = [], [], 0
    while len(values) < n:
        value = int.from_bytes(stream.read(bpn), "little")
        if value < order:
            values.append(value)
            accepted_at.append(attempt)
        attempt += 1
    oracle = ChaChaStream(seed)
    oracle.read(offset)
    assert values == [generate_integer(oracle, order) for _ in range(n)]
    return values, offset + attempt * bpn, accepted_at


def _unit_offsets(seeds, order) -> list[int]:
    offsets = []
    for seed in seeds:
        sampler = StreamSampler(seed)
        sampler.draw_int(order)
        offsets.append(sampler.consumed_bytes)
    return offsets


def _lengths(seed: bytes, offset: int, order: int) -> dict[str, int]:
    """Lengths whose last draw of ``seed`` ends inside the first keystream
    block, inside a segment, and on a segment's last candidate."""
    bpn = limbs.draw_width_for(order)
    _, _, at = _walk(seed, offset, 6 * SEGMENT, order)
    first_block = max(
        (i + 1 for i, a in enumerate(at) if (offset % BLOCK_BYTES) + (a + 1) * bpn <= BLOCK_BYTES),
        default=1,
    )
    inside = next(i + 1 for i, a in enumerate(at) if a >= 2 * SEGMENT and a % SEGMENT not in (0, SEGMENT - 1))
    border = next(i + 1 for i, a in enumerate(at) if a >= SEGMENT and a % SEGMENT == SEGMENT - 1)
    return {"first-block": first_block, "inside": inside, "border": border}


@needs_native
@pytest.mark.parametrize("ends", ["first-block", "inside", "border"])
@pytest.mark.parametrize("k", [1, 2, 9])
@pytest.mark.parametrize("bpn", STREAMED)
def test_streamed_sum_and_cursors_match_the_sequential_sampler(bpn, k, ends):
    """One order of every draw width up to 16 bytes: the streamed sum is
    ``Aggregation`` over ``derive_mask`` and every seed's end cursor is the
    sequential walk's, from the unaligned offset the unit draw leaves, whether
    the last draw falls in the first block, inside a segment or on its last
    candidate."""
    cfg = _config(WIDTHS[bpn])
    order = cfg.order
    seeds = _seeds(k, bpn)
    offsets = _unit_offsets(seeds, order)
    assert all(o % BLOCK_BYTES for o in offsets)  # never block-aligned
    n = _lengths(seeds[0], offsets[0], order)[ends]
    vect, cursors = derive_sum.derive_sum_vect(
        seeds, offsets, n, order, threads=3, groups=1, segment=SEGMENT
    )
    _, ref_vect = _reference(seeds, n, cfg.pair())
    assert vect.dtype == np.uint32 and np.array_equal(vect, ref_vect)
    walks = [_walk(s, o, n, order) for s, o in zip(seeds, offsets)]
    assert cursors == [w[1] for w in walks]
    assert limbs.limbs_to_ints(vect) == [sum(col) % order for col in zip(*(w[0] for w in walks))]


@needs_native
@pytest.mark.parametrize("threads,groups", [(1, 1), (2, 1), (2, 2), (3, 1), (3, 3), (8, 1), (8, 3), (8, 8)])
@pytest.mark.parametrize("bpn", [7, 10, 16])
def test_result_does_not_depend_on_threads_or_groups(bpn, threads, groups):
    """1, 2, 3 and 8 threads, all on one seed at a time or a group a seed:
    identical arrays and cursors (2, 3 and 4 limbs; lazy u64, lazy 12-byte
    and eager 16-byte accumulators)."""
    order = _config(WIDTHS[bpn]).order
    seeds = _seeds(9, 0x40 + bpn)
    offsets = _unit_offsets(seeds, order)
    n = 211
    base = derive_sum.derive_sum_vect(seeds, offsets, n, order, threads=1, groups=1, segment=1 << 20)
    got = derive_sum.derive_sum_vect(
        seeds, offsets, n, order, threads=threads, groups=groups, segment=SEGMENT
    )
    assert np.array_equal(got[0], base[0]) and got[1] == base[1]


@needs_native
@pytest.mark.parametrize("threads,groups", [(32, 1), (64, 1), (32, 5), (48, 16)])
@pytest.mark.parametrize("bpn", [7, 10, 16])
def test_more_threads_than_cores_sample_ahead_and_change_nothing(bpn, threads, groups):
    """Many more threads than cores, on hundreds of 16-candidate segments a
    seed: threads are taken off their cores with a ticket in hand, the
    others sample ahead of the segment that cannot be placed yet, the ring of
    slots (four a thread of the group) is gone round many times, and every
    thread but the first finds tickets past the seed's end. Same arrays, same
    cursors, and it ends."""
    order = _config(WIDTHS[bpn]).order
    seeds = _seeds(7, 0x70 + bpn)
    offsets = _unit_offsets(seeds, order)
    # about 400 segments a seed at this width's acceptance rate
    n = max(64, int(400 * SEGMENT * order / 2 ** (8 * bpn)))
    base = derive_sum.derive_sum_vect(seeds, offsets, n, order, threads=1, groups=1, segment=1 << 20)
    for _ in range(3):
        got = derive_sum.derive_sum_vect(
            seeds, offsets, n, order, threads=threads, groups=groups, segment=SEGMENT
        )
        assert np.array_equal(got[0], base[0]) and got[1] == base[1]


@pytest.mark.parametrize("k", [1, 2, 9])
@pytest.mark.parametrize("bpn", STREAMED + [WIDER])
def test_entry_equals_aggregation_over_derive_mask(bpn, k):
    """``derive_and_sum`` with the grain it picks itself, unit and vector,
    for every width including one past 16 bytes (the bounded wave)."""
    pair = _config(WIDTHS[bpn]).pair()
    seeds = _seeds(k, 0x80 + bpn)
    unit, vect = derive_sum.derive_and_sum(seeds, 53, pair)
    ref_unit, ref_vect = _reference(seeds, 53, pair)
    assert np.array_equal(unit, ref_unit) and np.array_equal(vect, ref_vect)


@pytest.mark.parametrize("bpn", [7, 10, 16, WIDER])
def test_no_native_library_same_result(bpn, monkeypatch):
    """Without the library the bounded wave serves every width, in waves
    (more seeds than one wave holds), to the same bits."""
    pair = _config(WIDTHS[bpn]).pair()
    seeds = _seeds(derive_sum._WAVE + 3, bpn)
    with_lib = derive_sum.derive_and_sum(seeds, 29, pair)
    monkeypatch.setattr(native, "load", lambda: None)
    assert derive_sum.derive_sum_vect(seeds, [0] * len(seeds), 29, pair.vect.order) is None
    without = derive_sum.derive_and_sum(seeds, 29, pair)
    assert np.array_equal(with_lib[0], without[0]) and np.array_equal(with_lib[1], without[1])


SWITCH_ORDER = (1 << 62) + 12345  # 8-byte draws; (k + 1) * order < 2^64 up to k = 2


@pytest.mark.parametrize(
    "order,k,plan",
    [
        (SWITCH_ORDER, 2, (8, False)),
        (SWITCH_ORDER, 3, (12, False)),  # the exact k at which u64 no longer holds the sums
        ((1 << 95) - 5, 1, (12, False)),
        ((1 << 95) - 5, 2, (16, False)),
        (ORDERS[WIDTHS[16]], 1, (16, True)),  # a 128-bit order: no headroom in 16 bytes
        (ORDERS[WIDTHS[7]], 12, (8, False)),  # the 2-limb cell
        (ORDERS[WIDTHS[10]], 8, (12, False)),  # the 3-limb cell: sums in the output's limbs
    ],
)
def test_accumulator_plan(order, k, plan):
    assert derive_sum.accumulator_plan(order, k) == plan


@needs_native
@pytest.mark.parametrize("k", [2, 3, 5])
def test_accumulator_switch_is_exact_on_both_sides(k):
    """At the order where k = 2 still sums in u64 and k = 3 does not, both
    sides give the big-integer sum (a u64 that wrapped would not)."""
    order = SWITCH_ORDER
    seeds = _seeds(k, 0x33)
    offsets = [5] * k
    n = 300
    vect, cursors = derive_sum.derive_sum_vect(seeds, offsets, n, order, threads=2, segment=SEGMENT)
    walks = [_walk(s, 5, n, order) for s in seeds]
    assert limbs.limbs_to_ints(vect) == [sum(col) % order for col in zip(*(w[0] for w in walks))]
    assert cursors == [w[1] for w in walks]


def test_grain_follows_the_input():
    """Tens of elements: one thread, no start-up. 25M elements of 7 and 10
    bytes: every thread on one seed at a time (one accumulator), long
    segments. Hundreds of small seeds: a group a thread (seed-grained)."""
    o7, o10 = ORDERS[WIDTHS[7]], ORDERS[WIDTHS[10]]
    assert derive_sum._grain(64, 9, 7, o7, 8, 13)[:2] == (1, 1)
    for order, bpn, stride, k in ((o7, 7, 8, 12), (o10, 10, 12, 8)):
        threads, groups, segment = derive_sum._grain(25_557_032, k, bpn, order, stride, 13)
        assert (threads, groups, segment) == (13, 1, derive_sum._MAX_SEGMENT)
    o6 = ORDERS[("Integer", "F32", "B0", "M3")]
    threads, groups, _ = derive_sum._grain(817_872, 700, 6, o6, 8, 13)
    assert (threads, groups) == (13, 13)
    # a few middling seeds: the groups share the threads, the seeds the groups
    threads, groups, segment = derive_sum._grain(100_000, 3, 6, o6, 8, 13)
    assert (threads, groups) == (13, 3) and derive_sum._MIN_SEGMENT <= segment < derive_sum._MAX_SEGMENT


def test_threads_come_from_the_affinity_mask_or_the_pin(monkeypatch):
    import os

    monkeypatch.delenv("XAYNET_NATIVE_THREADS", raising=False)
    assert derive_sum.host_threads() == min(64, len(os.sched_getaffinity(0)))
    monkeypatch.setenv("XAYNET_NATIVE_THREADS", "3")
    assert derive_sum.host_threads() == 3
    monkeypatch.setenv("XAYNET_NATIVE_THREADS", "0")
    assert derive_sum.host_threads() == 1
    monkeypatch.setenv("XAYNET_NATIVE_THREADS", "many")
    assert derive_sum.host_threads() == min(64, len(os.sched_getaffinity(0)))


def _count_error(pair, count):
    """What aggregating ``count`` valid masks one by one raises: the parent's
    loop, kept here as the reference of ``check_nb_models``."""
    agg = Aggregation(pair, 1)
    mask = MaskSeed(bytes(32)).derive_mask(1, pair)
    try:
        for i in range(count):
            agg.nb_models = i
            agg.validate_aggregation(mask)
    except AggregationError as err:
        return err.kind
    return None


@pytest.mark.parametrize("over", [-1, 0, 1, 2])
@pytest.mark.parametrize(
    "vect_model,unit_model", [("M3", "M3"), ("M3", "M6"), ("M6", "M3")]
)
def test_too_many_seeds_raises_the_sequential_loops_error(vect_model, unit_model, over):
    """The count alone decides, and it decides what the mask-by-mask
    validation decided: nothing up to the smaller capacity, past it
    ``TooManyModels`` unless only the unit's count is full."""
    from xaynet_tpu.core.mask import MaskConfigPair

    pair = MaskConfigPair(
        vect=_config(("Integer", "F32", "B0", vect_model)),
        unit=_config(("Integer", "F32", "B0", unit_model)),
    )
    count = min(pair.vect.max_nb_models, pair.unit.max_nb_models) + over
    expected = _count_error(pair, count)
    assert expected == (None if over <= 0 else ("TooManyScalars" if unit_model < vect_model else "TooManyModels"))
    if expected is None:
        check_nb_models(pair, count)
    else:
        with pytest.raises(AggregationError, match=expected):
            check_nb_models(pair, count)


def test_sum_participant_host_arm_streams_and_counts_the_route():
    """``_aggregate_masks`` with ``device_sum2=False`` is the one entry: the
    sum it returns is the reference's, too many seeds raise before anything
    is derived, and ``xaynet_codec_elements_total{op="derive"}`` says
    ``fused`` for exactly the elements summed without a mask in memory."""
    from xaynet_tpu.sdk.state_machine import StateMachine
    from xaynet_tpu.telemetry.registry import get_registry

    cfg = _config(("Integer", "F32", "B0", "M3"))
    sm = StateMachine.__new__(StateMachine)
    sm.device_sum2 = False
    seeds = [MaskSeed(s) for s in _seeds(5, 0x77)]
    labels = {"op": "derive", "route": "fused"}
    before = get_registry().sample_value("xaynet_codec_elements_total", labels) or 0
    obj = StateMachine._aggregate_masks(sm, seeds, 41, cfg.pair())
    after = get_registry().sample_value("xaynet_codec_elements_total", labels) or 0
    ref_unit, ref_vect = _reference([s.as_bytes() for s in seeds], 41, cfg.pair())
    assert np.array_equal(obj.unit.data, ref_unit) and np.array_equal(obj.vect.data, ref_vect)
    assert obj.is_valid()
    assert after - before == (5 * 41 if native.load() is not None else 0)
    too_many = [MaskSeed(i.to_bytes(32, "little")) for i in range(cfg.max_nb_models + 1)]
    with pytest.raises(AggregationError, match="TooManyModels"):
        StateMachine._aggregate_masks(sm, too_many, 4, cfg.pair())
    with pytest.raises(ValueError, match="no seeds"):
        StateMachine._aggregate_masks(sm, [], 4, cfg.pair())


@pytest.mark.parametrize("key", sorted(ORDERS), ids="-".join)
def test_every_catalogue_entry(key):
    """All 240 configurations through the entry (6 to 268 byte draws), three
    seeds of seven elements each."""
    pair = _config(key).pair()
    seeds = _seeds(3, sum(map(ord, "".join(key))))
    unit, vect = derive_sum.derive_and_sum(seeds, 7, pair)
    ref_unit, ref_vect = _reference(seeds, 7, pair)
    assert np.array_equal(unit, ref_unit) and np.array_equal(vect, ref_vect)


def test_first_sum2_message_is_observed_once_a_round():
    """``xaynet_sum2_first_arrival_seconds``: one observation per Sum2
    phase, of the time from the phase's announcement to the first POSTed
    message's headers; messages of other phases and later ones add none."""
    import asyncio

    from xaynet_tpu.server.events import EventPublisher, EventSubscriber, PhaseName
    from xaynet_tpu.server.rest import RestServer
    from xaynet_tpu.server.services import Fetcher
    from xaynet_tpu.telemetry.registry import MetricsRegistry

    class _Handler:
        async def handle_message(self, body):
            return None

    async def run():
        registry = MetricsRegistry()
        events = EventPublisher(1, None, None, PhaseName.UPDATE)
        server = RestServer(Fetcher(EventSubscriber(events)), _Handler(), registry=registry)
        child = registry.get("xaynet_sum2_first_arrival_seconds").labels()

        async def post():
            assert (await server._route("POST", "/message", b"sealed", {}))[0] == 200

        await post()
        assert child.count == 0  # Update phase
        events.broadcast_phase(PhaseName.SUM2)
        entered = events.phase.get_latest().at
        await asyncio.sleep(0.05)
        await post()
        await post()
        assert child.count == 1 and 0.05 <= child.sum <= 0.05 + (asyncio.get_running_loop().time() - entered)
        events.broadcast_phase(PhaseName.UNMASK)
        await post()
        events.broadcast_phase(PhaseName.SUM2)  # the next round's
        await post()
        assert child.count == 2

    asyncio.run(run())
