"""How a store counts the Sum2 votes (storage/memory.py, docs/DESIGN.md §16
"The vote key"): two votes share a count exactly when their masks are equal
(both configurations, every element, the unit), whatever layout the mask
arrived in; the in-memory store keeps the parsed object it was given and
hands that object to the election, where the Redis store serialises and
parses; both give the same scores, errors and ties for the same votes."""

import asyncio

import numpy as np
import pytest

from test_redis_storage import FakeRedis

from xaynet_tpu.core.crypto.prng import uniform_ints
from xaynet_tpu.core.mask import (
    BoundType, DataType, GroupType, MaskConfig, MaskObject, Masker, ModelType, Scalar)
from xaynet_tpu.core.mask.masking import Aggregation
from xaynet_tpu.core.mask.object import MaskUnit, MaskVect
from xaynet_tpu.core.mask.serialization import parse_mask_object, serialize_mask_object
from xaynet_tpu.server.phases.base import PhaseError
from xaynet_tpu.server.phases.unmask import Unmask
from xaynet_tpu.storage.memory import InMemoryCoordinatorStorage
from xaynet_tpu.storage.redis import RedisCoordinatorStorage
from xaynet_tpu.storage.traits import MASK_VOTES, MaskScoreIncrError
from xaynet_tpu.telemetry import codec

CFG = MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3)
# another group of the same width: an element under both orders is valid in both
OTHER = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M3)
N = 40  # over the prefilter's sixteen elements: a late difference is past it


def _pk(i: int) -> bytes:
    return bytes([i]) * 32


def _mask(seed: int = 1, n: int = N) -> MaskObject:
    ints = uniform_ints(bytes([seed]) * 32, n + 1, min(CFG.order, OTHER.order))
    return MaskObject.new(CFG.pair(), ints[1:], ints[0])


def _copy(mask: MaskObject, vect_config=None, unit_config=None) -> MaskObject:
    return MaskObject(
        MaskVect(vect_config or mask.vect.config, mask.vect.data.copy()),
        MaskUnit(unit_config or mask.unit.config, mask.unit.data.copy()),
    )


def _one_element_off(mask: MaskObject) -> MaskObject:
    other = _copy(mask)
    other.vect.data[N - 1, 0] ^= 1
    return other


def _unit_off(mask: MaskObject) -> MaskObject:
    other = _copy(mask)
    other.unit.data[0] ^= 1
    return other


DIFFERENT = {
    "last_element": _one_element_off,
    "unit": _unit_off,
    "vect_config": lambda mask: _copy(mask, vect_config=OTHER),
    "unit_config": lambda mask: _copy(mask, unit_config=OTHER),
}


class _Stores:
    """The in-memory store, or the Redis store over the fake server that runs
    the store's scripts as Lua text (tests/test_redis_storage.py)."""

    def __init__(self, kind: str):
        self.kind = kind
        self.fake = None

    async def __aenter__(self):
        if self.kind == "memory":
            return InMemoryCoordinatorStorage()
        self.fake = FakeRedis()
        self.store = RedisCoordinatorStorage(port=await self.fake.start())
        return self.store

    async def __aexit__(self, *exc):
        if self.fake is not None:
            await self.store.client.close()
            await self.fake.stop()


@pytest.fixture(params=["memory", "redis"])
def kind(request):
    return request.param


async def _with_sum_participants(store, n: int = 6):
    for i in range(1, n + 1):
        assert await store.add_sum_participant(_pk(i), b"e" * 32) is None
    return store


def _run(kind: str, body):
    async def run():
        async with _Stores(kind) as store:
            return await body(await _with_sum_participants(store))

    return asyncio.run(run())


def test_equal_masks_interleaved_and_byte_planar_are_one_vote_key(kind):
    """The same mask sent in the v1 layout and in the byte-planar v2 layout
    (``_split_count_word``) is one mask: one key, score 2."""
    mask = _mask()
    interleaved, _ = parse_mask_object(serialize_mask_object(mask))
    planar, _ = parse_mask_object(serialize_mask_object(mask, planar_vect=True))
    assert serialize_mask_object(mask) != serialize_mask_object(mask, planar_vect=True)

    async def body(store):
        assert await store.incr_mask_score(_pk(1), interleaved) is None
        assert await store.incr_mask_score(_pk(2), planar) is None
        assert await store.number_of_unique_masks() == 1
        return await store.best_masks()

    assert _run(kind, body) == [(mask, 2)]


@pytest.mark.parametrize("what", sorted(DIFFERENT))
def test_masks_that_differ_anywhere_are_two_vote_keys(kind, what):
    mask = _mask()
    other = DIFFERENT[what](mask)
    assert other != mask

    async def body(store):
        assert await store.incr_mask_score(_pk(1), mask) is None
        assert await store.incr_mask_score(_pk(2), other) is None
        assert await store.incr_mask_score(_pk(3), _copy(mask)) is None
        assert await store.number_of_unique_masks() == 2
        return await store.best_masks()

    assert _run(kind, body) == [(mask, 2), (other, 1)]


def test_vote_errors_and_their_order(kind):
    """Membership is checked before the single submission, and a refused
    vote moves no score."""
    mask = _mask()

    async def body(store):
        assert await store.incr_mask_score(_pk(9), mask) is MaskScoreIncrError.UNKNOWN_SUM_PK
        assert await store.best_masks() is None
        assert await store.incr_mask_score(_pk(1), mask) is None
        for again in (mask, _mask(2)):
            assert (
                await store.incr_mask_score(_pk(1), again)
                is MaskScoreIncrError.MASK_ALREADY_SUBMITTED
            )
        # a participant of no round that also voted: still the membership error
        await store.delete_dicts()
        assert await store.incr_mask_score(_pk(1), mask) is MaskScoreIncrError.UNKNOWN_SUM_PK
        await _with_sum_participants(store)
        assert await store.incr_mask_score(_pk(1), mask) is None
        assert await store.number_of_unique_masks() == 1
        return await store.best_masks()

    assert _run(kind, body) == [(mask, 1)]


def test_best_masks_returns_at_most_two_highest_first(kind):
    masks = [_mask(seed) for seed in (1, 2, 3)]

    async def body(store):
        voters = iter(range(1, 7))
        for mask, votes in zip(masks, (1, 3, 2)):
            for _ in range(votes):
                assert await store.incr_mask_score(_pk(next(voters)), _copy(mask)) is None
        assert await store.number_of_unique_masks() == 3
        return await store.best_masks()

    best = _run(kind, body)
    assert best == [(masks[1], 3), (masks[2], 2)]
    assert Unmask._freeze_mask_dict(best) == masks[1]


def test_a_tie_at_the_top_is_ambiguous(kind):
    async def body(store):
        for i, seed in ((1, 1), (2, 2), (3, 1), (4, 2), (5, 3)):
            assert await store.incr_mask_score(_pk(i), _mask(seed)) is None
        return await store.best_masks()

    best = _run(kind, body)
    assert [score for _, score in best] == [2, 2]
    with pytest.raises(PhaseError) as err:
        Unmask._freeze_mask_dict(best)
    assert err.value.kind == "AmbiguousMasks"


# votes as (sum participant, mask seed, sent byte-planar?)
SEQUENCES = {
    "one_vote": [(1, 1, False)],
    "all_agree": [(1, 1, False), (2, 1, True), (3, 1, False)],
    "majority": [(1, 2, True), (2, 1, False), (3, 2, False), (4, 3, False), (5, 2, True)],
    "refused_votes_between": [(1, 1, False), (9, 1, False), (1, 2, False), (2, 2, True), (3, 2, False)],
}


@pytest.mark.parametrize("name", sorted(SEQUENCES))
def test_memory_and_redis_stores_give_the_same_scores(name):
    async def body(store):
        verdicts = []
        for voter, seed, planar in SEQUENCES[name]:
            sent, _ = parse_mask_object(serialize_mask_object(_mask(seed), planar_vect=planar))
            verdicts.append(await store.incr_mask_score(_pk(voter), sent))
        best = await store.best_masks()
        return verdicts, await store.number_of_unique_masks(), [
            (serialize_mask_object(mask), score) for mask, score in best
        ]

    assert _run("memory", body) == _run("redis", body)


@pytest.mark.parametrize("planar", [False, True], ids=["interleaved", "byte_planar"])
def test_replayed_votes_land_on_the_key_of_live_votes(kind, planar):
    """``restore_round_dicts`` replays the journal's serialised votes through
    ``incr_mask_score``: a replayed vote and a live vote for one mask are one
    key, and a vote the store still holds is not counted twice."""
    mask = _mask()
    live, _ = parse_mask_object(serialize_mask_object(mask, planar_vect=planar))

    async def body(store):
        assert await store.incr_mask_score(_pk(1), live) is None
        sum_dict = {_pk(i): b"e" * 32 for i in range(1, 7)}
        votes = [(_pk(1), serialize_mask_object(mask)), (_pk(2), serialize_mask_object(mask)),
                 (_pk(3), serialize_mask_object(_mask(2)))]
        await store.restore_round_dicts(sum_dict, {}, votes)
        assert await store.number_of_unique_masks() == 2
        return await store.best_masks()

    assert _run(kind, body) == [(mask, 2), (_mask(2), 1)]


def test_votes_are_counted_by_how_the_store_keyed_them(kind):
    route, other = {"memory": ("kept", "serialised"), "redis": ("serialised", "kept")}[kind]

    def read():
        return MASK_VOTES.labels(route=route).value, MASK_VOTES.labels(route=other).value

    async def body(store):
        before = read()
        assert await store.incr_mask_score(_pk(1), _mask()) is None
        assert await store.incr_mask_score(_pk(2), _mask()) is None
        # refused votes are no votes
        assert await store.incr_mask_score(_pk(2), _mask()) is not None
        assert await store.incr_mask_score(_pk(9), _mask()) is not None
        return before, read()

    before, after = _run(kind, body)
    assert (after[0] - before[0], after[1] - before[1]) == (2, 0)


def test_the_memory_store_keeps_the_object_it_scored():
    """One vote: what ``best_masks`` returns IS the object that was scored;
    a second vote for an equal mask keeps the first object."""
    mask, twin = _mask(), _copy(_mask())

    async def body(store):
        assert await store.incr_mask_score(_pk(1), mask) is None
        (kept, score), = await store.best_masks()
        assert kept is mask and score == 1
        assert await store.incr_mask_score(_pk(2), twin) is None
        (kept, score), = await store.best_masks()
        assert kept is mask and score == 2
        await store.delete_dicts()
        assert await store.best_masks() is None and await store.number_of_unique_masks() == 0

    _run("memory", body)


def _parsed_elements() -> float:
    return sum(
        child.value for labels, child in codec.ELEMENTS.children() if labels[0] == "parse"
    )


def test_unmask_elects_the_scored_object_without_a_parse():
    """The Unmask phase over the in-memory store: the mask it validates and
    subtracts is the object the Sum2 phase scored, and no element is parsed
    between the vote and the model (``xaynet_codec_elements_total{op="parse"}``)."""
    from test_resilience import _settings

    from xaynet_tpu.server.coordinator import CoordinatorState
    from xaynet_tpu.server.events import EventPublisher, PhaseName
    from xaynet_tpu.server.phases.base import Shared
    from xaynet_tpu.server.requests import RequestReceiver
    from xaynet_tpu.storage.memory import InMemoryModelStorage
    from xaynet_tpu.storage.traits import Store

    n = 257
    settings = _settings(model_len=n)
    state = CoordinatorState.from_settings(settings)
    config = state.round_params.mask_config
    model = np.random.default_rng(3).uniform(-1, 1, size=n).astype(np.float32)
    seed, masked = Masker(config).mask(Scalar(1, 1), model)
    agg = Aggregation(config, n)
    agg.validate_aggregation(masked)
    agg.aggregate(masked)
    mask = seed.derive_mask(n, config)

    seen = []
    validate = agg.validate_unmasking
    agg.validate_unmasking = lambda elected: (seen.append(elected), validate(elected))[1]

    async def run():
        coord = InMemoryCoordinatorStorage()
        assert await coord.add_sum_participant(_pk(1), b"e" * 32) is None
        assert await coord.incr_mask_score(_pk(1), mask) is None
        shared = Shared(
            state=state,
            request_rx=RequestReceiver(),
            events=EventPublisher(
                round_id=0, keys=state.keys, params=state.round_params, phase=PhaseName.IDLE),
            store=Store(coord, InMemoryModelStorage(), None),
            settings=settings,
            metrics=None,
        )
        phase = Unmask(shared, agg)
        before = _parsed_elements()
        await phase.process()
        return phase.global_model, _parsed_elements() - before

    global_model, parsed = asyncio.run(run())
    assert len(seen) == 1 and seen[0] is mask
    assert parsed == 0
    np.testing.assert_allclose(global_model, model.astype(np.float64), atol=1e-9)
