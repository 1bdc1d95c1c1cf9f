"""Served rounds of v1 bodies on a coordinator whose staging slots are byte
planes (ISSUE 51): the message handler is told of its consumer as the runner
tells it (``update_planes = slots_take_planes(settings)``), an Update's
vector is relaid to checked planes once, in its parse, and its slot takes
them by copy. Held to the limb road (the same round with a handler that is
told nothing) bit for bit, and to the plain integer reference.

The round, its participants and its counters are ``test_packed_wire_round``'s.
"""

import types

import jax
import pytest

from test_packed_wire_round import (
    K,
    MASKS,
    MODEL_LEN,
    ROUTES,
    WIRES,
    _forged,
    _reference,
    _run,
    _same_bits,
    _settings,
    _with_element,
    fallback_calls,  # noqa: F401  (the fixture)
)
from xaynet_tpu.core.mask import BoundType, DataType, GroupType, MaskConfig, ModelType
from xaynet_tpu.parallel import aggregator as aggregator_mod
from xaynet_tpu.parallel import streaming
from xaynet_tpu.parallel.mesh import make_mesh
from xaynet_tpu.server.aggregation import build_staged_aggregator, slots_take_planes
from xaynet_tpu.server.settings import Settings
from xaynet_tpu.tenancy.pool import get_pool
from xaynet_tpu.utils import native


@pytest.fixture(params=[1, 2], ids=["one-shard", "two-shards"])
def shards(request, monkeypatch, tmp_path):
    monkeypatch.setenv("XAYNET_FLIGHT_DIR", str(tmp_path / "flight"))
    n = request.param
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:n]))
    return n


def _as_the_runner(settings: Settings) -> dict:
    """What ``server/runner.py`` tells the handler of these settings."""
    return {"wire_ingest": settings.aggregation.wire_ingest,
            "update_planes": slots_take_planes(settings)}


def _generic(moved: dict) -> float:
    return sum(moved["codec", op, "generic"] for op in ("parse", "validate", "stage"))


@pytest.mark.parametrize("mask", list(MASKS))
def test_a_served_round_of_v1_bodies_is_the_limb_roads_bit_for_bit(mask, shards, fallback_calls):
    config, n_update = MASKS[mask], 2 * K  # two fold batches
    assert native.load() is not None
    depth0, leases0 = streaming.STAGING_DEPTH.value, get_pool().stats()["leases"]
    settings = _settings(config, n_update, "legacy")
    told = _as_the_runner(settings)
    assert told == {"wire_ingest": False, "update_planes": True}
    out = _run(settings, ["sdk"] * n_update, **told)
    limb_road = _run(_settings(config, n_update, "legacy"), ["sdk"] * n_update)

    assert out["model"].tobytes() == limb_road["model"].tobytes()
    assert _same_bits(out["model"], _reference(config, list(range(n_update))))
    moved, was, block = out["moved"], limb_road["moved"], config.bytes_per_number * MODEL_LEN
    for m in (moved, was):
        assert (m["accepted"], m["rejected"], m["failed"], m["rows"]) == (n_update, 0, 0, n_update)
        assert m["folded"] >= 2 and _generic(m) == 0
    # the counter that says the mechanism engages: every staged byte came on
    # the legacy wire and went into its slot by copy
    assert moved["wire", "legacy", "copy"] == n_update * block
    assert sum(moved["wire", w, r] for w in WIRES for r in ROUTES) == n_update * block
    assert was["wire", "legacy", "relayout"] == n_update * block and was["wire", "legacy", "copy"] == 0
    # what wire.legacy_copy_share, wire.packed_share and wire.copy_share read
    assert moved["wire", "packed", "copy"] == 0 and sum(
        moved["wire", "packed", r] for r in ROUTES) == 0
    # every element is compared with the order once, in the pass that parses
    # it, where the limb road scans in the parse and again in
    # validate_aggregation; both parse every vector once (units and the Sum2
    # message's mask alike on both roads)
    assert moved["codec", "parse", "fast"] == was["codec", "parse", "fast"]
    assert was["codec", "validate", "fast"] - moved["codec", "validate", "fast"] \
        == n_update * MODEL_LEN
    assert moved["codec", "stage", "fast"] == was["codec", "stage", "fast"] == n_update * MODEL_LEN
    assert fallback_calls == []  # nobody asked for limb rows
    assert out["depth"] == depth0 and get_pool().stats()["leases"] == leases0
    last = out["healthz"]["wire"]
    assert (last["legacy"], last["packed"], last["copied"]) == (K, 0, K)
    assert out["healthz"]["shards"] == shards


def test_v1_and_v2_bodies_in_one_batch_both_go_by_copy(shards, fallback_calls):
    config, n_update = MASKS["integer-b0m6"], 2 * K
    senders = ["sdk", "legacy", "sdk", "legacy", "legacy", "sdk"]
    settings = _settings(config, n_update, "packed")
    out = _run(settings, senders, **_as_the_runner(settings))
    assert _same_bits(out["model"], _reference(config, list(range(n_update))))
    moved, block = out["moved"], config.bytes_per_number * MODEL_LEN
    assert (moved["accepted"], moved["rejected"], moved["failed"]) == (n_update, 0, 0)
    assert moved["wire", "packed", "copy"] == senders.count("sdk") * block
    assert moved["wire", "legacy", "copy"] == senders.count("legacy") * block
    assert moved["wire", "legacy", "relayout"] == moved["wire", "packed", "relayout"] == 0
    assert fallback_calls == [] and _generic(moved) == 0
    last = out["healthz"]["wire"]
    assert last["packed"] + last["legacy"] == K == last["copied"]


@pytest.mark.parametrize("coordinator", ["host", "wire-ingest", "unpacked-staging"])
def test_the_coordinators_that_want_limb_rows_or_the_wire_keep_their_routes(
        coordinator, monkeypatch, tmp_path, fallback_calls):
    monkeypatch.setenv("XAYNET_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:1]))
    config, n_update = MASKS["integer-b0m6"], K
    settings = _settings(config, n_update, "legacy")
    if coordinator == "host":
        settings.aggregation.device = False
    elif coordinator == "wire-ingest":
        settings.aggregation.wire_ingest = True
    else:
        settings.aggregation.packed_staging = False
    told = _as_the_runner(settings)
    # a wire-ingest coordinator's slots are planes too, but its parse is lazy:
    # the device unpacks the body as it came
    assert told["update_planes"] is (coordinator == "wire-ingest")
    out = _run(settings, ["sdk"] * n_update, **told)
    assert _same_bits(out["model"], _reference(config, list(range(n_update))))
    moved, block = out["moved"], config.bytes_per_number * MODEL_LEN
    route = "device" if coordinator == "wire-ingest" else "relayout"
    assert moved["wire", "legacy", route] == n_update * block
    assert sum(moved["wire", w, r] for w in WIRES for r in ROUTES) == n_update * block
    assert fallback_calls == [] and _generic(moved) == 0


@pytest.mark.parametrize("mask", list(MASKS))
def test_a_v1_member_out_of_the_group_is_dropped_by_the_parse_on_the_plane_road(mask, shards):
    """An element equal to the order, at the first, a middle and the last
    position of a v1 body: ``ServiceError`` of stage ``parse`` with the limb
    road's words, nothing reaches a slot, the aggregate is the others'."""
    config, n_update = MASKS[mask], 2 * K
    depth0, leases0 = streaming.STAGING_DEPTH.value, get_pool().stats()["leases"]

    def forged(params, sums):
        return [_forged(config, params, sums, 90 + j, False, _with_element(pos, config.order))
                for j, pos in enumerate([0, MODEL_LEN // 2, MODEL_LEN - 1])]

    settings = _settings(config, n_update, "legacy")
    out = _run(settings, ["sdk"] * n_update, forged, **_as_the_runner(settings))
    limb_road = _run(_settings(config, n_update, "legacy"), ["sdk"] * n_update, forged)
    assert out["answers"] == limb_road["answers"] \
        == [("ServiceError", "parse: mask vector element >= group order")] * 3
    assert _same_bits(out["model"], _reference(config, list(range(n_update))))
    moved = out["moved"]
    assert (moved["accepted"], moved["rows"], moved["failed"]) == (n_update, n_update, 0)
    assert moved["wire", "legacy", "copy"] == n_update * config.bytes_per_number * MODEL_LEN
    assert out["depth"] == depth0 and get_pool().stats()["leases"] == leases0


_SETTINGS_MASKS = {
    **MASKS,
    # an order of 2^96 fills its three limbs: packed staging shrinks nothing
    "power2-bmax-m9": MaskConfig(GroupType.POWER2, DataType.I32, BoundType.BMAX, ModelType.M9),
    "power2-b4m12": MaskConfig(GroupType.POWER2, DataType.F32, BoundType.B4, ModelType.M12),
}


@pytest.mark.parametrize("packed_staging", [True, False], ids=["packed", "unpacked"])
@pytest.mark.parametrize("device", [True, False], ids=["device", "host"])
@pytest.mark.parametrize("mask", list(_SETTINGS_MASKS))
def test_what_the_handler_is_told_is_what_the_aggregator_takes(mask, device, packed_staging,
                                                               monkeypatch):
    """``slots_take_planes`` reads the settings before a round has an
    aggregator; the aggregator those settings build has to agree."""
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:1]))
    config = _SETTINGS_MASKS[mask]
    settings = _settings(MASKS["integer-b0m6"], K, "legacy")
    settings.mask.group_type, settings.mask.data_type = config.group_type, config.data_type
    settings.mask.bound_type, settings.mask.model_type = config.bound_type, config.model_type
    settings.aggregation.device, settings.aggregation.packed_staging = device, packed_staging
    shared = types.SimpleNamespace(
        settings=settings, tenant="default",
        state=types.SimpleNamespace(round_params=types.SimpleNamespace(
            mask_config=settings.mask.to_config().pair(), model_length=64)))
    stream = build_staged_aggregator(shared)._stream
    assert slots_take_planes(settings) is (stream is not None and stream.takes_planes)
    fills_its_limbs = config.bytes_per_number % 4 == 0
    assert slots_take_planes(settings) is (device and packed_staging and not fills_its_limbs)


def test_a_handler_told_of_plane_slots_brings_kept_pages_to_its_parses():
    from xaynet_tpu.ops.limbs import PlaneBuffers
    from xaynet_tpu.server.services import MessageWorkers, PetMessageHandler

    workers = MessageWorkers(2)
    try:
        told = PetMessageHandler(events=None, request_tx=None, workers=workers, update_planes=True)
        assert isinstance(told.update_planes, PlaneBuffers)
        assert told.update_planes._keep == workers.size + 4  # a vector a worker, and the slot copies'
        assert PetMessageHandler(events=None, request_tx=None, workers=workers).update_planes is None
    finally:
        workers.close()
