"""Packed wire (v2 byte-planar) transport through the aggregation path.

Promotes the packed-rows pivot smoke into the suite: a round that mixes
wire v1 (interleaved uint32) and wire v2 (byte-planar) members on the
device aggregator must finalize byte-identically to the host eager
control — at mesh=1 and mesh=8 — while v2 members stay PACKED uint8
rows through staging. Malformed and truncated packed bodies must reject
cleanly without poisoning the accumulator, and the round-parameter
negotiation must round-trip the wire format.
"""

import jax
import numpy as np
import pytest

from xaynet_tpu.core.common import RoundParameters, RoundSeed
from xaynet_tpu.core.mask import (
    BoundType,
    DataType,
    GroupType,
    MaskConfig,
    Masker,
    ModelType,
    Scalar,
)
from xaynet_tpu.core.mask.masking import AggregationError
from xaynet_tpu.core.mask.object import MaskObject
from xaynet_tpu.core.mask.serialization import (
    DecodeError,
    parse_mask_vect,
    serialize_mask_vect,
)
from xaynet_tpu.parallel.mesh import make_mesh
from xaynet_tpu.server.aggregation import StagedAggregator

CFG = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)
N = 57


def _mesh(n_devices: int):
    assert len(jax.devices()) >= n_devices, jax.devices()
    return make_mesh(jax.devices()[:n_devices])


def _mixed_members(k: int, seed: int = 5):
    """k masked members, alternating wire v2 (planar) / v1, each
    round-tripped through the real serializer so staging sees exactly the
    bytes a participant would put on the wire."""
    rng = np.random.default_rng(seed)
    members = []
    for i in range(k):
        w = rng.uniform(-1, 1, N).astype(np.float32)
        _, masked = Masker(CFG.pair()).mask(Scalar(1, k), w)
        planar = i % 2 == 0
        blob = serialize_mask_vect(masked.vect, planar=planar)
        vect, _ = parse_mask_vect(blob, lazy=True)
        assert vect.planar is planar
        members.append((MaskObject(vect, masked.unit), masked))
    return members


@pytest.mark.parametrize("mesh_n", [1, 8])
def test_mixed_wire_round_matches_all_legacy_control(mesh_n):
    members = _mixed_members(6)
    host = StagedAggregator(CFG.pair(), N, device=False, batch_size=8)
    dev = StagedAggregator(
        CFG.pair(), N, device=True, batch_size=8, kernel="xla",
        mesh=_mesh(mesh_n),
    )
    # batch-prevalidate half, per-member validate the rest: both intake
    # code paths must land in the same accumulator state
    dev.prevalidate_wire_batch([obj for obj, _ in members[:3]])
    for obj, masked in members:
        host.validate_aggregation(masked)
        host.aggregate(masked)
        dev.validate_aggregation(obj)
        staged = obj.vect._staged_planar
        assert staged is not None
        if obj.vect.planar:
            # the v2 promise: accepted rows stay byte-planar uint8 planes
            # (bytes_per_number x padded), never widened to uint32 limbs
            assert staged.dtype == np.uint8 and staged.ndim == 2
            assert staged.shape[0] == CFG.bytes_per_number
        else:
            assert staged.dtype == np.uint32
        dev.aggregate(obj)
    dev.drain()
    a, b = host.finalize(), dev.finalize()
    assert a.nb_models == b.nb_models == len(members)
    assert a.object == b.object


@pytest.mark.parametrize("mesh_n", [1, 8])
def test_invalid_planar_member_rejects_without_poisoning(mesh_n):
    rng = np.random.default_rng(11)
    w = rng.uniform(-1, 1, N).astype(np.float32)
    _, masked = Masker(CFG.pair()).mask(Scalar(1, 2), w)
    blob = bytearray(serialize_mask_vect(masked.vect, planar=True))
    # blast every plane of element 0 to 0xFF -> value >= group order
    bpn = CFG.bytes_per_number
    hdr = len(blob) - bpn * N
    for p in range(bpn):
        blob[hdr + p * N] = 0xFF
    vect, _ = parse_mask_vect(bytes(blob), lazy=True)
    bad = MaskObject(vect, masked.unit)

    agg = StagedAggregator(
        CFG.pair(), N, device=True, batch_size=8, kernel="xla",
        mesh=_mesh(mesh_n),
    )
    with pytest.raises(AggregationError, match="InvalidObject"):
        agg.validate_aggregation(bad)

    # the reject must not poison the round: a good member still folds and
    # the aggregate equals the host control
    host = StagedAggregator(CFG.pair(), N, device=False, batch_size=8)
    for obj, good in _mixed_members(2, seed=13):
        host.validate_aggregation(good)
        host.aggregate(good)
        agg.validate_aggregation(obj)
        agg.aggregate(obj)
    agg.drain()
    assert host.finalize().object == agg.finalize().object


def test_truncated_planar_body_rejects_cleanly():
    rng = np.random.default_rng(17)
    w = rng.uniform(-1, 1, N).astype(np.float32)
    _, masked = Masker(CFG.pair()).mask(Scalar(1, 1), w)
    blob = serialize_mask_vect(masked.vect, planar=True)
    # eager and lazy parse must both reject every truncation point
    for cut in (len(blob) - 1, len(blob) // 2, 5):
        for lazy in (False, True):
            with pytest.raises(DecodeError):
                vect, _ = parse_mask_vect(blob[:cut], lazy=lazy)
                # lazy parses defer the element block: force it
                np.asarray(vect.numbers())


def test_planar_staging_strictly_narrower_than_legacy_uint32():
    """The point of v2: both wire framings pack bytes_per_number bytes per
    element, but a LEGACY member is widened to 4*n_limbs uint32 planes
    before host->device staging while a v2 member stages its byte planes
    verbatim — strictly fewer bytes per accepted update whenever the
    group order is not a whole number of limbs, which is true of the
    production default (PRIME/F32/B0/M3: 6 < 8 bytes per element)."""
    from xaynet_tpu.ops.limbs import n_limbs_for_order
    from xaynet_tpu.server.settings import MaskSettings

    cfg = MaskSettings().to_config()
    assert cfg.bytes_per_number < 4 * n_limbs_for_order(cfg.order)
    # and framing v2 never costs more wire bytes than v1 for one member
    rng = np.random.default_rng(19)
    w = rng.uniform(-1, 1, N).astype(np.float32)
    _, masked = Masker(cfg.pair()).mask(Scalar(1, 1), w)
    v2 = serialize_mask_vect(masked.vect, planar=True)
    v1 = serialize_mask_vect(masked.vect, planar=False)
    assert len(v2) <= len(v1)


def test_round_parameters_negotiate_wire_format():
    params = RoundParameters(
        pk=b"\x01" * 32,
        sum=0.5,
        update=0.9,
        seed=RoundSeed(b"\x07" * 32),
        mask_config=CFG.pair(),
        model_length=N,
        wire_format=2,
    )
    assert RoundParameters.from_dict(params.to_dict()).wire_format == 2
    # legacy coordinators omit the field: clients must default to v1
    legacy = params.to_dict()
    legacy.pop("wire_format")
    assert RoundParameters.from_dict(legacy).wire_format == 1


# --- the eager parse of a v2 block (ISSUE 50) --------------------------------

EAGER_MASKS = {
    "integer-b0m6": CFG,
    "integer-b6m6": MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B6, ModelType.M6),
    "prime-b0m3": MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3),
}


@pytest.mark.parametrize("streamed", [False, True], ids=["buffer", "stream"])
@pytest.mark.parametrize("mask", list(EAGER_MASKS))
def test_eager_v2_parse_equals_the_v1_parse_and_the_masked_limbs(mask, streamed, monkeypatch):
    """v2 eager == v1 eager == the limbs that were masked; the v2 vector is a
    checked view of the body (no limb row until someone asks for ``.data``,
    and then through the counted fallback), and re-emits byte for byte."""
    from xaynet_tpu.core.mask import serialization
    from xaynet_tpu.core.mask.object import LazyWireMaskVect, wire_route
    from xaynet_tpu.core.message.encoder import ChunkReader
    from xaynet_tpu.telemetry.registry import get_registry

    cfg = EAGER_MASKS[mask]
    w = np.random.default_rng(23).uniform(-1, 1, N).astype(np.float32)
    _, masked = Masker(cfg.pair()).mask(Scalar(1, 4), w)

    def parse(blob):
        if streamed:
            return serialization.parse_mask_vect_stream(ChunkReader([blob[:11], blob[11:]]))
        return parse_mask_vect(blob)[0]

    v1 = parse(serialize_mask_vect(masked.vect, planar=False))
    blob2 = serialize_mask_vect(masked.vect, planar=True)
    calls = []
    real = serialization.planar_to_interleaved
    monkeypatch.setattr(serialization, "planar_to_interleaved",
                        lambda *a: calls.append(a[1]) or real(*a))
    v2 = parse(blob2)
    assert type(v1).__name__ == "MaskVect" and wire_route(v1) == ("legacy", "relayout")
    assert isinstance(v2, LazyWireMaskVect) and v2.planar and v2.checked and not v2.materialized
    assert wire_route(v2) == ("packed", "copy")
    assert v2.is_valid() and not calls and not v2.materialized  # the parse's verdict, no scan
    assert v2.planar_block.shape == (cfg.bytes_per_number, N)
    assert serialize_mask_vect(v2, planar=True) == blob2 and not v2.materialized
    generic = lambda: get_registry().sample_value(  # noqa: E731
        "xaynet_codec_elements_total", {"op": "parse", "route": "generic"}) or 0.0
    before = generic()
    assert v2 == v1 == masked.vect  # materialises: the fallback, counted
    assert calls == [N] and generic() - before >= N
    assert wire_route(v2) == ("packed", "relayout")
    assert np.array_equal(v2.data, masked.vect.data) and v2.is_valid()


@pytest.mark.parametrize("mask", list(EAGER_MASKS))
def test_eager_v2_parse_rejects_an_element_out_of_the_group_as_the_v1_parse_does(mask):
    cfg = EAGER_MASKS[mask]
    w = np.random.default_rng(29).uniform(-1, 1, N).astype(np.float32)
    _, masked = Masker(cfg.pair()).mask(Scalar(1, 4), w)
    from xaynet_tpu.ops import limbs as limb_ops

    masked.vect.data[N // 2] = limb_ops.int_to_limbs(cfg.order, masked.vect.data.shape[1])
    errors = []
    for planar in (False, True):
        with pytest.raises(DecodeError) as err:
            parse_mask_vect(serialize_mask_vect(masked.vect, planar=planar))
        errors.append(str(err.value))
    assert errors[0] == errors[1] == "mask vector element >= group order"
    # the lazy parse (wire ingest) still defers the verdict to the device
    vect, _ = parse_mask_vect(serialize_mask_vect(masked.vect, planar=True), lazy=True)
    assert not vect.checked


@pytest.mark.parametrize("library", ["native", "numpy"])
def test_copy_planes_copies_a_column_range_through_both_strides(library, monkeypatch):
    from xaynet_tpu.ops import limbs as limb_ops
    from xaynet_tpu.utils import native

    if library == "numpy":
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", True)
    rng = np.random.default_rng(31)
    n = 5_000_011  # more than one thread's slice
    planes = np.frombuffer(rng.bytes(3 * n), dtype=np.uint8).reshape(3, n)
    out = np.full((3, n + 64), 7, dtype=np.uint8)
    limb_ops.copy_planes(planes[:, 5 : n - 9], out[:, : n - 14])
    assert np.array_equal(out[:, : n - 14], planes[:, 5 : n - 9])
    assert (out[:, n - 14 :] == 7).all()
    with pytest.raises(ValueError):
        limb_ops.copy_planes(planes, out)


@pytest.mark.parametrize("mask", list(EAGER_MASKS))
def test_the_planar_serialiser_writes_the_same_bytes_with_and_without_the_library(
        mask, monkeypatch):
    """The sender's side: the planes of a v2 body come from the library's
    plane pack where it loads, from numpy's strided copy otherwise; plane b
    holds byte b of every element either way."""
    from xaynet_tpu.utils import native

    cfg = EAGER_MASKS[mask]
    n = 600_011  # more than one thread's slice
    w = np.random.default_rng(37).uniform(-1, 1, n).astype(np.float32)
    _, masked = Masker(cfg.pair()).mask(Scalar(1, 2), w)
    assert native.load() is not None
    fast = serialize_mask_vect(masked.vect, planar=True)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)
    assert serialize_mask_vect(masked.vect, planar=True) == fast
    bpn = cfg.bytes_per_number
    planes = np.frombuffer(fast, dtype=np.uint8)[-bpn * n:].reshape(bpn, n)
    wire = np.frombuffer(serialize_mask_vect(masked.vect, planar=False), dtype=np.uint8)
    assert np.array_equal(planes.T, wire[-bpn * n:].reshape(n, bpn))
