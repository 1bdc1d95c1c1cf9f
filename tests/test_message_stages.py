"""The stages of one message (server/stages.py, docs/DESIGN.md §16): one
span and one histogram observation per stage per accepted update, all under
the message's own request span and request id, labelled with the phase the
message arrived in, closing on the message's residence; the host-to-device
copy counted in bytes. And the run outside the Update window: the Sum2
message's chain, the Unmask phase's stages (telemetry/unmask.py) and the
start-up timeline (telemetry/startup.py). The message workers: the pool's
size from the host's cores, one pool a process, and a long message's
signature checked beside its parse with no result before the verdict."""

import asyncio
import concurrent.futures
import json
import os
import threading
import time
from fractions import Fraction
from pathlib import Path

import numpy as np
import pytest

from xaynet_tpu.core.crypto.encrypt import EncryptKeyPair
from xaynet_tpu.core.crypto.sign import SigningKeyPair
from xaynet_tpu.sdk.client import HttpClient
from xaynet_tpu.sdk.simulation import keys_for_task
from xaynet_tpu.sdk.state_machine import PetSettings, StateMachine as ParticipantSM
from xaynet_tpu.sdk.traits import ModelStore
from xaynet_tpu.server import stages
from xaynet_tpu.server.rest import RestServer
from xaynet_tpu.server.services import Fetcher, PetMessageHandler
from xaynet_tpu.server.settings import (
    CountSettings,
    PhaseSettings,
    PetSettings as ServerPet,
    Settings,
    Sum2Settings,
    TimeSettings,
)
from xaynet_tpu.server.state_machine import StateMachineInitializer
from xaynet_tpu.storage.memory import (
    InMemoryCoordinatorStorage,
    InMemoryModelStorage,
    NoOpTrustAnchor,
)
from xaynet_tpu.storage.traits import Store
from xaynet_tpu.telemetry import BridgedMetrics, tracing
from xaynet_tpu.telemetry import unmask as unmask_stages

REPO = Path(__file__).resolve().parent.parent
COORD = EncryptKeyPair.derive_from_seed(bytes(range(32)))
SIGNER = SigningKeyPair.derive_from_seed(bytes(range(32, 64)))
N_SUM, N_UPDATE, MODEL_LEN = 1, 4, 500_009
# at 6 wire bytes an element a message of this many is over
# ``unlocked.UNLOCKED_MIN``: its signature is checked beside its parse
LONG_MODEL_LEN = 750_011
SUM_PROB, UPDATE_PROB = 0.4, 0.5
# the chain of a message's residence, in order (to_planar runs beside it)
CHAIN = ("read_body", "pool_wait", "open", "verify", "parse", "resume_wait",
         "request_wait", "validate", "seed_dict", "stage", "flush", "verdict_wait")
# the Sum2 message's chain: the same stages up to the channel, then the
# Sum2 phase's own
SUM2_CHAIN = ("read_body", "pool_wait", "open", "verify", "parse", "resume_wait",
              "request_wait", "score", "verdict_wait")
# the Unmask phase on the host arm (no device, a trust anchor, no journal)
UNMASK_HOST = ("elect", "validate", "subtract", "decode", "save", "proof")
UNMASK_ELSEWHERE = ("mask_put", "fetch", "retire")


class ArrayModelStore(ModelStore):
    def __init__(self, model):
        self.model = model

    async def load_model(self):
        return self.model


def _counts(phase: str = "update") -> dict:
    """Observations so far of each stage, of messages that arrived in ``phase``."""
    return {key[0]: child.count for key, child in stages.SECONDS.children() if key[1] == phase}


def _unmask_counts() -> dict:
    return {key[0]: child.count for key, child in unmask_stages.SECONDS.children()}


def _settings(model_length: int = MODEL_LEN) -> Settings:
    settings = Settings(
        pet=ServerPet(
            sum=PhaseSettings(prob=SUM_PROB, count=CountSettings(N_SUM, N_SUM), time=TimeSettings(0, 30)),
            update=PhaseSettings(prob=UPDATE_PROB, count=CountSettings(N_UPDATE, N_UPDATE), time=TimeSettings(0, 30)),
            sum2=Sum2Settings(count=CountSettings(N_SUM, N_SUM), time=TimeSettings(0, 30)),
        )
    )
    settings.model.length = model_length
    return settings


async def _round(model_length: int = MODEL_LEN) -> dict:
    """One PET round over the REST API on localhost, host aggregation, a
    fold batch of one (so every accepted update fills its batch and pays a
    flush). Returns what the assertions need."""
    settings = _settings(model_length)
    settings.aggregation.batch_size = 1
    store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
    # a recorder, so that the phases' durations reach /metrics as they do
    # behind the runner
    machine, request_tx, events = await StateMachineInitializer(
        settings, store, BridgedMetrics()).init()
    fetcher = Fetcher(events)
    rest = RestServer(fetcher, PetMessageHandler(events, request_tx))
    host, port = await rest.start("127.0.0.1", 0)
    machine_task = asyncio.create_task(machine.run())
    url = f"http://{host}:{port}"
    probe = HttpClient(url)
    out = {"sum2_before": _counts("sum2"), "unmask_before": _unmask_counts()}
    try:
        while fetcher.phase().value != "sum":
            await asyncio.sleep(0.01)
        seed = (await probe.get_round_params()).seed.as_bytes()
        rng = np.random.default_rng(11)
        summers = [
            ParticipantSM(
                PetSettings(keys=keys_for_task(seed, SUM_PROB, UPDATE_PROB, "sum"),
                            max_message_size=None),
                HttpClient(url), ArrayModelStore(None))
        ]
        updaters = [
            ParticipantSM(
                PetSettings(
                    keys=keys_for_task(seed, SUM_PROB, UPDATE_PROB, "update", start=(20 + i) * 1000),
                    scalar=Fraction(1, N_UPDATE),
                    max_message_size=None),  # one message per update, no chunks
                HttpClient(url),
                ArrayModelStore(rng.uniform(-1, 1, model_length).astype(np.float32)))
            for i in range(N_UPDATE)
        ]

        async def drive(sm):
            for _ in range(1000):
                try:
                    await sm.transition()
                except Exception:
                    pass
                # the round's model is published: whatever role the next
                # round's draw gives this participant is not of this round
                if await probe.get_model() is not None:
                    return
                await asyncio.sleep(0.01)

        sum_tasks = [asyncio.create_task(drive(p)) for p in summers]
        while fetcher.phase().value != "update":
            await asyncio.sleep(0.01)
        # the Update window: counters and /metrics read around it, as the
        # benchmark reads them around its window
        out["before"] = _counts()
        out["metrics_open"] = (await probe._request("GET", "/metrics"))[2].decode()
        out["t_open"] = time.monotonic()

        async def close_window():
            # the window closes with the phase: what participants send once
            # they have re-drawn roles for the next round is not of this round
            while fetcher.phase().value == "update":
                await asyncio.sleep(0.002)
            out["after"] = _counts()
            out["t_close"] = time.monotonic()
            out["metrics_close"] = (await probe._request("GET", "/metrics"))[2].decode()

        await asyncio.gather(close_window(), *(drive(p) for p in updaters), *sum_tasks)
        out["health"] = json.loads((await probe._request("GET", "/healthz"))[2])
        assert await probe.get_model() is not None
        # the model is published: the round's one Sum2 phase and one Unmask
        # phase lie between the window's opening and here
        out["sum2_after"], out["unmask_after"] = _counts("sum2"), _unmask_counts()
        out["metrics_end"] = (await probe._request("GET", "/metrics"))[2].decode()
    finally:
        machine_task.cancel()
        await rest.stop()
        await asyncio.gather(machine_task, return_exceptions=True)
    return out


def _served(model_length: int) -> dict:
    tracer = tracing.get_tracer()
    mode = tracer.mode
    tracer.configure(mode="on")
    try:
        out = asyncio.run(asyncio.wait_for(_round(model_length), timeout=120))
        out["spans"] = [s for s in tracer.ring_spans() if s.start >= out["t_open"]]
    finally:
        tracer.configure(mode=mode)
    return out


@pytest.fixture(scope="module")
def served_round():
    return _served(MODEL_LEN)


@pytest.fixture(scope="module")
def served_long_round():
    """The same round with messages over ``unlocked.UNLOCKED_MIN``."""
    return _served(LONG_MODEL_LEN)


@pytest.mark.parametrize("label", CHAIN)
def test_each_stage_observed_once_per_accepted_update(served_round, label):
    # observations of messages that arrived in the Update phase: the sum
    # participant's Sum2 message, which under a loaded host is read, opened
    # and parsed before the poll has seen the phase end, is not among them
    before, after = served_round["before"], served_round["after"]
    assert after.get(label, 0) - before.get(label, 0) == N_UPDATE


@pytest.mark.parametrize("label", SUM2_CHAIN)
def test_sum2_message_chain_observed_once_per_sum_participant(served_round, label):
    before, after = served_round["sum2_before"], served_round["sum2_after"]
    assert after.get(label, 0) - before.get(label, 0) == N_SUM


def test_sum2_message_has_no_stage_of_the_update_phase(served_round):
    before, after = served_round["sum2_before"], served_round["sum2_after"]
    for label in ("validate", "seed_dict", "stage", "flush", "to_planar"):
        assert after.get(label, 0) == before.get(label, 0), label


def test_stages_of_one_message_carry_one_phase(served_round):
    """The last update's ``verdict_wait`` ends after the phase has moved on
    and the Sum2 message's ``read_body`` begins as the phase does: each
    message's spans all carry the phase its ``rest.request`` opened in."""
    spans = served_round["spans"]
    requests = {s.span_id: s for s in spans
                if s.name == "rest.request" and s.attrs.get("path") == "/message"}
    assert {r.attrs["phase"] for r in requests.values()} >= {"update", "sum2"}
    names = set(stages._SPANS.values()) - {stages._SPANS["to_planar"]}  # beside the chain

    def request_of(span):
        # the Sum2 message's verdict reaches its sender after the round's
        # window has closed (the state machine runs on through Unmask without
        # yielding), and the flush turns a parent still open into a link
        return requests.get(span.parent_id or span.attrs.get("link"))

    seen = 0
    for s in spans:
        if s.name in names and request_of(s) is not None:
            assert s.attrs["phase"] == request_of(s).attrs["phase"], s.name
            seen += 1
    assert seen >= len(CHAIN) * N_UPDATE + len(SUM2_CHAIN) * N_SUM
    score = [s for s in spans if s.name == "sum2.score"]
    assert len(score) == N_SUM and score[0].attrs["phase"] == "sum2"
    assert request_of(score[0]).attrs["phase"] == "sum2"
    assert score[0].attrs["bytes"] > 0


def test_sum_participant_spans_its_two_steps_before_the_compose(served_round):
    """``sum2.open_seeds`` then ``sum2.derive`` then the Sum2 message's
    ``message.compose``, in the participant's own trace (here one process)."""
    spans = served_round["spans"]
    opened = [s for s in spans if s.name == "sum2.open_seeds"]  # the empty polls leave none
    derived = [s for s in spans if s.name == "sum2.derive"]
    assert len(opened) == N_SUM and len(derived) == N_SUM
    assert opened[0].attrs["masks"] == N_UPDATE
    assert derived[0].attrs["masks"] == N_UPDATE and derived[0].attrs["elements"] == MODEL_LEN
    assert derived[0].attrs["route"] in ("fused", "fast", "generic")
    composed = [s for s in spans if s.name == "message.compose" and s.start >= derived[0].start]
    assert composed and opened[0].start + opened[0].duration <= derived[0].start
    assert derived[0].start + derived[0].duration <= composed[0].start


def test_stage_spans_share_the_rid_and_parent_of_their_message(served_round):
    spans = served_round["spans"]
    by_id = {s.span_id: s for s in spans}
    names = {stages._SPANS[label] for label in CHAIN}
    by_rid: dict[str, list] = {}
    for s in spans:
        if s.name in names:
            by_rid.setdefault(s.attrs["rid"], []).append(s)
    # The window's messages are the Updates that reached the phase before it
    # closed. Not "every span that started before ``t_close``": the sum
    # participant posts its Sum2 message within milliseconds of the phase's
    # end, and under a loaded host its ``rest.read_body`` starts before the
    # test's poll has read ``t_close`` (a fifth rid with one span); the next
    # round's messages follow within tens of milliseconds.
    reached = stages._SPANS["request_wait"]
    by_rid = {
        rid: chain for rid, chain in by_rid.items()
        if any(s.name == reached and s.start <= served_round["t_close"] for s in chain)
    }
    assert len(by_rid) == N_UPDATE and "-" not in by_rid
    for rid, chain in by_rid.items():
        assert sorted(s.name for s in chain) == sorted(names), rid
        parents = {s.parent_id for s in chain}
        assert len(parents) == 1, (rid, [(s.name, s.parent_id) for s in chain])
        parent = by_id[parents.pop()]
        assert parent.name == "rest.request" and parent.attrs["path"] == "/message"
        lo, hi = parent.start, parent.start + parent.duration
        for s in chain:  # tools/trace_report.py's containment, with its tolerance
            assert s.start >= lo - 0.05 and s.start + s.duration <= hi + 0.05, s.name
        assert [s for s in chain if s.name == "rest.read_body"][0].attrs["bytes"] > 6 * MODEL_LEN


def test_stage_closure_from_metrics_reaches_95_percent(served_round):
    """``pipeline.stage_closure`` as the benchmark computes it: the metric's
    own spec, run by the shipped reader over the round's two /metrics reads."""
    from benchmark.harness.coordinator import parse_metrics
    from benchmark.readers import prom_ratio

    spec = json.loads((REPO / "benchmark/layer_metrics/pipeline.stage_closure.json").read_text())
    assert spec["reader"] == "prom_ratio"
    ctx = {"metrics": {"open": parse_metrics(served_round["metrics_open"]),
                       "close": parse_metrics(served_round["metrics_close"])}}
    closure = prom_ratio.read(ctx, **spec["args"])
    assert closure is not None and 95.0 <= closure <= 100.5, closure


@pytest.mark.parametrize("label", UNMASK_HOST)
def test_each_unmask_stage_of_the_host_arm_observed_once_a_round(served_round, label):
    before, after = served_round["unmask_before"], served_round["unmask_after"]
    assert after.get(label, 0) - before.get(label, 0) == 1


def test_unmask_stages_of_other_arms_are_not_observed_on_the_host_arm(served_round):
    before, after = served_round["unmask_before"], served_round["unmask_after"]
    for label in UNMASK_ELSEWHERE:
        assert after.get(label, 0) == before.get(label, 0), label


def test_unmask_spans_lie_under_the_phase_span(served_round):
    spans = served_round["spans"]
    phase = [s for s in spans if s.name == "phase.unmask"]
    assert len(phase) == 1
    lo, hi = phase[0].start, phase[0].start + phase[0].duration
    under = [s for s in spans if s.name.startswith("unmask.")]
    assert sorted(s.name for s in under) == sorted(f"unmask.{label}" for label in UNMASK_HOST)
    for s in under:
        assert s.parent_id == phase[0].span_id, s.name
        assert s.start >= lo and s.start + s.duration <= hi, s.name
    moved = {s.name: s.attrs.get("bytes") for s in under}
    assert moved["unmask.subtract"] >= 6 * MODEL_LEN and moved["unmask.save"] == 8 * MODEL_LEN


def test_unmask_stage_closure_from_metrics_reaches_95_percent(served_round):
    """``unmask.stage_closure`` as the benchmark computes it: the metric's own
    spec, run by the shipped reader over the round's /metrics reads."""
    from benchmark.harness.coordinator import parse_metrics
    from benchmark.readers import prom_ratio

    spec = json.loads((REPO / "benchmark/layer_metrics/unmask.stage_closure.json").read_text())
    assert spec["reader"] == "prom_ratio" and spec["args"]["span"] == ["open", "end"]
    ctx = {"metrics": {"open": parse_metrics(served_round["metrics_open"]),
                       "end": parse_metrics(served_round["metrics_end"])}}
    closure = prom_ratio.read(ctx, **spec["args"])
    assert closure is not None and 95.0 <= closure <= 100.5, closure


def test_healthz_lists_the_mirrored_span_names(served_round):
    section = served_round["health"]["trace"]
    assert section["mode"] == "on" and section["mirror"] is False  # no device, no sink
    listed = set(section["mirrored_spans"])
    assert {"rest.read_body", "pipeline.verify", "update.await_request", "update.flush"} <= listed
    assert {"sum2.score"} | {f"unmask.{label}" for label in UNMASK_HOST + UNMASK_ELSEWHERE} <= listed
    assert not listed & {"round", "rest.request", "phase.update", "phase.unmask",
                         "pipeline.pool_wait", "update.request_wait",
                         "startup.imports", "startup.serving"}


def test_loop_lag_is_observed_while_the_server_runs(served_round):
    from benchmark.harness.coordinator import parse_metrics, sample_sum

    samples = parse_metrics(served_round["metrics_close"])
    assert sample_sum(samples, "xaynet_event_loop_lag_seconds_count") >= 1


def test_h2d_bytes_equal_the_staged_batch_and_to_planar_is_observed():
    """Device aggregation on the CPU backend: one fold batch staged, copied
    row by row and folded; the copies' counter moves by the staged batch's
    bytes."""
    pytest.importorskip("jax")
    import jax

    from xaynet_tpu.core.mask import (
        BoundType, DataType, GroupType, MaskConfig, Masker, ModelType, Scalar)
    from xaynet_tpu.parallel import streaming
    from xaynet_tpu.parallel.mesh import make_mesh
    from xaynet_tpu.parallel.aggregator import BYTES_STAGED
    from xaynet_tpu.server.aggregation import StagedAggregator

    config = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M3).pair()
    n, k = 1_003, 3
    # one device: the single-worker pipeline, as on a one-chip host (the
    # conftest gives the CPU backend eight)
    agg = StagedAggregator(config, n, device=True, batch_size=k, kernel="xla",
                           mesh=make_mesh(jax.devices()[:1]))
    staged0 = sum(child.value for _, child in BYTES_STAGED.children())
    h2d0, n0 = streaming.H2D_BYTES.value, streaming.H2D_SECONDS.count
    planar0 = _counts("-").get("to_planar", 0)  # staged here, by no message
    rng = np.random.default_rng(3)
    for _ in range(k):
        _, masked = Masker(config).mask(
            Scalar(1, k), rng.uniform(-1, 1, size=n).astype(np.float32))
        agg.validate_aggregation(masked)
        agg.stage(masked)
    agg.drain()
    staged = sum(child.value for _, child in BYTES_STAGED.children()) - staged0
    assert agg.nb_models == k and staged > 0
    assert streaming.H2D_BYTES.value - h2d0 == staged
    assert streaming.H2D_SECONDS.count - n0 == k  # staged at arrival: a copy a row
    assert _counts("-").get("to_planar", 0) - planar0 == k
    h2d = [s for s in tracing.get_tracer().ring_spans() if s.name == "stream.h2d"][-k:]
    assert [s.attrs["route"] for s in h2d] == ["row"] * k
    assert sum(s.attrs["bytes"] for s in h2d) == staged


def test_unmask_stages_of_the_device_arm_are_observed_once_and_carry_bytes():
    """Device aggregation on the CPU backend, one device: the mask goes to
    the device, is subtracted there and the result comes back, a stage each;
    the unmasked model is the mean."""
    pytest.importorskip("jax")
    import jax

    from xaynet_tpu.core.mask import (
        BoundType, DataType, GroupType, MaskConfig, Masker, ModelType, Scalar)
    from xaynet_tpu.core.mask.masking import Aggregation
    from xaynet_tpu.parallel.mesh import make_mesh
    from xaynet_tpu.server.aggregation import DeviceAggregation, StagedAggregator

    config = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M3).pair()
    n, k = 1_003, 3
    agg = StagedAggregator(config, n, device=True, batch_size=k, kernel="xla",
                           mesh=make_mesh(jax.devices()[:1]))
    masks = Aggregation(config, n)
    rng = np.random.default_rng(5)
    weights = rng.uniform(-1, 1, size=(k, n)).astype(np.float32)
    for row in weights:
        seed, masked = Masker(config).mask(Scalar(1, k), row)
        agg.validate_aggregation(masked)
        agg.stage(masked)
        mask = seed.derive_mask(n, config)
        masks.validate_aggregation(mask)
        masks.aggregate(mask)
    view = agg.finalize_inplace()
    assert isinstance(view, DeviceAggregation)
    before, t0 = _unmask_counts(), time.monotonic()
    view.validate_unmasking(masks.object)
    model = view.unmask_array(masks.object)
    after = _unmask_counts()
    np.testing.assert_allclose(model, weights.astype(np.float64).mean(axis=0), atol=1e-9)
    for label in ("mask_put", "subtract", "fetch", "decode"):
        assert after.get(label, 0) - before.get(label, 0) == 1, label
    spans = {s.name: s for s in tracing.get_tracer().ring_spans() if s.start >= t0}
    wire_bytes = masks.object.vect.data.nbytes
    assert spans["unmask.mask_put"].attrs["bytes"] == wire_bytes
    assert spans["unmask.fetch"].attrs["bytes"] == wire_bytes
    assert spans["unmask.subtract"].attrs["bytes"] >= wire_bytes  # planar, padded
    order = [spans[f"unmask.{label}"] for label in ("mask_put", "subtract", "fetch", "decode")]
    for first, then in zip(order, order[1:]):
        assert first.start + first.duration <= then.start


def test_two_tenants_in_different_phases_are_labelled_apart():
    """One process, one listener, two tenants: ``a``'s machine runs and
    stands in Sum, ``b``'s was never started and stands in Idle. A message
    POSTed to each is labelled by its own coordinator's phase."""
    from xaynet_tpu.server.rest import TenantRoutes

    async def scenario():
        built, tasks = {}, []
        for tenant in ("a", "b"):
            store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
            settings = _settings(11)
            machine, request_tx, events = await StateMachineInitializer(
                settings, store, tenant=tenant).init()
            built[tenant] = (machine, Fetcher(events), PetMessageHandler(events, request_tx))
        routes = {t: TenantRoutes(fetcher=f, handler=h) for t, (_, f, h) in built.items()}
        rest = RestServer(built["a"][1], built["a"][2], tenants=routes, default_tenant="a")
        host, port = await rest.start("127.0.0.1", 0)
        tasks.append(asyncio.create_task(built["a"][0].run()))
        probe = HttpClient(f"http://{host}:{port}")
        try:
            while built["a"][1].phase().value != "sum":
                await asyncio.sleep(0.01)
            assert built["b"][1].phase().value == "idle"
            before = {phase: _counts(phase) for phase in ("sum", "idle")}
            t0 = time.monotonic()
            for tenant in ("a", "b"):  # a box that does not open: dropped at `open`
                status, _, _ = await probe._request("POST", f"/t/{tenant}/message", b"\x07" * 200)
                assert status == 200
            after = {phase: _counts(phase) for phase in ("sum", "idle")}
        finally:
            for task in tasks:
                task.cancel()
            await rest.stop()
            await asyncio.gather(*tasks, return_exceptions=True)
        return before, after, t0

    tracer = tracing.get_tracer()
    mode = tracer.mode
    tracer.configure(mode="on")
    try:
        before, after, t0 = asyncio.run(asyncio.wait_for(scenario(), timeout=60))
        spans = [s for s in tracer.ring_spans() if s.start >= t0]
    finally:
        tracer.configure(mode=mode)
    for phase in ("sum", "idle"):
        for label in ("pool_wait", "open"):
            assert after[phase].get(label, 0) - before[phase].get(label, 0) == 1, (phase, label)
    requests = {s.span_id: s for s in spans if s.name == "rest.request"}
    assert sorted((r.attrs["tenant"], r.attrs["phase"]) for r in requests.values()) == [
        ("a", "sum"), ("b", "idle")]
    opened = [s for s in spans if s.name == "pipeline.open"]
    assert sorted((requests[s.parent_id].attrs["tenant"], s.attrs["phase"]) for s in opened) == [
        ("a", "sum"), ("b", "idle")]


# --- the message workers ------------------------------------------------------


def _bare_handler(size: int):
    """A handler on workers of its own (closed by the caller), with no state
    machine behind it: what ``_decrypt_parse_one`` needs and no more."""
    from xaynet_tpu.server.services import MessageWorkers

    workers = MessageWorkers(size)
    return PetMessageHandler(events=None, request_tx=None, workers=workers), workers


_LONG: dict = {}


def _long_sum2_bytes() -> bytearray:
    """One signed Sum2 message over ``unlocked.UNLOCKED_MIN`` bytes, a fresh
    copy a call (the callers corrupt theirs)."""
    from xaynet_tpu.core.mask.config import BoundType, DataType, GroupType, MaskConfig, ModelType
    from xaynet_tpu.core.mask.object import MaskObject, MaskUnit, MaskVect
    from xaynet_tpu.core.message import Message, Sum2
    from xaynet_tpu.ops import limbs as limb_ops

    if "raw" not in _LONG:
        config = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M3)
        n_limb = limb_ops.n_limbs_for_bytes(config.bytes_per_number)
        data = np.random.default_rng(7).integers(
            0, 1 << 32, size=(LONG_MODEL_LEN, n_limb), dtype=np.uint64).astype(np.uint32)
        data[:, -1] &= (1 << (config.order.bit_length() - 1 - 32 * (n_limb - 1))) - 1
        unit = np.zeros(limb_ops.n_limbs_for_order(config.order), dtype=np.uint32)
        message = Message(
            participant_pk=SIGNER.public, coordinator_pk=COORD.public.as_bytes(),
            payload=Sum2(sum_signature=bytes(64),
                         model_mask=MaskObject(MaskVect(config, data), MaskUnit(config, unit))))
        _LONG["raw"] = message.to_bytes(SIGNER.secret)
    return bytearray(_LONG["raw"])


def _drop(handler, raw, phase) -> "str | None":
    """How ``_decrypt_parse_one`` drops the sealed ``raw`` (None: it does not)."""
    from xaynet_tpu.server.services import ServiceError

    try:
        handler._decrypt_parse_one(bytearray(COORD.public.encrypt(bytes(raw))), COORD, phase)
    except ServiceError as err:
        return str(err)
    return None


def _verify_bytes_moved() -> dict:
    from xaynet_tpu.server import services

    return {key[0]: child.value for key, child in services._VERIFY_BYTES.children()}


@pytest.mark.parametrize("cores,threads", [(1, 4), (4, 4), (13, 8), (30, 16)])
def test_the_pool_is_sized_from_the_cores_and_never_under_four(monkeypatch, cores, threads):
    from xaynet_tpu.server import services

    assert services.worker_count(cores) == threads
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(cores)))
    workers = services.MessageWorkers()
    try:
        assert workers.size == threads
        assert workers.pool._max_workers == workers.verdicts._max_workers == threads
    finally:
        workers.close()


def test_the_rule_grows_with_the_host_and_leaves_it_cores():
    from xaynet_tpu.server.services import worker_count

    sizes = [worker_count(cores) for cores in range(1, 257)]
    assert sizes == sorted(sizes) and min(sizes) == 4
    # past the floor the other threads of a message's way keep cores
    assert all(size < cores for cores, size in enumerate(sizes, 1) if cores > 6)


def test_where_the_platform_has_no_affinity_the_cpu_count_is_used(monkeypatch):
    from xaynet_tpu.server import services

    monkeypatch.delattr(os, "sched_getaffinity")
    monkeypatch.setattr(os, "cpu_count", lambda: 30)
    assert services.available_cores() == 30
    monkeypatch.setattr(os, "cpu_count", lambda: None)
    assert services.available_cores() == 1


def test_two_tenants_share_the_process_workers_and_the_gauge_says_their_size():
    from xaynet_tpu.server import services

    a = PetMessageHandler(events=None, request_tx=None)
    b = PetMessageHandler(events=None, request_tx=None, wire_ingest=True)
    assert a.workers is b.workers is services.shared_workers()
    assert a.workers.size == services.worker_count(services.available_cores()) >= 4
    assert services._MESSAGE_WORKERS.value == a.workers.size


CORRUPTIONS = {
    "bad signature, sound body": ("signature",),
    "good signature, malformed body": ("body",),
    "both bad": ("body", "signature"),
}


@pytest.mark.parametrize("what", list(CORRUPTIONS))
def test_a_long_message_is_dropped_with_the_error_the_inline_order_gives(monkeypatch, what):
    """Verify-then-parse on the worker (the order before the signature pass
    ran beside the parse, which a short message still takes) and the
    side-by-side route drop one message with one error."""
    from xaynet_tpu.core.crypto import unlocked
    from xaynet_tpu.core.message import Message
    from xaynet_tpu.core.message.message import HEADER_LENGTH
    from xaynet_tpu.server.events import PhaseName

    raw = _long_sum2_bytes()
    if "body" in CORRUPTIONS[what]:  # an element over the group's order, then signed
        first = HEADER_LENGTH + 64 + 8
        raw[first:first + 6] = b"\xff" * 6
        Message.sign_into(raw, 0, len(raw), SIGNER.secret)
    if "signature" in CORRUPTIONS[what]:
        raw[0] ^= 1
    handler, workers = _bare_handler(2)
    try:
        before = _verify_bytes_moved()
        beside = _drop(handler, raw, PhaseName.SUM2)
        moved = _verify_bytes_moved()
        assert moved.get("beside", 0) - before.get("beside", 0) == len(raw)
        assert moved.get("inline", 0) == before.get("inline", 0)
        monkeypatch.setattr(unlocked, "UNLOCKED_MIN", len(raw) + 1)
        inline = _drop(handler, raw, PhaseName.SUM2)
        assert _verify_bytes_moved().get("inline", 0) - before.get("inline", 0) == len(raw)
    finally:
        workers.close()
    assert beside is not None and beside == inline
    if "signature" in CORRUPTIONS[what]:
        assert beside == "parse: invalid message signature"
    else:
        assert beside == "parse: mask vector element >= group order"


def test_no_message_leaves_before_the_verdict(monkeypatch):
    """The parse is done and the signature pass is not: the function has not
    returned; the verdict comes and it returns the message."""
    from xaynet_tpu.core.message import Message
    from xaynet_tpu.server.events import PhaseName

    verdict_due, real = threading.Event(), Message.verify_bytes
    entered = threading.Event()

    def slow_verify(data):
        entered.set()
        assert verdict_due.wait(timeout=30)
        real(data)

    monkeypatch.setattr(Message, "verify_bytes", staticmethod(slow_verify))
    sealed = bytearray(COORD.public.encrypt(bytes(_long_sum2_bytes())))
    handler, workers = _bare_handler(1)
    try:
        parsed0 = _counts("sum2").get("parse", 0)
        result = workers.pool.submit(handler._decrypt_parse_one, sealed, COORD, PhaseName.SUM2)
        deadline = time.monotonic() + 30
        while _counts("sum2").get("parse", 0) == parsed0 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert _counts("sum2").get("parse", 0) == parsed0 + 1 and entered.is_set()
        time.sleep(0.05)
        assert not result.done()
        verdict_due.set()
        assert isinstance(result.result(timeout=30), Message)
    finally:
        verdict_due.set()
        workers.close()


def test_a_bad_verdict_that_comes_late_still_drops_the_message(monkeypatch):
    from xaynet_tpu.core.mask.serialization import DecodeError
    from xaynet_tpu.core.message import Message
    from xaynet_tpu.server.events import PhaseName
    from xaynet_tpu.server.services import ServiceError

    def late_refusal(data):
        time.sleep(0.2)
        raise DecodeError("invalid message signature")

    monkeypatch.setattr(Message, "verify_bytes", staticmethod(late_refusal))
    sealed = bytearray(COORD.public.encrypt(bytes(_long_sum2_bytes())))
    handler, workers = _bare_handler(1)
    try:
        with pytest.raises(ServiceError, match="parse: invalid message signature"):
            handler._decrypt_parse_one(sealed, COORD, PhaseName.SUM2)
    finally:
        workers.close()


def test_four_workers_all_waiting_for_their_verdicts_do_not_deadlock(monkeypatch):
    """Twelve long messages on a pool of four, each signature pass held
    until four passes run at once: every worker is then inside its wait for
    the verdict, and the passes still run, on threads that are not the
    pool's."""
    from xaynet_tpu.core.message import Message
    from xaynet_tpu.server.events import PhaseName

    together, real = threading.Barrier(4, timeout=60), Message.verify_bytes
    threads = set()

    def verify_with_the_others(data):
        threads.add(threading.current_thread().name)
        together.wait()
        real(data)

    monkeypatch.setattr(Message, "verify_bytes", staticmethod(verify_with_the_others))
    box = COORD.public.encrypt(bytes(_long_sum2_bytes()))
    handler, workers = _bare_handler(4)
    try:
        results = [
            workers.pool.submit(handler._decrypt_parse_one, bytearray(box), COORD, PhaseName.SUM2)
            for _ in range(12)
        ]
        done, pending = concurrent.futures.wait(results, timeout=120)
        assert not pending
        assert all(isinstance(r.result(), Message) for r in done)
    finally:
        together.abort()
        workers.close()
    assert threads and all(name.startswith("pet-verify") for name in threads)


def test_verify_and_verify_beside_once_a_long_message_verify_alone_a_short_one():
    from xaynet_tpu.core.message import Message, Sum, Tag
    from xaynet_tpu.server.events import PhaseName

    handler, workers = _bare_handler(1)
    tracer = tracing.get_tracer()
    mode = tracer.mode
    tracer.configure(mode="on")
    try:
        long_raw = _long_sum2_bytes()
        before, t0 = _counts("sum2"), time.monotonic()
        handler._decrypt_parse_one(
            bytearray(COORD.public.encrypt(bytes(long_raw))), COORD, PhaseName.SUM2)
        after = _counts("sum2")
        for label in ("open", "verify", "parse", "verify_beside"):
            assert after.get(label, 0) - before.get(label, 0) == 1, label
        spans = {s.name: s for s in tracer.ring_spans() if s.start >= t0}
        beside, parse = spans["pipeline.verify_beside"], spans["pipeline.parse"]
        assert beside.attrs["bytes"] == len(long_raw) and beside.attrs["phase"] == "sum2"
        assert beside.parent_id == parse.parent_id  # beside the parse, not under it
        # the chain's `verify` begins where the parse ends: it is the wait
        wait = spans["pipeline.verify"]
        assert wait.start >= parse.start + parse.duration - 1e-4

        short = Message(
            participant_pk=SIGNER.public, coordinator_pk=COORD.public.as_bytes(),
            payload=Sum(sum_signature=b"\x01" * 64, ephm_pk=b"\x02" * 32), tag=Tag.SUM,
        ).to_bytes(SIGNER.secret)
        before, moved = _counts("sum"), _verify_bytes_moved()
        handler._decrypt_parse_one(COORD.public.encrypt(short), COORD, PhaseName.SUM)
        after = _counts("sum")
        for label in ("open", "verify", "parse"):
            assert after.get(label, 0) - before.get(label, 0) == 1, label
        assert after.get("verify_beside", 0) == before.get("verify_beside", 0)
        assert _verify_bytes_moved().get("inline", 0) - moved.get("inline", 0) == len(short)
    finally:
        tracer.configure(mode=mode)
        workers.close()


# the chain and the pass beside it, for messages over UNLOCKED_MIN
@pytest.mark.parametrize("label", CHAIN + ("verify_beside",))
def test_each_stage_observed_once_per_accepted_long_update(served_long_round, label):
    before, after = served_long_round["before"], served_long_round["after"]
    assert after.get(label, 0) - before.get(label, 0) == N_UPDATE


def test_the_long_sum2_message_has_its_pass_beside_too(served_long_round):
    before, after = served_long_round["sum2_before"], served_long_round["sum2_after"]
    for label in SUM2_CHAIN + ("verify_beside",):
        assert after.get(label, 0) - before.get(label, 0) == N_SUM, label


def _read_metric(name: str, served: dict):
    """A per-layer metric as the benchmark computes it: its own spec, run by
    the shipped reader over the round's /metrics reads."""
    import importlib

    from benchmark.harness.coordinator import parse_metrics

    spec = json.loads((REPO / f"benchmark/layer_metrics/{name}.json").read_text())
    ctx = {"metrics": {at: parse_metrics(served[f"metrics_{at}"])
                       for at in ("open", "close", "end")}}
    return importlib.import_module(f"benchmark.readers.{spec['reader']}").read(ctx, **spec["args"])


def test_the_chain_still_closes_with_the_pass_beside_it(served_long_round):
    """``verify`` is the wait for the verdict, ``verify_beside`` no part of
    the chain: the twelve stages still sum to the residence."""
    closure = _read_metric("pipeline.stage_closure", served_long_round)
    assert closure is not None and 95.0 <= closure <= 100.5, closure
    # the pass is longer than what the chain waited for it
    beside = _read_metric("pipeline.verify_beside_ms", served_long_round)
    waited = _read_metric("pipeline.verify_ms", served_long_round)
    assert beside is not None and waited is not None and beside > 0


def test_the_new_metrics_read_the_workers_and_the_route(served_long_round, served_round):
    from xaynet_tpu.server import services

    assert _read_metric("pipeline.workers", served_long_round) == services.shared_workers().size
    # by bytes: the Sum message and nothing else of the long round is inline
    share = _read_metric("pipeline.verify_beside_share", served_long_round)
    assert share is not None and 99.0 <= share <= 100.0, share
    # the short round's messages are all under UNLOCKED_MIN
    assert _read_metric("pipeline.verify_beside_share", served_round) == 0.0
    assert _read_metric("pipeline.verify_beside_ms", served_round) is None


_SERVE_ONCE = """
import asyncio, json, socket, sys
from xaynet_tpu.resilience.checkpoint import RECOVERY_SECONDS
from xaynet_tpu.core.crypto.encrypt import EncryptKeyPair
from xaynet_tpu.core.crypto.sign import SigningKeyPair
from xaynet_tpu.sdk.client import HttpClient
from xaynet_tpu.server import runner
from xaynet_tpu.telemetry import startup

sys.path.insert(0, "tests")
from test_message_stages import _settings

tenants, scratch = json.loads(sys.argv[1]), sys.argv[2]
with socket.socket() as sock:
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
settings = _settings(11)
settings.api.bind_address = f"127.0.0.1:{port}"
settings.metrics.flight_dir = scratch + "/flight"
settings.storage.model_dir = scratch + "/models"
if tenants:
    settings.tenancy.enabled, settings.tenancy.tenants = True, tenants

async def scenario():
    serving = asyncio.create_task(runner.serve(settings))
    probe = HttpClient(f"http://127.0.0.1:{port}")
    try:
        for _ in range(500):
            try:
                return json.loads((await probe._request("GET", "/healthz"))[2])
            except Exception:
                assert not serving.done(), serving.exception()
                await asyncio.sleep(0.02)
        raise AssertionError("the coordinator did not start serving")
    finally:
        serving.cancel()
        await asyncio.gather(serving, return_exceptions=True)

health = asyncio.run(asyncio.wait_for(scenario(), timeout=60))
section = health["startup"]
print(json.dumps({"section": section, "recovery": RECOVERY_SECONDS.value,
                  "workers": health["message_workers"],
                  "gauge": {key[0]: child.value for key, child in startup.SECONDS.children()}}))
"""


@pytest.mark.parametrize("tenants", [[], ["t0", "t1"]], ids=["one", "tenants"])
def test_serve_marks_its_start_up_and_healthz_reports_it(tmp_path, tenants):
    """The runner's ``serve()`` (and ``serve_tenants()``, by the same helper)
    as a process of its own, since it configures the process: the five marks
    in order, ``serving`` the last, the gauge and the recovery wall from the
    same marks."""
    import subprocess
    import sys

    done = subprocess.run(
        [sys.executable, "-c", _SERVE_ONCE, json.dumps(tenants), str(tmp_path)], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    section, steps = out["section"], ("imports", "backend", "store", "machine", "serving")
    assert section["origin"] in ("proc", "import")
    marks = [section[step] for step in steps]
    assert [m["at"] for m in marks] == sorted(m["at"] for m in marks)
    assert section["serving"]["at"] == max(m["at"] for m in marks)
    assert marks[0]["took"] == marks[0]["at"] > 0
    for before, mark in zip(marks, marks[1:]):
        assert mark["took"] == pytest.approx(mark["at"] - before["at"], abs=2e-6)
    assert section["backend"]["took"] + section["machine"]["took"] <= section["serving"]["at"]
    for step in steps:
        assert out["gauge"][step] == pytest.approx(section[step]["took"], abs=1e-6)
    assert out["recovery"] == pytest.approx(
        section["serving"]["at"] - section["imports"]["at"], abs=2e-6)
    # one pool a process, two tenants or one, at the size the rule gives this host
    from xaynet_tpu.server.services import available_cores, worker_count

    assert out["workers"] == worker_count(available_cores())
