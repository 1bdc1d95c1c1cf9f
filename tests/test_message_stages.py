"""The stages of one Update message (server/stages.py, docs/DESIGN.md §16):
one span and one histogram observation per stage per accepted update, all
under the message's own request span and request id, closing on the
message's residence; the host-to-device copy counted in bytes."""

import asyncio
import json
import time
from fractions import Fraction
from pathlib import Path

import numpy as np
import pytest

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
from xaynet_tpu.telemetry import tracing

REPO = Path(__file__).resolve().parent.parent
N_SUM, N_UPDATE, MODEL_LEN = 1, 4, 20_011
SUM_PROB, UPDATE_PROB = 0.4, 0.5
# the chain of a message's residence, in order (to_planar runs beside it)
CHAIN = ("read_body", "pool_wait", "open", "verify", "parse", "resume_wait",
         "request_wait", "validate", "seed_dict", "stage", "flush", "verdict_wait")


class ArrayModelStore(ModelStore):
    def __init__(self, model):
        self.model = model

    async def load_model(self):
        return self.model


def _counts() -> dict:
    return {key[0]: child.count for key, child in stages.SECONDS.children()}


async def _round() -> dict:
    """One PET round over the REST API on localhost, host aggregation, a
    fold batch of one (so every accepted update fills its batch and pays a
    flush). Returns what the assertions need."""
    settings = Settings(
        pet=ServerPet(
            sum=PhaseSettings(prob=SUM_PROB, count=CountSettings(N_SUM, N_SUM), time=TimeSettings(0, 30)),
            update=PhaseSettings(prob=UPDATE_PROB, count=CountSettings(N_UPDATE, N_UPDATE), time=TimeSettings(0, 30)),
            sum2=Sum2Settings(count=CountSettings(N_SUM, N_SUM), time=TimeSettings(0, 30)),
        )
    )
    settings.model.length = MODEL_LEN
    settings.aggregation.batch_size = 1
    store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
    machine, request_tx, events = await StateMachineInitializer(settings, store).init()
    fetcher = Fetcher(events)
    rest = RestServer(fetcher, PetMessageHandler(events, request_tx))
    host, port = await rest.start("127.0.0.1", 0)
    machine_task = asyncio.create_task(machine.run())
    url = f"http://{host}:{port}"
    probe = HttpClient(url)
    out = {}
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
                ArrayModelStore(rng.uniform(-1, 1, MODEL_LEN).astype(np.float32)))
            for i in range(N_UPDATE)
        ]

        async def drive(sm):
            for _ in range(1000):
                try:
                    await sm.transition()
                except Exception:
                    pass
                if await probe.get_model() is not None and sm.phase.value == "awaiting":
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
    finally:
        machine_task.cancel()
        await rest.stop()
        await asyncio.gather(machine_task, return_exceptions=True)
    return out


@pytest.fixture(scope="module")
def served_round():
    tracer = tracing.get_tracer()
    mode = tracer.mode
    tracer.configure(mode="on")
    try:
        out = asyncio.run(asyncio.wait_for(_round(), timeout=120))
        out["spans"] = [s for s in tracer.ring_spans() if s.start >= out["t_open"]]
    finally:
        tracer.configure(mode=mode)
    return out


@pytest.mark.parametrize("label", CHAIN)
def test_each_stage_observed_once_per_accepted_update(served_round, label):
    before, after = served_round["before"], served_round["after"]
    assert after.get(label, 0) - before.get(label, 0) == N_UPDATE


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


def test_healthz_lists_the_mirrored_span_names(served_round):
    section = served_round["health"]["trace"]
    assert section["mode"] == "on" and section["mirror"] is False  # no device, no sink
    listed = set(section["mirrored_spans"])
    assert {"rest.read_body", "pipeline.verify", "update.await_request", "update.flush"} <= listed
    assert not listed & {"round", "rest.request", "phase.update",
                         "pipeline.pool_wait", "update.request_wait"}


def test_loop_lag_is_observed_while_the_server_runs(served_round):
    from benchmark.harness.coordinator import parse_metrics, sample_sum

    samples = parse_metrics(served_round["metrics_close"])
    assert sample_sum(samples, "xaynet_event_loop_lag_seconds_count") >= 1


def test_h2d_bytes_equal_the_staged_batch_and_to_planar_is_observed():
    """Device aggregation on the CPU backend: one fold batch staged, copied
    and folded; the copy's counter moves by the staged batch's bytes."""
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
    planar0 = _counts().get("to_planar", 0)
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
    assert streaming.H2D_SECONDS.count - n0 == 1
    assert _counts().get("to_planar", 0) - planar0 == k
    h2d = [s for s in tracing.get_tracer().ring_spans() if s.name == "stream.h2d"][-1]
    assert h2d.attrs["bytes"] == staged
