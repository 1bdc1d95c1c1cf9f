"""The coordinator as shipped under a cross-device fan-in (ISSUE 42): the
default mask Prime/F32/B0/M3 (a 45-bit prime order, 6 wire bytes), the
shipped fold batch of 64, 128 uploads over 64 connections, served over the
socket with ``[aggregation] device = true`` and held to a plain integer
reference bit for bit. It is the benchmark's cell
``femnist-cnn-prime-f32m3.flood`` at a small length and two batches (the
cell runs three: ISSUE 42's steadiness rule).

The reference below is the benchmark's rule (``benchmark/harness/
reference.py``) copied: weights are multiples of 2^-23, the scalar is
dyadic, so every step is exact in int64 and in Python integers; it imports
nothing of the program's encode, decode, limb or fold code.

Beside the round: what the REST intake counts under a fan-in
(``telemetry/intake.py``): which carrier read each large body and why, the
bodies held sealed at once, the loop thread's CPU seconds; and the fold at
K = 64 under the prime order on every route, at the elements where a
conditional subtract can go wrong.
"""

import asyncio
import logging
import sys
import threading
from fractions import Fraction

import jax
import numpy as np
import pytest

from xaynet_tpu.core.mask import BoundType, DataType, GroupType, MaskConfig, ModelType
from xaynet_tpu.ops import fold_pallas
from xaynet_tpu.ops import limbs as host_limbs
from xaynet_tpu.parallel import aggregator as aggregator_mod
from xaynet_tpu.parallel.aggregator import ShardedAggregator
from xaynet_tpu.parallel.mesh import make_mesh
from xaynet_tpu.sdk.client import HttpClient
from xaynet_tpu.sdk.simulation import keys_for_task
from xaynet_tpu.sdk.state_machine import PetSettings, PhaseKind, StateMachine as ParticipantSM
from xaynet_tpu.sdk.traits import ModelStore
from xaynet_tpu.server import rest as rest_mod
from xaynet_tpu.server.rest import BODY_READERS, DIRECT_BODY_MIN, RestServer
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
from xaynet_tpu.telemetry.intake import BodyIntake
from xaynet_tpu.telemetry.registry import MetricsRegistry, get_registry

CONFIG = MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3)  # as shipped
ORDER = 20_000_000_000_021
K = 64  # [aggregation] batch_size as shipped
SUM_PROB, UPDATE_PROB = 0.4, 0.5
FIXED_BITS = 23


# --- the plain reference: integers only --------------------------------------


def weights_fixed(index: int, n: int) -> np.ndarray:
    """Participant ``index``'s weights as numerators over 2^23, in [-1, 1)."""
    rng = np.random.default_rng([42, index])
    return rng.integers(-(1 << FIXED_BITS), 1 << FIXED_BITS, n, dtype=np.int32)


def to_f32(fixed: np.ndarray) -> np.ndarray:
    return (fixed.astype(np.float32) / np.float32(1 << FIXED_BITS)).astype(np.float32)


def reference_model(fixed: list[np.ndarray], den: int, add_shift: int, exp_shift: int) -> np.ndarray:
    """Each participant's ``floor((w / den + A) * E)`` summed in int64 and
    decoded by the published rule ``((S / E) - nb * A) / scalar_sum`` to the
    nearest float64, each element the quotient of two Python integers."""
    nb = len(fixed)
    sums = np.zeros(len(fixed[0]), dtype=np.int64)
    for f in fixed:
        sums += np.int64(add_shift * exp_shift) + np.floor_divide(
            f.astype(np.int64) * np.int64(exp_shift), np.int64(den << FIXED_BITS))
    unit = (Fraction(1, den) + add_shift) * exp_shift
    scalar_sum = Fraction(nb * (unit.numerator // unit.denominator), exp_shift) - nb * add_shift
    c, mul, div = nb * add_shift * exp_shift, scalar_sum.denominator, exp_shift * scalar_sum.numerator
    return np.array([((int(s) - c) * mul) / div for s in sums.tolist()], dtype=np.float64)


def test_the_reference_is_the_published_rule_in_rationals():
    fixed = [weights_fixed(i, 5) for i in range(3)]
    den, a, e = 4, 1, 10**10
    total = [0] * 5
    for f in fixed:
        for j, w in enumerate(to_f32(f).tolist()):
            t = (Fraction(w) / den + a) * e
            total[j] += t.numerator // t.denominator
    unit = ((Fraction(1, den) + a) * e)
    ssum = Fraction(3 * (unit.numerator // unit.denominator), e) - 3 * a
    want = [float((Fraction(s, e) - 3 * a) / ssum) for s in total]
    assert reference_model(fixed, den, a, e).tolist() == want
    assert CONFIG.order == ORDER and CONFIG.bytes_per_number == 6
    assert (int(CONFIG.add_shift), CONFIG.exp_shift) == (1, 10**10)


# --- a served round ------------------------------------------------------------


class _Store(ModelStore):
    def __init__(self, model):
        self.model = model

    async def load_model(self):
        return self.model


@pytest.fixture
def one_device(monkeypatch, tmp_path):
    """The chip has one device; the tests' CPU backend has eight."""
    monkeypatch.setenv("XAYNET_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.setattr(aggregator_mod, "make_mesh", lambda: make_mesh(jax.devices()[:1]))


def _settings(length: int, n_update: int, batch: int, kernel: str) -> Settings:
    window = TimeSettings(min=0.0, max=120.0)
    s = Settings(pet=ServerPet(
        sum=PhaseSettings(prob=SUM_PROB, count=CountSettings(1, 1), time=window),
        update=PhaseSettings(prob=UPDATE_PROB, time=window,
                             count=CountSettings(n_update, n_update)),
        sum2=Sum2Settings(count=CountSettings(1, 1), time=window),
    ))
    s.model.length = length
    s.mask.group_type, s.mask.data_type = GroupType.PRIME, DataType.F32
    s.mask.bound_type, s.mask.model_type = BoundType.B0, ModelType.M3
    s.aggregation.device = True
    s.aggregation.batch_size = batch
    s.aggregation.kernel = kernel
    return s


def _sample(name: str, labels: dict | None = None) -> float:
    return get_registry().sample_value(name, labels) or 0.0


def _intake_counters() -> dict:
    out = {reason: _sample("xaynet_rest_body_reads_total", {"route": "stream", "reason": reason})
           for reason in ("small", "tls", "no_reader", "no_socket")}
    out["large"] = _sample("xaynet_rest_body_reads_total", {"route": "direct", "reason": "large"})
    out["overflow"] = _sample("xaynet_rest_body_reads_total",
                              {"route": "overflow", "reason": "no_reader"})
    out["accepted"] = _sample("xaynet_messages_total", {"phase": "update", "outcome": "accepted"})
    return out


def _loop_clock() -> tuple[float, float]:
    return (_sample("xaynet_event_loop_cpu_seconds_total"),
            _sample("xaynet_event_loop_wall_seconds_total"))


async def _served_round(settings: Settings, fixed: list[np.ndarray], den: int,
                        connections: int, together: bool = False) -> dict:
    """One PET round over the REST API on localhost: ``connections`` workers,
    each with a connection of its own, take the updaters off one queue, so
    at most that many uploads are in flight. With ``together`` every
    worker's first upload waits until all have theirs composed and they are
    sent at one instant."""
    store = Store(InMemoryCoordinatorStorage(), InMemoryModelStorage(), NoOpTrustAnchor())
    machine, request_tx, events = await StateMachineInitializer(settings, store).init()
    fetcher = Fetcher(events)
    rest = RestServer(fetcher, PetMessageHandler(events, request_tx))
    host, port = await rest.start("127.0.0.1", 0)
    url = f"http://{host}:{port}"
    machine_task = asyncio.create_task(machine.run())
    clients = [HttpClient(url) for _ in range(connections + 1)]
    clock = [_loop_clock()]
    try:
        while fetcher.phase().value != "sum":
            await asyncio.sleep(0.005)
        seed = fetcher.round_params().seed.as_bytes()
        summer = ParticipantSM(
            PetSettings(keys=keys_for_task(seed, SUM_PROB, UPDATE_PROB, "sum"),
                        device_sum2=False, max_message_size=None),
            clients[-1], _Store(None))

        async def drive_summer():
            while fetcher.model() is None:
                await summer.transition()
                await asyncio.sleep(0.005)

        async def watch_clock():
            while True:
                await asyncio.sleep(0.05)
                clock.append(_loop_clock())

        sum_task = asyncio.create_task(drive_summer())
        clock_task = asyncio.create_task(watch_clock())
        while fetcher.phase().value != "update":
            await asyncio.sleep(0.005)
        before = _intake_counters()
        queue = list(range(len(fixed)))
        ready, go = [], asyncio.Event()

        async def worker(client: HttpClient):
            if together:
                send = client.send_message

                async def send_with_the_others(encrypted):
                    ready.append(client)
                    if len(ready) == connections:
                        go.set()
                    await go.wait()
                    await send(encrypted)

                client.send_message = send_with_the_others
            while queue:
                i = queue.pop(0)
                sm = ParticipantSM(
                    PetSettings(keys=keys_for_task(seed, SUM_PROB, UPDATE_PROB, "update",
                                                   start=(10 + i) * 1000),
                                scalar=Fraction(1, den), max_message_size=None),
                    client, _Store(to_f32(fixed[i])))
                sent = False
                while not (sent and sm.phase is PhaseKind.AWAITING):
                    await sm.transition()
                    sent = sent or sm.phase is PhaseKind.UPDATE

        await asyncio.gather(*(worker(c) for c in clients[:connections]))
        while fetcher.phase().value == "update":
            await asyncio.sleep(0.005)
        update = {k: v - before[k] for k, v in _intake_counters().items()}
        resident_max = _sample("xaynet_rest_bodies_resident_max")
        # served while the round is in Sum2: the one sum participant's seeds
        seed_dict = [len(seeds) for seeds in (fetcher.seed_dict() or {}).values()]
        await sum_task
        clock_task.cancel()
        clock.append(_loop_clock())
        return {"model": np.asarray(fetcher.model(), dtype=np.float64), "update": update,
                "resident_max": resident_max, "resident": _sample("xaynet_rest_bodies_resident"),
                "clock": clock, "seed_dict": seed_dict}
    finally:
        machine_task.cancel()
        for c in clients:
            c.close()
        await rest.stop()
        await asyncio.gather(machine_task, return_exceptions=True)


# lengths that do and do not divide the Pallas fold's tile, and the route the
# chip's race may choose beside the one the CPU backend takes
@pytest.mark.parametrize("length,kernel", [
    (2 * fold_pallas.TILE, "pallas-interpret"),
    (2 * fold_pallas.TILE + 3, "pallas-interpret"),
    (2 * fold_pallas.TILE + 3, "auto"),
])
def test_served_round_of_two_shipped_batches_equals_the_plain_reference(length, kernel, one_device):
    """(a) and (d): 128 uploads over 64 connections, two fold batches of 64
    under the shipped mask; the loop's CPU clock beside its wall clock."""
    n_update, den = 2 * K, 128
    fixed = [weights_fixed(i, length) for i in range(n_update)]
    out = asyncio.run(asyncio.wait_for(
        _served_round(_settings(length, n_update, K, kernel), fixed, den, connections=64), 150))

    want = reference_model(fixed, den, int(CONFIG.add_shift), CONFIG.exp_shift)
    assert out["model"].shape == want.shape
    assert np.array_equal(out["model"].view(np.uint64), want.view(np.uint64))
    assert out["update"]["accepted"] == n_update
    # bodies of 6 x length bytes are small: all through the StreamReader, by name
    assert 6 * length < DIRECT_BODY_MIN
    assert out["update"]["small"] >= n_update
    assert out["update"]["large"] == out["update"]["overflow"] == out["update"]["no_reader"] == 0
    # every connection held a sealed body at some instant, none is held now
    assert 1 <= out["resident_max"] <= 64 and out["resident"] == 0
    # (d) both counters only rise, together, and the share is a share
    cpu, wall = zip(*out["clock"])
    assert all(b >= a for a, b in zip(cpu, cpu[1:])) and all(b >= a for a, b in zip(wall, wall[1:]))
    d_cpu, d_wall = cpu[-1] - cpu[0], wall[-1] - wall[0]
    assert d_wall > 0.0 and 0.0 < 100.0 * d_cpu / d_wall <= 100.0


def test_a_round_of_large_bodies_is_read_by_both_carriers_and_each_update_counts_once(
        one_device, monkeypatch, caplog):
    """(b): more connections than ``BODY_READERS``, every body over
    ``DIRECT_BODY_MIN``, all sent at one instant: sixteen find a ``rest-body``
    reader, the rest are received by the ``rest-overflow`` thread as
    ``no_reader``, none through the StreamReader, and every one of them is in
    the aggregate once."""
    length, batch, connections = 180_001, 8, BODY_READERS + 8
    assert 6 * length > DIRECT_BODY_MIN
    # a reader keeps its body, as a peer on a real link would, until the other
    # eight have asked for one: no clock decides who finds the readers busy
    recv, receive = rest_mod._recv_exactly, rest_mod._OverflowReader.receive
    asked, all_asked = [], threading.Event()

    def held_reader(*args):
        all_asked.wait(120)
        return recv(*args)

    def counted(self, *args):
        asked.append(args)
        if len(asked) == connections - BODY_READERS:
            all_asked.set()
        return receive(self, *args)

    monkeypatch.setattr(rest_mod, "_recv_exactly", held_reader)
    monkeypatch.setattr(rest_mod._OverflowReader, "receive", counted)
    caplog.set_level(logging.INFO, logger="xaynet.rest")
    fixed = [weights_fixed(100 + i, length) for i in range(connections)]
    out = asyncio.run(asyncio.wait_for(
        _served_round(_settings(length, connections, batch, "auto"), fixed, 32,
                      connections=connections, together=True), 150))

    want = reference_model(fixed, 32, int(CONFIG.add_shift), CONFIG.exp_shift)
    assert np.array_equal(out["model"].view(np.uint64), want.view(np.uint64))
    update = out["update"]
    assert update["accepted"] == connections and out["seed_dict"] == [connections]
    assert (update["large"], update["overflow"]) == (BODY_READERS, connections - BODY_READERS)
    assert update["no_reader"] == update["tls"] == update["no_socket"] == 0
    # what rest.reader_full_share reads: no_reader over large + no_reader, on any
    # route; what rest.large_stream_share reads: of those, the StreamReader's
    assert 100.0 * update["overflow"] / (update["large"] + update["overflow"]) \
        == pytest.approx(100.0 * 8 / 24)
    assert out["resident_max"] == connections and out["resident"] == 0
    assert _sample("xaynet_rest_overflow_bodies") == 0
    # and the round's log line tells the operator, once
    told = [r.getMessage() for r in caplog.records if "large bodies" in r.getMessage()]
    assert len(told) == 1 and "16 read by rest-body threads, 8 by the rest-overflow thread, " \
        "0 through the StreamReader (none); at most 24 message bodies held sealed at once" in told[0]


# --- the fold at K = 64 under the prime order -----------------------------------


def _edge_batch(n: int) -> tuple[np.ndarray, np.ndarray, list[int]]:
    """(accumulator, batch, the exact sums mod ORDER) as Python integers per
    column: zeros, all ``ORDER - 1`` (the sum passes the order K - 1 times,
    K times with the accumulator), sums that reach the order exactly and
    pass it once, the rest uniform."""
    rng = np.random.default_rng(64)
    batch = rng.integers(0, ORDER, size=(K, n), dtype=np.int64)
    acc = rng.integers(0, ORDER, size=n, dtype=np.int64)
    batch[:, 0], acc[0] = 0, 0                      # nothing to add
    batch[:, 1], acc[1] = ORDER - 1, 0              # K - 1 times over the order
    batch[:, 2], acc[2] = ORDER - 1, ORDER - 1      # K times, accumulator included
    batch[:, 3], acc[3] = 0, 0
    batch[0, 3], batch[1, 3] = ORDER - 1, 1         # the order itself: 0
    batch[:, 4], acc[4] = 0, ORDER - 1
    batch[K - 1, 4] = ORDER - 1                     # once over: ORDER - 2
    batch[:, n - 1], acc[n - 1] = ORDER - 1, 1      # the last column, past a tile's end
    want = [(int(acc[j]) + sum(int(v) for v in batch[:, j])) % ORDER for j in range(n)]
    return acc, batch, want


def _limbs_of(values: np.ndarray) -> np.ndarray:
    """int64[..., n] -> uint32[..., 2, n] planar limbs."""
    v = values.astype(np.uint64)
    return np.stack([(v & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                     (v >> np.uint64(32)).astype(np.uint32)], axis=-2)


@pytest.mark.parametrize("n", [fold_pallas.TILE, fold_pallas.TILE + 5], ids=["tile", "tile+5"])
@pytest.mark.parametrize("staging", ["planar", "packed"])
@pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
def test_fold_of_64_at_the_prime_orders_edges_is_exact(kernel, staging, n):
    """(c): 0, ``ORDER - 1`` and sums that pass the 45-bit prime order once,
    K - 1 and K times, on every route a coordinator can take."""
    acc, batch, want = _edge_batch(n)
    agg = ShardedAggregator(CONFIG, n, mesh=make_mesh(jax.devices()[:1]), kernel=kernel)
    assert (agg.n_limbs, agg.packed_width, agg.order) == (2, 6, ORDER)
    planar = _limbs_of(batch)
    if staging == "packed":
        fold, staged = agg._make_packed_fold_fn(kernel), host_limbs.pack_planar(planar, 6)
        assert staged.shape == (K, 6, n)
    else:
        fold, staged = agg._make_fold_fn(kernel), planar
    out = np.asarray(fold(jax.numpy.asarray(_limbs_of(acc)), jax.numpy.asarray(staged)))
    got = (out[1].astype(np.uint64) << np.uint64(32)) | out[0].astype(np.uint64)
    assert got.tolist() == want


def test_resident_count_loses_no_update_under_contending_threads():
    """Bodies are counted in on the loop and out on ``pet-msg`` workers: more
    threads than cores take and release holds, one of them twice, while the
    interpreter switches every few instructions; the count comes back to
    zero and the high-water mark never passes the threads."""
    registry, threads, rounds = MetricsRegistry(), 32, 300
    intake = BodyIntake(registry)

    def churn():
        for _ in range(rounds):
            held = intake.hold()
            held.release()
            held.release()  # a second release counts nothing

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=churn) for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert registry.sample_value("xaynet_rest_bodies_resident") == 0
    assert 1 <= registry.sample_value("xaynet_rest_bodies_resident_max") <= threads
    intake.new_window()
    assert registry.sample_value("xaynet_rest_bodies_resident_max") == 0
