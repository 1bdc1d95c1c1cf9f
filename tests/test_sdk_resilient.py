"""Resilient SDK client: typed error taxonomy, retry wrapper, participant
state-machine recovery, and the participant-side chaos sites.

Pins the PR-5 SDK contracts:

1. **typed errors** — HTTP statuses map onto the
   ``ClientShedError``/``ClientTransientError``/``ClientPermanentError``
   hierarchy (429 carrying ``Retry-After``), so callers classify without
   string-matching;
2. **retry wrapper** — transient failures retry on the decorrelated-jitter
   schedule with the server's ``Retry-After`` as a floor, permanent ones
   fail on the first attempt;
3. **same-round recovery** — a transient failure inside a phase step keeps
   the participant IN its phase (resumed next tick), while a permanent
   send rejection abandons the upload instead of retrying forever;
4. **chaos sites** — ``sdk.drop`` loses a send on the wire,
   ``sdk.straggle`` delays it, ``sdk.send`` fails attempts (retried), and
   the ``flood`` dropout/straggler knobs are deterministic per seed.
"""

import asyncio
import random
import time
from fractions import Fraction

import numpy as np
import pytest

from xaynet_tpu.core.common import RoundParameters, RoundSeed
from xaynet_tpu.core.mask.config import (
    BoundType,
    DataType,
    GroupType,
    MaskConfig,
    ModelType,
)
from xaynet_tpu.resilience import FaultPlan, RetryPolicy, clear_plan, install_plan
from xaynet_tpu.sdk.client import (
    ClientPermanentError,
    ClientShedError,
    ClientTransientError,
    ResilientClient,
    classify_status,
)
from xaynet_tpu.sdk.simulation import flood, plan_churn
from xaynet_tpu.sdk.state_machine import (
    PetSettings,
    PhaseKind,
    StateMachine,
    TransitionOutcome,
)
from xaynet_tpu.sdk.traits import ModelStore, XaynetClient


@pytest.fixture(autouse=True)
def _no_leftover_fault_plan():
    clear_plan()
    yield
    clear_plan()


def _fast_policy(attempts=4) -> RetryPolicy:
    return RetryPolicy(
        max_attempts=attempts,
        base_delay_s=0.001,
        max_delay_s=0.005,
        deadline_s=5.0,
        rng=random.Random(3),
    )


# --------------------------------------------------------------------------
# Typed status mapping
# --------------------------------------------------------------------------


def test_classify_status_hierarchy():
    shed = classify_status(429, 2.5, "POST /message")
    assert isinstance(shed, ClientShedError) and shed.transient
    assert shed.retry_after == 2.5 and shed.status == 429

    # any 5xx except 501 is transient — proxies in front of a coordinator
    # emit plenty beyond the 502/503/504 gateway family
    for status in (408, 425, 500, 502, 503, 504, 507, 520, 529, 599):
        err = classify_status(status, None, "GET /params")
        assert isinstance(err, ClientTransientError) and err.transient
        assert not isinstance(err, ClientShedError)

    for status in (400, 403, 404, 413, 501):
        err = classify_status(status, None, "GET /params")
        assert isinstance(err, ClientPermanentError) and not err.transient

    # 503 + Retry-After keeps the server's floor
    assert classify_status(503, 1.5, "GET /sums").retry_after == 1.5

    # typed markers drive the shared transient classifier
    from xaynet_tpu.resilience.policy import is_transient

    assert is_transient(ClientTransientError("x"))
    assert not is_transient(ClientPermanentError("x"))


def test_redirects_are_errors_not_success():
    """The client never follows redirects, so a 3xx is a failed call (a
    misconfigured proxy), never a silent success that loses the upload."""
    from xaynet_tpu.sdk.client import HttpClient

    client = HttpClient("http://h")
    for status in (301, 302, 307, 308):
        err = classify_status(status, None, "GET /params")
        assert isinstance(err, ClientPermanentError) and not err.transient
        with pytest.raises(ClientPermanentError):
            client._raise_for_status(status, {}, "GET /params")
    client._raise_for_status(200, {}, "GET /params")  # 2xx passes


def test_http_client_stalled_peer_times_out_transient():
    """A peer that sends the status line then stalls mid-body must surface
    as a fast ClientTransientError (idle read timeout), not hang the
    participant forever."""

    async def run():
        release = asyncio.Event()

        async def handler(reader, writer):
            await reader.readline()
            writer.write(b"HTTP/1.1 200 OK\r\nContent-Length: 10\r\n\r\n")
            await writer.drain()
            await release.wait()  # the body never arrives
            # Python 3.12: wait_closed() below waits for this connection
            writer.close()

        server = await asyncio.start_server(handler, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        from xaynet_tpu.sdk.client import HttpClient

        client = HttpClient(f"http://127.0.0.1:{port}", timeout=0.2)
        t0 = time.monotonic()
        with pytest.raises(ClientTransientError):
            await client.get_model()
        assert time.monotonic() - t0 < 5.0  # idle timeout, not the stall
        release.set()
        server.close()
        await server.wait_closed()

    asyncio.run(asyncio.wait_for(run(), 20))


def test_fsm_transient_classifier_excludes_local_oserrors():
    """The FSM's stay-in-phase retry must not spin forever on a LOCAL
    fault: generic OSErrors (a model store's FileNotFoundError) propagate;
    typed markers and connection/timeout builtins stay transient."""
    from xaynet_tpu.sdk.state_machine import _is_transient_client_error

    assert _is_transient_client_error(ClientTransientError("x"))
    assert _is_transient_client_error(ConnectionResetError())
    assert _is_transient_client_error(asyncio.TimeoutError())
    assert not _is_transient_client_error(ClientPermanentError("x"))
    assert not _is_transient_client_error(FileNotFoundError("model.npz"))
    assert not _is_transient_client_error(PermissionError("denied"))


# --------------------------------------------------------------------------
# ResilientClient
# --------------------------------------------------------------------------


class _FlakyClient(XaynetClient):
    """Scripted inner client: pops one error per call until the script is
    exhausted, then succeeds."""

    def __init__(self, errors=()):
        self.errors = list(errors)
        self.calls = {"params": 0, "sums": 0, "seeds": 0, "model": 0, "send": 0}
        self.sent = []

    def _maybe_fail(self, endpoint):
        self.calls[endpoint] += 1
        if self.errors:
            raise self.errors.pop(0)

    async def get_round_params(self):
        self._maybe_fail("params")
        return "params"

    async def get_sums(self):
        self._maybe_fail("sums")
        return {}

    async def get_seeds(self, pk):
        self._maybe_fail("seeds")
        return {}

    async def get_model(self):
        self._maybe_fail("model")
        return None

    async def send_message(self, encrypted):
        self._maybe_fail("send")
        self.sent.append(encrypted)


def test_resilient_client_retries_transient_then_succeeds():
    inner = _FlakyClient([ClientTransientError("a"), ClientTransientError("b")])
    client = ResilientClient(inner, policy=_fast_policy())
    assert asyncio.run(client.get_round_params()) == "params"
    assert inner.calls["params"] == 3


def test_resilient_client_permanent_fails_on_first_attempt():
    inner = _FlakyClient([ClientPermanentError("no", status=404)])
    client = ResilientClient(inner, policy=_fast_policy())
    with pytest.raises(ClientPermanentError):
        asyncio.run(client.get_model())
    assert inner.calls["model"] == 1


def test_resilient_client_honors_retry_after_floor():
    floor = 0.15
    inner = _FlakyClient([ClientShedError("shed", status=429, retry_after=floor)])
    client = ResilientClient(inner, policy=_fast_policy())
    t0 = time.monotonic()
    asyncio.run(client.send_message(b"x"))
    elapsed = time.monotonic() - t0
    assert elapsed >= floor  # jitter delay (~1ms) was floored by Retry-After
    assert inner.sent == [b"x"]


def test_resilient_client_gives_up_after_policy_and_raises_last():
    inner = _FlakyClient([ClientTransientError(f"t{i}") for i in range(10)])
    client = ResilientClient(inner, policy=_fast_policy(attempts=3))
    with pytest.raises(ClientTransientError) as ei:
        asyncio.run(client.get_sums())
    assert str(ei.value) == "t2"  # the LAST error propagates
    assert inner.calls["sums"] == 3


def test_sdk_fault_sites_drop_straggle_send():
    install_plan(
        FaultPlan.parse(
            "seed=5;sdk.drop:error,nth=1;sdk.straggle:latency,delay=0.1,nth=2;"
            "sdk.send:error,nth=1"
        )
    )
    inner = _FlakyClient()
    client = ResilientClient(inner, policy=_fast_policy())

    # send 1: dropped on the wire — "succeeds" but the inner never sees it
    asyncio.run(client.send_message(b"one"))
    assert inner.sent == []

    # send 2: straggles 0.1s, then the first ATTEMPT hits sdk.send and is
    # retried transparently — the message still lands exactly once
    t0 = time.monotonic()
    asyncio.run(client.send_message(b"two"))
    assert time.monotonic() - t0 >= 0.1
    assert inner.sent == [b"two"]

    # send 3: clean
    asyncio.run(client.send_message(b"three"))
    assert inner.sent == [b"two", b"three"]


# --------------------------------------------------------------------------
# Participant state machine recovery
# --------------------------------------------------------------------------

_CFG = MaskConfig(GroupType.PRIME, DataType.F32, BoundType.B0, ModelType.M3)


def _round_params(seed=b"\x07" * 32, sum_prob=0.0, update_prob=0.999):
    return RoundParameters(
        pk=b"\x01" * 32,
        sum=sum_prob,
        update=update_prob,
        seed=RoundSeed(seed),
        mask_config=_CFG.pair(),
        model_length=4,
    )


class _ScriptedClient(XaynetClient):
    def __init__(self, params, sums_errors=(), send_errors=()):
        self.params = params
        self.sums_errors = list(sums_errors)
        self.send_errors = list(send_errors)
        self.sums_calls = 0
        self.sent = []

    async def get_round_params(self):
        return self.params

    async def get_sums(self):
        self.sums_calls += 1
        if self.sums_errors:
            raise self.sums_errors.pop(0)
        return {b"\x02" * 32: b"\x03" * 32}

    async def get_seeds(self, pk):
        return None

    async def get_model(self):
        return None

    async def send_message(self, encrypted):
        if self.send_errors:
            raise self.send_errors.pop(0)
        self.sent.append(encrypted)


class _ArrayStore(ModelStore):
    def __init__(self, model):
        self.model = model

    async def load_model(self):
        return self.model


def _update_machine(client):
    """A machine whose key takes the UPDATE task for the scripted round."""
    from xaynet_tpu.sdk.simulation import keys_for_task

    params = client.params
    keys = keys_for_task(params.seed.as_bytes(), params.sum, params.update, "update")
    return StateMachine(
        PetSettings(keys=keys, scalar=Fraction(1, 1), max_message_size=None),
        client,
        _ArrayStore(np.zeros(4, dtype=np.float32)),
    )


def test_sm_stays_in_phase_on_transient_failure_and_resumes():
    async def run():
        client = _ScriptedClient(
            _round_params(), sums_errors=[ClientTransientError("conn reset")]
        )
        sm = _update_machine(client)
        # tick 1: fresh params -> NEW_ROUND handler -> UPDATE task
        assert await sm.transition() == TransitionOutcome.COMPLETE
        assert sm.phase == PhaseKind.UPDATE
        # transient get_sums failure: PENDING, SAME phase, signatures kept
        sig_before = sm.update_signature
        assert await sm.transition() == TransitionOutcome.PENDING
        assert sm.phase == PhaseKind.UPDATE
        assert sm.update_signature == sig_before
        # next tick resumes within the round and uploads
        assert await sm.transition() == TransitionOutcome.COMPLETE
        assert client.sent, "update never uploaded after recovery"
        assert sm.phase == PhaseKind.AWAITING

    asyncio.run(asyncio.wait_for(run(), 30))


def test_sm_abandons_send_on_permanent_rejection():
    async def run():
        client = _ScriptedClient(
            _round_params(),
            send_errors=[ClientPermanentError("payload too large", status=413)],
        )
        sm = _update_machine(client)
        await sm.transition()  # fresh params -> NEW_ROUND -> UPDATE task
        assert sm.phase == PhaseKind.UPDATE
        outcome = await sm.transition()  # trains, masks, send -> 413
        assert outcome == TransitionOutcome.COMPLETE
        assert sm.phase == PhaseKind.AWAITING  # upload abandoned, not looped
        assert sm._pending is None
        assert client.sent == []
        # later ticks idle instead of resending the rejected payload
        assert await sm.transition() == TransitionOutcome.PENDING
        assert client.sent == []

    asyncio.run(asyncio.wait_for(run(), 30))


def test_sm_retries_send_on_transient_rejection():
    async def run():
        client = _ScriptedClient(
            _round_params(), send_errors=[ClientTransientError("broken pipe")]
        )
        sm = _update_machine(client)
        await sm.transition()  # fresh params -> NEW_ROUND -> UPDATE task
        assert sm.phase == PhaseKind.UPDATE
        assert await sm.transition() == TransitionOutcome.PENDING  # send failed
        assert sm.phase == PhaseKind.UPDATE and sm._pending is not None
        assert await sm.transition() == TransitionOutcome.COMPLETE  # resent
        assert len(client.sent) == 1
        assert sm.phase == PhaseKind.AWAITING

    asyncio.run(asyncio.wait_for(run(), 30))


# --------------------------------------------------------------------------
# flood churn knobs
# --------------------------------------------------------------------------


def test_plan_churn_deterministic_and_disjoint():
    d1, s1 = plan_churn(10, 0.3, 2, seed=42)
    d2, s2 = plan_churn(10, 0.3, 2, seed=42)
    assert d1 == d2 and s1 == s2
    assert len(d1) == 3 and len(s1) == 2
    assert not (d1 & s1)  # stragglers are drawn from the survivors
    d3, _ = plan_churn(10, 0.3, 2, seed=43)
    assert d3 != d1 or plan_churn(10, 0.3, 2, seed=43)[1] != s1

    with pytest.raises(ValueError):
        plan_churn(10, 1.0, 0, seed=1)


def test_flood_dropout_withholds_and_stragglers_delay():
    received = []

    async def sink(blob: bytes) -> None:
        received.append(blob)

    async def run():
        return await flood(
            sink,
            _round_params(),
            {b"\x02" * 32: b"\x03" * 32},
            8,
            dropout_rate=0.25,
            stragglers=2,
            straggle_delay_s=0.05,
            churn_seed=11,
            build=lambda i: bytes([i]),  # payload = index, no crypto needed
        )

    stats = asyncio.run(asyncio.wait_for(run(), 30))
    assert stats.dropped == 2 and len(stats.dropped_indices) == 2
    assert stats.straggled == 2
    assert stats.sent == 6 and stats.accepted == 6
    # exactly the survivors were delivered
    assert sorted(b[0] for b in received) == [
        i for i in range(8) if i not in stats.dropped_indices
    ]

    asyncio.run(asyncio.sleep(0))
