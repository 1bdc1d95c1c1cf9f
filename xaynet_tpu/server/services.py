"""Message-processing pipeline and data fetchers.

Functional port of the reference's tower service stack (reference:
rust/xaynet-server/src/services/messages/mod.rs:30-118):

    Decryptor -> MessageParser (phase filter + signature verification)
    -> MultipartHandler (chunk reassembly) -> TaskValidator -> StateMachine

CPU-heavy stages (sealed-box open, Ed25519 verify, parse) run on a thread
pool so the asyncio loop stays responsive — the analogue of the reference's
rayon offload with a concurrency limit. The pool is the process's, sized
from the cores the process may run on (:class:`MessageWorkers`), and a large
message's signature is checked beside its parse (docs/DESIGN.md §16 "The
message workers").

``Fetcher`` exposes the latest event-bus values to the API layer
(reference: rust/xaynet-server/src/services/fetchers/mod.rs:27-42).
"""

from __future__ import annotations

import asyncio
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

from ..core.common import RoundParameters
from ..core.crypto import unlocked
from ..core.crypto.encrypt import DecryptError, EncryptKeyPair
from ..core.crypto.sign import is_eligible, verify_detached
from ..core.mask.object import wire_route
from ..core.mask.serialization import DecodeError
from ..core.message import Chunk, Message, Sum, Sum2, Tag, Update, peek_header
from ..core.message.encoder import MessageBuilder
from ..ops.limbs import PlaneBuffers
from ..telemetry import tracing as trace
from ..telemetry.registry import get_registry
from ..utils import tracing
from . import stages
from .events import EventSubscriber, PhaseName
from .requests import RequestSender, request_from_message

_PHASE_TAGS = {
    PhaseName.SUM: Tag.SUM,
    PhaseName.UPDATE: Tag.UPDATE,
    PhaseName.SUM2: Tag.SUM2,
}

_MULTIPART_BUFFERS = get_registry().gauge(
    "xaynet_multipart_buffers",
    "Multipart reassembly buffers currently held (bounded, oldest-evicted).",
)


_MESSAGE_WORKERS = get_registry().gauge(
    "xaynet_message_workers",
    "Threads of the process's pet-msg pool: the size the rule chose from the "
    "cores the process may run on (server/services.py::worker_count).",
)
_VERIFY_BYTES = get_registry().counter(
    "xaynet_verify_bytes_total",
    "Signed bytes of messages by where their signature was checked: beside = "
    "on a pet-verify thread while the pet-msg worker parsed (a plaintext of "
    "unlocked.UNLOCKED_MIN bytes or more), inline = on the worker, before the "
    "parse.",
    ("route",),
)


def worker_count(cores: int) -> int:
    """Threads of the ``pet-msg`` pool on a host of ``cores`` cores: half of
    them rounded up, and one more, never under four. A worker that checks a
    large message's signature beside its parse keeps a second core busy for
    the length of the signature pass, so half the cores in workers is the
    host in use; the other threads that touch a body (``rest-body`` readers,
    the ``xn-ingest`` pool, the loop) wait on sockets or on these workers
    most of their time. 13 cores: 8, 30 cores: 16 (docs/DESIGN.md §16)."""
    return max(4, (cores + 1) // 2 + 1)


def available_cores() -> int:
    """The cores this process may run on (its affinity mask where the
    platform has one: a container's share, not the machine's count)."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # no affinity on this platform
        return os.cpu_count() or 1


class MessageWorkers:
    """The threads that open, verify and parse messages: the ``pet-msg`` pool
    and as many ``pet-verify`` threads, which make a large message's
    signature pass beside its parse. A ``pet-verify`` thread takes nothing
    from the ``pet-msg`` pool and waits for nothing, and a worker has at most
    one pass outstanding, so a pass starts at once and a pool in which every
    worker waits for its verdict still drains. Threads start when first
    needed. One a process (:func:`shared_workers`) unless a caller brings its
    own."""

    def __init__(self, size: Optional[int] = None):
        self.size = size if size is not None else worker_count(available_cores())
        self.pool = ThreadPoolExecutor(self.size, thread_name_prefix="pet-msg")
        self.verdicts = ThreadPoolExecutor(self.size, thread_name_prefix="pet-verify")

    def close(self) -> None:
        self.pool.shutdown(wait=False)
        self.verdicts.shutdown(wait=False)


_shared: Optional[MessageWorkers] = None
_shared_lock = threading.Lock()


def shared_workers() -> MessageWorkers:
    """The process's message workers, made on first use: every handler of
    the process (one a tenant) runs on them, so two tenants are not two pools
    of the host's size."""
    global _shared
    with _shared_lock:
        if _shared is None:
            _shared = MessageWorkers()
            _MESSAGE_WORKERS.set(_shared.size)
        return _shared


class ServiceError(Exception):
    """A message was dropped by the pipeline (with the stage as context)."""

    def __init__(self, stage: str, detail: str):
        super().__init__(f"{stage}: {detail}")
        self.stage = stage


class PetMessageHandler:
    """End-to-end handling of one encrypted PET message."""

    def __init__(
        self,
        events: EventSubscriber,
        request_tx: RequestSender,
        wire_ingest: bool = False,
        workers: Optional[MessageWorkers] = None,
        update_planes: bool = False,
    ):
        self.events = events
        self.request_tx = request_tx
        # device-ingest coordinators parse Update masked models LAZILY (raw
        # element block kept; unpack + validity run on the accelerator in
        # validate_aggregation, before the seed-dict insert)
        self.wire_ingest = wire_ingest
        self.workers = workers if workers is not None else shared_workers()
        # the staged aggregator's slots are byte planes
        # (aggregation.slots_take_planes): an Update's v1 vector is parsed
        # into checked planes, which its slot takes by copy, and not into
        # limb rows that nothing but the plane pack would read. The planes
        # lie on pages kept from earlier messages: one vector a worker and
        # the few that wait for their slot copy on the xn-ingest pool
        self.update_planes = (
            PlaneBuffers(keep=self.workers.size + 4) if update_planes else None
        )
        # multipart reassembly buffers keyed by (participant_pk, message_id);
        # bounded: abandoned reassemblies are evicted oldest-first so a
        # client cannot grow coordinator memory without completing messages
        self._multipart: dict[tuple[bytes, int], MessageBuilder] = {}
        self.max_multipart_buffers = 4096

    async def handle_message(self, encrypted: "bytes | bytearray") -> None:
        """Decrypt, verify, validate and forward one message. A
        ``bytearray`` is given up by the caller (opened in place).

        Raises ``ServiceError`` (pipeline drop) or ``RequestError`` (state
        machine rejection).
        """
        # the REST layer has named the message and the phase it arrived in;
        # callers that skip the socket (in-process clients, tests) have not
        arrived = stages.current_phase()
        if arrived == "-":
            arrived = self.events.phase.get_latest().event.value
        with tracing.use_request_id(tracing.request_id_or_fresh()), stages.use_phase(arrived):
            with stages.seconds("total").time():
                with stages.seconds("decrypt_parse").time():
                    message = await self._parse_message(encrypted)
                if message is None:
                    return  # multipart message still incomplete
                self._validate_task(message)
                await self.request_tx.request(request_from_message(message))

    # --- pipeline stages --------------------------------------------------

    def _decrypt_parse_one(
        self,
        encrypted: bytes,
        keys: EncryptKeyPair,
        phase: PhaseName,
        ctx: Optional[trace.TraceContext] = None,
        rid: str = "-",
        arrived: Optional[str] = None,
        held=None,
    ) -> Message:
        """Sealed-box open + phase filter + signature verify + parse.

        Synchronous CPU body shared by the per-message path and the batched
        ingest workers; always runs on a worker thread, so the caller hands
        over what does not cross the hop: the parent span's ``ctx``, the
        request id and the phase the message arrived in (a batch, whose
        members' arrivals the intake does not keep, is labelled by the
        phase its filter runs against), and the REST layer's count of the
        body as sealed (``held``), which ends when the box is open.
        """
        arrived = arrived or phase.value
        # sealed-box open (CPU) — reference: decryptor.rs:48-69. Passing our
        # public key skips a per-message X25519 recompute of it (milliseconds
        # per message on the pure-python fallback). A ``bytearray`` is the
        # buffer ``rest.py`` read the body into, which nothing reads again:
        # the pipeline gives it up and a long box is opened over its own
        # ciphertext; ``raw`` is then a view that keeps the buffer alive (and
        # so does a lazily parsed vector that points into it)
        with stages.stage("open", ctx=ctx, rid=rid, phase=arrived, bytes=len(encrypted)):
            try:
                if isinstance(encrypted, bytearray):
                    raw = keys.secret.decrypt_in_place(encrypted, keys.public)
                else:
                    raw = keys.secret.decrypt(encrypted, keys.public)
            except (DecryptError, ValueError) as e:
                raise ServiceError("decrypt", str(e)) from e
        if held is not None:
            held.release()
        # phase filter before the expensive signature check
        # (reference: message_parser.rs:88-141)
        try:
            _, tag, _ = peek_header(raw)
        except DecodeError as e:
            raise ServiceError("parse", str(e)) from e
        expected = _PHASE_TAGS.get(phase)
        if expected is None or tag != expected:
            # the tag rides in the decrypted header, so the taint pass sees
            # plaintext-derived bytes here — but a message-type enum name is
            # a one-byte projection, not key material
            raise ServiceError(  # lint: taint-ok: one-byte message-type tag, not key bytes
                "phase-filter", f"{tag.name} message during {phase.value}"
            )
        # the signature pass and the full parse read the same opened bytes
        # and nothing of each other. A short message (Sum: 280 bytes) has
        # them one after the other, a long one side by side: the route
        # depends on the length alone
        at = dict(ctx=ctx, rid=rid, phase=arrived, bytes=len(raw))
        try:
            if len(raw) < unlocked.UNLOCKED_MIN:
                _VERIFY_BYTES.labels(route="inline").inc(len(raw))
                with stages.stage("verify", **at):
                    Message.verify_bytes(raw)
                return self._parse(raw, at)
            _VERIFY_BYTES.labels(route="beside").inc(len(raw))
            return self._parse(raw, at, beside=True)
        except DecodeError as e:
            raise ServiceError("parse", str(e)) from e

    def _parse(self, raw, at: dict, beside: bool = False) -> Message:
        """The full parse; with ``beside`` the signature pass is handed to a
        ``pet-verify`` thread first (inside the ``parse`` bracket: a queue
        put, and a thread's start at its first use) and awaited after."""
        verdict = None
        try:
            with stages.stage("parse", **at) as span:
                if beside:
                    verdict = self.workers.verdicts.submit(self._verify_beside, raw, at)
                message = Message.from_bytes(
                    raw,
                    verify=False,
                    lazy_update_vect=self.wire_ingest,
                    planes_update_vect=self.update_planes,
                )
                if isinstance(message.payload, Update):
                    # which wire the vector came on and what the parse made
                    # of it: limb rows, checked planes, or a view of the
                    # body's bytes
                    wire, route = wire_route(message.payload.masked_model.vect)
                    span.set(wire=wire, route=route)
                return message
        finally:
            # nothing of the parse, an error included, leaves before the
            # verdict is in: a bad signature raises here, over whatever the
            # parse returned or raised. `verify` is what the chain waits for
            # the verdict once the parse has returned
            if verdict is not None:
                with stages.stage("verify", **at):
                    verdict.result()

    @staticmethod
    def _verify_beside(raw, at: dict) -> None:
        """The whole signature pass of a long message, on a ``pet-verify``
        thread, beside the chain as ``to_planar`` is."""
        with stages.stage("verify_beside", **at):
            Message.verify_bytes(raw)

    async def _parse_message(self, encrypted: bytes) -> Optional[Message]:
        loop = asyncio.get_running_loop()
        keys: EncryptKeyPair = self.events.keys.get_latest().event
        phase: PhaseName = self.events.phase.get_latest().event
        ctx, rid, submitted = trace.current_ctx(), tracing.current_request_id(), time.monotonic()
        arrived, held = stages.current_phase(), stages.current_held()

        def on_worker() -> tuple[Message, float]:
            stages.waited("pool_wait", submitted, ctx=ctx, rid=rid, phase=arrived)
            message = self._decrypt_parse_one(encrypted, keys, phase, ctx, rid, arrived, held)
            return message, time.monotonic()

        message, returned = await loop.run_in_executor(self.workers.pool, on_worker)
        # the worker is done; this coroutine waited for the loop since then
        stages.waited("resume_wait", returned)
        if message.is_multipart:
            return self._handle_chunk(message)
        return message

    async def process_batch(self, batch: list[bytes]) -> list:
        """Decrypt + verify + task-validate a whole batch in ONE thread-pool
        hop (the ingest workers' entry point).

        Returns one slot per input, aligned: a verified ``Message``, a
        ``ServiceError`` (the drop, with its stage), or ``None`` (multipart
        chunk absorbed, message still incomplete). Unlike
        ``handle_message`` nothing is forwarded to the state machine — the
        caller owns request submission and batching policy.
        """
        loop = asyncio.get_running_loop()
        keys: EncryptKeyPair = self.events.keys.get_latest().event
        phase: PhaseName = self.events.phase.get_latest().event
        params: RoundParameters = self.events.params.get_latest().event
        ctx = trace.current_ctx()  # the ingest.decrypt_batch span

        def run() -> list:
            out = []
            for encrypted in batch:
                try:
                    message = self._decrypt_parse_one(encrypted, keys, phase, ctx)
                    if not message.is_multipart:
                        self._validate_task_with(message, params)
                    out.append(message)
                except ServiceError as e:
                    out.append(e)
            return out

        with stages.seconds("decrypt_parse_batch", phase.value).time():
            results = await loop.run_in_executor(self.workers.pool, run)
        final = []
        for res in results:
            if isinstance(res, ServiceError) or res is None or not res.is_multipart:
                final.append(res)
                continue
            # multipart reassembly state is loop-owned — finish on the loop
            try:
                message = self._handle_chunk(res)
                if message is not None:
                    self._validate_task_with(message, params)
                final.append(message)
            except ServiceError as e:
                final.append(e)
        return final

    def _handle_chunk(self, message: Message) -> Optional[Message]:
        """Reassembly per (participant, message_id)
        (reference: multipart/service.rs:26-117)."""
        chunk = message.payload
        assert isinstance(chunk, Chunk)
        key = (message.participant_pk, chunk.message_id)
        if key not in self._multipart and len(self._multipart) >= self.max_multipart_buffers:
            evicted = next(iter(self._multipart))
            del self._multipart[evicted]
        builder = self._multipart.setdefault(key, MessageBuilder())
        _MULTIPART_BUFFERS.set(len(self._multipart))
        if not builder.add(chunk):
            return None
        del self._multipart[key]
        _MULTIPART_BUFFERS.set(len(self._multipart))
        # streaming parse: chunk buffers are consumed as the parser reads,
        # never concatenated (reference: multipart/service.rs streaming
        # FromBytes re-parse; chunkable_iterator.rs:17-60)
        from ..core.message.payloads import parse_payload_stream

        try:
            payload = parse_payload_stream(
                message.tag,
                builder.take_reader(),
                lazy_update_vect=self.wire_ingest,
                planes_update_vect=self.update_planes,
            )
        except DecodeError as e:
            raise ServiceError("multipart", str(e)) from e
        return Message(
            participant_pk=message.participant_pk,
            coordinator_pk=message.coordinator_pk,
            payload=payload,
            tag=message.tag,
            is_multipart=False,
            signature=message.signature,
        )

    def _validate_task(self, message: Message) -> None:
        """Sum/update task eligibility (reference: task_validator.rs:40-88)."""
        self._validate_task_with(message, self.events.params.get_latest().event)

    @staticmethod
    def _validate_task_with(message: Message, params: RoundParameters) -> None:
        """Pure-compute validation body (thread-safe; params pre-fetched)."""
        seed = params.seed.as_bytes()
        payload = message.payload
        if isinstance(payload, (Sum, Sum2)):
            if not verify_detached(message.participant_pk, payload.sum_signature, seed + b"sum"):
                raise ServiceError("task-validator", "invalid sum task signature")
            if not is_eligible(payload.sum_signature, params.sum):
                raise ServiceError("task-validator", "not eligible for the sum task")
        elif isinstance(payload, Update):
            if not verify_detached(message.participant_pk, payload.sum_signature, seed + b"sum"):
                raise ServiceError("task-validator", "invalid sum task signature")
            if not verify_detached(
                message.participant_pk, payload.update_signature, seed + b"update"
            ):
                raise ServiceError("task-validator", "invalid update task signature")
            # an update participant must NOT be a sum participant, and must
            # be eligible for the update task
            if is_eligible(payload.sum_signature, params.sum):
                raise ServiceError("task-validator", "sum participant sent an update message")
            if not is_eligible(payload.update_signature, params.update):
                raise ServiceError("task-validator", "not eligible for the update task")
        else:
            raise ServiceError("task-validator", f"unexpected payload {type(payload)}")


class Fetcher:
    """Read access to the latest round data for the API layer."""

    def __init__(self, events: EventSubscriber):
        self.events = events

    def round_params(self) -> RoundParameters:
        return self.events.params.get_latest().event

    def phase(self) -> PhaseName:
        return self.events.phase.get_latest().event

    def sum_dict(self):
        return self.events.sum_dict.get_latest().event.dict

    def seed_dict(self):
        return self.events.seed_dict.get_latest().event.dict

    def seeds_for(self, pk: bytes):
        """The UpdateSeedDict slice for one sum participant (GET /seeds)."""
        seed_dict = self.seed_dict()
        if seed_dict is None:
            return None
        return seed_dict.get(pk)

    def model(self):
        return self.events.model.get_latest().event.model
