"""The stages of the Unmask phase, election to retire (docs/DESIGN.md §16).

The phase does nine things to one object of the vector's size, all on the
state machine's task. Each is written down twice by one call here
(``tracing.timed_span``, as a message's stages are in ``server/stages.py``):
as a span under ``phase.unmask`` and as one observation on
``xaynet_unmask_seconds{stage=...}``, bracketed where the work happens:

- ``elect``: the store's count of masks, its two best (the objects the
  in-memory store kept; the Redis store parses its serialised members) and
  the unique-maximum election (``phases/unmask.py``);
- ``validate``: ``validate_unmasking`` (the mask's validity scan);
- ``mask_put``: the mask relaid out planar and padded, once a phase, and
  its ``device_put`` until the array is ready (``parallel/aggregator.py``);
- ``subtract``: the subtract kernel until its result is ready (on the host
  arm ``mod_sub`` over the vector, ``core/mask/masking.py``);
- ``fetch``: device to host, the copy alone: every device arm hands
  ``decode`` the planar array it fetched (``ops/limbs.py::PlanarLimbs``);
- ``decode``: the unit's ``mod_sub``, ``decode_scalar_sum``, ``decode_vect_*``
  (planes read in place, the element axis on the native library's threads);
- ``save``: the float64 bytes (the phase's one serialisation), the model
  store, the latest-model pointer;
- ``proof`` and ``retire``: the trust anchor's proof (the bytes ``save``
  made) and the journal's retire, where they run.

The eager per-shard unmask (docs/DESIGN.md §22) does the device's part on
the shard workers (``streaming.SPAN_EAGER_UNMASK``); what the phase's task
does meanwhile carries the same names: ``mask_put`` is the relayout, ``subtract``
the wait for the shards' tail jobs, ``fetch`` the assembled result.

Between the kernel's result and the end of the phase the model should be
walked once and serialised at most once. ``xaynet_unmask_model_bytes_total
{pass}`` counts the bytes each vector-sized host pass writes, where the
pass runs: ``transpose`` (planar to wire, ``PlanarLimbs.wire``: only a
caller that asks for wire pays it), ``decode`` (``decode_vect_*``: the
float64 written) and ``serialise`` (the phase's ``tobytes()``). All passes
over ``decode`` is how many times the model was written: 2.0 for a served
round (PERF.md section 3, ``unmask.host_passes``).
"""

from __future__ import annotations

from . import tracing as trace
from .registry import get_registry

# a toy round's microseconds up to the seconds a stage takes of a 256 MB mask
SECONDS = get_registry().histogram(
    "xaynet_unmask_seconds",
    "Wall time of one stage of the Unmask phase, by stage: elect, validate, "
    "mask_put, subtract, fetch, decode, save, proof, retire "
    "(telemetry/unmask.py).",
    ("stage",),
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0),
)

MODEL_BYTES = get_registry().counter(
    "xaynet_unmask_model_bytes_total",
    "Bytes written by each vector-sized host pass between the subtract "
    "kernel's result and the end of the Unmask phase, by pass: transpose "
    "(planar to wire), decode (the float64), serialise (its bytes) "
    "(telemetry/unmask.py).",
    ("pass",),
)

# stage label -> span name; spelled out (not built in a loop) so the
# analysis `span` pass reads the literal set against the DESIGN §16 table.
# Every stage starts and ends on the state machine's thread: all mirrored.
# The three whose work is that thread's own say what it spent (`usage`,
# telemetry/tracing.py; `mask_put` with the crew that packs its planes);
# `decode` and `save` fan out over the native library's threads and an
# executor's while the tail has nothing else of the vector's size in flight,
# so they read the whole process; `subtract` and `fetch` wait for the
# device, `proof` and `retire` for a store.
_SPANS: dict[str, str] = {
    "elect": trace.declare_span("unmask.elect", mirror=True, usage="thread"),
    "validate": trace.declare_span("unmask.validate", mirror=True, usage="thread"),
    "mask_put": trace.declare_span("unmask.mask_put", mirror=True, usage="crew"),
    "subtract": trace.declare_span("unmask.subtract", mirror=True),
    "fetch": trace.declare_span("unmask.fetch", mirror=True),
    "decode": trace.declare_span("unmask.decode", mirror=True, usage="process"),
    "save": trace.declare_span("unmask.save", mirror=True, usage="process"),
    "proof": trace.declare_span("unmask.proof", mirror=True),
    "retire": trace.declare_span("unmask.retire", mirror=True),
}


def count_pass(name: str, nbytes: int) -> None:
    """One vector-sized host pass wrote ``nbytes`` (counted where it runs)."""
    MODEL_BYTES.labels(**{"pass": name}).inc(nbytes)


def stage(label: str, **attrs):
    """Bracket one stage of the Unmask phase where it runs (a ``with``
    block on the phase's task: the ambient ``phase.unmask`` is the parent)."""
    return trace.timed_span(_SPANS[label], SECONDS.labels(stage=label), **attrs)
