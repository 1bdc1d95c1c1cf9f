"""Whether the width-dependent host operations ran in the native library.

One property decides the label, for every operation: ``fast`` means a kernel
of ``native/libxaynet_native.so`` handled the elements, ``generic`` means
numpy or Python did (the route every operation falls to without the library,
and the one a shape the kernel refuses falls to). Which loop a kernel runs
inside the library (one ``uint64`` an element up to 8 wire bytes, a per-byte
or per-limb loop above) is not counted: at 25.5M elements of 10 bytes those
loops cost 3-23% of a kernel and nothing end to end (PERF.md section 6, PR 26),
where numpy costs a multiple. One counter says, in elements, which ran,
counted where the choice is made:

- ``parse``: ``ops/limbs.py::bytes_le_to_limbs`` (wire bytes -> limbs),
  ``wire_to_planes`` (v1 wire bytes -> checked byte planes in one pass), and
  ``core/mask/serialization.py::planar_to_interleaved``, always ``generic``:
  numpy's transpose of a planar block whose limb rows someone asks for;
- ``validate``: ``ops/limbs.py::all_lt_order`` (element < order, on limb
  rows), ``planes_lt_order`` (the same on a wire v2 vector's byte planes) and
  ``wire_to_planes`` (the comparison inside its pass: its elements count
  under ``parse`` and here);
- ``stage``: ``ops/limbs.py::pack_wire_slice``, ``pack_wire``, ``pack_planar``
  (limbs -> byte planes) and ``copy_planes`` (a v2 vector's planes copied
  into the slot);
- ``derive``: ``core/crypto/prng.py::StreamSampler.draw_limbs`` (seed -> mask
  elements), in the process that derives: the sum participant's, not the
  coordinator's. A third route, ``fused``, counts the elements that
  ``core/mask/derive_sum.py`` derived and summed in one native pass with no
  mask in memory (the sum participant's Sum2 on a host with the library);
- ``decode``: ``core/mask/encode.py::decode_vect_fast``, ``decode_vect_any``
  (unmasked limbs -> float64).
"""

from __future__ import annotations

from .registry import get_registry

ELEMENTS = get_registry().counter(
    "xaynet_codec_elements_total",
    "Group elements through a width-dependent host operation (parse, validate, "
    "stage, derive, decode), by the route it took: fast = a kernel of the native "
    "library, generic = numpy or Python, fused (derive only) = the library "
    "derived and summed with no mask in memory (telemetry/codec.py).",
    ("op", "route"),
)


def count(op: str, fast: bool, elements: int) -> None:
    ELEMENTS.labels(op=op, route="fast" if fast else "generic").inc(elements)


def count_fused(op: str, elements: int) -> None:
    ELEMENTS.labels(op=op, route="fused").inc(elements)
