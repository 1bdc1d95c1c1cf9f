"""Mask objects: masked models / masks as fixed-width limb tensors.

Reference shape (rust/xaynet-core/src/mask/object/mod.rs:24,65,117):
``MaskVect`` (vector of group elements) + ``MaskUnit`` (one group element for
the masked scalar) compose a ``MaskObject``. Validity means every element is
below the configured group order.

TPU-native representation: elements live as ``uint32[n, L]`` limb arrays
(little-endian limb order) — the exact layout the aggregation kernels and the
wire codec consume — instead of python bignums.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ...ops import limbs as limb_ops
from .config import MaskConfig, MaskConfigPair


class InvalidMaskObjectError(ValueError):
    """Mask object data does not satisfy its masking configuration."""


def _order_limbs(config: MaskConfig) -> np.ndarray:
    return limb_ops.order_limbs_for(config.order)


@dataclass
class MaskVect:
    """A vector of finite-group elements with its masking configuration."""

    config: MaskConfig
    data: np.ndarray  # uint32[n, L]

    @classmethod
    def from_ints(cls, config: MaskConfig, values) -> "MaskVect":
        n_limb = limb_ops.n_limbs_for_order(config.order)
        return cls(config, limb_ops.ints_to_limbs(values, n_limb))

    @classmethod
    def new(cls, config: MaskConfig, values) -> "MaskVect":
        obj = cls.from_ints(config, values) if not isinstance(values, np.ndarray) else cls(config, values)
        if not obj.is_valid():
            raise InvalidMaskObjectError("mask vector element >= group order")
        return obj

    def to_ints(self) -> list[int]:
        return limb_ops.limbs_to_ints(self.data)

    def __len__(self) -> int:
        return self.data.shape[0]

    def is_valid(self) -> bool:
        if self.data.ndim != 2:
            return False
        n_limb = limb_ops.n_limbs_for_order(self.config.order)
        if self.data.shape[1] != n_limb:
            return False
        return limb_ops.all_lt_order(self.data, self.config.order)

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, MaskVect)
            and self.config == other.config
            and self.data.shape == other.data.shape
            and bool(np.array_equal(self.data, other.data))
        )


class LazyWireMaskVect(MaskVect):
    """A ``MaskVect`` parsed from wire with limb materialization DEFERRED.

    Carries the fixed-width element block as bytes (``wire_block``, uint8)
    and says two things of it apart: ``packed_wire``, which wire the
    MESSAGE came on (it carried the v2 flag, or it is a v1 message), and
    ``planar``, which LAYOUT the block has (byte planes, or interleaved as
    on the v1 wire). It takes one of three roads:

    - **wire ingest** (``parse_mask_vect(lazy=True)``, v1 or v2): a
      device-ingest coordinator unpacks + validity-checks + folds on the
      accelerator without ever running the host element parse. The eager
      parse rejects invalid elements with ``DecodeError`` at parse time;
      this road defers that rejection to ``validate_aggregation`` (device)
      or the first host materialization — same update rejected, one stage
      later. The block is a zero-copy view of the body, in its layout.
    - **the packed wire, eager** (a v2 block under ``lazy=False``): the
      parse scans the planes against the order (:meth:`check_planes`) and
      rejects with ``DecodeError`` as for a v1 body; the verdict rides on
      the object (``checked``), so ``is_valid()`` scans nothing again, and
      a packed-staging coordinator copies ``planar_block`` into the staging
      slot: no interleaved block and no limb row is ever made.
    - **the legacy wire, relaid in the parse** (a v1 block under
      ``parse_mask_vect(planes=True)``: the consumer's slots are byte
      planes): one native pass wrote the planes from the interleaved bytes
      and compared every element with the order (``ops/limbs.py::
      wire_to_planes``), so the object is born ``checked`` over planes of
      its own (not a view: the body is not kept), ``packed_wire`` false,
      and everything after it is the packed wire's road.

    Any host access to ``data`` materializes the limbs exactly like the v1
    eager parse would have (a planar block through the counted transposing
    fallback ``planar_to_interleaved``).
    """

    def __init__(
        self,
        config: MaskConfig,
        wire_block: np.ndarray,
        count: int,
        planar: bool = False,
        packed_wire: bool | None = None,
        checked: bool = False,
    ):
        self.config = config
        self.wire_block = wire_block  # uint8[count * bytes_per_number]
        self._count = count
        # the block is byte-planar (bpn planes of count bytes: the wire v2
        # layout, and the packed staging layout) instead of interleaved
        self.planar = planar
        # the message carried the v2 flag; a block's layout follows its wire
        # unless the maker says otherwise (the v1 parse that writes planes)
        self.packed_wire = planar if packed_wire is None else packed_wire
        self._data: np.ndarray | None = None
        # device planar cached by StagedAggregator.validate_aggregation so
        # stage() never re-uploads; _wire_invalid is the cached REJECTED
        # verdict from a batch prevalidation (validate_aggregation raises
        # on it without another device round-trip)
        self._staged_planar = None
        self._wire_invalid = False
        # the host's verdict on the planes (check_planes, or the maker's own
        # pass: ``checked``), None = not scanned
        self._planes_valid: bool | None = True if checked else None

    @property
    def materialized(self) -> bool:
        return self._data is not None

    @property
    def checked(self) -> bool:
        """Whether the host has compared this block's planes with the order
        (the eager v2 parse, or the v1 parse that wrote them):
        ``is_valid()`` then repeats the verdict."""
        return self._planes_valid is not None

    def check_planes(self) -> bool:
        """Element validity of a planar block on its byte planes (no limb
        row made), once: the verdict is kept for ``is_valid()``."""
        if self._planes_valid is None:
            self._planes_valid = limb_ops.planes_lt_order(self.planar_block, self.config.order)
        return self._planes_valid

    def is_valid(self) -> bool:
        if self._planes_valid is not None:
            return self._planes_valid
        return super().is_valid()

    @property
    def planar_block(self) -> np.ndarray:
        """Zero-copy ``uint8[bpn, count]`` view of a planar element block
        (the shape the packed staging rings and the device planar-unpack
        consume directly)."""
        if not self.planar:
            raise ValueError("planar_block on an interleaved element block")
        return np.asarray(self.wire_block).reshape(
            self.config.bytes_per_number, self._count
        )

    @property  # type: ignore[override]
    def data(self) -> np.ndarray:
        if self._data is None:
            block = np.asarray(self.wire_block)
            if self.planar:
                from .serialization import planar_to_interleaved

                block = planar_to_interleaved(
                    block, self._count, self.config.bytes_per_number
                )
            self._data = limb_ops.bytes_le_to_limbs(
                block, self._count, self.config.bytes_per_number
            )
        return self._data

    @data.setter
    def data(self, value) -> None:  # dataclass-compat (never used in practice)
        self._data = value
        self._planes_valid = None  # a verdict on other bytes

    def __len__(self) -> int:
        return self._count


def wire_route(vect: MaskVect) -> tuple[str, str]:
    """``(wire, route)`` of a parsed Update vector, the labels of
    ``xaynet_update_wire_bytes_total`` and of the ``parse`` / ``validate`` /
    ``to_planar`` spans. ``wire``: ``packed`` = the message carried the v2
    flag, ``legacy`` = v1, whatever layout the parse gave the block.
    ``route``: ``copy`` = byte planes the host has checked (a v2 body's own,
    or the ones the v1 parse wrote; a packed-staging slot takes them by
    copy), ``device`` = an unchecked wire block for the device's unpack
    (wire ingest), ``relayout`` = limb rows (the v1 parse for a consumer
    that wants them, or a block since materialized)."""
    wire = "packed" if getattr(vect, "packed_wire", False) else "legacy"
    if isinstance(vect, LazyWireMaskVect) and not vect.materialized:
        return wire, "copy" if vect.checked else "device"
    return wire, "relayout"


@dataclass
class MaskUnit:
    """A single finite-group element (the masked scalar) with its config."""

    config: MaskConfig
    data: np.ndarray  # uint32[L]

    @classmethod
    def from_int(cls, config: MaskConfig, value: int) -> "MaskUnit":
        n_limb = limb_ops.n_limbs_for_order(config.order)
        return cls(config, limb_ops.int_to_limbs(value, n_limb))

    @classmethod
    def new(cls, config: MaskConfig, value: int) -> "MaskUnit":
        obj = cls.from_int(config, value)
        if not obj.is_valid():
            raise InvalidMaskObjectError("mask unit element >= group order")
        return obj

    def to_int(self) -> int:
        return limb_ops.limbs_to_int(self.data)

    def is_valid(self) -> bool:
        n_limb = limb_ops.n_limbs_for_order(self.config.order)
        if self.data.shape != (n_limb,):
            return False
        return bool(limb_ops.elements_lt_order(self.data[None, :], self.config.order)[0])

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, MaskUnit)
            and self.config == other.config
            and bool(np.array_equal(self.data, other.data))
        )


@dataclass
class MaskObject:
    """A masked model (or mask): vector part + unit (scalar) part."""

    vect: MaskVect
    unit: MaskUnit

    @classmethod
    def new(cls, config: MaskConfigPair, vect_values, unit_value: int) -> "MaskObject":
        return cls(MaskVect.new(config.vect, vect_values), MaskUnit.new(config.unit, unit_value))

    @classmethod
    def empty(cls, config: MaskConfigPair, size: int) -> "MaskObject":
        n_limb_v = limb_ops.n_limbs_for_order(config.vect.order)
        n_limb_u = limb_ops.n_limbs_for_order(config.unit.order)
        return cls(
            MaskVect(config.vect, np.zeros((size, n_limb_v), dtype=np.uint32)),
            MaskUnit(config.unit, np.zeros(n_limb_u, dtype=np.uint32)),
        )

    @property
    def config(self) -> MaskConfigPair:
        return MaskConfigPair(vect=self.vect.config, unit=self.unit.config)

    def __len__(self) -> int:
        return len(self.vect)

    def is_valid(self) -> bool:
        return self.vect.is_valid() and self.unit.is_valid()

    def __eq__(self, other) -> bool:
        return isinstance(other, MaskObject) and self.vect == other.vect and self.unit == other.unit
