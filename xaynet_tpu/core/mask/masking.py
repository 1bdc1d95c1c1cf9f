"""Masking, aggregation and unmasking of models.

Functional port of the reference engine (reference:
rust/xaynet-core/src/mask/masking.rs:74-418) over the TPU-native limb
representation:

- ``Masker.mask``: clamp/scale/shift weights into the finite group (see
  ``encode``), then add ChaCha20-derived random group elements — the random
  draws are bit-identical to the reference so sum participants and the
  coordinator derive identical masks from the same seed;
- ``Aggregation.aggregate``: elementwise modular addition over ``uint32[n,L]``
  limb tensors (the coordinator hot loop; device version in
  ``xaynet_tpu.ops.limbs_jax``);
- ``Aggregation.unmask``: modular subtract of the aggregated mask, then
  fixed-point decode and scalar-sum correction.
"""

from __future__ import annotations

from typing import Union

import numpy as np

from ...ops import limbs as limb_ops
from ...telemetry import unmask as unmask_stages
from ..crypto.prng import StreamSampler
from . import encode as _encode
from .config import MaskConfig, MaskConfigPair
from .encode import (
    clamp_scalar,
    decode_scalar_sum,
    decode_vect_exact,
    encode_unit,
    encode_vect_limbs,
    has_fast_path,
)
from fractions import Fraction
from .model import Model, Scalar
from .object import MaskObject, MaskUnit, MaskVect
from .seed import MaskSeed


class AggregationError(ValueError):
    """Aggregation validation failure; ``kind`` mirrors the reference enum."""

    def __init__(self, kind: str):
        super().__init__(f"aggregation error: {kind}")
        self.kind = kind


class UnmaskingError(ValueError):
    """Unmasking validation failure; ``kind`` mirrors the reference enum."""

    def __init__(self, kind: str):
        super().__init__(f"unmasking error: {kind}")
        self.kind = kind


def check_nb_models(config: MaskConfigPair, count: int) -> None:
    """Raise what ``Aggregation.validate_aggregation`` raises while ``count``
    valid objects of ``config`` are aggregated one by one: nothing up to the
    smaller ``max_nb_models``; past it the object at that index finds the
    vector's count full first (``TooManyModels``) unless only the unit's
    is (``TooManyScalars``)."""
    cap_vect, cap_unit = config.vect.max_nb_models, config.unit.max_nb_models
    if count > min(cap_vect, cap_unit):
        raise AggregationError("TooManyModels" if cap_vect <= cap_unit else "TooManyScalars")


def _order_limbs(config: MaskConfig) -> np.ndarray:
    return limb_ops.order_limbs_for(config.order)


def _mask_native(seed: bytes, sampler: StreamSampler, weights: np.ndarray,
                 s_clamped: Fraction, config: MaskConfig):
    """Fused native mask (draw + dd encode + mod add); None when unavailable."""
    from ...ops import dd
    from ...utils import native

    lib = native.load()
    if lib is None or not hasattr(lib, "xn_mask_f32"):
        return None
    order = config.order
    draw_nbytes = limb_ops.draw_width_for(order)
    elem_nbytes = config.bytes_per_number
    if draw_nbytes > 16:
        return None
    import ctypes

    n = weights.shape[0]
    s_hi, s_lo = dd.from_fraction(s_clamped)
    out = np.empty(n * elem_nbytes, dtype=np.uint8)
    w = np.ascontiguousarray(weights, dtype=np.float32)
    new_offset = lib.xn_mask_f32(
        native.as_u8p(seed),
        sampler.consumed_bytes,
        w.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n,
        native.as_u8p(order.to_bytes(draw_nbytes, "little")),
        draw_nbytes,
        elem_nbytes,
        ctypes.c_double(float(int(config.add_shift))),
        ctypes.c_double(float(config.exp_shift)),
        ctypes.c_double(s_hi),
        ctypes.c_double(s_lo),
        native.np_u8p(out),
    )
    if new_offset == 0:
        return None
    sampler.skip_bytes(new_offset - sampler.consumed_bytes)
    return limb_ops.bytes_le_to_limbs(out, n, elem_nbytes, op=None)


class Masker:
    """Masks a model with a (possibly given) random 32-byte seed."""

    def __init__(self, config: MaskConfigPair, seed: MaskSeed | None = None):
        self.config = config
        self.seed = seed if seed is not None else MaskSeed.generate()

    def mask(self, scalar: Scalar, model: Union[Model, np.ndarray]) -> tuple[MaskSeed, MaskObject]:
        """Mask ``model``; returns (seed, masked object).

        ``model`` may be an exact ``Model`` or a numpy float array (fast path
        for bounded-f32 configs).
        """
        config_n, config_1 = self.config.vect, self.config.unit
        sampler = StreamSampler(self.seed.as_bytes())
        # draw order matters: one unit draw first, then the vector draws
        rand_1 = sampler.draw_limbs(1, config_1.order)[0]
        length = len(model)

        s_clamped = clamp_scalar(scalar.value, config_1)

        weights = model if isinstance(model, np.ndarray) else model.weights
        masked_vect = None
        if (
            isinstance(weights, np.ndarray)
            and weights.dtype == np.float32
            and has_fast_path(config_n)
        ):
            masked_vect = _mask_native(
                self.seed.as_bytes(), sampler, weights, s_clamped, config_n
            )
        if masked_vect is None:
            rand_n = sampler.draw_limbs(length, config_n.order)
            encoded = encode_vect_limbs(weights, s_clamped, config_n)
            masked_vect = limb_ops.mod_add(encoded, rand_n, _order_limbs(config_n))

        shifted_1 = encode_unit(s_clamped, config_1)
        n_limb_1 = limb_ops.n_limbs_for_order(config_1.order)
        masked_unit = limb_ops.mod_add(
            limb_ops.int_to_limbs(shifted_1, n_limb_1)[None, :],
            rand_1[None, :],
            _order_limbs(config_1),
        )[0]

        obj = MaskObject(MaskVect(config_n, masked_vect), MaskUnit(config_1, masked_unit))
        return self.seed, obj


class Aggregation:
    """An aggregator for masks and masked models (modular accumulation)."""

    def __init__(self, config: MaskConfigPair, object_size: int):
        self.nb_models = 0
        self.object = MaskObject.empty(config, object_size)
        self.object_size = object_size

    @classmethod
    def from_object(cls, obj: MaskObject) -> "Aggregation":
        agg = cls(obj.config, len(obj))
        agg.aggregate(obj)
        return agg

    def __len__(self) -> int:
        return self.object_size

    @property
    def config(self) -> MaskConfigPair:
        return self.object.config

    # --- validation (reference: masking.rs:142-169, 253-279) -------------

    def validate_unmasking(self, mask: MaskObject) -> None:
        if self.nb_models == 0:
            raise UnmaskingError("NoModel")
        if self.nb_models > self.object.vect.config.max_nb_models:
            raise UnmaskingError("TooManyModels")
        if self.nb_models > self.object.unit.config.max_nb_models:
            raise UnmaskingError("TooManyScalars")
        if self.object.vect.config != mask.vect.config or self.object_size != len(mask.vect):
            raise UnmaskingError("MaskManyMismatch")
        if self.object.unit.config != mask.unit.config:
            raise UnmaskingError("MaskOneMismatch")
        if not mask.is_valid():
            raise UnmaskingError("InvalidMask")

    def validate_aggregation(self, obj: MaskObject) -> None:
        if self.object.vect.config != obj.vect.config:
            raise AggregationError("ModelMismatch")
        if self.object.unit.config != obj.unit.config:
            raise AggregationError("ScalarMismatch")
        if self.object_size != len(obj.vect):
            raise AggregationError("ModelMismatch")
        if self.nb_models >= self.object.vect.config.max_nb_models:
            raise AggregationError("TooManyModels")
        if self.nb_models >= self.object.unit.config.max_nb_models:
            raise AggregationError("TooManyScalars")
        if not obj.is_valid():
            raise AggregationError("InvalidObject")

    # --- aggregation (reference: masking.rs:292-316) ----------------------

    def aggregate(self, obj: MaskObject) -> None:
        if self.nb_models == 0:
            # fresh containers so later accumulation never mutates the
            # caller's object (the reference takes ownership by move)
            self.object = MaskObject(
                MaskVect(obj.vect.config, obj.vect.data),
                MaskUnit(obj.unit.config, obj.unit.data),
            )
            self.nb_models = 1
            return
        config_n, config_1 = self.object.vect.config, self.object.unit.config
        self.object.vect.data = limb_ops.mod_add(
            self.object.vect.data, obj.vect.data, _order_limbs(config_n)
        )
        self.object.unit.data = limb_ops.mod_add(
            self.object.unit.data[None, :], obj.unit.data[None, :], _order_limbs(config_1)
        )[0]
        self.nb_models += 1

    def aggregate_batch(self, stack: np.ndarray, unit_stack: np.ndarray) -> None:
        """Aggregate ``K`` updates at once: ``uint32[K, n, L]`` + ``uint32[K, L]``.

        Tree-reduces the batch (log2 K flat kernels) then folds into the
        accumulator — the staging-friendly shape for the device path.
        """
        k = stack.shape[0]
        if k == 0:
            return
        config_n, config_1 = self.object.vect.config, self.object.unit.config
        ol_n = _order_limbs(config_n)
        batch_u = limb_ops.batch_mod_sum(unit_stack[:, None, :], _order_limbs(config_1))[0]
        # vector part: native single-pass fold (batch + accumulator in one
        # read) — u64 kernel for <=2-limb orders, generic n-limb kernel for
        # the rest; numpy pairwise tree only without the native library
        acc_v = self.object.vect.data if self.nb_models else np.zeros_like(stack[0])
        fast = limb_ops.fold_wire_batch_host(acc_v, stack, ol_n)
        if fast is not None:
            self.object.vect.data = fast
        else:
            batch_v = limb_ops.batch_mod_sum(stack, ol_n)
            if self.nb_models == 0:
                self.object.vect.data = batch_v
            else:
                self.object.vect.data = limb_ops.mod_add(
                    self.object.vect.data, batch_v, ol_n
                )
        if self.nb_models == 0:
            self.object.unit.data = batch_u
        else:
            self.object.unit.data = limb_ops.mod_add(
                self.object.unit.data[None, :], batch_u[None, :], _order_limbs(config_1)
            )[0]
        self.nb_models += k

    def aggregate_partial(self, obj: MaskObject, nb_models: int) -> None:
        """Fold a pre-aggregated PARTIAL — the modular sum of ``nb_models``
        already-masked updates — as one addition.

        Masked aggregation is modular addition (associative and
        commutative), so an edge-side partial folded here is byte-identical
        to folding its member updates individually; only the model count
        must advance by the partial's member count instead of one.
        """
        if nb_models < 1:
            raise AggregationError("EmptyPartial")
        remaining = min(
            self.object.vect.config.max_nb_models, self.object.unit.config.max_nb_models
        ) - self.nb_models
        if nb_models > remaining:
            raise AggregationError("TooManyModels")
        self.aggregate(obj)
        self.nb_models += nb_models - 1

    # --- unmasking (reference: masking.rs:190-231) ------------------------

    def _unmasked_vect(self, mask_obj: MaskObject) -> "np.ndarray | limb_ops.PlanarLimbs":
        # the host arm's `subtract` stage of the Unmask phase
        # (telemetry/unmask.py), on wire rows; the device arms
        # (server/aggregation.py) bracket their own three and hand over the
        # planes they fetched: the decoders are told which by the type
        with unmask_stages.stage("subtract", bytes=mask_obj.vect.data.nbytes):
            return limb_ops.mod_sub(
                self.object.vect.data, mask_obj.vect.data, _order_limbs(self.config.vect)
            )

    def _unmasked_unit(self, mask_obj: MaskObject) -> int:
        n_unit = limb_ops.mod_sub(
            self.object.unit.data[None, :],
            mask_obj.unit.data[None, :],
            _order_limbs(self.config.unit),
        )[0]
        return limb_ops.limbs_to_int(n_unit)

    def _unmasked_limbs(self, mask_obj: MaskObject) -> tuple[np.ndarray, int]:
        return self._unmasked_vect(mask_obj), self._unmasked_unit(mask_obj)

    # configs are read through ``self.config``, never ``self.object``: on the
    # device-resident subclass (server/aggregation.py) the latter gathers
    # the mesh accumulator

    def unmask(self, mask_obj: MaskObject) -> Model:
        """Exact unmasking -> ``Model`` of rational weights (reference parity)."""
        config = self.config
        n_vect, n_unit = self._unmasked_limbs(mask_obj)
        scalar_sum = decode_scalar_sum(n_unit, config.unit, self.nb_models)
        if isinstance(n_vect, limb_ops.PlanarLimbs):
            n_vect = n_vect.wire()
        values = limb_ops.limbs_to_ints(n_vect)
        return Model(decode_vect_exact(values, config.vect, self.nb_models, scalar_sum))

    def unmask_array(self, mask_obj: MaskObject) -> np.ndarray:
        """Fast unmasking -> float64 numpy array (double-double decode):
        the vector's subtract (its stages bracketed where they run), then
        the `decode` stage of the Unmask phase (telemetry/unmask.py)."""
        config = self.config
        n_vect = self._unmasked_vect(mask_obj)
        with unmask_stages.stage("decode", bytes=n_vect.nbytes):
            scalar_sum = decode_scalar_sum(self._unmasked_unit(mask_obj), config.unit, self.nb_models)
            # the vector's decoders are looked up in their module when they
            # run, as the device arm always did: a launcher may stand in for
            # one (benchmark/tests/serve_broken.py alters the answer there)
            fast = has_fast_path(config.vect)
            decode = _encode.decode_vect_fast if fast else _encode.decode_vect_any
            model = decode(n_vect, config.vect, self.nb_models, scalar_sum)
            # the limbs go back inside the stage that read them
            del n_vect
        return model
