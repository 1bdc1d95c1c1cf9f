"""A whole PET round as one jittable JAX program.

The production stack runs a round as a socketed conversation: participants
mask locally, the coordinator folds masked updates as they arrive, sum
participants reconstruct the aggregate mask from the seed dictionary, and
the Unmask phase subtracts and decodes. Every step of that conversation is
deterministic given (mask config, participant seeds, local models, scalar)
— so the round is equally expressible as a pure function, which is what
``SimRound`` builds (the DrJAX observation applied to PET):

    phase 1 (update):  vmap over participants of
                       ``derive_mask_ingraph`` + modular add of the
                       fixed-point-encoded model  -> masked models
    phase 2 (fold):    modular tree-sum of the masked population,
                       scanned over participant blocks (and sharded
                       over the mesh's participant axis when present)
    phase 3 (sum2):    the sum mask — the modular sum of every
                       participant's mask — reconstructed in-graph
    phase 4 (unmask):  modular subtract, still in-graph

All four phases trace into ONE ``jax.jit`` program over ``uint32`` limb
tensors: exact group arithmetic, no float in the graph, no host syncs, no
Python-level per-participant loop. The float boundary — fixed-point encode
of the local models before the program, fixed-point decode of the unmasked
aggregate after it — runs through the SAME production host functions
(``core/mask/encode.py``) a real participant and the real Unmask phase
use, which is what makes the simulated global model byte-identical to the
production server round (asserted by ``sim.oracle``).

Scaling knobs: ``block_size`` bounds how many participants derive
concurrently (device memory ~ block_size x keystream chunk); blocks fold
sequentially under ``lax.scan``; a multi-device mesh shards whole blocks
across its devices (the PR-7 shard-plan idiom turned 90 degrees: the
production fold shards the *model* axis because updates arrive serially —
the simulation owns all participants up front, so it shards the
*participant* axis and modularly combines the per-device partial
aggregates, which is exact because masked aggregation is a commutative
modular sum).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from fractions import Fraction
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.mask.config import MaskConfigPair
from ..core.mask.encode import (
    clamp_scalar,
    decode_scalar_sum,
    decode_vect_any,
    decode_vect_fast,
    encode_unit,
    has_fast_path,
)
from ..ops import limbs as host_limbs, limbs_jax
from ..ops.masking_jax import (
    derive_chunk_budgets,
    derive_mask_ingraph,
    encode_models_batch,
    seed_words,
)
from ..parallel.mesh import MODEL_AXIS
from ..telemetry import profiling


def seeds_for(n: int, root: int = 0) -> list[bytes]:
    """``n`` deterministic 32-byte mask seeds (research-workload helper)."""
    rng = np.random.default_rng(root)
    return [rng.bytes(32) for _ in range(n)]


@dataclass(frozen=True)
class SimSpec:
    """Static shape of a simulated round (hashable: one compiled program each)."""

    config: MaskConfigPair
    model_length: int
    block_size: int = 128  # participants deriving concurrently per vmap block
    fuse_mask_sum: bool = True  # derive once, feed update fold AND sum-mask fold
    return_internals: bool = False  # also return the pre-unmask aggregates

    def __post_init__(self):
        if self.model_length < 1:
            raise ValueError("model_length must be >= 1")
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")


@dataclass
class SimResult:
    """Outcome of one simulated round."""

    global_model: np.ndarray  # float64[model_length], the unmasked aggregate
    nb_models: int
    scalar_sum: Fraction
    model_vect_limbs: np.ndarray  # uint32[model_length, L] — unmasked group elements
    model_unit_int: int
    internals: Optional[dict] = field(default=None, repr=False)


class SimRound:
    """One compiled whole-round program for a fixed (spec, mesh).

    ``run(seeds, weights, scalar)`` simulates the round for any population
    size (padded up to the compiled block grid); population shapes are
    static per spec, so successive runs reuse the compiled program.
    """

    def __init__(self, spec: SimSpec, mesh=None):
        self.spec = spec
        self.mesh = mesh if mesh is not None and len(mesh.devices.flat) > 1 else None
        cfg = spec.config
        self._ol_v = tuple(int(x) for x in host_limbs.order_limbs_for(cfg.vect.order))
        self._ol_u = tuple(int(x) for x in host_limbs.order_limbs_for(cfg.unit.order))
        self._n_limb_v = host_limbs.n_limbs_for_order(cfg.vect.order)
        self._n_limb_u = host_limbs.n_limbs_for_order(cfg.unit.order)
        # chunk budgets: block_size lanes derive concurrently (scan blocks
        # are sequential; each mesh device runs block_size lanes too) — the
        # shared provisioning rule of the promoted production derive
        self._unit_chunk, self._vect_chunk = derive_chunk_budgets(
            spec.model_length, cfg, spec.block_size
        )
        self._program = jax.jit(self._build_program())
        self.program_calls = 0  # observability: one per run(), never per participant

    # --- in-graph program bodies (host syncs forbidden, see tools/lint.py) --

    def _build_program(self):
        spec, mesh = self.spec, self.mesh
        n = spec.model_length
        ol_v, ol_u = np.asarray(self._ol_v, np.uint32), np.asarray(self._ol_u, np.uint32)
        unit_chunk, vect_chunk = self._unit_chunk, self._vect_chunk
        config = spec.config
        zero_carry = self._zero_carry

        def _prog_derive(kw):
            return derive_mask_ingraph(kw, n, config, unit_chunk, vect_chunk)

        def _prog_update_fold(carry, xs):
            """One participant block: derive masks, mask the encoded models,
            fold the masked population (and, when ``fuse_mask_sum``, the
            mask sum in the same pass — phases 1+2+3)."""
            acc_mv, acc_mu, acc_kv, acc_ku = carry
            kw, enc, unit_enc, valid = xs
            units, vects = jax.vmap(_prog_derive)(kw)  # [B, L1], [B, n, L]
            masked = limbs_jax.mod_add(enc, vects, ol_v)
            unit_masked = limbs_jax.mod_add(unit_enc, units, ol_u)
            # padding lanes contribute the group identity (zero) everywhere
            masked = jnp.where(valid[:, None, None], masked, jnp.uint32(0))
            unit_masked = jnp.where(valid[:, None], unit_masked, jnp.uint32(0))
            acc_mv = limbs_jax.mod_add(acc_mv, limbs_jax.batch_mod_sum(masked, ol_v), ol_v)
            acc_mu = limbs_jax.mod_add(
                acc_mu[None, :], limbs_jax.batch_mod_sum(unit_masked[:, None, :], ol_u), ol_u
            )[0]
            if spec.fuse_mask_sum:
                vects = jnp.where(valid[:, None, None], vects, jnp.uint32(0))
                units = jnp.where(valid[:, None], units, jnp.uint32(0))
                acc_kv = limbs_jax.mod_add(acc_kv, limbs_jax.batch_mod_sum(vects, ol_v), ol_v)
                acc_ku = limbs_jax.mod_add(
                    acc_ku[None, :], limbs_jax.batch_mod_sum(units[:, None, :], ol_u), ol_u
                )[0]
            return (acc_mv, acc_mu, acc_kv, acc_ku), None

        def _prog_mask_sum_fold(carry, xs):
            """Phase 3 standalone (``fuse_mask_sum=False``): the sum
            participants' reconstruction re-derives every mask from the
            seed dictionary, exactly like a real Sum2 leg."""
            acc_kv, acc_ku = carry
            kw, valid = xs
            units, vects = jax.vmap(_prog_derive)(kw)
            vects = jnp.where(valid[:, None, None], vects, jnp.uint32(0))
            units = jnp.where(valid[:, None], units, jnp.uint32(0))
            acc_kv = limbs_jax.mod_add(acc_kv, limbs_jax.batch_mod_sum(vects, ol_v), ol_v)
            acc_ku = limbs_jax.mod_add(
                acc_ku[None, :], limbs_jax.batch_mod_sum(units[:, None, :], ol_u), ol_u
            )[0]
            return (acc_kv, acc_ku), None

        def _prog_shard(kw, enc, unit_enc, valid):
            """Per-device slice of the block grid: scan the local blocks,
            return partial accumulators with a leading singleton axis so
            shard_map concatenates them into ``[ndev, ...]`` partials."""
            unit_b = jnp.broadcast_to(unit_enc, kw.shape[:2] + unit_enc.shape[-1:])
            (mv, mu, kv, ku), _ = jax.lax.scan(
                _prog_update_fold, zero_carry(), (kw, enc, unit_b, valid)
            )
            if not spec.fuse_mask_sum:
                zeros = zero_carry()
                (kv, ku), _ = jax.lax.scan(_prog_mask_sum_fold, (zeros[2], zeros[3]), (kw, valid))
            return mv[None], mu[None], kv[None], ku[None]

        def _prog_round(kw, enc, unit_enc, valid):
            """The whole round. Inputs: ``kw`` uint32[nblocks, B, 8] seed
            words, ``enc`` uint32[nblocks, B, n, L] encoded models,
            ``unit_enc`` uint32[L1], ``valid`` bool[nblocks, B]."""
            if mesh is None:
                mv, mu, kv, ku = _prog_shard(kw, enc, unit_enc, valid)
                mv, mu, kv, ku = mv[0], mu[0], kv[0], ku[0]
            else:
                from jax.sharding import PartitionSpec as P

                sharded = jax.shard_map(
                    _prog_shard,
                    mesh=mesh,
                    in_specs=(P(MODEL_AXIS), P(MODEL_AXIS), P(), P(MODEL_AXIS)),
                    out_specs=(P(MODEL_AXIS), P(MODEL_AXIS), P(MODEL_AXIS), P(MODEL_AXIS)),
                    check_vma=False,
                )
                pmv, pmu, pkv, pku = sharded(kw, enc, unit_enc, valid)
                # cross-device combine: modular sums are associative and
                # commutative, so folding per-device partials is exact
                mv = limbs_jax.batch_mod_sum(pmv, ol_v)
                mu = limbs_jax.batch_mod_sum(pmu[:, None, :], ol_u)[0]
                kv = limbs_jax.batch_mod_sum(pkv, ol_v)
                ku = limbs_jax.batch_mod_sum(pku[:, None, :], ol_u)[0]
            # phase 4: unmask — subtract the reconstructed sum mask
            model_v = limbs_jax.mod_sub(mv, kv, ol_v)
            model_u = limbs_jax.mod_sub(mu[None, :], ku[None, :], ol_u)[0]
            if spec.return_internals:
                return model_v, model_u, (mv, mu, kv, ku)
            return model_v, model_u, None

        return _prog_round

    def _zero_carry(self):
        n = self.spec.model_length
        return (
            jnp.zeros((n, self._n_limb_v), dtype=jnp.uint32),
            jnp.zeros((self._n_limb_u,), dtype=jnp.uint32),
            jnp.zeros((n, self._n_limb_v), dtype=jnp.uint32),
            jnp.zeros((self._n_limb_u,), dtype=jnp.uint32),
        )

    # --- host boundary ----------------------------------------------------

    def _grid(self, n_participants: int) -> tuple[int, int]:
        """(nblocks, padded population) for this spec/mesh."""
        block = self.spec.block_size
        n_dev = 1 if self.mesh is None else len(self.mesh.devices.flat)
        stride = block * n_dev
        padded = -(-n_participants // stride) * stride
        return padded // block, padded

    def run(
        self,
        seeds: list[bytes] | np.ndarray,
        weights: np.ndarray,
        scalar: Fraction = Fraction(1),
    ) -> SimResult:
        """Simulate one round: ``seeds`` are the participants' mask seeds
        (list of 32-byte strings or ``uint32[P, 8]`` key words), ``weights``
        the ``[P, model_length]`` local models, ``scalar`` the shared
        update scalar (the homogeneous-population shape; the production
        analogue is every participant sending ``scalar=1/P``)."""
        spec = self.spec
        if isinstance(seeds, np.ndarray):
            kw = np.asarray(seeds, dtype=np.uint32)
        else:
            kw = seed_words(list(seeds))
        if kw.ndim != 2 or kw.shape[1] != 8:
            raise ValueError("seeds must be 32-byte strings or uint32[P, 8] key words")
        p = kw.shape[0]
        if p < 1:
            raise ValueError("need at least one participant")
        cfg = spec.config
        if p > min(cfg.vect.max_nb_models, cfg.unit.max_nb_models):
            raise ValueError("TooManyModels: population exceeds the config's max_nb_models")
        weights = np.asarray(weights)
        if weights.shape != (p, spec.model_length):
            raise ValueError(f"weights must be [{p}, {spec.model_length}], got {weights.shape}")

        # float -> group boundary: the production fixed-point encode,
        # vectorized once over the whole population
        unit_enc, enc = encode_models_batch(weights, scalar, cfg)

        nblocks, padded = self._grid(p)
        if padded != p:
            kw = np.concatenate([kw, np.zeros((padded - p, 8), np.uint32)])
            enc = np.concatenate([enc, np.zeros((padded - p, *enc.shape[1:]), np.uint32)])
        valid = np.arange(padded) < p
        shape_b = (nblocks, spec.block_size)

        model_v, model_u, internals = profiling.timed_kernel(
            "sim_round",
            p * spec.model_length,
            lambda: self._program(
                jnp.asarray(kw.reshape(*shape_b, 8)),
                jnp.asarray(enc.reshape(*shape_b, *enc.shape[1:])),
                jnp.asarray(unit_enc),
                jnp.asarray(valid.reshape(shape_b)),
            ),
        )
        self.program_calls += 1

        # group -> float boundary: the production unmask decode
        n_vect = np.asarray(model_v)  # lint: sync-ok (host decode boundary)
        unit_int = host_limbs.limbs_to_int(np.asarray(model_u))  # lint: sync-ok
        scalar_sum = decode_scalar_sum(unit_int, cfg.unit, p)
        # unit-channel integrity: the unmasked unit must decode to exactly
        # P quantized clamped scalars (quantization per the fixed-point
        # encode, identical to what P production participants submit)
        s_clamped = clamp_scalar(scalar, cfg.unit)
        expect = decode_scalar_sum(p * encode_unit(s_clamped, cfg.unit), cfg.unit, p)
        if scalar_sum != expect:
            raise AssertionError(
                f"unit channel corrupted: decoded scalar sum {scalar_sum} != {expect}"
            )
        if has_fast_path(cfg.vect):
            global_model = decode_vect_fast(n_vect, cfg.vect, p, scalar_sum)
        else:
            global_model = decode_vect_any(n_vect, cfg.vect, p, scalar_sum)

        out_internals = None
        if internals is not None:
            mv, mu, kv, ku = internals
            out_internals = {
                "masked_vect_sum": np.asarray(mv),  # lint: sync-ok
                "masked_unit_sum": np.asarray(mu),  # lint: sync-ok
                "mask_vect_sum": np.asarray(kv),  # lint: sync-ok
                "mask_unit_sum": np.asarray(ku),  # lint: sync-ok
            }
        return SimResult(
            global_model=np.asarray(global_model, dtype=np.float64),
            nb_models=p,
            scalar_sum=scalar_sum,
            model_vect_limbs=n_vect,
            model_unit_int=unit_int,
            internals=out_internals,
        )
