"""Fold-kernel registry.

Single source of truth for the aggregation fold kernel names, shared by
``parallel.aggregator`` (which executes them) and ``server.settings`` (which
validates configs without importing jax).
"""

FOLD_KERNELS = ("auto", "xla", "pallas", "pallas-interpret")

# Sum2 mask derive+sum kernels (``ops.masking_jax.sum_masks``):
#
# - ``batch``        — ALL derivations of a seed group in ONE jitted in-graph
#                      program (``derive_mask_limbs_batch``), the resulting
#                      mask planes streamed through the PR-7 shard pipeline;
# - ``fused-pallas`` — the Pallas keystream→reject→modular-add kernel
#                      (``ops.fold_pallas.mask_fold_planar_pallas``): the mask
#                      is never materialized in HBM, only the accumulator is;
# - ``fused-pallas-interpret`` — the same kernel through the Pallas
#                      interpreter (the CPU route that keeps the fused kernel
#                      continuously exercised without a Mosaic compiler);
# - ``host-threaded`` — the CPU incumbent: the host's one streaming
#                      derive-and-sum (``core.mask.derive_sum``): accepted
#                      draws accumulate as they are sampled, over every core,
#                      and no mask materializes (``xn_derive_sum``, draws of
#                      up to 16 bytes); wider orders and a host without the
#                      library fold a bounded wave of ``StreamSampler`` masks;
# - ``host-chunked`` — the pre-promotion device path (host unit draws per
#                      seed + host-chunked device vector derivation), kept
#                      as an explicit fallback;
# - ``auto``         — first call races the candidates on a probe seed group
#                      (the fold-kernel auto-calibration idiom) and memoizes
#                      the winner process-wide.
MASK_KERNELS = (
    "auto",
    "batch",
    "fused-pallas",
    "fused-pallas-interpret",
    "host-threaded",
    "host-chunked",
)
