"""The host derive-and-sum: the sum of the masks of many seeds, streamed.

A sum participant holds one seed per update of the round and owes the
coordinator the modular sum of the masks they expand to (reference:
rust/xaynet-core/src/mask/seed.rs:61-78 per seed, masking.rs:292-316 for the
sum). This module is the one host implementation of that, for the SDK's
state machine and for ``ops.masking_jax``'s ``host-threaded`` kernel alike; it
imports no JAX.

With the native library and a draw of up to 16 bytes (every bounded f32 and
f64 configuration of the catalogue) no mask is ever in memory: ``xn_derive_sum``
adds each accepted draw into one accumulator as it is sampled, over every core
the process may run on, whatever the number of seeds (docs/DESIGN.md section
15 has the argument that lets the cores share a seed). Memory is one
accumulator and the result, independent of the number of seeds. Wider orders
(the Bmax families: 37 bytes and up) and a host without the library take the
bounded wave: a few masks at a time from ``StreamSampler``, folded and dropped.

Either way the result is bit-identical to ``Aggregation`` over
``MaskSeed.derive_mask`` and does not depend on the number of threads.
"""

from __future__ import annotations

import logging
import os
from concurrent.futures import ThreadPoolExecutor
from fractions import Fraction

import numpy as np

from ...ops import limbs as limb_ops
from ...telemetry import codec
from ..crypto.prng import StreamSampler
from .config import MaskConfigPair

logger = logging.getLogger("xaynet.mask")

# Candidates a segment, the unit the cores share a seed by and commit in
# order: at most 1.8-4 MB of keystream at the catalogue's 7-16 byte draws
# (a millisecond or two of sampling, long against a scheduler's hiccup on one
# of the threads the others would wait for), and shorter where a seed would
# not give each of its threads eight of them.
_MAX_SEGMENT = 262144
_MIN_SEGMENT = 4096
_SEGMENTS_PER_THREAD = 8
# A thread is started for this much keystream at least (about a millisecond
# of sampling): the tens-of-elements sums of the tests run inline.
_MIN_KEYSTREAM_PER_THREAD = 1 << 20
# What the extra accumulators of seed-grained work may take: many small
# seeds get an accumulator a thread (nothing waits for another thread's
# segment), a 200 MB accumulator is shared by all of them.
_GROUP_ACC_BUDGET = 128 << 20
# Masks in memory at once on the bounded-wave route.
_WAVE = 8


def host_threads() -> int:
    """Threads for host derive-and-sum: ``XAYNET_NATIVE_THREADS`` if set
    (values under 1 mean one), else the CPUs this process may run on."""
    env = os.environ.get("XAYNET_NATIVE_THREADS", "")
    if env:
        try:
            return max(1, min(int(env), 64))
        except ValueError:
            logger.warning("ignoring non-integer XAYNET_NATIVE_THREADS=%r", env)
    try:
        return max(1, min(len(os.sched_getaffinity(0)), 64))
    except AttributeError:  # not on this platform
        return max(1, min(os.cpu_count() or 1, 64))


def accumulator_plan(order: int, k: int) -> tuple[int, bool]:
    """``(stride, eager)``: the bytes of an accumulator word for ``k`` lazy
    sums below ``order``, the narrowest of 8, 12 and 16 with ``(k + 1) *
    order < 2 ** (8 * stride)``; where not even 16 bytes have the headroom
    (the 128-bit orders of the catalogue), 16 with every add a modular one."""
    for stride in (8, 12, 16):
        if (k + 1) * order < 1 << (8 * stride):
            return stride, False
    return 16, True


def _grain(
    n: int, k: int, bpn: int, order: int, stride: int, threads: int
) -> tuple[int, int, int]:
    """``(threads, groups, segment)`` from the size of the work: a thread for
    every ``_MIN_KEYSTREAM_PER_THREAD`` of expected keystream, up to
    ``threads``; as many groups (seeds in flight, an accumulator each) as
    there are seeds, threads and room in ``_GROUP_ACC_BUDGET``; a segment
    that gives each thread of a group ``_SEGMENTS_PER_THREAD`` of a seed."""
    candidates = int(n / Fraction(order, 1 << (8 * bpn)))  # expected, a seed
    threads = max(1, min(threads, k * candidates * bpn // _MIN_KEYSTREAM_PER_THREAD))
    groups = max(1, min(k, threads, 1 + _GROUP_ACC_BUDGET // (n * stride)))
    sharing = -(-threads // groups)  # threads on one seed
    segment = candidates // (sharing * _SEGMENTS_PER_THREAD)
    return threads, groups, max(_MIN_SEGMENT, min(segment, _MAX_SEGMENT))


def derive_threads(k: int, length: int, order: int) -> int:
    """The threads ``derive_sum_vect`` gives the native pass for ``k`` seeds
    of ``length`` draws below ``order``, left to itself (what a reader of the
    derive's CPU seconds sets them against)."""
    bpn = limb_ops.draw_width_for(order)
    return _grain(length, k, bpn, order, accumulator_plan(order, k)[0], host_threads())[0]


def derive_sum_vect(
    seeds: list[bytes],
    offsets: list[int],
    length: int,
    order: int,
    *,
    threads: int | None = None,
    groups: int | None = None,
    segment: int | None = None,
) -> tuple[np.ndarray, list[int]] | None:
    """Sum mod ``order`` of ``length`` uniform draws from each seed's
    keystream, seed ``s`` read from byte ``offsets[s]``: ``(uint32[length,
    L], end cursors)`` from the native streaming pass, or ``None`` where it
    does not apply (no library, a draw over 16 bytes). ``threads``, ``groups``
    and ``segment`` default to what the input calls for; the result does not
    depend on them (the tests pin them to prove it)."""
    from ...utils import native

    lib = native.load()
    bpn = limb_ops.draw_width_for(order)
    if lib is None or bpn > 16:
        return None
    k = len(seeds)
    n_limbs = limb_ops.n_limbs_for_order(order)
    if length == 0:
        return np.zeros((0, n_limbs), dtype=np.uint32), list(offsets)
    stride, eager = accumulator_plan(order, k)
    auto = _grain(length, k, bpn, order, stride, host_threads())
    threads = auto[0] if threads is None else threads
    groups = min(auto[1], threads) if groups is None else groups
    segment = auto[2] if segment is None else segment
    out = np.zeros((length, n_limbs), dtype=np.uint32)
    # an accumulator word that is an element's limbs: sum in place
    acc = out if stride == 4 * n_limbs else np.zeros(length * stride, dtype=np.uint8)
    ends = np.zeros(k, dtype=np.uint64)
    rc = lib.xn_derive_sum(
        native.as_u8p(b"".join(seeds)),
        native.np_u64p(np.asarray(offsets, dtype=np.uint64)),
        k,
        length,
        native.as_u8p(order.to_bytes(bpn, "little")),
        bpn,
        stride,
        native.np_u8p(acc),
        int(eager),
        n_limbs,
        native.np_u32p(out),
        native.np_u64p(ends),
        threads,
        groups,
        segment,
    )
    if rc != 0:
        raise RuntimeError(f"xn_derive_sum refused its arguments (code {rc})")
    codec.count_fused("derive", k * length)
    return out, ends.tolist()


def _sum_vect_waves(samplers: list, length: int, order: int) -> np.ndarray:
    """The bounded wave: ``_WAVE`` masks at a time from the samplers (each
    past its unit draw), folded into the running sum and dropped. A sampler
    is taken out of ``samplers`` once it has drawn: the numpy sampler's
    cursor keeps the keystream it sliced its draws from alive."""
    ol = limb_ops.order_limbs_for(order)
    acc = np.zeros((length, limb_ops.n_limbs_for_order(order)), dtype=np.uint32)
    workers = min(_WAVE, len(samplers), host_threads())
    with ThreadPoolExecutor(max_workers=workers) as pool:
        for g0 in range(0, len(samplers), _WAVE):
            wave = samplers[g0 : g0 + _WAVE]
            samplers[g0 : g0 + _WAVE] = [None] * len(wave)
            stack = np.stack(list(pool.map(lambda s: s.draw_limbs(length, order), wave)))
            folded = limb_ops.fold_wire_batch_host(acc, stack, ol)
            if folded is None:  # no library: numpy's pairwise tree
                folded = limb_ops.mod_add(acc, limb_ops.batch_mod_sum(stack, ol), ol)
            acc = folded
    return acc


def derive_and_sum(
    seeds: list[bytes], length: int, config: MaskConfigPair
) -> tuple[np.ndarray, np.ndarray]:
    """``(unit limbs, vector limbs uint32[length, L])`` of the sum of the
    masks of ``seeds``: per seed one unit draw, then ``length`` vector draws
    from the same stream, as ``MaskSeed.derive_mask`` orders them."""
    if not seeds:
        raise ValueError("no seeds to aggregate")
    samplers = [StreamSampler(seed) for seed in seeds]
    unit_order, vect_order = config.unit.order, config.vect.order
    unit = sum(sampler.draw_int(unit_order) for sampler in samplers) % unit_order
    unit_limbs = limb_ops.int_to_limbs(unit, limb_ops.n_limbs_for_order(unit_order))
    fused = derive_sum_vect(
        seeds, [sampler.consumed_bytes for sampler in samplers], length, vect_order
    )
    if fused is not None:
        return unit_limbs, fused[0]
    return unit_limbs, _sum_vect_waves(samplers, length, vect_order)
