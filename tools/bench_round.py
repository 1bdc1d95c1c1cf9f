"""End-to-end update-phase benchmark: wire bytes -> global model.

Measures the full coordinator-side PET round hot path as one script, with a
per-leg wall-clock breakdown (VERDICT round-1 item 3):

  1. wire parse         — serialized masked-model bytes -> limb tensors
                          (thread-pool, like the REST ingest path)
  2. validate           — config/length/element-validity per update
                          (reference ordering: validate -> seed dict ->
                          aggregate, update.rs:119-152)
  3. seed-dict insert   — atomic conditional insert per update
  4. stage + fold       — accelerator: wire->planar, device_put, lazy-carry
                          fold into the sharded HBM accumulator (device work
                          overlaps the next batch's parse via async
                          dispatch); CPU: the host Aggregation path a
                          CPU-only coordinator runs (native wire fold)
  5. sum2 (participant) — ONE sum participant deriving + summing k2 masks
                          on device (the client-side hot loop)
  6. unmask + decode    — modular subtract + fixed-point decode -> float32

Usage:
  python tools/bench_round.py                    # scaled CPU smoke
  python tools/bench_round.py --updates 10000 --model-len 25000000  # TPU
Prints a human breakdown table, plus one JSON line (machine-readable tail).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--updates", type=int, default=None, help="total updates (default: scaled to platform)")
    ap.add_argument("--model-len", type=int, default=None)
    ap.add_argument("--batch", type=int, default=16, help="updates per staged batch")
    ap.add_argument("--sum2-seeds", type=int, default=None, help="seeds for the sum2 participant leg")
    ap.add_argument(
        "--mask-kernel",
        default=None,
        help="pin the sum2 mask derive+sum route (utils.kernels.MASK_KERNELS); "
        "default: masking_jax's auto-calibrated winner",
    )
    ap.add_argument("--cpu", action="store_true", help="force the CPU backend")
    ap.add_argument(
        "--overlap",
        action="store_true",
        help="phase-overlap round: speculatively derive the sum2 masks in a "
        "background worker DURING the update phase (ops.speculation, "
        "docs/DESIGN.md §22); the sum2 leg then settles (reconciliation "
        "only) and the hidden derive seconds come off the round wall",
    )
    ap.add_argument(
        "--calib-cache",
        default=None,
        metavar="PATH",
        help="persist/load kernel auto-calibration verdicts at PATH "
        "(utils.calibcache; XAYNET_CALIB_CACHE works too) — a warm run "
        "skips the fold/mask probe races entirely",
    )
    ap.add_argument(
        "--assert-flat-rss-mb",
        type=float,
        default=None,
        help="fail (exit 2) if RSS grows more than this many MB across the "
        "update phase — sustained-ingest proof for the north-star count "
        "(the per-update loop is unbounded by design, update.rs:119-152)",
    )
    ap.add_argument(
        "--history",
        action="store_true",
        help="append the JSON result line to BENCH_HISTORY.jsonl",
    )
    args = ap.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from xaynet_tpu.utils.jaxcache import enable_compile_cache

    enable_compile_cache()
    from xaynet_tpu.utils import calibcache

    if args.calib_cache:
        calibcache.configure(args.calib_cache)
    else:
        calibcache.configure_from_env()
    import numpy as np

    from xaynet_tpu.core.mask.config import BoundType, DataType, GroupType, MaskConfig, ModelType
    from xaynet_tpu.core.mask.encode import decode_vect_fast
    from xaynet_tpu.core.mask.object import MaskObject, MaskUnit, MaskVect
    from xaynet_tpu.core.mask.serialization import parse_mask_vect, serialize_mask_vect
    from xaynet_tpu.ops import limbs as host_limbs
    from xaynet_tpu.storage.memory import InMemoryCoordinatorStorage

    platform = jax.devices()[0].platform
    # XAYNET_BENCH_FORCE_DEVICE_PATH=1 drives the accelerator CODE PATH on
    # the virtual CPU mesh — the smoke that keeps the rare-TPU-window branch
    # continuously tested. It must not also flip the workload defaults to
    # TPU scale (that would make the "smoke" a multi-hour 25M run).
    real_tpu = platform != "cpu"
    device_forced = bool(os.environ.get("XAYNET_BENCH_FORCE_DEVICE_PATH"))
    on_tpu = real_tpu or device_forced
    model_len = args.model_len or (25_000_000 if real_tpu else 1_000_000)
    n_updates = args.updates or (10_000 if real_tpu else 96)
    k_batch = args.batch
    k_sum2 = args.sum2_seeds or (1_000 if real_tpu else 8)

    config = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)
    order = config.order
    n_limb = host_limbs.n_limbs_for_order(order)
    ol = host_limbs.order_limbs_for(order)

    # --- synthesize one batch of wire messages (reused; generation excluded
    # from timings) -------------------------------------------------------
    rng = np.random.default_rng(0)
    top = int(order >> (32 * (n_limb - 1)))
    batch_limbs = rng.integers(0, 1 << 32, size=(k_batch, model_len, n_limb), dtype=np.uint32)
    batch_limbs[:, :, n_limb - 1] = rng.integers(
        0, top, size=(k_batch, model_len), dtype=np.uint32
    )
    wire_msgs = [
        serialize_mask_vect(MaskVect(config, batch_limbs[i])) for i in range(k_batch)
    ]
    del batch_limbs

    if on_tpu:
        # the integrated wire-ingest path (aggregation.wire_ingest):
        # per-update device validation (one <=~175 MB transfer each, never a
        # multi-GB batch put) + chunked device flush, via the same
        # StagedAggregator the coordinator runs
        from xaynet_tpu.server.aggregation import StagedAggregator

        staged = StagedAggregator(
            config.pair(), model_len, device=True, batch_size=k_batch, kernel="auto"
        )
        agg_validate = staged.validate_aggregation
        agg_stage = staged.aggregate
        zero_unit_obj = MaskUnit.from_int(config, 0)

        class _WireAggregator:
            """Adapter keeping this script's acc/nb_models/unmask surface."""

            @property
            def acc(self):
                return staged._device.acc

            @property
            def nb_models(self):
                return staged.nb_models

            def unmask_limbs(self, mask_vect):
                return staged._device.unmask_limbs(mask_vect)

            def flush(self):
                # drain, not flush: this script reads .acc right after, so
                # the streaming pipeline must have fully folded the batch
                staged.drain()

        agg = _WireAggregator()
    else:
        # CPU smoke measures the path a CPU-only coordinator actually runs
        # ([aggregation] device=false default: Aggregation.aggregate_batch
        # -> native single-pass wire fold), mirroring the sum2 leg's
        # real-CPU-participant philosophy; the device path's transposes/
        # padding belong to the accelerator scenario only. Delegating (not
        # copying) keeps this timing honest if the coordinator path evolves.
        from xaynet_tpu.core.mask.masking import Aggregation

        class _HostAggregator:
            def __init__(self):
                self._agg = Aggregation(config.pair(), model_len)
                unit_l = host_limbs.n_limbs_for_order(config.pair().unit.order)
                self._zero_units = np.zeros((k_batch, unit_l), dtype=np.uint32)

            @property
            def acc(self):
                return self._agg.object.vect.data

            @property
            def nb_models(self):
                return self._agg.nb_models

            def add_batch(self, stack):
                self._agg.aggregate_batch(stack, self._zero_units[: stack.shape[0]])

            def unmask_limbs(self, mask_vect):
                return host_limbs.mod_sub(self.acc, mask_vect, ol)

        agg = _HostAggregator()
    store = InMemoryCoordinatorStorage()
    sum_pks = [bytes([i + 1]) * 32 for i in range(3)]

    async def _seed_store():
        for i, pk in enumerate(sum_pks):
            await store.add_sum_participant(pk, bytes([i + 9]) * 32)

    import asyncio

    asyncio.run(_seed_store())

    def _rss_mb() -> float:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1024.0
        return 0.0

    # --- speculative sum2 derive (--overlap, docs/DESIGN.md §22): the mask
    # seeds are known at the sum→update transition (the sum dictionary is
    # sealed), so a background worker derives + folds them WHILE the
    # update-phase folds below run — the sum2 leg then settles to
    # reconciliation only and the derive seconds are hidden under the
    # update wall instead of extending the round
    from xaynet_tpu.ops import masking_jax

    seeds = [bytes([i & 0xFF, i >> 8]) + b"\x33" * 30 for i in range(k_sum2)]
    spec = None
    if args.overlap:
        from xaynet_tpu.ops.speculation import SpeculativeMaskSession

        if (args.mask_kernel or "auto") == "auto":
            # resolve the route BEFORE offering: the probe race is a
            # one-time process cost, not speculation work to hide
            masking_jax.calibrate_mask_kernel(seeds, model_len, config.pair())
        spec = SpeculativeMaskSession(model_len, config.pair(), kernel=args.mask_kernel)
        spec.offer(seeds)

    stage_label = "stage + fold (device)" if on_tpu else "stage + fold (host)"
    t_parse = t_validate = t_seed = t_stage = 0.0
    pool = ThreadPoolExecutor(max_workers=max(2, (os.cpu_count() or 2)))
    rss_start = _rss_mb()
    rss_peak = rss_start
    t_total0 = time.perf_counter()

    if n_updates < k_batch:
        ap.error(f"--updates ({n_updates}) must be >= --batch ({k_batch})")
    n_batches = round(n_updates / k_batch)  # nearest whole batch, >= 1
    if n_batches * k_batch != n_updates:
        print(
            f"note: rounding {n_updates} updates to {n_batches * k_batch} "
            f"(whole {k_batch}-update batches)",
            file=sys.stderr,
        )
    seed_entry = {pk: b"\x07" * 80 for pk in sum_pks}
    for b in range(n_batches):
        if on_tpu:
            # device ingest, the integrated coordinator path: the LAZY parse
            # keeps the raw element block (header checks + zero-copy view),
            # then per-update device unpack + validity runs in the validate
            # leg — exactly [aggregation] wire_ingest = true
            t0 = time.perf_counter()
            lazy_objs = [
                MaskObject(parse_mask_vect(w, lazy=True)[0], zero_unit_obj) for w in wire_msgs
            ]
            t_parse += time.perf_counter() - t0

            t0 = time.perf_counter()
            for obj in lazy_objs:
                agg_validate(obj)  # device transfer + unpack + validity
            t_validate += time.perf_counter() - t0
            parsed = None
        else:
            # 1. wire parse on the thread pool
            t0 = time.perf_counter()
            parsed = list(pool.map(lambda w: parse_mask_vect(w)[0], wire_msgs))
            t_parse += time.perf_counter() - t0

            # 2. validate (is_valid is part of parse; re-assert config +
            # length, the validate_aggregation ordering of update.rs:119-152)
            t0 = time.perf_counter()
            for v in parsed:
                assert v.config == config and len(v) == model_len
            t_validate += time.perf_counter() - t0

        async def _inserts(base, accepted):
            for i in range(k_batch):
                if not accepted[i]:
                    continue
                pk = (b"%16d" % (base + i)).ljust(32, b"u")
                err = await store.add_local_seed_dict(pk, dict(seed_entry))
                assert err is None, err

        if on_tpu:
            # validate (device) already ran above, preserving the reference's
            # validate -> seed-dict -> aggregate ordering (update.rs:119-152)
            t0 = time.perf_counter()
            asyncio.run(_inserts(b * k_batch, [True] * k_batch))
            t_seed += time.perf_counter() - t0

            t0 = time.perf_counter()
            for obj in lazy_objs:
                agg_stage(obj)  # stages the cached device planar; flushes per batch
            t_stage += time.perf_counter() - t0
        else:
            # 3. seed-dict conditional insert per update
            t0 = time.perf_counter()
            asyncio.run(_inserts(b * k_batch, [True] * k_batch))
            t_seed += time.perf_counter() - t0

            # 4. stage + fold (device dispatch is async: the fold of batch b
            # overlaps the parse of batch b+1)
            t0 = time.perf_counter()
            stack = np.stack([v.data for v in parsed])
            agg.add_batch(stack)
            t_stage += time.perf_counter() - t0
        if b == 2:
            # steady-state baseline: the first batches pay one-time costs
            # (thread-pool arenas, parse buffers, kernel warmup) that are
            # not per-update growth
            rss_warm = _rss_mb()
        if b % 50 == 0 or b == n_batches - 1:
            rss_peak = max(rss_peak, _rss_mb())

    if on_tpu:
        agg.flush()  # remainder batch through the same chunked device path
    jax.block_until_ready(agg.acc)
    t_update_phase = time.perf_counter() - t_total0
    rss_end = _rss_mb()
    if n_batches <= 2:
        rss_warm = rss_end
    rss_peak = max(rss_peak, rss_end)
    agg_kernel_used = staged.kernel_used if on_tpu else "host"

    # 5. sum2 participant leg: derive + sum k_sum2 masks through the
    # PRODUCTION promoted pipeline (state_machine.py device_sum2 ->
    # masking_jax.sum_masks): every route batches the derivations in-graph
    # (or fuses them in the Pallas kernel) and streams the mask planes
    # through the shard pipeline — the chunked per-seed StreamSampler loop
    # this leg used to run stopped being representative of production when
    # the fused mask pipeline landed.
    speculated = 0
    if spec is not None:
        # overlap round: everything the worker folded during the update
        # phase is a hit; settle() reconciles (misses derive on demand,
        # discards subtract back out) — byte-identical to sum_masks
        t0 = time.perf_counter()
        speculated = spec.speculated()
        _, mask_acc = spec.settle(seeds)
        t_sum2 = time.perf_counter() - t0
    else:
        if (args.mask_kernel or "auto") == "auto":
            # resolve the route BEFORE the wall: the probe race is a one-time
            # process cost a long-running participant amortizes across rounds
            masking_jax.calibrate_mask_kernel(seeds, model_len, config.pair())
        t0 = time.perf_counter()
        _, mask_acc = masking_jax.sum_masks(
            seeds, model_len, config.pair(), kernel=args.mask_kernel
        )
        jax.block_until_ready(mask_acc)
        t_sum2 = time.perf_counter() - t0
    mask_kernel_used = masking_jax.resolved_mask_kernel() or "unknown"

    # 6. unmask + fixed-point decode to float
    t0 = time.perf_counter()
    unmasked_wire = agg.unmask_limbs(np.asarray(mask_acc))
    from fractions import Fraction

    out = decode_vect_fast(unmasked_wire, config, agg.nb_models, Fraction(agg.nb_models))
    t_unmask = time.perf_counter() - t0
    assert out.shape == (model_len,)

    total = t_update_phase + t_sum2 + t_unmask
    ups = (n_batches * k_batch) / t_update_phase

    overlap_info = None
    if spec is not None:
        from xaynet_tpu.telemetry.timeline import drain_overlap_window

        entries = drain_overlap_window()
        spec_entries = [e for e in entries if e.get("kind") == "spec_derive"]
        hidden_s = sum(e["seconds"] for e in spec_entries)
        tail = spec_entries[-1] if spec_entries else {}
        overlap_info = {
            "speculated": speculated,
            "hidden_derive_s": round(hidden_s, 3),
            "hits": int(tail.get("hits", 0)),
            "misses": int(tail.get("misses", 0)),
            "discards": int(tail.get("discards", 0)),
        }

    rows = [
        ("wire parse (thread pool)", t_parse),
        ("validate", t_validate),
        ("seed-dict inserts", t_seed),
        (stage_label, t_stage),
        ("update phase wall", t_update_phase),
        (f"sum2 mask derive+sum ({k_sum2} seeds)", t_sum2),
        ("unmask + decode", t_unmask),
        ("TOTAL", total),
    ]
    print(f"# E2E round bench: platform={platform} model_len={model_len} "
          f"updates={n_batches * k_batch} batch={k_batch}", file=sys.stderr)
    for name, t in rows:
        print(f"  {name:<38} {t:8.2f}s", file=sys.stderr)
    print(f"  update-phase throughput: {ups:.1f} updates/s", file=sys.stderr)
    if overlap_info is not None:
        print(
            "  overlap: {h}/{n} seeds speculated during the update phase "
            "({s:.2f}s of derive hidden; {hit} hit / {miss} miss / "
            "{disc} discard)".format(
                h=overlap_info["speculated"],
                n=k_sum2,
                s=overlap_info["hidden_derive_s"],
                hit=overlap_info["hits"],
                miss=overlap_info["misses"],
                disc=overlap_info["discards"],
            ),
            file=sys.stderr,
        )
    rss_growth = rss_end - rss_warm
    print(
        f"  RSS start/warm/peak/end: {rss_start:.1f}/{rss_warm:.1f}/{rss_peak:.1f}/"
        f"{rss_end:.1f} MB (steady-state growth {rss_growth:+.1f} MB over "
        f"{n_batches * k_batch} updates, seed dict {n_batches * k_batch} entries)",
        file=sys.stderr,
    )

    # series identity for the regression gate: (metric, kernel, mesh,
    # threads) — a kernel or mesh change starts a NEW series instead of
    # reading as a regression (the BENCH_r05 lesson)
    mesh_size = len(jax.devices())
    native_threads = os.environ.get("XAYNET_NATIVE_THREADS")
    common = {
        "platform": platform,
        # a forced smoke measured the DEVICE branch on cpu — never mix it
        # with genuine cpu-coordinator baselines in history comparisons
        **({"device_path_forced": True} if device_forced else {}),
        **({"native_threads": int(native_threads)} if native_threads else {}),
        "model_len": model_len,
        "mesh": mesh_size,
        # host core count: the gate splits every series on it — a 1-cpu
        # box re-measuring a 4-cpu record is a different experiment
        "cpus": os.cpu_count(),
    }
    # the sum2 + unmask walls as their own gated families (higher-is-better
    # element rates, so the gate's best-prior floor logic applies unchanged;
    # the raw walls ride along for humans)
    # the workload shape rides in the METRIC NAME (the fold headline's
    # "@25M params" variant idiom): a 1M smoke and a 25M run are different
    # series, not a regression of one another
    extra_records = [
        # with --overlap the sum2 leg wall is RECONCILIATION time (the
        # derive ran speculatively under the update phase), so a
        # model_len/t_sum2 "throughput" would be a nonsense record future
        # serial runs regress against — the derive cost lives in the
        # round-wall record's overlap section instead
        *(
            []
            if overlap_info
            else [
                {
                    "metric": (
                        f"e2e sum2 mask throughput @{model_len} params "
                        f"({k_sum2} seeds)"
                    ),
                    "value": round(k_sum2 * model_len / max(t_sum2, 1e-9), 2),
                    "unit": "elements/s",
                    "kernel": mask_kernel_used,
                    "seeds": k_sum2,
                    "wall_s": round(t_sum2, 3),
                    **common,
                }
            ]
        ),
        {
            "metric": f"e2e unmask throughput @{model_len} params",
            "value": round(model_len / max(t_unmask, 1e-9), 2),
            "unit": "elements/s",
            "kernel": agg_kernel_used,
            "wall_s": round(t_unmask, 3),
            **common,
        },
        # the operator headline (docs/DESIGN.md §20): end-to-end round wall
        # — update phase + sum2 + unmask, the same bracket the always-on
        # timeline fold reports in production. LOWER is better: the gate
        # inverts its floor for the s/round unit (the §17 bytes idiom)
        {
            "metric": f"round wall @{model_len} params",
            "value": round(total, 3),
            "unit": "s/round",
            "kernel": agg_kernel_used,
            "updates": n_batches * k_batch,
            # overlap rides ALONG the series (not in the gate's config
            # fingerprint): an overlapped round is the same experiment
            # measured with the engines on, and a lower wall is the win
            **({"overlap": overlap_info} if overlap_info else {}),
            **common,
        },
    ]
    result = {
        "metric": "e2e update-phase throughput",
        "value": round(ups, 2),
        "unit": "updates/s",
        "kernel": agg_kernel_used,
        **common,
        "updates": n_batches * k_batch,
        "breakdown_s": {name: round(t, 3) for name, t in rows},
        "rss_mb": {
            "start": round(rss_start, 1),
            "warm": round(rss_warm, 1),
            "peak": round(rss_peak, 1),
            "end": round(rss_end, 1),
        },
    }
    for rec in extra_records:
        print(json.dumps(rec))
    print(json.dumps(result))  # the machine-readable tail stays LAST
    if args.history:
        hist = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "BENCH_HISTORY.jsonl"
        )
        with open(hist, "a") as f:
            for rec in (*extra_records, result):
                f.write(
                    json.dumps({"ts": round(time.time(), 3), "source": "bench_round", **rec})
                    + "\n"
                )
    if args.assert_flat_rss_mb is not None and rss_growth > args.assert_flat_rss_mb:
        print(
            f"RSS NOT FLAT: grew {rss_growth:.1f} MB > allowed {args.assert_flat_rss_mb} MB",
            file=sys.stderr,
        )
        sys.exit(2)


if __name__ == "__main__":
    main()
