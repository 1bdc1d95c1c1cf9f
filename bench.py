"""Headline benchmark: masked-update aggregation throughput @ 25M params.

North star (BASELINE.json): aggregate 10k masked 25M-parameter updates in
< 60 s on TPU — i.e. >= 166.7 updates/s. The reference aggregates with a
sequential per-update big-int loop on one CPU core
(rust/xaynet-core/src/mask/masking.rs:292-316); here updates are planar
uint32 limb tensors folded into an HBM-resident accumulator with the
single-pass lazy-carry kernel (xaynet_tpu/ops/fold_jax.py).

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "updates/s", "vs_baseline": N}
``vs_baseline`` is the speedup over the 166.7 updates/s target.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _sync(x) -> None:
    # device->host fetch: reliable completion barrier on every backend
    np.asarray(x[:1, :8])


def main() -> None:
    # Pin the native fold thread config BEFORE anything touches the kernel,
    # and RECORD it in the headline JSON: BENCH_r05 re-measured 29.46
    # updates/s where r03 recorded ~49 on the same code path purely because
    # the implicit 2x-cores default resolved differently across container
    # migrations — a pinned, recorded config makes same-series comparisons
    # meaningful and lets bench_gate treat a config change as a NEW series.
    default_threads = str(min(16, 2 * (os.cpu_count() or 1)))
    os.environ.setdefault("XAYNET_NATIVE_THREADS", default_threads)
    # per-shard budget for the mesh fold legs: the full budget per shard
    # (measured faster than a split budget on cgroup-limited CPUs — the
    # oversubscription hides per-thread DRAM stalls, same rationale as the
    # 2x-cores default inside the kernel)
    os.environ.setdefault(
        "XAYNET_NATIVE_SHARD_THREADS", os.environ["XAYNET_NATIVE_THREADS"]
    )
    native_threads = int(os.environ["XAYNET_NATIVE_THREADS"])
    shard_threads = int(os.environ["XAYNET_NATIVE_SHARD_THREADS"])

    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        # the explicit CPU run measures the multi-device story on a virtual
        # mesh: force 8 host devices before jax initializes so the mesh=8
        # shard-parallel leg below has real (if virtual) devices to shard
        # over (the single-device headline keeps using device 0 only)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    import jax
    import jax.numpy as jnp

    from xaynet_tpu.utils.jaxcache import enable_compile_cache

    enable_compile_cache()
    # a measurement path that finds no chip fails: the CPU legs below run
    # only when the operator NAMED cpu in JAX_PLATFORMS
    if jax.default_backend() == "cpu" and os.environ.get("JAX_PLATFORMS", "") != "cpu":
        sys.exit(
            "bench.py: no accelerator found and JAX_PLATFORMS does not name "
            "cpu; refusing to benchmark XLA:CPU under a device headline"
        )

    from xaynet_tpu.core.mask.config import BoundType, DataType, GroupType, MaskConfig, ModelType
    from xaynet_tpu.ops import limbs as host_limbs
    from xaynet_tpu.ops.fold_jax import fold_planar_batch

    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"
    # M6 allows up to 1e6 aggregated models (covers the 10k target)
    config = MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)
    n_limb = host_limbs.n_limbs_for_order(config.order)
    order = config.order

    if on_tpu:
        model_len, k, n_batches = 25_000_000, 16, 24
    else:
        # CPU fallback: measure the REAL 25M-param case when the host has
        # room for it (stack is k*n_limb*25M*4B twice: numpy + jax copies),
        # so the headline number needs no "scaled from a smaller model"
        # caveat; only tiny machines drop to the scaled 1M smoke.
        try:
            with open("/proc/meminfo") as f:
                avail_kb = next(
                    int(line.split()[1]) for line in f if line.startswith("MemAvailable:")
                )
        except (OSError, StopIteration):
            # non-Linux hosts: estimate from total physical pages rather
            # than silently dropping a well-provisioned box to the scaled
            # 1M smoke (ADVICE r3)
            try:
                avail_kb = (
                    os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE") // 1024 // 2
                )
            except (ValueError, OSError, AttributeError):
                avail_kb = 0
                print("meminfo unavailable; falling back to scaled smoke", file=sys.stderr)
        if avail_kb >= 16 * 1024 * 1024:
            # k=16 amortizes the accumulator read/write against the
            # mandatory one-read-of-the-batch (measured +10% vs k=8)
            model_len, k, n_batches = 25_000_000, 16, 3
        else:
            model_len, k, n_batches = 1_000_000, 8, 4
    warmup = 2

    # Synthesize K masked updates host-side in the planar device layout
    # (uniform group elements are exactly what masked updates look like).
    rng = np.random.default_rng(0)
    host_stack = rng.integers(0, 2**32, size=(k, n_limb, model_len), dtype=np.uint32)
    host_stack[:, n_limb - 1, :] &= np.uint32((1 << 20) - 1)
    if on_tpu:
        # transfer per-update slices (~200 MB each @25M), never one multi-GB
        # device_put
        slices = []
        for i in range(k):
            s = jax.device_put(host_stack[i])
            jax.block_until_ready(s)
            slices.append(s)
            print(f"staged update {i + 1}/{k}", file=sys.stderr)
        stack = jnp.stack(slices)
        jax.block_until_ready(stack)
        del slices
    else:
        # local CPU device: one copy, no RPC to protect against (the 16 GB
        # gate above is sized for exactly numpy + jax copies of the stack)
        stack = jax.device_put(host_stack)
        host_stack_np = host_stack  # the native candidate reads it directly
    del host_stack

    # candidate kernels: XLA fold, (on real accelerators) the Pallas fold at
    # several tile sizes, and (on CPU) the native single-pass u64 fold;
    # calibrate quickly and measure with the fastest. Each candidate carries
    # its own initial-accumulator factory so host kernels run on numpy.
    def _zero_acc_jax():
        return jnp.zeros((n_limb, model_len), dtype=jnp.uint32)

    candidates = {"xla": (lambda a, s: fold_planar_batch(a, s, order), _zero_acc_jax)}
    if on_tpu:
        try:
            from xaynet_tpu.ops.fold_pallas import fold_planar_batch_pallas

            for tile in (1024, 2048, 4096, 8192):

                def _pallas(a, s, _t=tile):
                    return fold_planar_batch_pallas(a, s, order, tile_size=_t)

                candidates[f"pallas-t{tile}"] = (_pallas, _zero_acc_jax)
        except Exception:
            pass
    else:
        from xaynet_tpu.utils import native as native_lib

        order_limbs = host_limbs.order_limbs_for(order)
        _native_spare = {"buf": None}

        def _native(a, s):
            # ping-pong the result buffer: a fresh 200 MB np.empty per fold
            # costs ~0.15 s of page faults — the dropped accumulator becomes
            # the next spare (same trick as the aggregator's native kernel)
            out = host_limbs.fold_planar_batch_host(
                a, host_stack_np, order_limbs, out=_native_spare["buf"]
            )
            reusable = out is not a and isinstance(a, np.ndarray) and a.flags.writeable
            _native_spare["buf"] = a if reusable else None
            return out

        def _zero_acc_np():
            return np.zeros((n_limb, model_len), dtype=np.uint32)

        # only register when the C kernel is actually loadable — the label
        # in the headline JSON must never claim 'native' for a numpy run
        if native_lib.load() is not None:
            candidates["native-u64"] = (_native, _zero_acc_np)

    def calibrate(fn, make_acc):
        acc = make_acc()
        acc = fn(acc, stack)  # compile
        _sync(acc)
        t0 = time.perf_counter()
        for _ in range(2):
            acc = fn(acc, stack)
        _sync(acc)
        return time.perf_counter() - t0

    timings = {}
    for name, (fn, make_acc) in candidates.items():
        try:
            timings[name] = calibrate(fn, make_acc)
        except Exception as e:  # a kernel variant failing must not sink the bench
            print(f"kernel {name} unavailable: {type(e).__name__}: {e}", file=sys.stderr)
    best = min(timings, key=timings.get)
    fold, make_acc = candidates[best]
    print(f"kernel selection: {timings} -> {best}", file=sys.stderr)

    acc = make_acc()
    acc = fold(acc, stack)  # compile against the zeroed accumulator shape
    _sync(acc)

    for _ in range(warmup):
        acc = fold(acc, stack)
    _sync(acc)

    # median of >=3 repetitions with min/max spread (VERDICT r04 weak 1):
    # the r4 headline (26.4) sat 17% under a same-code mid-round draw (30.8)
    # purely from shared-container noise — one draw is not defensible. CPU
    # reps are ~1s each, so take 5 there (two bad draws can no longer drag
    # the median); TPU reps stay at 3
    reps = 3 if on_tpu else 5
    rep_ups = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(n_batches):
            acc = fold(acc, stack)
        _sync(acc)
        dt = time.perf_counter() - t0
        rep_ups.append(k * n_batches / dt)
    ups = float(np.median(rep_ups))

    # --- mesh=8 shard-parallel fold headline (CPU fallback) ---------------
    # The SAME fold-only measurement as the single-device headline above
    # (pre-staged batch, repeated folds, no staging in the timed loop), but
    # through the production multi-device path: a ShardedAggregator over
    # every virtual device, kernel=auto racing mesh-XLA against the
    # per-shard native fold (one concurrent strided kernel call per shard
    # under the pinned per-shard thread budget). ROADMAP item 1's exit
    # criterion: this number must beat the best single-device native-u64
    # headline in BENCH_HISTORY.
    mesh8 = None
    n_dev = len(jax.devices())
    if not on_tpu and n_dev > 1:
        try:
            del acc, stack  # free the single-device copies first
            from xaynet_tpu.parallel.aggregator import ShardedAggregator
            from xaynet_tpu.parallel.mesh import make_mesh

            agg8 = ShardedAggregator(config, model_len, mesh=make_mesh(), kernel="auto")
            staged8 = jax.device_put(host_stack_np, agg8._batch_sharding)
            agg8.add_planar_batch(staged8)  # resolve (XLA vs per-shard native) + warm
            if agg8.kernel_used == "native-u64":
                # the host kernel reads the host batch in place — the
                # device copy only existed for the calibration race
                batch8 = host_stack_np
                del staged8
            else:
                batch8 = staged8
            agg8.add_planar_batch(batch8)
            _sync(np.asarray(agg8.acc))
            m_ups = []
            for _ in range(reps):
                t0 = time.perf_counter()
                for _ in range(n_batches):
                    agg8.add_planar_batch(batch8)
                _sync(np.asarray(agg8.acc))
                m_ups.append(k * n_batches / (time.perf_counter() - t0))
            mesh8 = {
                "value_raw": float(np.median(m_ups)),
                "mesh": n_dev,
                "kernel": agg8.kernel_used,
                "min_raw": float(min(m_ups)),
                "max_raw": float(max(m_ups)),
                "median_of": reps,
            }
            print(
                f"mesh={n_dev} shard-parallel fold: "
                f"{mesh8['value_raw']:.2f} updates/s "
                f"(kernel {agg8.kernel_used}, shard_threads {shard_threads}) "
                f"vs single-device {ups:.2f}",
                file=sys.stderr,
            )
            del agg8, batch8
        except Exception as e:  # the mesh leg must never sink the headline
            print(f"mesh8 leg unavailable: {type(e).__name__}: {e}", file=sys.stderr)
    # streaming vs sync: the SAME staged-per-batch aggregation through the
    # production ShardedAggregator — sequential add_batch (stage then fold,
    # serialized) vs the streaming pipeline (ring-buffer staging of batch
    # N+1 overlapping the fold of batch N). The headline above measures the
    # bare fold; this field tracks what the pipeline overlap buys on the
    # full stage+fold path. CPU-only: the TPU capture path never holds a
    # host-side wire copy of the stack (per-slice staging).
    streaming_vs_sync = None
    bytes_per_fold = None
    if not on_tpu:
        try:
            # the comparison runs at half the headline batch so its extra
            # footprint (wire copy + 2 ring buffers + a second aggregator,
            # ~3x one half-batch) stays well inside the remaining headroom;
            # a cgroup OOM kill here would lose the headline JSON entirely,
            # which a try/except cannot catch — so gate on CURRENT
            # MemAvailable and skip rather than gamble
            k_s = max(2, k // 2)
            extra_kb = int(3.5 * k_s * n_limb * model_len * 4) // 1024
            try:
                with open("/proc/meminfo") as f:
                    avail_now_kb = next(
                        int(line.split()[1])
                        for line in f
                        if line.startswith("MemAvailable:")
                    )
            except (OSError, StopIteration):
                avail_now_kb = extra_kb * 2  # no meminfo: proceed (tiny smoke)
            if avail_now_kb < extra_kb * 2:
                raise MemoryError(
                    f"skipping: {avail_now_kb // 1024} MB available, "
                    f"comparison needs ~{extra_kb // 1024} MB"
                )
            from xaynet_tpu.parallel.aggregator import ShardedAggregator
            from xaynet_tpu.parallel.streaming import StreamingAggregator

            wire_stack = np.ascontiguousarray(host_stack_np[:k_s].transpose(0, 2, 1))
            b_batches = 3
            seq = ShardedAggregator(config, model_len, kernel="auto")
            seq.add_batch(wire_stack)  # resolve kernel + warm
            t0 = time.perf_counter()
            for _ in range(b_batches):
                seq.add_batch(wire_stack)
            _sync(np.asarray(seq.acc))
            t_sync = time.perf_counter() - t0
            stream_agg = ShardedAggregator(config, model_len, kernel=seq.kernel_used)
            stream = StreamingAggregator(
                stream_agg, staging_buffers=2, dispatch_ahead=2, max_batch=k_s
            )
            stream.submit_batch(wire_stack)
            stream.drain()  # warm (kernel resolve + ring page-in)
            t0 = time.perf_counter()
            for _ in range(b_batches):
                stream.submit_batch(wire_stack)
            stream.drain()
            t_stream = time.perf_counter() - t0
            stream.close()
            streaming_vs_sync = round(t_sync / t_stream, 3)
            print(
                f"streaming_vs_sync: sync {t_sync:.2f}s vs streaming {t_stream:.2f}s "
                f"-> {streaming_vs_sync}x (kernel {seq.kernel_used}, k={k_s}, "
                f"mesh={len(jax.devices())})",
                file=sys.stderr,
            )
            # --- bytes moved per fold: packed vs unpacked staging ---------
            # The packed-reduction exit metric (ROADMAP item 3): drive the
            # SAME wire batch through the production streaming pipeline with
            # packed staging on and off, and read the telemetry byte
            # counters (staging copies + cross-shard combine traffic) the
            # pipeline itself maintains. Lower is better; bench_gate.py
            # gates this family with inverted floor logic.
            from xaynet_tpu.parallel.aggregator import BYTES_REDUCED
            from xaynet_tpu.parallel.streaming import BYTES_STAGED

            def _bytes_sample():
                staged = sum(
                    BYTES_STAGED.labels(layout=lay).value
                    for lay in ("packed", "unpacked", "wire")
                )
                reduced = sum(
                    BYTES_REDUCED.labels(path=p).value for p in ("scatter", "gather")
                )
                return staged + reduced

            bytes_per_fold = {}
            for packed_mode in (False, True):
                bagg = ShardedAggregator(config, model_len, kernel=seq.kernel_used)
                bstream = StreamingAggregator(
                    bagg, staging_buffers=2, dispatch_ahead=2, max_batch=k_s,
                    packed=packed_mode,
                )
                bstream.submit_batch(wire_stack)
                bstream.drain()  # warm
                before = _bytes_sample()
                for _ in range(b_batches):
                    bstream.submit_batch(wire_stack)
                bstream.drain()
                bagg.snapshot()  # the final model download (gather leg)
                moved = _bytes_sample() - before
                bstream.close()
                bytes_per_fold["packed" if packed_mode else "unpacked"] = int(
                    moved / b_batches
                )
                bytes_per_fold["kernel"] = bagg.kernel_used
                del bagg, bstream
            print(
                f"bytes moved per fold (k={k_s}): "
                f"unpacked {bytes_per_fold['unpacked']:,} vs packed "
                f"{bytes_per_fold['packed']:,} "
                f"({1 - bytes_per_fold['packed'] / max(1, bytes_per_fold['unpacked']):.1%} saved)",
                file=sys.stderr,
            )
            del wire_stack
        except Exception as e:  # diagnostics must never sink the headline
            print(f"streaming_vs_sync unavailable: {type(e).__name__}: {e}", file=sys.stderr)

    # --- multi-tenant interleaved fold (2 tenants, one mesh) --------------
    # Two tenants with DIFFERENT model sizes fold concurrently through the
    # production streaming pipelines over the shared paged accumulator pool
    # and the tenant fold-batch scheduler (docs/DESIGN.md §19): tenant A at
    # the full 25M headline size, tenant B at a quarter of it. The headline
    # is combined 25M-equivalent updates/s (tenant B's updates scaled by
    # its length fraction); the scheduler's fairness split is recorded next
    # to it so a starved tenant is visible in the history, and both
    # tenants' pool leases must balance at the end (zero leaks).
    multi_tenant = None
    if not on_tpu:
        try:
            import threading as _threading

            from xaynet_tpu.parallel.aggregator import ShardedAggregator
            from xaynet_tpu.parallel.streaming import StreamingAggregator
            from xaynet_tpu.tenancy import get_pool, get_scheduler

            k_mt, b_mt = max(2, k // 2), 3
            len_b = model_len // 4
            wire_a = np.ascontiguousarray(host_stack_np[:k_mt].transpose(0, 2, 1))
            wire_b = np.ascontiguousarray(
                host_stack_np[:k_mt, :, :len_b].transpose(0, 2, 1)
            )
            sched = get_scheduler()
            streams = {}
            for tenant, (mlen, wire) in {
                "bench-a": (model_len, wire_a),
                "bench-b": (len_b, wire_b),
            }.items():
                agg_t = ShardedAggregator(config, mlen, kernel="auto")
                streams[tenant] = (
                    agg_t,
                    StreamingAggregator(
                        agg_t, staging_buffers=2, dispatch_ahead=2,
                        max_batch=k_mt, tenant=tenant,
                    ),
                    wire,
                )
                streams[tenant][1].submit_batch(wire)  # resolve + warm
                streams[tenant][1].drain()
            # capture AFTER the warm-up drains: the recorded fairness split
            # must cover exactly the measured window's grants
            split_before = sched.split()
            walls = {}

            def run_tenant(tenant: str) -> None:
                _agg, stream, wire = streams[tenant]
                t0 = time.perf_counter()
                for _ in range(b_mt):
                    stream.submit_batch(wire)
                stream.drain()
                walls[tenant] = time.perf_counter() - t0

            t0 = time.perf_counter()
            threads = [
                _threading.Thread(target=run_tenant, args=(t,)) for t in streams
            ]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            wall = time.perf_counter() - t0
            equivalent = (
                k_mt * b_mt  # tenant A at the reference 25M size
                + k_mt * b_mt * (len_b / model_len)  # tenant B, scaled
            ) / wall
            split_after = sched.split()
            fairness = {
                t: split_after.get(t, 0) - split_before.get(t, 0)
                for t in streams
            }
            kernel_mt = streams["bench-a"][0].kernel_used
            pool = get_pool()
            for tenant, (agg_t, stream, _wire) in streams.items():
                stream.close()
                agg_t.release_plan_pages()
                assert pool.balanced(tenant), f"{tenant} leaked pool leases"
            multi_tenant = {
                "value_raw": equivalent,
                "tenants": 2,
                "model_lens": [model_len, len_b],
                "kernel": kernel_mt,
                "mesh": len(jax.devices()),
                "fairness": fairness,
                "walls_s": {t: round(w, 2) for t, w in walls.items()},
            }
            print(
                f"multi-tenant interleaved fold: {equivalent:.2f} equivalent "
                f"updates/s over {wall:.2f}s (25M + {len_b / 1e6:.1f}M params, "
                f"kernel {kernel_mt}, fairness {fairness})",
                file=sys.stderr,
            )
            del streams, wire_a, wire_b
        except Exception as e:  # the tenancy leg must never sink the headline
            print(f"multi-tenant leg unavailable: {type(e).__name__}: {e}", file=sys.stderr)

    # --- sim headline: whole federated rounds as ONE jitted program -------
    # A genuinely different workload from the fold headline above: per-
    # participant ChaCha mask derivation + masked-model generation +
    # aggregation + sum-mask reconstruction + unmask, all in-graph
    # (xaynet_tpu/sim/, DESIGN §13), measured end-to-end (host fixed-point
    # encode/decode included) in simulated participants per second. The
    # series identity is (model size, participants, block, mesh) — a
    # population-shape change starts a NEW series for tools/bench_gate.py.
    sim_out = None
    try:
        from fractions import Fraction

        from xaynet_tpu.parallel.mesh import make_mesh
        from xaynet_tpu.sim import SimRound, SimSpec, seeds_for

        sim_len, sim_p, sim_block = 1000, 2048, 256
        sim_cfg = config.pair()
        sim_seeds = seeds_for(sim_p, root=42)
        sim_rng = np.random.default_rng(42)
        sim_weights = sim_rng.uniform(-1, 1, (sim_p, sim_len)).astype(np.float32)
        sim_scalar = Fraction(1, sim_p)
        sim_legs = {}
        meshes = {1: None}
        if n_dev > 1:
            # unlike the mesh8 FOLD leg (deliberately CPU-only: its point
            # is the virtual-mesh production path), the sim mesh leg runs
            # on real accelerators too — that is the only place the
            # participant-axis sharding story produces a meaningful number
            meshes[n_dev] = make_mesh()
        for mesh_size, mesh in meshes.items():
            simr = SimRound(SimSpec(sim_cfg, sim_len, block_size=sim_block), mesh=mesh)
            simr.run(sim_seeds, sim_weights, scalar=sim_scalar)  # compile + warm
            pps = []
            for _ in range(3):
                t0 = time.perf_counter()
                simr.run(sim_seeds, sim_weights, scalar=sim_scalar)
                pps.append(sim_p / (time.perf_counter() - t0))
            sim_legs[mesh_size] = {
                "value": round(float(np.median(pps)), 2),
                "unit": "participants/s",
                "model_len": sim_len,
                "participants": sim_p,
                "block": sim_block,
                "mesh": mesh_size,
                "spread": {
                    "median_of": 3,
                    "min": round(min(pps), 2),
                    "max": round(max(pps), 2),
                },
            }
            print(
                f"sim round (mesh={mesh_size}): {sim_legs[mesh_size]['value']:.2f} "
                f"participants/s @n={sim_len} P={sim_p} block={sim_block}",
                file=sys.stderr,
            )
        sim_out = sim_legs
        # the sim series appends to BENCH_HISTORY.jsonl directly (same
        # contract as the mesh8 fold series: the driver only captures the
        # single fold-headline JSON line)
        try:
            hist = os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "BENCH_HISTORY.jsonl"
            )
            # the gate follows the LATEST record's series per family: append
            # the single-device leg last so the default sim gate tracks the
            # leg that is meaningful on every host (the mesh leg only says
            # something on real devices)
            with open(hist, "a") as f:
                for mesh_size, leg in sorted(sim_legs.items(), reverse=True):
                    record = {
                        "ts": time.time(),
                        "source": "bench.py:sim",
                        "parsed": {
                            "metric": (
                                f"sim round throughput @{sim_len} params "
                                "(in-graph federated round)"
                            ),
                            "platform": platform,
                            # rate series split on host core count (gate)
                            "cpus": os.cpu_count(),
                            **leg,
                        },
                    }
                    f.write(json.dumps(record) + "\n")
        except Exception as e:  # history append must never sink the bench
            print(f"BENCH_HISTORY sim append failed: {e}", file=sys.stderr)
    except Exception as e:  # the sim leg must never sink the fold headline
        print(f"sim leg unavailable: {type(e).__name__}: {e}", file=sys.stderr)

    # scale CPU smoke runs to the 25M-param metric so the number is comparable
    scale = model_len / 25_000_000
    scaled_ups = ups * scale
    baseline = 10_000 / 60.0  # north-star floor: 10k updates in 60s
    if on_tpu:
        metric = "masked-update aggregation throughput @25M params (PET update phase)"
    elif model_len == 25_000_000:
        metric = (
            "masked-update aggregation throughput @25M params, CPU fallback "
            "(PET update phase)"
        )
    else:
        metric = (
            f"masked-update aggregation throughput, CPU fallback @{model_len} params "
            "scaled to the 25M metric (PET update phase)"
        )
    mesh8_out = None
    if mesh8 is not None:
        mesh8_out = {
            "value": round(mesh8["value_raw"] * scale, 2),
            "unit": "updates/s",
            "vs_baseline": round(mesh8["value_raw"] * scale / baseline, 3),
            "mesh": mesh8["mesh"],
            "kernel": mesh8["kernel"],
            "beats_single_device": mesh8["value_raw"] > ups,
            "spread": {
                "median_of": mesh8["median_of"],
                "min": round(mesh8["min_raw"] * scale, 2),
                "max": round(mesh8["max_raw"] * scale, 2),
            },
        }
    multi_tenant_out = None
    if multi_tenant is not None:
        multi_tenant_out = {
            "value": round(multi_tenant["value_raw"], 2),
            "unit": "updates/s",
            "tenants": multi_tenant["tenants"],
            "model_lens": multi_tenant["model_lens"],
            "kernel": multi_tenant["kernel"],
            "mesh": multi_tenant["mesh"],
            "fairness": multi_tenant["fairness"],
            "walls_s": multi_tenant["walls_s"],
        }
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(scaled_ups, 2),
                "unit": "updates/s",
                "vs_baseline": round(scaled_ups / baseline, 3),
                "platform": platform,
                "kernel": best,
                "model_len": model_len,
                "native_threads": native_threads,
                "shard_threads": shard_threads,
                "streaming_vs_sync": streaming_vs_sync,
                "bytes_per_fold": bytes_per_fold,
                "mesh8": mesh8_out,
                "multi_tenant": multi_tenant_out,
                "sim": sim_out,
                "spread": {
                    "median_of": reps,
                    "min": round(min(rep_ups) * scale, 2),
                    "max": round(max(rep_ups) * scale, 2),
                },
            }
        )
    )
    # The mesh=8 series is appended to BENCH_HISTORY.jsonl directly: the
    # driver only captures the single JSON line above as the single-device
    # headline, and the tier-2 gate (tools/bench_gate.py) must cover the
    # sharded path as its own series from this round onward. ONLY the
    # canonical @25M run appends — the gate keys on the LATEST record's
    # series, so a scaled smoke run on a small host must not plant a
    # throwaway series as the newest line and de-gate the real one.
    # both layouts or neither: a failure between the two measurement legs
    # must not plant an unpaired record as the family's latest line (the
    # gate keys the gated series on the latest record)
    if (
        bytes_per_fold is not None
        and model_len == 25_000_000
        and all(lay in bytes_per_fold for lay in ("unpacked", "packed"))
    ):
        # the bytes-moved series (staging + cross-shard combine traffic per
        # fold, from the pipeline's own telemetry counters): packed staging
        # and its unpacked control are separate metrics of one
        # lower-is-better family (tools/bench_gate.py inverts the floor
        # logic for bytes/fold units)
        try:
            hist = os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "BENCH_HISTORY.jsonl"
            )
            with open(hist, "a") as f:
                for layout in ("unpacked", "packed"):
                    record = {
                        "ts": time.time(),
                        "source": "bench.py:bytes",
                        "parsed": {
                            "metric": (
                                f"bytes moved per fold @25M params ({layout} staging)"
                            ),
                            "value": bytes_per_fold[layout],
                            "unit": "bytes/fold",
                            "platform": platform,
                            "kernel": bytes_per_fold.get("kernel"),
                            "mesh": len(jax.devices()),
                            "model_len": model_len,
                            "native_threads": native_threads,
                            "shard_threads": shard_threads,
                        },
                    }
                    f.write(json.dumps(record) + "\n")
        except Exception as e:  # history append must never sink the bench
            print(f"BENCH_HISTORY bytes append failed: {e}", file=sys.stderr)
    if multi_tenant_out is not None and model_len == 25_000_000:
        # the multi-tenant interleaved series: 25M-equivalent updates/s of
        # two tenants folding concurrently through the paged pool + tenant
        # scheduler, with the fairness split recorded on the record (§19)
        try:
            hist = os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "BENCH_HISTORY.jsonl"
            )
            record = {
                "ts": time.time(),
                "source": "bench.py:multi_tenant",
                "parsed": {
                    "metric": "multi-tenant interleaved fold @25M params (2 tenants)",
                    "value": multi_tenant_out["value"],
                    "unit": "updates/s",
                    "platform": platform,
                    "kernel": multi_tenant_out["kernel"],
                    "mesh": multi_tenant_out["mesh"],
                    "model_len": model_len,
                    "native_threads": native_threads,
                    "shard_threads": shard_threads,
                    "cpus": os.cpu_count(),
                    "tenants": multi_tenant_out["tenants"],
                    "model_lens": multi_tenant_out["model_lens"],
                    "fairness": multi_tenant_out["fairness"],
                },
            }
            with open(hist, "a") as f:
                f.write(json.dumps(record) + "\n")
        except Exception as e:  # history append must never sink the bench
            print(f"BENCH_HISTORY multi-tenant append failed: {e}", file=sys.stderr)
    if mesh8_out is not None and model_len == 25_000_000:
        mesh8_metric = (
            f"masked-update aggregation throughput @25M params, "
            f"mesh={mesh8['mesh']} CPU fallback (PET update phase)"
        )
        try:
            hist = os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "BENCH_HISTORY.jsonl"
            )
            record = {
                "ts": time.time(),
                "source": "bench.py:mesh8",
                "parsed": {
                    "metric": mesh8_metric,
                    "value": mesh8_out["value"],
                    "unit": "updates/s",
                    "vs_baseline": mesh8_out["vs_baseline"],
                    "platform": platform,
                    "kernel": mesh8_out["kernel"],
                    "mesh": mesh8_out["mesh"],
                    "model_len": model_len,
                    "native_threads": native_threads,
                    "shard_threads": shard_threads,
                    "cpus": os.cpu_count(),
                    "spread": mesh8_out["spread"],
                },
            }
            with open(hist, "a") as f:
                f.write(json.dumps(record) + "\n")
        except Exception as e:  # history append must never sink the bench
            print(f"BENCH_HISTORY append failed: {e}", file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
