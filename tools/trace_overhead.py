"""Tracing-overhead bench leg: the streaming fold path, spans on vs off.

The fold headline's span surface is the streaming pipeline (stage/fold/
commit/drain spans per batch) — the raw kernel loop carries no spans, so
measuring it would trivially show zero. This leg drives the PRODUCTION
submit/drain path at the headline batch shape with tracing ``on`` and
``off`` and reports the relative delta; BENCH.md records the number, and
the DESIGN §16 policy is: the default stays ``[metrics] trace = "on"``
while the overhead is <2%, else the default flips to failure-only
sampling.

A second leg bounds the ALWAYS-ON timeline fold (DESIGN §20): the per-round
``fold_spans`` pass over a realistic synthetic buffer, reported in µs and
as a share of the measured window wall — the §20 policy keeps the fold
always-on while that share is ≤0.1%.

Usage:
  JAX_PLATFORMS=cpu python tools/trace_overhead.py [--model-len N]
                    [--k K] [--batches B] [--reps R]
Prints one JSON line: {updates_per_s_on, updates_per_s_off, overhead_pct,
span_cost_us, span_cost_mirrored_us (the mirror sink set, no profiler
session), unmask_bracket_on_us / unmask_bracket_off_us (one stage bracket of
``telemetry/unmask.py``: span + histogram observation, and the observation
alone with the tracer off), usage_bracket_us (what ``usage="thread"`` adds to
one such bracket: two ``getrusage`` calls, the differences onto the four
``xaynet_span_*`` counters and into the span), usage_bracket_crew_us (the
same for ``usage="crew"``: with the native workers' tally read twice),
getrusage_us (one ``getrusage(RUSAGE_THREAD)`` on this box: a bracket makes two),
timeline_fold_us, timeline_fold_pct_of_window, ...}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _one_window(mode: str, stack, config, model_len: int, n_batches: int) -> float:
    """updates/s of one submit+drain window in ``mode``."""
    from xaynet_tpu.parallel.aggregator import ShardedAggregator
    from xaynet_tpu.parallel.streaming import StreamingAggregator
    from xaynet_tpu.telemetry import tracing

    tracing.get_tracer().configure(mode=mode, trace_dir="")
    k = stack.shape[0]
    agg = ShardedAggregator(config.vect, model_len)
    stream = StreamingAggregator(agg, max_batch=k)
    try:
        # one untimed window resolves the kernel + warms the rings
        stream.submit_batch(stack)
        stream.drain()
        t0 = time.perf_counter()
        for _ in range(n_batches):
            stream.submit_batch(stack)
        stream.drain()
        return k * n_batches / (time.perf_counter() - t0)
    finally:
        stream.close()


def measure(mode: str, stack, config, model_len: int, n_batches: int, reps: int) -> float:
    """Median updates/s over ``reps`` windows in ``mode`` (standalone use;
    ``main`` interleaves on/off windows instead — see below)."""
    import numpy as np

    return float(
        np.median(
            [_one_window(mode, stack, config, model_len, n_batches) for _ in range(reps)]
        )
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model-len", type=int, default=1_000_000)
    ap.add_argument("--k", type=int, default=8, help="updates per batch")
    ap.add_argument("--batches", type=int, default=6, help="batches per timed window")
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()

    import numpy as np

    from xaynet_tpu.core.mask.config import (
        BoundType, DataType, GroupType, MaskConfig, ModelType,
    )
    from xaynet_tpu.ops import limbs as host_limbs
    from xaynet_tpu.utils.jaxcache import enable_compile_cache

    enable_compile_cache()
    config = MaskConfig(
        GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6
    ).pair()
    n_limb = host_limbs.n_limbs_for_order(config.vect.order)
    rng = np.random.default_rng(0)
    # wire layout [K, model_len, L] — what submit_batch stages
    stack = rng.integers(
        0, 2**32, size=(args.k, args.model_len, n_limb), dtype=np.uint32
    )
    stack[:, :, n_limb - 1] &= np.uint32((1 << 20) - 1)

    # PAIRED off/on windows, ALTERNATING order, median-of-ratios: this
    # bench box throttles (walls drift 2-3x across a run), so two
    # back-to-back whole passes measure the drift, not the spans — the
    # first draft of this tool did exactly that and "measured" ~10%.
    # Pairing adjacent windows cancels the slow drift; alternating which
    # mode runs first cancels the intra-pair heat-up bias (an A/A off-vs-
    # off control showed the SECOND window of a pair runs up to ~10%
    # different on its own); the median ratio resists contended outlier
    # draws. One discarded warm window pays the jit compile + kernel-race
    # one-time costs for both modes.
    _one_window("off", stack, config, args.model_len, args.batches)
    off_ups, on_ups, ratios = [], [], []
    for i in range(args.reps):
        first, second = ("off", "on") if i % 2 == 0 else ("on", "off")
        x = _one_window(first, stack, config, args.model_len, args.batches)
        y = _one_window(second, stack, config, args.model_len, args.batches)
        on_i, off_i = (y, x) if first == "off" else (x, y)
        on_ups.append(on_i)
        off_ups.append(off_i)
        ratios.append(on_i / off_i)
        time.sleep(1.0)  # breather between pairs (thermal)
    off = float(np.median(off_ups))
    on = float(np.median(on_ups))
    ratio = float(np.median(ratios))
    overhead = (1.0 - ratio) * 100.0

    # the analytic bound alongside the noisy end-to-end number: spans per
    # batch are a handful, so cost-per-span x spans-per-batch / batch wall
    # bounds the overhead independently of machine noise
    from xaynet_tpu.telemetry import tracing

    tracer = tracing.get_tracer()
    tracer.configure(mode="on")
    name = tracing.declared_span_names()
    probe = "trace.overhead_probe"
    if probe not in name:
        tracing.declare_span(probe)
    n_probe = 20_000
    t0 = time.perf_counter()
    for _ in range(n_probe):
        with tracer.span(probe, batch=1):
            pass
    span_cost_us = (time.perf_counter() - t0) / n_probe * 1e6

    # the same span with the mirror sink set as the runner sets it
    # (jax.profiler.TraceAnnotation) and no profiler session open: what a
    # mirror=True span costs a coordinator that nobody is profiling
    import jax

    mirrored_probe = "trace.overhead_probe_mirrored"
    if mirrored_probe not in name:
        tracing.declare_span(mirrored_probe, mirror=True)
    tracer.set_mirror(jax.profiler.TraceAnnotation)
    try:
        t0 = time.perf_counter()
        for _ in range(n_probe):
            with tracer.span(mirrored_probe, batch=1):
                pass
        span_cost_mirrored_us = (time.perf_counter() - t0) / n_probe * 1e6
    finally:
        tracer.set_mirror(None)

    # one stage bracket of the tables outside the Update window
    # (telemetry/unmask.py; server/stages.py's are the same call): a span
    # and a histogram observation with the tracer on, the observation alone
    # with it off. A round makes about 25 of them.
    from xaynet_tpu.telemetry import unmask as unmask_stages

    def _bracket_cost_us(mode: str) -> float:
        tracer.configure(mode=mode)
        t0 = time.perf_counter()
        for _ in range(n_probe):
            with unmask_stages.stage("retire"):
                pass
        return (time.perf_counter() - t0) / n_probe * 1e6

    try:
        bracket_off_us = _bracket_cost_us("off")
        bracket_on_us = _bracket_cost_us("on")
    finally:
        tracer.configure(mode="on")

    # what reading the thread's usage adds to such a bracket: the same
    # `timed_span` on a name declared with `usage` against one declared
    # without, interleaved, the least of several passes of each (the box's
    # drift is larger than the difference)
    from xaynet_tpu.utils import native

    native.load()  # a crew's tally is the library's
    usage_probes = {"none": "trace.overhead_probe_usage_none",
                    "thread": "trace.overhead_probe_usage_thread",
                    "crew": "trace.overhead_probe_usage_crew"}
    for word, probe_name in usage_probes.items():
        if probe_name not in name:
            tracing.declare_span(probe_name, usage=None if word == "none" else word)
    seconds = unmask_stages.SECONDS.labels(stage="retire")
    passes: dict[str, list[float]] = {word: [] for word in usage_probes}
    for _ in range(9):
        for word, probe_name in usage_probes.items():
            t0 = time.perf_counter()
            for _ in range(n_probe // 4):
                with tracing.timed_span(probe_name, seconds):
                    pass
            passes[word].append((time.perf_counter() - t0) / (n_probe // 4) * 1e6)
    usage_bracket_us = min(passes["thread"]) - min(passes["none"])
    # the floor under it on this box: the system call itself, twice a bracket
    import resource

    who = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)
    calls = []
    for _ in range(9):
        t0 = time.perf_counter()
        for _ in range(n_probe // 4):
            resource.getrusage(who)
        calls.append((time.perf_counter() - t0) / (n_probe // 4) * 1e6)
    getrusage_us = min(calls)
    usage_bracket_crew_us = min(passes["crew"]) - min(passes["none"])

    # the always-on timeline fold (DESIGN §20): one O(n) pass per round
    # over the span buffer. Time it on a synthetic buffer shaped like a
    # real round (phase spans + streaming children, half the 8192 cap) and
    # bound it against the measured ON window wall — a real round wall is
    # LONGER than one window, so the reported share is conservative. The
    # §20 policy: the fold stays always-on while this is <=0.1%.
    from xaynet_tpu.telemetry.timeline import fold_spans
    from xaynet_tpu.telemetry.tracing import Span

    def _synthetic_round(n_children: int) -> list:
        spans = []
        t = 1000.0
        idle = Span("phase.idle", "t", "s0", None, t, {"tenant": "default"})
        idle.duration = 0.05
        spans.append(idle)
        t += idle.duration
        for j, phase in enumerate(("sum", "update", "sum2", "unmask")):
            p = Span(f"phase.{phase}", "t", f"p{j}", None, t, {
                "tenant": "default", "round_id": 7, "outcome": "full",
            })
            p.duration = 2.0
            spans.append(p)
            per = max(1, n_children // 4)
            for c in range(per):
                ch = Span("stream.fold", "t", f"c{j}-{c}", f"p{j}",
                          t + c * (p.duration / per), {"batch": c})
                ch.duration = p.duration / per
                spans.append(ch)
            t += p.duration
        root = Span("round", "t", "r", None, spans[0].start, {"round_id": 7})
        root.duration = t - spans[0].start
        spans.append(root)
        return spans

    buffer = _synthetic_round(4096)
    n_folds = 50
    t0 = time.perf_counter()
    for _ in range(n_folds):
        decomp = fold_spans(7, buffer)
    fold_cost_us = (time.perf_counter() - t0) / n_folds * 1e6
    assert decomp is not None and decomp["spans"] == len(buffer)
    window_wall_s = args.k * args.batches / on
    fold_pct_of_window = fold_cost_us / 1e6 / window_wall_s * 100.0
    print(
        json.dumps(
            {
                "updates_per_s_on": round(on, 2),
                "updates_per_s_off": round(off, 2),
                "overhead_pct": round(overhead, 2),
                "pair_ratios": [round(r, 4) for r in ratios],
                "span_cost_us": round(span_cost_us, 2),
                "span_cost_mirrored_us": round(span_cost_mirrored_us, 2),
                "unmask_bracket_on_us": round(bracket_on_us, 2),
                "unmask_bracket_off_us": round(bracket_off_us, 2),
                "usage_bracket_us": round(usage_bracket_us, 2),
                "usage_bracket_crew_us": round(usage_bracket_crew_us, 2),
                "getrusage_us": round(getrusage_us, 2),
                "timeline_fold_us": round(fold_cost_us, 2),
                "timeline_fold_spans": len(buffer),
                "timeline_fold_pct_of_window": round(fold_pct_of_window, 4),
                "model_len": args.model_len,
                "k": args.k,
                "batches": args.batches,
                "reps": args.reps,
            }
        )
    )


if __name__ == "__main__":
    main()
