"""One cell of the benchmark, once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The unit of work is one live PET round served by the coordinator on the
chip and driven from the clients' side over the socket. Set-up starts the
coordinator (``benchmark/serve.py``), masks the round's uploads from
``--seed`` in CPU forge processes, and drives one warm-up round of one fold
batch so that everything the measured round runs is compiled. The window is
the measured round's Update phase under the cell's traffic; its tail (Sum2
by the SDK's sum participant, unmask, publish) and the comparison with the
plain reference follow it. The last line of standard output is the result.

This process never imports JAX: the chip has one owner, the coordinator
child. Without an accelerator the child refuses to start and this exits
non-zero; ``JAX_PLATFORMS=cpu`` has to be named for the toy rehearsal, and
the result line then says ``cpu``.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import os
import shutil
import signal
import subprocess
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import data, forge as forge_mod, reference, replay  # noqa: E402
from benchmark.harness.coordinator import (  # noqa: E402
    SIGTERM_GRACE_S_PER_DEVICE, SUM_PROB, UPDATE_PROB, Coordinator, HarnessError, sample_sum)

CACHE_DIR = os.path.join(ROOT, ".bench_cache")  # fixed: the path is part of the cache's key


def log(message: str) -> None:
    print(f"[{time.monotonic() - T_START:8.2f}] {message}", flush=True)


def child_env(run_dir: str, trace_dir: str | None, trace_max_s: float) -> dict:
    """Environment of the coordinator child. ``JAX_PLATFORMS`` is passed on
    as the caller set it: unset on a chip host, ``cpu`` for the rehearsal."""
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE_DIR, "jax")
        os.makedirs(env["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    env.pop("XAYNET_CALIB_CACHE", None)  # every run races its fold candidates, as shipped
    env["XAYNET_FLIGHT_DIR"] = os.path.join(run_dir, "flight")  # forensic dumps stay in the checkout
    if trace_dir:
        env["BENCH_TRACE_DIR"] = trace_dir
        env["BENCH_TRACE_MAX_S"] = str(trace_max_s)
    return env


def ensure_native() -> None:
    """Build ``native/libxaynet_native.so`` if it is missing or older than
    its source; the forge and the sum participant need it."""
    native_dir = os.path.join(ROOT, "native")
    so = os.path.join(native_dir, "libxaynet_native.so")
    sources = [os.path.join(native_dir, f) for f in ("xaynet_native.cpp", "xaynet_orders.h")]
    fresh = os.path.exists(so) and all(
        os.path.getmtime(so) >= os.path.getmtime(s) for s in sources if os.path.exists(s))
    if not fresh:
        errors = []
        for args in (["make", "-s", "libxaynet_native.so"],
                     ["make", "-s", "libxaynet_native.so", "ARCHFLAGS="]):
            built = subprocess.run(args, cwd=native_dir, capture_output=True, text=True, timeout=600)
            if built.returncode == 0 and os.path.exists(so):
                break
            errors.append(built.stderr.strip()[-400:])
        else:
            raise HarnessError(f"native build failed: {errors}")
    from xaynet_tpu.utils import native

    if native.load() is None:
        raise HarnessError("native library did not load")


def apply_overrides(cfg: dict, traffic: dict, pairs: list[str]) -> None:
    """``--set key=value`` (rehearsals and trials only; the driver passes
    none): a dotted key walks into nested groups (``toml.aggregation.kernel``,
    ``check.sample_positions``), and ``traffic.<key>`` changes the mix
    instead of the configuration. Values are JSON, else strings."""
    for pair in pairs:
        key, _, raw = pair.partition("=")
        try:
            value = json.loads(raw)
        except json.JSONDecodeError:
            value = raw
        *groups, leaf = key.split(".")
        node = cfg
        if groups and groups[0] == "traffic":
            node, groups = traffic, groups[1:]
        for group in groups:
            node = node.setdefault(group, {})
        node[leaf] = value


def tick_until(participant, done, deadline: float, what: str) -> None:
    while not done():
        if time.monotonic() > deadline:
            raise HarnessError(f"timed out: {what}")
        participant.tick()
        if not participant.made_progress():
            time.sleep(0.02)


def wait_for(done, deadline: float, what: str, coord, poll_s: float = 0.02):
    while True:
        value = done()
        if value:
            return value
        coord.alive()
        if time.monotonic() > deadline:
            raise HarnessError(f"timed out: {what}")
        time.sleep(poll_s)


def accepted_updates(samples) -> float:
    return sample_sum(samples, "xaynet_messages_total", {"phase": "update", "outcome": "accepted"})


def folded_batches(samples) -> float:
    return sample_sum(samples, "xaynet_streaming_batches_total", {"stage": "folded"})


class Round:
    """One PET round driven from the clients' side."""

    def __init__(self, coord, forge, deadline: float, timeout: float):
        self.coord, self.forge, self.deadline, self.timeout = coord, forge, deadline, timeout
        self.summer = None
        self.sum_pk = None
        self.round_id = None

    def _with_probe(self, call, timeout: float | None = None):
        from xaynet_tpu.sdk.client import HttpClient

        probe = HttpClient(self.coord.url, timeout=timeout or self.timeout)
        try:
            return call(probe)
        finally:
            probe.close()

    def open(self, indices: list[int]) -> tuple[dict, dict]:
        """Sum phase by one SDK sum participant, then the round's uploads
        sealed for it. Returns ({index: bytes}, {index: participant pk})."""
        from xaynet_tpu.sdk.client import HttpClient
        from xaynet_tpu.sdk.participant import Participant
        from xaynet_tpu.sdk.simulation import keys_for_task

        wait_for(lambda: self.coord.health()["phase"] == "sum", self.deadline,
                 "coordinator in the Sum phase", self.coord)
        params = self._with_probe(lambda probe: asyncio.run(probe.get_round_params()))
        keys = keys_for_task(params.seed.as_bytes(), SUM_PROB, UPDATE_PROB, "sum")
        self.sum_pk = keys.public
        # the SDK's sum participant as shipped for a CPU host: native sampler,
        # no device derive; a bare client (no retry wrapper)
        self.summer = Participant(HttpClient(self.coord.url, timeout=self.timeout),
                                  keys=keys, device_sum2=False, max_message_size=None)
        tick_until(self.summer, lambda: self.coord.health()["phase"] == "update",
                   self.deadline, "sum message accepted")
        sums = self._with_probe(lambda probe: wait_for(
            lambda: asyncio.run(probe.get_sums()), self.deadline,
            "sum dictionary published", self.coord))
        self.round_id = self.coord.health()["round_id"]
        return self.forge.seal(params.to_dict(), sums, indices)

    def seed_dict_keys(self) -> set:
        """The accepted set: the update keys of the sum participant's seed
        dictionary (served once the round is in Sum2)."""
        return set(self._with_probe(lambda probe: wait_for(
            lambda: asyncio.run(probe.get_seeds(self.sum_pk)), self.deadline,
            "seed dictionary served", self.coord)))

    def finish(self) -> float:
        """Sum2 by the sum participant, then wait until the round's model is
        published (the coordinator moves on to the next round). Returns the
        monotonic instant at which that was first seen."""
        tick_until(self.summer, lambda: self.coord.health()["round_id"] > self.round_id,
                   self.deadline, "sum2 sent and global model published")
        t = time.monotonic()
        self.summer.close()
        return t

    def fetch_model(self):
        return self._with_probe(lambda probe: asyncio.run(probe.get_model()), timeout=600.0)


def compare_and_check(args, cfg: dict, expect_platform: str, dev: dict, model, accepted: list,
                      answered: list, accepted_n: int, metrics: dict, health: dict) -> bool:
    """The comparison that decides ``correct``; every number compared is
    printed beside its limit."""
    checks = []

    def check(name: str, value, limit, ok: bool) -> None:
        checks.append(ok)
        log(f"check {name}: {value} (limit {limit}) {'ok' if ok else 'FAILED'}")

    t_ref = time.monotonic()
    n, den, exp_shift = cfg["model_length"], int(cfg["scalar_denominator"]), int(cfg["exp_shift"])
    chk = cfg.get("check", {})
    positions = reference.sample_positions(
        args.seed, n, int(chk.get("sample_positions", 0)), int(chk.get("edge_positions", 0)))
    if accepted and model is not None:
        ref, mean = reference.reference_model(
            args.seed, accepted, n, den, int(cfg["add_shift"]), exp_shift, positions)
        cmp = reference.compare(model, ref, positions, mean, den, exp_shift)
    else:
        cmp = {"model_length": 0, "model_length_want": n, "positions_compared": 0,
               "mismatched_positions": None, "max_abs_error": None}
    check("model length", cmp["model_length"], cmp["model_length_want"],
          cmp["model_length"] == cmp["model_length_want"])
    check(f"positions differing from the plain reference, of {cmp['positions_compared']}",
          cmp["mismatched_positions"], 0, cmp["mismatched_positions"] == 0)
    check("largest distance from the float64 mean", cmp["max_abs_error"],
          cmp.get("max_abs_error_limit"),
          cmp["max_abs_error"] is not None and cmp["max_abs_error"] <= cmp["max_abs_error_limit"])
    check("accepted counter vs seed dictionary", accepted_n, len(accepted),
          accepted_n == len(accepted))
    check("answered 200 vs seed dictionary", len(answered), len(accepted),
          set(answered) == set(accepted))
    compiles = [health[at]["device"]["compile"]["compiles"] for at in ("open", "close")]
    check("compilations inside the window", compiles[1] - compiles[0], 0, compiles[1] == compiles[0])
    failures = int(sample_sum(metrics["end"], "xaynet_phase_transitions_total",
                              {"phase": "failure"}))
    check("phases that entered Failure", failures, 0, failures == 0)
    # the start-up race ran at the warm-up round's flush; the measured round's
    # resolution is its memoized verdict and carries no record
    raced = health["open"]["device"]["fold"]
    race = raced.get("race") or {}
    bad = {name: r.get("status") for name, r in race.items() if r.get("status") != "ok"}
    check("fold-race candidates that failed", json.dumps(bad), "{}", not bad)
    check("fold-race candidates agree", raced.get("results_equal"), True,
          not race or raced.get("results_equal") is True)
    fold = health["end"]["device"]["fold"]
    check("fold ran at the configured length", fold.get("model_length"), n,
          fold.get("model_length") == n)
    check("device platform", dev.get("platform"), expect_platform,
          dev.get("platform") == expect_platform)
    log(f"reference and comparison took {time.monotonic() - t_ref:.2f} s")
    return all(checks)


def run(args) -> int:
    bench = data.load_benchmark()
    cell = data.load_cell(args.workload, bench)
    cfg = data.load_config(cell["config"], bench)
    traffic = data.load_traffic(cell["traffic"])
    apply_overrides(cfg, traffic, args.set or [])
    named = [p.strip() for p in os.environ.get("JAX_PLATFORMS", "").split(",") if p.strip()]
    expect_platform = "cpu" if named == ["cpu"] else "tpu"

    n = replay.n_uploads(traffic, cfg, args.seconds)
    k = int(cfg["batch_size"])
    if n % k:
        raise HarnessError(f"{n} uploads are not whole fold batches of {k}")
    events = replay.schedule(traffic, n, args.seed)
    measured = list(range(n))
    warm = list(range(n, n + k))
    timeout = float(traffic["request_timeout_s"])
    concurrency = int(traffic["concurrency"])
    run_dir = os.path.join(CACHE_DIR, "run", f"{args.workload}.{os.getpid()}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    trace_dir = os.path.join(run_dir, "trace") if args.trace else None
    budget = time.monotonic() + 1100.0

    ensure_native()
    env = child_env(run_dir, trace_dir, float(traffic.get("trace", {}).get("max_s", args.seconds)))
    os.environ["JAX_PLATFORMS"] = "cpu"  # this process, its forge workers, its sum participant
    forge = forge_mod.Forge(
        seed=args.seed, order=warm + measured, scalar_den=int(cfg["scalar_denominator"]),
        model_length=cfg["model_length"], mask=cfg["mask"],
        workers=forge_mod.default_workers(), control=args.control)
    coord = Coordinator(cfg, n, run_dir, env, launcher=args.serve)
    try:
        dev = coord.wait_up(budget).get("device") or {}
        log(f"coordinator serving on {dev.get('platform')} {dev.get('device_kind')} "
            f"x{dev.get('device_count')}")
        if dev.get("platform") != expect_platform:
            raise HarnessError(f"coordinator came up on {dev.get('platform')}")
        if dev.get("device_count", 0) < cell["chips"]:
            raise HarnessError(f"{dev.get('device_count')} devices, the cell needs {cell['chips']}")
        peak_row = data.load_peaks().get(dev.get("device_kind"))
        if peak_row is None and expect_platform != "cpu":
            raise HarnessError(f"device kind {dev.get('device_kind')!r} is not in peaks.json")

        # --- warm-up round: one fold batch, Sum2, unmask, publish ----------
        warm_round = Round(coord, forge, budget, timeout)
        sealed, _ = warm_round.open(warm)
        log(f"warm-up round open: {len(sealed)} uploads sealed (masking took "
            f"{forge.mask_seconds:.1f} s per worker)")
        res = asyncio.run(replay.replay(
            coord.url, sealed, [(0.0, i) for i in warm], concurrency=concurrency,
            cap_seconds=600.0, timeout=timeout))
        if len(res.ok) != k:
            raise HarnessError(f"warm-up: {len(res.ok)} of {k} uploads answered")
        del sealed
        # where the batch is less than the round (count.min), the phase closes
        # degraded once nothing has been accepted for liveness.stall_grace_s
        warm_round.finish()
        log("warm-up round published")

        # --- measured round: set-up ends when its uploads are sealed --------
        main_round = Round(coord, forge, budget, timeout)
        sealed, pks = main_round.open(measured)
        log(f"measured round {main_round.round_id} open: {len(sealed)} uploads sealed, "
            f"{sum(len(b) for b in sealed.values()) / 1e9:.2f} GB")
        metrics = {"open": coord.metrics()}
        health = {"open": coord.health()}
        base_acc, base_fold = accepted_updates(metrics["open"]), folded_batches(metrics["open"])

        async def window():
            task = asyncio.ensure_future(replay.replay(
                coord.url, sealed, events, concurrency=concurrency, cap_seconds=args.seconds,
                timeout=timeout, max_shed_retries=int(traffic["max_shed_retries"])))
            if trace_dir:
                await asyncio.sleep(float(traffic["trace"]["start_s"]))
                coord.signal(signal.SIGUSR1)
            return await task

        setup_s = time.monotonic() - T_START
        res = asyncio.run(window())
        if len(res.ok) < n:
            # count.min is the whole round: it cannot close, and there is no result
            raise HarnessError(f"the window's cap came with {len(res.ok)} of {n} uploads answered "
                               f"({res.errors} errors, {res.unsent} unsent)")
        # the window closes when every answered upload is also folded
        cap = res.t_open + args.seconds + 30.0

        def caught_up():
            samples = coord.metrics()
            done = (accepted_updates(samples) - base_acc >= n
                    and folded_batches(samples) - base_fold >= n // k)
            return samples if done or time.monotonic() > cap else None
        metrics["close"] = wait_for(caught_up, budget, "folds caught up", coord, poll_s=0.01)
        window_s = time.monotonic() - res.t_open
        if trace_dir:
            coord.signal(signal.SIGUSR2)
        health["close"] = coord.health()
        accepted_n = int(accepted_updates(metrics["close"]) - base_acc)
        folded_n = int(folded_batches(metrics["close"]) - base_fold)
        log(f"window closed after {window_s:.3f} s: {len(res.ok)} answered, {accepted_n} accepted, "
            f"{folded_n} batches folded")

        # --- tail and check, outside the window ----------------------------
        accepted_pks = main_round.seed_dict_keys()
        round_tail_s = main_round.finish() - res.t_last_ok
        metrics["end"] = coord.metrics()
        health["end"] = coord.health()
        model = main_round.fetch_model()
        del sealed
        accepted = sorted(i for i in measured if pks[i] in accepted_pks)
        log(f"model fetched ({0 if model is None else model.shape[0]} elements); "
            f"{len(accepted)} participants in the seed dictionary")
        trace = reduce_trace(trace_dir, coord, budget) if trace_dir else None
        gone = coord.terminate(SIGTERM_GRACE_S_PER_DEVICE * max(1, dev.get("device_count", 1)))
        log(f"coordinator gone {gone:.2f} s after SIGTERM")
        forge.close()
        correct = compare_and_check(args, cfg, expect_platform, dev, model, accepted, res.ok,
                                    accepted_n, metrics, health)

        # --- the numbers ----------------------------------------------------
        latencies = [1e3 * v for v in res.latency_s.values()]
        p95 = replay.percentile(latencies, 95.0)
        log(f"uploads timed: {len(latencies)} (p50 {replay.percentile(latencies, 50.0)} ms, "
            f"p95 {p95} ms, max {max(latencies)} ms)")
        peak_bytes = [p for p in health["end"]["device"].get("peak_bytes_in_use") or [] if p]
        device = {"platform": dev["platform"], "kind": dev["device_kind"],
                  "count": dev["device_count"],
                  "memory_peak_bytes": max(peak_bytes) if peak_bytes else 0}
        out_metrics = {}
        if args.trace:
            if trace is not None:
                device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
            driver = {
                "upload_p95_ms": p95,
                "lateness_p95_ms": replay.percentile([1e3 * v for v in res.lateness_s.values()], 95.0),
                "offered_rate": (n - 1) / events[-1][0] if events[-1][0] > 0 else None,
            }
            ctx = {"metrics": metrics, "health": health, "driver": driver, "trace": trace,
                   "cfg": cfg, "peak": peak_row}
            for m in data.metrics_for(args.workload, bench, "per_layer"):
                spec = data.load_layer_metric(m["name"])
                reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
                value = reader.read(ctx, **spec.get("args", {}))
                if value is not None:
                    out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            values = {
                "setup_s": setup_s,
                "updates_per_s": min(accepted_n, folded_n * k) / window_s,
                "upload_p95_ms": p95,
                "round_tail_s": round_tail_s,
            }
            for m in data.metrics_for(args.workload, bench, "end_to_end"):
                out_metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        result = {"correct": correct, "attempted": n, "failed": n - len(accepted),
                  "metrics": out_metrics, "device": device}
        if trace is not None:
            # an operation's name is its HLO text: keep what stands before " = "
            result["breakdown"] = {
                "device_ops": [[name.split(" = ")[0][:80], s_] for name, s_ in trace["device_ops"][:10]],
                "idle_gaps": trace["idle_gaps"][:10]}
    except HarnessError as failure:
        print(f"benchmark run FAILED: {failure}", file=sys.stderr)
        print(f"--- coordinator log tail ({coord.log_path}) ---\n{coord.log_tail()}", file=sys.stderr)
        return 1
    finally:
        coord.close()
        forge.close()
        if not args.keep:
            shutil.rmtree(run_dir, ignore_errors=True)
    if "jax" in sys.modules:
        print("benchmark run FAILED: the parent imported jax", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


def reduce_trace(trace_dir: str, coord, deadline: float) -> dict | None:
    """Wait for the profiler to have written the window, then reduce the
    ``.xplane.pb`` in a process of its own (this one never imports JAX)."""
    import glob

    window_file = os.path.join(trace_dir, "window.json")
    wait_for(lambda: os.path.exists(window_file), min(deadline, time.monotonic() + 120.0),
             "profiler window written", coord, poll_s=0.1)
    with open(window_file, encoding="utf-8") as f:
        window = json.load(f)
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.xtrace", files[0]], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=300)
    if done.returncode != 0:
        log(f"trace reduction failed: {done.stderr[-400:]}")
        return None
    trace = json.loads(done.stdout.strip().splitlines()[-1])
    trace["window_s"] = window["window_s"]
    trace["file"] = files[0]
    log(f"trace: {os.path.getsize(files[0])} bytes, window {window['window_s']:.2f} s, "
        f"device busy {trace['busy_s']:.4f} s on {trace['devices']} device(s)")
    return trace


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # rehearsals, trials and the tests' controls; the driver passes none of these
    ap.add_argument("--set", action="append", metavar="KEY=VALUE",
                    help="override a key of the configuration, traffic.<key> or toml.<section>.<key>")
    ap.add_argument("--control", choices=("bf16",), default=None,
                    help="forge the uploads from weights rounded to bfloat16 (must fail `correct`)")
    ap.add_argument("--serve", default=None, help="another launcher than benchmark/serve.py")
    ap.add_argument("--keep", action="store_true", help="keep the run directory (log, trace)")
    args = ap.parse_args(argv)
    for needed in ("xaynet_tpu", os.path.join("native", "xaynet_native.cpp"), "BENCHMARK.json"):
        if not os.path.exists(os.path.join(ROOT, needed)):
            print(f"benchmark: {needed} not found in this checkout", file=sys.stderr)
            return 2
    try:
        return run(args)
    except HarnessError as failure:
        print(f"benchmark run FAILED: {failure}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
