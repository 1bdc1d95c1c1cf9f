"""One PET round on the chip, through the coordinator, at full width.

    python chip_smoke.py          # on a TPU host: 25M parameters, f32/B0/M6
    python chip_smoke.py --cpu    # the same path at a toy size (sandbox, tier-1)

The quickest proof that the system still starts on its target. It launches
the coordinator the way an operator does (``python -m
xaynet_tpu.server.runner -c <config>`` with ``[aggregation] device = true``),
drives one full round over the socket with the participant SDK (1 sum
participant, 2 x batch_size update participants -> two fold flushes), fetches
the global model and compares it with the float64 mean of the f32 inputs,
then reads what the coordinator says it ran on — ``/healthz`` and ``/metrics``
are the only source of truth — and sends SIGTERM.

One process per chip: this parent never imports jax, and its participants
are pinned to the CPU (``JAX_PLATFORMS=cpu``, ``device_sum2=False``) — edge
devices are CPUs, and the accelerator has one owner, the coordinator child.

Exits non-zero (and prints no result line) unless every check holds: the
platform is the one asked for, no phase entered Failure, every fold-race
candidate ran and they agree, every device reports a non-zero peak, the
model is within ``n_update / exp_shift``, and the coordinator is gone
within 10 s (per device) of SIGTERM. The last line of stdout is then

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time
from dataclasses import dataclass
from fractions import Fraction
from urllib.request import urlopen

ROOT = os.path.dirname(os.path.abspath(__file__))
os.environ["JAX_PLATFORMS"] = "cpu"  # this process and its participants
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

SEED = 20260926
SUM_PROB, UPDATE_PROB = 0.5, 0.9
HBM_BYTES = 16 * 2**30  # the smallest chip served: one TPU v5e
# SIGTERM to process gone, per device. The coordinator's own shutdown takes
# well under a second; the rest is the accelerator runtime unmapping its
# pinned transfer buffers at process exit, which no exit path shortens
# (v5e without transparent hugepages, one chip: 3.9 s orderly, 5.5 s via
# os._exit, 5.6 s via SIGKILL) and which a four-chip host pays four times.
SIGTERM_GRACE_S_PER_DEVICE = 10.0


class SmokeFailure(Exception):
    """A check failed; the message says which."""


@dataclass(frozen=True)
class Size:
    platform: str  # JAX_PLATFORMS of the coordinator child, and what it must report
    model_length: int
    batch_size: int
    max_message_size: int
    kernel: str | None  # None = the shipped default ("auto": race on the chip)
    budget_s: float

    @property
    def n_update(self) -> int:
        return 2 * self.batch_size  # two full flushes


def batch_size_for(model_length: int, n_limbs: int, bpn: int) -> int:
    """The fold batch K, from arithmetic: the largest power of two <= 8
    whose device footprint stays under half of one chip's HBM (the other
    half is headroom for XLA's own scratch and a remainder-flush program).

    Device bytes at K, with a = 4*L*n (one accumulator), p = K*a (a planar
    batch) and q = K*bpn*n (a packed batch); the temporaries are what the
    v5e compiler reports for these programs (tests/test_aot_tpu.py):
    - the kernel race: the live accumulator, the planar batch, a scratch and
      two kept results (3a), and fold temporaries up to 1.1x the fold's
      arguments;
    - steady state: up to dispatch_ahead + 1 = 3 packed batches in flight,
      the accumulator, and 3.5 q of temporaries on the Pallas route (the
      unpacked planar plus its per-call pad to a tile multiple; the XLA
      route needs 1.1 q).
    The bound is a worst case: one-chip runs peaked at 1.7 GB (K = 4) and
    3.2 GB (K = 8).
    """
    a = 4 * n_limbs * model_length
    k = 8
    while k > 1:
        p, q = k * a, k * bpn * model_length
        race = a + p + 3 * a + 1.1 * (p + a)
        steady = 3 * q + a + 3.5 * q
        if max(race, steady) <= HBM_BYTES / 2:
            break
        k //= 2
    return k


def mask_config():
    """Integer/F32/B0/M6, as in ``write_config``'s ``[mask]`` section."""
    from xaynet_tpu.core.mask.config import BoundType, DataType, GroupType, MaskConfig, ModelType

    return MaskConfig(GroupType.INTEGER, DataType.F32, BoundType.B0, ModelType.M6)


def sizes(cpu: bool) -> Size:
    if cpu:
        # not a multiple of the 4 virtual devices, the Pallas tile or 128
        return Size("cpu", 20_011, 2, 64 * 1024, "pallas-interpret", 300.0)
    from xaynet_tpu.ops import limbs

    cfg = mask_config()
    n = 25_000_000
    k = batch_size_for(n, limbs.n_limbs_for_order(cfg.order), cfg.bytes_per_number)
    return Size("tpu", n, k, 32 * 2**20, None, 1100.0)


# --- set-up ----------------------------------------------------------------


def rebuild_native() -> None:
    """Force a rebuild of the native host kernels and require them: the
    ``.so`` is untracked and the loader trusts mtimes, and at 25M elements
    the pure-Python sampler is not a fallback, it is a hang."""
    native_dir = os.path.join(ROOT, "native")
    so = os.path.join(native_dir, "libxaynet_native.so")
    if os.path.exists(so):
        os.remove(so)
    errors = []
    for args in (
        ["make", "-s", "libxaynet_native.so"],
        ["make", "-s", "libxaynet_native.so", "ARCHFLAGS="],
    ):
        built = subprocess.run(args, cwd=native_dir, capture_output=True, text=True, timeout=300)
        if built.returncode == 0 and os.path.exists(so):
            break
        errors.append(built.stderr.strip()[-500:])
    else:
        raise SmokeFailure(f"native build failed: {errors}")
    from xaynet_tpu.utils import native

    if native.load() is None:
        raise SmokeFailure("native library built but did not load")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def write_config(size: Size, run_dir: str, port: int) -> str:
    window = f"min = 0.0\nmax = {size.budget_s}"
    lines = [
        "[log]", 'filter = "info"',
        "[api]", f'bind_address = "127.0.0.1:{port}"',
        "[pet.sum]", f"prob = {SUM_PROB}",
        "[pet.sum.count]", "min = 1", "max = 1",
        "[pet.sum.time]", window,
        "[pet.update]", f"prob = {UPDATE_PROB}",
        "[pet.update.count]", f"min = {size.n_update}", f"max = {size.n_update}",
        # the first fold compiles inside the Update phase
        "[pet.update.time]", window,
        "[pet.sum2.count]", "min = 1", "max = 1",
        "[pet.sum2.time]", window,
        "[mask]", 'group_type = "integer"', 'data_type = "f32"',
        'bound_type = "b0"', 'model_type = "m6"',
        "[model]", f"length = {size.model_length}",
        # every other [aggregation] key stays at its shipped default
        "[aggregation]", "device = true", f"batch_size = {size.batch_size}",
    ]
    if size.kernel is not None:
        lines.append(f'kernel = "{size.kernel}"')
    path = os.path.join(run_dir, "config.toml")
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    return path


# --- the coordinator child -------------------------------------------------


class Coordinator:
    def __init__(self, size: Size, run_dir: str):
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(run_dir, "coordinator.log")
        env = dict(os.environ, JAX_PLATFORMS=size.platform)
        if size.platform == "cpu":
            # four virtual devices: the shape of the four-chip host
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
            ).strip()
        self._log = open(self.log_path, "w", encoding="utf-8")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "xaynet_tpu.server.runner",
             "-c", write_config(size, run_dir, self.port)],
            cwd=ROOT, env=env, stdout=self._log, stderr=subprocess.STDOUT,
        )

    def get(self, path: str, timeout: float = 30.0) -> tuple[int, bytes]:
        """A one-shot GET (urllib sends ``Connection: close``: no idle
        socket is left open on the coordinator)."""
        with urlopen(self.url + path, timeout=timeout) as resp:
            return resp.status, resp.read()

    def health(self) -> dict:
        status, body = self.get("/healthz")
        if status != 200:
            raise SmokeFailure(f"/healthz -> {status}")
        return json.loads(body)

    def metric(self, name: str, **labels: str) -> float:
        """One sample of the Prometheus exposition (0 if absent)."""
        _, body = self.get("/metrics")
        for line in body.decode().splitlines():
            series, _, value = line.rpartition(" ")
            if series.partition("{")[0] == name and all(
                f'{k}="{v}"' in series for k, v in labels.items()
            ):
                return float(value)
        return 0.0

    def wait_up(self, deadline: float) -> dict:
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"coordinator exited with {self.proc.returncode} before serving"
                )
            try:
                return self.health()
            except OSError:  # not listening yet
                time.sleep(0.25)
        raise SmokeFailure("coordinator did not start serving in time")

    def terminate(self, grace_s: float) -> float:
        """SIGTERM; seconds until the process is gone (raises past the grace)."""
        t0 = time.monotonic()
        self.proc.send_signal(signal.SIGTERM)
        try:
            self.proc.wait(grace_s)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"coordinator still running {grace_s:.0f} s after SIGTERM"
            ) from None
        return time.monotonic() - t0

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()

    def log_tail(self, lines: int = 60) -> str:
        self._log.flush()
        with open(self.log_path, encoding="utf-8", errors="replace") as f:
            return "".join(f.readlines()[-lines:])


# --- the round -------------------------------------------------------------


def local_model(index: int, length: int) -> np.ndarray:
    """Participant ``index``'s weights: seeded, in [-1, 1), different per
    participant and per position — a constant vector cannot see an element
    land in the wrong lane, tile or shard."""
    rng = np.random.default_rng([SEED, index])
    return rng.random(length, dtype=np.float32) * np.float32(2) - np.float32(1)


def tick_until(participant, done, deadline: float, what: str) -> None:
    while not done():
        if time.monotonic() > deadline:
            raise SmokeFailure(f"timed out: {what}")
        participant.tick()
        if participant.should_set_model():
            raise SmokeFailure(f"{what}: participant asked for a model it was given")
        if not participant.made_progress():
            time.sleep(0.05)


def run_round(coord: Coordinator, size: Size, deadline: float) -> tuple[np.ndarray, np.ndarray]:
    """Drive one round; returns (global model, float64 reference mean)."""
    from xaynet_tpu.sdk.client import HttpClient
    from xaynet_tpu.sdk.participant import Participant
    from xaynet_tpu.sdk.simulation import keys_for_task

    def participant(task: str, index: int, **kwargs) -> Participant:
        keys = keys_for_task(seed, SUM_PROB, UPDATE_PROB, task, start=index * 200_000)
        # a bare HttpClient: no retry wrapper re-sending a 175 MB upload;
        # the idle timeout covers a reply that waits for a first compile
        client = HttpClient(coord.url, timeout=size.budget_s)
        return Participant(
            client, keys=keys, max_message_size=size.max_message_size,
            device_sum2=False, **kwargs,
        )

    probe = HttpClient(coord.url, timeout=size.budget_s)
    try:
        params = asyncio.run(probe.get_round_params())
        seed = params.seed.as_bytes()
        if params.model_length != size.model_length:
            raise SmokeFailure(f"coordinator serves model_length {params.model_length}")

        summer = participant("sum", 0)
        tick_until(
            summer, lambda: coord.health()["phase"] == "update", deadline,
            "sum message accepted",
        )
        reference = np.zeros(size.model_length, dtype=np.float64)
        for i in range(size.n_update):
            weights = local_model(i, size.model_length)
            reference += weights
            updater = participant("update", i + 1, scalar=Fraction(1, size.n_update))
            updater.set_model(weights)
            accepted = lambda: coord.metric(  # noqa: E731
                "xaynet_messages_total", phase="update", outcome="accepted"
            ) >= i + 1
            tick_until(updater, accepted, deadline, f"update {i + 1}/{size.n_update} accepted")
            updater.close()
            print(f"update {i + 1}/{size.n_update} accepted", flush=True)
        reference /= size.n_update

        def model_ready() -> bool:
            return coord.get("/model")[0] == 200

        tick_until(summer, model_ready, deadline, "sum2 sent and global model published")
        summer.close()
        model = asyncio.run(probe.get_model())
    finally:
        probe.close()
    return np.asarray(model), reference


# --- the checks ------------------------------------------------------------


def check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


def report_and_check(coord: Coordinator, size: Size, expect_platform: str,
                     model: np.ndarray, reference: np.ndarray) -> dict:
    """Print the coordinator's own report and hold it to the contract."""
    health = coord.health()
    dev = health.get("device")
    check(dev is not None, "/healthz has no device section")
    fold, comp = dev["fold"], dev["compile"]
    flushes = int(coord.metric("xaynet_streaming_batches_total", stage="folded"))
    failures = int(coord.metric("xaynet_phase_transitions_total", phase="failure"))
    tolerance = size.n_update / mask_config().exp_shift
    check(model.shape == reference.shape, f"model shape {model.shape}")
    check(bool(np.all(np.isfinite(model))), "global model has non-finite values")
    max_err = float(np.max(np.abs(model - reference)))

    print(f"platform: {dev['platform']}")
    print(f"device_kind: {dev['device_kind']}")
    print(f"device_count: {dev['device_count']}")
    print(f"model_length: {fold.get('model_length')}")
    print(f"batch_size: {size.batch_size}  updates: {size.n_update}  flushes: {flushes}")
    print(f"fold_kernel: {fold.get('kernel')} ({fold.get('source')})")
    for name, outcome in (fold.get("race") or {}).items():
        print(f"race[{name}]: {json.dumps(outcome)}")
    print(f"race_results_equal: {fold.get('results_equal')}")
    print(f"acc_slices: {fold.get('acc_slices')}")
    print(f"setup_compile_seconds: {comp['seconds']} "
          f"({comp['compiles']} compiles, {comp['cache_hits']} cache hits, "
          f"{comp['cache_writes']} cache writes)")
    print(f"compile_cache: {comp['cache_dir']} "
          f"entries {comp['cache_entries_start']} -> {comp['cache_entries_now']}")
    print(f"peak_bytes_in_use: {dev['peak_bytes_in_use']}")
    print(f"failure_phases: {failures}")
    print(f"max_abs_error: {max_err:.3e} (tolerance {tolerance:.3e})")

    check(dev["platform"] == expect_platform,
          f"coordinator runs on {dev['platform']}, not {expect_platform}")
    check(fold.get("model_length") == size.model_length, "fold ran at another length")
    check(flushes >= 2, f"{flushes} fold flushes, need >= 2")
    check(failures == 0, f"{failures} phase(s) entered Failure")
    race = fold.get("race") or {}
    if size.kernel is None:
        check(fold.get("source") == "race" and len(race) >= 2, "no fold-kernel race ran")
    bad = {n: r["status"] for n, r in race.items() if r["status"] != "ok"}
    check(not bad, f"fold race candidates failed: {bad}")
    check(not race or fold.get("results_equal") is True, "fold candidates disagree")
    slices = fold["acc_slices"]
    check(len(slices) == dev["device_count"], "one accumulator slice per device expected")
    check(len({hi - lo for lo, hi in slices}) == 1, f"unequal accumulator slices {slices}")
    peaks = dev["peak_bytes_in_use"]
    if expect_platform != "cpu":  # the cpu backend keeps no memory stats
        check(len(peaks) == dev["device_count"] and all(p and p > 0 for p in peaks),
              f"a device reports no memory use: {peaks}")
    check(max_err <= tolerance, f"model off by {max_err:.3e} > {tolerance:.3e}")
    return {"platform": dev["platform"], "kind": dev["device_kind"], "count": dev["device_count"]}


def main(argv=None, expect_platform: str | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu", action="store_true",
                    help="toy size on the CPU backend (sandbox / tier-1)")
    args = ap.parse_args(argv)
    for needed in ("xaynet_tpu", os.path.join("native", "xaynet_native.cpp")):
        if not os.path.exists(os.path.join(ROOT, needed)):
            print(f"chip_smoke: {needed} not found beside this script", file=sys.stderr)
            return 2
    size = sizes(args.cpu)
    expect = expect_platform or size.platform
    deadline = time.monotonic() + size.budget_s
    run_dir = os.path.join(ROOT, "chiprun_out", "chip_smoke")
    os.makedirs(run_dir, exist_ok=True)
    coord = None
    try:
        rebuild_native()
        coord = Coordinator(size, run_dir)
        started = coord.wait_up(deadline)
        got = (started.get("device") or {}).get("platform")
        check(got == expect, f"coordinator came up on {got}, not {expect}")
        model, reference = run_round(coord, size, deadline)
        device = report_and_check(coord, size, expect, model, reference)
        gone = coord.terminate(SIGTERM_GRACE_S_PER_DEVICE * device["count"])
        print(f"coordinator_exit_seconds_after_sigterm: {gone:.2f}")
    except SmokeFailure as failure:
        print(f"chip_smoke FAILED: {failure}", file=sys.stderr)
        if coord is not None:
            print(f"--- coordinator log tail ({coord.log_path}) ---\n{coord.log_tail()}",
                  file=sys.stderr)
        return 1
    finally:
        if coord is not None:
            coord.close()
    if "jax" in sys.modules:
        print("chip_smoke FAILED: the parent imported jax", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
