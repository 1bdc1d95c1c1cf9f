"""The system under test as a child process: its configuration file, its
start and stop, and reads of its ``/healthz`` and ``/metrics``.

The child handling is a copy of ``chip_smoke.py``'s. The coordinator is
started through ``benchmark/serve.py``, which calls the same
``xaynet_tpu.server.runner.main()`` that an operator's ``python -m
xaynet_tpu.server.runner -c <config>`` calls.
"""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
from urllib.request import urlopen

from .data import BENCH_DIR, ROOT

SUM_PROB, UPDATE_PROB = 0.5, 0.9  # roles are pinned by key search; any values do
PHASE_TIME_MAX_S = 900.0  # no phase's own clock ends a run: the harness caps the window
SIGTERM_GRACE_S_PER_DEVICE = 15.0


class HarnessError(RuntimeError):
    """The run cannot produce a result (not the same as ``correct: false``)."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _toml_value(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, float)):
        return repr(v)
    return json.dumps(str(v))


def config_toml(cfg: dict, n_updates: int, port: int) -> str:
    """The coordinator's TOML for one run: the shipped defaults of
    ``configs/config.toml`` (every key not written here), the
    configuration's mask, length and ``toml`` overrides, and the round's
    count windows. Update closes at ``n_updates``; ``quorum = batch_size``
    lets the warm-up round (one fold batch, then silence) and a round cut by
    the window's cap close degraded after ``liveness.stall_grace_s`` instead
    of entering Failure."""
    window = {"min": 0.0, "max": PHASE_TIME_MAX_S}
    one = {"min": cfg["sum_participants"], "max": cfg["sum_participants"]}
    sections: dict[str, dict] = {
        "log": {"filter": "info"},
        "api": {"bind_address": f"127.0.0.1:{port}"},
        "pet.sum": {"prob": SUM_PROB},
        "pet.sum.count": dict(one),
        "pet.sum.time": dict(window),
        "pet.update": {"prob": UPDATE_PROB},
        "pet.update.count": {"min": n_updates, "max": n_updates,
                             "quorum": min(cfg["batch_size"], n_updates)},
        "pet.update.time": dict(window),
        "pet.sum2.count": dict(one),
        "pet.sum2.time": dict(window),
        "mask": dict(cfg["mask"]),
        "model": {"length": cfg["model_length"]},
        "aggregation": {"batch_size": cfg["batch_size"]},
        "ingest": {"wire_format": cfg["wire_format"]},
    }
    for section, keys in cfg.get("toml", {}).items():
        sections.setdefault(section, {}).update(keys)
    lines = []
    for section, keys in sections.items():
        lines.append(f"[{section}]")
        lines.extend(f"{k} = {_toml_value(v)}" for k, v in keys.items())
    return "\n".join(lines) + "\n"


_SAMPLE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{.*\})?\s+(\S+)$")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_metrics(text: str) -> list[tuple[str, dict, float]]:
    """Prometheus text exposition -> ``(name, labels, value)`` samples."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line.strip())
        if not m:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")), value))
    return out


def sample_sum(samples: list, name: str, labels: dict | None = None) -> float:
    """Sum of the samples of ``name`` whose labels match ``labels``: each
    wanted value is a regular expression the label's value must match in
    full (0.0 when none does)."""
    total = 0.0
    for sname, slabels, value in samples:
        if sname != name:
            continue
        if all(re.fullmatch(str(want), slabels.get(k, "")) for k, want in (labels or {}).items()):
            total += value
    return total


class Coordinator:
    def __init__(self, cfg: dict, n_updates: int, run_dir: str, env: dict,
                 launcher: str | None = None):
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(run_dir, "coordinator.log")
        self.config_path = os.path.join(run_dir, "config.toml")
        with open(self.config_path, "w", encoding="utf-8") as f:
            f.write(config_toml(cfg, n_updates, self.port))
        self._log = open(self.log_path, "w", encoding="utf-8")
        launcher = launcher or os.path.join(BENCH_DIR, "serve.py")
        self.proc = subprocess.Popen(
            [sys.executable, launcher, "-c", self.config_path],
            cwd=ROOT, env=env, stdout=self._log, stderr=subprocess.STDOUT,
        )

    def get(self, path: str, timeout: float = 60.0) -> tuple[int, bytes]:
        with urlopen(self.url + path, timeout=timeout) as resp:
            return resp.status, resp.read()

    def health(self) -> dict:
        status, body = self.get("/healthz")
        if status != 200:
            raise HarnessError(f"/healthz -> {status}")
        return json.loads(body)

    def metrics(self) -> list:
        return parse_metrics(self.get("/metrics")[1].decode())

    def alive(self) -> None:
        if self.proc.poll() is not None:
            raise HarnessError(f"coordinator exited with {self.proc.returncode}")

    def wait_up(self, deadline: float) -> dict:
        while time.monotonic() < deadline:
            self.alive()
            try:
                return self.health()
            except OSError:  # not listening yet
                time.sleep(0.1)
        raise HarnessError("coordinator did not start serving in time")

    def signal(self, sig: int) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(sig)

    def terminate(self, grace_s: float) -> float:
        """SIGTERM; seconds until the process is gone (killed past the grace)."""
        t0 = time.monotonic()
        self.signal(signal.SIGTERM)
        try:
            self.proc.wait(grace_s)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        return time.monotonic() - t0

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()

    def log_tail(self, lines: int = 40) -> str:
        self._log.flush()
        with open(self.log_path, encoding="utf-8", errors="replace") as f:
            return "".join(f.readlines()[-lines:])
