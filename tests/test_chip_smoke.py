"""``chip_smoke.py --cpu`` end to end, and the process hygiene it relies on.

The smoke is the program the driver runs on the chip; here the same script
runs at its toy size on the CPU backend (a coordinator child with
``[aggregation] device = true`` over four virtual devices, the Pallas fold
through the interpreter). Also pinned here: a SIGTERM'd coordinator lets go
within seconds even with an idle keep-alive connection open, and
``device = true`` without a named backend refuses to start on a host with
no accelerator.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env.update(PYTHONPATH=str(REPO), **extra)
    return env


def _field(stdout: str, name: str) -> str:
    for line in stdout.splitlines():
        if line.startswith(name + ":"):
            return line.split(":", 1)[1].strip()
    raise AssertionError(f"no '{name}:' line in\n{stdout}")


def test_chip_smoke_cpu_passes_and_prints_the_report():
    run = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--cpu"],
        env=_env(), capture_output=True, text=True, timeout=300,
    )
    assert run.returncode == 0, run.stdout + run.stderr
    out = run.stdout
    assert json.loads(out.strip().splitlines()[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 4}
    }
    assert _field(out, "platform") == "cpu"
    assert _field(out, "device_count") == "4"
    assert _field(out, "model_length") == "20011"
    assert "flushes: 2" in _field(out, "batch_size")
    assert _field(out, "fold_kernel") == "pallas-interpret (configured)"
    assert len(json.loads(_field(out, "acc_slices"))) == 4
    assert float(_field(out, "setup_compile_seconds").split()[0]) > 0
    assert "entries" in _field(out, "compile_cache")
    assert _field(out, "failure_phases") == "0"
    assert float(_field(out, "coordinator_exit_seconds_after_sigterm")) < 10


def test_chip_smoke_fails_when_the_coordinator_is_on_another_platform():
    """The coordinator's own report is the source of truth: asked for a TPU
    and told ``cpu``, the smoke fails before it drives anything."""
    code = "import sys, chip_smoke; sys.exit(chip_smoke.main(['--cpu'], expect_platform='tpu'))"
    run = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=_env(),
        capture_output=True, text=True, timeout=300,
    )
    assert run.returncode == 1, run.stdout + run.stderr
    assert "coordinator came up on cpu, not tpu" in run.stderr
    assert '"ok"' not in run.stdout


def test_chip_smoke_without_the_repo_fails(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    alone.write_bytes((REPO / "chip_smoke.py").read_bytes())
    run = subprocess.run(
        [sys.executable, str(alone), "--cpu"],
        env={k: v for k, v in _env().items() if k != "PYTHONPATH"},
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
    )
    assert run.returncode != 0
    assert '"ok"' not in run.stdout


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _start_runner(tmp_path, env: dict, aggregation: str = ""):
    port = _free_port()
    cfg = tmp_path / "config.toml"
    cfg.write_text(
        f'[api]\nbind_address = "127.0.0.1:{port}"\n[model]\nlength = 16\n'
        f"[aggregation]\n{aggregation}\n"
    )
    log = open(tmp_path / "coordinator.log", "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "xaynet_tpu.server.runner", "-c", str(cfg)],
        cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
    )
    return proc, port, log


def test_device_true_without_a_chip_or_a_named_cpu_refuses_to_start(tmp_path):
    """No silent XLA:CPU: with ``JAX_PLATFORMS`` unset JAX falls back to
    the cpu backend on this host, and the runner exits with an error."""
    proc, _port, log = _start_runner(tmp_path, _env(), "device = true")
    try:
        assert proc.wait(120) != 0
    finally:
        proc.kill()
        log.close()
    text = (tmp_path / "coordinator.log").read_text()
    assert "device = true but JAX resolved the cpu backend" in text


def test_sigterm_with_an_idle_keepalive_connection_exits_within_seconds(tmp_path):
    """Python 3.12's ``Server.wait_closed()`` waits for open connections;
    an idle keep-alive peer must not hold a SIGTERM'd coordinator (and the
    accelerator it owns) for the 120 s read timeout."""
    proc, port, log = _start_runner(tmp_path, _env(JAX_PLATFORMS="cpu"))
    idle = None
    try:
        deadline = time.monotonic() + 60
        while True:
            try:
                idle = socket.create_connection(("127.0.0.1", port), timeout=5)
                break
            except OSError:
                assert proc.poll() is None and time.monotonic() < deadline
                time.sleep(0.1)
        idle.sendall(b"GET /healthz HTTP/1.1\r\nHost: t\r\n\r\n")  # keep-alive
        assert idle.recv(65536).startswith(b"HTTP/1.1 200")
        t0 = time.monotonic()
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(10) == 0
        assert time.monotonic() - t0 < 10
    finally:
        if idle is not None:
            idle.close()
        proc.kill()
        log.close()
