"""The toy rehearsal the tests share: a cell end to end on the CPU backend
(``JAX_PLATFORMS=cpu`` named), Pallas in interpret mode, a 20,011-element
model, fold batches of 4, a few tens of uploads."""

from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = ["--set", "model_length=20011", "--set", "batch_size=4",
       "--set", 'toml.aggregation.kernel="pallas-interpret"',
       "--set", "check.sample_positions=5000", "--set", "check.edge_positions=64"]
FLOOD = TOY + ["--set", "updates_per_round=8", "--set", "scalar_denominator=8", "--seconds", "10"]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(cell: str, args: list[str], *, root: str = ROOT, seed: int = 7, trace: int = 0,
             platforms: str | None = "cpu", timeout: float = 300.0):
    """(exit code, the result line's object or None, stdout, stderr)."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    if platforms is not None:
        env["JAX_PLATFORMS"] = platforms
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", cell,
         "--seed", str(seed), "--trace", str(trace), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
    lines = done.stdout.strip().splitlines()
    result = None
    if done.returncode == 0 and lines:
        result = json.loads(lines[-1])
    return done.returncode, result, done.stdout, done.stderr
