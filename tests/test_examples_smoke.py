"""Baseline-config examples run end to end, shrunken (VERDICT r02 item 5).

The cifar_lenet (baseline config #2) and shakespeare_lstm (config #3)
examples are executed as real subprocesses — the same command a user runs —
with tiny shapes and ``--check-loss``, which makes the script itself exit
nonzero unless the federated global model improves on the initial loss.
Reference analogue: bindings/python/examples/keras_house_prices/ is a
living, documented scenario.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(args: list[str], timeout: int = 280) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, *args],
        cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_cifar_lenet_example_smoke():
    r = _run_example(
        [
            "examples/cifar_lenet.py",
            "--rounds", "2",
            "--participants", "6",
            "--image-size", "8",
            "--epochs", "3",
            "--lr", "0.01",
            "--check-loss",
        ]
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-2000:]}"
    assert "eval loss" in r.stdout


@pytest.mark.slow
def test_cifar_lenet_quantized_round_accuracy_gate():
    """The pre-mask quantization accuracy gate (docs/DESIGN.md §17): a
    quantized round (level 5 — 1-limb prime order, 4-byte wire width)
    through the REAL coordinator + SDK must still pass the --check-loss
    gate, the way PR-3 gated byte-identity. Slow-marked (a full 2-round
    federated example, ~1-4 min on shared cores): CI's unfiltered pytest
    run covers it; the fast analytic accuracy bound lives in
    tests/test_packed_codec.py::test_quantized_round_accuracy_bound."""
    r = _run_example(
        [
            "examples/cifar_lenet.py",
            "--rounds", "2",
            "--participants", "6",
            "--image-size", "8",
            "--epochs", "3",
            "--lr", "0.01",
            "--check-loss",
            "--quant", "5",
        ]
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-2000:]}"
    assert "eval loss" in r.stdout


def test_lora_federated_example_smoke():
    """Baseline config #5 (stretch): int-masked LoRA adapter federation with
    the loss-improvement gate (VERDICT r04 item 8)."""
    r = _run_example(["examples/lora_federated.py", "--rounds", "2", "--check-loss"])
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-2000:]}"
    assert "eval loss" in r.stdout


def test_shakespeare_lstm_example_smoke():
    r = _run_example(
        [
            "examples/shakespeare_lstm.py",
            "--rounds", "1",
            "--participants", "5",
            "--hidden", "16",
            "--seq-len", "20",
            "--epochs", "3",
            "--lr", "0.01",
            "--check-loss",
        ]
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-2000:]}"
    assert "eval loss" in r.stdout


def test_sim_quickstart_example_smoke():
    """The sim quickstart (DESIGN §13) runs a whole-round program twice and
    must report exactly one program invocation per round."""
    r = _run_example(
        [
            "examples/sim_quickstart.py",
            "-p", "64",
            "-l", "50",
            "-b", "16",
            "--rounds", "2",
        ]
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-2000:]}"
    assert "program invocations: 2" in r.stdout
    assert "participants/s" in r.stdout
