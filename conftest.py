"""Pytest configuration: force a deterministic multi-device CPU platform.

Sharding tests run on a virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count``); benchmarks use real TPU
hardware separately (``chip_smoke.py``).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
