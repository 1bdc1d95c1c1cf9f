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


def pytest_configure(config):
    """Build ``native/libxaynet_native.so`` once a session, under a file
    lock. The library is built from source on first use
    (``xaynet_tpu/utils/native.py``); in a fresh checkout every pytest-xdist
    worker would find it missing while it collects and build it over the
    others' heads, and a worker that loads a half-written file skips every
    test that needs the library. The first process here builds, the others
    wait for the lock and find the file fresh."""
    import fcntl

    from xaynet_tpu.utils import native

    root = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(root, "native", ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        native.ensure_built()
