"""Device meshes and shardings for the aggregation buffers.

The reference scales by a single-threaded bignum loop on one CPU core; the
TPU-native design shards the ``uint32[model_len, L]`` aggregation buffer over
the model-length axis of a 1-D device mesh (``NamedSharding``). Modular
aggregation and unmasking are purely elementwise over that axis, so the
sharded kernels run with zero collectives — each device owns a contiguous
slice of the model and the full round needs only the initial host->device
scatter and the final gather. Multi-host pods extend the same mesh over
ICI/DCN without code changes (jax.sharding handles placement).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MODEL_AXIS = "model"


def make_mesh(devices=None) -> Mesh:
    """A 1-D mesh over all (or the given) devices, named for the model axis."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (MODEL_AXIS,))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_to_multiple(n: int, k: int) -> int:
    """Model length padded so every device holds an equal slice."""
    return -(-n // k) * k


def shard_slices(padded_len: int, n_dev: int) -> list[tuple[int, int]]:
    """The contiguous model-axis column slice ``[lo, hi)`` each mesh device
    owns under the 1-D ``P(None, MODEL_AXIS)`` sharding, in mesh-device
    order. ``padded_len`` must already be a multiple of ``n_dev``
    (``pad_to_multiple`` guarantees it), so the slices are equal-width and
    the device-d slice of a serialized wire block is element-aligned."""
    if padded_len % n_dev:
        raise ValueError("padded length must divide evenly across devices")
    width = padded_len // n_dev
    return [(d * width, (d + 1) * width) for d in range(n_dev)]
