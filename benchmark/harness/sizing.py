"""Arithmetic on shapes: the fold batch that fits a chip, and the bytes a
fold has to move. Functions of sizes only; nothing here runs on a device.
"""

from __future__ import annotations


def fold_bytes(k: int, bpn: int, n_limbs: int, n: int) -> int:
    """HBM bytes one fold of ``k`` packed updates must move: read the
    ``[k, bpn, n]`` uint8 batch once, read and write the ``[n_limbs, n]``
    uint32 accumulator once each: ``k*bpn*n + 2*4*n_limbs*n``. The fold does
    a handful of integer operations per byte, so HBM bandwidth bounds it."""
    return k * bpn * n + 2 * 4 * n_limbs * n


def footprint(n: int, n_limbs: int, bpn: int, k: int) -> dict:
    """Worst-case device bytes at fold batch ``k`` (copied from
    ``chip_smoke.py::batch_size_for``; the temporaries are what the v5e
    compiler reports, see ``benchmark/aot_check.py``): the start-up race
    holds the accumulator, a planar batch, a scratch and two kept results,
    and fold temporaries up to 1.1x the arguments; steady state holds up to
    three packed batches in flight (``dispatch_ahead`` + 1), the accumulator,
    and up to 3.5 packed batches of temporaries on the Pallas route."""
    a = 4 * n_limbs * n
    p, q = k * a, k * bpn * n
    return {
        "race_bytes": int(a + p + 3 * a + 1.1 * (p + a)),
        "steady_bytes": int(3 * q + a + 3.5 * q),
    }


def batch_size_for(n: int, n_limbs: int, bpn: int, hbm_bytes: int, k_max: int = 65536) -> int:
    """The largest power of two whose race and steady-state footprints fit
    ``hbm_bytes`` (the whole chip: the coordinator is its only tenant)."""
    k = 1
    while 2 * k <= k_max and max(footprint(n, n_limbs, bpn, 2 * k).values()) <= hbm_bytes:
        k *= 2
    return k
