"""Arithmetic on shapes for a model sharded on the model axis: the columns a
chip holds, and the bytes ONE shard's fold has to move. Functions of sizes
only, beside ``sizing.py``, whose ``fold_bytes`` counts the whole model
against one chip's bandwidth and so would read one fold per chip as many
times too fast as there are chips.
"""

from __future__ import annotations

from .sizing import fold_bytes


def shard_length(n: int, devices: int) -> int:
    """Columns of the padded model a chip holds on a 1-D mesh of ``devices``:
    the length is padded so that every chip holds the same."""
    return -(-n // devices)


def shard_fold_bytes(k: int, bpn: int, n_limbs: int, shard_len: int) -> int:
    """HBM bytes one shard's fold of ``k`` packed updates must move on its
    own chip: ``sizing.fold_bytes`` at the shard's length (its slice of the
    batch read once, its slice of the accumulator read and written once)."""
    return fold_bytes(k, bpn, n_limbs, shard_len)

