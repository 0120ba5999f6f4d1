"""The least HBM bytes a batch of key-value ops has to move.

An op that is served touches one latch word (two int32 lanes, 8 bytes)
and one line of ``width`` int32 lanes, and each is read and written
once: ``2 * (8 + 4 * width)`` bytes per op.  The count depends only on
shapes and on how many ops were served, not on the backend or the
layout that serves them, so the same number stands for every way a
later change implements the verb.
"""

from __future__ import annotations


def op_bytes(width: int) -> int:
    """Least HBM bytes of one served op on lines of ``width`` lanes."""
    return 2 * (8 + 4 * int(width))


def least_bytes(n_ops: int, width: int) -> int:
    """Least HBM bytes of ``n_ops`` served ops."""
    return int(n_ops) * op_bytes(width)


def roofline_share(n_bytes: int, busy_s: float, bytes_per_s: float,
                   n_chips: int) -> float:
    """Share (0..1) of the bandwidth roofline: the least time ``n_bytes``
    take at ``n_chips`` chips' peak HBM bandwidth, over the device busy
    time (mean over those chips) they took."""
    least_s = n_bytes / (bytes_per_s * n_chips)
    return least_s / busy_s
