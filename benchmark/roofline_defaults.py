"""Bytes and operations a reconstruct needs when the rows it lacks were not
lost but outrun (a hedged read of a healthy set): the cost function of
``hedge_reconstruct_roofline``.  Peaks and ``least_seconds`` are
``roofline.py``'s."""

from __future__ import annotations


def hedged_reconstruct_cost(counted: float, rebuilt: float, k: int, m: int) -> "tuple[float, float]":
    """``counted``: the bytes the seam counted for its reconstruct calls, all
    n rows of every stripe as staged.  ``rebuilt``: the bytes of the data rows
    the decode had to rebuild (``reconstruct.bytes_rebuilt``; one row of a
    stripe where one hedge won).  The algorithm reads k rows of a stripe and
    writes the rebuilt ones, each byte of them k multiply-adds in GF(2^8).
    Padding stripes and the rows staged past k are the program's own cost
    and count for nothing, so the share cannot pass 100 %."""
    n = k + m
    return counted * k / n + rebuilt, 2.0 * k * rebuilt
