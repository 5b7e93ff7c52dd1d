"""Block-size rules shared by the Pallas kernel families.

Mosaic (the TPU kernel compiler) accepts a block whose second-to-last
dimension is a multiple of 8 or the whole array dimension; anything else is
refused at compile time, while interpret mode runs it happily.  Every
kernel that blocks a token axis takes its block size from here.
"""
from __future__ import annotations


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def block_rows(t: int, cap: int = 256) -> int:
    """Largest multiple of 8 that divides ``t`` and is <= ``cap``, or ``t``
    itself when no such multiple exists (the whole axis is one block)."""
    for d in range(min(cap, t) // 8 * 8, 0, -8):
        if t % d == 0:
            return d
    return t
