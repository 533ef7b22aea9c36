"""Shared tiling rules for the Pallas kernels over flat vectors.

On TPU a flat f32 vector is handed to the kernels as a lane-dense
(rows, 128) array, and a block must cover whole (8, 128) tiles: a
smaller or unaligned block does not lower. ``fit_block`` therefore only
returns blocks that are multiples of the caller's tile and divide the
(padded) length, and ``pad_to`` gives the padded length.
"""
from __future__ import annotations

LANES = 128                 # lane width of a vector register
TILE = 8 * LANES            # elements in one (8, 128) f32 tile


def pad_to(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def fit_block(f: int, block: int, multiple: int = 1) -> int:
    """Largest block that is a multiple of ``multiple``, divides ``f``
    and is at most ``block`` (never less than ``multiple``). ``f`` must
    itself be a multiple of ``multiple``."""
    if multiple < 1 or f % multiple:
        raise ValueError(f"length {f} is not a multiple of {multiple}")
    units = f // multiple
    want = max(1, min(block // multiple, units))
    while units % want:
        want -= 1
    return want * multiple
