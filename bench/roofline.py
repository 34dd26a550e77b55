"""Bytes the decode + format step has to move, counted from the work and
not from the compiled program, so the count stays the same whatever
implements the decode.

Per decoded block the step reads the block's device-resident rows once
(what the container's layout makes resident: its streams, consensus window
and directory row) and writes, once, every array the consumer receives for
that block (the format's output plus the token plane and the per-read
metadata rows). No operation count is kept: the decode is integer work,
for which the peaks table has no peak, so the share is of the bytes bound.
"""

from __future__ import annotations

import math


def output_bytes_per_block(shapes: dict) -> int:
    """Bytes of one block's slice of each output array. ``shapes`` maps an
    output name to ``(shape, itemsize)`` with the block axis first."""
    return sum(math.prod(shape[1:]) * itemsize for shape, itemsize in shapes.values())


def decode_bytes(blocks: int, resident_bytes_per_block: int, shapes: dict) -> int:
    """Least bytes moved to decode and format ``blocks`` blocks."""
    return blocks * (resident_bytes_per_block + output_bytes_per_block(shapes))


def roofline_share(bytes_moved: int, device_seconds: float, hbm_bytes_per_s: float):
    """Share (0..1) of the bytes bound reached in ``device_seconds``; None
    when there is no device time to divide by."""
    if device_seconds <= 0 or bytes_moved <= 0:
        return None
    return bytes_moved / hbm_bytes_per_s / device_seconds
