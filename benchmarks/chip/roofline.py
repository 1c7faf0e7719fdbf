"""Bytes a kernel needs, from the algorithm's logical sizes.

Padding to the chip's tiles is not counted, so the count is the same work
whatever implements it, and a later change of layout cannot move it.
"""

from __future__ import annotations

F32 = 4      # bytes of a float32 or int32 element


def event_engine_bytes(slot_passes: int, flushes: int, *, depth: int,
                       links: int) -> int:
    """HBM bytes that ``flushes`` flush passes over ``slot_passes`` live
    slots in all (the sum of each pass's live slots) must move.

    Per live slot: its link path (``depth`` int32 ids) and remaining
    bytes, rate and completion time in and out (three float32 each).
    Per pass: each link's bandwidth and occupancy (float32), the flush
    instant in and the earliest completion out.
    """
    per_slot = (depth + 3 + 3) * F32
    per_flush = (2 * links + 2) * F32
    return slot_passes * per_slot + flushes * per_flush
