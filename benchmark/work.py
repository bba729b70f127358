"""Bytes a layer has to move, computed from shapes and counts.

Each function counts the layer's logical inputs read once and its
outputs written once, independent of how a kernel tiles, so that a later
kernel doing the same work is read against the same work. The state is
planar int32: ``K = 2 * D + 1`` words per slot (position, velocity,
alive flag).
"""

from __future__ import annotations

WORD = 4  # bytes of one int32 / float32


def row_bytes(K: int) -> int:
    """Bytes of one particle row on the wire."""
    return K * WORD


def driftbin_bytes(slots: int, D: int, K: int) -> int:
    """Drift + wrap + bin over every slot: read the K words of each slot,
    write its D new position words and its destination key."""
    return slots * WORD * (K + D + 1)


def landing_bytes(arrivals: int, K: int) -> int:
    """Landing of arrived rows: read each arrival's K words and its target
    slot, write its K words into the state."""
    return arrivals * WORD * (2 * K + 1)
