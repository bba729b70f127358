"""Bytes a one-call redistribute has to move, from its shapes.

The least any implementation moves: every row read once from where it
was handed in and written once at its owner, whatever the kernels, the
layout or the number of passes. A later engine doing the same call is
read against the same bytes.
"""

from __future__ import annotations


def call_bytes(rows: int, row_bytes: int) -> int:
    """One call over ``rows`` rows of ``row_bytes`` bytes: each row read
    once and written once."""
    return 2 * rows * row_bytes
