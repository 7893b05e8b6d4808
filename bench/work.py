"""The bytes a batched multi-word CAS apply must move, from real work.

Counted from the real CAS cells and rows only, never from padding, so
the count reads the same work whatever implements the apply.  A cell is
one target word of one op: the apply reads its address, expected and
desired words (4 B each), reads the current word (4 B) and writes the
new one (4 B).  A row is one op and returns one verdict byte.
"""
from __future__ import annotations

WORD = 4
CELL_BYTES = 5 * WORD        # addr, expected, desired in; word read, write
ROW_BYTES = 1                # verdict out


def write_bytes(writes: int, words_per_record: int) -> int:
    """Bytes for ``writes`` committed record writes of
    ``words_per_record`` words each (one row per write)."""
    return writes * (words_per_record * CELL_BYTES + ROW_BYTES)
