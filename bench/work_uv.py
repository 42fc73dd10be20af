"""Work of the tape replay of one full SVD, counted from its shape alone.

The yardstick for the replay of the stage-2 reflector tape into U^T and
V^T (``repro.core.transforms.replay_chase``): every chase task at the tile
width ``tw = bw - 1`` of one stage applies its right reflector to tw + 1
full-width rows of V^T and its left one to tw + 1 rows of U^T, so a replay
without a cache reads and writes those rows once and reads the two
reflectors.  The count is fixed by (n, bw), whatever the program's own tile
width, fuse depth or blocking, and can only understate what one moves.
"""

from __future__ import annotations

from bench.work import WORD_BYTES, total_chase_cycles


def replay_bytes(n: int, bw: int, dtype: str = "float32") -> int:
    """Bytes the cache-less replay moves for an n x n band of width ``bw``:
    per task and per side, (tw + 1) rows of n read and written, and a
    reflector of tw + 1 entries and its tau read."""
    tw = bw - 1
    per_side = 2 * (tw + 1) * n + tw + 2
    return total_chase_cycles(n, bw, tw) * 2 * per_side * WORD_BYTES[dtype]
