"""Work of one reduction, counted from its shape alone.

The benchmark's yardstick for the stage-2 chase: the bytes the paper's
cache-less bulge chase moves (Ringoot, Alomairy, Edelman,
arXiv:2510.12705).  Each chase task reads and writes its H x W window of
the band once, with the tile width fixed at ``tw = bw - 1`` whatever the
program's own ``tw`` or fuse depth, so the count is the same for every
implementation of stage 2 and can only understate what one moves.
"""

from __future__ import annotations

WORD_BYTES = {"float32": 4, "bfloat16": 2, "float64": 8}


def total_chase_cycles(n: int, b_in: int, tw: int) -> int:
    """Chase tasks of one band-reduction stage from bandwidth ``b_in`` to
    ``b_in - tw``: sweep ``r`` runs ``(n - 1 - r - b_out) // b_in + 1``
    tasks, for every sweep that has one."""
    b_out = b_in - tw
    return sum((n - 1 - r - b_out) // b_in + 1
               for r in range(max(n - 1 - b_out, 0)))


def chase_window(bw: int, tw: int) -> tuple[int, int]:
    """(H, W) of one chase task's window: H = bw + 2 tw + 1, W = bw + tw + 1."""
    return bw + 2 * tw + 1, bw + tw + 1


def chase_bytes(n: int, bw: int, dtype: str = "float32") -> int:
    """Bytes the cache-less chase moves to take an n x n upper band of
    width ``bw`` to bidiagonal in one stage (tw = bw - 1): every task reads
    and writes its window once."""
    tw = bw - 1
    h, w = chase_window(bw, tw)
    return total_chase_cycles(n, bw, tw) * 2 * h * w * WORD_BYTES[dtype]
