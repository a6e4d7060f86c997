"""Host work split into blocks of rows on a pool of threads.

NumPy's and SciPy's kernels release the interpreter lock, so blocks of
rows run at once. Each block computes its rows with the same code, in the
same order, as one call over all rows would, so the result is
bit-identical at any number of blocks.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

Span = Tuple[int, int]

# below this many rows a call is not split: threads would cost more
MIN_ROWS = 1 << 16
# bytes a block's temporaries may take: small enough for the allocator to
# hand the same memory from block to block instead of faulting in new pages
BLOCK_BYTES = 1 << 22


def threads() -> int:
    """The cores this process may run on."""
    return len(os.sched_getaffinity(0))


def even(n: int, blocks: Optional[int] = None, *,
         row_bytes: Optional[int] = None) -> List[Span]:
    """`n` rows in `blocks` spans of near-equal length. By default one span
    when `n` is small, else spans of about `BLOCK_BYTES` of rows
    `row_bytes` wide, or four spans a thread."""
    if blocks is None:
        blocks = (1 if n < MIN_ROWS
                  else -(-n * row_bytes // BLOCK_BYTES) if row_bytes
                  else 4 * threads())
    cuts = np.linspace(0, n, max(min(blocks, n), 1) + 1).astype(np.int64)
    return [(int(a), int(b)) for a, b in zip(cuts[:-1], cuts[1:])]


def run(fn: Callable[[int, int], None], spans: Sequence[Span]) -> None:
    """Call `fn(lo, hi)` for every span, on threads when there are several;
    an exception in any block is raised here."""
    if len(spans) <= 1:
        for lo, hi in spans:
            fn(lo, hi)
        return
    with ThreadPoolExecutor(min(threads(), len(spans))) as pool:
        for f in [pool.submit(fn, lo, hi) for lo, hi in spans]:
            f.result()
