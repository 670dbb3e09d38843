"""How one ``score_batch`` call splits into jitted forward calls.

A copy of the serving engine's batching arithmetic (``serving/engine.py``:
context grouping, cross-request dedup, chunking to the request bucket, and
the split of chunks into per-worker spans), kept here so the per-call
shapes that the byte and operation counts use cannot move with the program.
The harness checks it against the engine's own ``rows_scored`` counter on
every call and drops the shape-dependent metrics where the two disagree.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np


def bucket(n: int, minimum: int) -> int:
    """Smallest power of two >= n, floored at ``minimum``."""
    b = max(1, minimum)
    while b < n:
        b *= 2
    return b


def split_spans(n_chunks: int, workers: int) -> List[int]:
    """Lengths of the contiguous near-equal spans ``n_chunks`` splits into."""
    if workers <= 1 or n_chunks <= 1:
        return [n_chunks]
    w = min(workers, n_chunks)
    base, rem = divmod(n_chunks, w)
    return [base + (1 if i < rem else 0) for i in range(w)]


def call_layout(requests: Sequence[Tuple], *, min_bucket: int, workers: int,
                dedup: bool = True):
    """``(nb, unique_rows, spans)`` of one call: the candidate bucket, the
    rows left after dedup, and per forward call ``(row_bucket, rows)``."""
    fcand = requests[0][2].shape[1]
    group_of, groups = [], {}
    for ci, cv, _, _ in requests:
        key = (np.asarray(ci, np.int32).tobytes(),
               np.asarray(cv, np.float32).tobytes())
        group_of.append(groups.setdefault(key, len(groups)))
    if not dedup:
        group_of = list(range(len(requests)))
    counts = np.asarray([r[2].shape[0] for r in requests], np.int64)
    total = int(counts.sum())
    if total == 0:
        return 0, 0, []
    g_row = np.repeat(np.asarray(group_of, np.int64), counts)
    ki = np.concatenate([np.asarray(r[2], np.int32) for r in requests])
    kv = np.concatenate([np.asarray(r[3], np.float32) for r in requests])
    if dedup:
        mat = np.empty((total, 1 + 2 * fcand), np.int32)
        mat[:, 0] = g_row
        mat[:, 1:1 + fcand] = ki
        mat[:, 1 + fcand:] = kv.view(np.int32)
        packed = np.ascontiguousarray(mat).view(
            np.dtype((np.void, mat.itemsize * mat.shape[1])))[:, 0]
        _, first = np.unique(packed, return_index=True)
    else:
        first = np.arange(total)
    nb = bucket(int(counts.max()), min_bucket)
    gcounts = np.bincount(g_row[first])
    n_chunks = int((-(-gcounts // nb)).sum())
    spans = [(bucket(m, 1), m) for m in split_spans(n_chunks, workers)]
    return nb, int(first.size), spans
