"""Host reference of BFS: hop levels from each root.

The served program (``bfs``, the paper's BFS_ECP) answers ``old_level``:
1 at the root, hop count + 1 elsewhere, -1 where unreached. The levels
are exact integers, so the comparison counts the vertices whose level
differs, and its limit is 0.

A run checks its answers with ``reference_batch``: one bit-parallel,
level-synchronous sweep per ``LANES`` roots (``Arcs.frontiers``), which
keeps the check short on graphs of 2^29 arcs. ``reference`` is scipy's
C BFS from one root; the tests and the control hold the two equal."""
from __future__ import annotations

from typing import Iterator, List

import numpy as np
from scipy.sparse import csgraph

from bench.arcs import LANES

#: the property of a served result that holds the answer
ANSWER = "old_level"
#: name of the number compared, and its limit (an exact comparison)
GAP = "bfs_levels_wrong"
LIMIT = 0
#: vertices whose lanes are unpacked at once (bounds the sweep's memory)
BLOCK = 1 << 18


def key(params: dict):
    """Queries with equal keys have equal answers."""
    return int(params["root"])


def reference(arcs, params: dict) -> np.ndarray:
    d = csgraph.shortest_path(arcs.adjacency, method="D", directed=True,
                              unweighted=True, indices=int(params["root"]))
    return np.where(np.isinf(d), -1, d + 1).astype(np.int64)


def reference_batch(arcs, params: List[dict]) -> Iterator[np.ndarray]:
    """``reference`` of each query in ``params`` (distinct roots), in
    order, as int32; one sweep answers ``LANES`` of them at a time."""
    roots = [key(p) for p in params]
    for lo in range(0, len(roots), LANES):
        chunk = roots[lo:lo + LANES]
        levels = np.full((len(chunk), arcs.n), -1, np.int32)
        for depth, frontier in enumerate(arcs.frontiers(chunk), start=1):
            reached = np.flatnonzero(frontier)
            for b in range(0, reached.size, BLOCK):
                v = reached[b:b + BLOCK]
                bits = np.unpackbits(frontier[v].view(np.uint8).reshape(-1, 8),
                                     axis=1, bitorder="little")[:, :len(chunk)]
                vi, lane = np.nonzero(bits)
                levels[lane, v[vi]] = depth
        yield from levels


def gap(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got).astype(np.int64)
    if got.shape != want.shape:
        return float(want.size)
    return float(np.count_nonzero(got != want))
