"""Host reference of BFS: hop levels from one root (scipy's C BFS).

The served program (``bfs``, the paper's BFS_ECP) answers ``old_level``:
1 at the root, hop count + 1 elsewhere, -1 where unreached. The levels
are exact integers, so the comparison counts the vertices whose level
differs, and its limit is 0."""
from __future__ import annotations

import numpy as np
from scipy.sparse import csgraph

#: the property of a served result that holds the answer
ANSWER = "old_level"
#: name of the number compared, and its limit (an exact comparison)
GAP = "bfs_levels_wrong"
LIMIT = 0


def key(params: dict):
    """Queries with equal keys have equal answers."""
    return int(params["root"])


def reference(arcs, params: dict) -> np.ndarray:
    d = csgraph.shortest_path(arcs.adjacency, method="D", directed=True,
                              unweighted=True, indices=int(params["root"]))
    return np.where(np.isinf(d), -1, d + 1).astype(np.int64)


def gap(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got).astype(np.int64)
    if got.shape != want.shape:
        return float(want.size)
    return float(np.count_nonzero(got != want))
