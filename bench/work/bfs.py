"""Least bytes one BFS query must move through HBM: a top-down traversal.

Counted from the graph and the answer, never from what an implementation
did, so the share of the roofline reads the same work whatever answers
the query:

- each reached vertex reads its two CSR row bounds (8 B), each of its
  out-arcs once (4 B target id) and the level of that target (4 B);
- every vertex's level is written once at the start (4 B) and each
  reached vertex's level once more when it is reached (4 B).

A direction-optimising BFS can legitimately move fewer bytes (bottom-up
levels stop at the first parent found), so a share above what a top-down
traversal allows is possible; see PERF.md."""
from __future__ import annotations

import numpy as np

ARC_BYTES = 8
REACHED_VERTEX_BYTES = 12
VERTEX_BYTES = 4


def bytes_needed(arcs, params: dict, answer: np.ndarray) -> int:
    reached = np.asarray(answer) > 0
    arcs_read = int(arcs.out_degree[reached].sum())
    return (ARC_BYTES * arcs_read + REACHED_VERTEX_BYTES * int(reached.sum())
            + VERTEX_BYTES * arcs.n)
