"""A generated graph as the host holds it: arc arrays and the views that
the references, the controls and the work counts read.

Nothing here imports the system under test."""
from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property
from typing import Iterator

import numpy as np
import scipy.sparse as sp

#: roots one bit-parallel traversal serves (one bit of a word each)
LANES = 64


@dataclass
class Arcs:
    """``n`` vertices and directed arcs ``src[i] -> dst[i]`` (int32).

    Parallel arcs and self-loops are kept as generated: they are part of
    the graph the system is asked to serve."""

    n: int
    src: np.ndarray
    dst: np.ndarray

    @property
    def m(self) -> int:
        return int(self.src.shape[0])

    @cached_property
    def out_degree(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.n).astype(np.int64)

    @cached_property
    def adjacency(self) -> sp.csr_matrix:
        """Row u holds u's out-neighbours (parallel arcs summed)."""
        return sp.csr_matrix(
            (np.ones(self.m, np.float32), (self.src, self.dst)),
            shape=(self.n, self.n),
        )

    @cached_property
    def in_adjacency(self):
        """(indptr, sources): row v lists v's in-neighbours, each once."""
        t = self.adjacency.T.tocsr()
        return t.indptr.astype(np.int64), t.indices

    def frontiers(self, roots) -> Iterator[np.ndarray]:
        """A level-synchronous BFS from up to ``LANES`` distinct roots at
        once, pulled over ``in_adjacency``: yields each level's frontier as
        one word per vertex, whose bit ``j`` says that root ``j`` first
        reaches the vertex at that level (level 0 holds the roots)."""
        roots = np.asarray(roots, np.int64)
        k = roots.size
        if not 0 < k <= LANES or np.unique(roots).size != k:
            raise ValueError(f"frontiers takes 1 to {LANES} distinct roots")
        indptr, sources = self.in_adjacency
        has_in = np.diff(indptr) > 0
        starts = indptr[:-1][has_in]
        frontier = np.zeros(self.n, np.uint64)
        frontier[roots] = np.uint64(1) << np.arange(k, dtype=np.uint64)
        seen = frontier.copy()
        while frontier.any():
            yield frontier
            nxt = np.zeros_like(frontier)
            nxt[has_in] = np.bitwise_or.reduceat(frontier[sources], starts)
            frontier = nxt & ~seen
            seen |= frontier
