"""A generated graph as the host holds it: arc arrays and the views that
the references, the controls and the work counts read.

Nothing here imports the system under test."""
from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property
import numpy as np
import scipy.sparse as sp


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
