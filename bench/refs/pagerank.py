"""Host reference of PageRank in float64, as the served program defines it.

Each of ``iters`` rounds pushes rank/out-degree along every arc (a vertex
without out-arcs pushes nothing), then sets rank = (1 - damp)/|V| +
damp * sum. The configuration states float32 ranks: the device sums in
float32, in another order than this reference, so the number compared is
the largest relative error over all vertices. Every rank is at least
(1 - damp)/|V| > 0, so the relative error is defined everywhere.

The limit sits between the two readings it was set from (PERF.md, section
2): the program's largest error over a dozen seeds or more, and the
smallest error of the control, this reference computed in bfloat16."""
from __future__ import annotations

import numpy as np

ANSWER = "rank"
GAP = "pagerank_max_rel_err"
LIMIT = 1e-3


def key(params: dict):
    return (int(params["iters"]), float(params["damp"]))


def reference(arcs, params: dict) -> np.ndarray:
    iters, damp = int(params["iters"]), float(params["damp"])
    n = arcs.n
    deg = arcs.out_degree.astype(np.float64)
    rank = np.full(n, 1.0 / n)
    for _ in range(iters):
        contrib = np.bincount(arcs.dst, weights=(rank / np.maximum(deg, 1))[arcs.src],
                              minlength=n)
        rank = (1.0 - damp) / n + damp * contrib
    return rank


def gap(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want) / want))
