"""Least bytes one PageRank query must move through HBM.

Each of ``iters`` rounds reads every arc once (4 B source id in a CSC
stream) and the contribution of that source (4 B), and per vertex reads
its column bound (4 B), rank (4 B) and out-degree (4 B) and writes its
new rank (4 B). Counted from the graph and the parameters alone."""
from __future__ import annotations

ARC_BYTES = 8
VERTEX_BYTES = 16


def bytes_needed(arcs, params: dict, answer=None) -> int:
    return int(params["iters"]) * (ARC_BYTES * arcs.m + VERTEX_BYTES * arcs.n)
