"""Control of the BFS comparison: the reference with one guarantee broken.

The configuration states exact hop levels for every reachable vertex. The
control stops the traversal one level early, as an early exit on a small
last frontier would: the deepest level's vertices read unreached. The
comparison has to call this wrong."""
from __future__ import annotations

import numpy as np

from bench.refs import bfs as ref


def answer(arcs, params: dict) -> np.ndarray:
    levels = ref.reference(arcs, params).copy()
    levels[levels == levels.max()] = -1
    return levels
