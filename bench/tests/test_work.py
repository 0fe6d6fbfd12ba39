"""The least-bytes counts of bench/work on graphs small enough to count by
hand."""
import numpy as np

from bench.arcs import Arcs
from bench.work import bfs as work_bfs
from bench.work import pagerank as work_pr


def diamond():
    # 0 -> 1, 0 -> 2, 1 -> 3, 2 -> 3; vertex 4 has one arc, 4 -> 0
    return Arcs(5, np.array([0, 0, 1, 2, 4], np.int32),
                np.array([1, 2, 3, 3, 0], np.int32))


def test_bfs_bytes_by_hand():
    g = diamond()
    levels = np.array([1, 2, 2, 3, -1])  # from root 0; 4 unreached
    # reached 0, 1, 2, 3 have out-degrees 2, 1, 1, 0: 4 arcs at 8 B;
    # 4 reached vertices at 12 B; 5 vertices initialised at 4 B
    assert work_bfs.bytes_needed(g, {"root": 0}, levels) == 32 + 48 + 20


def test_bfs_bytes_grow_with_reach():
    g = diamond()
    only_root = np.array([-1, -1, -1, -1, 1])
    assert work_bfs.bytes_needed(g, {"root": 4}, only_root) == 8 + 12 + 20


def test_pagerank_bytes_by_hand():
    g = diamond()
    # per iteration: 5 arcs at 8 B, 5 vertices at 16 B
    assert work_pr.bytes_needed(g, {"iters": 3, "damp": 0.85}) == 3 * (40 + 80)
