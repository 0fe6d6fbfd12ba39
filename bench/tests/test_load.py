"""The traffic generator without the program: who sends which query, when
the window closes, and what the warm-up holds."""
import concurrent.futures
import time

import numpy as np
import pytest
from scipy.sparse import csgraph

from bench import load
from bench.arcs import Arcs
from bench.graphs import kronecker

DEG = np.array([0, 3, 1, 0, 2, 5, 1, 1, 0, 4, 2, 2])
# a graph with those out-degrees (targets do not matter to the plans below)
ARCS = Arcs(12, np.repeat(np.arange(12), DEG).astype(np.int32),
            np.zeros(int(DEG.sum()), np.int32))
SPEC = {"scale": 9, "edge_factor": 8, "a": 0.57, "b": 0.19, "c": 0.19,
        "permute": False, "undirected": False}


def _dense_levels_one(arcs, root, share):
    d = csgraph.shortest_path(arcs.adjacency, method="D", unweighted=True,
                              indices=int(root))
    reached = np.isfinite(d)
    per_level = np.bincount(d[reached].astype(np.int64),
                            weights=arcs.out_degree[reached])
    return int(np.count_nonzero(per_level > share * arcs.m))


@pytest.mark.parametrize("share", [0.05, 0.25])
def test_dense_levels_agree_with_one_bfs_per_root(share):
    g = kronecker.generate(SPEC, 2**33 + 5)
    roots = np.flatnonzero(g.out_degree > 0)[:64]
    got = load.dense_levels(g, roots, share)
    assert list(got) == [_dense_levels_one(g, r, share) for r in roots]


def test_fresh_roots_each_sent_once_and_warmed_on_a_copy():
    t = {"params": {"x": 1},
         "roots": {"rule": "out_degree_positive", "count": 5}}
    p = load.plan(t, ARCS, 7)
    window = [next(p.window) for _ in range(7)]
    roots = [q["root"] for q in window[:5]]
    assert len(set(roots)) == 5 and all(DEG[r] > 0 for r in roots)
    assert [q["root"] for q in window[5:]] == roots[:2]  # starts over
    assert all(q["x"] == 1 for q in window + p.warmup)
    # the graph itself gets one root the window never sends
    assert len(p.warmup) == 1 and p.warmup[0]["root"] not in roots
    assert DEG[p.warmup[0]["root"]] > 0
    # the copy gets each window root under the permutation
    assert sorted(p.relabel) == list(range(12))
    assert [q["root"] for q in p.warmup_relabeled] == [
        p.relabel[r] for r in roots]
    again = load.plan(t, ARCS, 7)
    assert [next(again.window)["root"] for _ in range(5)] == roots
    other = load.plan(t, ARCS, 2**40 + 7)
    assert [next(other.window)["root"] for _ in range(5)] != roots


def test_relabeled_copy_has_the_same_frontiers():
    g = kronecker.generate(SPEC, 2**33 + 9)
    t = {"roots": {"rule": "out_degree_positive", "count": 6}}
    p = load.plan(t, g, 3)
    twin = Arcs(g.n, p.relabel[g.src], p.relabel[g.dst])
    for q, w in zip([next(p.window) for _ in range(6)], p.warmup_relabeled):
        for share in (0.01, 0.1, 0.25):
            assert (_dense_levels_one(g, q["root"], share)
                    == _dense_levels_one(twin, w["root"], share))


def test_strata_fix_the_mix_of_traversal_shapes():
    g = kronecker.generate(SPEC, 2**33 + 5)
    t = {"roots": {"rule": "out_degree_positive", "count": 8,
                   "strata": {"share": 0.25, "block": {"1": 1, "2": 1}}}}
    for seed in (1, 2**35):
        p = load.plan(t, g, seed)
        roots = [next(p.window)["root"] for _ in range(8)]
        assert len(set(roots)) == 8
        shapes = load.dense_levels(g, roots, 0.25)
        for b in range(4):  # every block holds one root of each stratum
            assert sorted(shapes[2 * b:2 * b + 2]) == [1, 2]
    t["roots"]["strata"]["block"] = {"5": 1}
    with pytest.raises(ValueError):
        load.plan(t, g, 1)
    t["roots"]["strata"]["block"] = {"1": 1, "2": 1}
    t["roots"]["count"] = 7
    with pytest.raises(ValueError):
        load.plan(t, g, 1)


def test_no_roots_repeats_the_params():
    p = load.plan({"params": {"iters": 2}}, ARCS, 1)
    assert p.warmup == [{"iters": 2}] and next(p.window) == {"iters": 2}
    assert p.relabel is None and p.warmup_relabeled == []


def _instant(params):
    f = concurrent.futures.Future()
    f.set_result(params["i"])
    return f


def test_one_client_waits_for_each_answer_before_the_next():
    ex = concurrent.futures.ThreadPoolExecutor(2)

    def submit(params):
        def work():
            time.sleep(0.01)
            return params["i"]
        return ex.submit(work)

    try:
        qs = load.drive(submit, ({"i": i} for i in range(5)),
                        keep=lambda r: r * 10)
    finally:
        ex.shutdown()
    assert [q.result for q in qs] == [0, 10, 20, 30, 40]
    assert [q.index for q in qs] == list(range(5))
    assert all(a.t_done <= b.t_submit for a, b in zip(qs, qs[1:]))


def test_window_closes_and_failures_are_counted():
    calls = []

    def submit(params):
        calls.append(params["i"])
        if params["i"] == 1:
            raise RuntimeError("refused")
        time.sleep(0.02)
        return _instant(params)

    qs = load.drive(submit, ({"i": i} for i in range(10**6)),
                    until=time.perf_counter() + 0.2)
    assert 3 <= len(qs) < 50
    assert [q.error is not None for q in qs][:2] == [False, True]
    assert all(q.settled.is_set() for q in qs)


def test_unknown_rules_are_refused():
    with pytest.raises(ValueError):
        load.plan({"roots": {"rule": "by_name", "count": 1}}, ARCS, 1)


def test_rate_counts_the_running_query_by_its_share():
    from bench import run

    def q(t_submit, t_done, error=None):
        qy = load.Query(0, {}, t_submit=t_submit, t_done=t_done, error=error)
        qy.settled.set()
        return qy

    never = load.Query(0, {}, t_submit=9.0)
    sent = [q(0.0, 4.0), q(4.0, 8.0), q(8.0, 12.0), q(1.0, 2.0, "refused"),
            never]
    # two done by t1 = 10, and half of the third's 4 s fell inside
    assert run.completed_share(sent, 10.0) == pytest.approx(2.5)
    assert run.completed_share(sent, 12.0) == pytest.approx(3.0)
