"""The benchmark's host references and controls on a tiny graph: the
references agree with the served program, and every control is called
wrong by the comparison a run makes."""
import numpy as np
import pytest

import repro
from bench import load
from bench.graphs import kronecker
from bench.refs import bfs as ref_bfs
from bench.refs import pagerank as ref_pr
from bench.controls import bfs as ctl_bfs
from bench.controls import pagerank as ctl_pr
from repro.graph.storage import GraphData

SPEC = {"scale": 8, "edge_factor": 8, "a": 0.57, "b": 0.19, "c": 0.19,
        "permute": True, "undirected": False}
PR_PARAMS = {"iters": 10, "damp": 0.85}


@pytest.fixture(scope="module")
def arcs():
    return kronecker.generate(SPEC, 2**33 + 3)


@pytest.fixture(scope="module")
def roots(arcs):
    plan = load.plan({"roots": {"rule": "out_degree_positive", "count": 4}},
                     arcs, 5)
    return [next(plan.window)["root"] for _ in range(4)]


@pytest.fixture(scope="module")
def served(arcs, roots):
    g = GraphData(arcs.n, arcs.src, arcs.dst)
    with repro.serve(False) as svc:
        levels = [np.asarray(svc.run("bfs", g, root=r).properties["old_level"])
                  for r in roots]
        rank = np.asarray(svc.run("pagerank", g, **PR_PARAMS).properties["rank"])
    return levels, rank


def test_bfs_reference_matches_program(arcs, roots, served):
    for r, got in zip(roots, served[0]):
        want = ref_bfs.reference(arcs, {"root": r})
        assert want[r] == 1
        assert ref_bfs.gap(got, want) == 0


def test_pagerank_reference_matches_program(arcs, served):
    want = ref_pr.reference(arcs, PR_PARAMS)
    assert want.min() >= (1 - PR_PARAMS["damp"]) / arcs.n - 1e-15
    assert ref_pr.gap(served[1], want) <= ref_pr.LIMIT


def test_bfs_control_is_wrong(arcs, roots):
    for r in roots:
        p = {"root": r}
        assert ref_bfs.gap(ctl_bfs.answer(arcs, p),
                           ref_bfs.reference(arcs, p)) > ref_bfs.LIMIT


def test_pagerank_control_is_wrong(arcs):
    want = ref_pr.reference(arcs, PR_PARAMS)
    assert ref_pr.gap(ctl_pr.answer(arcs, PR_PARAMS), want) > ref_pr.LIMIT


def test_gap_of_a_malformed_answer():
    want = np.array([1, 2, -1])
    assert ref_bfs.gap(np.array([1, 2]), want) == 3
    assert ref_pr.gap(np.array([np.nan, 1.0, 1.0]),
                      np.array([1.0, 1.0, 1.0])) == float("inf")


@pytest.mark.parametrize("undirected", [False, True])
@pytest.mark.parametrize("k", [1, 63, 64, 65])
def test_bfs_batched_reference_equals_scipy(undirected, k):
    spec = dict(SPEC, scale=9, edge_factor=4, undirected=undirected)
    g = kronecker.generate(spec, 2**33 + 11 + undirected)
    roots = np.random.default_rng(k).choice(g.n, k, replace=False)
    params = [{"root": int(r)} for r in roots]
    got = list(ref_bfs.reference_batch(g, params))
    assert len(got) == k
    want = [ref_bfs.reference(g, p) for p in params]
    for p, a, b in zip(params, got, want):
        assert a.dtype == np.int32 and a[p["root"]] == 1
        assert np.array_equal(a, b) and ref_bfs.gap(a, b) == 0
    assert (np.concatenate(want) == -1).any()  # some vertices unreached
