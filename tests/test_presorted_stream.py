"""Dst-ordered edge stream: full-stream commits without a runtime permutation.

Under a shuffle target the Burst Read plan streams edges in burst order
stably sorted by destination, so a dst-lane write commits as one sorted
segment reduction over the stream as it arrives:

* the compiled full-stream kernels gather no more than the raw-scatter
  substrate (PageRank ``computeContrib`` 2 gathers, BFS ``EdgeTraversal`` 1);
* answers agree across substrates (bit for bit where the reduction is
  order-free), also after an in-place graph update and a rebind;
* ``EngineStats.presorted_launches`` counts exactly the full-stream edge
  launches that committed that way.
"""
import re

import numpy as np
import pytest

import repro
from repro.algorithms import sources
from repro.core import Target, backend
from repro.core.accelerator import GraphShape, _scalar_specs, _state_specs
from repro.graph import generators
from repro.graph.storage import GraphDelta


def _full_kernel_gathers(src, kernel, target, graph):
    lib = repro.compile(src).lower(target, GraphShape.of(graph)).library
    shape = lib.shape
    text = lib._generic[kernel].jit_full.lower(
        backend.gb_array_specs(shape.n_vertices, shape.n_edges),
        _state_specs(lib.module, shape),
        _scalar_specs(lib.module, lib.module.kernels[kernel]),
    ).compile().as_text()
    return len(re.findall(r"= \S+ gather\(", text))


@pytest.mark.parametrize("src,kernel,want", [
    (sources.PAGERANK, "computeContrib", 2),
    (sources.BFS_ECP, "EdgeTraversal", 1),
], ids=["pagerank", "bfs"])
def test_full_stream_kernel_gathers_no_permutation(src, kernel, want):
    g = generators.power_law(512, 4096, seed=3)
    got = _full_kernel_gathers(src, kernel, Target(), g)
    assert got == want
    assert got <= _full_kernel_gathers(src, kernel, Target(shuffle=False), g)


def test_bind_streams_edges_in_dst_order():
    g = generators.power_law(300, 2400, seed=4)
    for target in (Target(), Target(burst=False)):
        gb = backend._graph_bindings(g, None, target)
        order, dst = np.asarray(gb["order"]), np.asarray(gb["dst"])
        assert gb["dst_sorted"]
        assert np.all(np.diff(dst) >= 0)
        np.testing.assert_array_equal(dst, g.dst[order])
        np.testing.assert_array_equal(np.sort(order), np.arange(g.n_edges))
    # burst keeps ascending src inside each destination
    gb = backend._graph_bindings(g, None, Target())
    src, dst = np.asarray(gb["src"]), np.asarray(gb["dst"])
    same_dst = dst[1:] == dst[:-1]
    assert np.all(np.diff(src)[same_dst] >= 0)
    # without shuffle the burst order stays as it was
    gb = backend._graph_bindings(g, None, Target(shuffle=False))
    assert not gb["dst_sorted"]
    np.testing.assert_array_equal(
        np.asarray(gb["order"]), g.partition_by_dst(1).edge_order)
    assert "dst_sort_perm" not in gb


# ---------------------------------------------------------------------------
# answers across substrates
# ---------------------------------------------------------------------------

TARGETS = {
    "default": Target(),
    "no_burst": Target(burst=False),
    "baseline": Target.baseline(),
}

# (source, run params, property, exact): exact where the reduction is
# order-free (min), else float sums compared to a tight tolerance
CASES = {
    "pagerank": (sources.PAGERANK, {"iters": 10}, "rank", False),
    "bfs": (sources.BFS_ECP, {"root": 0}, "old_level", True),
    "sssp": (sources.SSSP, {"root": 0}, "SP", True),
    "cgaw": (sources.CGAW, {}, "weight", False),
}


def _skewed(weighted):
    g = generators.power_law(600, 6000, exponent=1.8, seed=9, weighted=weighted)
    shape = GraphShape.bucket_for(g.n_vertices, g.n_edges, weighted=weighted)
    return g.pad_to(shape.n_vertices, shape.n_edges)


def _agree(results, prop, exact):
    ref = np.asarray(results["baseline"].properties[prop])
    for name, res in results.items():
        got = np.asarray(res.properties[prop])
        if exact:
            np.testing.assert_array_equal(got, ref, err_msg=name)
        else:
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-9,
                                       err_msg=name)


@pytest.mark.parametrize("case", sorted(CASES))
def test_answers_agree_across_substrates(case):
    src, params, prop, exact = CASES[case]
    weighted = case in ("sssp", "cgaw")
    graphs = {name: _skewed(weighted) for name in TARGETS}
    prog = repro.compile(src)
    sessions = {name: prog.bind(graphs[name], target=t)
                for name, t in TARGETS.items()}
    _agree({n: s.run(**params) for n, s in sessions.items()}, prop, exact)

    rng = np.random.default_rng(2)
    lv = graphs["default"].n_vertices_logical
    edges = rng.integers(0, lv, size=(40, 2)).astype(np.int32)
    w = rng.integers(1, 64, size=40).astype(np.float32) if weighted else None
    after = {}
    for name, sess in sessions.items():
        graphs[name].apply_updates(GraphDelta(added_edges=edges, added_weights=w))
        sess.refresh_graph(graphs[name])
        after[name] = sess.run(**params)
    _agree(after, prop, exact)
    # the rebound session answers as a fresh bind of the updated graph
    fresh = prog.bind(graphs["default"], target=Target()).run(**params)
    np.testing.assert_array_equal(after["default"].properties[prop],
                                  fresh.properties[prop])


# ---------------------------------------------------------------------------
# EngineStats.presorted_launches
# ---------------------------------------------------------------------------


def test_presorted_launches_pagerank():
    g = generators.power_law(400, 3200, seed=6)
    prog = repro.compile(sources.PAGERANK)
    st = prog.bind(g).run(iters=10).stats
    assert st.presorted_launches == 10
    assert prog.bind(g, target=Target(shuffle=False)).run(
        iters=10).stats.presorted_launches == 0


def test_presorted_launches_bfs_counts_dense_levels_only():
    g = generators.power_law(2000, 16000, seed=5)
    root = int(np.argmax(g.out_degree))
    prog = repro.compile(sources.BFS_ECP)
    st = prog.bind(g).run(root=root).stats
    dense = st.kernel_launches["EdgeTraversal"] - st.compacted_launches
    assert st.compacted_launches > 0 and dense > 0
    assert st.presorted_launches == dense
    assert prog.bind(g, target=Target(shuffle=False)).run(
        root=root).stats.presorted_launches == 0


def test_presorted_launches_merge_in_batched_runs():
    g = generators.power_law(400, 3200, seed=6)
    sess = repro.compile(sources.PAGERANK).lower(graph=g).bind(g)
    results = sess.run_many([{"iters": 10}, {"iters": 10}])
    st = results[0].stats
    assert st.batch_size == 2
    assert st.presorted_launches == 10
