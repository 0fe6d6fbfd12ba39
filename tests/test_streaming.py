"""Streaming graph updates + incremental recomputation.

Acceptance criteria of the streaming PR:

* ``GraphDelta`` + ``GraphData.apply_updates`` mutate in place through the
  ``pad_to`` padding slack — tombstoned removals, free-slot additions,
  logical-count maintenance, periodic compaction — and never change the
  physical shape (same GraphShape bucket);
* logical vs padded counts: globally-normalized programs (PageRank's
  ``vertices.size()``) agree between padded and unpadded runs;
* ``GraphShape.bucket_for`` rounds to shared geometric buckets;
* incremental re-convergence is **bit-identical** to a from-scratch run for
  monotone programs (BFS / SSSP / WCC) after random additions-only deltas,
  across passes default/none and the local + distributed backends, and
  PageRank-class programs transparently fall back to a full recompute;
* in-bucket updates perform no new lowering (Accelerator-backed sessions
  keep ``stats.compile_time_s == 0`` across updates);
* concurrent SessionPool queries during ``update()`` never observe a torn
  version: every result is pinned to the version it was admitted under.
"""
import threading

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro
from repro.algorithms import sources
from repro.core import CompileOptions
from repro.core.accelerator import GraphShape
from repro.core.passes import analyze_incremental
from repro.graph import generators
from repro.graph.storage import GraphData, GraphDelta, GraphUpdateError
from repro.streaming import StreamingSession


def _bucketed(n_vertices=300, n_edges=1800, *, weighted=False, seed=1):
    g = generators.uniform_random(n_vertices, n_edges, weighted=weighted,
                                  seed=seed)
    shape = GraphShape.bucket_for(g.n_vertices, g.n_edges, weighted=weighted)
    return g.pad_to(shape.n_vertices, shape.n_edges)


def _random_delta(rng, graph, k, *, weighted=False):
    lv = graph.n_vertices_logical
    edges = rng.integers(0, lv, size=(k, 2)).astype(np.int32)
    w = rng.integers(1, 64, size=k).astype(np.float32) if weighted else None
    return GraphDelta(added_edges=edges, added_weights=w)


def _assert_same_result(a, b):
    assert set(a.properties) == set(b.properties)
    for name in a.properties:
        x, y = np.asarray(a.properties[name]), np.asarray(b.properties[name])
        assert x.dtype == y.dtype, name
        np.testing.assert_array_equal(x, y, err_msg=name)
    assert a.host_env == b.host_env


# ---------------------------------------------------------------------------
# GraphDelta + apply_updates (storage layer)
# ---------------------------------------------------------------------------


def test_graph_delta_validation_and_introspection():
    d = GraphDelta(added_edges=[(0, 1), (2, 3)], removed_edges=[(4, 5)])
    assert d.n_added == 2 and d.n_removed == 1
    assert not d.additions_only
    assert sorted(d.endpoints().tolist()) == [0, 1, 2, 3, 4, 5]
    assert GraphDelta(added_edges=[(7, 8)]).additions_only
    with pytest.raises(ValueError):
        GraphDelta(added_edges=np.zeros((2, 3)))
    with pytest.raises(ValueError):
        GraphDelta(added_edges=[(0, 1)], added_weights=[1.0, 2.0])


def test_apply_updates_add_and_remove_in_place():
    g = GraphData(4, src=[0, 1, 2], dst=[1, 2, 3]).pad_to(6, 8)
    assert g.n_vertices_logical == 4 and g.n_edges_logical == 3
    buffers = (g.src, g.dst)
    v0 = g.version

    g.apply_updates(GraphDelta(added_edges=[(3, 0), (0, 2)]))
    assert g.n_edges_logical == 5 and g.n_edges == 8  # physical unchanged
    assert g.src is buffers[0] and g.dst is buffers[1]  # in place
    assert g.version == v0 + 1
    real = ~g._free_slot_mask()
    pairs = set(zip(g.src[real].tolist(), g.dst[real].tolist()))
    assert pairs == {(0, 1), (1, 2), (2, 3), (3, 0), (0, 2)}

    g.apply_updates(GraphDelta(removed_edges=[(1, 2)]))
    assert g.n_edges_logical == 4
    real = ~g._free_slot_mask()
    pairs = set(zip(g.src[real].tolist(), g.dst[real].tolist()))
    assert (1, 2) not in pairs and len(pairs) == 4
    # tombstones are pad-vertex self-loops: degree caches see them as pad
    assert int(g.out_degree[:4].sum()) == 4


def test_apply_updates_errors():
    g = GraphData(4, src=[0, 1, 2], dst=[1, 2, 3]).pad_to(6, 8)
    with pytest.raises(GraphUpdateError, match="vertex"):
        g.apply_updates(GraphDelta(added_edges=[(0, 99)]))
    with pytest.raises(GraphUpdateError, match="present"):
        g.apply_updates(GraphDelta(removed_edges=[(3, 3)]))
    with pytest.raises(GraphUpdateError, match="bucket_for"):
        g.apply_updates(GraphDelta(added_edges=[(0, 1)] * 50))
    # failed updates must not partially mutate
    assert g.n_edges_logical == 3
    # unpadded graphs have no free slots at all
    flat = GraphData(4, src=[0, 1, 2], dst=[1, 2, 3])
    with pytest.raises(GraphUpdateError):
        flat.apply_updates(GraphDelta(added_edges=[(0, 3)]))


def test_apply_updates_duplicate_edges_and_compact():
    g = GraphData(4, src=[0, 1, 1, 2], dst=[1, 2, 2, 3]).pad_to(6, 12)
    # duplicate (1, 2): removal takes out exactly one instance per request
    g.apply_updates(GraphDelta(removed_edges=[(1, 2)]))
    real = ~g._free_slot_mask()
    assert list(zip(g.src[real], g.dst[real])).count((1, 2)) == 1
    g.apply_updates(GraphDelta(added_edges=[(3, 0)]), compact=True)
    # after compaction every real edge precedes every free slot
    real = ~g._free_slot_mask()
    assert real[: g.n_edges_logical].all() and not real[g.n_edges_logical:].any()


def test_logical_counts_propagate_through_transforms():
    g = generators.uniform_random(50, 300, weighted=True, seed=0)
    p = g.pad_to(64, 512)
    assert (p.n_vertices_logical, p.n_edges_logical) == (50, 300)
    assert p.relabel_by_degree()[0].n_vertices_logical == 50
    assert p.with_unit_weights().n_edges_logical == 300


# ---------------------------------------------------------------------------
# GraphShape.bucket_for (satellite: shared geometric buckets)
# ---------------------------------------------------------------------------


def test_bucket_for_geometric_rounding():
    s = GraphShape.bucket_for(300, 1800)
    assert s.n_vertices >= 300 * 1.12 and s.n_edges >= 1800 * 1.12
    # deterministic + shared across nearby sizes
    assert s == GraphShape.bucket_for(300, 1800)
    assert s == GraphShape.bucket_for(310, 1850)
    # monotone in both arguments
    big = GraphShape.bucket_for(3000, 18000)
    assert big.n_vertices > s.n_vertices and big.n_edges > s.n_edges
    assert GraphShape.bucket_for(10, 50, weighted=True).weighted
    # padding edges requires at least one pad vertex to hang self-loops on
    exact_v = GraphShape.bucket_for(1024, 100)
    assert exact_v.n_vertices > 1024


def test_bucket_for_pads_and_binds():
    g = generators.uniform_random(200, 1200, seed=3)
    shape = GraphShape.bucket_for(g.n_vertices, g.n_edges)
    padded = g.pad_to(shape.n_vertices, shape.n_edges)
    assert GraphShape.of(padded) == shape
    acc = repro.compile(sources.BFS_ECP).lower(graph=g, bucket=True)
    assert acc.shape == shape
    r = acc.bind(padded).run(root=1)
    assert r.stats.compile_time_s >= 0


# ---------------------------------------------------------------------------
# Logical vs padded counts (satellite: PageRank teleport mass)
# ---------------------------------------------------------------------------


def test_pagerank_padded_matches_unpadded():
    """vertices.size() must read the LOGICAL count: 1/|V| teleport mass and
    the rank vector on real vertices agree between padded and unpadded runs
    (allclose: padding changes float segment-reduction partition sizes)."""
    g = generators.uniform_random(120, 700, seed=2)
    program = repro.compile(sources.PAGERANK)
    base = program.bind(g).run(iters=10)
    padded = _bucketed(120, 700, seed=2)
    padded_r = program.bind(padded).run(iters=10)
    np.testing.assert_allclose(
        np.asarray(padded_r.properties["rank"])[:120],
        np.asarray(base.properties["rank"]),
        rtol=1e-5, atol=1e-7,
    )


# ---------------------------------------------------------------------------
# Monotonicity analysis (MIR-level)
# ---------------------------------------------------------------------------


MONOTONE_EXPECT = {
    "BFS_ECP": ("unit_distance", True),
    "BFS_HYBRID": ("unit_distance", True),
    "SSSP": ("weighted_distance", True),
    "WCC": ("label", True),
    "PAGERANK": (None, False),
    "PPR": (None, False),
    "CGAW": (None, False),
    "KCORE": (None, False),
}


@pytest.mark.parametrize("name", sorted(MONOTONE_EXPECT))
def test_analyze_incremental_verdicts(name):
    kind, monotone = MONOTONE_EXPECT[name]
    info = analyze_incremental(repro.compile(getattr(sources, name)).module)
    assert info.monotone is monotone, info.reasons
    if monotone:
        assert info.incremental_ok and info.template.kind == kind
    else:
        assert not info.incremental_ok and info.reasons


# ---------------------------------------------------------------------------
# Incremental == from-scratch (the tentpole equivalence)
# ---------------------------------------------------------------------------


STREAM_CASES = {
    "bfs": (sources.BFS_ECP, {"root": 3}, False),
    "sssp": (sources.SSSP, {"root": 3}, True),
    "wcc": (sources.WCC, {}, False),
    "pagerank": (sources.PAGERANK, {"iters": 6}, False),
}


@pytest.mark.parametrize("passes", ["default", "none"])
@pytest.mark.parametrize("algo", sorted(STREAM_CASES))
def test_incremental_matches_from_scratch_local(algo, passes):
    src, params, weighted = STREAM_CASES[algo]
    program = repro.compile(src, CompileOptions(passes=passes))
    rng = np.random.default_rng(11)
    ss = StreamingSession(program, _bucketed(weighted=weighted))
    try:
        ss.run(**params)
        for _ in range(3):
            ss.update(_random_delta(rng, ss.graph, 20, weighted=weighted))
            got = ss.run(**params)
            ref = program.bind(ss.graph).run(**params)
            _assert_same_result(got, ref)
            assert got.version == ss.version
        if algo == "pagerank":
            assert ss.incremental_runs == 0 and ss.full_runs == 4
        else:
            assert ss.incremental_runs == 3 and ss.full_runs == 1
    finally:
        ss.close()


def test_incremental_matches_from_scratch_distributed(subproc):
    out = subproc(
        """
import numpy as np, repro
from repro.algorithms import sources
from repro.core.accelerator import GraphShape
from repro.graph import generators
from repro.graph.storage import GraphDelta
from repro.streaming import StreamingSession

rng = np.random.default_rng(5)
for src, params, weighted in [
    (sources.BFS_ECP, {"root": 2}, False),
    (sources.SSSP, {"root": 2}, True),
    (sources.WCC, {}, False),
]:
    g = generators.uniform_random(160, 900, weighted=weighted, seed=4)
    shape = GraphShape.bucket_for(g.n_vertices, g.n_edges, weighted=weighted)
    program = repro.compile(src)
    ss = StreamingSession(program, g.pad_to(shape.n_vertices, shape.n_edges),
                          backend="distributed")
    ss.run(**params)
    for _ in range(2):
        lv = ss.graph.n_vertices_logical
        e = rng.integers(0, lv, size=(12, 2)).astype(np.int32)
        w = rng.integers(1, 64, size=12).astype(np.float32) if weighted else None
        ss.update(GraphDelta(added_edges=e, added_weights=w))
        got = ss.run(**params)
        ref = program.bind(ss.graph, backend="distributed").run(**params)
        for p in ref.properties:
            np.testing.assert_array_equal(
                np.asarray(got.properties[p]), np.asarray(ref.properties[p]),
                err_msg=p)
        assert got.host_env == ref.host_env
    assert ss.incremental_runs == 2
    ss.close()
print("DIST-STREAM-OK")
"""
    )
    assert "DIST-STREAM-OK" in out


def test_removals_fall_back_to_full_recompute():
    program = repro.compile(sources.BFS_ECP)
    ss = StreamingSession(program, _bucketed())
    try:
        ss.run(root=3)
        real = np.flatnonzero(~ss.graph._free_slot_mask())[:4]
        rem = np.stack([ss.graph.src[real], ss.graph.dst[real]], axis=1)
        ss.update(GraphDelta(removed_edges=rem))
        got = ss.run(root=3)
        ref = program.bind(ss.graph).run(root=3)
        _assert_same_result(got, ref)
        assert ss.incremental_runs == 0 and ss.full_runs == 2
    finally:
        ss.close()


def test_rebucket_on_overflow_is_transparent():
    program = repro.compile(sources.BFS_ECP)
    ss = StreamingSession(program, _bucketed())
    try:
        slack = ss.graph.n_edges - ss.graph.n_edges_logical
        rng = np.random.default_rng(0)
        ss.update(_random_delta(rng, ss.graph, slack + 16))
        assert ss.rebuckets == 1 and ss.version == 1
        got = ss.run(root=3)
        ref = program.bind(ss.graph).run(root=3)
        _assert_same_result(got, ref)
    finally:
        ss.close()


def test_same_version_cache_hit_and_repair_reuse():
    program = repro.compile(sources.BFS_ECP)
    ss = StreamingSession(program, _bucketed())
    try:
        first = ss.run(root=3)
        assert ss.run(root=3) is first and ss.cache_hits == 1
        ss.update(_random_delta(np.random.default_rng(1), ss.graph, 8))
        repaired = ss.run(root=3)
        assert repaired is not first and ss.incremental_runs == 1
        assert ss.run(root=3) is repaired  # repaired result is re-cached
    finally:
        ss.close()


# ---------------------------------------------------------------------------
# No re-lowering across in-bucket updates (accelerator warm path)
# ---------------------------------------------------------------------------


def test_in_bucket_update_performs_no_new_lowering():
    g = generators.uniform_random(200, 1200, seed=6)
    program = repro.compile(sources.BFS_ECP)
    acc = program.lower(graph=g, bucket=True)
    padded = g.pad_to(acc.shape.n_vertices, acc.shape.n_edges)
    ss = StreamingSession(program, padded, accelerator=acc)
    try:
        ss.run(root=0)  # warm-up
        rng = np.random.default_rng(2)
        for step in range(3):
            ss.update(_random_delta(rng, ss.graph, 10))
            full = ss.run(root=step + 1)  # unseen param: full run, warm library
            assert full.stats.compile_time_s == 0.0
            inc = ss.run(root=0)  # repaired: pure host work
            assert inc.stats.compile_time_s == 0.0
    finally:
        ss.close()


# ---------------------------------------------------------------------------
# Concurrency: SessionPool queries racing update()
# ---------------------------------------------------------------------------


def test_concurrent_queries_never_observe_torn_versions():
    program = repro.compile(sources.BFS_ECP)
    ss = StreamingSession(program, _bucketed(), pool_size=2, compact_every=0)
    try:
        ss.warmup(root=0)
        rng = np.random.default_rng(3)
        errors = []
        done = threading.Event()

        def updater():
            try:
                for _ in range(6):
                    ss.update(_random_delta(rng, ss.graph, 6))
            except Exception as exc:  # pragma: no cover
                errors.append(exc)
            finally:
                done.set()

        t = threading.Thread(target=updater)
        t.start()
        futures = []
        while not done.is_set():
            futures.extend(ss.submit(root=r % 5) for r in range(4))
            for f in futures[-4:]:
                f.result()
        t.join()
        assert not errors
        results = [f.result() for f in futures]
        assert {r.version for r in results} <= set(range(ss.version + 1))
        # quiesced: current-version answers equal a fresh independent bind
        _assert_same_result(ss.run(root=1), program.bind(ss.graph).run(root=1))
        assert ss.updates == 6
    finally:
        ss.close()


# ---------------------------------------------------------------------------
# Property-based equivalence (hypothesis when available)
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    n_deltas=st.integers(min_value=1, max_value=3),
    k=st.integers(min_value=1, max_value=30),
)
def test_random_deltas_preserve_equivalence(seed, n_deltas, k):
    rng = np.random.default_rng(seed)
    algo = ["bfs", "sssp", "wcc"][seed % 3]
    src, params, weighted = STREAM_CASES[algo]
    program = repro.compile(src)
    ss = StreamingSession(program, _bucketed(150, 900, weighted=weighted,
                                             seed=seed % 7))
    try:
        ss.run(**params)
        for _ in range(n_deltas):
            ss.update(_random_delta(rng, ss.graph, k, weighted=weighted))
        got = ss.run(**params)
        ref = program.bind(ss.graph).run(**params)
        _assert_same_result(got, ref)
        assert ss.incremental_runs >= 1
    finally:
        ss.close()

