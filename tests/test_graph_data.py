"""Graph substrate property tests (storage, partitioning, generators)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.graph import generators
from repro.graph.datasets import TABLE_II, make_dataset


@settings(max_examples=20, deadline=None)
@given(
    n_v=st.integers(2, 300),
    n_e=st.integers(1, 2000),
    n_parts=st.integers(1, 12),
    seed=st.integers(0, 2**31 - 1),
)
def test_partition_by_dst_properties(n_v, n_e, n_parts, seed):
    g = generators.uniform_random(n_v, n_e, seed=seed)
    pe = g.partition_by_dst(n_parts)
    # the edge order is a permutation
    assert sorted(pe.edge_order.tolist()) == list(range(g.n_edges))
    p_eff = pe.n_partitions
    for p in range(p_eff):
        src, dst, _ = pe.partition_edges(p)
        lo, hi = pe.vertex_bounds[p], pe.vertex_bounds[p + 1]
        # every dst lands in the partition's vertex range
        assert ((dst >= lo) & (dst < hi)).all()
        # ascending src inside each partition (paper §III-D)
        assert (np.diff(src) >= 0).all()


@settings(max_examples=20, deadline=None)
@given(n_v=st.integers(2, 200), n_e=st.integers(1, 1500), seed=st.integers(0, 2**31 - 1))
def test_csr_roundtrip(n_v, n_e, seed):
    g = generators.uniform_random(n_v, n_e, seed=seed)
    indptr, indices, eids = g.csr
    assert indptr[-1] == g.n_edges
    # CSR reconstructs the edge multiset
    recon = set()
    for v in range(n_v):
        for i in range(indptr[v], indptr[v + 1]):
            recon.add((v, int(indices[i]), int(eids[i])))
    orig = {(int(s), int(d), i) for i, (s, d) in enumerate(zip(g.src, g.dst))}
    assert recon == orig


def test_relabel_by_degree_preserves_structure():
    g = generators.power_law(200, 1500, seed=3)
    g2, old2new = g.relabel_by_degree()
    # edges map 1:1
    assert g2.n_edges == g.n_edges
    np.testing.assert_array_equal(old2new[g.src], g2.src)
    np.testing.assert_array_equal(old2new[g.dst], g2.dst)
    # hubs first: new id 0 has the max total degree
    tot = g.out_degree.astype(np.int64) + g.in_degree
    assert tot[g.degree_rank[0]] == tot.max()
    d2 = g2.out_degree.astype(np.int64) + g2.in_degree
    assert d2[0] == tot.max()


def test_dst_sort_perm():
    g = generators.uniform_random(100, 800, seed=4)
    perm = g.dst_sort_perm
    assert (np.diff(g.dst[perm]) >= 0).all()


def test_star_graph_hub_detection():
    g = generators.star(64)
    assert g.degree_rank[0] == 0  # the hub


@pytest.mark.parametrize("short", list(TABLE_II))
def test_table_ii_datasets_scaled(short):
    g = make_dataset(short, scale=0.001, seed=0)
    spec = TABLE_II[short]
    assert g.n_vertices >= 64
    assert g.n_edges >= 256
    # degree ratio approximates the published average
    target = spec.n_edges / spec.n_vertices
    got = g.n_edges / g.n_vertices
    assert 0.3 * target <= got <= 3 * target


def test_rmat_skew():
    g = generators.rmat(10, 16, seed=0)
    deg = np.sort(g.out_degree)[::-1]
    # power-law-ish: top 1% of vertices own >5% of edges
    top = deg[: max(1, len(deg) // 100)].sum()
    assert top / g.n_edges > 0.05


def test_edge_list_loader(tmp_path):
    p = tmp_path / "g.txt"
    p.write_text("# comment\n0 1 2.5\n1 2 1.0\n2 0 3.5\n")
    g = generators.load_edge_list(str(p))
    assert g.n_vertices == 3 and g.n_edges == 3 and g.weighted
    np.testing.assert_allclose(g.weights, [2.5, 1.0, 3.5])


# ---------------------------------------------------------------------------
# device-ABI dtype stability (int32 CSR/CSC) + shape-bucket padding
# ---------------------------------------------------------------------------


def test_csr_csc_all_int32():
    """Regression: indptr used to be int64 while indices/edge_perm were
    int32 — device buffers and AOT shape signatures need one stable ABI."""
    g = generators.power_law(200, 1500, seed=3)
    for indptr, indices, eids in (g.csr, g.csc):
        assert indptr.dtype == np.int32, "indptr must be int32"
        assert indices.dtype == np.int32
        assert eids.dtype == np.int32
    assert g.src.dtype == np.int32 and g.dst.dtype == np.int32
    assert g.csr[0][-1] == g.n_edges and g.csc[0][-1] == g.n_edges


def test_indptr_overflow_guard():
    from repro.graph.storage import MAX_INT32_EDGES, _indptr_from_degrees

    deg = np.array([1, 2, 3], dtype=np.int64)
    out = _indptr_from_degrees(deg, 6)
    assert out.dtype == np.int32
    np.testing.assert_array_equal(out, [0, 1, 3, 6])
    with pytest.raises(OverflowError, match="int32 indptr"):
        _indptr_from_degrees(deg, MAX_INT32_EDGES)


def test_pad_to_bucket():
    g = generators.power_law(100, 700, seed=1, weighted=True)
    p = g.pad_to(128, 768)
    assert (p.n_vertices, p.n_edges) == (128, 768)
    # real edges untouched, padding edges are self-loops on the last vertex
    np.testing.assert_array_equal(p.src[:700], g.src)
    np.testing.assert_array_equal(p.dst[:700], g.dst)
    assert (p.src[700:] == 127).all() and (p.dst[700:] == 127).all()
    np.testing.assert_array_equal(p.weights[:700], g.weights)
    # real vertices keep their degrees
    np.testing.assert_array_equal(p.out_degree[:100], g.out_degree)
    np.testing.assert_array_equal(p.in_degree[:100], g.in_degree)
    # no-op and error cases
    assert g.pad_to(100, 700) is g
    with pytest.raises(ValueError, match="smaller"):
        g.pad_to(50, 700)
    with pytest.raises(ValueError, match="padding vertex"):
        g.pad_to(100, 768)
