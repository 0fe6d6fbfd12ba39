"""chip_smoke.py's phases and host references, on the CPU at a small size.

The script itself only runs on a TPU; these tests drive the same phase
functions on a small R19 (scale 0.01) so its logic and its references are
checked on every change.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import networkx as nx
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
SCRIPT = REPO / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def graphs(smoke):
    return smoke.build_graphs(scale=0.01, seed=0)


def test_small_r19_shape(graphs):
    g, gw = graphs
    assert (g.n_vertices, g.n_edges) == (8192, 172032)
    np.testing.assert_array_equal(g.src, gw.src)
    np.testing.assert_array_equal(g.dst, gw.dst)
    assert gw.weights.min() >= 1 and gw.weights.max() < 64


def test_references_match_networkx(smoke):
    """The scipy/numpy references agree with networkx on a tiny graph."""
    g, gw = smoke.build_graphs(scale=0.0005, seed=3)
    nxg = nx.MultiDiGraph()
    nxg.add_nodes_from(range(g.n_vertices))
    nxg.add_weighted_edges_from(zip(gw.src.tolist(), gw.dst.tolist(),
                                    gw.weights.tolist()))
    root = smoke.pick_roots(g, 1)[0]
    hops = nx.single_source_shortest_path_length(nxg, root)
    want = np.full(g.n_vertices, -1)
    for v, d in hops.items():
        want[v] = d + 1
    np.testing.assert_array_equal(smoke.ref_bfs_levels(g, [root])[0], want)
    dist = nx.single_source_dijkstra_path_length(nxg, root)
    want = np.full(g.n_vertices, smoke.SSSP_INF)
    for v, d in dist.items():
        want[v] = d
    np.testing.assert_array_equal(smoke.ref_sssp(gw, root), want)
    labels = smoke.ref_wcc(g)
    comps = list(nx.weakly_connected_components(nxg))
    assert len(set(labels.tolist())) == len(comps)
    for c in comps:
        assert len({int(labels[v]) for v in c}) == 1


def test_partition_check_rejects_merged_components(smoke):
    want = np.array([0, 0, 1, 1, 2])
    smoke.check_partition("same", np.array([7, 7, 3, 3, 9]), want)
    with pytest.raises(smoke.CheckFailed):
        smoke.check_partition("merged", np.array([7, 7, 7, 7, 9]), want)
    with pytest.raises(smoke.CheckFailed):
        smoke.check_partition("split", np.array([7, 8, 3, 3, 9]), want)


def test_one_chip_phases_on_cpu(smoke, graphs, tmp_path, capsys):
    g, gw = graphs
    out = smoke.run_one_chip(g, gw, tmp_path / "store")
    assert out["pallas_interpret"] is True  # CPU: Pallas interprets
    lines = capsys.readouterr().out.splitlines()
    checks = [ln for ln in lines if ln.startswith("check ")]
    assert checks and all(": PASS" in ln for ln in checks), checks
    for name in ("bfs root=", "sssp root=", "pagerank", "wcc",
                 "burst formed a batch", "burst took the ms-bfs path",
                 "pallas bfs root=", "warm start loaded the artifact",
                 "no executable re-lowered"):
        assert any(ln.startswith(f"check {name}") for ln in checks), name


def test_distributed_phase_on_four_cpu_devices(subproc, tmp_path):
    out = subproc(f"""
import sys
from pathlib import Path
sys.path.insert(0, {str(REPO)!r})
import chip_smoke as cs
g, _ = cs.build_graphs(scale=0.01)
cs.run_distributed(g, Path({str(tmp_path / "store")!r}), 4)
print("DIST-SMOKE-OK")
""", devices=4)
    assert "DIST-SMOKE-OK" in out
    assert "check one bucket slice per device: PASS" in out


def test_main_refuses_the_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "'cpu'" in captured.err
    assert '"ok"' not in captured.out


def test_script_alone_fails(tmp_path):
    """Without the rest of the checkout the script exits non-zero and
    prints no result line."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, capture_output=True,
        text=True, timeout=120, env=env,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
