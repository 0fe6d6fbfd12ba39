#!/usr/bin/env python3
"""Smoke run of the served graph path on a TPU.

Builds the paper's Table II graph R19 (rmat-19-32: 2^19 vertices, 2^24
edges) and a weighted twin from a seed, answers graph queries through
``repro.serve`` and checks every answer against a plain host reference
(numpy/scipy, independent of the code under test).

    python3 chip_smoke.py              # one chip: BFS x2, SSSP, PageRank,
                                       # WCC, an 8-root BFS burst, Pallas
                                       # BFS, and an artifact warm start
    python3 chip_smoke.py --chips 4    # only BFS and PageRank on the
                                       # distributed backend, 4-device mesh

Each phase prints its wall time and each check its result; these are smoke
timings, not benchmark numbers. The last stdout line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. A failed check or
an error in any phase exits non-zero without it, and so does a host where
JAX finds no TPU: nothing runs on the CPU in the chip's place.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import scipy.sparse as sp  # noqa: E402
from scipy.sparse import csgraph  # noqa: E402

import repro  # noqa: E402
from repro.algorithms import sources  # noqa: E402
from repro.core.target import Target  # noqa: E402
from repro.graph import datasets  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

#: artifact store of the smoke's services (listed in .gitignore)
STORE = ROOT / ".smoke_store"
#: SSSP's "unreached" distance, as the SSSP program declares it
SSSP_INF = 1073741823
PAGERANK_ITERS = 10
PAGERANK_DAMP = 0.85
#: The device sums PageRank contributions in float32, in an order other
#: than the float64 reference's. The rounding error of a sum grows with its
#: number of terms (R19 hubs have in-degrees in the tens of thousands) and
#: carries across iterations; 1e-3 relative bounds it with room to spare,
#: and every rank is at least (1 - damp) / |V| > 0, so no absolute term.
PAGERANK_RTOL = 1e-3


class CheckFailed(AssertionError):
    pass


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"check {name}: {'PASS' if ok else 'FAIL'} {detail}".rstrip(), flush=True)
    if not ok:
        raise CheckFailed(f"{name}: {detail}")


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    print(f"phase {name} ...", flush=True)
    yield
    print(f"phase {name}: {time.perf_counter() - t0:.3f}s wall", flush=True)


# ---------------------------------------------------------------------------
# graph and host references
# ---------------------------------------------------------------------------


def build_graphs(scale: float = 1.0, seed: int = 0, weighted: bool = True):
    """R19 and its weighted twin (same edges, integer weights in [1, 64));
    the twin is None when not ``weighted``."""
    g = datasets.make_dataset("R19", scale=scale, seed=seed)
    gw = (datasets.make_dataset("R19", scale=scale, weighted=True, seed=seed)
          if weighted else None)
    return g, gw


def pick_roots(g, n: int, seed: int = 0) -> list:
    """The highest out-degree vertex, then seeded picks among vertices with
    out-edges (distinct)."""
    hub = int(np.argmax(g.out_degree))
    cand = np.flatnonzero(g.out_degree > 0)
    cand = cand[cand != hub]
    rng = np.random.default_rng(seed)
    rest = rng.choice(cand, size=n - 1, replace=False)
    return [hub] + [int(r) for r in rest]


def _adjacency(g) -> sp.csr_matrix:
    n = g.n_vertices
    return sp.csr_matrix(
        (np.ones(g.n_edges, np.float32), (g.src, g.dst)), shape=(n, n)
    )


def ref_bfs_levels(g, roots) -> np.ndarray:
    """BFS_ECP's ``old_level`` per root: root 1, hop count + 1, -1 unreached."""
    d = csgraph.shortest_path(
        _adjacency(g), method="D", directed=True, unweighted=True,
        indices=list(roots),
    )
    return np.where(np.isinf(d), -1, d + 1).astype(np.int64)


def ref_sssp(gw, root: int) -> np.ndarray:
    """SSSP's ``SP``: shortest weighted distance, SSSP_INF unreached.
    Parallel edges keep their smallest weight (a sparse matrix would sum)."""
    n = gw.n_vertices
    key = gw.src.astype(np.int64) * n + gw.dst
    order = np.lexsort((gw.weights, key))
    first = np.ones(len(order), bool)
    first[1:] = key[order][1:] != key[order][:-1]
    sel = order[first]
    a = sp.csr_matrix(
        (gw.weights[sel].astype(np.float64), (gw.src[sel], gw.dst[sel])),
        shape=(n, n),
    )
    d = csgraph.dijkstra(a, directed=True, indices=root)
    return np.where(np.isinf(d), SSSP_INF, d).astype(np.int64)


def ref_wcc(g) -> np.ndarray:
    """A weak-component label per vertex. WCC's ``comp`` labels each
    component by the smallest id it sees, which under the hub-cache
    relabeling is a relabeled id: compare partitions, not label values."""
    _, labels = csgraph.connected_components(
        _adjacency(g), directed=True, connection="weak"
    )
    return labels


def ref_pagerank(g, iters: int = PAGERANK_ITERS,
                 damp: float = PAGERANK_DAMP) -> np.ndarray:
    """PAGERANK's ``rank`` in float64: push rank/outdeg along each edge
    (sources without out-edges push nothing), then teleport (1-damp)/|V|."""
    n = g.n_vertices_logical
    deg = g.out_degree.astype(np.float64)
    has_out = deg[g.src] > 0
    src, dst = g.src[has_out], g.dst[has_out]
    rank = np.full(g.n_vertices, 1.0 / n)
    for _ in range(iters):
        contrib = np.bincount(dst, weights=rank[src] / deg[src],
                              minlength=g.n_vertices)
        rank = (1.0 - damp) / n + damp * contrib
    return rank


def check_exact(name: str, got, want) -> None:
    got = np.asarray(got).astype(np.int64)
    bad = int(np.count_nonzero(got != want))
    check(name, bad == 0, f"({bad} of {len(want)} differ)")


def check_partition(name: str, got, want) -> None:
    """Same components: the labels correspond one to one."""
    got = np.asarray(got).astype(np.int64)
    pairs = len(np.unique(np.stack([got, want]), axis=1)[0])
    n_got, n_want = len(np.unique(got)), len(np.unique(want))
    check(name, pairs == n_got == n_want,
          f"({n_got} components served, {n_want} in the reference, "
          f"{pairs} label pairs)")


def check_pagerank(name: str, got, want) -> None:
    got = np.asarray(got, np.float64)
    rel = float(np.max(np.abs(got - want) / want))
    check(name, rel <= PAGERANK_RTOL,
          f"(max relative error {rel:.3e}, limit {PAGERANK_RTOL:g})")


# ---------------------------------------------------------------------------
# served queries: each takes (graph, service) and returns what it observed
# ---------------------------------------------------------------------------


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _describe(res, wall: float) -> str:
    s = res.stats
    return (f"wall {wall:.3f}s (compile {s.compile_time_s:.3f}s, "
            f"run {s.run_time_s:.3f}s), launches {s.total_launches}, "
            f"supersteps {s.dist_supersteps}, batch {s.batch_size}")


def serve_bfs(g, svc, roots) -> list:
    """One BFS query per root, in turn; returns (levels, stats) per root."""
    levels = []
    for r in roots:
        res, wall = _timed(lambda r=r: svc.run("bfs", g, root=r))
        print(f"  bfs root={r}: {_describe(res, wall)}", flush=True)
        levels.append((np.asarray(res.properties["old_level"]), res.stats))
    return levels


def serve_sssp(gw, svc, root: int):
    res, wall = _timed(lambda: svc.run("sssp", gw, root=root))
    print(f"  sssp root={root}: {_describe(res, wall)}", flush=True)
    return np.asarray(res.properties["SP"])


def serve_pagerank(g, svc, iters: int = PAGERANK_ITERS):
    res, wall = _timed(lambda: svc.run("pagerank", g, iters=iters))
    print(f"  pagerank iters={iters}: {_describe(res, wall)}", flush=True)
    return np.asarray(res.properties["rank"]), res.stats


def serve_wcc(g, svc):
    res, wall = _timed(lambda: svc.run("wcc", g))
    print(f"  wcc: {_describe(res, wall)}", flush=True)
    return np.asarray(res.properties["comp"])


def serve_bfs_burst(g, svc, roots):
    """Submit every root before reading any result, so the scheduler can
    form a batch; returns (levels per root, batch sizes, MS-BFS used)."""
    t0 = time.perf_counter()
    futures = [svc.submit("bfs", g, root=r) for r in roots]
    results = [f.result() for f in futures]
    wall = time.perf_counter() - t0
    sizes = [r.stats.batch_size for r in results]
    msbfs = any("__msbfs__" in r.stats.kernel_launches for r in results)
    print(f"  bfs burst of {len(roots)}: wall {wall:.3f}s, batch sizes "
          f"{sizes}, ms-bfs {msbfs}", flush=True)
    return [np.asarray(r.properties["old_level"]) for r in results], sizes, msbfs


def serve_warm_bfs(g, svc, root: int):
    """One BFS query on a fresh service over an existing artifact store;
    returns (levels, registry snapshot)."""
    res, wall = _timed(lambda: svc.run("bfs", g, root=root))
    print(f"  warm bfs root={root}: {_describe(res, wall)}", flush=True)
    return np.asarray(res.properties["old_level"]), svc.stats()["registry"]


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------


def run_one_chip(g, gw, store: Path, seed: int = 0) -> dict:
    """Every one-chip phase; raises CheckFailed on a wrong answer. Returns
    what ``main`` checks about the device (Pallas interpret mode)."""
    roots = pick_roots(g, 12, seed)
    bfs_roots, burst_roots = roots[:2], roots[2:10]
    pallas_root, warm_root = roots[10], roots[11]
    with phase("host references"):
        want_bfs = ref_bfs_levels(g, roots)
        want_sssp = ref_sssp(gw, bfs_roots[0])
        want_pr = ref_pagerank(g)
        want_wcc = ref_wcc(g)
    out = {}
    # a long fill-wait so the burst below forms one batch
    with repro.serve(str(store), max_wait_s=0.1) as svc:
        with phase("bfs"):
            for i, (lv, _) in enumerate(serve_bfs(g, svc, bfs_roots)):
                check_exact(f"bfs root={bfs_roots[i]}", lv, want_bfs[i])
        with phase("sssp"):
            check_exact(f"sssp root={bfs_roots[0]}",
                        serve_sssp(gw, svc, bfs_roots[0]), want_sssp)
        with phase("pagerank"):
            rank, _ = serve_pagerank(g, svc)
            check_pagerank("pagerank", rank, want_pr)
        with phase("wcc"):
            check_partition("wcc", serve_wcc(g, svc), want_wcc)
        with phase("bfs burst"):
            levels, sizes, msbfs = serve_bfs_burst(g, svc, burst_roots)
            for i, lv in enumerate(levels):
                check_exact(f"burst bfs root={burst_roots[i]}", lv,
                            want_bfs[2 + i])
            check("burst formed a batch", max(sizes) > 1, f"(sizes {sizes})")
            check("burst took the ms-bfs path", msbfs)
    with phase("pallas bfs"):
        target = Target(pallas=True)
        out["pallas_interpret"] = target.interpret_effective
        print(f"  pallas interpret_effective={target.interpret_effective}")
        with repro.serve(str(store), target=target) as psvc:
            (lv, stats), = serve_bfs(g, psvc, [pallas_root])
        check_exact(f"pallas bfs root={pallas_root}", lv, want_bfs[10])
    with phase("artifact warm start"):
        with repro.serve(str(store)) as wsvc:
            lv, reg = serve_warm_bfs(g, wsvc, warm_root)
        check_exact(f"warm bfs root={warm_root}", lv, want_bfs[11])
        exe = reg["executables"]
        print(f"  registry: artifact_hits={reg['artifact_hits']} "
              f"lowerings={reg['lowerings']} executables={exe}")
        check("warm start loaded the artifact", reg["artifact_hits"] == 1
              and reg["lowerings"] == 0)
        check("no executable re-lowered",
              exe["deserialized"] > 0 and exe["relowered"] == 0, f"({exe})")
    return out


def run_distributed(g, store: Path, n_devices: int, seed: int = 0) -> None:
    """BFS and PageRank on the distributed backend, against the host
    references; checks the supersteps ran and each device holds one slice
    of the partitioned edge buckets."""
    root = pick_roots(g, 1, seed)[0]
    with phase("host references"):
        want_bfs = ref_bfs_levels(g, [root])[0]
        want_pr = ref_pagerank(g)
    with repro.serve(str(store), backend="distributed") as svc:
        with phase("distributed bfs"):
            (lv, stats), = serve_bfs(g, svc, [root])
            check_exact(f"distributed bfs root={root}", lv, want_bfs)
            check("bfs ran supersteps", stats.dist_supersteps > 0,
                  f"({stats.dist_supersteps})")
        with phase("distributed pagerank"):
            rank, stats = serve_pagerank(g, svc)
            check_pagerank("distributed pagerank", rank, want_pr)
            check("pagerank ran supersteps", stats.dist_supersteps > 0,
                  f"({stats.dist_supersteps})")
        program = repro.compile(sources.BFS_ECP)
        entry = svc.registry.acquire(
            program, g, program.options.resolve_target(kind="distributed"))
        try:
            placement = entry.session.backend.engine.bucket_placement()
        finally:
            entry.release()
    print(f"  edge bucket slices by device: {placement}")
    slices = sorted(s for held in placement.values() for s in held)
    check("one bucket slice per device",
          len(placement) == n_devices
          and all(len(held) == 1 for held in placement.values())
          and slices == list(range(n_devices)), f"({placement})")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the distributed "
                         "BFS and PageRank on a 4-device mesh")
    ap.add_argument("--seed", type=int, default=0, help="graph and root seed")
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              f"nothing runs in its place", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    print(f"device: {devices[0].device_kind} x{len(devices)}")
    print(f"compile cache: {enable_compile_cache()}")
    shutil.rmtree(STORE, ignore_errors=True)
    print(f"artifact store: {STORE}")

    with phase("build R19"):
        g, gw = build_graphs(seed=args.seed, weighted=args.chips == 1)
    weights = f" (+{gw.weights.nbytes} bytes of SSSP weights)" if gw else ""
    print(f"R19: |V|={g.n_vertices} |E|={g.n_edges}, edge arrays "
          f"{g.src.nbytes + g.dst.nbytes} bytes{weights}")

    if args.chips == 1:
        out = run_one_chip(g, gw, STORE, args.seed)
        check("pallas compiled, not interpreted",
              out["pallas_interpret"] is False)
    else:
        run_distributed(g, STORE, args.chips, args.seed)

    for d in devices:
        stats = d.memory_stats() or {}
        print(f"device {d.id} peak_bytes_in_use: "
              f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
