"""Accelerator artifact tests: the compile -> lower -> bind split.

Covers the acceptance criteria of the Target/Accelerator PR:

* ``Target`` is hashable, validates its fields, and absorbs the legacy
  CompileOptions substrate kwargs through the compat shim;
* ``program.lower(target, shape).bind(graph)`` produces results
  bit-identical to ``program.bind(graph)`` — and two different graphs of
  one shape bucket bound to ONE accelerator match independently compiled
  Programs, on the local and distributed backends;
* ``accelerator.save`` / ``repro.load_accelerator`` round-trips are
  bit-identical to the in-process path across all 8 algorithms x
  local/distributed x passes default/none;
* ``accelerator.report()`` exposes the per-kernel launch plan and
  resource estimates;
* warm binds skip compilation (EngineStats.compile_time_s == 0) and the
  Program cache is a bounded LRU with observable counters.
"""
import numpy as np
import pytest

import repro
from repro.algorithms import sources
from repro.core import CompileOptions, Target
from repro.core.accelerator import (
    ARTIFACT_FORMAT,
    AcceleratorError,
    GraphShape,
    accelerator_fingerprint,
    load_or_lower,
)
from repro.graph import generators

ALGORITHMS = {
    "bfs": (sources.BFS_ECP, {"root": 3}, "old_level"),
    "bfs_hybrid": (sources.BFS_HYBRID, {"root": 3}, "old_level"),
    "pagerank": (sources.PAGERANK, {"iters": 5}, "rank"),
    "sssp": (sources.SSSP, {"root": 3}, "SP"),
    "ppr": (sources.PPR, {"source": 3, "max_iters": 8}, "PR_old"),
    "cgaw": (sources.CGAW, {}, "weight"),
    "wcc": (sources.WCC, {}, "comp"),
    "kcore": (sources.KCORE, {"k": 3}, "alive"),
}


@pytest.fixture(scope="module")
def graph():
    return generators.power_law(200, 1400, seed=5, weighted=True)


@pytest.fixture(scope="module")
def twin_graph():
    """A different graph with the identical (|V|, |E|, weighted) bucket."""
    return generators.power_law(200, 1400, seed=11, weighted=True)


def _assert_results_equal(a, b):
    assert set(a.properties) == set(b.properties)
    for name in a.properties:
        np.testing.assert_array_equal(a.properties[name], b.properties[name])
    assert a.host_env == b.host_env


# ---------------------------------------------------------------------------
# Target + CompileOptions split
# ---------------------------------------------------------------------------


def test_target_is_hashable_and_validates():
    t = Target()
    assert hash(t) == hash(Target())
    assert t.backend_name == "local"
    with pytest.raises(ValueError, match="kind"):
        Target(kind="gpu-cluster")
    with pytest.raises(ValueError, match="dtype_policy"):
        Target(dtype_policy="bf16")
    with pytest.raises(ValueError, match="n_devices"):
        Target(n_devices=-1)
    with pytest.raises(ValueError, match="partition_vertices"):
        Target(partition_vertices=0)


def test_target_auto_partitions():
    assert Target(partition_vertices=1000).auto_partitions(5000) == 5
    assert Target(n_partitions=7).auto_partitions(5000) == 7
    assert Target().auto_partitions(10) == 1


def test_compile_options_shim_maps_legacy_kwargs():
    opts = CompileOptions(burst=False, pallas=True)
    assert opts.burst is False and opts.pallas is True and opts.cache is True
    t = Target.from_options(opts)
    assert t.burst is False and t.pallas is True and t.cache is True
    # canonicalization: default-valued legacy kwargs don't split the cache
    assert CompileOptions(pallas=False) == CompileOptions()
    assert repr(CompileOptions(burst=True)) == repr(CompileOptions())
    with pytest.raises(TypeError, match="moved to repro.Target"):
        CompileOptions(mesh_shape=(2,))


def test_compile_options_ablation_constructors_roundtrip():
    base = CompileOptions.baseline()
    t = Target.from_options(base)
    assert (t.burst, t.cache, t.shuffle, t.compact_frontier) == (False,) * 4
    assert base.passes == "none"
    only = CompileOptions.with_only("shuffle")
    ts = Target.from_options(only)
    assert ts.shuffle is True and ts.burst is False
    assert Target.baseline() == Target.from_options(base)
    assert Target.with_only("shuffle") == ts


def test_target_dict_roundtrip():
    t = Target(kind="distributed", n_devices=2, burst=False, interpret=True)
    assert Target.from_dict(t.to_dict()) == t
    with pytest.raises(ValueError, match="unknown Target fields"):
        Target.from_dict({"kind": "local", "hbm_channels": 32})


# ---------------------------------------------------------------------------
# GraphShape buckets
# ---------------------------------------------------------------------------


def test_graph_shape_of_and_bucketed(graph):
    s = GraphShape.of(graph)
    assert s == GraphShape(200, 1400, True)
    b = s.bucketed(v_round=256, e_round=1024)
    assert b == GraphShape(256, 2048, True)
    padded = graph.pad_to(b.n_vertices, b.n_edges)
    assert b.accepts(padded) and not b.accepts(graph)


def test_lower_requires_shape():
    prog = repro.compile(sources.BFS_ECP)
    with pytest.raises(repro.ProgramError, match="shape bucket"):
        prog.lower()


def test_weighted_program_needs_weighted_bucket():
    prog = repro.compile(sources.SSSP)
    with pytest.raises(AcceleratorError, match="weighted"):
        prog.lower(shape=GraphShape(100, 500, weighted=False))


def test_bind_shape_mismatch_raises(graph):
    prog = repro.compile(sources.BFS_ECP)
    acc = prog.lower(shape=GraphShape(100, 500))
    with pytest.raises(AcceleratorError, match="pad the"):
        acc.bind(graph)


# ---------------------------------------------------------------------------
# lower -> bind equivalence + shape-bucket rebinding
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["local", "distributed"])
def test_bucket_rebinding_matches_independent_programs(graph, twin_graph, backend):
    """Two different generated graphs of one padded bucket bound to ONE
    accelerator produce results identical to independently compiled+bound
    Programs (the satellite acceptance test)."""
    src = sources.SSSP
    prog = repro.compile(src)
    target = Target.from_options(prog.options, kind=backend)
    acc = prog.lower(target, GraphShape.of(graph))
    for g in (graph, twin_graph):
        ref = repro.compile(src).bind(g, backend=backend).run(root=3)
        got = acc.bind(g).run(root=3)
        _assert_results_equal(ref, got)
    assert acc.binds == 2


def test_rebind_after_warm_is_compile_free(graph, twin_graph):
    acc = repro.compile(sources.BFS_ECP).lower(graph=graph)
    first = acc.bind(graph).run(root=3)
    rebind = acc.bind(twin_graph).run(root=3)
    # the AOT full-stream path is born warm; the rebind reuses every
    # compacted-subset bucket the first bind compiled
    assert rebind.stats.compile_time_s == 0.0
    assert rebind.stats.run_time_s == rebind.stats.wall_time_s
    assert first.stats.wall_time_s > 0


def test_run_many_and_batch_on_accelerator_session(graph):
    """Batched rerouting works on accelerator-backed sessions (trace_full)."""
    acc = repro.compile(sources.BFS_ECP).lower(graph=graph)
    sess = acc.bind(graph)
    sets = [{"root": int(r)} for r in (0, 3, 9, 17)]
    batched = sess.run_many(sets)
    for p, r in zip(sets, batched):
        _assert_results_equal(repro.compile(sources.BFS_ECP).bind(graph).run(**p), r)


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------


def test_report_contents(graph):
    acc = repro.compile(sources.PAGERANK).lower(graph=graph)
    rep = acc.report()
    assert rep.shape == GraphShape.of(graph)
    assert rep.state_bytes > 0 and rep.gb_bytes > 0
    assert rep.live_buffer_peak_bytes >= rep.state_bytes + rep.gb_bytes
    assert all(k.mode == "aot" for k in rep.kernels)
    assert any(k.kind == "edge" or k.stages for k in rep.kernels)
    assert all((k.flops or 0) > 0 for k in rep.kernels)
    text = rep.describe()
    assert "accelerator [local" in text and "live peak" in text
    assert rep.total_flops_per_launch_set > 0
    # pass report rides along (the artifact documents its own pipeline)
    assert any("pass " in line for line in text.splitlines())


def test_distributed_lowering_is_lazy_but_reported(graph):
    prog = repro.compile(sources.PAGERANK)
    acc = prog.lower(Target(kind="distributed"), GraphShape.of(graph))
    assert acc.library is None
    assert all(k.mode == "lazy" for k in acc.report().kernels)
    ref = prog.bind(graph, backend="distributed").run(iters=4)
    got = acc.bind(graph).run(iters=4)
    _assert_results_equal(ref, got)


# ---------------------------------------------------------------------------
# save / load round-trip (the acceptance matrix)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("algo", sorted(ALGORITHMS))
@pytest.mark.parametrize("backend", ["local", "distributed"])
@pytest.mark.parametrize("passes", ["default", "none"])
def test_save_load_roundtrip_matrix(graph, tmp_path, algo, backend, passes):
    src, params, prop = ALGORITHMS[algo]
    opts = CompileOptions(passes=passes)
    prog = repro.compile(src, opts)
    target = Target.from_options(opts, kind=backend)
    acc = prog.lower(target, GraphShape.of(graph))
    ref = prog.bind(graph, backend=backend).run(**params)
    path = acc.save(str(tmp_path / f"{algo}-{backend}-{passes}"))
    loaded = repro.load_accelerator(path)
    assert loaded.fingerprint == acc.fingerprint
    got = loaded.bind(graph).run(**params)
    _assert_results_equal(ref, got)
    assert prop in got.properties


def test_loaded_artifact_prefers_stored_executables(graph, tmp_path):
    acc = repro.compile(sources.BFS_ECP).lower(graph=graph)
    path = acc.save(str(tmp_path / "bfs"))
    loaded = repro.load_accelerator(path)
    modes = {k.mode for k in loaded.report().kernels}
    # either every executable deserialized (aot-loaded) or the backend
    # cannot serialize and everything transparently re-lowered (aot)
    assert modes <= {"aot-loaded", "aot"}
    _assert_results_equal(acc.bind(graph).run(root=7),
                          loaded.bind(graph).run(root=7))


def test_executable_counts_report_relowering(graph, tmp_path):
    """Every kernel's executable is counted through save and load, and one
    that cannot be loaded is counted as re-lowered, not hidden."""
    import os

    acc = repro.compile(sources.BFS_ECP).lower(graph=graph)
    n = len(acc.report().kernels)
    path = acc.save(str(tmp_path / "bfs"))
    assert acc.executable_counts()["serialized"] == n
    loaded = repro.load_accelerator(path)
    assert loaded.executable_counts() == {
        "serialized": 0, "deserialized": n, "relowered": 0}
    exe_dir = os.path.join(path, "executables")
    with open(os.path.join(exe_dir, sorted(os.listdir(exe_dir))[0]), "wb") as f:
        f.write(b"not an executable")
    damaged = repro.load_accelerator(path)
    assert damaged.executable_counts() == {
        "serialized": 0, "deserialized": n - 1, "relowered": 1}
    _assert_results_equal(acc.bind(graph).run(root=7),
                          damaged.bind(graph).run(root=7))


def test_save_without_executables_relowers(graph, tmp_path):
    acc = repro.compile(sources.WCC).lower(graph=graph)
    path = acc.save(str(tmp_path / "wcc"), include_executables=False)
    loaded = repro.load_accelerator(path)
    assert all(k.mode == "aot" for k in loaded.report().kernels)
    _assert_results_equal(acc.bind(graph).run(), loaded.bind(graph).run())


@pytest.mark.parametrize("stale_format", [ARTIFACT_FORMAT - 1, 999])
def test_load_rejects_stale_artifact(graph, tmp_path, stale_format):
    import json
    import os

    acc = repro.compile(sources.BFS_ECP).lower(graph=graph)
    path = acc.save(str(tmp_path / "bfs"))
    # tamper with the stored source: the recompiled fingerprint must differ
    with open(os.path.join(path, "program.gt")) as f:
        drifted = f.read().replace(
            "func main()", "const drift: int = 1;\nfunc main()", 1
        )
    with open(os.path.join(path, "program.gt"), "w") as f:
        f.write(drifted)
    with pytest.raises(AcceleratorError, match="stale"):
        repro.load_accelerator(path)
    # and a wrong format version fails loudly
    mpath = os.path.join(path, "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["format"] = stale_format
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(AcceleratorError, match="format"):
        repro.load_accelerator(path)
    # an artifact store re-lowers a stale-format artifact at its key
    prog, shape = acc.program, acc.shape
    store = str(tmp_path / "store")
    keyed = os.path.join(
        store, accelerator_fingerprint(prog.fingerprint, acc.target, shape)[:24])
    acc.save(keyed)
    with open(os.path.join(keyed, "manifest.json")) as f:
        manifest = json.load(f)
    manifest["format"] = stale_format
    with open(os.path.join(keyed, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    relowered, loaded, _ = load_or_lower(prog, acc.target, shape, store)
    assert not loaded
    _assert_results_equal(acc.bind(graph).run(root=7),
                          relowered.bind(graph).run(root=7))
    with open(os.path.join(keyed, "manifest.json")) as f:
        assert json.load(f)["format"] == ARTIFACT_FORMAT


def test_accelerator_fingerprint_is_content_keyed(graph):
    prog = repro.compile(sources.BFS_ECP)
    s = GraphShape.of(graph)
    f1 = accelerator_fingerprint(prog.fingerprint, Target(), s)
    assert f1 == accelerator_fingerprint(prog.fingerprint, Target(), s)
    assert f1 != accelerator_fingerprint(prog.fingerprint, Target.baseline(), s)
    assert f1 != accelerator_fingerprint(
        prog.fingerprint, Target(), GraphShape(s.n_vertices, s.n_edges + 1, True)
    )


# ---------------------------------------------------------------------------
# engine time split + LRU program cache satellites
# ---------------------------------------------------------------------------


def test_compile_time_split_cold_then_warm(graph):
    from repro.core.program import clear_program_cache

    clear_program_cache()
    sess = repro.compile(sources.PAGERANK).bind(graph)
    cold = sess.run(iters=4)
    assert cold.stats.compile_time_s > 0
    assert cold.stats.wall_time_s >= cold.stats.compile_time_s
    warm = sess.run(iters=4)
    assert warm.stats.compile_time_s == 0.0
    assert warm.stats.run_time_s == warm.stats.wall_time_s > 0


def test_program_cache_is_lru():
    from repro.core.program import (
        clear_program_cache,
        program_cache_size,
        set_program_cache_limit,
    )

    clear_program_cache()
    set_program_cache_limit(2)
    try:
        srcs = [
            sources.BFS_ECP,
            sources.PAGERANK,
            sources.WCC,
        ]
        progs = [repro.compile(s) for s in srcs]
        info = repro.program_cache_info()
        assert info.maxsize == 2 and info.currsize == 2
        assert info.evictions >= 1
        # evicted entries recompile to an equal (but distinct) Program
        again = repro.compile(srcs[0])
        assert again is not progs[0]
        assert again.fingerprint == progs[0].fingerprint
        # cached entries hit
        hits_before = repro.program_cache_info().hits
        assert repro.compile(srcs[0]) is again
        assert repro.program_cache_info().hits > hits_before
    finally:
        set_program_cache_limit(64)
        clear_program_cache()


def test_program_cache_info_counts():
    from repro.core.program import clear_program_cache

    clear_program_cache()
    repro.compile(sources.BFS_ECP)
    misses = repro.program_cache_info().misses
    assert misses >= 1
    repro.compile(sources.BFS_ECP)
    info = repro.program_cache_info()
    assert info.hits >= 1 and info.currsize == 1
