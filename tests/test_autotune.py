"""repro.autotune: profile-guided Target search, persisted and reused.

Covers the tentpole contracts:

* analysis-pruned candidate enumeration — a GT101-racy program can never
  search ``shuffle=False`` (the engine forces shuffle back on, so those
  candidates are dead duplicates), and a pipeline whose only frontier
  kernel carries a DENSE verdict skips ``compact_frontier`` variants;
* the TuningCache round-trips configs through per-key JSON files,
  tolerates corrupt/foreign files as misses, and a *fresh* cache
  instance over the same store (the fresh-process analogue) resolves a
  persisted config with **zero** search trials;
* TunedConfig survives to_dict/from_dict with an identical Target — same
  hash, same equality, same ``accelerator_fingerprint`` — including when
  the Target is rebuilt through the CompileOptions ``target_overrides``
  compat shim and through legacy substrate kwargs (DeprecationWarning
  path): tuned configs must rehydrate to identical fingerprint keys;
* ``program.lower(..., tuned=True)`` is a pure lookup that stamps the
  config into the Accelerator (and its saved manifest), and the serving
  tier resolves tuned Targets on submission (``tuned_hits`` in stats);
* ``accelerator.report()`` degrades gracefully when XLA cost analysis is
  unavailable — explicit ``None`` estimates, never an exception — and
  the tuner's cost model tolerates those ``None`` s.
"""
from __future__ import annotations

import json
import os
import warnings

import pytest

import repro
from repro.autotune import (
    AutoTuner,
    TunedConfig,
    TuningCache,
    autotune,
    program_mir_fingerprint,
    shape_bucket,
    tuning_dir_for,
    tuning_key,
)
from repro.core.accelerator import (
    GraphShape,
    accelerator_fingerprint,
    load_accelerator,
)
from repro.core.options import CompileOptions
from repro.core.target import Target
from repro.graph import generators

RACY_GT = """
element Vertex end
const edges: edgeset{Vertex}(Vertex, Vertex) = load(argv(1));
const vertices: vertexset{Vertex};
const P: vector{Vertex}(int);
func initP(v: Vertex)
    P[v] = 0;
end
func upd(src: Vertex, dst: Vertex)
    P[dst] = P[src] + 1;
end
func main()
    vertices.init(initP);
    edges.process(upd);
end
"""


@pytest.fixture
def graph():
    return generators.power_law(400, 2400, seed=0)


@pytest.fixture
def bfs_program():
    from repro.algorithms import sources

    return repro.compile(sources.BFS_ECP)


# --------------------------------------------------------------------------
# analysis-pruned candidate enumeration
# --------------------------------------------------------------------------


def test_candidates_enumerate_boolean_knob_grid(bfs_program):
    base = bfs_program.options.resolve_target()
    cands, pruned = AutoTuner(TuningCache()).candidates(bfs_program, base)
    # BFS: non-racy, frontier-relevant, has edge kernels -> full 2^4 grid
    assert len(cands) == 16
    assert pruned == []
    # the grid never touches the pallas routing axis
    assert all(t.pallas == base.pallas for t in cands)
    assert len(set(cands)) == len(cands)


def test_racy_program_pins_shuffle_on():
    program = repro.compile(RACY_GT)
    base = program.options.resolve_target()
    cands, pruned = AutoTuner(TuningCache()).candidates(program, base)
    assert all(t.shuffle for t in cands), \
        "racy programs must never search shuffle=False (engine forces it)"
    assert any("shuffle pinned on" in p for p in pruned)
    assert len(cands) < 16


def test_dense_only_program_skips_compact_frontier_variants():
    from repro.algorithms import sources

    program = repro.compile(sources.PAGERANK)
    base = program.options.resolve_target()
    cands, pruned = AutoTuner(TuningCache()).candidates(program, base)
    if any("compact_frontier variants skipped" in p for p in pruned):
        assert all(
            t.compact_frontier == base.compact_frontier for t in cands
        )
    else:  # pagerank grew a sparse frontier kernel: grid must include both
        assert {t.compact_frontier for t in cands} == {True, False}


# --------------------------------------------------------------------------
# TuningCache persistence
# --------------------------------------------------------------------------


def _mk_config(mir_fp="a" * 64, target=None, bucket=None) -> TunedConfig:
    return TunedConfig(
        mir_fingerprint=mir_fp,
        bucket=bucket or GraphShape.bucket_for(400, 2400, weighted=False),
        target=target or Target(),
        objective_s=0.010,
        baseline_s=0.025,
        trials=5,
    )


def test_cache_memory_roundtrip():
    cache = TuningCache()
    cfg = _mk_config()
    cache.put(cfg)
    got = cache.get(cfg.mir_fingerprint, cfg.bucket, cfg.target.kind)
    assert got == cfg
    assert cache.stats()["hits"] == 1
    assert cache.get("b" * 64, cfg.bucket) is None
    assert cache.stats()["misses"] == 1


def test_cache_disk_roundtrip_fresh_instance(tmp_path):
    store = str(tmp_path / "tuning")
    cfg = _mk_config()
    TuningCache(store).put(cfg)
    fresh = TuningCache(store)  # fresh process analogue: empty memory
    got = fresh.get(cfg.mir_fingerprint, cfg.bucket, cfg.target.kind)
    assert got == cfg
    assert got.target is not cfg.target  # rebuilt from JSON, equal by value
    assert fresh.stats() == {"entries": 1, "hits": 1, "misses": 0,
                             "stores": 0}


def test_cache_corrupt_file_is_a_miss_not_a_crash(tmp_path):
    store = str(tmp_path / "tuning")
    cfg = _mk_config()
    cache = TuningCache(store)
    cache.put(cfg)
    path = cache._path(cfg.key)
    with open(path, "w") as f:
        f.write("{not json")
    fresh = TuningCache(store)
    assert fresh.get(cfg.mir_fingerprint, cfg.bucket, cfg.target.kind) is None
    # a re-search overwrites the corrupt entry
    fresh.put(cfg)
    assert TuningCache(store).get(
        cfg.mir_fingerprint, cfg.bucket, cfg.target.kind
    ) == cfg


def test_cache_foreign_file_content_mismatch_is_a_miss(tmp_path):
    store = str(tmp_path / "tuning")
    cfg = _mk_config()
    cache = TuningCache(store)
    cache.put(cfg)
    other_key = tuning_key("c" * 64, cfg.bucket, cfg.target.kind)
    os.replace(cache._path(cfg.key), cache._path(other_key))
    fresh = TuningCache(store)
    assert fresh.get("c" * 64, cfg.bucket, cfg.target.kind) is None


# --------------------------------------------------------------------------
# TunedConfig / Target identity round trips (fingerprint stability)
# --------------------------------------------------------------------------


def test_tuned_config_dict_roundtrip_preserves_target_identity():
    cfg = _mk_config(target=Target(burst=False, shuffle=False))
    back = TunedConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
    assert back == cfg
    assert back.target == cfg.target
    assert hash(back.target) == hash(cfg.target)
    assert back.key == cfg.key
    # the identity that matters downstream: same artifact fingerprint
    shape = GraphShape(n_vertices=512, n_edges=4096, weighted=False)
    assert accelerator_fingerprint("f" * 64, back.target, shape) == \
        accelerator_fingerprint("f" * 64, cfg.target, shape)


def test_target_roundtrip_through_target_overrides_shim():
    """A tuned Target rebuilt via CompileOptions(target_overrides=...)
    must rehydrate to the identical fingerprint key (satellite: hash/eq
    round-trip through the compat shim)."""
    tuned = Target(burst=True, cache=False, shuffle=True,
                   compact_frontier=False)
    overrides = tuple(sorted(
        (k, v) for k, v in tuned.to_dict().items()
        if getattr(Target(), k) != v
    ))
    opts = CompileOptions(target_overrides=overrides)
    rebuilt = opts.resolve_target()
    assert rebuilt == tuned
    assert hash(rebuilt) == hash(tuned)
    shape = GraphShape(n_vertices=512, n_edges=4096, weighted=False)
    assert accelerator_fingerprint("f" * 64, rebuilt, shape) == \
        accelerator_fingerprint("f" * 64, tuned, shape)


def test_target_roundtrip_through_legacy_kwargs_shim():
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # anything but the deprecation fails
        with pytest.warns(DeprecationWarning):
            opts = CompileOptions(cache=False, shuffle=False)
    rebuilt = opts.resolve_target()
    direct = Target(cache=False, shuffle=False)
    assert rebuilt == direct
    assert hash(rebuilt) == hash(direct)


def test_mir_fingerprint_is_options_independent(graph):
    from repro.algorithms import sources

    a = repro.compile(sources.BFS_ECP)
    b = repro.compile(sources.BFS_ECP, CompileOptions(
        target_overrides=(("burst", False),)
    ))
    assert a.fingerprint != b.fingerprint or a is b  # program cache key
    assert program_mir_fingerprint(a) == program_mir_fingerprint(b)


def test_shape_bucket_is_padding_invariant(graph):
    bucket = shape_bucket(graph=graph)
    padded = graph.pad_to(bucket.n_vertices, bucket.n_edges)
    assert shape_bucket(graph=padded) == bucket


# --------------------------------------------------------------------------
# the search end to end
# --------------------------------------------------------------------------


def test_tune_searches_then_fresh_cache_reuses_with_zero_trials(
        bfs_program, graph, tmp_path):
    store = tuning_dir_for(str(tmp_path))
    tuner = AutoTuner(TuningCache(store), reps=1, max_candidates=3)
    report = tuner.tune(bfs_program, graph, params={"root": 0})
    assert not report.cache_hit
    assert report.trials >= 2  # base + at least the baseline referee
    assert report.config.objective_s > 0
    # tuned is never slower than the measured baseline referee
    assert report.config.objective_s <= report.config.baseline_s * 1.0001
    assert report.accelerator is not None
    assert report.accelerator.tuned == report.config.to_dict()

    fresh = AutoTuner(TuningCache(store))
    warm = fresh.tune(bfs_program, graph, params={"root": 0})
    assert warm.cache_hit
    assert warm.trials == 0
    assert warm.config == report.config
    assert fresh.cache.hits >= 1


def test_autotune_convenience_and_force(bfs_program, graph, tmp_path):
    cache = TuningCache(tuning_dir_for(str(tmp_path)))
    first = autotune(bfs_program, graph, params={"root": 0}, cache=cache,
                     reps=1, max_candidates=2)
    again = autotune(bfs_program, graph, params={"root": 0}, cache=cache)
    assert again.cache_hit and again.trials == 0
    forced = autotune(bfs_program, graph, params={"root": 0}, cache=cache,
                      reps=1, max_candidates=2, force=True)
    assert not forced.cache_hit and forced.trials >= 2
    assert first.config.key == forced.config.key


def test_lower_tuned_true_is_pure_lookup_and_stamps_manifest(
        bfs_program, graph, tmp_path):
    cache = TuningCache(tuning_dir_for(str(tmp_path)))
    tuned_target = Target(cache=False, shuffle=False)
    cache.put(TunedConfig(
        mir_fingerprint=program_mir_fingerprint(bfs_program),
        bucket=shape_bucket(graph=graph),
        target=tuned_target,
        objective_s=0.001, baseline_s=0.002, trials=3,
    ))
    acc = bfs_program.lower(graph=graph, tuned=True, tuning_cache=cache)
    assert acc.target == tuned_target
    assert acc.tuned is not None
    assert Target.from_dict(acc.tuned["target"]) == tuned_target
    # miss -> default target, no stamp
    other = generators.power_law(5000, 60000, seed=1)
    acc_miss = bfs_program.lower(graph=other, tuned=True, tuning_cache=cache)
    assert acc_miss.tuned is None
    assert acc_miss.target == bfs_program.options.resolve_target()

    # the stamp survives save -> load (manifest round trip)
    art = acc.save(str(tmp_path / "art"))
    loaded = load_accelerator(art)
    assert loaded.tuned == acc.tuned
    assert loaded.target == tuned_target


def test_serving_resolves_tuned_target_and_counts_hits(
        bfs_program, graph, tmp_path):
    store = str(tmp_path / "registry")
    tuned_target = Target(shuffle=False, compact_frontier=False)
    TuningCache(tuning_dir_for(store)).put(TunedConfig(
        mir_fingerprint=program_mir_fingerprint(bfs_program),
        bucket=shape_bucket(graph=graph),
        target=tuned_target,
        objective_s=0.001, baseline_s=0.002, trials=3,
    ))
    with repro.serve(store, workers=1) as svc:
        svc.run(bfs_program, graph, root=0)
        svc.run(bfs_program, graph, root=1)
        snap = svc.stats()
    label = bfs_program.fingerprint[:12]
    assert snap["programs"][label]["tuned_hits"] == 2
    assert snap["queries"]["tuned_hits"] == 2
    assert snap["tuning"]["hits"] == 2
    assert snap["tuning"]["enabled"] is True


def test_serving_pinned_target_wins_over_tuning(bfs_program, graph, tmp_path):
    store = str(tmp_path / "registry")
    TuningCache(tuning_dir_for(store)).put(TunedConfig(
        mir_fingerprint=program_mir_fingerprint(bfs_program),
        bucket=shape_bucket(graph=graph),
        target=Target(shuffle=False),
        objective_s=0.001, baseline_s=0.002, trials=3,
    ))
    pinned = Target()
    with repro.serve(store, workers=1, target=pinned) as svc:
        svc.run(bfs_program, graph, root=0)
        snap = svc.stats()
    assert snap["queries"]["tuned_hits"] == 0


def test_serving_autotune_off_skips_lookup(bfs_program, graph, tmp_path):
    store = str(tmp_path / "registry")
    TuningCache(tuning_dir_for(store)).put(TunedConfig(
        mir_fingerprint=program_mir_fingerprint(bfs_program),
        bucket=shape_bucket(graph=graph),
        target=Target(shuffle=False),
        objective_s=0.001, baseline_s=0.002, trials=3,
    ))
    with repro.serve(store, workers=1, autotune=False) as svc:
        svc.run(bfs_program, graph, root=0)
        snap = svc.stats()
    assert snap["queries"]["tuned_hits"] == 0
    assert snap["tuning"]["enabled"] is False


# --------------------------------------------------------------------------
# satellite: report() degrades to None estimates, cost model tolerates
# --------------------------------------------------------------------------


def test_xla_estimates_none_compiled():
    from repro.core.accelerator import _xla_estimates

    est = _xla_estimates(None)
    assert est == {"flops": None, "bytes_accessed": None, "arg_bytes": None,
                   "out_bytes": None, "temp_bytes": None}


def test_xla_estimates_raising_executable_degrades_to_none():
    from repro.core.accelerator import _xla_estimates

    class Hostile:
        def cost_analysis(self):
            raise NotImplementedError("no cost analysis on this backend")

        def memory_analysis(self):
            raise RuntimeError("interpreted executables have no memory stats")

    est = _xla_estimates(Hostile())
    assert est["flops"] is None
    assert est["bytes_accessed"] is None
    assert est["temp_bytes"] is None


def test_report_survives_missing_cost_analysis(bfs_program, graph,
                                               monkeypatch):
    import repro.core.accelerator as accel_mod

    monkeypatch.setattr(
        accel_mod, "_xla_estimates",
        lambda compiled: {"flops": None, "bytes_accessed": None,
                          "arg_bytes": None, "out_bytes": None,
                          "temp_bytes": None},
    )
    acc = bfs_program.lower(graph=graph)
    rep = acc.report()
    assert rep.kernels
    # static lane-count fallback keeps flops usable for the cost model
    assert all((k.flops or 0) > 0 for k in rep.kernels)
    assert all(k.bytes_accessed is None for k in rep.kernels)


def test_cost_score_tolerates_none_estimates():
    class Plan:
        kind = "edge"
        direction = "auto"
        flops = None
        bytes_accessed = None

    score = AutoTuner._cost_score(Target(), [Plan()])
    assert score > 0


def test_objective_falls_back_to_wall_time():
    assert AutoTuner._objective_from_trace(None, 0.5) == 0.5
    assert AutoTuner._objective_from_trace({"spans": {}}, 0.5) == 0.5
    # launch spans time the enqueue: only the run span counts
    trace = {"spans": {"launch:k": {"total_s": 0.2}, "run": {"total_s": 9.0}}}
    assert AutoTuner._objective_from_trace(trace, 0.5) == pytest.approx(9.0)
    launches_only = {"spans": {"launch:k": {"total_s": 0.2}}}
    assert AutoTuner._objective_from_trace(launches_only, 0.5) == 0.5


def test_tuner_parameter_validation():
    with pytest.raises(ValueError):
        AutoTuner(TuningCache(), reps=0)
    with pytest.raises(ValueError):
        AutoTuner(TuningCache(), margin=1.0)
    with pytest.raises(ValueError):
        AutoTuner(TuningCache(), max_candidates=0)
