"""CI perf-regression gate: quick BFS + PageRank benchmark on small
synthetic graphs.

Two modes:

* measure (default): runs the benchmark subset and writes ``BENCH_ci.json``
  with, per workload, the cold compile+first-run wall time, the steady-state
  (warm session) wall time, and the kernel-launch reduction achieved by the
  MIR pass pipeline (passes on vs off). A second ``batched`` section times
  K parameterized queries answered sequentially vs through one
  ``BatchSession`` execution (bfs_batched64: 64 BFS roots; pagerank_batched8:
  8 query batches) and records the wall-time speedup plus the launch ratio.
  A ``streaming`` section (bfs_incremental) applies a 1% additions-only
  GraphDelta through a StreamingSession and gates incremental repair at
  >= 3x over a warm full recompute, with zero re-lowering and bit-identical
  results. A ``serving`` section (serve_mixed_slo) drives sustained mixed
  BFS + PPR + SSSP traffic across two weighted tenants through one
  ``repro.serve()`` service and gates per-tenant p99 latency against an
  SLO ceiling with zero dropped-below-deadline admissions and one
  lowering per program. A ``telemetry`` section (telemetry_overhead)
  gates the tracing subsystem's cost: a fully traced warm BFS run must
  stay within 1.05x of the untraced run, the disabled null tracer within
  1.01x (measured as per-launch null-path cost scaled by the run's span
  count), and the traced run's Chrome trace is exported to
  ``BENCH_trace.json`` (uploaded as a CI artifact). An ``autotune``
  section (autotune_bfs) runs the repro.autotune search on a deep
  multigraph where frontier compaction is a structural win, and gates
  the tuned Target at >= 1.15x over ``Target.baseline()`` (interleaved
  within-run pairing), zero-trial reuse from a fresh TuningCache,
  manifest round-tripping of the config, and >= 1 serving tuned hit.

* ``--check``: compares a freshly written ``BENCH_ci.json`` against the
  committed ``BENCH_baseline.json`` and exits non-zero when any workload's
  compile+run or steady-state wall time regressed by more than
  ``--threshold`` (default 1.5x), when the pass pipeline's launch
  reduction fell below the acceptance floor of 1.3x, or when a batched
  workload's batched-vs-sequential speedup fell below its recorded floor
  (2x for bfs_batched64 at K=64). Speedups and launch ratios are measured
  within one run, so the batched gates are machine-independent and always
  fatal.

Wall-time comparisons are only meaningful between similar machines, so
the gate self-arms: while the committed baseline's ``meta.source`` is
"local" (measured on a dev machine) wall-time regressions are reported as
advisory warnings; once a baseline produced by a CI run (``meta.source ==
"ci"`` — download the ``bench-ci`` artifact of a green run and commit it)
is in place, they become fatal. A sub-50ms absolute delta is always
treated as runner jitter. The launch-reduction floor is
machine-independent and enforced unconditionally.

Refreshing the baseline after an intentional perf change::

    PYTHONPATH=src python -m benchmarks.ci_bench --out BENCH_baseline.json
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time
from dataclasses import replace

LAUNCH_REDUCTION_FLOOR = 1.3


def _workloads():
    import numpy as np

    from repro.algorithms import embedded, sources
    from repro.graph import generators

    g_bfs = generators.power_law(2000, 16000, seed=0)
    g_pr = generators.power_law(2000, 16000, seed=1)
    bfs_root = int(np.argmax(g_bfs.out_degree))
    return {
        "bfs": (sources.BFS_ECP, g_bfs, {"root": bfs_root}),
        # same algorithm/graph/params compiled through the embedded Python
        # front-end: gates compile-path wall-time parity with the text
        # parser (to_fir + analyze vs lex + parse + analyze) and that the
        # pass pipeline treats both front-ends identically
        "bfs_embedded": (embedded.build_bfs_ecp(), g_bfs, {"root": bfs_root}),
        "pagerank": (sources.PAGERANK, g_pr, {"iters": 10}),
    }


def _batched_workloads():
    import numpy as np

    from repro.algorithms import sources
    from repro.graph import generators

    g_bfs = generators.power_law(2000, 16000, seed=0)
    g_pr = generators.power_law(2000, 16000, seed=1)
    rng = np.random.default_rng(3)
    bfs_sets = [{"root": int(r)} for r in rng.integers(0, g_bfs.n_vertices, 64)]
    pr_sets = [{"iters": int(i)} for i in rng.integers(8, 14, 8)]
    # name -> (source, graph, param sets, fatal speedup floor or None)
    return {
        "bfs_batched64": (sources.BFS_ECP, g_bfs, bfs_sets, 2.0),
        "pagerank_batched8": (sources.PAGERANK, g_pr, pr_sets, None),
    }


def _time_batched(src, graph, param_sets, floor):
    """Warm sequential-vs-batched wall times for one K-query workload."""
    import repro
    from repro.core.program import clear_program_cache

    clear_program_cache()
    program = repro.compile(src)
    session = program.bind(graph)
    batch = program.bind_batch(graph)
    # warm both paths (jit compilation out of the measurement)
    session.run(**param_sets[0])
    batch.run_many(param_sets)
    t0 = time.perf_counter()
    seq_results = [session.run(**p) for p in param_sets]
    seq_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    bat_results = batch.run_many(param_sets)
    bat_s = time.perf_counter() - t0
    seq_launches = sum(r.stats.total_launches for r in seq_results)
    bat_launches = bat_results[0].stats.total_launches
    out = {
        "k": len(param_sets),
        "sequential_s": round(seq_s, 4),
        "batched_s": round(bat_s, 4),
        "batched_speedup": round(seq_s / max(bat_s, 1e-9), 3),
        "launches_sequential": seq_launches,
        "launches_batched": bat_launches,
        "launch_ratio": round(bat_launches / max(seq_launches, 1), 4),
    }
    if floor is not None:
        out["speedup_floor"] = floor
    return out


def _time_warm_bind():
    """Artifact warm-start gate: cold ``repro.compile(...).bind(...).run``
    vs warm ``Accelerator.bind(...).run`` on a different graph of the same
    shape bucket. The speedup is measured within one run (same machine for
    both sides), so the >= 3x floor is machine-independent and fatal.

    The accelerator is loaded from the artifact cache directory
    (``$REPRO_ARTIFACT_DIR``, default ``~/.cache/repro-artifacts`` — CI
    persists it across runs via actions/cache) when a matching-fingerprint
    artifact exists, and lowered+saved otherwise.
    """
    import repro
    from repro.algorithms import sources
    from repro.core.accelerator import GraphShape, load_or_lower
    from repro.core.program import clear_program_cache
    from repro.core.target import Target
    from repro.graph import generators

    g_cold = generators.power_law(2000, 16000, seed=7)
    g_warm = generators.power_law(2000, 16000, seed=8)  # same bucket
    root = 1
    # cold: front-end + passes + per-bind jit compilation + first run
    clear_program_cache()
    t0 = time.perf_counter()
    repro.compile(sources.BFS_ECP).bind(g_cold).run(root=root)
    cold_s = time.perf_counter() - t0

    prog = repro.compile(sources.BFS_ECP)
    art_dir = os.environ.get(
        "REPRO_ARTIFACT_DIR", os.path.expanduser("~/.cache/repro-artifacts")
    )
    acc, loaded, lower_s = load_or_lower(
        prog, Target.from_options(prog.options), GraphShape.of(g_warm), art_dir
    )
    # prime the library's shared compacted-frontier pad buckets (the AOT
    # executables cover the full-stream path; subset buckets are lazy and
    # frontier-size dependent, so serving traffic warms them once per
    # bucket) — then time what a warm server pays per fresh bind: a shape
    # check plus ready-compiled execution
    acc.bind(g_cold).run(root=root)
    acc.bind(g_warm).run(root=root)
    t0 = time.perf_counter()
    res_w = acc.bind(g_warm).run(root=root)
    warm_s = time.perf_counter() - t0
    return {
        "cold_compile_bind_run_s": round(cold_s, 4),
        "warm_bind_run_s": round(warm_s, 4),
        "lower_or_load_s": round(lower_s, 4),
        "artifact_loaded": loaded,
        "warm_speedup": round(cold_s / max(warm_s, 1e-9), 3),
        "speedup_floor": 3.0,
        "warm_compile_time_s": round(res_w.stats.compile_time_s, 4),
    }


def _time_streaming():
    """Streaming incremental-recompute gate: after an additions-only delta
    of ~1% of |E|, a repeated BFS query answered by incremental repair must
    beat a warm full recompute by >= 3x — and must perform **zero**
    re-lowering (``stats.compile_time_s == 0``: in-bucket updates rebind
    the Accelerator's AOT executables, never recompile). Both sides are
    measured within one run on the same machine, so the floor is
    machine-independent and fatal.
    """
    import numpy as np

    import repro
    from repro.algorithms import sources
    from repro.core.program import clear_program_cache
    from repro.graph import generators
    from repro.graph.storage import GraphDelta
    from repro.streaming import StreamingSession

    clear_program_cache()
    base = generators.power_law(2000, 16000, seed=0)
    root = int(np.argmax(base.out_degree))
    program = repro.compile(sources.BFS_ECP)
    acc = program.lower(graph=base, bucket=True)
    graph = base.pad_to(acc.shape.n_vertices, acc.shape.n_edges)
    rng = np.random.default_rng(9)
    n_add = max(1, base.n_edges // 100)  # 1% edge delta
    session = StreamingSession(program, graph, accelerator=acc)
    session.run(root=root)  # warm-up: AOT executables touched, result cached

    delta = GraphDelta(added_edges=rng.integers(
        0, base.n_vertices, size=(n_add, 2)).astype(np.int32))
    t0 = time.perf_counter()
    session.update(delta)
    update_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    inc_res = session.run(root=root)  # incremental repair of the cached result
    inc_s = time.perf_counter() - t0
    assert session.incremental_runs == 1, "repair path was not taken"

    # referee: warm full recompute on the SAME updated graph (steady-state
    # best-of-3 through the same warm accelerator library)
    full_session = acc.bind(session.graph)
    full_res = full_session.run(root=root)
    full_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        full_res = full_session.run(root=root)
        full_s = min(full_s, time.perf_counter() - t0)
    identical = all(
        np.array_equal(inc_res.properties[p], full_res.properties[p])
        for p in full_res.properties
    )
    session.close()
    return {
        "n_added": n_add,
        "update_apply_s": round(update_s, 4),
        "incremental_s": round(inc_s, 4),
        "full_recompute_s": round(full_s, 4),
        "incremental_speedup": round(full_s / max(inc_s, 1e-9), 3),
        "speedup_floor": 3.0,
        "repair_compile_time_s": round(inc_res.stats.compile_time_s, 4),
        "bit_identical": identical,
    }


def _time_serving():
    """Serving-tier SLO gate (serve_mixed_slo): sustained mixed traffic —
    BFS roots, PPR seeds, and SSSP queries interleaved across two weighted
    tenants — through one ``repro.serve()`` GraphService.

    Warm-up traffic runs under a separate ``warmup`` tenant (cold
    lowerings and per-batch-size trace compilation land on its histogram,
    not the measured tenants'), then 90 deadline-carrying queries are
    submitted for tenants ``alpha`` (weight 1) and ``beta`` (weight 2) in
    closed-loop waves of 8 outstanding requests — bounded client
    concurrency keeps the measured latency about service time plus
    scheduling, not backlog wait, while per-program runs of same-group
    requests still exercise batch formation. Gates, all
    machine-independent invariants except
    the deliberately generous absolute SLO: per-tenant p99 latency must
    stay under ``slo_p99_ms``, zero queries dropped below their deadline
    (no ``DeadlineExceeded``/``Overloaded`` rejections, no misses, no
    errors), every admission completed, and exactly one lowering per
    program (the registry served all repeat traffic warm)."""
    import numpy as np

    import repro
    from repro.core.program import clear_program_cache
    from repro.graph import generators

    clear_program_cache()
    g = generators.power_law(2000, 16000, seed=4, weighted=True)
    rng = np.random.default_rng(11)
    max_batch = 2
    programs = {
        "bfs": lambda: {"root": int(rng.integers(0, g.n_vertices))},
        "ppr": lambda: {"source": int(rng.integers(0, g.n_vertices)),
                        "max_iters": 8},
        "sssp": lambda: {"root": int(rng.integers(0, g.n_vertices))},
    }
    per_burst = 15  # x 3 programs x 2 tenants = 90 measured queries
    deadline_s = 15.0
    # ~4x the locally measured tail (bfs waves tail at ~2s: K=2 bit-packed
    # multi-source batches process full edge streams per level, ~0.45s per
    # batch) — generous enough for slower CI runners, tight enough that a
    # backlog pathology (p99 ~= total elapsed, ~9s+) or a cold compile
    # leaking onto serving traffic still trips it
    slo_p99_ms = 8000.0
    with repro.serve(False, workers=2, max_batch=max_batch, max_queue=256,
                     tenant_weights={"alpha": 1.0, "beta": 2.0}) as svc:
        # warm every (program, batch-size) execution trace: BatchSession
        # compiles one XLA trace per K, so serve K=1..max_batch up front
        for name, mk in programs.items():
            svc.run(name, g, tenant="warmup", **mk())
            futs = [svc.submit(name, g, tenant="warmup", **mk())
                    for _ in range(max_batch)]
            for f in futs:
                f.result()
        jobs = [
            (name, tenant, mk())
            for name, mk in programs.items()
            for tenant in ("alpha", "beta")
            for _ in range(per_burst)
        ]
        t0 = time.perf_counter()
        done = 0
        for i in range(0, len(jobs), 8):  # closed-loop waves of 8
            wave = [
                svc.submit(name, g, tenant=tenant,
                           deadline_s=deadline_s, **params)
                for name, tenant, params in jobs[i:i + 8]
            ]
            for f in wave:
                f.result()
                done += 1
        elapsed = time.perf_counter() - t0
        snap = svc.stats()
        lowerings = svc.registry.lowerings
    tenants = {t: snap["tenants"][t] for t in ("alpha", "beta")}
    q = snap["queries"]
    return {
        "programs": sorted(programs),
        "queries": done,
        "completed_measured": sum(t["completed"] for t in tenants.values()),
        "errors": q["errors"],
        "rejected_overloaded": q["rejected_overloaded"],
        "rejected_deadline": q["rejected_deadline"],
        "deadline_misses": q["deadline_misses"],
        "deadline_s": deadline_s,
        "p99_ms": round(max(t["latency_ms"]["p99_ms"]
                            for t in tenants.values()), 3),
        "p50_ms": round(max(t["latency_ms"]["p50_ms"]
                            for t in tenants.values()), 3),
        "slo_p99_ms": slo_p99_ms,
        "throughput_qps": round(done / max(elapsed, 1e-9), 1),
        "batch_occupancy": snap["batches"]["occupancy"],
        "lowerings": lowerings,
        "expected_lowerings": len(programs),
    }


def _time_telemetry():
    """Tracing-overhead gate (telemetry_overhead): the telemetry subsystem
    must be effectively free. Three measurements on one warm BFS session:

    * **untraced**: best-of-5 warm runs with the default null tracer.
    * **traced**: best-of-5 warm runs under ``repro.telemetry.enable()``
      — full span capture (run + per-launch spans with frontier
      occupancy attributes). Gated at <= 1.05x untraced (with the usual
      absolute-delta jitter guard); the final traced run is exported as
      a Chrome ``trace_event`` file (``BENCH_trace.json``, uploaded as a
      CI artifact).
    * **null path**: the disabled hot path is one tracer lookup plus an
      ``enabled`` check per launch site — measured directly over 200k
      iterations and scaled by the traced run's span count, it must
      imply <= 1.01x overhead on the untraced wall time. Measuring the
      per-op cost instead of differencing two noisy wall times keeps
      this sub-percent gate deterministic.
    """
    import numpy as np

    import repro
    from repro import telemetry as tel
    from repro.algorithms import sources
    from repro.core.program import clear_program_cache
    from repro.graph import generators

    clear_program_cache()
    tel.disable()
    g = generators.power_law(2000, 16000, seed=0)
    root = int(np.argmax(g.out_degree))
    session = repro.compile(sources.BFS_ECP).bind(g)
    session.run(root=root)  # warm: jit compilation out of the measurement

    reps = 5
    untraced_s = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        session.run(root=root)
        untraced_s = min(untraced_s, time.perf_counter() - t0)

    trace_path = os.environ.get("REPRO_BENCH_TRACE", "BENCH_trace.json")
    tel.enable()
    try:
        traced_s = float("inf")
        spans_per_run = 0
        for _ in range(reps):
            tr = tel.get()
            tr.reset()
            t0 = time.perf_counter()
            session.run(root=root)
            traced_s = min(traced_s, time.perf_counter() - t0)
            spans_per_run = max(spans_per_run, len(tr.spans()))
        # the last traced run's spans become the CI trace artifact
        trace_events = tel.get().export_chrome(trace_path)
    finally:
        tel.disable()

    # null-path microbench: what every traced call site pays when tracing
    # is off. Differencing two wall-time runs cannot resolve a <= 1% gate
    # through runner noise; per-op cost x span count can.
    n_ops = 200_000
    t0 = time.perf_counter()
    for _ in range(n_ops):
        if tel.get().enabled:
            raise AssertionError("tracer must be disabled here")
    null_op_s = (time.perf_counter() - t0) / n_ops
    null_ratio = 1.0 + spans_per_run * null_op_s / max(untraced_s, 1e-9)

    return {
        "untraced_s": round(untraced_s, 4),
        "traced_s": round(traced_s, 4),
        "traced_ratio": round(traced_s / max(untraced_s, 1e-9), 4),
        "overhead_ceiling": 1.05,
        "spans_per_run": spans_per_run,
        "null_op_ns": round(null_op_s * 1e9, 1),
        "null_ratio": round(null_ratio, 6),
        "null_ceiling": 1.01,
        "trace_events": trace_events,
        "trace_path": trace_path,
    }


def _time_autotune():
    """Autotuning gate (autotune_bfs): the full repro.autotune story on
    one workload where the knob choice is structural, not noise.

    The probe is BFS on a deep multigraph (200-level chain, 1000 parallel
    edges per hop): frontiers stay single-vertex while full-edge streaming
    pays ~400k edges per level, so ``compact_frontier`` Targets win by a
    wide, machine-independent margin (~200x fewer edges traversed).
    Measures and gates:

    * the search finds a tuned Target whose interleaved best-of-5 warm
      wall time beats ``Target.baseline()`` by >= 1.15x (fatal, within-run
      paired comparison);
    * a fresh TuningCache over the same store (the fresh-process
      analogue) resolves the config with **zero** search trials and >= 1
      cache hit (fatal);
    * the winner's accelerator stamps the config into its artifact
      manifest and ``load_accelerator`` restores it bit-identically
      (fatal);
    * a ``repro.serve()`` service over the same store resolves the tuned
      Target on submission — ``programs.bfs.tuned_hits >= 1`` (fatal).
    """
    import shutil
    import tempfile

    import repro
    from repro.autotune import AutoTuner, TuningCache, tuning_dir_for
    from repro.core.accelerator import load_accelerator
    from repro.core.program import clear_program_cache
    from repro.core.target import Target
    from repro.graph import generators
    from repro.serving.service import NAMED_ALGORITHMS

    clear_program_cache()
    store = tempfile.mkdtemp(prefix="repro-bench-autotune-")
    try:
        g = generators.deep_chain(200, multiplicity=1000)
        program = repro.compile(NAMED_ALGORITHMS["bfs"])
        params = {"root": 0}

        tuner = AutoTuner(TuningCache(tuning_dir_for(store)),
                          reps=2, max_candidates=6)
        t0 = time.perf_counter()
        report = tuner.tune(program, g, params=params)
        search_s = time.perf_counter() - t0

        # fresh-process analogue: a new cache instance over the same
        # store must resolve the config from disk with zero trials
        warm_cache = TuningCache(tuning_dir_for(store))
        warm = AutoTuner(warm_cache).tune(program, g, params=params)

        # paired steady-state: tuned vs Target.baseline(), interleaved
        # best-of-5 warm wall times (interleaving cancels runner drift)
        base_target = replace(
            Target.baseline(), kind=report.config.target.kind
        )
        tuned_acc = report.accelerator
        if tuned_acc is None:  # pragma: no cover - search always sets it
            tuned_acc = program.lower(report.config.target, graph=g)
        base_acc = program.lower(base_target, graph=g)
        tuned_sess = tuned_acc.bind(g)
        base_sess = base_acc.bind(g)
        tuned_res = tuned_sess.run(**params)   # warm both paths
        base_res = base_sess.run(**params)
        tuned_s = base_s = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            tuned_sess.run(**params)
            tuned_s = min(tuned_s, time.perf_counter() - t0)
            t0 = time.perf_counter()
            base_sess.run(**params)
            base_s = min(base_s, time.perf_counter() - t0)
        tuned_sess.close()
        base_sess.close()

        # artifact manifest round trip
        art_dir = tuned_acc.save(os.path.join(store, "bfs-tuned"))
        loaded = load_accelerator(art_dir)
        manifest_roundtrip = loaded.tuned == report.config.to_dict()

        # serving resolves the tuned Target by lookup on every submit
        with repro.serve(store, workers=1) as svc:
            svc.run("bfs", g, **params)
            snap = svc.stats()
        service_tuned_hits = snap["programs"]["bfs"]["tuned_hits"]

        return {
            "tuned_target": report.config.target.describe(),
            "search_s": round(search_s, 3),
            "trials_search": report.trials,
            "candidates": report.candidates,
            "objective_s": round(report.config.objective_s, 4),
            "tuned_steady_s": round(tuned_s, 4),
            "baseline_steady_s": round(base_s, 4),
            "tuned_speedup": round(base_s / max(tuned_s, 1e-9), 3),
            "speedup_floor": 1.15,
            "edges_tuned": int(tuned_res.stats.edges_traversed),
            "edges_baseline": int(base_res.stats.edges_traversed),
            "trials_cached": warm.trials,
            "cache_hits": warm_cache.hits,
            "manifest_roundtrip": manifest_roundtrip,
            "service_tuned_hits": service_tuned_hits,
        }
    finally:
        shutil.rmtree(store, ignore_errors=True)


def _time_workload(src, graph, params, options):
    """(cold compile+bind+first-run seconds, warm best-of-3 seconds, stats)."""
    import repro
    from repro.core.program import clear_program_cache

    clear_program_cache()
    t0 = time.perf_counter()
    session = repro.compile(src, options).bind(graph)
    res = session.run(**params)
    compile_run_s = time.perf_counter() - t0

    steady = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        res = session.run(**params)
        steady = min(steady, time.perf_counter() - t0)
    return compile_run_s, steady, res.stats


def measure() -> dict:
    from repro.core import CompileOptions

    opts_on = CompileOptions.full()
    opts_off = replace(opts_on, passes="none")
    out = {
        "meta": {
            "python": platform.python_version(),
            "machine": platform.machine(),
            # wall times only gate hard against a baseline measured on the
            # same runner class; "local" baselines make them advisory
            "source": "ci" if os.environ.get("GITHUB_ACTIONS") else "local",
        },
        "workloads": {},
    }
    for name, (src, graph, params) in _workloads().items():
        compile_run_s, steady_s, stats_on = _time_workload(src, graph, params, opts_on)
        _, _, stats_off = _time_workload(src, graph, params, opts_off)
        launches_on = stats_on.total_launches
        launches_off = stats_off.total_launches
        out["workloads"][name] = {
            "compile_run_s": round(compile_run_s, 4),
            "steady_s": round(steady_s, 4),
            "launches_passes_on": launches_on,
            "launches_passes_off": launches_off,
            "launch_reduction": round(launches_off / max(launches_on, 1), 3),
            "fused_launches": stats_on.fused_launches,
        }
    out["batched"] = {}
    for name, (src, graph, sets, floor) in _batched_workloads().items():
        out["batched"][name] = _time_batched(src, graph, sets, floor)
    out["warm_bind"] = {"bfs_warm_bind": _time_warm_bind()}
    out["streaming"] = {"bfs_incremental": _time_streaming()}
    out["serving"] = {"serve_mixed_slo": _time_serving()}
    out["telemetry"] = {"telemetry_overhead": _time_telemetry()}
    out["autotune"] = {"autotune_bfs": _time_autotune()}
    return out


# a wall-time "regression" below this absolute delta is runner jitter, not
# a signal — millisecond-scale steady-state times on shared CI runners can
# easily move 1.5x without any code change
MIN_REGRESSION_DELTA_S = 0.05


def check(ci: dict, baseline: dict, threshold: float) -> int:
    failures = []
    base_wl = baseline.get("workloads", {})
    ci_wl = ci.get("workloads", {})
    # absolute wall times are only comparable within one runner class: a
    # baseline not measured on CI (source != "ci") arms the wall-time gate
    # in advisory mode — regressions are reported but non-fatal — until a
    # CI-produced bench-ci artifact replaces the committed baseline; the
    # machine-independent launch-reduction floor is always fatal
    walltime_fatal = baseline.get("meta", {}).get("source") == "ci"
    warnings = []
    # every measured workload must be gated: a workload added to
    # _workloads() without refreshing the committed baseline fails loudly
    # instead of silently shipping ungated
    for name in sorted(set(ci_wl) - set(base_wl)):
        failures.append(
            f"{name}: measured but absent from the baseline — refresh "
            f"BENCH_baseline.json to gate it"
        )
    for name, base in base_wl.items():
        got = ci_wl.get(name)
        if got is None:
            failures.append(f"{name}: missing from current run")
            continue
        for key in ("compile_run_s", "steady_s"):
            if key not in got or key not in base:
                failures.append(f"{name}.{key}: metric missing "
                                f"(ci={key in got}, baseline={key in base})")
                continue
            ratio = got[key] / max(base[key], 1e-9)
            delta = got[key] - base[key]
            line = (f"{name}.{key}: {got[key]:.4f}s vs baseline "
                    f"{base[key]:.4f}s ({ratio:.2f}x)")
            if ratio > threshold and delta > MIN_REGRESSION_DELTA_S:
                if walltime_fatal:
                    failures.append(f"REGRESSION {line} > {threshold}x")
                else:
                    warnings.append(
                        f"WARNING {line} > {threshold}x (advisory: baseline "
                        f"was not measured on a CI runner)"
                    )
            else:
                print(f"ok   {line}")
        lr = got.get("launch_reduction", 0.0)
        if lr < LAUNCH_REDUCTION_FLOOR:
            failures.append(
                f"REGRESSION {name}.launch_reduction: {lr:.2f}x < "
                f"{LAUNCH_REDUCTION_FLOOR}x acceptance floor"
            )
        else:
            print(f"ok   {name}.launch_reduction: {lr:.2f}x "
                  f"(floor {LAUNCH_REDUCTION_FLOOR}x)")
    # batched execution gates: the speedup and launch ratios are measured
    # within one run (same machine for both sides), so floors are fatal
    # regardless of where the baseline came from
    base_batched = baseline.get("batched", {})
    ci_batched = ci.get("batched", {})
    for name in sorted(set(ci_batched) - set(base_batched)):
        failures.append(
            f"{name}: batched workload measured but absent from the baseline "
            f"— refresh BENCH_baseline.json to gate it"
        )
    for name in sorted(base_batched):
        got = ci_batched.get(name)
        if got is None:
            failures.append(f"{name}: batched workload missing from current run")
            continue
        speedup = got.get("batched_speedup", 0.0)
        floor = got.get("speedup_floor") or base_batched[name].get("speedup_floor")
        line = (f"{name}.batched_speedup: {speedup:.2f}x over sequential "
                f"(K={got.get('k')}, launch_ratio={got.get('launch_ratio')})")
        if floor is not None and speedup < floor:
            failures.append(f"REGRESSION {line} < {floor}x acceptance floor")
        else:
            print(f"ok   {line}")
    # accelerator warm-start gates: within-run speedups, floors always fatal
    base_warm = baseline.get("warm_bind", {})
    ci_warm = ci.get("warm_bind", {})
    for name in sorted(set(ci_warm) - set(base_warm)):
        failures.append(
            f"{name}: warm-bind workload measured but absent from the "
            f"baseline — refresh BENCH_baseline.json to gate it"
        )
    for name in sorted(base_warm):
        got = ci_warm.get(name)
        if got is None:
            failures.append(f"{name}: warm-bind workload missing from current run")
            continue
        speedup = got.get("warm_speedup", 0.0)
        floor = got.get("speedup_floor") or base_warm[name].get("speedup_floor")
        line = (f"{name}.warm_speedup: {speedup:.2f}x "
                f"(cold {got.get('cold_compile_bind_run_s')}s vs warm bind+run "
                f"{got.get('warm_bind_run_s')}s, artifact_loaded="
                f"{got.get('artifact_loaded')})")
        if floor is not None and speedup < floor:
            failures.append(f"REGRESSION {line} < {floor}x acceptance floor")
        else:
            print(f"ok   {line}")
    # streaming incremental gates: within-run speedup + the zero-re-lowering
    # and bit-identity invariants; all machine-independent, always fatal
    base_stream = baseline.get("streaming", {})
    ci_stream = ci.get("streaming", {})
    for name in sorted(set(ci_stream) - set(base_stream)):
        failures.append(
            f"{name}: streaming workload measured but absent from the "
            f"baseline — refresh BENCH_baseline.json to gate it"
        )
    for name in sorted(base_stream):
        got = ci_stream.get(name)
        if got is None:
            failures.append(f"{name}: streaming workload missing from current run")
            continue
        speedup = got.get("incremental_speedup", 0.0)
        floor = got.get("speedup_floor") or base_stream[name].get("speedup_floor")
        line = (f"{name}.incremental_speedup: {speedup:.2f}x over full "
                f"recompute (repair {got.get('incremental_s')}s vs "
                f"{got.get('full_recompute_s')}s after "
                f"{got.get('n_added')} added edges)")
        if floor is not None and speedup < floor:
            failures.append(f"REGRESSION {line} < {floor}x acceptance floor")
        else:
            print(f"ok   {line}")
        if got.get("repair_compile_time_s", 0.0) != 0.0:
            failures.append(
                f"REGRESSION {name}: incremental repair re-lowered kernels "
                f"(compile_time_s={got.get('repair_compile_time_s')}, "
                f"expected 0 — in-bucket updates must be rebind-only)"
            )
        else:
            print(f"ok   {name}.repair_compile_time_s: 0 (rebind-only)")
        if not got.get("bit_identical", False):
            failures.append(
                f"REGRESSION {name}: incremental result diverged from "
                f"full recompute"
            )
        else:
            print(f"ok   {name}.bit_identical: true")
    # serving-tier SLO gates: admission/deadline/error invariants are exact
    # and always fatal; the p99 SLO ceiling is deliberately generous (orders
    # of magnitude above warm per-query latency) so it gates pathologies —
    # cold compiles leaking onto serving traffic, scheduler stalls — not
    # runner speed
    base_serve = baseline.get("serving", {})
    ci_serve = ci.get("serving", {})
    for name in sorted(set(ci_serve) - set(base_serve)):
        failures.append(
            f"{name}: serving workload measured but absent from the "
            f"baseline — refresh BENCH_baseline.json to gate it"
        )
    for name in sorted(base_serve):
        got = ci_serve.get(name)
        if got is None:
            failures.append(f"{name}: serving workload missing from current run")
            continue
        p99 = got.get("p99_ms", float("inf"))
        slo = got.get("slo_p99_ms") or base_serve[name].get("slo_p99_ms")
        line = (f"{name}.p99_ms: {p99:.1f}ms "
                f"(p50 {got.get('p50_ms')}ms, "
                f"{got.get('throughput_qps')} qps, "
                f"occupancy {got.get('batch_occupancy')})")
        if slo is not None and p99 > slo:
            failures.append(f"REGRESSION {line} > {slo}ms SLO ceiling")
        else:
            print(f"ok   {line} (SLO {slo}ms)")
        dropped = (
            got.get("rejected_deadline", 0) + got.get("rejected_overloaded", 0)
            + got.get("deadline_misses", 0) + got.get("errors", 0)
        )
        if dropped:
            failures.append(
                f"REGRESSION {name}: {dropped} queries dropped/late "
                f"(rejected_deadline={got.get('rejected_deadline')}, "
                f"rejected_overloaded={got.get('rejected_overloaded')}, "
                f"deadline_misses={got.get('deadline_misses')}, "
                f"errors={got.get('errors')}) — expected 0 under this load"
            )
        else:
            print(f"ok   {name}: zero rejections, misses, and errors")
        if got.get("completed_measured") != got.get("queries"):
            failures.append(
                f"REGRESSION {name}: {got.get('completed_measured')}/"
                f"{got.get('queries')} admitted queries completed"
            )
        else:
            print(f"ok   {name}.completed: {got.get('completed_measured')}"
                  f"/{got.get('queries')}")
        if got.get("lowerings") != got.get("expected_lowerings"):
            failures.append(
                f"REGRESSION {name}: {got.get('lowerings')} lowerings for "
                f"{got.get('expected_lowerings')} programs — repeat serving "
                f"traffic must reuse resident sessions, not re-lower"
            )
        else:
            print(f"ok   {name}.lowerings: {got.get('lowerings')} "
                  f"(one per program)")
    # telemetry overhead gates: traced-vs-untraced is a within-run ratio
    # (same machine, same warm session) with the absolute-delta jitter
    # guard; the null-tracer ratio is derived from a per-op microbench and
    # is deterministic — both always fatal
    base_tel = baseline.get("telemetry", {})
    ci_tel = ci.get("telemetry", {})
    for name in sorted(set(ci_tel) - set(base_tel)):
        failures.append(
            f"{name}: telemetry workload measured but absent from the "
            f"baseline — refresh BENCH_baseline.json to gate it"
        )
    for name in sorted(base_tel):
        got = ci_tel.get(name)
        if got is None:
            failures.append(f"{name}: telemetry workload missing from current run")
            continue
        ratio = got.get("traced_ratio", float("inf"))
        ceiling = got.get("overhead_ceiling") or base_tel[name].get("overhead_ceiling")
        delta = got.get("traced_s", 0.0) - got.get("untraced_s", 0.0)
        line = (f"{name}.traced_ratio: {ratio:.3f}x "
                f"(traced {got.get('traced_s')}s vs untraced "
                f"{got.get('untraced_s')}s, {got.get('spans_per_run')} "
                f"spans/run)")
        if ceiling is not None and ratio > ceiling and delta > MIN_REGRESSION_DELTA_S:
            failures.append(f"REGRESSION {line} > {ceiling}x ceiling")
        else:
            print(f"ok   {line} (ceiling {ceiling}x)")
        null_ratio = got.get("null_ratio", float("inf"))
        null_ceiling = got.get("null_ceiling") or base_tel[name].get("null_ceiling")
        nline = (f"{name}.null_ratio: {null_ratio:.6f}x "
                 f"({got.get('null_op_ns')}ns per disabled call site)")
        if null_ceiling is not None and null_ratio > null_ceiling:
            failures.append(f"REGRESSION {nline} > {null_ceiling}x ceiling")
        else:
            print(f"ok   {nline} (ceiling {null_ceiling}x)")
        if not got.get("trace_events"):
            failures.append(
                f"REGRESSION {name}: traced run exported no Chrome trace "
                f"events (expected a non-empty {got.get('trace_path')})"
            )
        else:
            print(f"ok   {name}.trace_events: {got.get('trace_events')} "
                  f"-> {got.get('trace_path')}")
    # autotuning gates: the tuned-vs-baseline speedup is a within-run
    # interleaved paired measurement on a structurally-differentiated
    # workload, and the cache/manifest/serving reuse checks are exact
    # invariants — all always fatal
    base_tune = baseline.get("autotune", {})
    ci_tune = ci.get("autotune", {})
    for name in sorted(set(ci_tune) - set(base_tune)):
        failures.append(
            f"{name}: autotune workload measured but absent from the "
            f"baseline — refresh BENCH_baseline.json to gate it"
        )
    for name in sorted(base_tune):
        got = ci_tune.get(name)
        if got is None:
            failures.append(f"{name}: autotune workload missing from current run")
            continue
        speedup = got.get("tuned_speedup", 0.0)
        floor = got.get("speedup_floor") or base_tune[name].get("speedup_floor")
        line = (f"{name}.tuned_speedup: {speedup:.2f}x over Target.baseline() "
                f"(tuned {got.get('tuned_steady_s')}s [{got.get('tuned_target')}] "
                f"vs baseline {got.get('baseline_steady_s')}s, "
                f"{got.get('edges_tuned')} vs {got.get('edges_baseline')} "
                f"edges traversed)")
        if floor is not None and speedup < floor:
            failures.append(f"REGRESSION {line} < {floor}x acceptance floor")
        else:
            print(f"ok   {line} (floor {floor}x)")
        if got.get("trials_cached", -1) != 0 or got.get("cache_hits", 0) < 1:
            failures.append(
                f"REGRESSION {name}: fresh TuningCache re-resolution ran "
                f"{got.get('trials_cached')} trial(s) with "
                f"{got.get('cache_hits')} hit(s) — a persisted config must "
                f"reuse with zero search"
            )
        else:
            print(f"ok   {name}: warm re-resolution trials=0, "
                  f"cache_hits={got.get('cache_hits')} "
                  f"(search was {got.get('trials_search')} trial(s) in "
                  f"{got.get('search_s')}s)")
        if not got.get("manifest_roundtrip", False):
            failures.append(
                f"REGRESSION {name}: tuned config did not survive "
                f"Accelerator.save/load_accelerator (manifest stamp "
                f"mismatch)"
            )
        else:
            print(f"ok   {name}.manifest_roundtrip: true")
        if got.get("service_tuned_hits", 0) < 1:
            failures.append(
                f"REGRESSION {name}: serving resolved "
                f"{got.get('service_tuned_hits')} tuned Target(s) — "
                f"GraphService must pick persisted configs on submission"
            )
        else:
            print(f"ok   {name}.service_tuned_hits: "
                  f"{got.get('service_tuned_hits')}")
    for w in warnings:
        print(w)
    for f in failures:
        print(f, file=sys.stderr)
    return 1 if failures else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_ci.json", help="measurement output path")
    ap.add_argument("--check", action="store_true",
                    help="compare --ci against --baseline instead of measuring")
    ap.add_argument("--ci", default="BENCH_ci.json")
    ap.add_argument("--baseline", default="BENCH_baseline.json")
    ap.add_argument("--threshold", type=float, default=1.5,
                    help="max allowed wall-time regression ratio")
    args = ap.parse_args(argv)

    if args.check:
        with open(args.ci) as f:
            ci = json.load(f)
        with open(args.baseline) as f:
            baseline = json.load(f)
        return check(ci, baseline, args.threshold)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    results = measure()
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(results, indent=2, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
