"""End-to-end tracing tests: span trees, exporters, host syncs, profiler.

The tracer is process-global, so every test runs under the ``tracer``
fixture, which guarantees a fresh enabled tracer on entry and a swap
back to the null tracer on exit (pytest-xdist shards by test, and within
one worker tests are sequential, so no cross-test bleed).
"""
import glob
import json
import os

import numpy as np
import pytest

import repro
from repro import telemetry
from repro.algorithms import sources
from repro.core.program import clear_program_cache
from repro.graph import generators
from repro.telemetry.tracer import ANNOTATION_PREFIX


@pytest.fixture
def tracer():
    tr = telemetry.enable()
    tr.reset()
    yield tr
    telemetry.disable()


def _tree_names(tr, root_span):
    """All span names reachable from root_span (exclusive) via parent links."""
    by_parent = {}
    for s in tr.spans():
        by_parent.setdefault(s.parent_id, []).append(s)
    names, stack = [], [root_span.span_id]
    while stack:
        sid = stack.pop()
        for child in by_parent.get(sid, []):
            names.append(child.name)
            stack.append(child.span_id)
    return names


# --------------------------------------------------------------------------
# golden span trees
# --------------------------------------------------------------------------


def test_golden_span_tree_local_bfs(tracer):
    clear_program_cache()
    g = generators.power_law(300, 2400, seed=2)
    program = repro.compile(sources.BFS_ECP)
    acc = program.lower(repro.Target(), shape=repro.GraphShape.of(g))
    result = acc.bind(g).run(root=3)

    by_name = {}
    for s in tracer.spans():
        by_name.setdefault(s.name, []).append(s)
    assert len(by_name["compile"]) == 1
    assert len(by_name["lower"]) == 1
    assert len(by_name["bind"]) == 1
    assert len(by_name["run"]) == 1

    # every kernel launch counted by EngineStats appears as a launch span
    launch_spans = [
        s for s in tracer.spans() if s.name.startswith("launch:")
    ]
    assert len(launch_spans) == result.stats.total_launches

    # one connected tree: all launch spans descend from the run span
    run_span = by_name["run"][0]
    names = _tree_names(tracer, run_span)
    assert sum(n.startswith("launch:") for n in names) == len(launch_spans)
    assert all(s.trace_id == run_span.trace_id for s in launch_spans)

    # spans carry their typed attributes
    assert by_name["compile"][0].attrs["fingerprint"]
    assert by_name["lower"][0].attrs["target"] == "local"
    assert by_name["bind"][0].attrs["n_vertices"] == g.n_vertices
    assert run_span.attrs["launches"] == result.stats.total_launches
    modes = {s.attrs.get("mode") for s in launch_spans}
    assert modes <= {"full", "compacted"}

    # the per-run summary rides on the result and matches the tree
    assert result.trace is not None
    trace_launches = sum(
        agg["count"] for name, agg in result.trace["spans"].items()
        if name.startswith("launch:")
    )
    assert trace_launches == result.stats.total_launches


def test_golden_span_tree_distributed_bfs(subproc):
    out = subproc(
        """
import numpy as np
import repro
from repro import telemetry
from repro.algorithms import sources
from repro.graph import generators

tr = telemetry.enable()
g = generators.power_law(300, 2400, seed=2)
program = repro.compile(sources.BFS_ECP)
result = program.bind(g, backend="distributed").run(root=3)

spans = tr.spans()
by_name = {}
for s in spans:
    by_name.setdefault(s.name, []).append(s)
launch_spans = [s for s in spans if s.name.startswith("launch:")]
assert len(launch_spans) == result.stats.total_launches, (
    len(launch_spans), result.stats.total_launches)
assert len(by_name["run"]) == 1
run_span = by_name["run"][0]
assert run_span.attrs["engine"] == "DistEngine"
assert all(s.trace_id == run_span.trace_id for s in launch_spans)
supersteps = by_name.get("superstep", [])
assert result.stats.dist_supersteps > 0
assert len(supersteps) == result.stats.dist_supersteps, (
    len(supersteps), result.stats.dist_supersteps)
assert all(s.attrs["devices"] >= 1 for s in supersteps)
assert all(s.attrs["shuffle_elements"] > 0 for s in supersteps)
dist_modes = {s.attrs.get("mode") for s in launch_spans}
assert "dist" in dist_modes, dist_modes
telemetry.disable()
print("dist trace ok")
""",
        devices=4,
    )
    assert "dist trace ok" in out


# --------------------------------------------------------------------------
# enable/disable round trip
# --------------------------------------------------------------------------


def test_disable_retains_zero_spans():
    tr = telemetry.enable()
    tr.reset()
    g = generators.power_law(200, 1200, seed=0)
    repro.compile(sources.BFS_ECP).bind(g).run(root=0)
    assert tr.spans()

    telemetry.disable()
    assert telemetry.get().spans() == []
    assert not telemetry.enabled()
    # the old tracer object was drained too (no hidden retention)
    assert tr.spans() == []

    # instrumented paths still run (as no-ops) while disabled
    result = repro.compile(sources.BFS_ECP).bind(g).run(root=1)
    assert telemetry.get().spans() == []
    assert result.trace is None

    # re-enable starts clean
    tr2 = telemetry.enable()
    try:
        assert tr2.spans() == []
        r2 = repro.compile(sources.BFS_ECP).bind(g).run(root=2)
        assert r2.trace is not None
        assert any(s.name == "run" for s in tr2.spans())
    finally:
        telemetry.disable()


def test_null_tracer_api_is_complete(tmp_path):
    telemetry.disable()
    tr = telemetry.get()
    assert not tr.enabled
    with tr.span("anything", attr=1) as sp:
        sp.set(more=2)
    assert tr.current() is None
    assert tr.spans() == []
    assert tr.summarize()["span_count"] == 0
    # exporters still produce valid (empty) documents
    out = tmp_path / "empty.json"
    assert tr.export_chrome(str(out)) == 0
    doc = json.loads(out.read_text())
    assert doc["traceEvents"] == []
    assert tr.prometheus_text() == ""  # empty exposition is valid


# --------------------------------------------------------------------------
# exporters
# --------------------------------------------------------------------------


def test_chrome_export_valid_trace_event_json(tracer, tmp_path):
    g = generators.power_law(200, 1200, seed=1)
    repro.compile(sources.BFS_ECP).bind(g).run(root=0)
    path = tmp_path / "trace.json"
    n = tracer.export_chrome(str(path))
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    assert len(complete) == n == len(tracer.spans())
    for e in complete:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert "span_id" in e["args"] and "trace_id" in e["args"]
    # thread metadata events make Perfetto lanes readable
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in events)


def test_prometheus_exposition(tracer):
    g = generators.power_law(200, 1200, seed=1)
    repro.compile(sources.BFS_ECP).bind(g).run(root=0)
    text = tracer.prometheus_text()
    assert 'repro_span_count{span="run"} 1' in text
    assert 'repro_span_duration_seconds_sum{span="run"}' in text
    assert 'quantile="0.99"' in text


# --------------------------------------------------------------------------
# serving integration
# --------------------------------------------------------------------------


def test_service_request_span_trees_and_stats(tracer):
    g = generators.power_law(200, 1400, seed=5)
    with repro.serve(False, workers=2, max_batch=4) as svc:
        futs = [svc.submit("bfs", g, root=r) for r in range(3)]
        for f in futs:
            f.result()
        svc.scheduler.drain(timeout=30)
        stats = svc.stats()

    roots = [s for s in tracer.spans() if s.name == "schedule"]
    assert len(roots) == 3
    for root in roots:
        names = _tree_names(tracer, root)
        assert "queue_wait" in names
        assert "execute" in names
    # the text exposition is merged into the stats snapshot
    assert "repro_span_count" in stats["telemetry"]
    assert 'span="execute"' in stats["telemetry"]


# --------------------------------------------------------------------------
# host syncs and the profiler's clock
# --------------------------------------------------------------------------


def _descendants(tr, root_span):
    by_parent = {}
    for s in tr.spans():
        by_parent.setdefault(s.parent_id, []).append(s)
    out, stack = [], [root_span.span_id]
    while stack:
        for child in by_parent.get(stack.pop(), []):
            out.append(child)
            stack.append(child.span_id)
    return out


@pytest.mark.parametrize("batched", [False, True], ids=["engine", "batch"])
def test_run_span_holds_its_host_syncs(tracer, batched):
    g = generators.power_law(300, 2400, seed=2)
    program = repro.compile(sources.BFS_ECP)
    if batched:
        result = program.bind_batch(g, msbfs=False).run_many(
            [{"root": 0}, {"root": 3}])[0]
    else:
        result = program.bind(g).run(root=3)
    (run_span,) = [s for s in tracer.spans() if s.name == "run"]
    syncs = [s for s in _descendants(tracer, run_span)
             if s.name == "host_sync"]
    # every counted sync is a span under the run, the readback among them
    assert len(syncs) == result.stats.host_syncs
    assert run_span.attrs["host_syncs"] == result.stats.host_syncs
    assert sum(s.attrs["bytes"] for s in syncs) == result.stats.host_sync_bytes
    results = [s for s in syncs if s.attrs["reason"] == "result"]
    assert len(results) == 4
    assert all(s.parent_id == run_span.span_id for s in results)
    assert max(s.t_end for s in syncs) <= run_span.t_end
    reasons = {s.attrs["reason"] for s in syncs}
    assert reasons <= {"scalar", "cond", "frontier_mask", "result"}
    assert "scalar" in reasons
    assert ("frontier_mask" in reasons) is not batched
    assert result.trace["spans"]["host_sync"]["count"] == len(syncs)


def _xplane_host_events(trace_dir):
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    assert paths, f"no xplane under {trace_dir}"
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ANNOTATION_PREFIX):
                    events.append((e.name, e.duration_ns * 1e-9))
    return events


def test_profiler_trace_mirrors_live_spans(tracer, tmp_path):
    import jax

    g = generators.power_law(300, 2400, seed=2)
    session = repro.compile(sources.BFS_ECP).bind(g)
    session.run(root=0)  # compile outside the trace
    tracer.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        session.run(root=3)
        # recorded after the fact: in the tracer only
        tracer.record_span("queue_wait", 0.0, 1.0)
    finally:
        jax.profiler.stop_trace()
    events = _xplane_host_events(str(tmp_path))
    live = [s for s in tracer.spans() if s.name != "queue_wait"]
    want = sorted(ANNOTATION_PREFIX + s.name for s in live)
    assert sorted(name for name, _ in events) == want
    assert "gt.queue_wait" not in {name for name, _ in events}
    names = {name for name, _ in events}
    assert {"gt.run", "gt.host_sync"} <= names
    assert any(n.startswith("gt.launch:") for n in names)
    # same intervals on the profiler's clock: each span's duration matches
    # an annotation of its name within a millisecond
    by_name = {}
    for name, dur in events:
        by_name.setdefault(name, []).append(dur)
    for name, durs in by_name.items():
        mine = sorted(s.duration_s for s in live
                      if ANNOTATION_PREFIX + s.name == name)
        assert np.allclose(sorted(durs), mine, rtol=0, atol=1e-3), name


def test_summarize_reads_only_its_own_trace():
    tr = telemetry.tracer.Tracer()
    with tr.span("other"):
        with tr.span("launch:x"):
            pass
    with tr.span("run") as root:
        with tr.span("launch:k"):
            with tr.span("host_sync"):
                pass
        with tr.span("host_sync"):
            pass
    tr.record_span("queue_wait", 0.0, 1.0, parent=root.context())
    with tr.span("later"):
        pass
    summary = tr.summarize(root=root.context())
    assert summary["spans"].keys() == {"run", "launch:k", "host_sync",
                                       "queue_wait"}
    assert summary["spans"]["host_sync"]["count"] == 2
    assert summary["span_count"] == 5
    # the whole buffer is still summarized without a root
    assert tr.summarize()["span_count"] == 8
    tr.reset()
    assert tr.summarize(root=root.context())["span_count"] == 0


def test_result_trace_none_when_untraced():
    telemetry.disable()
    g = generators.power_law(200, 1200, seed=0)
    result = repro.compile(sources.BFS_ECP).bind(g).run(root=0)
    assert result.trace is None


def test_batched_runs_share_one_trace_summary(tracer):
    g = generators.power_law(300, 2400, seed=4)
    batch = repro.compile(sources.BFS_ECP).bind_batch(g)
    roots = np.arange(4)
    results = batch.run_many([{"root": int(r)} for r in roots])
    traces = {id(r.trace) for r in results}
    assert len(traces) == 1
    trace = results[0].trace
    assert trace["span_count"] >= 1
    run_spans = [s for s in tracer.spans() if s.name == "run"]
    assert any(s.attrs.get("batch_size", 0) >= 1 for s in run_spans)


# --------------------------------------------------------------------------
# head-based trace sampling (always-on production tracing)
# --------------------------------------------------------------------------


def test_sample_zero_drops_whole_traces():
    tr = telemetry.tracer.Tracer(sample=0.0)
    with tr.span("root") as root:
        # the whole trace is dropped: descendants are no-ops, the context
        # never leaks a half-recorded tree
        assert root.context() is None
        assert tr.current() is None
        with tr.span("child") as child:
            assert child is telemetry.NULL_SPAN
            with tr.span("grandchild"):
                pass
    assert tr.spans() == []
    assert tr.sampled_out == 1  # one dropped *trace*, not three spans
    assert tr.summarize()["span_count"] == 0


def test_sample_one_keeps_everything():
    tr = telemetry.tracer.Tracer(sample=1.0)
    for _ in range(20):
        with tr.span("root"):
            with tr.span("child"):
                pass
    assert len(tr.spans()) == 40
    assert tr.sampled_out == 0


def test_sampling_is_per_root_and_seed_deterministic():
    def kept_roots(seed):
        tr = telemetry.tracer.Tracer(sample=0.5, seed=seed)
        kept = []
        for i in range(200):
            with tr.span("root", i=i):
                with tr.span("child"):
                    pass
        kept = sorted(s.attrs["i"] for s in tr.spans() if s.name == "root")
        # every kept root kept its child too; every dropped root dropped it
        n_roots = len(kept)
        assert len(tr.spans()) == 2 * n_roots
        assert tr.sampled_out == 200 - n_roots
        return kept

    a, b = kept_roots(seed=7), kept_roots(seed=7)
    assert a == b
    assert 0 < len(a) < 200  # actually sampling, not all-or-nothing
    assert kept_roots(seed=8) != a


def test_explicit_parent_bypasses_sampling():
    # cross-thread handoff: a span with an explicit parent token belongs
    # to an already-kept trace — it must never be re-sampled away
    tr = telemetry.tracer.Tracer(sample=0.0)
    ctx = telemetry.tracer.SpanContext(trace_id=42, span_id=42)
    with tr.span("handed-off", parent=ctx) as sp:
        assert sp is not telemetry.NULL_SPAN
    assert [s.name for s in tr.spans()] == ["handed-off"]
    assert tr.spans()[0].trace_id == 42


def test_record_span_respects_sampling():
    tr = telemetry.tracer.Tracer(sample=0.0)
    sp = tr.record_span("queue_wait", 0.0, 1.0)
    assert sp is not None  # no-op stand-in, never an AttributeError
    assert tr.spans() == []
    assert tr.sampled_out == 1


def test_reset_zeroes_sampled_out_counter():
    tr = telemetry.tracer.Tracer(sample=0.0)
    with tr.span("root"):
        pass
    assert tr.sampled_out == 1
    tr.reset()
    assert tr.sampled_out == 0


def test_enable_sample_validates_and_updates_in_place():
    tr = telemetry.enable(sample=0.25, seed=3)
    try:
        assert tr.sample == 0.25
        # re-enable with an explicit rate retunes the active tracer
        same = telemetry.enable(sample=1.0)
        assert same is tr
        assert tr.sample == 1.0
        # without an explicit rate, enable() leaves the rate alone
        telemetry.enable()
        assert tr.sample == 1.0
        with pytest.raises(ValueError):
            telemetry.enable(sample=1.5)
        with pytest.raises(ValueError):
            telemetry.tracer.Tracer(sample=-0.1)
    finally:
        telemetry.disable()


def test_sampled_trace_still_counts_engine_runs(tracer):
    # a sampled-out run must still *execute* normally — sampling drops
    # telemetry, never work. sample=0.0 on the active tracer, then run.
    telemetry.enable(sample=0.0)
    g = generators.chain(64)
    acc = repro.compile(sources.BFS_ECP).lower(graph=g)
    session = acc.bind(g)
    try:
        res = session.run(root=0)
    finally:
        session.close()
    assert (np.asarray(res.properties["old_level"]) >= 0).sum() == 64
    assert res.trace is None  # dropped trace -> no per-run summary
    assert tracer.sampled_out >= 1
    assert tracer.spans() == []
