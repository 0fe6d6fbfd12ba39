"""The host interpreter's per-layer readers on a synthetic window: host
syncs per query from the program's counters, and interpreter self time
per query from its ``run`` and ``host_sync`` spans. A program without the
counter or the spans reads nothing."""
from types import SimpleNamespace

import pytest

from bench import load, run
from repro.telemetry.tracer import Tracer

T0, T1 = 100.0, 200.0


def _stats(syncs, batch_size=1):
    return SimpleNamespace(host_syncs=syncs, batch_size=batch_size,
                           total_launches=3, compile_time_s=0.0)


def _queries(stats_per_query):
    return [load.Query(i, {"root": i}, result=run.Answer(None, s))
            for i, s in enumerate(stats_per_query)]


def _window(queries, spans=()):
    return run.Window(queries, T0, T1, spans=list(spans))


def _read(metric, window):
    return run.load_module("metrics", metric).read(window)


def test_host_syncs_per_query_over_batches():
    solo_a, solo_b, batch = _stats(12), _stats(16), _stats(9, batch_size=2)
    # two queries share the batch's stats object: counted once
    win = _window(_queries([solo_a, solo_b, batch, batch]))
    assert _read("host_syncs_per_query", win) == pytest.approx(37 / 4)


def test_host_syncs_per_query_reads_nothing_without_the_counter():
    old = SimpleNamespace(batch_size=1, total_launches=3)
    assert _read("host_syncs_per_query", _window(_queries([old]))) is None
    assert _read("host_syncs_per_query", _window([])) is None


def _span(tr, name, t_start, t_end, parent=None, **attrs):
    return tr.record_span(name, t_start, t_end, parent=parent, **attrs)


def test_interpreter_self_time_subtracts_nested_host_syncs():
    tr = Tracer()
    # one query: a run of 1.0 s holding a result read of 0.3 s and, inside
    # a launch, a frontier mask read of 0.2 s
    run_a = _span(tr, "run", 110.0, 111.0, batch_size=1)
    launch = _span(tr, "launch:EdgeTraversal", 110.1, 110.5,
                   parent=run_a.context())
    _span(tr, "host_sync", 110.2, 110.4, parent=launch.context(),
          reason="frontier_mask")
    _span(tr, "host_sync", 110.7, 111.0, parent=run_a.context(),
          reason="result")
    # a batch of two: a run of 0.6 s holding 0.2 s of reads
    run_b = _span(tr, "run", 120.0, 120.6, batch_size=2)
    _span(tr, "host_sync", 120.1, 120.3, parent=run_b.context(),
          reason="scalar")
    # a run that started before the window is not counted, nor its reads
    run_c = _span(tr, "run", 99.0, 100.5, batch_size=1)
    _span(tr, "host_sync", 99.5, 100.4, parent=run_c.context(),
          reason="result")
    win = _window([], tr.spans())
    got = _read("interpreter_self_s_per_query", win)
    assert got == pytest.approx(((1.0 - 0.5) + (0.6 - 0.2)) / 3)


def test_interpreter_self_time_reads_nothing_without_host_syncs():
    tr = Tracer()
    run_a = _span(tr, "run", 110.0, 111.0, batch_size=1)
    _span(tr, "launch:k", 110.1, 110.5, parent=run_a.context())
    assert _read("interpreter_self_s_per_query", _window([], tr.spans())) \
        is None
    assert _read("interpreter_self_s_per_query", _window([])) is None


def test_readers_on_a_served_cpu_run():
    """The program's own spans and counters, read as the harness reads a
    traced window: the counter and the span tree agree."""
    import repro
    import repro.telemetry as tel
    from repro.algorithms import sources
    from repro.graph import generators

    g = generators.power_law(300, 2400, seed=2)
    session = repro.compile(sources.BFS_ECP).bind(g)
    session.run(root=0)
    tracer = tel.enable()
    tracer.reset()
    try:
        results = [session.run(root=r) for r in (3, 17)]
        spans = tracer.spans()
    finally:
        tel.disable()
    queries = _queries([r.stats for r in results])
    t0 = min(s.t_start for s in spans)
    win = run.Window(queries, t0, t0 + 1e6, spans=spans)
    want = sum(r.stats.host_syncs for r in results) / 2
    assert _read("host_syncs_per_query", win) == want
    self_s = _read("interpreter_self_s_per_query", win)
    runs = [s for s in spans if s.name == "run"]
    assert 0 < self_s < sum(s.duration_s for s in runs) / 2
