"""Tracing & profiling quickstart: one switch turns every stage of the
pipeline into a span tree you can open in chrome://tracing or Perfetto.

    PYTHONPATH=src python examples/trace_profile.py

The workflow is:

    repro.telemetry.enable()          # process-wide tracer (off by default)
    ... compile / lower / bind / run  # stages emit nested spans
    tracer.export_chrome("trace.json")  # open in chrome://tracing

Launch spans time the enqueue of a kernel, not its run on the device; the
`run` span ends when the answer is on the host, and its `host_sync`
children hold the time the host waited on the device. Under
`jax.profiler.start_trace` each live span is also a `gt.<name>` event of
the profiler's trace, beside the device's kernels (`jit_gt_<kernel>_...`).
"""
import os
import tempfile

import repro
from repro import telemetry
from repro.algorithms import sources
from repro.graph import generators


def main():
    telemetry.enable()

    graph = generators.power_law(5_000, 60_000, seed=0)

    # compile -> lower -> bind -> run, all under the tracer
    program = repro.compile(sources.BFS_ECP)
    target = repro.Target()
    acc = program.lower(target, shape=repro.GraphShape.of(graph))
    session = acc.bind(graph)
    result = session.run(root=3)

    # 1. per-run summary rides on the result itself
    trace = result.trace
    print("=== per-run trace summary (result.trace) ===")
    print(f"spans in this run: {trace['span_count']}, "
          f"wall: {trace['total_s'] * 1e3:.1f}ms")

    # 2. top-5 kernels by launch-span time: the host's enqueue (and a
    #    first touch's compile), not the kernel's time on the device
    launches = {
        name[len("launch:"):]: agg
        for name, agg in trace["spans"].items()
        if name.startswith("launch:")
    }
    print("\n=== top-5 kernels by launch-span (enqueue) time ===")
    ranked = sorted(launches.items(), key=lambda kv: -kv[1]["total_s"])
    for name, agg in ranked[:5]:
        print(f"  {name:>24}: {agg['total_s'] * 1e3:8.1f}ms "
              f"over {agg['count']} launch(es) "
              f"(max {agg['max_s'] * 1e3:.1f}ms)")

    # 3. where the run waited on the device: its host syncs, by reason
    syncs = [s for s in telemetry.get().spans() if s.name == "host_sync"]
    print(f"\n{result.stats.host_syncs} host syncs, "
          f"{result.stats.host_sync_bytes} bytes read back")
    for reason in sorted({s.attrs["reason"] for s in syncs}):
        mine = [s for s in syncs if s.attrs["reason"] == reason]
        print(f"  {reason:>14}: {len(mine)} sync(s), "
              f"{sum(s.duration_s for s in mine) * 1e3:.1f}ms")

    # 4. export the whole session as a Chrome trace
    with tempfile.TemporaryDirectory() as d:
        out = os.path.join(d, "trace.json")
        n = telemetry.get().export_chrome(out)
        size = os.path.getsize(out)
        print(f"\nexported {n} events ({size} bytes) -> {out}")
        print("open chrome://tracing or https://ui.perfetto.dev and load it")

    # 5. Prometheus-style exposition of the same histograms
    text = telemetry.get().prometheus_text()
    print("\n=== prometheus exposition (first 6 lines) ===")
    print("\n".join(text.splitlines()[:6]))

    telemetry.disable()  # back to the zero-overhead null tracer


if __name__ == "__main__":
    main()
