#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine and print its result.

    python3 bench/run.py --workload r19.bfs.serial --seed 7 --seconds 51 --trace 0

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration, ``bench/configs/<config>.json`` (the graph), and a traffic
mix, ``bench/traffic/<traffic>.json``. The run:

1. draws the graph from ``--seed`` with ``bench/graphs/<generator>.py`` and
   starts the service with its own settings, ``repro.serve(<artifact
   store>)``;
2. warms up: sends the mix's warm-up queries through the same path (for a
   mix with roots, most of them to a relabeled copy of the graph; see
   ``bench/load.py``);
3. measures for ``--seconds``: one closed-loop client sends queries
   through ``GraphService.submit`` and times each from submit to result;
4. reads the device's peak memory and closes the service;
5. compares every answer with the host reference
   ``bench/refs/<algorithm>.py``.

With ``--trace 0`` the metrics are the cell's end-to-end metrics. With
``--trace 1`` the window runs under the profiler and the program's own
telemetry, and the metrics are the per-layer ones, each read by
``bench/metrics/<metric>.py``.

Set-up phases go to standard output as they end; the last line of
standard output is the result, one JSON object. Each number compared and
its limit end standard error. A host where JAX finds no TPU, or fewer
chips than the cell asks for, exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import load  # noqa: E402

#: fixed paths inside the checkout (the cache directory is part of its key)
COMPILE_CACHE = BENCH / ".jax_cache"
ARTIFACT_STORE = BENCH / ".store"
#: how long past the window's close an answer is still awaited
LATE_S = 60.0
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# the files a cell is made of, found by name
# ---------------------------------------------------------------------------


def load_manifest(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def data_file(kind: str, name: str, suffix: str = ".json") -> Path:
    if not NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    return BENCH / kind / f"{name}{suffix}"


def load_json(kind: str, name: str) -> dict:
    with open(data_file(kind, name)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = data_file(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve_cell(manifest: dict, workload: str):
    """(cell, config file, traffic file, end-to-end and per-layer metric
    entries that the cell reports)."""
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(ROOT / conf["file"]) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"])

    def reports(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in manifest["end_to_end"] if reports(m)]
    per_layer = [m for m in manifest["per_layer"] if reports(m)]
    return cell, config, traffic, e2e, per_layer


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclass
class Answer:
    """What a query record keeps of a served result."""

    answer: np.ndarray
    stats: Any  # the program's EngineStats; one object per executed batch


@dataclass
class Window:
    """What a per-layer metric reader is given.

    ``queries``: the window's queries that completed in it (``load.Query``,
    ``result`` an :class:`Answer`); ``batches``: the distinct stats objects
    of those queries (one per executed batch); ``spans``: the program's
    telemetry spans of the window (traced runs); ``t0``/``t1``: the
    window's bounds on ``time.perf_counter``; ``trace``: the reduced
    profiler trace (``trace_reduce.reduce_trace``), or None; ``work_bytes``:
    the least HBM bytes the window's queries must move (``bench/work``),
    or None; ``peak``: the device's entry of ``bench/peaks.json``."""

    queries: List[load.Query]
    t0: float
    t1: float
    spans: List[Any] = field(default_factory=list)
    trace: Optional[dict] = None
    work_bytes: Optional[int] = None
    peak: Optional[dict] = None

    @property
    def batches(self) -> List[Any]:
        seen = {}
        for q in self.queries:
            seen.setdefault(id(q.result.stats), q.result.stats)
        return list(seen.values())


def quantity(metric: str) -> str:
    """What a metric measures: its name up to the first dot. A suffix
    (``queries_per_s.bfs``) names the cells it is kept for, so that cells
    whose runs spread differently hold the same quantity to bounds of
    their own; per-layer readers are found by the quantity."""
    return metric.split(".")[0]


def device_peak(kind: str) -> dict:
    with open(BENCH / "peaks.json") as f:
        peaks = json.load(f)["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def quantile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run_cell(workload: str, config: dict, traffic: dict, e2e: List[dict],
             per_layer: List[dict], seed: int, seconds: float, trace: bool,
             devices: list, store: Path = ARTIFACT_STORE) -> dict:
    """One run of one cell on ``devices``; returns the result object."""
    import jax
    import repro
    import repro.telemetry as tel
    from repro.graph.storage import GraphData

    algorithm = traffic["algorithm"]
    ref = load_module("refs", algorithm)

    t = time.perf_counter()
    gen = load_module("graphs", config["graph"]["generator"])
    arcs = gen.generate(config["graph"], seed)
    log(f"setup generate: {time.perf_counter() - t:.3f}s "
        f"(|V|={arcs.n} arcs={arcs.m})")
    graph = GraphData(arcs.n, arcs.src, arcs.dst)
    t = time.perf_counter()
    queries = load.plan(traffic, arcs, seed)
    log(f"setup plan: {time.perf_counter() - t:.3f}s")

    def keep(result) -> Answer:
        return Answer(np.asarray(result.properties[ref.ANSWER]), result.stats)

    svc = repro.serve(str(store))
    try:
        def submitter(g):
            return lambda params: svc.submit(algorithm, g, **params)

        submit = submitter(graph)
        t = time.perf_counter()
        warm = load.drive(submit, queries.warmup, keep=keep)
        log(f"setup warm-up: {time.perf_counter() - t:.3f}s for {len(warm)} "
            f"queries (first, with bind and lowering: "
            f"{warm[0].latency_s:.3f}s)")
        if queries.relabel is not None:
            t = time.perf_counter()
            twin = GraphData(arcs.n, queries.relabel[arcs.src],
                             queries.relabel[arcs.dst])
            more = load.drive(submitter(twin), queries.warmup_relabeled,
                              keep=keep)
            log(f"setup warm-up on the relabeled copy: "
                f"{time.perf_counter() - t:.3f}s for {len(more)} queries "
                f"(first, with bind: {more[0].latency_s:.3f}s)")
            warm += more
            del twin
        bad = [q for q in warm if q.error is not None]
        if bad:
            raise RuntimeError(f"warm-up query failed: {bad[0].error!r}")
        reg = svc.stats()["registry"]
        log(f"setup registry: lowerings={reg['lowerings']} "
            f"artifact_hits={reg['artifact_hits']} "
            f"executables={reg['executables']}")

        tracer, trace_dir = None, None
        if trace:
            tracer = tel.enable()
            tracer.reset()
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(trace_dir)
        annotate = jax.profiler.TraceAnnotation if trace else None

        setup_s = time.perf_counter() - T_START
        t0 = time.perf_counter()
        t1 = t0 + seconds
        marker = None
        if trace:
            marker = _window_marker(seconds)
        sent = load.drive(submit, queries.window, until=t1, keep=keep,
                          annotate=annotate, late_s=LATE_S)
        if marker is not None:
            marker.join()
        spans = []
        if trace:
            jax.profiler.stop_trace()
            spans = tracer.spans()
            tel.disable()
        memory_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices)
    finally:
        svc.close()
    del graph

    done = [q for q in sent if q.error is None and q.settled.is_set()
            and q.t_done <= t1]
    failed = [q for q in sent if q.error is not None or not q.settled.is_set()]
    for q in failed[:3]:
        log(f"query {q.index} {q.params} failed: {q.error!r}")
    for q in done[:64]:
        s = q.result.stats
        log(f"query {q.index} {q.params}: {q.latency_s:.4f}s batch "
            f"{s.batch_size} launches {s.total_launches} (compacted "
            f"{s.compacted_launches}, full {s.full_launches}) compile "
            f"{s.compile_time_s:.3f}s")

    checked, gap = check_answers(ref, arcs, sent)
    log(f"answers checked: {checked} of {len(sent) - len(failed)}")
    checks = {"queries_failed": {"value": len(failed), "limit": 0},
              ref.GAP: {"value": gap, "limit": ref.LIMIT}}
    correct = checked > 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())

    if trace:
        import bench.trace_reduce as tr

        try:
            reduced = tr.reduce_trace(tr.find_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        work = None
        work_path = data_file("work", algorithm, ".py")
        if work_path.exists():
            wmod = load_module("work", algorithm)
            work = sum(wmod.bytes_needed(arcs, q.params, q.result.answer)
                       for q in done)
        win = Window(done, t0, t1, spans=spans, trace=reduced,
                     work_bytes=work, peak=device_peak(devices[0].device_kind))
        metrics = {}
        for m in per_layer:
            value = load_module("metrics", quantity(m["name"])).read(win)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        lat = [q.latency_s for q in done]
        values = {
            "queries_per_s": completed_share(sent, t1) / seconds,
            "query_p50_s": quantile(lat, 50) if lat else None,
            "query_p95_s": quantile(lat, 95) if lat else None,
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[quantity(m["name"])],
                               "unit": m["unit"]}
                   for m in e2e if values.get(quantity(m["name"])) is not None}

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    out = {"correct": bool(correct), "attempted": len(sent),
           "failed": len(failed), "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out


def _window_marker(seconds: float):
    """A host span named ``bench.window`` over exactly the measured window,
    on a thread of its own."""
    import threading

    import jax

    def mark():
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(seconds)

    th = threading.Thread(target=mark, daemon=True)
    th.start()
    return th


def completed_share(sent: List[load.Query], t1: float) -> float:
    """Queries the window completed, counting each query still running at
    its close by the share of its latency that fell inside it, so that the
    count does not move in whole queries. A query that failed or never
    answered counts nothing."""
    total = 0.0
    for q in sent:
        if q.error is not None or not q.settled.is_set():
            continue
        if q.t_done <= t1:
            total += 1.0
        elif q.t_submit < t1:
            total += (t1 - q.t_submit) / (q.t_done - q.t_submit)
    return total


def check_answers(ref, arcs, sent: List[load.Query]):
    """Compare every answer the window's queries got with the host
    reference, once per group of equal ``ref.key`` (equal keys, equal
    answers). Returns (answers compared, the largest ``ref.gap``)."""
    import jax

    groups: Dict[Any, List[load.Query]] = {}
    for q in sent:
        if q.error is None and q.settled.is_set():
            groups.setdefault(ref.key(q.params), []).append(q)
    worst, checked = 0.0, 0
    with jax.profiler.TraceAnnotation("bench.check"):
        for qs in groups.values():
            want = ref.reference(arcs, qs[0].params)
            for q in qs:
                worst = max(worst, ref.gap(q.result.answer, want))
                checked += 1
    return checked, worst


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def configure_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else a fixed directory in the checkout. Every program is
    cached, also those that compile in under JAX's default second."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(COMPILE_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, config, traffic, e2e, per_layer = resolve_cell(
        load_manifest(), args.workload)
    import jax

    log(f"compile cache: {configure_compile_cache()}")
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"bench: JAX found no TPU (platform {platform!r}); nothing "
              f"runs in its place", file=sys.stderr)
        return 1
    if len(devices) < int(cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:int(cell["chips"])]
    log(f"device: {devices[0].device_kind} x{len(devices)}")
    out = run_cell(args.workload, config, traffic, e2e, per_layer, args.seed,
                   args.seconds, bool(args.trace), devices)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
