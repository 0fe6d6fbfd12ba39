#!/usr/bin/env python3
"""Read the control of a cell's comparison at the cell's own size.

    python3 bench/control.py --workload r19.bfs.serial --seeds 1 2 3

For each seed: the cell's graph and queries, drawn as a run draws them;
the control (``bench/controls/<algorithm>.py``: the reference with one
guarantee broken, or computed in the precision below the configuration's)
answers up to ``--queries`` of the window's queries in the program's
place; the reading is the largest ``gap`` of those answers against the
reference, as a run computes it. A sound limit lies below every seed's
reading.
Prints one JSON line per seed. Runs on the chip (the PageRank control
computes on the device); the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for _p in (BENCH.parent, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import load, run  # noqa: E402


def read_control(workload: str, seed: int, n_queries: int) -> dict:
    cell, config, traffic, _, _ = run.resolve_cell(run.load_manifest(),
                                                   workload)
    algorithm = traffic["algorithm"]
    ref = run.load_module("refs", algorithm)
    control = run.load_module("controls", algorithm)
    gen = run.load_module("graphs", config["graph"]["generator"])
    arcs = gen.generate(config["graph"], seed)
    plan = load.plan(traffic, arcs, seed)
    groups = {}
    for p in itertools.islice(plan.window, n_queries):
        groups.setdefault(ref.key(p), p)
    t = time.perf_counter()
    gaps = [ref.gap(control.answer(arcs, p), ref.reference(arcs, p))
            for p in groups.values()]
    return {"workload": workload, "seed": seed, "queries": len(gaps),
            "gap": ref.GAP, "control_reading": max(gaps),
            "smallest_of_one_query": min(gaps), "limit": ref.LIMIT,
            "fails": max(gaps) > ref.LIMIT,
            "seconds": time.perf_counter() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--queries", type=int, default=8)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        print(json.dumps(read_control(args.workload, seed, args.queries)),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
