#!/usr/bin/env python3
"""Run one cell several times, one process after another, and summarise.

    python3 bench/sets.py --workload r19.bfs.serial --seconds 51 \
        --seeds 11 12 13 14 15 16 --sets 2 --out chiprun_out/r19.jsonl

Each run is ``bench/run.py`` in a process of its own (a process that has
touched JAX holds the chip, so this one never imports JAX). Every run's
record (seed, trace flag, exit code, wall time, result line, its set-up
and per-query lines, the end of its standard error) is appended to
``--out`` as one JSON line. The
summary gives, per metric and per set, the median and the spread: the
distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path
from typing import Optional

ROOT = Path(__file__).resolve().parent.parent


def one_run(workload: str, seed: int, seconds: float, trace: int,
            timeout: float) -> dict:
    cmd = [sys.executable, "bench/run.py", "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t = time.perf_counter()
    try:
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           timeout=timeout)
        rc, out, err = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc, out, err = 124, e.stdout or "", e.stderr or ""
        out = out.decode() if isinstance(out, bytes) else out
        err = err.decode() if isinstance(err, bytes) else err
    lines = out.strip().splitlines()
    result = None
    if rc == 0 and lines:
        try:
            result = json.loads(lines[-1])
        except json.JSONDecodeError:
            result = None
    return {"workload": workload, "seed": seed, "trace": trace, "rc": rc,
            "wall_s": time.perf_counter() - t, "result": result,
            "setup_lines": [ln for ln in lines if ln.startswith("setup")],
            "query_lines": [ln for ln in lines if ln.startswith("query")],
            "stderr_tail": err[-2000:]}


def spread(values) -> Optional[float]:
    q1, _, q3 = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q3 - q1) / median if median else None


def summarise(records) -> dict:
    by_set: dict = {}
    for r in records:
        res = r["result"] or {}
        for name, m in res.get("metrics", {}).items():
            by_set.setdefault(name, {}).setdefault(r["set"], []).append(
                m["value"])
    out = {}
    for name, sets in by_set.items():
        out[name] = {
            s: {"n": len(v), "median": statistics.median(v),
                "spread": spread(v) if len(v) >= 2 else None, "values": v}
            for s, v in sets.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--timeout", type=float, default=1300)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    records = []
    for s in range(args.sets):
        for seed in args.seeds:
            r = one_run(args.workload, seed, args.seconds, args.trace,
                        args.timeout)
            r["set"] = s
            records.append(r)
            with open(out, "a") as f:
                f.write(json.dumps(r) + "\n")
            res = r["result"] or {}
            print(f"{args.workload} set {s} seed {seed} trace {args.trace}: "
                  f"rc {r['rc']} wall {r['wall_s']:.1f}s correct "
                  f"{res.get('correct')} metrics "
                  f"{ {k: v['value'] for k, v in res.get('metrics', {}).items()} } "
                  f"checks {res.get('checks')}", flush=True)
            if r["rc"] != 0:
                print(r["stderr_tail"][-1500:], flush=True)
    print(json.dumps(summarise(records)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
