"""Benchmark harness entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``--fast`` shrinks datasets.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only fig8,fig9,...]
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="smaller datasets")
    ap.add_argument("--scale", type=float, default=0.0, help="Table II dataset scale")
    ap.add_argument("--only", type=str, default="")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import fig8_speedups, fig9_ablation, fig10_productivity
    from . import table3_flexibility, roofline_report
    from .common import DEFAULT_SCALE

    scale = args.scale or (0.001 if args.fast else DEFAULT_SCALE)
    sections = {
        "fig8": lambda: fig8_speedups.main(scale=scale),
        "fig9": lambda: fig9_ablation.main(scale=scale),
        "fig10": fig10_productivity.main,
        "table3": table3_flexibility.main,
        "roofline": roofline_report.main,
    }
    chosen = [s.strip() for s in args.only.split(",") if s.strip()] or list(sections)
    print("name,us_per_call,derived")
    for name in chosen:
        for line in sections[name]():
            print(line)
            sys.stdout.flush()


if __name__ == "__main__":
    main()
