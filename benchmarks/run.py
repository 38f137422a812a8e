# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure plus the
static-analysis gate. Usage:

    PYTHONPATH=src python -m benchmarks.run [--only fig1,table3]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (
    analysis_gate,
    fig1_degree,
    fig2_size,
    fig4_bifurcation,
    table2_wiki,
    table3_dos,
)

SUITES = {
    "fig1": fig1_degree.run,
    "fig2": fig2_size.run,
    "table2": table2_wiki.run,
    "table3": table3_dos.run,
    "fig4": fig4_bifurcation.run,
    # Static-analysis gate (lint / HLO audit / VMEM / compile-budget
    # sentinel); any unsuppressed violation fails the harness. Same
    # checks as `python -m repro.analysis`.
    "analysis": analysis_gate.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(SUITES)
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        t0 = time.time()
        try:
            SUITES[name]()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        print(f"# {name} finished in {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        print(f"# FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
