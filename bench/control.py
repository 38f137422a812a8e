#!/usr/bin/env python3
"""The lower-precision control of a cell's comparison.

The configurations state float32. The control is the plain reference
put in the program's place and computed one precision lower, in
bfloat16: it plays the cell's own tenants and deltas, at the cell's
size, for as many ticks as a run scores, and its scores, top-k and
final statistics go through the same comparison as the program's.
The comparison has to call it not correct. A float32 run of the same
reference is printed beside it, as a second witness of what sound
float32 arithmetic reads.

    python3 bench/control.py --workload dos.replay --ticks 400 --seeds 1 2 3

The benchmark's own runs never run this. It needs no chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seed: int, ticks: int, dtype) -> dict:
    """The comparison's numbers for the reference computed in ``dtype``
    in the program's place, on seed ``seed``: ``ticks`` ticks that each
    carry one delta of every tenant."""
    from bench import harness

    top_k = cell.config["top_k"]
    generator = harness.load_module(cell.root, "generators",
                                    cell.config["generator"])
    reference = harness.load_module(cell.root, "refs",
                                    cell.config["reference"])
    tenants = generator.generate(cell.config, ticks, seed)
    names = [t.name for t in tenants]
    schedule = np.repeat(np.arange(ticks)[:, None], len(tenants), axis=1)
    ref_scores, ref_stats = harness.reference_replay(
        reference, tenants, schedule)
    scores, stats = harness.reference_replay(
        reference, tenants, schedule, dtype=dtype)
    tops = [[(names[j], row[j])
             for j in np.argsort(-row, kind="stable")[:top_k]]
            for row in scores]
    return harness.compare(scores, tops, names, ref_scores, stats,
                           ref_stats, top_k)


def main(argv=None) -> int:
    import ml_dtypes

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--ticks", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench import harness

    cell = harness.load_cell(args.workload, ROOT)
    for seed in args.seeds:
        for label, dtype in (("bfloat16", ml_dtypes.bfloat16),
                             ("float32", np.float32)):
            got = readings(cell, seed, args.ticks, dtype)
            limits = harness.limits_of(cell.config)
            failed = [k for k, v in got.items() if v > limits[k]]
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": label, "ticks": args.ticks,
                              "readings": got, "fails": failed}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
