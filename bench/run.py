#!/usr/bin/env python3
"""Run one cell of the FINGER fleet's on-chip benchmark.

    python3 bench/run.py --workload dos.replay --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and
a traffic mix; everything else follows from them (see
`bench.harness`). The run needs a TPU with as many chips as the cell
asks for, and exits non-zero without a result otherwise. The last line
of standard output is the result as one JSON object; the last lines of
standard error give each number compared with its limit.

JAX's persistent compilation cache is where ``JAX_COMPILATION_CACHE_DIR``
says, and otherwise in ``.jax_cache/`` inside the checkout. Every
compile is cached, however short, host eager compiles included, so only
a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        return fail("--seed must be a non-negative whole number")

    sys.path.insert(0, ROOT)
    from bench import harness

    harness.add_src_path(ROOT)
    try:
        cell = harness.load_cell(args.workload, ROOT)
    except (harness.SetupError, OSError) as e:
        return fail(str(e))
    try:
        import repro.fleet  # noqa: F401  (the system under test)
    except ImportError as e:
        return fail(f"the program is not in this checkout: {e}")

    import jax

    cache_dir = None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache_dir = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return fail(f"needs a TPU, JAX found {dev.platform!r}")
    if len(devices) < cell.chips:
        return fail(f"{args.workload} needs {cell.chips} chips, JAX found "
                    f"{len(devices)}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compilation cache "
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR') or cache_dir}")
    try:
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), log, cache_dir, T_START,
                               dev.platform)
    except harness.SetupError as e:
        return fail(str(e))
    result = harness.result(out, dev.platform, dev.device_kind,
                            len(devices), bool(args.trace))
    for line in harness.stderr_lines(out["checks"]):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
