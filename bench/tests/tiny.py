"""The benchmark's cells cut to a size a CPU test run holds: the same
generators, pools and client loops, small graphs and more churn."""
import copy
import os

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_TINY = {
    "dos-as-fleet": (
        dict(snapshots=3, min_ases=200, max_ases=300, min_edges=420,
             max_edges=640, churn_frac=0.02, attack_within=4),
        [dict(name="oregon", n_pad=320, shards=1, streams_per_shard=3,
              k_pad=64, method="sparse_tick", n_slots=384, m_pad=768,
              tick_path="xla", launch="stacked")]),
}


def tiny(cell: harness.Cell) -> harness.Cell:
    """``cell`` with its configuration cut to CPU-test size and its
    stream long enough for a CPU's faster ticks."""
    cell = copy.deepcopy(cell)
    sizes, pools = _TINY[cell.config["name"]]
    cell.config.update(sizes)
    cell.config["pools"] = copy.deepcopy(pools)
    if "max_ticks_per_s" in cell.traffic:
        cell.traffic["max_ticks_per_s"] = 1000
    return cell


def run(name: str, seed: int = 2**31 + 99, seconds: float = 1.0,
        trace: bool = False, faults=(), root: str = ROOT,
        cell: harness.Cell = None) -> dict:
    """One CPU run of cell ``name`` at tiny size, through the real
    `FingerFleet` (the look for a chip skipped)."""
    import time

    harness.add_src_path(root)
    if cell is None:
        cell = tiny(harness.load_cell(name, root))
    return harness.run_cell(cell, seed, seconds, trace, lambda _: None,
                            None, time.perf_counter(), "cpu",
                            faults=faults)
