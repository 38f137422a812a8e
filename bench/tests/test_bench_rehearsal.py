"""Each cell's client loop, rehearsed on the CPU at a tiny size through
the real `FingerFleet`: it passes the comparison and makes a
well-formed result line. The real entry refuses to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import tiny

CELLS = ["dos.replay"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_cell_rehearsal_is_correct_and_well_formed(name, traced):
    out = tiny.run(name, trace=traced)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    line = json.loads(json.dumps(harness.result(out, "cpu", "cpu", 1,
                                                traced)))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    for check in line["checks"].values():
        assert set(check) == {"value", "limit"}
    cell = harness.load_cell(name)
    wanted = cell.per_layer if traced else cell.end_to_end
    names = {m["name"] for m in wanted}
    assert set(line["metrics"]) <= names
    if traced:
        # off the chip only the host spans have something to read
        assert {"ingest_ms", "readout_ms"} <= set(line["metrics"])
        assert line["device"]["window_s"] > 0
        assert len(line["breakdown"]["idle_gaps"]) <= 10
    else:
        assert set(line["metrics"]) == names
        for m in line["metrics"].values():
            assert m["value"] > 0


def _entry(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"),
         "--workload", "dos.replay", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120)


def test_entry_refuses_a_cpu():
    done = _entry(tiny.ROOT)
    assert done.returncode != 0
    assert "needs a TPU" in done.stderr
    assert not done.stdout.strip().endswith("}")


def test_entry_refuses_a_checkout_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(tiny.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = _entry(str(tmp_path), {"PYTHONPATH": ""})
    assert done.returncode != 0
    assert not done.stdout.strip().endswith("}")
