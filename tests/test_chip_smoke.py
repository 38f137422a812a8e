"""`chip_smoke.py` off the chip.

The script must refuse a CPU outright. Its phases are rehearsed here at
a tiny size in interpret mode: every check — score parity against the
per-tenant references, detection of the planted attack, zero compiles
and one launch per pool in the steady window, the XLA path of the pool
over the VMEM guard, sharded against local placement on 4 virtual
devices — passes, except the kernel pools' check, which only a TPU
passes.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from repro.fleet import PoolSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = module  # its dataclasses resolve here
    spec.loader.exec_module(module)
    return module


def _run(args, **env):
    full_env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    full_env["PYTHONPATH"] = os.path.join(ROOT, "src")
    return subprocess.run([sys.executable, *args], env=full_env,
                          capture_output=True, text=True, timeout=600)


def test_refuses_a_cpu():
    proc = _run([SCRIPT])
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_fleet_phases_pass_until_the_kernel_check(smoke):
    # Widths 68-341 churn, a fully active planted tenant of 600 nodes
    # (~1.2k edges, 150-edge fan-in) and two 6-locus Hi-C tenants. The
    # "wide" pool's tile (k_pad 168, 640 slots, 2,048 edge slots) is
    # over the 8 MB guard, so it must tick in XLA.
    dep = smoke.Deployment(
        tenants=8, ticks=3, max_width=600, active_cap=64,
        attack_frac=0.25, hic_widths=(200, 400), hic_loci=6,
        pools=(
            PoolSpec(name="dense", n_pad=96, shards=2,
                     streams_per_shard=2, k_pad=8),
            PoolSpec(name="fused", n_pad=256, shards=2,
                     streams_per_shard=2, k_pad=16, method="fused_tick"),
            PoolSpec(name="sparse", n_pad=512, shards=2,
                     streams_per_shard=2, k_pad=24, method="sparse_tick",
                     n_slots=128, m_pad=512),
            PoolSpec(name="wide", n_pad=600, shards=1,
                     streams_per_shard=1, k_pad=168, method="sparse_tick",
                     n_slots=640, m_pad=2048),
        ))
    lines = []
    with pytest.raises(smoke.SmokeFailure,
                       match=r"pools \['fused', 'sparse'\] do not tick"):
        smoke.run_fleet(dep, seed=0, log=lines.append)
    by_phase = {line.split(":")[0]: line for line in lines}
    assert ", 0 compiles, launches per poll [4] for 4 pools;" \
        in by_phase["steady window"]
    assert by_phase["detection"].endswith(": True")
    for name in ("parity scores", "parity statistics"):
        value = by_phase[name].split("= ")[1].split()[0]
        assert float(value) <= smoke.TOL
    assert "weighted Hi-C (2 tenants" in by_phase["parity scores"]
    wide = by_phase["tick path wide"]
    assert "claimed XLA" in wide and "0 tpu_custom_call" in wide


_FOUR_DEVICE_SCRIPT = r"""
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro.fleet import PoolSpec
dep = smoke.Deployment(
    tenants=8, ticks=3, max_width=128, active_cap=64, hic_widths=(),
    pools=(PoolSpec(name="fused", n_pad=128, shards=1,
                    streams_per_shard=8, k_pad=16, method="fused_tick"),),
    kernel_pools=("fused",))
print(json.dumps(smoke.run_four_chips(dep, 0, log=lambda m: None)))
"""


def test_sharded_matches_local_on_four_virtual_devices():
    proc = _run(["-c", _FOUR_DEVICE_SCRIPT, SCRIPT],
                XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["top_equal"] and out["max_abs_err"] <= 1e-5
