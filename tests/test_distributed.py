"""Distributed FINGER (shard_map) == serial, verified in a subprocess
with 8 placeholder devices (the flag must not leak into other tests)."""
import json
import os
import subprocess
import sys

import pytest

# the whole module drives an 8-placeholder-device jax in a subprocess
pytestmark = pytest.mark.slow

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import finger_state, vnge_hat
from repro.distributed.finger_dist import (
    distributed_finger_state,
    distributed_power_iteration,
    shard_edge_list,
)
from repro.graphs import EdgeList
from repro.graphs.generators import erdos_renyi
from repro.graphs.spectral import power_iteration_lmax
from repro.distributed.sharding import auto_mesh

mesh = auto_mesh((8,), ("data",))
g = erdos_renyi(200, 0.05, seed=3, weighted=True)
el = EdgeList.from_dense(g)
el_sharded = shard_edge_list(el, mesh, "data")

serial = finger_state(g)
dist = distributed_finger_state(el_sharded, mesh, "data")

lam_serial = float(power_iteration_lmax(g, num_iters=200, tol=1e-9))
lam_dist = float(distributed_power_iteration(el_sharded, mesh, "data",
                                             num_iters=200, tol=1e-9))
out = {
    "q_serial": float(serial.q), "q_dist": float(dist.q),
    "smax_serial": float(serial.s_max), "smax_dist": float(dist.s_max),
    "stot_serial": float(serial.s_total), "stot_dist": float(dist.s_total),
    "lam_serial": lam_serial, "lam_dist": lam_dist,
    "n_devices": jax.device_count(),
}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def dist_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_runs_on_8_devices(dist_results):
    assert dist_results["n_devices"] == 8


def test_distributed_q_matches_serial(dist_results):
    assert abs(dist_results["q_serial"] - dist_results["q_dist"]) < 1e-5


def test_distributed_smax_stot_match(dist_results):
    assert abs(dist_results["smax_serial"] - dist_results["smax_dist"]) < 1e-4
    r = dist_results
    assert abs(r["stot_serial"] - r["stot_dist"]) / r["stot_serial"] < 1e-6


def test_distributed_power_iteration_matches(dist_results):
    r = dist_results
    assert abs(r["lam_serial"] - r["lam_dist"]) / r["lam_serial"] < 1e-3
