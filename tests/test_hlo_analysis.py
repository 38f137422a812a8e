"""The loop-aware HLO cost model: exactness on known-FLOP programs."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.hlo_analysis import analyze, parse_hlo


def _flops_of(f, *specs):
    compiled = jax.jit(f).lower(*specs).compile()
    return analyze(compiled.as_text())


def test_single_matmul_exact():
    s = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    r = _flops_of(lambda a, b: a @ b, s, s)
    assert abs(r["flops"] - 2 * 256 ** 3) / (2 * 256 ** 3) < 1e-6


def test_scan_multiplies_by_trip_count():
    s = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def f(x, w):
        return jax.lax.scan(lambda c, _: (c @ w, None), x, None, length=7)[0]

    r = _flops_of(f, s, s)
    expect = 7 * 2 * 128 ** 3
    assert abs(r["flops"] - expect) / expect < 1e-6


def test_nested_scans_multiply():
    s = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def f(x, w):
        def outer(c, _):
            inner = jax.lax.scan(lambda c2, _: (c2 @ w, None), c, None,
                                 length=4)[0]
            return inner, None
        return jax.lax.scan(outer, x, None, length=3)[0]

    r = _flops_of(f, s, s)
    expect = 12 * 2 * 128 ** 3
    assert abs(r["flops"] - expect) / expect < 1e-6


def test_xla_cost_analysis_undercounts_loops():
    """Documents WHY hlo_analysis exists: XLA counts scan bodies once."""
    s = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def f(x, w):
        return jax.lax.scan(lambda c, _: (c @ w, None), x, None, length=9)[0]

    compiled = jax.jit(f).lower(s, s).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    xla_flops = float(ca.get("flops", 0.0))
    assert xla_flops < 2 * 2 * 128 ** 3  # body counted once, not 9x


def test_traffic_nonzero_and_scales_with_loop():
    s = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def f1(x, w):
        return jax.lax.scan(lambda c, _: (c @ w, None), x, None, length=2)[0]

    def f2(x, w):
        return jax.lax.scan(lambda c, _: (c @ w, None), x, None, length=8)[0]

    b1 = _flops_of(f1, s, s)["bytes"]
    b2 = _flops_of(f2, s, s)["bytes"]
    assert b2 > 2.5 * b1


@pytest.mark.slow
def test_collectives_counted():
    import subprocess, sys, os, json
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.analysis.hlo_analysis import analyze
from repro.distributed.sharding import auto_mesh
mesh = auto_mesh((4,), ("d",))
sh = NamedSharding(mesh, P("d", None))
def f(x):
    y = x @ x.T          # needs all-gather of the sharded operand
    return jnp.sum(y)
spec = jax.ShapeDtypeStruct((256, 256), jnp.float32)
with mesh:
    c = jax.jit(f, in_shardings=(sh,)).lower(spec).compile()
r = analyze(c.as_text())
print(json.dumps({"coll": r["collective_bytes"]}))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-1500:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["coll"] > 0
