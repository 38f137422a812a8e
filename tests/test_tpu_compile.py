"""The served path's Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) accepts block shapes, stores
and VMEM footprints that Mosaic refuses, so these tests compile each
kernel entry point with ``interpret=False`` for a *described* v5e chip
(no chip attached: the TPU compiler runs here) at the tile sizes
`chip_smoke.py` serves, and check that the program really holds the
kernel (``tpu_custom_call``). Nothing runs; a compile that passes says
nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, and every
test worker imports this file.
"""
import importlib.util

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.delta_stats.kernel import delta_stats_sorted_pallas
from repro.kernels.sparse_tick.kernel import (
    sparse_tick_pallas,
    sparse_tick_pallas_stacked,
)
from repro.kernels.dispatch import SCALAR_LANES
from repro.kernels.stream_tick.kernel import (
    stream_tick_pallas,
    stream_tick_pallas_stacked,
)

f32, i32 = jnp.float32, jnp.int32

# chip_smoke.py pools: "fused" 2 shards x 16 streams, n_pad=512,
# k_pad=160 (2k = 512 lane-aligned endpoints); "sparse" 2 shards x 8
# streams, n_slots=512, m_pad=4096, k_pad=128 (2k = 256). Node-slot
# lanes pad to one sublane group (j = 8).
FUSED = dict(s=2, b=16, n=512, two_k=512, j=8)
SPARSE = dict(s=2, b=8, n=512, m=4096, two_k=256, j=8)
# delta_stats admits up to 1024 sorted endpoints (ops._MAX_FUSED_ENDPOINTS).
DELTA_STATS_ENDPOINTS = 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) in this installation")
    # Any other failure to describe the chip — the TPU library refusing
    # a second load under several test workers among them — fails these
    # tests instead of hiding a kernel regression behind a skip.
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _rows(sharding, lead, widths):
    """``(*lead, 1, w)`` operand shapes, the kernels' one-row blocks."""
    return [jax.ShapeDtypeStruct((*lead, 1, w), dt, sharding=sharding)
            for w, dt in widths]


def _stream_tick_args(sharding, lead, n, two_k, j):
    return _rows(sharding, lead, [
        (SCALAR_LANES, f32), (n, f32), (n, f32),
        (two_k, i32), (two_k, f32), (two_k, f32), (two_k, f32),
        (j, i32), (j, f32)])


def _sparse_tick_args(sharding, lead, n, m, two_k, j):
    return _rows(sharding, lead, [
        (SCALAR_LANES, f32), (n, f32), (n, f32), (m, f32),
        (two_k, i32), (two_k, f32), (two_k, f32), (two_k, f32),
        (two_k // 2, i32), (j, i32), (j, f32)])


def _lower(name, sharding):
    fz, sp = FUSED, SPARSE
    if name == "stream_tick_pallas":
        return stream_tick_pallas.lower(*_stream_tick_args(
            sharding, (fz["b"],), fz["n"], fz["two_k"], fz["j"]),
            interpret=False)
    if name == "stream_tick_pallas_stacked":
        return stream_tick_pallas_stacked.lower(*_stream_tick_args(
            sharding, (fz["s"], fz["b"]), fz["n"], fz["two_k"], fz["j"]),
            interpret=False)
    if name == "sparse_tick_pallas":
        return sparse_tick_pallas.lower(*_sparse_tick_args(
            sharding, (sp["b"],), sp["n"], sp["m"], sp["two_k"],
            sp["j"]), interpret=False)
    if name == "sparse_tick_pallas_stacked":
        return sparse_tick_pallas_stacked.lower(*_sparse_tick_args(
            sharding, (sp["s"], sp["b"]), sp["n"], sp["m"], sp["two_k"],
            sp["j"]), interpret=False)
    two_k = DELTA_STATS_ENDPOINTS

    def row(w, dt=f32):
        return jax.ShapeDtypeStruct((1, w), dt, sharding=sharding)
    return delta_stats_sorted_pallas.lower(
        row(two_k, i32), row(two_k), row(two_k), row(two_k),
        row(two_k // 2), row(two_k // 2), row(two_k // 2),
        interpret=False)


KERNEL_NAMES = {
    "stream_tick_pallas": "stream_tick",
    "stream_tick_pallas_stacked": "stream_tick_stacked",
    "sparse_tick_pallas": "sparse_tick",
    "sparse_tick_pallas_stacked": "sparse_tick_stacked",
    "delta_stats_sorted_pallas": "delta_stats",
}


@pytest.mark.parametrize("name", [
    "stream_tick_pallas",
    "stream_tick_pallas_stacked",
    "sparse_tick_pallas",
    "sparse_tick_pallas_stacked",
    "delta_stats_sorted_pallas",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    text = _lower(name, one_chip).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    # The kernel's instruction carries its pallas_call's stable name,
    # which a reader of the device trace looks for.
    assert f"%{KERNEL_NAMES[name]}." in text
