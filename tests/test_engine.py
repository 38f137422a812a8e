"""Batched StreamEngine + compact/fused delta-stats: equivalence with
the serial dense paths, shard_map serving, and edge cases."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    delta_stats,
    delta_stats_compact,
    finger_state,
    jsdist_incremental,
    jsdist_stream,
    update_state,
)
from repro.engine import StreamEngine, stack_deltas, stack_states
from repro.graphs import GraphDelta, apply_delta_dense
from repro.graphs.generators import erdos_renyi
from repro.kernels.delta_stats.ops import delta_stats_fused


def _random_delta(g, rng, k=16, k_pad=None, delete_frac=0.4,
                  hit_argmax=False):
    """Random add/delete/re-weight delta; optionally delete at argmax."""
    n = g.n_nodes
    w = np.asarray(g.weights)
    pairs = {}
    if hit_argmax:
        amax = int(w.sum(1).argmax())
        nbrs = np.flatnonzero(w[amax])
        for j in nbrs[:3]:
            a, b = min(amax, int(j)), max(amax, int(j))
            pairs[(a, b)] = (-w[a, b], w[a, b])  # deletion at the argmax
    while len(pairs) < k:
        i, j = rng.integers(0, n, 2)
        if i == j:
            continue
        i, j = min(i, j), max(i, j)
        if (i, j) in pairs:
            continue
        w_old = w[i, j]
        if w_old > 0 and rng.random() < delete_frac:
            dw = -w_old
        else:
            dw = float(rng.uniform(0.1, 2.0))
        pairs[(i, j)] = (dw, w_old)
    ii = np.array([p[0] for p in pairs], np.int32)
    jj = np.array([p[1] for p in pairs], np.int32)
    dw = np.array([v[0] for v in pairs.values()], np.float32)
    wo = np.array([v[1] for v in pairs.values()], np.float32)
    return GraphDelta.from_arrays(ii, jj, dw, wo, n_nodes=n, k_pad=k_pad)


class TestCompactDeltaStats:
    @pytest.mark.parametrize("seed", range(6))
    @pytest.mark.parametrize("hit_argmax", [False, True])
    def test_compact_matches_dense(self, seed, hit_argmax):
        rng = np.random.default_rng(seed)
        g = erdos_renyi(90, 0.1, seed=seed, weighted=True)
        st = finger_state(g)
        d = _random_delta(g, rng, k=20, k_pad=32, hit_argmax=hit_argmax)
        ds_d, dq_d, _, mx_d = delta_stats(st, d)
        ds_c, dq_c, mx_c = delta_stats_compact(st, d)
        assert abs(float(ds_d) - float(ds_c)) < 1e-5
        np.testing.assert_allclose(float(dq_d), float(dq_c),
                                   rtol=1e-5, atol=1e-5)
        assert abs(float(mx_d) - float(mx_c)) < 1e-5

    @pytest.mark.parametrize("exact_smax", [False, True])
    def test_compact_update_chain_matches_recompute(self, exact_smax):
        """10 chained compact updates (incl. argmax deletions) track the
        from-scratch state."""
        rng = np.random.default_rng(11)
        g = erdos_renyi(70, 0.12, seed=11, weighted=True)
        st = finger_state(g)
        for step in range(10):
            d = _random_delta(g, rng, k=14, k_pad=32,
                              hit_argmax=step % 3 == 0)
            st = update_state(st, d, exact_smax=exact_smax,
                              method="compact")
            g = apply_delta_dense(g, d)
        ref = finger_state(g)
        assert abs(float(st.q) - float(ref.q)) < 1e-4
        assert abs(float(st.s_total) - float(ref.s_total)) < 1e-2
        np.testing.assert_allclose(np.asarray(st.strengths),
                                   np.asarray(ref.strengths), atol=1e-3)
        if exact_smax:
            assert abs(float(st.s_max) - float(ref.s_max)) < 1e-3
        else:  # eq. (3): never decreases
            assert float(st.s_max) >= float(ref.s_max) - 1e-4

    def test_compact_empty_delta(self):
        g = erdos_renyi(40, 0.2, seed=0, weighted=True)
        st = finger_state(g)
        d = GraphDelta.from_arrays([], [], [], [], n_nodes=40, k_pad=8)
        new = update_state(st, d, method="compact")
        assert abs(float(new.q) - float(st.q)) < 1e-6
        assert abs(float(new.h_tilde()) - float(st.h_tilde())) < 1e-6


class TestFusedKernel:
    @pytest.mark.parametrize("seed", range(4))
    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_fused_matches_dense(self, seed, use_pallas):
        """Pallas (interpret on CPU) and ref oracle vs the dense path on
        randomized add/delete/re-weight deltas."""
        rng = np.random.default_rng(seed + 100)
        g = erdos_renyi(120, 0.08, seed=seed, weighted=True)
        st = finger_state(g)
        d = _random_delta(g, rng, k=30, k_pad=48,
                          hit_argmax=seed % 2 == 0)
        ds_d, dq_d, _, mx_d = delta_stats(st, d)
        ds_f, dq_f, mx_f = delta_stats_fused(st, d, use_pallas=use_pallas)
        assert abs(float(ds_d) - float(ds_f)) < 1e-5
        np.testing.assert_allclose(float(dq_d), float(dq_f),
                                   rtol=1e-5, atol=1e-5)
        assert abs(float(mx_d) - float(mx_f)) < 1e-5

    def test_fused_htilde_to_1e5(self):
        """End metric: H̃ after the update from fused stats matches the
        dense-path H̃ to ≤1e-5."""
        rng = np.random.default_rng(7)
        g = erdos_renyi(100, 0.1, seed=7, weighted=True)
        st = finger_state(g)
        d = _random_delta(g, rng, k=24, k_pad=32)
        dense_new = update_state(st, d, method="dense")
        compact_new = update_state(st, d, method="compact")
        assert abs(float(dense_new.h_tilde())
                   - float(compact_new.h_tilde())) < 1e-5

    def test_fused_empty_delta(self):
        g = erdos_renyi(64, 0.1, seed=1, weighted=True)
        st = finger_state(g)
        d = GraphDelta.from_arrays([], [], [], [], n_nodes=64, k_pad=4)
        for use_pallas in (False, True):
            ds, dq, mx = delta_stats_fused(st, d, use_pallas=use_pallas)
            assert float(ds) == 0.0 and float(dq) == 0.0
            assert np.isneginf(float(mx))


class TestStreamEngine:
    def _make_streams(self, b, n, k, t, seed=0):
        rng = np.random.default_rng(seed)
        graphs = [erdos_renyi(n, 0.1, seed=s, weighted=True)
                  for s in range(b)]
        gs = list(graphs)
        ticks = []
        for _ in range(t):
            ds = [_random_delta(g, rng, k=k, k_pad=k) for g in gs]
            gs = [apply_delta_dense(g, d) for g, d in zip(gs, ds)]
            ticks.append(stack_deltas(ds))
        seq = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ticks)
        return graphs, seq

    @pytest.mark.parametrize("method", ["dense", "compact"])
    def test_engine_matches_per_stream_scan_b256(self, method):
        """Acceptance: B=256 engine sequences == per-stream jsdist_stream
        to ≤1e-5."""
        b, n, k, t = 256, 48, 8, 4
        graphs, seq = self._make_streams(b, n, k, t, seed=3)
        engine = StreamEngine(method=method)
        dists, final = engine.run(StreamEngine.init_states(graphs), seq)
        assert dists.shape == (t, b)
        for s in range(0, b, 37):  # spot-check streams across the batch
            per = jax.tree_util.tree_map(lambda x: x[:, s], seq)
            ref, _ = jsdist_stream(finger_state(graphs[s]), per)
            np.testing.assert_allclose(np.asarray(dists[:, s]),
                                       np.asarray(ref), atol=1e-5)

    def test_tick_matches_run(self):
        b, n, k, t = 16, 40, 6, 3
        graphs, seq = self._make_streams(b, n, k, t, seed=9)
        engine = StreamEngine()
        run_d, _ = engine.run(StreamEngine.init_states(graphs), seq)
        st = StreamEngine.init_states(graphs)
        for i in range(t):
            tick_d, st = engine.tick(
                st, jax.tree_util.tree_map(lambda x: x[i], seq))
            np.testing.assert_allclose(np.asarray(tick_d),
                                       np.asarray(run_d[i]), atol=1e-6)

    def test_engine_matches_incremental_loop(self):
        b, n, k = 8, 40, 6
        graphs, seq = self._make_streams(b, n, k, 1, seed=5)
        engine = StreamEngine(exact_smax=True)
        d0 = jax.tree_util.tree_map(lambda x: x[0], seq)
        dists, _ = engine.tick(StreamEngine.init_states(graphs), d0)
        for s in range(b):
            d = jax.tree_util.tree_map(lambda x: x[s], d0)
            ref, _ = jsdist_incremental(finger_state(graphs[s]), d,
                                        exact_smax=True)
            assert abs(float(dists[s]) - float(ref)) < 1e-6

    def test_stack_deltas_rejects_mixed_k_pad(self):
        d1 = GraphDelta.from_arrays([0], [1], [1.0], [0.0], n_nodes=4,
                                    k_pad=4)
        d2 = GraphDelta.from_arrays([0], [1], [1.0], [0.0], n_nodes=4,
                                    k_pad=8)
        with pytest.raises(ValueError, match="common k_pad"):
            stack_deltas([d1, d2])

    def test_stack_states_roundtrip(self):
        graphs = [erdos_renyi(30, 0.2, seed=s, weighted=True)
                  for s in range(4)]
        stacked = stack_states([finger_state(g) for g in graphs])
        assert stacked.q.shape == (4,)
        assert stacked.strengths.shape == (4, 30)


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import auto_mesh
from repro.engine import StreamEngine, stack_deltas
from repro.graphs import GraphDelta
from repro.graphs.generators import erdos_renyi

b, n, k = 32, 40, 6
rng = np.random.default_rng(0)
graphs = [erdos_renyi(n, 0.1, seed=s, weighted=True) for s in range(b)]
deltas = []
for g in graphs:
    w = np.asarray(g.weights)
    iu, ju = np.triu_indices(n, k=1)
    pick = rng.choice(len(iu), size=k, replace=False)
    ii, jj = iu[pick], ju[pick]
    wo = w[ii, jj]
    dw = np.where(wo > 0, -wo, 1.0).astype(np.float32)
    deltas.append(GraphDelta.from_arrays(ii, jj, dw, wo, n_nodes=n, k_pad=k))
stacked = stack_deltas(deltas)

engine = StreamEngine()
local_d, _ = engine.tick(StreamEngine.init_states(graphs), stacked)

mesh = auto_mesh((8,), ("data",))
tick = engine.make_sharded_tick(mesh, "data")
st = engine.shard_states(StreamEngine.init_states(graphs), mesh, "data")
sharding = NamedSharding(mesh, P("data"))
stacked_sh = jax.tree_util.tree_map(
    lambda x: jax.device_put(x, sharding), stacked)
shard_d, _ = tick(st, stacked_sh)
print(json.dumps({
    "n_devices": jax.device_count(),
    "max_err": float(jnp.abs(shard_d - local_d).max()),
}))
"""


@pytest.mark.slow
def test_sharded_tick_matches_local():
    """shard_map serving over 8 placeholder devices == single-device."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["n_devices"] == 8
    assert out["max_err"] < 1e-6
