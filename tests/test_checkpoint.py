"""`repro.serving.checkpoint`: the one on-disk format of every serving
checkpoint.

- `TestCheckpoint`: the module's own contract on a `FingerState` tree —
  round trip, pruning, finding the latest step, shape checks.
- `TestServiceResume`: a `FingerService` of each tick method, saved
  before its first tick or after five churn ticks, restored through the
  module in a fresh service, ticks five more times with scores equal to
  those of the service that never stopped (sparse services rebuild
  their `SlotMap`s from the stored arrays).
"""
import os

import numpy as np
import pytest

import jax

from repro.core import finger_state
from repro.graphs.generators import erdos_renyi
from repro.graphs.types import GraphDelta
from repro.serving import (
    CheckpointPolicy,
    FingerService,
    ServiceConfig,
    TopKSpec,
)
from repro.serving.checkpoint import (
    latest_checkpoint,
    restore_checkpoint,
    save_checkpoint,
)

N_PAD, K_PAD = 16, 4


def _state(n_pad=N_PAD):
    g = erdos_renyi(12, 0.3, seed=0, weighted=True).pad_to(n_pad)
    return finger_state(g)


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        state = _state()
        path = save_checkpoint(str(tmp_path), 7, state, metadata={"a": 1})
        restored, manifest = restore_checkpoint(path, state)
        assert manifest["step"] == 7 and manifest["metadata"] == {"a": 1}
        _assert_trees_equal(state, restored)

    def test_keep_last_prunes(self, tmp_path):
        state = _state()
        for step in range(5):
            save_checkpoint(str(tmp_path), step, state, keep_last=2)
        kept = sorted(os.listdir(tmp_path))
        assert len(kept) == 2 and kept[-1] == "step_00000004"

    def test_resume_finds_latest(self, tmp_path):
        state = _state()
        save_checkpoint(str(tmp_path), 3, state)
        save_checkpoint(str(tmp_path), 9, state)
        path = latest_checkpoint(str(tmp_path))
        assert os.path.basename(path) == "step_00000009"
        restored, manifest = restore_checkpoint(path, state)
        assert manifest["step"] == 9
        _assert_trees_equal(state, restored)

    def test_resume_empty_dir(self, tmp_path):
        assert latest_checkpoint(str(tmp_path / "nope")) is None

    def test_shape_mismatch_raises(self, tmp_path):
        path = save_checkpoint(str(tmp_path), 1, _state())
        with pytest.raises(ValueError):
            restore_checkpoint(path, _state(n_pad=N_PAD + 4))


def _config(method, directory):
    sparse = method == "sparse_tick"
    return ServiceConfig(
        batch_size=3, n_pad=N_PAD, k_pad=K_PAD, method=method,
        n_slots=N_PAD if sparse else None, m_pad=64 if sparse else None,
        topk=TopKSpec(k=2), checkpoint=CheckpointPolicy(directory=str(directory)))


def _churn(graphs, ticks, seed=0):
    """``ticks`` ticks of per-stream churn: one live edge deleted and
    one absent pair added a tick."""
    rng = np.random.default_rng(seed)
    live = []
    for g in graphs:
        w = np.asarray(g.weights)
        live.append({(i, j): float(w[i, j]) for i in range(g.n_nodes)
                     for j in range(i + 1, g.n_nodes) if w[i, j] > 0})
    out = []
    for _ in range(ticks):
        row = []
        for g, w in zip(graphs, live):
            edges = sorted(w)
            gone = edges[rng.integers(len(edges))]
            absent = [(i, j) for i in range(g.n_nodes)
                      for j in range(i + 1, g.n_nodes) if (i, j) not in w]
            new = absent[rng.integers(len(absent))]
            add = float(rng.uniform(0.5, 2.0))
            row.append(GraphDelta.from_arrays(
                [gone[0], new[0]], [gone[1], new[1]], [-w[gone], add],
                [w[gone], 0.0], n_nodes=g.n_nodes, n_pad=N_PAD,
                k_pad=K_PAD))
            del w[gone]
            w[new] = add
        out.append(row)
    return out


class TestServiceResume:
    @pytest.mark.parametrize("saved_at", [0, 5])
    @pytest.mark.parametrize(
        "method", ["dense", "compact", "fused_tick", "sparse_tick"])
    def test_restored_service_scores_as_unbroken(self, method, saved_at,
                                                 tmp_path):
        graphs = [erdos_renyi(n, 0.3, seed=n, weighted=True)
                  for n in (8, 10, 12)]
        ticks = _churn(graphs, 10)
        config = _config(method, tmp_path)
        want = []
        with FingerService.open(config, graphs) as svc:
            for t, d in enumerate(ticks):
                if t == saved_at:
                    svc.save()
                svc.ingest(d)
                svc.poll()
                want.append(np.asarray(svc.scores()).copy())

        resumed = FingerService.restore(config)
        try:
            assert resumed.step == saved_at
            for t in range(saved_at, saved_at + 5):
                resumed.ingest(ticks[t])
                resumed.poll()
                np.testing.assert_array_equal(resumed.scores(), want[t])
        finally:
            resumed.close()
